"""Share, in %, of the window's dispatched rows whose batch carried the
filter form named in `args["filter"]` (the program's
`pio_filter_rows_total{filter}`: none, ids or mask; the same value rides on
`pio.turn.dispatch` as `filter=`).  None where the program has no such
counter or dispatched nothing."""


def read(run: dict, args: dict):
    rows = run.get("filter_rows")
    if not rows:
        return None
    total = sum(rows.values())
    if total <= 0:
        return None
    return 100.0 * rows.get(args["filter"], 0.0) / total
