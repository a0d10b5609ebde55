"""Mean width of the excluded-ids array over the window's batches that
carried one: the rungs of the program's
`pio_filter_exclude_width_total{width}` weighted by their batches.  None
where the program has no such counter or no batch carried ids."""


def read(run: dict, args: dict):
    by_width = run.get("exclude_width_batches")
    if not by_width:
        return None
    batches = sum(by_width.values())
    if batches <= 0:
        return None
    return sum(int(width) * n for width, n in by_width.items()) / batches
