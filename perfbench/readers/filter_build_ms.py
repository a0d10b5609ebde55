"""Mean host time a batch, in ms, of the program's `pio.filter.build` span
(the batch's filters resolved into the ids array or the mask), from its
histogram `pio_filter_build_seconds` (one observation a batch) over the
window.  None where the program has no such span or observed nothing."""


def read(run: dict, args: dict):
    total, n = run.get("filter_build") or (0.0, 0)
    if n <= 0:
        return None
    return 1e3 * total / n
