"""Mean host time a batch, in ms, of the program's `pio.seen.read` span (the
batch's users' seen items read from the event store inside the turn), from
its histogram `pio_seen_read_seconds` (one observation a batch) over the
window.  None where the program has no such span or observed nothing."""


def read(run: dict, args: dict):
    total, n = run.get("seen_read") or (0.0, 0)
    if n <= 0:
        return None
    return 1e3 * total / n
