"""Mean per turn of the batch dispatcher, in ms, of the sum of the named
segments of the program's own turn records
(`predictionio_tpu.obs.timeline.batch_turns`: `pio.turn.<segment>` spans
kept in memory), over the turns that overlap the time from the first start
to the last end of the window's batch spans.  A turn begins where its thread
starts to wait for work, so the one that was waiting when the window opened
counts without its `park`: that wait lay before the window.  None where the
program keeps no such records, as before PR 26."""


def window(run: dict):
    """(lo, hi) on `perf_counter`: the window the harness already cut."""
    spans = run.get("batch_spans")
    if not spans:
        return None
    return min(s[0] for s in spans), max(s[1] for s in spans)


def turns_in_window(run: dict) -> list:
    cut = window(run)
    if cut is None:
        return []
    try:
        from predictionio_tpu.obs.timeline import batch_turns
    except ImportError:
        return []
    lo, hi = cut
    turns = []
    for t in batch_turns():
        if t["t0"] > hi or t["t0"] + sum(t["wall"].values()) < lo:
            continue
        if t["t0"] < lo:
            t = dict(t, wall=dict(t["wall"], park=0.0),
                     cpu=dict(t["cpu"], park=0.0))
        turns.append(t)
    return turns


def read(run: dict, args: dict):
    turns = turns_in_window(run)
    if not turns:
        return None
    total = sum(t["wall"].get(s, 0.0) for t in turns for s in args["segments"])
    return 1e3 * total / len(turns)
