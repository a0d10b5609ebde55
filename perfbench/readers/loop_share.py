"""A share, in %, of the event loop's thread over the window, from the
program's own record of that thread (`predictionio_tpu.obs.timeline.
loop_beats`: cumulative sums, copied about ten times a second while the loop
is at work).  The first and the last beat inside the window (the first start
to the last end of its batch spans, as `turn_segment_ms.window` cuts it) are
subtracted, loop by loop, and the differences added up.

    {"of": "busy"}     1 - wall seconds inside `select` / seconds between
                       the beats: the thread had something to do
    {"of": "offcpu"}   1 - thread CPU seconds outside `select` / wall
                       seconds outside it: of the time it had something
                       to do, the share it was runnable and not running
                       (behind the interpreter lock or the scheduler)

The CPU outside `select` is the thread's whole CPU less the program's
estimate of what `select` itself burnt (`pollCpu`, from one call in seven).
The chip machine's kernel charges thread CPU by the 10 ms tick, so a
difference may stand one tick outside [0, work time] and is held inside;
further out the booking is wrong and the reading is None, not 0 or 100.
None too where the program keeps no such record (before PR 38) or fewer
than two beats of a loop lie in the window; never 0 for want of data."""

from perfbench.readers.turn_segment_ms import window

SUMS = ("cpu", "pollCpu", "responses", "handoffs", "handoffWaitSec")
TICK_S = 0.01


def between_beats(run: dict):
    """What the loops added between their first and last beat inside the
    window: `{"elapsed", "wall": {phase: s}, <each of SUMS>}`, or None."""
    cut = window(run)
    if cut is None:
        return None
    try:
        from predictionio_tpu.obs.timeline import loop_beats
    except ImportError:
        return None
    lo, hi = cut
    by_loop: dict = {}
    for b in loop_beats():
        if lo <= b["t"] <= hi:
            by_loop.setdefault(b["loop"], []).append(b)
    pairs = [(beats[0], beats[-1]) for beats in by_loop.values()
             if beats[-1]["t"] > beats[0]["t"]]
    if not pairs:
        return None
    out = {key: sum(last[key] - first[key] for first, last in pairs)
           for key in SUMS}
    out["elapsed"] = sum(last["t"] - first["t"] for first, last in pairs)
    out["wall"] = {
        phase: sum(last["wall"][phase] - first["wall"][phase]
                   for first, last in pairs)
        for phase in pairs[0][1]["wall"]}
    return out


def work_cpu(d: dict):
    """`(cpu, wall)` seconds of the loops outside `select`, the CPU held
    inside [0, wall]; None where it stands further out than a tick, or
    the loops never left `select`."""
    wall = d["elapsed"] - d["wall"]["poll"]
    cpu = d["cpu"] - d["pollCpu"]
    if wall <= 0.0 or not -TICK_S <= cpu <= wall + TICK_S:
        return None
    return min(max(cpu, 0.0), wall), wall


def read(run: dict, args: dict):
    d = between_beats(run)
    if d is None:
        return None
    if args["of"] == "busy":
        share = 1.0 - d["wall"]["poll"] / d["elapsed"]
    else:
        work = work_cpu(d)
        if work is None:
            return None
        share = 1.0 - work[0] / work[1]
    return 100.0 * min(max(share, 0.0), 1.0)
