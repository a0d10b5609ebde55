"""Milliseconds the cyclic garbage collector paused the server's process
per second of the window, from the program's own collector hook
(`predictionio_tpu.obs.gcpause.pauses`: every collection's start and
length), over the collections that began between the first start and the
last end of the window's batch spans.  0 where the hook is installed and
nothing was collected; None where the program has no hook."""

from perfbench.readers.turn_segment_ms import window


def read(run: dict, args: dict):
    cut = window(run)
    if cut is None:
        return None
    try:
        from predictionio_tpu.obs.gcpause import installed, pauses
    except ImportError:
        return None
    if not installed():
        return None
    lo, hi = cut
    paused = sum(dt for t0, dt, _ in pauses() if lo <= t0 <= hi)
    return 1e3 * paused / (hi - lo)
