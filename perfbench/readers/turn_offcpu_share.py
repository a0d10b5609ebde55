"""Share, in %, of the named segments' wall time in which the batch
dispatcher's thread was not on a CPU: 1 - thread CPU seconds / wall
seconds, summed over the window's turns (see `turn_segment_ms`).  The
segments named are those that do not wait by design, so what is left is
time spent runnable behind other threads, the interpreter lock or the
host's scheduler.  None where there is nothing to read."""

from perfbench.readers.turn_segment_ms import turns_in_window


def read(run: dict, args: dict):
    turns = turns_in_window(run)
    wall = sum(t["wall"].get(s, 0.0) for t in turns for s in args["segments"])
    if wall <= 0.0:
        return None
    cpu = sum(t["cpu"].get(s, 0.0) for t in turns for s in args["segments"])
    return 100.0 * (1.0 - cpu / wall)
