"""Mean, in ms, that an answer finished on another thread (the batch
dispatcher's completion callback) lay queued before the event loop's thread
took it up to write it: `handoffWaitSec` / `handoffs` between the first and
the last beat inside the window (`loop_share.between_beats`).  It holds the
wake through the self-pipe and whatever the loop was busy with; it is part
of what `edge_host_ms` books as `write`.  None where the program keeps no
such record or nothing was handed over."""

from perfbench.readers.loop_share import between_beats


def read(run: dict, args: dict):
    d = between_beats(run)
    if d is None or d["handoffs"] <= 0:
        return None
    return 1e3 * d["handoffWaitSec"] / d["handoffs"]
