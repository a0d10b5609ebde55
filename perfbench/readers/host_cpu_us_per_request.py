"""Thread-CPU microseconds a request on the server's two hot threads: the
batch dispatcher's (every segment of the turns that overlap the window, over
the rows those turns sent to the device) plus the event loop's (its CPU
outside `select` between its first and last beat inside the window,
`loop_share.work_cpu`, over the answers it flushed between them; what a
blocking `select` itself burns, some 100 us a call on the chip's host, is in
neither).  Each thread is divided by its own count because the two are cut a
little differently (whole turns; beats a tenth of a second apart).  Times the
requests per second it is the cores the two threads fill.  None where the
program lacks either record (before PR 38) or the loop's CPU cannot be
booked."""

from perfbench.readers.loop_share import between_beats, work_cpu
from perfbench.readers.turn_segment_ms import turns_in_window


def read(run: dict, args: dict):
    loop = between_beats(run)
    turns = turns_in_window(run)
    rows = sum(t["rows"] for t in turns)
    if loop is None or loop["responses"] <= 0 or rows <= 0:
        return None
    work = work_cpu(loop)
    if work is None:
        return None
    dispatcher = sum(sum(t["cpu"].values()) for t in turns)
    return 1e6 * (dispatcher / rows + work[0] / loop["responses"])
