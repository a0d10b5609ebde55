"""Microseconds a request of the named parts of the dispatcher's `complete`
(`parts` of the program's turn records, `timeline.mark_part`: book, serve,
observe, encode, handoff; seconds summed over a turn's requests), over the
turns that overlap the window (`turn_segment_ms.turns_in_window`) and the
`requests` their callbacks answered.  None where the turns carry no parts
(before PR 38), answered nothing, or one of the named parts was booked by
no turn of the window (a part renamed, or a responder that never marks
it): never 0 for want of data."""

from perfbench.readers.turn_segment_ms import turns_in_window


def read(run: dict, args: dict):
    turns = [t for t in turns_in_window(run) if "parts" in t]
    requests = sum(t["requests"] for t in turns)
    if requests <= 0:
        return None
    by_part = [sum(t["parts"].get(p, 0.0) for t in turns)
               for p in args["parts"]]
    if not all(by_part):
        return None
    return 1e6 * sum(by_part) / requests
