"""Mean per request, in ms, of the sum of the named segments of
`pio_serve_segment_seconds` over the window."""


def read(run: dict, args: dict):
    segments = run.get("segments")
    if not segments:
        return None
    requests = max(segments[s][1] for s in args["segments"])
    if requests <= 0:
        return None
    return 1e3 * sum(segments[s][0] for s in args["segments"]) / requests
