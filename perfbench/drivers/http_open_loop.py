"""Driver `http_open_loop`: Poisson arrivals at the rate fixed in the
traffic file, each request timed from when it was due to its last byte.
The end-to-end metric is the 95th percentile over all requests."""

from perfbench import serve


def run(cell, opts) -> dict:
    return serve.run(cell, opts, mode="open")
