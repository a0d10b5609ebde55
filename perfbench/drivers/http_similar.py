"""Driver `http_similar`: the `similarproduct` engine behind the normal
server path, driven by `loadgen_similar.py` with queries that carry seed
items and a blackList.  The traffic file's `mode` says which loop: `open`
(Poisson arrivals at `rate_per_s`, each request timed from when it was due;
the end-to-end metric is the 95th percentile over all requests) or `closed`
(`connections` clients, each sending its next request when the last is
answered; answers per second)."""

from perfbench import serve_similar


def run(cell, opts) -> dict:
    return serve_similar.run(cell, opts, mode=cell.traffic["mode"])
