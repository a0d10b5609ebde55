"""Driver `http_unseen`: the `ecommercerecommendation` engine with
`unseenOnly` behind the normal server path, over a live event store that
holds the query pool's users' histories, driven by `loadgen.py` with
`{"user", "num"}` queries.  The traffic file's `mode` says which loop:
`open` (Poisson arrivals at `rate_per_s`, each request timed from when it
was due; the end-to-end metric is the 95th percentile over all requests) or
`closed` (`connections` clients, each sending its next request when the last
is answered; answers per second).  It begins by asking the program whether
the pool's longest list of excluded ids can stay on the device path, and
exits 2 at once where it cannot (a program before PR 40 would build a
`[B, 9.35M]` mask on the host for every batch)."""

from perfbench import serve_unseen


def run(cell, opts) -> dict:
    serve_unseen.require_ids_on_the_device(cell.config, cell.traffic)
    return serve_unseen.run(cell, opts, mode=cell.traffic["mode"])
