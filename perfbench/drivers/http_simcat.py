"""Driver `http_simcat`: the `similarproduct` engine under `categories`
behind the normal server path, over a model that holds a category index,
driven by `loadgen_similar.py` with queries that carry seed items, a
blackList and categories.  The traffic file's `mode` says which loop:
`open` (Poisson arrivals at `rate_per_s`, each request timed from when it
was due; the end-to-end metric is the 95th percentile over all requests) or
`closed` (`connections` clients, each sending its next request when the last
is answered; answers per second).  It begins by asking the program whether
it can test a query's categories on the device, and exits 2 at once where
it cannot (a program before PR 42 would build a `[B, 9.35M]` mask on the
host for every batch)."""

from perfbench import serve_simcat


def run(cell, opts) -> dict:
    serve_simcat.require_categories_on_the_device()
    return serve_simcat.run(cell, opts, mode=cell.traffic["mode"])
