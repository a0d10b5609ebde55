"""Driver `http_sharded`: the recommendation engine with `distributedTopk`
behind the normal server path, over an item table drawn shard by shard on
the host's chips, driven by `loadgen.py` with `{"user", "num"}` queries.
The traffic file's `mode` says which loop: `open` (Poisson arrivals at
`rate_per_s`, each request timed from when it was due; the end-to-end
metric is the 95th percentile over all requests) or `closed` (`connections`
clients, each sending its next request when the last is answered; answers
per second).  It begins by asking the program whether it can take a table
that lies sharded on the chips as it lies, and exits 2 at once where it
cannot (such a program puts the whole table on one chip at warm-up)."""

from perfbench import serve_sharded


def run(cell, opts) -> dict:
    serve_sharded.require_table_stationary_scan()
    return serve_sharded.run(cell, opts, mode=cell.traffic["mode"])
