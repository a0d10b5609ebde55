"""Driver `http_closed_loop`: N clients on keep-alive connections, each
sending its next `POST /queries.json` when the last is answered.  The
end-to-end metric is the answers completed per second of the window."""

from perfbench import serve


def run(cell, opts) -> dict:
    return serve.run(cell, opts, mode="closed")
