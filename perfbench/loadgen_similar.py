"""The load generator for engines whose queries are not `{"user", "num"}`:
`loadgen.py`'s connections, schedule, percentiles and bookkeeping, with one
method replaced, so that each request's body comes from a pool the spec
carries (`bodies`, one JSON text a query) and the spec's `users` are indices
into that pool.  A child process like `loadgen.py`, started by
`perfbench/serve_similar.py`; never imports jax.
"""

from __future__ import annotations

import json
import sys
import time

try:
    import loadgen     # started as a script: this directory leads the path
except ImportError:
    from perfbench import loadgen


class _BodyConn(loadgen._Conn):
    """A connection that writes `bodies[user]` as the request's body."""

    __slots__ = ()
    bodies: list = []

    def send(self, head: bytes, user: int, num: int, t_due: float,
             index: int) -> None:
        body = self.bodies[user]
        self.sock.sendall(
            head + str(len(body)).encode() + b"\r\n\r\n" + body
        )
        self.user, self.index = user, index
        self.t_due, self.t_send = t_due, time.perf_counter()
        self.need = -1
        del self.buf[:]


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    _BodyConn.bodies = [body.encode() for body in spec.pop("bodies")]
    loadgen._Conn = _BodyConn

    def wait_go():
        sys.stdout.write('{"ready": true}\n')
        sys.stdout.flush()
        if sys.stdin.readline().strip() != "go":
            raise SystemExit(2)

    result = loadgen.generate(spec, wait_go)
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
