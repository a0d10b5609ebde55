"""The one general load generator: a single stdlib process that drives
keep-alive HTTP connections from one selector loop, closed loop (N clients,
each sends its next request when the last is answered) or open loop (a
fixed schedule of arrivals, each request timed from when it was DUE, so a
stall is charged to every request that sat behind it).

It runs as a child of the benchmark (`python perfbench/loadgen.py`, spec as
one JSON line on stdin) so that it shares no interpreter lock with the
server under test and never imports jax.  The arithmetic a test can check
(percentile, schedule, user draw) is plain functions at the top; the
percentile and the timing-from-schedule rule are copied from
`tools/loadgen.py`.
"""

from __future__ import annotations

import bisect
import gc
import json
import random
import selectors
import socket
import sys
import time

ANSWER_WAIT_S = 60.0   # how long past the window's close an answer may take


def percentile(sorted_vals, q: float) -> float:
    """Exact order statistic with linear interpolation (numpy's default)
    over an already sorted list."""
    n = len(sorted_vals)
    if n == 0:
        return float("nan")
    if n == 1:
        return float(sorted_vals[0])
    rank = (q / 100.0) * (n - 1)
    lo = int(rank)
    hi = min(lo + 1, n - 1)
    frac = rank - lo
    return float(sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac)


def arrival_offsets(rate: float, seconds: float, base_seed: int,
                    seed: int) -> list:
    """Offsets (s) from the window's start of a Poisson stream at `rate`.

    Every seed gets the same multiset of exponential gaps (drawn from
    `base_seed`) in another order, scaled so that the window holds exactly
    int(rate * seconds) arrivals: the seed changes the order of the work,
    never its amount."""
    n = int(rate * seconds)
    if n < 1:
        raise ValueError("the schedule holds no arrival")
    base = random.Random(base_seed)
    gaps = [base.expovariate(rate) for _ in range(n + 1)]
    random.Random(seed).shuffle(gaps)
    scale = seconds / sum(gaps)
    out, t = [], 0.0
    for gap in gaps[:n]:
        t += gap * scale
        out.append(t)
    return out


def zipf_users(n_users: int, exponent: float, count: int, base_seed: int,
               seed: int) -> list:
    """`count` user indices with P(rank k) ~ k**-exponent over `n_users`.
    The same draws for every seed, in another order."""
    cum, total = [], 0.0
    for k in range(1, n_users + 1):
        total += k ** -exponent
        cum.append(total)
    base = random.Random(base_seed)
    users = [
        min(bisect.bisect_left(cum, base.random() * total), n_users - 1)
        for _ in range(count)
    ]
    random.Random(seed).shuffle(users)
    return users


def latency_summary(latencies_s: list, missing: int) -> dict:
    """Percentiles over ALL requests: one that failed or never answered
    counts as slower than any that did."""
    vals = sorted(latencies_s) + [float("inf")] * missing
    return {
        "n": len(vals),
        "p50_ms": percentile(vals, 50) * 1e3,
        "p95_ms": percentile(vals, 95) * 1e3,
    }


# -- the generator process ---------------------------------------------------


class _Conn:
    __slots__ = ("sock", "buf", "need", "body_at", "t_due", "t_send", "user",
                 "index")

    def __init__(self, host: str, port: int):
        self.sock = socket.create_connection((host, port), timeout=30.0)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = bytearray()
        self.need = self.body_at = -1
        self.t_due = self.t_send = 0.0
        self.user = -1
        self.index = -1

    def send(self, head: bytes, user: int, num: int, t_due: float,
             index: int) -> None:
        body = b'{"user": "u%d", "num": %d}' % (user, num)
        self.sock.sendall(
            head + str(len(body)).encode() + b"\r\n\r\n" + body
        )
        self.user, self.index = user, index
        self.t_due, self.t_send = t_due, time.perf_counter()
        self.need = -1
        del self.buf[:]

    def feed(self):
        """Read what is there; (status, body) once the answer is whole."""
        chunk = self.sock.recv(65536)
        if not chunk:
            raise ConnectionError("peer closed the connection")
        buf = self.buf
        buf += chunk
        if self.need < 0:
            end = buf.find(b"\r\n\r\n")
            if end < 0:
                return None
            clen = 0
            for ln in bytes(buf[:end]).split(b"\r\n")[1:]:
                if ln[:15].lower() == b"content-length:":
                    clen = int(ln[15:])
            self.body_at = end + 4
            self.need = end + 4 + clen
        if len(buf) < self.need:
            return None
        status = int(bytes(buf[:16]).split(None, 2)[1])
        body = bytes(buf[self.body_at:self.need])
        return status, body


def _answer_ok(status: int, body: bytes, num: int) -> bool:
    if status != 200:
        return False
    try:
        scores = json.loads(body)["itemScores"]
    except (ValueError, KeyError, TypeError):
        return False
    return len(scores) == num


def generate(spec: dict, wait_go) -> dict:
    """Open the connections, warm each with one request, call `wait_go()`,
    then drive the window.  Returns the raw result."""
    host, port, num = spec["host"], spec["port"], int(spec["num"])
    users = spec["users"]
    seconds = float(spec["seconds"])
    closed = spec["mode"] == "closed"
    head = (
        b"POST " + spec["path"].encode() + b" HTTP/1.1\r\nHost: "
        + host.encode() + b"\r\nContent-Type: application/json\r\n"
        b"Content-Length: "
    )
    sel = selectors.DefaultSelector()
    conns = [_Conn(host, port) for _ in range(int(spec["connections"]))]
    for c in conns:
        sel.register(c.sock, selectors.EVENT_READ, c)
    # one warm request per connection, a few at a time, answers awaited
    for lo in range(0, len(conns), 16):
        group = conns[lo:lo + 16]
        for c in group:
            c.send(head, users[0], num, 0.0, -1)
        pending = len(group)
        while pending:
            for key, _ in sel.select(timeout=60.0) or [(None, None)]:
                if key is None:
                    raise TimeoutError("no answer to a warm request")
                if key.data.feed() is not None:
                    pending -= 1
    gc.collect()
    gc.freeze()
    gc.disable()
    wait_go()
    t0 = time.perf_counter()
    t_close = t0 + seconds
    lat, late, kept = [], [], []
    errors = sent = done_in_window = 0
    next_user = 0
    last_answer, silence = t0, 0.0   # longest time with no answer at all

    def finish(c, answer, now):
        nonlocal errors, done_in_window, last_answer, silence
        if now <= t_close:
            silence = max(silence, now - last_answer)
            last_answer = now
        status, body = answer
        if c.index < 0:
            return
        if not _answer_ok(status, body, num):
            errors += 1
            return
        if closed and now > t_close:
            return  # answered after the close: not the window's work
        done_in_window += 1
        lat.append(now - (c.t_send if closed else c.t_due))
        kept.append((c.user, body))

    if closed:
        for c in conns:
            c.send(head, users[next_user % len(users)], num, 0.0, sent)
            next_user += 1
            sent += 1
        in_flight = len(conns)
        while in_flight:
            events = sel.select(timeout=1.0)
            now = time.perf_counter()
            if not events and now > t_close + ANSWER_WAIT_S:
                break
            for key, _ in events:
                c = key.data
                try:
                    answer = c.feed()
                except (OSError, ValueError):
                    errors += 1
                    in_flight -= 1
                    sel.unregister(c.sock)
                    continue
                if answer is None:
                    continue
                now = time.perf_counter()
                finish(c, answer, now)
                if now < t_close:
                    c.send(head, users[next_user % len(users)], num, 0.0, sent)
                    next_user += 1
                    sent += 1
                else:
                    in_flight -= 1
        attempted = done_in_window + errors
    else:
        arrivals = spec["arrivals"]
        free = list(conns)
        waiting = []           # due arrivals for which no connection is free
        i = in_flight = 0
        n = len(arrivals)
        while i < n or in_flight or waiting:
            now = time.perf_counter()
            while i < n and t0 + arrivals[i] <= now:
                waiting.append(i)
                i += 1
            while waiting and free:
                j = waiting.pop(0)
                c = free.pop()
                t_due = t0 + arrivals[j]
                c.send(head, users[j % len(users)], num, t_due, j)
                late.append(c.t_send - t_due)
                in_flight += 1
                sent += 1
            if now > t_close + ANSWER_WAIT_S:
                break
            timeout = 0.5 if i >= n else max(t0 + arrivals[i] - now, 0.0)
            for key, _ in sel.select(timeout=timeout):
                c = key.data
                try:
                    answer = c.feed()
                except (OSError, ValueError):
                    errors += 1
                    in_flight -= 1
                    sel.unregister(c.sock)
                    continue
                if answer is None:
                    continue
                finish(c, answer, time.perf_counter())
                in_flight -= 1
                free.append(c)
        attempted = n
    wall = time.perf_counter() - t0
    for c in conns:
        try:
            c.sock.close()
        except OSError:
            pass
    rng = random.Random(int(spec["sample_seed"]))
    take = min(int(spec["sample"]), len(kept))
    sample = [
        {"user": kept[j][0], "body": kept[j][1].decode()}
        for j in sorted(rng.sample(range(len(kept)), take))
    ]
    return {
        "mode": spec["mode"], "seconds": seconds, "wall_s": wall,
        "attempted": attempted, "sent": sent, "answered": done_in_window,
        "failed": attempted - done_in_window, "longest_silence_s": silence,
        "latencies_s": lat, "late_s": late, "sample": sample,
    }


def main() -> int:
    spec = json.loads(sys.stdin.readline())

    def wait_go():
        sys.stdout.write('{"ready": true}\n')
        sys.stdout.flush()
        if sys.stdin.readline().strip() != "go":
            raise SystemExit(2)

    result = generate(spec, wait_go)
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
