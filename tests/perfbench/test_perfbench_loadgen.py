"""Percentile and open-loop timing arithmetic on synthetic schedules, and
the generator against a stub server whose delay is known."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from perfbench import loadgen


def test_percentile_is_the_linear_order_statistic():
    vals = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert loadgen.percentile(vals, 50) == 3.0
    assert loadgen.percentile(vals, 95) == pytest.approx(4.8)
    assert loadgen.percentile([7.0], 95) == 7.0
    assert loadgen.percentile(list(range(101)), 95) == 95.0


def test_missing_requests_count_as_slower_than_any():
    s = loadgen.latency_summary([0.010] * 90, missing=10)
    assert s["n"] == 100 and s["p50_ms"] == pytest.approx(10.0)
    assert s["p95_ms"] == float("inf")
    assert loadgen.latency_summary([0.010] * 99, 1)["p95_ms"] == pytest.approx(10.0)


def test_schedule_holds_the_same_gaps_for_every_seed():
    a = loadgen.arrival_offsets(100.0, 2.0, base_seed=5, seed=1)
    b = loadgen.arrival_offsets(100.0, 2.0, base_seed=5, seed=2)
    assert len(a) == len(b) == 200
    assert a != b and all(x < y for x, y in zip(a, a[1:]))
    assert 0.0 < a[0] and a[-1] < 2.0 and b[-1] < 2.0

    def gaps(offsets):
        return sorted(round(y - x, 9) for x, y in zip([0.0] + offsets, offsets))

    # the multiset of gaps differs only by the one gap left after the last
    # arrival: at least n-1 of n agree
    common = len(set(gaps(a)) & set(gaps(b)))
    assert common >= 198
    assert loadgen.arrival_offsets(100.0, 2.0, 5, 1) == a
    with pytest.raises(ValueError):
        loadgen.arrival_offsets(0.1, 1.0, 5, 1)


def test_zipf_users_are_the_same_draws_in_another_order():
    a = loadgen.zipf_users(1000, 1.1, 500, base_seed=3, seed=1)
    b = loadgen.zipf_users(1000, 1.1, 500, base_seed=3, seed=2)
    assert sorted(a) == sorted(b) and a != b
    assert min(a) >= 0 and max(a) < 1000
    assert a.count(0) > a.count(10) >= 0 and a.count(0) > 20


class _Stub(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    delay = 0.05
    stall_until = 0.0

    def do_POST(self):
        n = int(self.headers["Content-Length"])
        query = json.loads(self.rfile.read(n))
        wait = max(self.delay, type(self).stall_until - time.perf_counter())
        time.sleep(wait)
        body = json.dumps({"itemScores": [
            {"item": f"i{j}", "score": 1.0 - j * 0.1}
            for j in range(query["num"])
        ]}).encode()
        # one write: headers and body apart would wait on a delayed ACK
        self.wfile.write(
            b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n" + body
        )

    def log_message(self, *a):
        pass


@pytest.fixture()
def stub():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    _Stub.stall_until = 0.0
    yield srv.server_address[1]
    srv.shutdown()
    srv.server_close()
    t.join(timeout=5)


def _spec(port, mode, **kw):
    spec = {"host": "127.0.0.1", "port": port, "path": "/queries.json",
            "mode": mode, "num": 3, "seconds": 1.0, "users": [1, 2, 3, 4],
            "connections": 4, "sample": 5, "sample_seed": 11}
    spec.update(kw)
    return spec


def test_closed_loop_counts_what_the_window_finished(stub):
    r = loadgen.generate(_spec(stub, "closed"), lambda: None)
    # 4 clients, 50 ms an answer, 1 s: about 20 answers each
    assert 60 <= r["answered"] <= 80 and r["failed"] == 0
    assert r["attempted"] == r["answered"]
    assert all(0.045 < x < 0.2 for x in r["latencies_s"])
    assert len(r["sample"]) == 5
    body = json.loads(r["sample"][0]["body"])
    assert len(body["itemScores"]) == 3 and r["sample"][0]["user"] in (1, 2, 3, 4)


def test_open_loop_times_from_the_scheduled_arrival(stub):
    arrivals = [0.1 * j for j in range(1, 9)]    # 8 arrivals, 10/s
    # the server stalls until 0.6 s after the window opens: arrivals due
    # before then wait for it, and the wait is theirs
    def go():
        _Stub.stall_until = time.perf_counter() + 0.6
    r = loadgen.generate(
        _spec(stub, "open", arrivals=arrivals, connections=8), go)
    assert r["attempted"] == 8 and r["answered"] == 8 and r["failed"] == 0
    lat = sorted(r["latencies_s"], reverse=True)
    # the first arrival (due at 0.1 s) is answered at about 0.6 s: 0.5 s
    assert lat[0] == pytest.approx(0.5, abs=0.06)
    assert lat[-1] == pytest.approx(0.05, abs=0.03)
    assert len(r["late_s"]) == 8 and max(r["late_s"]) < 0.05


def test_open_loop_queues_when_no_connection_is_free(stub):
    arrivals = [0.01 * j for j in range(1, 9)]
    r = loadgen.generate(
        _spec(stub, "open", arrivals=arrivals, connections=2, seconds=0.2),
        lambda: None)
    assert r["answered"] == 8
    # 2 connections x 50 ms: the last pair is answered about 0.2 s in, and
    # its wait for a connection is counted from when it was due
    assert max(r["latencies_s"]) > 0.1
    assert max(r["late_s"]) > 0.05
