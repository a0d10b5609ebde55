"""pio-surge event-loop HTTP edge (`server/eventloop.py`): request
parsing, keep-alive, deferred (off-thread) responses, the connection
cap, and error framing — the transport contract every serving test
implicitly rides now that the EngineServer defaults to this edge."""

import http.client
import json
import queue
import socket
import threading
import time

import pytest

from predictionio_tpu.obs import timeline
from predictionio_tpu.server.eventloop import EventLoopHTTPServer


def _boot(handler, **kw):
    srv = EventLoopHTTPServer(("127.0.0.1", 0), handler, **kw)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv


def _echo_handler(req, respond):
    if req.method == "POST" and req.path.startswith("/echo"):
        respond(200, {
            "method": req.method,
            "path": req.path,
            "body": req.body.decode(),
            "ctype": req.header("content-type"),
        })
    elif req.method == "GET" and req.path == "/ping":
        respond(200, {"pong": True})
    else:
        respond(404, {"message": "not found"})


@pytest.fixture()
def echo_server():
    srv = _boot(_echo_handler)
    yield srv
    srv.shutdown()
    srv.server_close()


def _conn(srv):
    c = http.client.HTTPConnection("127.0.0.1", srv.server_address[1],
                                   timeout=10)
    c.connect()
    c.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return c


def test_roundtrip_and_keepalive(echo_server):
    c = _conn(echo_server)
    # many requests over ONE connection: keep-alive framing is correct
    for i in range(20):
        body = json.dumps({"i": i}).encode()
        c.request("POST", "/echo", body,
                  headers={"Content-Type": "application/json"})
        r = c.getresponse()
        assert r.status == 200
        out = json.loads(r.read().decode())
        assert out["body"] == body.decode()
        assert out["ctype"] == "application/json"
    c.request("GET", "/ping", None)
    assert json.loads(c.getresponse().read().decode()) == {"pong": True}
    c.close()


def test_response_from_another_thread(echo_server):
    """A handler may answer later from a different thread (the batcher
    dispatcher / aux pool path) — the loop must wake and flush."""
    done = []

    def deferred_handler(req, respond):
        def later():
            time.sleep(0.05)
            respond(200, {"deferred": True})
            done.append(1)

        threading.Thread(target=later, daemon=True).start()

    srv = _boot(deferred_handler)
    try:
        c = _conn(srv)
        t0 = time.perf_counter()
        c.request("POST", "/x", b"{}")
        r = c.getresponse()
        assert r.status == 200
        assert json.loads(r.read().decode()) == {"deferred": True}
        assert time.perf_counter() - t0 >= 0.04
        assert done == [1]
        c.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_double_respond_raises():
    errs = []

    def handler(req, respond):
        respond(200, {"first": True})
        try:
            respond(200, {"second": True})
        except RuntimeError as e:
            errs.append(str(e))

    srv = _boot(handler)
    try:
        c = _conn(srv)
        c.request("GET", "/", None)
        assert json.loads(c.getresponse().read().decode()) == {"first": True}
        # the first respond flushes the reply inline, so the client can
        # get here before the loop thread reaches the second respond
        deadline = time.monotonic() + 5.0
        while not errs and time.monotonic() < deadline:
            time.sleep(0.01)
        assert errs and "already answered" in errs[0]
        c.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_connection_cap_sheds_with_structured_503(echo_server_unused=None):
    srv = _boot(_echo_handler, max_connections=2)
    try:
        held = [_conn(srv), _conn(srv)]
        # keep both cap slots genuinely open (a request each proves it)
        for c in held:
            c.request("GET", "/ping", None)
            c.getresponse().read()
        # third connection: refused with a structured 503 + close
        extra = _conn(srv)
        deadline = time.monotonic() + 5.0
        status = None
        while time.monotonic() < deadline:
            try:
                extra.request("GET", "/ping", None)
                r = extra.getresponse()
                status = r.status
                body = json.loads(r.read().decode())
                break
            except (http.client.HTTPException, OSError):
                # the refusal can race the request write; reconnect
                extra.close()
                time.sleep(0.02)
                extra = _conn(srv)
        assert status == 503
        assert body["error"] == "TooManyConnections"
        for c in held:
            c.close()
        extra.close()
        # slots free up: a new connection serves again
        deadline = time.monotonic() + 5.0
        ok = False
        while time.monotonic() < deadline and not ok:
            c = _conn(srv)
            try:
                c.request("GET", "/ping", None)
                ok = c.getresponse().status == 200
            except (http.client.HTTPException, OSError):
                time.sleep(0.02)
            finally:
                c.close()
        assert ok
    finally:
        srv.shutdown()
        srv.server_close()


def test_malformed_request_line_400():
    srv = _boot(_echo_handler)
    try:
        s = socket.create_connection(
            ("127.0.0.1", srv.server_address[1]), timeout=5)
        s.sendall(b"NOT A REQUEST\r\n\r\n")
        data = s.recv(65536)
        assert b"400" in data.split(b"\r\n", 1)[0]
        s.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_oversized_header_431():
    srv = _boot(_echo_handler)
    try:
        s = socket.create_connection(
            ("127.0.0.1", srv.server_address[1]), timeout=5)
        s.sendall(b"GET /ping HTTP/1.1\r\nX-Big: " + b"a" * 40000)
        data = s.recv(65536)
        assert b"431" in data.split(b"\r\n", 1)[0]
        s.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_split_body_across_packets(echo_server):
    """A body arriving in dribbles (the slow-but-honest client) is
    reassembled; the request dispatches once it is complete."""
    body = json.dumps({"k": "v" * 500}).encode()
    s = socket.create_connection(
        ("127.0.0.1", echo_server.server_address[1]), timeout=5)
    head = (
        f"POST /echo HTTP/1.1\r\nHost: x\r\nContent-Type: application/json"
        f"\r\nContent-Length: {len(body)}\r\n\r\n"
    ).encode()
    s.sendall(head)
    for i in range(0, len(body), 97):
        s.sendall(body[i:i + 97])
        time.sleep(0.002)
    buf = b""
    while b"\r\n\r\n" not in buf or len(buf.split(b"\r\n\r\n", 1)[1]) == 0:
        chunk = s.recv(65536)
        if not chunk:
            break
        buf += chunk
    assert b"200" in buf.split(b"\r\n", 1)[0]
    payload = json.loads(buf.split(b"\r\n\r\n", 1)[1].decode())
    assert payload["body"] == body.decode()
    s.close()


def test_ephemeral_port_and_addr_in_use():
    srv = _boot(_echo_handler)
    try:
        port = srv.server_address[1]
        assert port > 0
        with pytest.raises(OSError):
            EventLoopHTTPServer(("127.0.0.1", port), _echo_handler)
    finally:
        srv.shutdown()
        srv.server_close()


def test_handler_exception_answers_500():
    def bad_handler(req, respond):
        raise ValueError("handler exploded")

    srv = _boot(bad_handler)
    try:
        c = _conn(srv)
        c.request("GET", "/", None)
        r = c.getresponse()
        assert r.status == 500
        assert "exploded" in json.loads(r.read().decode())["message"]
        c.close()
    finally:
        srv.shutdown()
        srv.server_close()


# -- the loop's record of its own thread (obs/timeline.py, `loop` family) ----


def _beats_of(name):
    return [b for b in timeline.loop_beats() if b["server"] == name]


def _between(b0, b1):
    assert b0["loop"] == b1["loop"]
    out = {k: b1[k] - b0[k] for k in b1
           if k not in ("loop", "server", "wall")}
    out["wall"] = {p: b1["wall"][p] - b0["wall"][p] for p in b1["wall"]}
    return out


def test_loop_beats_account_for_the_threads_time_and_the_handoffs():
    """A few hundred keep-alive requests, each answered from another
    thread: between two beats the phases tile the elapsed time, and the
    counts are those of the answers queued and written."""
    inbox = queue.Queue()

    def handler(req, respond):
        if req.path == "/ping":
            respond(200, {"pong": True})    # on the loop: no hand-off
        else:
            inbox.put(respond)

    def answer():
        while True:
            respond = inbox.get()
            if respond is None:
                return
            respond(200, {"ok": True})

    worker = threading.Thread(target=answer, daemon=True)
    worker.start()
    srv = _boot(handler, name="beats")
    try:
        conns = [_conn(srv) for _ in range(3)]
        # an iteration that ends a beat's period or more after the last
        # beat makes one: the mark the counts below are subtracted from
        time.sleep(0.15)
        conns[0].request("GET", "/ping", None)
        assert conns[0].getresponse().read()
        # the beat closes the loop's iteration AFTER the answer is
        # written: on a busy machine the answer can arrive first
        deadline = time.monotonic() + 5.0
        while not _beats_of("beats") and time.monotonic() < deadline:
            time.sleep(0.01)
        b0 = _beats_of("beats")[-1]
        assert b0["responses"] == 1 and b0["handoffs"] == 0
        n = 300
        for i in range(n):
            c = conns[i % 3]
            c.request("POST", "/q", b'{"i": 1}')
            r = c.getresponse()
            assert r.status == 200 and r.read()
        for c in conns:
            c.close()
    finally:
        inbox.put(None)
        srv.shutdown()      # the loop's last act is a beat
        srv.server_close()
    worker.join(5.0)
    assert not worker.is_alive()
    beats = _beats_of("beats")
    assert [b["t"] for b in beats] == sorted(b["t"] for b in beats)
    d = _between(b0, beats[-1])
    assert set(d["wall"]) == set(timeline.LOOP_PHASES)
    assert all(v >= 0 for v in d["wall"].values())
    assert sum(d["wall"].values()) == pytest.approx(d["t"], rel=0.01)
    assert d["responses"] == n and d["handoffs"] == n
    assert 0 < d["handoffWaitSec"] < d["t"] * n
    assert d["wall"]["read"] > 0 and d["wall"]["drain"] > 0
    # the thread's CPU is read at the beat, select's share of it round
    # one select in POLL_CPU_EVERY: the rest is the work's, inside the
    # work's wall time (a tick of the coarsest thread clock allowed)
    assert 0 <= d["pollCpu"] <= d["cpu"] + 0.011
    assert 0 < d["cpu"] <= d["t"] + 0.011
    assert d["cpu"] - d["pollCpu"] <= d["t"] - d["wall"]["poll"] + 0.011
    assert set(beats[-1]) == {
        "loop", "server", "t", "wall", "cpu", "pollCpu", "responses",
        "handoffs", "handoffWaitSec"}
    # the same sums, on /metrics since the last beat
    fam = timeline.LOOP_SECONDS_TOTAL
    assert fam.labels(server="beats", phase="read").value() == \
        pytest.approx(beats[-1]["wall"]["read"])
    assert timeline.LOOP_HANDOFFS_TOTAL.labels(
        server="beats").value() == n
    assert timeline.LOOP_CPU_SECONDS_TOTAL.labels(
        server="beats").value() == pytest.approx(beats[-1]["cpu"])
    assert timeline.LOOP_POLL_CPU_SECONDS_TOTAL.labels(
        server="beats").value() == pytest.approx(beats[-1]["pollCpu"])
    assert timeline.LOOP_HANDOFF_WAIT_SECONDS_TOTAL.labels(
        server="beats").value() == pytest.approx(
            beats[-1]["handoffWaitSec"])


def test_an_idle_loop_beats_once_a_second_and_books_it_to_poll():
    srv = _boot(_echo_handler, name="idle-beats")
    try:
        time.sleep(2.4)
    finally:
        srv.shutdown()
        srv.server_close()
    beats = _beats_of("idle-beats")
    # select's timeout wakes the loop once a second; the last beat is
    # the one the loop makes as it stops
    assert len(beats) == 3
    assert beats[1]["t"] - beats[0]["t"] == pytest.approx(1.0, abs=0.2)
    d = _between(beats[0], beats[-1])
    assert d["wall"]["poll"] >= 0.99 * d["t"]
    assert sum(d["wall"].values()) == pytest.approx(d["t"], rel=0.01)
    assert d["responses"] == d["handoffs"] == 0
    assert d["handoffWaitSec"] == 0.0
    assert 0 <= d["cpu"] < 0.1 * d["t"]


def test_two_loops_of_one_name_beat_under_their_own_numbers():
    """Every `EngineServer` names its loop "serving": two of them in one
    process (tenants) must not be subtracted one from the other."""
    a = _boot(_echo_handler, name="twins")
    b = _boot(_echo_handler, name="twins")
    try:
        time.sleep(0.3)
    finally:
        for srv in (a, b):
            srv.shutdown()
            srv.server_close()
    beats = _beats_of("twins")
    assert {b["loop"] for b in beats} == {a._rec.loop, b._rec.loop}
    assert a._rec.loop != b._rec.loop
    for loop in (a._rec.loop, b._rec.loop):
        polls = [b["wall"]["poll"] for b in beats if b["loop"] == loop]
        assert polls == sorted(polls)
