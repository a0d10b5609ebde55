"""pio-pulse request-lifecycle timelines (`obs/timeline.py`): the
accounting-identity property (segments are non-negative and sum to the
measured end-to-end wall time), segment threading through predict_json
/ the HTTP handler / the micro-batcher / the event-server ingest route,
flight-record decomposition attrs, the batch dispatcher's turn family
and the `annotate` scopes that book it, the on-demand profiler capture,
and the dashboard /pulse.html view."""

import json
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.obs import QUERY_LATENCY, get_tracer
from predictionio_tpu.obs import timeline as timeline_module
from predictionio_tpu.obs.timeline import (
    BATCH_SEGMENTS,
    BATCH_TURN_SECONDS,
    EVENT_SEGMENTS,
    EVENTS_SEGMENT_SECONDS,
    SERVE_SEGMENTS,
    TURN_PARTS,
    SERVE_SEGMENT_SECONDS,
    ProfileBusy,
    Timeline,
    Turn,
    annotate,
    batch_turns,
    capture_profile,
    current_timeline,
    mark,
    mark_part,
    timeline_scope,
)


def _busy(ms: float) -> None:
    end = time.perf_counter() + ms / 1e3
    while time.perf_counter() < end:
        pass


# -- the accounting identity ------------------------------------------------


def test_marks_sum_to_elapsed():
    tl = Timeline("serve")
    for seg, ms in (("parse", 2), ("auth", 1), ("device", 5),
                    ("serialize", 1), ("write", 2)):
        _busy(ms)
        tl.mark(seg)
    segs = tl.segments
    assert all(v >= 0 for v in segs.values())
    total = sum(segs.values())
    # everything between t0 and the last mark is attributed somewhere
    assert total == pytest.approx(tl._last - tl.t0, abs=1e-6)


def test_add_block_credits_residual_to_final_segment():
    tl = Timeline("serve")
    tl.mark("auth")
    _busy(6)  # the composite region: 6 ms of wall time ...
    # ... of which only 2 were measured by the interior stamps
    tl.add_block([("queue_wait", 0.001), ("device", 0.001)],
                 residual_to="device")
    segs = tl.segments
    assert segs["queue_wait"] == pytest.approx(0.001)
    # device got its measured share PLUS the ~4 ms residual
    assert segs["device"] >= 0.004
    assert sum(segs.values()) == pytest.approx(
        tl._last - tl.t0, abs=1e-6
    )


def test_timeline_property_random_walks():
    """Property: for ANY interleaving of marks and add_blocks, segments
    stay non-negative and sum exactly to the covered wall time."""
    rng = np.random.default_rng(42)
    names = list(SERVE_SEGMENTS)
    for _ in range(25):
        tl = Timeline("serve")
        for _step in range(rng.integers(1, 8)):
            _busy(float(rng.uniform(0.1, 1.5)))
            if rng.random() < 0.5:
                tl.mark(str(rng.choice(names)))
            else:
                parts = [
                    (str(rng.choice(names)),
                     float(rng.uniform(0, 0.0005)))
                    for _ in range(rng.integers(0, 3))
                ]
                tl.add_block(parts, residual_to="device")
        assert all(v >= -1e-12 for v in tl.segments.values())
        covered = tl._last - tl.t0
        assert sum(tl.segments.values()) == pytest.approx(
            covered, rel=1e-6, abs=1e-6
        )
        assert tl.elapsed() >= covered


def test_scope_is_thread_local_and_nests():
    outer, inner = Timeline("serve"), Timeline("serve")
    assert current_timeline() is None
    with timeline_scope(outer):
        assert current_timeline() is outer
        with timeline_scope(inner):
            assert current_timeline() is inner
        assert current_timeline() is outer
        seen = []
        t = threading.Thread(
            target=lambda: seen.append(current_timeline())
        )
        t.start()
        t.join()
        assert seen == [None]  # other threads don't inherit
    assert current_timeline() is None
    mark("parse")  # no scope: free no-op, must not raise


def test_finish_observes_into_family():
    before = SERVE_SEGMENT_SECONDS.labels(segment="device").snapshot()
    tl = Timeline("serve")
    _busy(0.2)
    tl.mark("device")
    segs = tl.finish()
    after = SERVE_SEGMENT_SECONDS.labels(segment="device").snapshot()
    assert after["count"] == before["count"] + 1
    assert after["sum"] >= before["sum"] + segs["device"] * 0.99
    # snapshot_ms rounds for span attrs
    assert tl.snapshot_ms()["device"] == pytest.approx(
        segs["device"] * 1e3, abs=0.002
    )


# -- the dispatcher's turn: segments booked by `annotate` ---------------------


def _walk(rng, depth=0):
    """Random nest of `pio.turn.*` scopes with busy time between them."""
    for _ in range(rng.integers(1, 4)):
        _busy(float(rng.uniform(0.05, 0.6)))
        with annotate("pio.turn." + str(rng.choice(BATCH_SEGMENTS))):
            _busy(float(rng.uniform(0.05, 0.6)))
            if depth < 2 and rng.random() < 0.5:
                _walk(rng, depth + 1)


def test_turn_property_nested_scopes_sum_to_wall_time():
    """Property: for ANY nest of scopes, each books its own time only,
    and with the residual to `complete` a finished turn's segments sum
    to its wall time; thread-CPU seconds never pass wall seconds."""
    rng = np.random.default_rng(7)
    cpu_all = wall_all = 0.0
    for _ in range(20):
        turn = Turn()
        with timeline_scope(turn):
            _walk(rng)
        t_end = time.perf_counter()
        segs = turn.finish()
        assert set(segs) <= set(BATCH_SEGMENTS)
        assert all(v >= -1e-9 for v in segs.values())
        wall = sum(segs.values())
        assert turn.t0 + wall >= t_end
        assert wall == pytest.approx(t_end - turn.t0, abs=2e-4)
        rec = batch_turns()[-1]
        assert rec["turn"] == turn.turn and rec["t0"] == turn.t0
        assert rec["wall"] == segs
        cpu = sum(rec["cpu"].values())
        assert 0 <= cpu <= wall + 1e-3
        cpu_all, wall_all = cpu_all + cpu, wall_all + wall
    # the walk spins, so it is on the CPU for most of it; judged over
    # the twenty turns, since a loaded machine takes one turn's thread
    # off the CPU for most of its 14 ms now and then
    assert cpu_all >= 0.25 * wall_all


def test_turn_numbers_rise_and_finish_observes_the_family():
    before = {s: BATCH_TURN_SECONDS.labels(segment=s).snapshot()["count"]
              for s in BATCH_SEGMENTS}
    a, b = Turn(), Turn()
    assert b.turn == a.turn + 1
    with timeline_scope(a), annotate("pio.turn.fetch", rows=3, padded=4):
        _busy(0.2)
    a.rows, a.padded = 3, 4
    a.finish()
    after = {s: BATCH_TURN_SECONDS.labels(segment=s).snapshot()["count"]
             for s in BATCH_SEGMENTS}
    assert after["fetch"] == before["fetch"] + 1
    assert after["complete"] == before["complete"] + 1
    assert after["park"] == before["park"]
    rec = batch_turns()[-1]
    assert (rec["rows"], rec["padded"], rec["gcSec"]) == (3, 4, 0.0)


PARTS = ("book", "serve", "observe", "encode", "handoff")


def test_turn_parts_are_one_tuple_and_a_name_outside_it_raises():
    """The parts are spelled once, in `TURN_PARTS`; the call sites in
    microbatch, serving and the edge cannot drift from it silently: a
    turn holds every part from its start and a misspelt one is a
    KeyError where it is marked, not a part that reads 0."""
    assert TURN_PARTS == PARTS
    turn = Turn()
    assert turn.parts == dict.fromkeys(PARTS, 0.0)
    with timeline_scope(turn):
        turn.open_part()
        with pytest.raises(KeyError):
            mark_part("encoding")
    with timeline_scope(None):
        mark_part("encoding")     # off a turn: still a no-op


def test_mark_part_books_parts_inside_complete_and_leaves_the_segments():
    """Parts are a second dictionary: they sum to no more than
    `complete`, count their requests, and the identity that the
    segments sum to the turn's wall time stands as it was."""
    turn = Turn()
    with timeline_scope(turn):
        with annotate("pio.turn.fetch"):
            _busy(0.3)
        with annotate("pio.turn.complete"):
            for _ in range(3):
                _busy(0.05)     # the batcher's own loop: in no part
                turn.open_part()
                for name in PARTS:
                    _busy(0.1)
                    mark_part(name)
    t_end = time.perf_counter()
    segs = turn.finish()
    assert sum(segs.values()) == pytest.approx(t_end - turn.t0, abs=2e-4)
    assert set(segs) == {"fetch", "complete"}
    rec = batch_turns()[-1]
    assert rec["turn"] == turn.turn and rec["requests"] == 3
    assert tuple(rec["parts"]) == PARTS
    assert all(v >= 3 * 1e-4 for v in rec["parts"].values())
    assert sum(rec["parts"].values()) <= rec["wall"]["complete"] - 3 * 5e-5
    assert timeline_module._TURNS.maxlen == 16384


def test_two_overlapping_turns_book_only_their_own_segments(monkeypatch):
    """Two turns led at once on two threads, the second launched while
    the first waits in `fetch`: each books only the scopes of its own
    thread and still tiles its own wall time, and the perfbench reader
    `turn_segment_ms` cuts both from the window of their batch spans."""
    from perfbench import cells

    a_sent, b_sent = threading.Event(), threading.Event()
    rec, fn_span = {}, {}

    def lead(name, body):
        turn = Turn()
        with timeline_scope(turn):
            body(name)
        t_end = time.perf_counter()
        segs = turn.finish()
        assert sum(segs.values()) == pytest.approx(t_end - turn.t0,
                                                   abs=2e-4)
        (rec[name],) = [t for t in batch_turns() if t["turn"] == turn.turn]

    def first(name):
        with annotate("pio.turn.park"):
            time.sleep(0.002)
        t0 = time.perf_counter()
        with annotate("pio.turn.prepare"):
            _busy(2.0)
        with annotate("pio.turn.dispatch"):
            a_sent.set()
        with annotate("pio.turn.fetch"):
            assert b_sent.wait(5)
            time.sleep(0.01)
        with annotate("pio.turn.decode"):
            _busy(1.0)
        fn_span[name] = (t0, time.perf_counter(), 1)

    def second(name):
        with annotate("pio.turn.park"):
            assert a_sent.wait(5)
        t0 = time.perf_counter()
        with annotate("pio.turn.prepare"):
            _busy(3.0)
        with annotate("pio.turn.dispatch"):
            b_sent.set()
        with annotate("pio.turn.fetch"):
            time.sleep(0.02)
        fn_span[name] = (t0, time.perf_counter(), 1)

    ta = threading.Thread(target=lead, args=("a", first))
    ta.start()
    assert a_sent.wait(5)
    tb = threading.Thread(target=lead, args=("b", second))
    tb.start()
    ta.join(10)
    tb.join(10)
    a, b = rec["a"], rec["b"]
    assert set(a["wall"]) == {"park", "prepare", "dispatch", "fetch",
                              "decode", "complete"}
    assert set(b["wall"]) == {"park", "prepare", "dispatch", "fetch",
                              "complete"}
    # the first turn's fetch holds the second's whole launch, which its
    # own thread did not book
    assert a["wall"]["fetch"] >= 0.01 + b["wall"]["prepare"] * 0.9
    assert a["wall"]["prepare"] < b["wall"]["prepare"]
    assert b["t0"] < a["t0"] + sum(a["wall"].values())
    monkeypatch.setattr(timeline_module, "batch_turns", lambda: [a, b])
    reader = cells.load_reader("turn_segment_ms")
    run = {"batch_spans": [fn_span["a"], fn_span["b"]]}
    # the first turn was parked before the window opened; the second
    # began to wait inside it
    for segs, want in ((["fetch"], a["wall"]["fetch"] + b["wall"]["fetch"]),
                       (["park"], b["wall"]["park"]),
                       (["prepare", "dispatch"],
                        a["wall"]["prepare"] + a["wall"]["dispatch"]
                        + b["wall"]["prepare"] + b["wall"]["dispatch"])):
        assert reader(run, {"segments": segs}) == pytest.approx(
            1e3 * want / 2)


@pytest.mark.parametrize("scope", ["request", "none"])
def test_mark_part_is_a_no_op_off_a_turn(scope):
    """Under a request's own timeline (a blocking `predict_json`, the aux
    pool) and under none (the loop's thread, a library call)."""
    serve = Timeline("serve")
    with timeline_scope(serve if scope == "request" else None):
        mark_part("serve")
        mark_part("encode")
    assert serve.segments == {} and not hasattr(serve, "parts")


def test_annotate_books_only_on_a_timeline_its_name_addresses():
    """`pio.turn.*` under a request's serve timeline (a direct
    predict, eval) and any other name under a turn book nothing."""
    serve = Timeline("serve")
    with timeline_scope(serve), annotate("pio.turn.prepare"):
        pass
    assert serve.segments == {}
    turn = Turn()
    with timeline_scope(turn), annotate("pio.serve.query"):
        pass
    assert turn.segments == {}
    with annotate("pio.turn.decode"):   # no timeline in scope at all
        pass


def _host_events(trace_dir, prefix):
    from jax.profiler import ProfileData

    (path,) = trace_dir.rglob("*.xplane.pb")
    found = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    found.append((ev.name, dict(ev.stats)))
    return found


def test_annotate_lands_in_a_profiler_session_it_did_not_start(tmp_path):
    """The benchmark, `jax.profiler.start_server` or a notebook start
    the profiler themselves; the program's scopes have to be in that
    trace too, sizes as the event's stats and not in its name."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with annotate("pio.turn.fetch", rows=5, padded=8):
            _busy(1.0)
        with annotate("pio.turn.decode"):
            _busy(1.0)
    finally:
        jax.profiler.stop_trace()
    events = dict(_host_events(tmp_path, "pio.turn."))
    assert set(events) == {"pio.turn.fetch", "pio.turn.decode"}
    assert int(events["pio.turn.fetch"]["rows"]) == 5
    assert int(events["pio.turn.fetch"]["padded"]) == 8


def test_annotate_imports_no_jax_in_a_process_that_has_none():
    code = (
        "import sys\n"
        "from predictionio_tpu.obs.timeline import Turn, annotate, "
        "timeline_scope\n"
        "turn = Turn()\n"
        "with timeline_scope(turn), annotate('pio.turn.claim', rows=1):\n"
        "    pass\n"
        "assert 'claim' in turn.segments\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']\n"
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr


# -- names for the kernels ----------------------------------------------------


def test_lowered_scorer_and_half_iteration_hold_the_scope_names():
    import jax.numpy as jnp
    from predictionio_tpu.models import als
    from predictionio_tpu.ops.topk import batch_topk_scores_t

    text = batch_topk_scores_t.lower(
        jnp.ones((4, 8)), jnp.ones((8, 64)), k=4
    ).as_text(debug_info=True)
    for name in ("topk.scores", "topk.select"):
        assert f"/{name}/" in text, name

    rng = np.random.default_rng(0)
    u, i = rng.integers(0, 30, 400), rng.integers(0, 20, 400)
    trainer = als.ALSTrainer(
        (u, i, rng.uniform(1, 5, 400).astype(np.float32)), 30, 20,
        als.ALSConfig(rank=4, num_iterations=1),
    )
    U, V = trainer.init_factors()
    side = trainer._user_side
    text = als._half_iteration.lower(
        U, V, side["buckets"], jnp.float32(0.1), jnp.float32(1.0),
        ks=side["ks"], implicit=False, weighted_lambda=True,
        precision="highest", solver=trainer.cfg.solver,
    ).as_text(debug_info=True)
    # als.positions: the mask alone here, the expansion at staging
    for name in ("als.positions", "als.gather", "als.gram", "als.solve",
                 "als.scatter"):
        assert f"/{name}/" in text, name
    layout = als.build_bucket_layout(u, i, np.ones(400, np.float32), 30)
    text = als._expand_side.lower(
        jnp.asarray(layout.col_sorted), jnp.asarray(layout.val_sorted),
        tuple((jnp.asarray(b.starts), jnp.asarray(b.counts))
              for b in layout.buckets),
        ks=tuple(b.k for b in layout.buckets),
    ).as_text(debug_info=True)
    assert "/als.positions/" in text


# -- serving integration ----------------------------------------------------


def _tiny_server(storage_memory, microbatch="auto", port=0):
    from predictionio_tpu.controller.base import (
        Algorithm, DataSource, WorkflowContext,
    )
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.workflow.train import run_train

    class DS(DataSource):
        def read_training(self, ctx):
            return 1

    class BatchedAlgo(Algorithm):
        def train(self, ctx, data):
            return {"w": 2}

        def predict(self, model, query):
            return {"y": model["w"] * query.get("x", 0)}

        def batch_predict(self, model, queries):
            return [self.predict(model, q) for q in queries]

    ctx = WorkflowContext(storage=storage_memory)
    engine = SimpleEngine(DS, BatchedAlgo)
    ep = engine.params_from_variant({})
    iid = run_train(engine, ep, ctx=ctx)
    return EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(port=port, microbatch=microbatch),
    )


def _seg_counts(family, segments):
    return {s: family.labels(segment=s).snapshot()["count"]
            for s in segments}


def _wait_counts(family, segments, expected, timeout=5.0):
    """The handler books its timeline AFTER the reply bytes go out, so
    a client that just got its response may read the family a few
    microseconds early — poll instead of racing."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        counts = _seg_counts(family, segments)
        if counts == expected:
            return counts
        time.sleep(0.01)
    return _seg_counts(family, segments)


def test_predict_json_owns_timeline_and_books_all_segments(
        storage_memory):
    srv = _tiny_server(storage_memory, microbatch="auto")
    before = _seg_counts(SERVE_SEGMENT_SECONDS, SERVE_SEGMENTS)
    n = 5
    for k in range(n):
        assert srv.predict_json({"x": k}) == {"y": 2 * k}
    after = _seg_counts(SERVE_SEGMENT_SECONDS, SERVE_SEGMENTS)
    # a direct (handler-less) call books everything except the socket
    # write, which only the HTTP handler can time
    for s in ("parse", "auth", "queue_wait", "batch_wait", "device",
              "serialize"):
        assert after[s] - before[s] == n, s
    assert after["write"] == before["write"]


def test_http_handler_adds_write_segment_and_flight_decomposes(
        storage_memory):
    from predictionio_tpu.obs import get_flight_recorder

    srv = _tiny_server(storage_memory)
    srv.start_background()
    try:
        base = f"http://127.0.0.1:{srv.config.port}"
        before = _seg_counts(SERVE_SEGMENT_SECONDS, SERVE_SEGMENTS)
        lat_before = QUERY_LATENCY.child().snapshot()
        tid = "t-pulse-http"
        req = urllib.request.Request(
            f"{base}/queries.json", data=b'{"x": 3}',
            headers={"Content-Type": "application/json",
                     "X-PIO-Trace": tid},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=15) as r:
            assert json.loads(r.read().decode()) == {"y": 6}
        expected = {s: c + 1 for s, c in before.items()}
        after = _wait_counts(SERVE_SEGMENT_SECONDS, SERVE_SEGMENTS,
                             expected)
        assert after == expected
        # per-process accounting: the new segment mass must cover the
        # new e2e latency mass (the handler window contains the
        # predict window)
        lat_after = QUERY_LATENCY.child().snapshot()
        seg_sum = sum(
            SERVE_SEGMENT_SECONDS.labels(segment=s).snapshot()["sum"]
            for s in SERVE_SEGMENTS
        )
        assert lat_after["count"] == lat_before["count"] + 1
        # the span carries the decomposition ...
        spans = get_tracer().spans(trace_id=tid, name="serve.query")
        assert spans, "serve.query span missing"
        segs_ms = spans[-1].attrs["segmentsMs"]
        assert {"parse", "auth", "queue_wait", "batch_wait",
                "device", "serialize"} <= set(segs_ms)
        assert spans[-1].attrs["modelFreshnessSec"] >= 0
        # ... and so does the flight record (worst-N admits this one:
        # the recorder is process-global, capacity >= 1)
        rec = get_flight_recorder().record_for(tid)
        if rec is not None:  # may be evicted by slower suite traffic
            assert "segmentsMs" in rec["attrs"]
            assert "modelFreshnessSec" in rec["attrs"]
        del seg_sum
    finally:
        srv.stop()


def test_status_json_microbatch_uses_locked_snapshot(storage_memory):
    srv = _tiny_server(storage_memory)
    srv.predict_json({"x": 1})
    mb = srv.status_json()["microbatch"]
    assert {"batches", "requests", "maxBatchSeen", "dispatched",
            "queueDepth"} <= set(mb)
    assert mb["requests"] >= 1
    assert mb["queueDepth"] == 0


def test_event_server_books_ingest_segments(storage_memory):
    from predictionio_tpu.server.event_server import (
        EventServer, EventServerConfig,
    )
    from predictionio_tpu.storage import AccessKey

    md = storage_memory.get_metadata()
    app = md.app_insert("pulseapp")
    key = md.access_key_insert(AccessKey(key="", appid=app.id))
    ev = EventServer(storage_memory, EventServerConfig(port=0))
    ev.start_background()
    try:
        before = _seg_counts(EVENTS_SEGMENT_SECONDS, EVENT_SEGMENTS)
        req = urllib.request.Request(
            f"http://127.0.0.1:{ev.config.port}/events.json"
            f"?accessKey={key}",
            data=json.dumps({
                "event": "rate", "entityType": "user",
                "entityId": "u1", "targetEntityType": "item",
                "targetEntityId": "i1",
                "properties": {"rating": 5.0},
            }).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=15) as r:
            assert r.status == 201
        expected = {s: c + 1 for s, c in before.items()}
        after = _wait_counts(EVENTS_SEGMENT_SECONDS, EVENT_SEGMENTS,
                             expected)
        assert after == expected
        # a rejected request books nothing (no decomposition to pollute
        # the family with)
        bad = urllib.request.Request(
            f"http://127.0.0.1:{ev.config.port}/events.json"
            f"?accessKey={key}",
            data=b"not json",
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(bad, timeout=15)
        time.sleep(0.1)  # give a (buggy) late booking time to land
        final = _seg_counts(EVENTS_SEGMENT_SECONDS, EVENT_SEGMENTS)
        assert final == after
    finally:
        ev.stop()


# -- profiler capture -------------------------------------------------------


def test_capture_profile_writes_nonempty_artifact(tmp_path):
    import jax.numpy as jnp

    stop = threading.Event()

    def work():
        while not stop.is_set():
            (jnp.ones((32, 32)) @ jnp.ones((32, 32))).block_until_ready()

    t = threading.Thread(target=work, daemon=True)
    t.start()
    try:
        res = capture_profile(0.3, out_dir=tmp_path)
    finally:
        stop.set()
        t.join(timeout=10)
    assert res["totalBytes"] > 0
    assert res["files"]
    assert str(tmp_path) in res["dir"]


def test_capture_profile_rejects_concurrent_capture(tmp_path):
    results = {}

    def first():
        results["first"] = capture_profile(0.8, out_dir=tmp_path)

    t = threading.Thread(target=first)
    t.start()
    time.sleep(0.25)  # first capture is inside its sleep window
    with pytest.raises(ProfileBusy):
        capture_profile(0.1, out_dir=tmp_path)
    t.join(timeout=15)
    assert results["first"]["totalBytes"] >= 0


def test_profile_endpoint_over_http(storage_memory, tmp_path,
                                    monkeypatch):
    monkeypatch.setenv("PIO_TPU_HOME", str(tmp_path))
    srv = _tiny_server(storage_memory)
    srv.start_background()
    try:
        base = f"http://127.0.0.1:{srv.config.port}"
        with urllib.request.urlopen(
            f"{base}/debug/profile?seconds=0.2", timeout=60
        ) as r:
            doc = json.loads(r.read().decode())
        assert doc["totalBytes"] > 0
        assert str(tmp_path) in doc["dir"]
        # bad seconds is a 400, not a wedge
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{base}/debug/profile?seconds=abc", timeout=15
            )
        assert ei.value.code == 400
    finally:
        srv.stop()


# -- dashboard --------------------------------------------------------------


def test_pulse_html_renders_segments_and_sweep(storage_memory, tmp_path,
                                               monkeypatch):
    from predictionio_tpu.server.dashboard import DashboardServer

    monkeypatch.setenv("PIO_TPU_HOME", str(tmp_path))
    dash = DashboardServer(storage_memory, port=0)
    html = dash.pulse_html()
    for s in SERVE_SEGMENTS:
        assert s in html
    assert "no sweep recorded yet" in html
    sweep_dir = tmp_path / "telemetry" / "sweeps"
    sweep_dir.mkdir(parents=True)
    (sweep_dir / "latest.json").write_text(json.dumps({
        "recorded_at": "2026-08-04T00:00:00Z", "slo_ms": 25.0,
        "platform": "cpu", "qps_at_slo": 1234.5,
        "concurrency_at_slo": 16,
        "points": [{"concurrency": 16, "qps": 1234.5, "p50_ms": 1.0,
                    "p99_ms": 9.0, "errors": 0,
                    "segments_ms": {"device": 0.8, "queue_wait": 0.1}}],
    }))
    html = dash.pulse_html()
    assert "1234.5" in html
    assert "device 0.80" in html
