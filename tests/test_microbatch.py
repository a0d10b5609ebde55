"""Serving micro-batcher (`server/microbatch.py`): correctness under
concurrency, coalescing into the dispatcher's turns, failure
propagation, and the EngineServer auto-gating."""

import concurrent.futures
import threading
import time

import pytest

from predictionio_tpu.server.microbatch import MicroBatcher


def test_sequential_results_match_direct():
    b = MicroBatcher(lambda xs: [x * 2 for x in xs])
    assert [b.submit(i) for i in range(10)] == [i * 2 for i in range(10)]
    # no concurrency -> every batch was a single item (no added latency)
    assert b.batches == b.requests == 10
    assert b.max_seen == 1


def test_concurrent_calls_coalesce():
    calls = []
    gate = threading.Event()

    def batch_fn(xs):
        calls.append(len(xs))
        if len(calls) == 1:
            gate.set()        # first batch entered
            time.sleep(0.15)  # hold the "device" busy while others arrive
        return [x + 100 for x in xs]

    b = MicroBatcher(batch_fn)
    with concurrent.futures.ThreadPoolExecutor(9) as ex:
        first = ex.submit(b.submit, 0)
        assert gate.wait(2.0)
        rest = [ex.submit(b.submit, i) for i in range(1, 9)]
        results = [first.result(5)] + [f.result(5) for f in rest]
    assert results == [i + 100 for i in range(9)]
    # the 8 requests that arrived while batch 1 ran coalesced into far
    # fewer than 8 additional device calls
    assert calls[0] == 1
    assert sum(calls) == 9
    assert len(calls) <= 4
    assert b.max_seen > 1


def test_max_batch_respected():
    sizes = []

    def batch_fn(xs):
        sizes.append(len(xs))
        time.sleep(0.02)
        return list(xs)

    b = MicroBatcher(batch_fn, max_batch=4)
    with concurrent.futures.ThreadPoolExecutor(16) as ex:
        assert sorted(ex.map(b.submit, range(16))) == list(range(16))
    assert max(sizes) <= 4


def test_exception_propagates_to_every_caller():
    def batch_fn(xs):
        raise RuntimeError("device fell over")

    b = MicroBatcher(batch_fn)
    with concurrent.futures.ThreadPoolExecutor(4) as ex:
        futs = [ex.submit(b.submit, i) for i in range(4)]
        for f in futs:
            with pytest.raises(RuntimeError, match="device fell over"):
                f.result(5)
    # the batcher recovers after a failed batch
    b.batch_fn = lambda xs: list(xs)
    assert b.submit(7) == 7


def test_one_bad_item_does_not_poison_the_batch():
    """A malformed query coalesced with good ones must fail ALONE: the
    batcher retries the failed batch item-by-item so innocent callers
    get their results, like per-request dispatch would have given."""
    entered = threading.Event()

    def batch_fn(xs):
        if len(xs) > 1 and not entered.is_set():
            entered.set()
        if any(x == "bad" for x in xs):
            raise TypeError(f"query {xs} is malformed")
        time.sleep(0.05)  # hold the device so arrivals coalesce
        return [f"ok:{x}" for x in xs]

    b = MicroBatcher(batch_fn, max_wait_s=0.2)
    with concurrent.futures.ThreadPoolExecutor(6) as ex:
        futs = {x: ex.submit(b.submit, x)
                for x in ["a", "bad", "c", "d", "e"]}
        for x, f in futs.items():
            if x == "bad":
                with pytest.raises(TypeError, match="malformed"):
                    f.result(5)
            else:
                assert f.result(5) == f"ok:{x}"


def _wait_pending(b, n, what="arrivals never queued"):
    """Poll (deterministically) until exactly `n` entries are queued."""
    deadline = time.time() + 10
    while True:
        with b._cond:
            if len(b._pending) == n:
                return
        assert time.time() < deadline, what
        time.sleep(0.002)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_base_exception_fails_followers_not_none():
    """A BaseException (KeyboardInterrupt) tearing through the
    dispatcher's turn must surface as an ERROR to every caller whose
    entry the turn had claimed — not as a silent value=None result that
    downstream serving would treat as a prediction (ADVICE r4).  The
    dispatcher dies of it; an entry still queued at that moment is
    claimed by a successor, and the next submit works."""
    started, release = threading.Event(), threading.Event()
    doomed_entered, doomed_release = threading.Event(), threading.Event()
    calls = []

    def batch_fn(xs):
        calls.append(len(xs))
        if len(calls) == 1:  # hold the device so arrivals coalesce
            started.set()
            release.wait(5)
        if len(calls) == 2:  # the coalesced batch's turn is killed
            doomed_entered.set()
            doomed_release.wait(5)
            raise KeyboardInterrupt
        return [f"ok:{x}" for x in xs]

    b = MicroBatcher(batch_fn)
    with concurrent.futures.ThreadPoolExecutor(4) as ex:
        f0 = ex.submit(b.submit, 0)
        assert started.wait(5)
        futs = [ex.submit(b.submit, i) for i in (1, 2)]
        # both queued behind the in-flight batch: ONE turn claims them
        _wait_pending(b, 2)
        release.set()
        assert f0.result(5) == "ok:0"
        # a third arrives while the doomed turn holds the device
        assert doomed_entered.wait(5)
        f3 = ex.submit(b.submit, 3)
        _wait_pending(b, 1)
        doomed_release.set()
        excs = []
        for f in futs:
            try:
                f.result(5)
                excs.append(None)
            except BaseException as e:  # noqa: BLE001 — the assertion
                excs.append(e)
        # stranded behind a dead dispatcher it would never return
        assert f3.result(5) == "ok:3"
    # the interrupt ends the dispatcher, on its own thread; both
    # waiters get a loud error, never a None result
    assert calls[1] == 2
    assert [type(e) for e in excs] == [RuntimeError, RuntimeError]
    assert all("aborted" in str(e) for e in excs)
    # the batcher recovers
    assert b.submit(9) == "ok:9"


def test_length_mismatch_is_an_error():
    b = MicroBatcher(lambda xs: [1])
    b2 = MicroBatcher(lambda xs: list(xs) + [99])
    with pytest.raises(RuntimeError, match="returned"):
        MicroBatcher(lambda xs: []).submit(1)
    del b, b2


def test_accumulation_window():
    """The window must ABSORB arrivals into the open turn's batch (a
    previous version slept the full window and then dispatched without
    them — pure added latency)."""
    sizes = []

    def batch_fn(xs):
        sizes.append(len(xs))
        return list(xs)

    b = MicroBatcher(batch_fn, max_batch=8, max_wait_s=0.5)
    with concurrent.futures.ThreadPoolExecutor(8) as ex:
        assert sorted(ex.map(b.submit, range(8))) == list(range(8))
    # the FIRST batch (the only one whose window was open while the
    # other submits raced in) picked up the arrivals
    assert sizes[0] > 1
    # a full batch short-circuits the window: all 8 in <= 2 batches
    assert len(sizes) <= 2


def test_barrier_driven_coalescing_and_padded_slicing():
    """Deterministic coalescing drill (pio-pulse): the dispatcher's
    first turn is parked on an event while 7 more blocking submits
    queue behind it; on release, exactly ONE more batch forms with all
    7 entries, the padding rounds it to 8, and every caller gets ITS
    OWN result sliced back out of the padded batch."""
    first_entered = threading.Event()
    release = threading.Event()
    seen_sizes = []

    def batch_fn(xs):
        seen_sizes.append(len(xs))
        if len(seen_sizes) == 1:
            first_entered.set()
            assert release.wait(10)
        return [x * 10 for x in xs]

    b = MicroBatcher(batch_fn, max_batch=64, pad_batches=True)
    with concurrent.futures.ThreadPoolExecutor(8) as ex:
        f0 = ex.submit(b.submit, 1)
        assert first_entered.wait(10)
        rest = [ex.submit(b.submit, x) for x in range(2, 9)]
        # deterministic: wait until ALL 7 are parked behind the turn
        _wait_pending(b, 7)
        release.set()
        assert f0.result(10) == 10
        assert [f.result(10) for f in rest] == [
            x * 10 for x in range(2, 9)
        ]
    # batch 1: the lone first entry (no padding at n=1); batch 2: the 7
    # coalesced entries padded to 8 — results sliced back to 7
    assert seen_sizes == [1, 8]
    stats = b.stats()
    assert stats["batches"] == 2
    assert stats["requests"] == 8
    assert stats["maxBatchSeen"] == 7  # pre-padding coalesced size
    # blocking callers park; none of them is a callback entry
    assert stats["dispatched"] == 0
    assert stats["dispatcher"] is True
    assert stats["queueDepth"] == 0


def test_submit_books_timeline_segments():
    """A submit under an active pulse timeline credits queue_wait /
    batch_wait / device; the segment sum stays equal to the covered
    wall time (the accounting identity)."""
    from predictionio_tpu.obs.timeline import Timeline, timeline_scope

    def batch_fn(xs):
        time.sleep(0.02)
        return list(xs)

    b = MicroBatcher(batch_fn)
    tl = Timeline("serve")
    with timeline_scope(tl):
        assert b.submit(5) == 5
    segs = tl.segments
    assert {"queue_wait", "batch_wait", "device"} <= set(segs)
    assert segs["device"] >= 0.015  # the sleep lands in device
    assert sum(segs.values()) == pytest.approx(
        tl._last - tl.t0, abs=1e-6
    )


def test_stats_snapshot_is_consistent_under_concurrency():
    """stats() reads under the lock: batches/requests/dispatched move
    together — a torn read (requests advanced, batches not) can never
    be observed through the snapshot."""
    def batch_fn(xs):
        time.sleep(0.001)
        return list(xs)

    b = MicroBatcher(batch_fn, max_batch=8)
    stop = threading.Event()
    torn = []
    called_back = []

    def reader():
        while not stop.is_set():
            s = b.stats()
            # every counted batch contributes >= 1 request, and a
            # callback entry is booked with its batch's requests
            if s["batches"] > s["requests"]:
                torn.append(s)
            if s["dispatched"] > s["requests"]:
                torn.append(s)

    r = threading.Thread(target=reader)
    r.start()
    with concurrent.futures.ThreadPoolExecutor(8) as ex:
        # half blocking, half by callback, interleaved
        blocking = [ex.submit(b.submit, x) for x in range(0, 200, 2)]
        for x in range(1, 200, 2):
            b.submit_nowait(x, lambda e: called_back.append(e.value))
        assert [f.result(10) for f in blocking] == list(range(0, 200, 2))
    deadline = time.time() + 10
    while len(called_back) < 100 and time.time() < deadline:
        time.sleep(0.002)
    stop.set()
    r.join(5)
    assert not r.is_alive()
    assert torn == []
    assert sorted(called_back) == list(range(1, 200, 2))
    final = b.stats()
    assert final["requests"] == 200
    assert final["dispatched"] == 100
    assert final["batches"] <= 200


def test_engine_server_auto_gating(storage_memory):
    """"auto" batches only when every algorithm has a REAL
    batch_predict; the base-class fallback would serialize inside the
    turn for no gain."""
    from predictionio_tpu.controller.base import (
        Algorithm, DataSource, WorkflowContext,
    )
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.workflow.train import run_train

    class DS(DataSource):
        def read_training(self, ctx):
            return 1

    class PlainAlgo(Algorithm):
        def train(self, ctx, data):
            return {"w": 2}

        def predict(self, model, query):
            return {"y": model["w"] * query.get("x", 0)}

    class BatchedAlgo(PlainAlgo):
        def batch_predict(self, model, queries):
            return [{"y": model["w"] * q.get("x", 0)} for q in queries]

    ctx = WorkflowContext(storage=storage_memory)
    for algo_cls, expect_batcher in ((PlainAlgo, False), (BatchedAlgo, True)):
        engine = SimpleEngine(DS, algo_cls)
        ep = engine.params_from_variant({})
        iid = run_train(engine, ep, ctx=ctx)
        srv = EngineServer(engine, ep, iid, ctx=ctx,
                           config=ServerConfig(port=0))
        assert (srv.batcher is not None) is expect_batcher
        assert srv.predict_json({"x": 3}) == {"y": 6}
        if expect_batcher:
            assert srv.status_json()["microbatch"]["requests"] >= 1
        # forced modes override the heuristic
        srv_off = EngineServer(engine, ep, iid, ctx=ctx,
                               config=ServerConfig(port=0, microbatch="off"))
        assert srv_off.batcher is None
        srv_on = EngineServer(engine, ep, iid, ctx=ctx,
                              config=ServerConfig(port=0, microbatch="on"))
        assert srv_on.batcher is not None
        assert srv_on.predict_json({"x": 5}) == {"y": 10}


# -- pio-surge: continuous admission (submit_nowait + deadlines) -----------


def test_mid_batch_admission_rides_next_device_call():
    """A request admitted WHILE a batch is executing must ride the
    very next device call (continuous admission), not wait out some
    batch-boundary barrier."""
    first_entered = threading.Event()
    release = threading.Event()
    sizes = []
    done = []

    def batch_fn(xs):
        sizes.append(len(xs))
        if len(sizes) == 1:
            first_entered.set()
            assert release.wait(10)
        return [x * 10 for x in xs]

    b = MicroBatcher(batch_fn, max_batch=64)
    b.submit_nowait(1, lambda e: done.append(("a", e.value)))
    assert first_entered.wait(10)  # dispatcher is mid-device-call
    # admitted mid-batch: these queue continuously behind the in-flight
    # batch and form the NEXT one together
    b.submit_nowait(2, lambda e: done.append(("b", e.value)))
    b.submit_nowait(3, lambda e: done.append(("c", e.value)))
    _wait_pending(b, 2)
    release.set()
    deadline = time.time() + 10
    while len(done) < 3 and time.time() < deadline:
        time.sleep(0.005)
    assert sorted(done) == [("a", 10), ("b", 20), ("c", 30)]
    assert sizes == [1, 2]  # the two arrivals coalesced into ONE next call
    stats = b.stats()
    assert stats["dispatched"] == 3
    assert stats["dispatcher"] is True
    b.close()


def test_deadline_expired_request_never_reaches_device():
    """Claim-time enforcement: an entry whose deadline lapsed in the
    queue completes with DeadlineExceeded and the device NEVER sees its
    item."""
    from predictionio_tpu.resilience.policy import (
        Deadline, DeadlineExceeded,
    )

    first_entered = threading.Event()
    release = threading.Event()
    seen_items = []
    done = {}

    def batch_fn(xs):
        seen_items.append(list(xs))
        if len(seen_items) == 1:
            first_entered.set()
            assert release.wait(10)
        return list(xs)

    b = MicroBatcher(batch_fn, max_batch=64)
    b.submit_nowait("warm", lambda e: done.setdefault("warm", e))
    assert first_entered.wait(10)
    # queued behind the in-flight batch with an already-tiny budget
    b.submit_nowait("doomed", lambda e: done.setdefault("doomed", e),
                    deadline=Deadline.after(0.01))
    b.submit_nowait("fine", lambda e: done.setdefault("fine", e))
    time.sleep(0.1)  # let the doomed deadline lapse while queued
    release.set()
    deadline = time.time() + 10
    while len(done) < 3 and time.time() < deadline:
        time.sleep(0.005)
    assert isinstance(done["doomed"].error, DeadlineExceeded)
    assert done["fine"].value == "fine"
    # the device saw the warm batch and the fine item — never "doomed"
    flat = [x for batch in seen_items for x in batch]
    assert "doomed" not in flat
    assert b.stats()["expired"] == 1
    b.close()


def test_continuous_path_timeline_identity():
    """The accounting identity survives the new admission path: an
    async entry's timeline segments still sum EXACTLY to the covered
    wall time (queue_wait/batch_wait/device booked from entry stamps,
    residual credited to device)."""
    from predictionio_tpu.obs.timeline import Timeline

    def batch_fn(xs):
        time.sleep(0.02)
        return list(xs)

    b = MicroBatcher(batch_fn)
    tl = Timeline("serve")
    tl.mark("parse")
    finished = threading.Event()

    def on_done(entry):
        finished.set()

    b.submit_nowait(5, on_done, timeline=tl)
    assert finished.wait(10)
    segs = tl.segments
    assert {"queue_wait", "batch_wait", "device"} <= set(segs)
    assert segs["device"] >= 0.015  # the sleep lands in device
    assert sum(segs.values()) == pytest.approx(tl._last - tl.t0, abs=1e-6)
    b.close()


def test_admission_estimate_and_rejection():
    """check_admission: silent while there is no service-time evidence;
    once the EWMA knows a batch costs ~50 ms, a 1 ms deadline is
    rejected up front (AdmissionRejected ⊂ DeadlineExceeded) and a
    roomy one admits."""
    from predictionio_tpu.resilience.policy import (
        Deadline, DeadlineExceeded,
    )
    from predictionio_tpu.server.microbatch import AdmissionRejected

    def batch_fn(xs):
        time.sleep(0.05)
        return list(xs)

    b = MicroBatcher(batch_fn)
    # no evidence yet: even a tight (unexpired) deadline admits
    assert b.estimate_wait_s() == 0.0
    b.check_admission(Deadline.after(0.001))
    assert b.submit(1) == 1  # teaches the EWMA
    assert b.estimate_wait_s() > 0.04
    with pytest.raises(AdmissionRejected):
        b.check_admission(Deadline.after(0.001))
    assert issubclass(AdmissionRejected, DeadlineExceeded)
    b.check_admission(Deadline.after(10.0))  # roomy budget admits
    b.check_admission(None)  # no deadline: never sheds
    # an already-expired deadline rejects regardless of evidence
    d = Deadline.after(0.0005)
    time.sleep(0.002)
    with pytest.raises(AdmissionRejected):
        b.check_admission(d)


def test_submit_nowait_after_close_raises_and_blocking_still_works():
    b = MicroBatcher(lambda xs: [x + 1 for x in xs])
    done = []
    b.submit_nowait(1, lambda e: done.append(e.value))
    deadline = time.time() + 10
    while not done and time.time() < deadline:
        time.sleep(0.005)
    assert done == [2]
    b.close()
    with pytest.raises(RuntimeError, match="closed"):
        b.submit_nowait(3, lambda e: None)
    # a blocking submit still works after close (a reload swaps
    # batchers under in-flight queries): the drained dispatcher has
    # exited, the submit starts another, which answers and exits too
    deadline = time.time() + 10
    while b.stats()["dispatcher"] and time.time() < deadline:
        time.sleep(0.005)
    assert b.stats()["dispatcher"] is False
    assert b.submit(9) == 10
    assert b.submit(10) == 11
    deadline = time.time() + 10
    while b.stats()["dispatcher"] and time.time() < deadline:
        time.sleep(0.005)
    assert b.stats()["dispatcher"] is False
    assert b.stats()["requests"] == 3


def test_mixed_blocking_and_continuous_coalesce():
    """Blocking submitters and callback entries share the dispatcher's
    batches."""
    first_entered = threading.Event()
    release = threading.Event()
    sizes = []
    async_done = []

    def batch_fn(xs):
        sizes.append(len(xs))
        if len(sizes) == 1:
            first_entered.set()
            assert release.wait(10)
        return [x * 2 for x in xs]

    b = MicroBatcher(batch_fn, max_batch=64)
    b.submit_nowait(1, lambda e: async_done.append(e.value))
    assert first_entered.wait(10)
    with concurrent.futures.ThreadPoolExecutor(2) as ex:
        blocking = [ex.submit(b.submit, x) for x in (2, 3)]
        _wait_pending(b, 2)
        release.set()
        assert sorted(f.result(10) for f in blocking) == [4, 6]
    assert async_done == [2]
    stats = b.stats()
    assert stats["requests"] == 3
    assert stats["batches"] == 2
    # the two blocking entries ran inside the dispatcher's second batch
    assert sizes == [1, 2]
    assert stats["dispatched"] == 1   # the callback entry alone
    b.close()


# -- the dispatcher's turn (obs/timeline.Turn) -------------------------------


def _turn(number):
    """The finished turn's record; a dispatcher finishes its turn after
    the last completion callback has returned, so poll for it."""
    from predictionio_tpu.obs.timeline import batch_turns

    deadline = time.time() + 10
    while True:
        found = [t for t in batch_turns() if t["turn"] == number]
        if found:
            (rec,) = found
            return rec
        assert time.time() < deadline, f"turn {number} never finished"
        time.sleep(0.002)


def test_dispatcher_turn_is_recorded_with_rows_and_segments():
    """One turn of the dispatcher: park until work arrives, claim and
    pad, the whole of a plain batch_fn under `fetch`, the callbacks
    under `complete`; the segments sum to the turn's wall time and the
    entries name the turn."""
    done = threading.Event()
    entries = []

    def batch_fn(xs):
        time.sleep(0.03)
        return list(xs)

    def on_done(entry):
        entries.append(entry)
        time.sleep(0.005)
        if len(entries) == 3:
            done.set()

    b = MicroBatcher(batch_fn, pad_batches=True)
    with b._cond:   # all three are pending before the dispatcher claims
        for x in range(3):
            b.submit_nowait(x, on_done)
    assert done.wait(10)
    b.close()
    (number,) = {e.turn for e in entries}
    rec = _turn(number)
    assert (rec["rows"], rec["padded"]) == (3, 4)
    wall = rec["wall"]
    assert {"park", "claim", "fetch", "complete"} <= set(wall)
    assert wall["fetch"] >= 0.03 and wall["complete"] >= 0.015
    assert all(v >= 0 for v in wall.values())
    # sleeping is not computing: the thread's CPU time stays far below
    assert rec["cpu"]["fetch"] < 0.5 * wall["fetch"]
    assert set(rec["cpu"]) == set(wall)


def test_finer_steps_of_the_engine_come_out_of_fetch():
    """An engine that books `prepare` / `dispatch` / `decode` inside its
    batch function leaves `fetch` only what it did not name."""
    from predictionio_tpu.obs.timeline import annotate

    def batch_fn(xs):
        with annotate("pio.turn.prepare"):
            time.sleep(0.02)
        with annotate("pio.turn.dispatch"):
            pass
        time.sleep(0.01)    # unnamed: stays in the batcher's `fetch`
        with annotate("pio.turn.decode"):
            time.sleep(0.02)
        return list(xs)

    got = []
    b = MicroBatcher(batch_fn)
    b.submit_nowait(1, got.append)
    deadline = time.time() + 10
    while not got:
        assert time.time() < deadline
        time.sleep(0.002)
    b.close()
    wall = _turn(got[0].turn)["wall"]
    assert wall["prepare"] >= 0.02 and wall["decode"] >= 0.02
    assert 0.01 <= wall["fetch"] < 0.02
    assert "dispatch" in wall


def test_blocking_submit_timeline_is_booked_from_the_dispatchers_turn():
    """A blocking submit's serve timeline holds its own segments only,
    booked from the stamps of the dispatcher's turn, and names that
    turn."""
    from predictionio_tpu.obs.timeline import (
        Timeline, current_timeline, timeline_scope,
    )

    def batch_fn(xs):
        time.sleep(0.01)
        return list(xs)

    b = MicroBatcher(batch_fn)
    tl = Timeline("serve")
    with timeline_scope(tl):
        assert b.submit(7) == 7
        assert current_timeline() is tl
    assert set(tl.segments) == {"queue_wait", "batch_wait", "device"}
    assert tl.segments["device"] >= 0.01
    rec = _turn(tl.turn)
    assert rec["rows"] == 1 and rec["wall"]["fetch"] >= 0.01
    assert "park" in rec["wall"]    # the dispatcher waited for the work
    b.close()


def test_served_request_names_its_turn(storage_memory):
    """Over HTTP on the continuous path: the request's `serve.query`
    span (and its flight record) carries `batchTurn`, the number of a
    turn in the deque whose rows include it."""
    import json
    import urllib.request

    from predictionio_tpu.controller.base import (
        Algorithm, DataSource, WorkflowContext,
    )
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.obs import get_flight_recorder, get_tracer
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
    srv = EngineServer(engine, ep, iid, ctx=ctx, config=ServerConfig(port=0))
    srv.start_background()
    try:
        tid = "t-turn-http"
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.config.port}/queries.json",
            data=b'{"x": 4}', method="POST",
            headers={"Content-Type": "application/json",
                     "X-PIO-Trace": tid},
        )
        with urllib.request.urlopen(req, timeout=15) as r:
            assert json.loads(r.read().decode()) == {"y": 8}
    finally:
        srv.stop()
    (span,) = get_tracer().spans(trace_id=tid, name="serve.query")
    rec = _turn(span.attrs["batchTurn"])
    assert rec["rows"] >= 1
    flight = get_flight_recorder().record_for(tid)
    if flight is not None:  # may be evicted by slower suite traffic
        assert flight["attrs"]["batchTurn"] == span.attrs["batchTurn"]


def test_blocking_submit_runs_on_the_dispatcher_thread():
    """ONE thread leads turns: a blocking submit, alone or among
    others, is executed on the thread named ``microbatch-dispatch`` and
    never on a caller's (at the parent a caller with no dispatcher
    alive led its own batch)."""
    ran_on = []

    def batch_fn(xs):
        ran_on.append(threading.current_thread())
        time.sleep(0.002)
        return [x + 1 for x in xs]

    b = MicroBatcher(batch_fn, max_batch=4)
    assert b.submit(0) == 1
    callers = []

    def call(x):
        callers.append(threading.current_thread())
        return b.submit(x)

    with concurrent.futures.ThreadPoolExecutor(8) as ex:
        assert sorted(ex.map(call, range(32))) == list(range(1, 33))
    assert {t.name for t in ran_on} == {"microbatch-dispatch"}
    assert len(set(ran_on)) == 1    # and it is one thread throughout
    assert not set(ran_on) & ({threading.current_thread()} | set(callers))
    assert b.stats()["requests"] == 33
    b.close()
