"""Deployment server tests: /queries.json, status, reload, stop
(reference `CreateServer.scala` routes)."""

import datetime as dt
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from predictionio_tpu.controller import WorkflowContext
from predictionio_tpu.server import EngineServer, ServerConfig
from predictionio_tpu.storage import DataMap, Event
from predictionio_tpu.templates.recommendation import recommendation_engine
from predictionio_tpu.workflow import run_train

UTC = dt.timezone.utc

VARIANT = {
    "datasource": {"params": {"appName": "srvapp"}},
    "algorithms": [
        {"name": "als", "params": {"rank": 4, "numIterations": 3, "lambda": 0.1}}
    ],
}


@pytest.fixture()
def deployed(storage_memory):
    md = storage_memory.get_metadata()
    app = md.app_insert("srvapp")
    es = storage_memory.get_event_store()
    es.init_channel(app.id)
    rng = np.random.default_rng(1)
    evs = [
        Event(event="rate", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{i}",
              properties=DataMap({"rating": float(rng.integers(1, 6))}),
              event_time=dt.datetime(2020, 1, 1, tzinfo=UTC))
        for u in range(8) for i in rng.choice(12, size=6, replace=False)
    ]
    es.insert_batch(evs, app_id=app.id)
    ctx = WorkflowContext(storage=storage_memory)
    engine = recommendation_engine()
    ep = engine.params_from_variant(VARIANT)
    iid = run_train(engine, ep, ctx=ctx, engine_variant="srv.json")
    server = EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(port=0),  # ephemeral port
        engine_variant="srv.json",
    )
    server.start_background()
    yield server, ctx, engine, ep
    server.stop()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, json.loads(r.read().decode())


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, json.loads(r.read().decode())


def test_queries_json(deployed):
    server, *_ = deployed
    base = f"http://127.0.0.1:{server.config.port}"
    status, body = _post(f"{base}/queries.json", {"user": "u1", "num": 3})
    assert status == 200
    assert len(body["itemScores"]) == 3
    scores = [s["score"] for s in body["itemScores"]]
    assert scores == sorted(scores, reverse=True)


def test_lone_query_is_a_batch_of_one(deployed, monkeypatch):
    """One route for a lone request: over HTTP, with nothing else in
    flight, it reaches `batch_predict` as a batch of one, on the
    dispatcher's thread, and never `predict` (at the parent the
    batcher's batch function sent a claimed batch of one there)."""
    import threading

    from predictionio_tpu.templates.recommendation import ALSAlgorithm

    server, *_ = deployed
    calls = []
    real_batch = ALSAlgorithm.batch_predict

    def batch_predict(self, model, queries):
        calls.append(("batch_predict", len(queries),
                      threading.current_thread().name))
        return real_batch(self, model, queries)

    def predict(self, model, query):
        calls.append(("predict", 1, threading.current_thread().name))
        raise AssertionError("a lone request must not reach predict")

    monkeypatch.setattr(ALSAlgorithm, "batch_predict", batch_predict)
    monkeypatch.setattr(ALSAlgorithm, "predict", predict)
    base = f"http://127.0.0.1:{server.config.port}"
    for num in (3, 1):
        status, body = _post(f"{base}/queries.json",
                             {"user": "u1", "num": num})
        assert status == 200 and len(body["itemScores"]) == num
    # an unknown user's empty answer takes the same route
    status, body = _post(f"{base}/queries.json", {"user": "ghost"})
    assert status == 200 and body["itemScores"] == []
    assert calls == [("batch_predict", 1, "microbatch-dispatch")] * 3


def test_unknown_user_empty_scores(deployed):
    server, *_ = deployed
    base = f"http://127.0.0.1:{server.config.port}"
    _, body = _post(f"{base}/queries.json", {"user": "ghost", "num": 3})
    assert body == {"itemScores": []}


def test_malformed_query_400(deployed):
    server, *_ = deployed
    base = f"http://127.0.0.1:{server.config.port}"
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(f"{base}/queries.json", {"num": 3})  # missing "user"
    assert exc.value.code == 400


def test_invalid_json_400(deployed):
    server, *_ = deployed
    base = f"http://127.0.0.1:{server.config.port}"
    req = urllib.request.Request(
        f"{base}/queries.json", data=b"{not json",
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=10)
    assert exc.value.code == 400


def test_status_page_latency_bookkeeping(deployed):
    server, *_ = deployed
    base = f"http://127.0.0.1:{server.config.port}"
    _post(f"{base}/queries.json", {"user": "u1", "num": 2})
    status, body = _get(f"{base}/")
    assert status == 200
    assert body["status"] == "alive"
    assert body["requestCount"] >= 1
    assert body["avgServingSec"] > 0
    assert body["engineInstanceId"] == server.instance_id


def test_status_json_exposes_resilience_observability(deployed):
    """Failure observability contract: queue depth/drops, breaker
    states, retry counts, and lastReloadError all ride the status
    JSON."""
    server, *_ = deployed
    base = f"http://127.0.0.1:{server.config.port}"
    _, body = _get(f"{base}/")
    res = body["resilience"]
    assert res["lastReloadError"] is None
    assert res["queryTimeoutSec"] is None  # default: unbounded
    for queue in (res["feedback"], res["remoteLog"]):
        for k in ("depth", "capacity", "submitted", "delivered",
                  "dropped", "retries", "sendFailures"):
            assert isinstance(queue[k], int), k
        assert queue["breaker"]["state"] == "closed"
        assert queue["breaker"]["consecutiveFailures"] == 0


def test_reload_swaps_to_latest(deployed):
    server, ctx, engine, ep = deployed
    old_iid = server.instance_id
    new_iid = run_train(engine, ep, ctx=ctx, engine_variant="srv.json")
    base = f"http://127.0.0.1:{server.config.port}"
    status, body = _get(f"{base}/reload")
    assert status == 200
    assert body["reloaded"] == new_iid != old_iid
    assert server.instance_id == new_iid


def test_reload_under_concurrent_load(deployed):
    """Hot-swap while queries are in flight: the micro-batcher is
    rebuilt for the new (algorithms, models) snapshot under the lock;
    every response during the swap must be a valid prediction from ONE
    coherent model — no errors, no torn state."""
    import concurrent.futures

    server, ctx, engine, ep = deployed
    base = f"http://127.0.0.1:{server.config.port}"
    new_iid = run_train(engine, ep, ctx=ctx, engine_variant="srv.json")
    stop = False

    def hammer(tid):
        n = 0
        while not stop:
            status, body = _post(f"{base}/queries.json",
                                 {"user": f"u{tid % 8}", "num": 3})
            assert status == 200 and len(body["itemScores"]) == 3
            scores = [s["score"] for s in body["itemScores"]]
            assert scores == sorted(scores, reverse=True)
            n += 1
        return n

    with concurrent.futures.ThreadPoolExecutor(6) as ex:
        futs = [ex.submit(hammer, t) for t in range(4)]
        try:
            for _ in range(3):
                status, body = _get(f"{base}/reload")
                assert status == 200 and body["reloaded"] == new_iid
        finally:
            stop = True  # always release the hammers, or shutdown hangs
        assert sum(f.result(30) for f in futs) > 0
    assert server.instance_id == new_iid


def test_unknown_route_404(deployed):
    server, *_ = deployed
    base = f"http://127.0.0.1:{server.config.port}"
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get(f"{base}/nope")
    assert exc.value.code == 404


def test_port_in_use_raises(deployed):
    """Binding a second server on a busy port must raise, not hang."""
    server, ctx, engine, ep = deployed
    dup = EngineServer(
        engine, ep, server.instance_id, ctx=ctx,
        config=ServerConfig(port=server.config.port),
        engine_variant="srv.json",
    )
    with pytest.raises(OSError):
        dup.start_background()


def test_warmup_called_on_load(storage_memory, monkeypatch):
    """Deploy must warm the scoring path before taking queries."""
    import numpy as np

    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSModel)
    from predictionio_tpu.storage.bimap import StringIndex

    model = ALSModel(
        user_factors=np.ones((3, 4), np.float32),
        item_factors=np.ones((5, 4), np.float32),
        users=StringIndex(["u0", "u1", "u2"]),
        items=StringIndex([f"i{n}" for n in range(5)]),
        item_props={},
    )
    algo = ALSAlgorithm()
    algo.warmup(model)  # must not raise, must populate the device cache
    assert getattr(model, "_dev_item_factors_native", None) is not None
    # empty model: warmup is a no-op, not a crash
    empty = ALSModel(
        user_factors=np.zeros((0, 4), np.float32),
        item_factors=np.zeros((0, 4), np.float32),
        users=StringIndex([]), items=StringIndex([]), item_props={},
    )
    algo.warmup(empty)


def test_bind_retry_then_fail():
    """Port conflict: retried, then surfaces as an OSError (reference
    MasterActor retries the bind 3x)."""
    import time

    from predictionio_tpu.server.http_base import HTTPServerBase

    class Dummy(HTTPServerBase):
        bind_retries = 2
        host = "127.0.0.1"

        def _make_handler(self):
            from predictionio_tpu.server.http_base import JsonRequestHandler

            return JsonRequestHandler

    a = Dummy()
    a.port = 0
    a._bind()
    taken = a.port
    b = Dummy()
    b.port = taken
    t0 = time.time()
    with pytest.raises(OSError):
        b._bind()
    assert time.time() - t0 >= 0.9  # at least one 1s retry gap
    a.stop()


def test_deploy_serves_trained_params_not_variant(storage_memory):
    """Reference engineInstanceToEngineParams semantics: serving must use
    the params the instance was trained with, even if engine.json (or the
    in-memory EngineParams) has drifted since."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
    from fixtures import Algo0, DataSource0, IdParams, Serving0

    from predictionio_tpu.controller import Engine, EngineParams
    from predictionio_tpu.controller.base import (
        IdentityPreparator, WorkflowContext)
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.workflow.train import run_train

    engine = Engine(DataSource0, IdentityPreparator, {"a0": Algo0}, Serving0)
    trained_ep = EngineParams(
        data_source=("", IdParams(id=1)),
        algorithms=[("a0", IdParams(id=42))],
    )
    ctx = WorkflowContext(storage=storage_memory, mode="Training")
    iid = run_train(engine, trained_ep, ctx=ctx, engine_id="drift",
                    engine_variant="v")

    # a *different* in-memory params object simulates a drifted engine.json
    drifted = EngineParams(
        data_source=("", IdParams(id=1)),
        algorithms=[("a0", IdParams(id=999))],
    )
    server = EngineServer(
        engine, drifted, iid,
        ctx=WorkflowContext(storage=storage_memory, mode="Serving"),
        config=ServerConfig(port=0), engine_id="drift", engine_variant="v",
    )
    # the reconstructed algorithm params are the trained ones
    (name, params), = server.engine_params.algorithms
    assert name == "a0" and params.id == 42


def test_generic_dataclass_query_decode_and_result_encode():
    """Engines whose Query is a plain dataclass (no from_json) and whose
    results are lists of dataclasses must serve without custom codecs —
    the generic analogue of json4s Extraction.extract
    (`CreateServer.scala:470-471`)."""
    from dataclasses import dataclass

    from predictionio_tpu.controller import (
        Algorithm, DataSource, Engine, EngineParams, FirstServing,
        IdentityPreparator,
    )
    from predictionio_tpu.server.serving import (
        _default_query_decoder, _result_to_json,
    )

    @dataclass
    class PlainQuery:
        user: str
        num: int = 4

    @dataclass
    class Score:
        item: str
        score: float

    class PlainAlgo(Algorithm):
        query_class = PlainQuery

        def train(self, ctx, pd):
            return None

        def predict(self, model, query):
            return [Score(item="a", score=1.0)]

    class DS(DataSource):
        def read_training(self, ctx):
            return None

    engine = Engine(DS, IdentityPreparator, {"a": PlainAlgo}, FirstServing)
    ep = EngineParams(algorithms=[("a", None)])
    decode = _default_query_decoder(engine, ep)
    q = decode({"user": "u1", "num": 7, "unknownKey": "ignored"})
    assert isinstance(q, PlainQuery) and q.user == "u1" and q.num == 7

    out = _result_to_json([Score(item="a", score=1.0),
                           Score(item="b", score=0.5)])
    assert out == [{"item": "a", "score": 1.0}, {"item": "b", "score": 0.5}]
    assert _result_to_json({"k": (Score(item="c", score=2.0),)}) == {
        "k": [{"item": "c", "score": 2.0}]
    }


def test_status_page_html_for_browsers(deployed):
    """`/` content-negotiates: browsers (Accept: text/html) get the HTML
    status page (the reference's Twirl index page role), API clients keep
    getting JSON."""
    server, *_ = deployed
    base = f"http://127.0.0.1:{server.config.port}"
    req = urllib.request.Request(
        f"{base}/", headers={"Accept": "text/html,application/xhtml+xml"}
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/html")
        page = r.read().decode()
    assert "<html" in page and "Engine Information" in page
    assert server.instance_id in page
    # component params are rendered
    assert "Algorithm [als]" in page and "rank" in page
    # JSON clients are unaffected
    status, body = _get(f"{base}/")
    assert status == 200 and body["status"] == "alive"


def test_concurrent_queries(deployed):
    """Concurrent /queries.json requests: the threading server + cached
    device tables + shared jit executables must serve in parallel without
    errors or cross-request corruption."""
    import concurrent.futures

    server, *_ = deployed
    base = f"http://127.0.0.1:{server.config.port}"

    def query(u):
        status, body = _post(f"{base}/queries.json",
                             {"user": f"u{u % 8}", "num": 3})
        assert status == 200
        scores = [s["score"] for s in body["itemScores"]]
        assert scores == sorted(scores, reverse=True)
        return body

    with concurrent.futures.ThreadPoolExecutor(max_workers=10) as ex:
        results = list(ex.map(query, range(60)))
    # same user -> same ranking regardless of interleaving; scores may
    # wobble at float ulp scale because the micro-batcher's batched
    # matmul compiles per batch size (different reduction order).
    # microbatch="off" restores bitwise per-request determinism.
    by_user = {}
    for u, body in zip(range(60), results):
        k = u % 8
        if k in by_user:
            ref = by_user[k]
            assert [s["item"] for s in body["itemScores"]] == [
                s["item"] for s in ref["itemScores"]
            ]
            for got, want in zip(body["itemScores"], ref["itemScores"]):
                assert abs(got["score"] - want["score"]) < 1e-4
        else:
            by_user[k] = body
    # the batcher actually coalesced under this load
    status = json.loads(
        urllib.request.urlopen(f"{base}/", timeout=10).read().decode()
    )
    assert status["microbatch"]["requests"] >= 60


def test_remote_error_log_shipping(storage_memory):
    """Serving failures POST to the configured log endpoint with the
    engine-instance identity and message, prefixed (reference
    `CreateServer.scala:413-424` remoteLog).  Delivery is off the hot
    path and a dead endpoint must never break serving."""
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    received = []
    got_one = threading.Event()

    class Sink(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            received.append(self.rfile.read(n).decode())
            got_one.set()
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    sink = HTTPServer(("127.0.0.1", 0), Sink)
    threading.Thread(target=sink.serve_forever, daemon=True).start()

    md = storage_memory.get_metadata()
    app = md.app_insert("logapp")
    es = storage_memory.get_event_store()
    es.init_channel(app.id)
    rng = np.random.default_rng(2)
    evs = [
        Event(event="rate", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{i}",
              properties=DataMap({"rating": float(rng.integers(1, 6))}),
              event_time=dt.datetime(2020, 1, 1, tzinfo=UTC))
        for u in range(6) for i in rng.choice(8, size=4, replace=False)
    ]
    es.insert_batch(evs, app_id=app.id)
    ctx = WorkflowContext(storage=storage_memory)
    engine = recommendation_engine()
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "logapp"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 4, "numIterations": 2, "lambda": 0.1}}],
    })
    iid = run_train(engine, ep, ctx=ctx, engine_variant="log.json")
    server = EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(
            port=0,
            log_url=f"http://127.0.0.1:{sink.server_port}/log",
            log_prefix="pio-err: ",
        ),
        engine_variant="log.json",
    )
    server.start_background()
    try:
        base = f"http://127.0.0.1:{server.config.port}"
        # a bad query (unknown key type) -> 400 + shipped log
        try:
            _post(f"{base}/queries.json", {"user": 123456, "num": "x"})
        except urllib.error.HTTPError as e:
            assert e.code in (400, 500)
        assert got_one.wait(5.0), "no remote log arrived"
        payload = received[0]
        assert payload.startswith("pio-err: ")
        body = json.loads(payload[len("pio-err: "):])
        assert body["engineInstance"]["id"] == iid
        assert "message" in body and body["message"]

        # good queries still work with shipping configured
        status, out = _post(f"{base}/queries.json", {"user": "u1", "num": 2})
        assert status == 200 and len(out["itemScores"]) == 2

        # dead endpoint: reconfigure and confirm serving unaffected
        sink.shutdown()
        server.config.log_url = "http://127.0.0.1:1/nope"
        try:
            _post(f"{base}/queries.json", {"user": 99, "num": "y"})
        except urllib.error.HTTPError as e:
            assert e.code in (400, 500)
        status, out = _post(f"{base}/queries.json", {"user": "u2", "num": 2})
        assert status == 200
    finally:
        server.stop()
