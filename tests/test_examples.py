"""End-to-end checks for the engines under examples/."""

import importlib
import os
import sys
from pathlib import Path

import numpy as np
import pytest

from predictionio_tpu.controller.base import WorkflowContext

EXAMPLES = Path(__file__).resolve().parent.parent / "examples"


@pytest.fixture()
def in_example():
    """Import an example's engine module the way the CLI would (cwd on
    path); teardown restores cwd/sys.path even if the import itself fails."""
    old_cwd = os.getcwd()
    added: list[str] = []

    def load(name):
        d = str(EXAMPLES / name)
        os.chdir(d)
        sys.path.insert(0, d)
        added.append(d)
        sys.modules.pop("engine", None)
        return importlib.import_module("engine")

    yield load
    os.chdir(old_cwd)
    for d in added:
        if d in sys.path:
            sys.path.remove(d)
    sys.modules.pop("engine", None)


def _train_and_params(m):
    import json

    engine = m.engine_factory()
    variant = json.loads(Path("engine.json").read_text())
    ep = engine.params_from_variant(variant)
    ctx = WorkflowContext()
    models = engine.train(ctx, ep)
    return engine, ep, models


def test_helloworld(in_example):
    m = in_example("helloworld")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    r = algo.predict(models[0], m.Query(day="Mon"))
    assert r.temperature == pytest.approx((75 + 62) / 2)


def test_regression(in_example):
    m = in_example("regression")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    # data is y = 1 + x1 + x2 exactly
    pred = algo.predict(models[0], m.Query(features=[2.0, 3.0]))
    assert pred == pytest.approx(6.0, abs=0.05)


def test_markovchain(in_example):
    m = in_example("markovchain")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    ranked = algo.predict(models[0], m.Query(state="search"))
    assert ranked and ranked[0][0] == "product"


def test_friendrec(in_example):
    m = in_example("friendrec")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    # shared 'music' keyword: 2.0 * 1.0 = 2.0 >= threshold
    r = algo.predict(models[0], m.Query(user="alice", item="jazz-club"))
    assert r.confidence == pytest.approx(2.0)
    assert r.acceptance
    # no shared keywords
    r = algo.predict(models[0], m.Query(user="carol", item="jazz-club"))
    assert r.confidence == 0.0 and not r.acceptance
    # unseen entity -> 0/False like the reference
    r = algo.predict(models[0], m.Query(user="nobody", item="jazz-club"))
    assert r.confidence == 0.0 and not r.acceptance
    # batch path agrees with the scalar path
    qs = [m.Query(user="alice", item="jazz-club"),
          m.Query(user="bob", item="trail-group")]
    batch = algo.batch_predict(models[0], qs)
    singles = [algo.predict(models[0], q) for q in qs]
    assert [b.confidence for b in batch] == pytest.approx(
        [s.confidence for s in singles])


def test_dimsum(in_example):
    m = in_example("dimsum")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    # i1 and i2 are co-rated high by u1-u3 -> most similar pair
    res = algo.predict(models[0], m.Query(items=("i1",), num=2))
    assert res and res[0].item == "i2"
    res34 = algo.predict(models[0], m.Query(items=("i3",), num=2))
    assert res34 and res34[0].item == "i4"
    # query items never recommend themselves
    assert all(r.item != "i1" for r in res)


def test_stock(in_example):
    m = in_example("stock")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    assert algo.predict(models[0], m.Query(ticker="UPCO")).signal == "long"
    assert algo.predict(models[0], m.Query(ticker="DNCO")).signal == "short"
    assert algo.predict(models[0], m.Query(ticker="FLAT")).signal == "flat"
    assert algo.predict(models[0], m.Query(ticker="NOPE")).signal == "flat"


def test_parallel_regression(in_example):
    m = in_example("parallel-regression")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    # data is y = 1 + 2*x1 - 0.5*x2 exactly; mesh run must recover it
    pred = algo.predict(models[0], m.Query(features=[1.0, 2.0]))
    assert pred == pytest.approx(1 + 2 * 1.0 - 0.5 * 2.0, abs=0.05)
    w = models[0]
    assert w[0] == pytest.approx(1.0, abs=0.05)
    assert w[1] == pytest.approx(2.0, abs=0.05)
    assert w[2] == pytest.approx(-0.5, abs=0.05)


def test_custom_datasource(in_example):
    m = in_example("custom-datasource")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    # u0 likes even items (group 0): top recommendation should be even
    res = algo.predict(models[0], m.Query(user="u0", num=3))
    assert res and int(res[0].item[1:]) % 2 == 0
    assert algo.predict(models[0], m.Query(user="ghost", num=3)) == []


def test_movielens_eval(in_example, tmp_path, monkeypatch):
    m = in_example("movielens-eval")
    import os

    from predictionio_tpu.workflow import run_evaluation

    # best.json should land in a scratch dir, not the example dir
    data = os.path.join(os.getcwd(), "ratings.csv")
    monkeypatch.chdir(tmp_path)
    candidates = [
        type(ep)(
            data_source=("", type(ep.data_source[1])(path=data)),
            algorithms=ep.algorithms,
        )
        for ep in m.engine_params_list()
    ]
    evaluation = m.evaluation_factory()
    _, result = run_evaluation(evaluation, candidates)
    assert result.metric_header == "MSE"
    scores = [s for _, s, _ in result.results]
    assert all(s == s for s in scores)  # finite
    # the stronger candidate (rank 6, 8 iters) must win
    assert result.best_engine_params.algorithms[0][1].rank == 6
    assert result.best_score == min(scores)


def test_entitymap(in_example):
    m = in_example("entitymap")
    engine, ep, models = _train_and_params(m)
    model = models[0]
    # required-attribute filter: u6 (no attr2) and i5 (no attrA) dropped
    assert "u6" not in model.users and len(model.users) == 6
    assert "i5" not in model.items and len(model.items) == 5
    # typed payloads survive extraction
    assert model.users["u2"] == m.User(attr0=3.5, attr1=2, attr2=12)
    assert model.items["i1"].attrA == "green"
    assert isinstance(model.items["i0"].attrC, bool)
    algo = engine._algorithms(ep)[0]
    r = algo.predict(model, m.Query(user="u0", num=3))
    assert len(r) == 3
    assert all(isinstance(s.payload, m.Item) for s in r)
    scores = [s.score for s in r]
    assert scores == sorted(scores, reverse=True)
    # unseen user -> empty, like the reference
    assert algo.predict(model, m.Query(user="nobody")) == []


def test_movielens_filtering(in_example, tmp_path):
    m = in_example("movielens-filtering")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    # serve against a scratch COPY of the blocklist so the test can edit
    # it without dirtying the checked-in example file
    import pathlib

    from predictionio_tpu.controller.base import instantiate

    blocked = tmp_path / "blocked.txt"
    blocked.write_text(pathlib.Path("blocked.txt").read_text())
    serving = instantiate(
        m.BlocklistServing, m.FilterParams(filepath=str(blocked))
    )

    def recommend(user, num=4):
        return serving.serve(
            m.Query(user=user, num=num),
            [algo.predict(models[0], m.Query(user=user, num=num))],
        )

    r = recommend("u0")
    items = [s.item for s in r.item_scores]
    assert len(items) == 4
    # blocklisted movies never surface, whatever their score
    assert "m0" not in items and "m7" not in items
    # the blocklist is read per request: editing it changes the result
    # without retraining (reference Filtering.scala re-reads the file)
    blocked.write_text("")
    r2 = recommend("u0", num=10)
    assert "m0" in [s.item for s in r2.item_scores]


def test_similarproduct_local(in_example):
    m = in_example("similarproduct-local")
    from predictionio_tpu.controller import ModelPlacement

    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    # the point of the variant: host placement routes persistence through
    # the plain pickle path and predict never dispatches to a device
    assert algo.placement is ModelPlacement.HOST
    model = models[0]
    import numpy as np

    assert isinstance(model.item_factors, np.ndarray)
    r = algo.predict(model, m.Query(items=("phone",), num=3))
    assert len(r) == 3
    got = [s.item for s in r]
    assert "phone" not in got  # query items never recommended back
    # co-viewed electronics outrank garden items for an electronics query
    assert set(got[:2]) <= {"laptop", "tablet", "camera"}, got
    # unseen query items -> empty
    assert algo.predict(model, m.Query(items=("nothere",))) == []


def test_recommendation_cat(in_example):
    m = in_example("recommendation-cat")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    # unfiltered: any item may appear
    r = algo.predict(models[0], m.Query(user="u0", num=5))
    assert len(r.item_scores) == 5
    # category-filtered: every result is a drama
    dramas = {"m2", "m3", "m6", "m7"}
    r = algo.predict(models[0], m.Query(user="u0", num=3,
                                        categories=("drama",)))
    assert r.item_scores and {s.item for s in r.item_scores} <= dramas
    # categories compose with blacklist
    r = algo.predict(models[0], m.Query(user="u0", num=3,
                                        categories=("drama",),
                                        blacklist=("m2",)))
    assert {s.item for s in r.item_scores} <= dramas - {"m2"}


def test_similarproduct_multi(in_example):
    m = in_example("similarproduct-multi")
    engine, ep, models = _train_and_params(m)
    algos = engine._algorithms(ep)
    assert len(algos) == 2 and len(models) == 2
    serving = engine._serving(ep)
    q = m.Query(items=("phone",), num=3)
    preds = [a.predict(mod, q) for a, mod in zip(algos, models)]
    r = serving.serve(q, preds)
    assert len(r.item_scores) == 3
    got = [s.item for s in r.item_scores]
    assert "phone" not in got
    # both electronics-cluster signals agree: blend prefers electronics
    assert got[0] in {"laptop", "tablet", "camera"}, got
    # z-scores: combined scores are O(1), not raw-cosine-scale
    assert all(abs(s.score) < 10 for s in r.item_scores)
    # single-item query path (no standardization) still works
    r1 = serving.serve(m.Query(items=("phone",), num=1), [
        a.predict(mod, m.Query(items=("phone",), num=1))
        for a, mod in zip(algos, models)
    ])
    assert len(r1.item_scores) == 1


def test_trim_app(in_example, storage_memory):
    import datetime as dt

    from predictionio_tpu.controller.base import WorkflowContext
    from predictionio_tpu.storage.event import DataMap, Event

    m = in_example("trim-app")
    UTC = dt.timezone.utc
    ctx = WorkflowContext(storage=storage_memory)
    es = ctx.storage.get_event_store()
    for day in (1, 2, 3, 4, 5):
        es.insert(Event(event="rate", entity_type="user", entity_id=f"u{day}",
                        target_entity_type="item", target_entity_id="i1",
                        properties=DataMap({"rating": 3.0}),
                        event_time=dt.datetime(2020, 1, day, tzinfo=UTC)),
                  app_id=1)
    import json
    from pathlib import Path

    engine = m.engine_factory()
    ep = engine.params_from_variant(json.loads(Path("engine.json").read_text()))
    models = engine.train(ctx, ep)
    summary = models[0]
    # window [Jan 2, Jan 4): days 2 and 3 only
    assert summary.copied == 2
    got = sorted(e.entity_id for e in es.find(app_id=2))
    assert got == ["u2", "u3"]
    # event ids preserved across the copy
    src_ids = {e.event_id for e in es.find(app_id=1)}
    assert {e.event_id for e in es.find(app_id=2)} <= src_ids
    # refuses a non-empty destination
    import pytest

    with pytest.raises(RuntimeError, match="not empty"):
        engine.train(ctx, ep)


def test_trim_app_failed_copy_leaves_dst_empty(in_example, storage_memory):
    """A mid-copy failure must clean the destination so a retry is
    possible — on ANY backend, including the non-transactional memory
    store."""
    import datetime as dt
    import json
    from pathlib import Path

    import pytest

    from predictionio_tpu.controller.base import WorkflowContext
    from predictionio_tpu.storage.event import DataMap, Event

    m = in_example("trim-app")
    UTC = dt.timezone.utc
    ctx = WorkflowContext(storage=storage_memory)
    es = ctx.storage.get_event_store()
    for day in (2, 3):
        es.insert(Event(event="rate", entity_type="user", entity_id=f"u{day}",
                        target_entity_type="item", target_entity_id="i1",
                        properties=DataMap({"rating": 3.0}),
                        event_time=dt.datetime(2020, 1, day, tzinfo=UTC)),
                  app_id=1)
    engine = m.engine_factory()
    ep = engine.params_from_variant(
        json.loads(Path("engine.json").read_text())
    )
    real = es.insert_batch

    def boom(events, app_id, *a, **kw):
        if app_id == 2:
            real(events[:1], app_id, *a, **kw)  # partial write, then die
            raise OSError("disk full")
        return real(events, app_id, *a, **kw)

    es.insert_batch = boom
    try:
        with pytest.raises(OSError):
            engine.train(ctx, ep)
    finally:
        es.insert_batch = real
    assert list(es.find(app_id=2)) == []  # cleaned up
    models = engine.train(ctx, ep)  # retry succeeds
    assert models[0].copied == 2


def test_lambda_sweep(in_example, capsys):
    m = in_example("lambda-sweep")
    m.main()
    out = capsys.readouterr().out
    assert "best lambda" in out
    # the winner must be an interior candidate (underfit/overfit extremes
    # lose on holdout) and every candidate row must print
    for lam in m.LAMBDAS:
        assert f"{lam:>8}" in out
    best = float(out.rsplit("best lambda = ", 1)[1].split()[0])
    assert best in (0.05, 0.1)


def test_sharded_scale(in_example, capsys):
    m = in_example("sharded-scale")
    m.main()
    out = capsys.readouterr().out
    assert "sharded-scale OK" in out
    assert "each device stores" in out
    # the example's own assertion guarantees numeric agreement; the
    # printed per-device count must be well under the replicated total
    import re

    stored = int(
        re.search(r"each device stores ([\d,]+)", out).group(1)
        .replace(",", "")
    )
    assert stored < 40_000 / 4


def test_simrank(in_example):
    m = in_example("simrank")
    engine, ep, models = _train_and_params(m)
    algo = engine._algorithms(ep)[0]
    model = models[0]
    # SimRank structure: s(a,a)=1, symmetric, decays with distance
    S = model.scores
    assert np.allclose(np.diag(S), 1.0)
    assert np.allclose(S, S.T, atol=1e-5)
    # 0 (nbrs {2,3,5}) and 4 (nbrs {2,3,5,9}) share three neighbors ->
    # each other's top recommendation
    res = algo.predict(model, m.Query(user="0", num=3))
    assert res and res[0].user == "4"
    res4 = algo.predict(model, m.Query(user="4", num=3))
    assert res4 and res4[0].user == "0"
    # unknown vertex -> empty, never a crash
    assert algo.predict(model, m.Query(user="nope", num=3)) == []

    # the sampling data sources produce valid sub-graphs the same
    # algorithm trains on (reference's Node/ForestFire sampling sources)
    for name in ("node", "forestfire"):
        ep2 = engine.params_from_variant({
            "datasource": {"name": name, "params": {
                "graph_edgelist_path": "edge_list_small.txt",
                "sample_fraction": 0.6}},
            "algorithms": [{"name": "simrank",
                            "params": {"num_iterations": 3}}],
        })
        sub = engine.train(WorkflowContext(), ep2)[0]
        n_sub = len(sub.vertices)
        assert 2 <= n_sub < 10
        assert np.allclose(np.diag(sub.scores), 1.0)


@pytest.mark.parametrize(
    "name", ["movielens-eval", "lambda-sweep", "sharded-scale"]
)
def test_standalone_example_mains_execute(tmp_path, name):
    """The examples with runnable ``__main__`` blocks execute end to
    end as a user would run them (the in_example tests above import
    their engine factories but never the main blocks — which is exactly
    where a `to_oneliner` API-drift bug hid until round 5)."""
    import shutil
    import subprocess

    src = EXAMPLES / name
    work = tmp_path / name
    shutil.copytree(src, work)
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # the multi-device mesh the sharded example's docstring
        # prescribes — without it that main prints and early-returns,
        # executing nothing
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": str(EXAMPLES.parent),
        "PIO_TPU_HOME": str(work / ".home"),
    })
    proc = subprocess.run(
        [sys.executable, "engine.py"], cwd=work, env=env,
        capture_output=True, text=True, timeout=400,
    )
    assert proc.returncode == 0, (
        f"{name} main failed:\n{proc.stderr[-2000:]}"
    )
