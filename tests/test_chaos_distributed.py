"""pio-armor chaos suite: straggler / dead worker / torn exchange on the
SIMULATED cluster (the in-process 8-virtual-device mesh every tier-1 run
has), so the coded-shard and deadline logic is certified on every box —
not just where multiprocess collectives exist.

Every scenario is a deterministic ``PIO_FAULT_PLAN``-style plan armed
through `resilience/faults.py`; the degradation path exercised is the
REAL one (`parallel/coded.py` reconstruction inside the sharded
half-iteration / ring top-k), not a mock.
"""

import time

import numpy as np
import pytest

from predictionio_tpu.models.als import ALSConfig, ALSTrainer, rmse, train_als
from predictionio_tpu.obs import SHARD_DEGRADED_TOTAL
from predictionio_tpu.parallel import ParityExhausted, make_mesh
from predictionio_tpu.parallel.ingest import (
    ExchangeTornError,
    exchange_ratings_by_owner,
)
from predictionio_tpu.resilience import (
    Deadline,
    RetryPolicy,
    deadline_scope,
    faults,
)

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _disarm():
    faults.disarm()
    yield
    faults.disarm()


def _degraded_total() -> float:
    return sum(
        child.value() for _, child in SHARD_DEGRADED_TOTAL.children()
    )


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(0)
    n_u, n_i, nnz = 60, 40, 900
    u = rng.integers(0, n_u, nnz).astype(np.int32)
    i = rng.integers(0, n_i, nnz).astype(np.int32)
    v = rng.integers(1, 6, nnz).astype(np.float32)
    return u, i, v, n_u, n_i


@pytest.fixture(scope="module")
def mesh():
    m = make_mesh()
    assert m.size >= 2, "chaos suite needs the virtual multi-device mesh"
    return m


BASE = dict(rank=4, num_iterations=8, lam=0.1, seed=3)
CODED = dict(factor_placement="sharded", coded_shards=True)


@pytest.fixture(scope="module")
def clean(problem):
    u, i, v, n_u, n_i = problem
    factors = train_als((u, i, v), n_u, n_i, ALSConfig(**BASE))
    return factors, rmse(factors, u, i, v)


def _coded_train(problem, mesh, plan=None, **cfg_extra):
    u, i, v, n_u, n_i = problem
    cfg = ALSConfig(**BASE, **CODED, **cfg_extra)
    if plan:
        faults.arm(plan)
    tr = ALSTrainer((u, i, v), n_u, n_i, cfg, mesh=mesh)
    factors = tr.train()
    faults.disarm()
    return tr, factors, rmse(factors, u, i, v)


def test_clean_coded_matches_replicated(problem, mesh, clean):
    """No faults: the coded half is the plain sharded half (parity
    reconstruction multiplies by zero) and matches the replicated
    reference model."""
    ref, _ = clean
    tr, factors, _ = _coded_train(problem, mesh)
    assert tr.coded
    np.testing.assert_allclose(
        factors.user_factors, ref.user_factors, rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        factors.item_factors, ref.item_factors, rtol=1e-4, atol=1e-4
    )
    assert tr.shard_health.degraded_polls == 0


def test_straggler_parity_serve_rmse_within_1pct(problem, mesh, clean):
    """A deterministically delayed shard mid-sweep is served from
    parity: the sweep completes, the model stays within 1% RMSE of the
    clean train, and the degradation is booked."""
    _, r_clean = clean
    before = _degraded_total()
    tr, _, r = _coded_train(
        problem, mesh,
        plan="dist.shard_delay:nth=7,times=1,shard=2,delay=0.05",
    )
    assert r <= 1.01 * r_clean, (r, r_clean)
    assert tr.shard_health.degraded_polls == 1
    assert _degraded_total() == before + 1
    assert SHARD_DEGRADED_TOTAL.labels(shard="2").value() >= 1


def test_straggler_within_hop_budget_is_tolerated(problem, mesh, clean):
    """A shard whose lag stays inside the hop budget is waited for —
    no parity serve, bitwise the clean coded model."""
    ref, _ = clean
    tr, factors, _ = _coded_train(
        problem, mesh,
        plan="dist.shard_delay:nth=3,times=1,shard=1,delay=0.01",
        shard_hop_budget_s=5.0,
    )
    assert tr.shard_health.degraded_polls == 0
    np.testing.assert_allclose(
        factors.user_factors, ref.user_factors, rtol=1e-4, atol=1e-4
    )


def test_dead_worker_mid_sweep(problem, mesh, clean):
    """A worker killed mid-sweep stays dead (sticky): every remaining
    half serves its shard from parity and freezes its rows, the train
    COMPLETES, RMSE stays bounded, and the counter reflects each
    degraded half."""
    _, r_clean = clean
    before = _degraded_total()
    tr, _, r = _coded_train(
        problem, mesh, plan="dist.worker_kill:nth=15,shard=1",
    )
    assert r <= 1.01 * r_clean, (r, r_clean)
    assert tr.shard_health.killed == {1}
    # killed at poll 15 of 16 -> the last two halves degrade
    assert tr.shard_health.degraded_polls == 2
    assert _degraded_total() == before + 2


def test_two_holes_raise_parity_exhausted(problem, mesh):
    """A single parity block reconstructs ONE missing shard; two
    simultaneous holes must fail loudly, not serve garbage."""
    with pytest.raises(ParityExhausted, match="parity"):
        _coded_train(
            problem, mesh,
            plan="dist.worker_kill:nth=1,shard=2;"
                 "dist.shard_drop:nth=1,shard=1",
        )


def test_chaos_plan_is_deterministic(problem, mesh):
    """Identically-armed plans produce the identical degradation
    sequence and the identical model — replayability is the whole point
    of PIO_FAULT_PLAN."""
    plan = "dist.shard_drop:nth=5,times=1,shard=3"
    _, f1, r1 = _coded_train(problem, mesh, plan=plan)
    _, f2, r2 = _coded_train(problem, mesh, plan=plan)
    assert r1 == r2
    np.testing.assert_array_equal(f1.user_factors, f2.user_factors)


# -- torn exchange: retry then degrade --------------------------------------


def test_torn_exchange_retried_once_then_succeeds(tmp_path):
    """One torn publish is retried under a fresh nonce and succeeds;
    single-process short-circuit keeps the data identity."""
    r = np.arange(5, dtype=np.int64)
    c = np.arange(5, dtype=np.int64) * 2
    v = np.ones(5, np.float32)
    faults.arm("dist.exchange_torn:times=1")
    r2, c2, v2 = exchange_ratings_by_owner(
        r, c, v, np.zeros(5, np.int64), tmp_path, "t",
        retry=RetryPolicy(max_attempts=2, base_s=0.0, cap_s=0.0, seed=0),
    )
    assert faults.armed().counters()["dist.exchange_torn"]["fires"] == 1
    np.testing.assert_array_equal(r2, r)
    np.testing.assert_array_equal(c2, c)


def test_torn_exchange_past_retries_raises_typed_error(tmp_path):
    """Persistent tearing exhausts the retry budget and surfaces as
    ExchangeTornError — a bounded, typed failure, never a hang."""
    r = np.arange(3, dtype=np.int64)
    faults.arm("dist.exchange_torn")
    with pytest.raises(ExchangeTornError, match="retry budget"):
        exchange_ratings_by_owner(
            r, r, r.astype(np.float32), np.zeros(3, np.int64),
            tmp_path, "t2",
            retry=RetryPolicy(max_attempts=3, base_s=0.0, cap_s=0.0,
                              seed=0),
        )
    assert faults.armed().counters()["dist.exchange_torn"]["calls"] == 3


def test_torn_exchange_degrades_to_replicated_trainer(
    problem, mesh, monkeypatch, tmp_path, storage_memory
):
    """distributed_trainer's degrade wiring: when the sharded-COO
    exchange fails past retries, it falls back to the replicated gather
    path (correct model, degraded memory scaling) and books the
    degradation."""
    from predictionio_tpu.models.als import ALSTrainer
    from predictionio_tpu.obs import RESILIENCE_TOTAL
    from predictionio_tpu.parallel import ingest

    u, i, v, n_u, n_i = problem

    def torn(*a, **k):
        raise ExchangeTornError("injected: exchange torn past retries")

    monkeypatch.setattr(ALSTrainer, "distributed", staticmethod(torn))

    import datetime as dt

    es = storage_memory.get_event_store()
    utc = dt.timezone.utc
    from predictionio_tpu.storage.event import DataMap, Event

    for n in range(12):
        es.insert(
            Event(
                event="rate", entity_type="user", entity_id=f"u{n % 4}",
                target_entity_type="item", target_entity_id=f"i{n % 3}",
                properties=DataMap({"rating": float(1 + n % 5)}),
                event_time=dt.datetime(2020, 1, 1, tzinfo=utc),
            ),
            app_id=1,
        )
    before = RESILIENCE_TOTAL.labels(
        kind="dist.exchange_degraded"
    ).value()
    cfg = ALSConfig(**BASE, **CODED)
    tr = ingest.distributed_trainer(
        es, tmp_path, cfg, mesh, rating_property="rating",
        app_id=1, event_names=["rate"],
    )
    assert tr.cfg.factor_placement == "replicated"
    assert not tr.cfg.coded_shards
    assert RESILIENCE_TOTAL.labels(
        kind="dist.exchange_degraded"
    ).value() == before + 1
    # the degraded trainer still trains
    factors = tr.train()
    assert np.isfinite(factors.user_factors).all()


# -- ring top-k under deadline ----------------------------------------------


def test_ring_topk_deadline_degrade_returns_in_budget(mesh):
    """A shard whose injected lag dwarfs the request deadline is served
    from parity: the call returns WITHOUT waiting out the lag, the
    result is exact (parity current), and the degradation is booked."""
    from predictionio_tpu.ops.distributed_topk import ShardedTopK

    rng = np.random.default_rng(1)
    q = rng.normal(size=(4, 8)).astype(np.float32)
    v = rng.normal(size=(50, 8)).astype(np.float32)
    idx = ShardedTopK(v, mesh)
    idx(q, 7)  # warm the clean variant

    dense = q @ v.T
    ref = np.sort(dense, axis=1)[:, ::-1][:, :7]

    before = SHARD_DEGRADED_TOTAL.labels(shard="3").value()
    faults.arm("dist.shard_delay:shard=3,delay=30.0,times=1")
    t0 = time.perf_counter()
    with deadline_scope(Deadline.after(0.4)):
        vals, ixs = idx(q, 7)
    elapsed = time.perf_counter() - t0
    vals = np.asarray(vals)
    np.testing.assert_allclose(vals, ref, rtol=1e-5, atol=1e-5)
    assert int(np.asarray(ixs).max()) < 50  # padding rows never win
    # waited only the per-shard hop budget (0.4/d), not the 30 s lag;
    # generous ceiling absorbs first-compile of the coded variant
    assert elapsed < 15.0, elapsed
    assert SHARD_DEGRADED_TOTAL.labels(shard="3").value() == before + 1
    assert idx.summary()["degradedPolls"] >= 1


def test_ring_topk_killed_shard_sticky_across_requests(mesh):
    """A worker killed under chaos stays killed for the index's
    lifetime: subsequent requests keep serving its shard from parity
    without re-consulting the plan."""
    from predictionio_tpu.ops.distributed_topk import ShardedTopK

    rng = np.random.default_rng(2)
    q = rng.normal(size=(2, 6)).astype(np.float32)
    v = rng.normal(size=(24, 6)).astype(np.float32)
    idx = ShardedTopK(v, mesh)
    dense = q @ v.T
    ref = np.sort(dense, axis=1)[:, ::-1][:, :5]

    faults.arm("dist.worker_kill:shard=2,times=1")
    vals1, _ = idx(q, 5)
    faults.disarm()
    vals2, _ = idx(q, 5)  # no plan armed; kill must persist
    np.testing.assert_allclose(np.asarray(vals1), ref, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(vals2), ref, rtol=1e-5,
                               atol=1e-5)
    assert idx.health.killed == {2}
    assert idx.summary()["degradedPolls"] >= 2


def test_serving_template_distributed_topk_rides_request_deadline(mesh):
    """The recommendation template's distributedTopk knob: predict
    answers through the ring index, and the request deadline in scope
    (what serving's predict_json arms) is the hop budget — no plumbing
    in between."""
    from predictionio_tpu.controller.base import instantiate
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSModel, Query, recommendation_engine,
    )

    eng = recommendation_engine()

    def algo_with(extra):
        p = eng.params_from_variant({
            "datasource": {"params": {"app_name": "x"}},
            "algorithms": [
                {"name": "als", "params": {"rank": 4, **extra}}
            ],
        })
        return instantiate(ALSAlgorithm, p.algorithms[0][1])

    rng = np.random.default_rng(3)
    model = ALSModel(
        user_factors=rng.normal(size=(5, 4)).astype(np.float32),
        item_factors=rng.normal(size=(21, 4)).astype(np.float32),
        users=StringIndex.from_values([f"u{i}" for i in range(5)]),
        items=StringIndex.from_values([f"i{i}" for i in range(21)]),
        item_props={},
    )
    local = algo_with({}).predict(model, Query(user="u1", num=6))
    dist = algo_with({"distributedTopk": True})
    clean = dist.predict(model, Query(user="u1", num=6))
    assert [s.item for s in clean.item_scores] == [
        s.item for s in local.item_scores
    ]

    faults.arm("dist.shard_delay:shard=1,delay=30.0,times=1")
    t0 = time.perf_counter()
    with deadline_scope(Deadline.after(0.4)):
        degraded = dist.predict(model, Query(user="u1", num=6))
    elapsed = time.perf_counter() - t0
    assert [s.item for s in degraded.item_scores] == [
        s.item for s in local.item_scores
    ]
    assert elapsed < 15.0, elapsed
    assert model.sharded_topk_index().summary()["degradedPolls"] >= 1


def test_ring_warmup_covers_what_serving_dispatches(mesh):
    """The ring index's warm-up covers the shapes serving dispatches —
    a lone query is a one-row `batch_predict`, coalesced ones every pow2
    batch the padded batcher can produce, all at the pow2 k of the
    default num — so no such query after warm-up compiles (what
    `chip_smoke.py` checks on the chips for the sharded variant), and no
    query rides a k of its own."""
    from predictionio_tpu.controller.base import instantiate
    from predictionio_tpu.ops.distributed_topk import _sharded_callable
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSModel, Query, recommendation_engine,
    )

    p = recommendation_engine().params_from_variant({
        "datasource": {"params": {"app_name": "x"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 4, "distributedTopk": True}}],
    })
    algo = instantiate(ALSAlgorithm, p.algorithms[0][1])
    rng = np.random.default_rng(5)
    model = ALSModel(
        user_factors=rng.normal(size=(9, 4)).astype(np.float32),
        item_factors=rng.normal(size=(43, 4)).astype(np.float32),
        users=StringIndex.from_values([f"u{i}" for i in range(9)]),
        items=StringIndex.from_values([f"i{i}" for i in range(43)]),
        item_props={},
    )
    algo.warmup(model, max_batch=8)
    idx = model.sharded_topk_index()

    def executables():
        return {
            (k, coded): _sharded_callable(
                idx.mesh, idx.axis, k, coded)._cache_size()
            for k in (1, 4, 10, 16, 20) for coded in (False, True)
        }

    warmed = executables()
    assert all(n == 0 for (k, _), n in warmed.items() if k != 16)
    assert all(n > 0 for (k, _), n in warmed.items() if k == 16)
    for num in (9, 10, 16):
        assert len(algo.predict(
            model, Query(user="u1", num=num)).item_scores) == num
    for batch in (2, 4, 8):
        out = algo.batch_predict(
            model, [Query(user=f"u{i}", num=10) for i in range(batch)])
        assert all(len(r.item_scores) == 10 for r in out)
    assert executables() == warmed
