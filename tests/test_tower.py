"""pio-tower: run manifests, registry merge, convergence watchdog,
cluster aggregation, and the training console surfaces.

Covers the contracts docs/ARCHITECTURE.md "Tower" documents:

* manifest crash tolerance (atomic header, torn trailing line dropped,
  live-vs-final);
* registry merge semantics — counters sum EXACTLY, histograms add
  bucket-wise and the merged exposition is byte-for-byte what a single
  process that saw all observations renders (golden), gauges gain a
  ``{worker}`` label;
* a worker that dies mid-run leaves the aggregate consistent
  (real processes via ``multihost_harness.spawn_workers``);
* always-on sweep telemetry + watchdog aborts (NaN via the
  ``train.nan`` fault point, divergence, stall) with the manifest
  finalized and ``pio_train_aborts_total{reason}`` booked;
* the run_train/run_evaluation lifecycle, ``GET /debug/train``, the
  dashboard console, and the ``tools/runlog.py`` CLI.
"""

import json
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from predictionio_tpu.obs import get_registry, runlog, tower
from predictionio_tpu.obs.registry import (
    MetricsRegistry,
    merge_states,
    render_state,
)
from predictionio_tpu.resilience import faults

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _tower_isolation(tmp_path, monkeypatch):
    """Every test gets its own runs root and no leaked active session
    or armed fault plan."""
    monkeypatch.setenv("PIO_TPU_RUNLOG_DIR", str(tmp_path / "runs"))
    yield
    s = tower.active_session()
    if s is not None:
        s.finalize("failed", error="test leaked session")
    faults.disarm()


def _tiny_coo(seed=0, n_u=50, n_i=30, nnz=600):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, n_u, nnz).astype(np.int32),
        rng.integers(0, n_i, nnz).astype(np.int32),
        rng.integers(1, 6, nnz).astype(np.float32),
        n_u, n_i,
    )


def _train(cfg=None, session_kw=None, iid="run-x"):
    from predictionio_tpu.models.als import ALSConfig, ALSTrainer

    u, i, v, n_u, n_i = _tiny_coo()
    cfg = cfg or ALSConfig(rank=4, num_iterations=4, lam=0.1)
    s = tower.TowerSession(iid, **(session_kw or {})).start()
    try:
        ALSTrainer((u, i, v), n_u, n_i, cfg).train()
        s.finalize("completed")
    except BaseException as e:
        s.finalize_error(e)
        raise
    return runlog.read_manifest(runlog.runs_root() / iid)


# -- manifest file contract --------------------------------------------------


def test_manifest_header_atomic_and_roundtrip(tmp_path):
    m = runlog.RunManifest("abc", meta={"sweepsPlanned": 2},
                           root=tmp_path)
    assert not list(tmp_path.glob("**/*.tmp"))  # tmp renamed away
    m.sweep(1, 0.5, {"user_half": 0.3, "item_half": 0.2}, loss=1.5)
    view = runlog.read_manifest(tmp_path / "abc")
    assert view["live"] and view["header"]["sweepsPlanned"] == 2
    m.finalize("completed", sweeps=1)
    view = runlog.read_manifest(tmp_path / "abc")
    assert not view["live"]
    assert view["final"]["status"] == "completed"
    assert view["sweeps"][0]["phases"]["user_half"] == 0.3


def test_manifest_torn_trailing_line_dropped(tmp_path):
    m = runlog.RunManifest("torn", root=tmp_path)
    m.sweep(1, 0.1, {"user_half": 0.1})
    m.close()
    path = tmp_path / "torn" / "run.jsonl"
    with open(path, "a") as f:
        f.write('{"kind": "sweep", "i": 2, "seconds"')  # crash mid-append
    view = runlog.read_manifest(path)
    assert len(view["sweeps"]) == 1 and view["live"]


def test_manifest_finalize_idempotent(tmp_path):
    m = runlog.RunManifest("idem", root=tmp_path)
    m.finalize("aborted", reason="nan_factors")
    m.finalize("completed")  # must not overwrite the verdict
    view = runlog.read_manifest(tmp_path / "idem")
    assert view["final"]["status"] == "aborted"


def test_manifest_unwritable_root_degrades_silently(tmp_path):
    target = tmp_path / "blocked"
    target.write_text("a file where the dir should be")
    m = runlog.RunManifest("x", root=target / "sub")
    m.sweep(1, 0.1, {})  # must not raise
    m.finalize("completed")


def test_diff_runs_phase_table(tmp_path):
    for iid, scale in (("A", 1.0), ("B", 3.0)):
        m = runlog.RunManifest(iid, root=tmp_path)
        for i in range(1, 3):
            m.sweep(i, 0.1 * scale, {"user_half": 0.06 * scale,
                                     "item_half": 0.04 * scale})
        m.finalize("completed")
    d = runlog.diff_runs(
        runlog.read_manifest(tmp_path / "A"),
        runlog.read_manifest(tmp_path / "B"),
    )
    assert d["sweepMeanRatio"] == pytest.approx(3.0, rel=1e-3)
    by_phase = {r["phase"]: r for r in d["phases"]}
    assert by_phase["user_half"]["ratio"] == pytest.approx(3.0, rel=1e-3)
    # ordered by absolute delta: user_half gained more than item_half
    assert d["phases"][0]["phase"] == "user_half"


# -- registry merge semantics ------------------------------------------------


def _seeded_registries():
    """Two worker registries plus ONE single-process registry that saw
    every observation — the golden reference for the merge."""
    regs, ops, lat = [], [], []
    for _ in range(3):
        r = MetricsRegistry()
        ops.append(r.counter("m_ops_total", "ops", labels=("kind",)))
        lat.append(r.histogram("m_lat_seconds", "lat",
                               buckets=(0.01, 0.1, 1.0)))
        regs.append(r)
    w0, w1, golden = regs
    # dyadic values: float addition is exact in ANY order, so the
    # merged _sum renders byte-identically to the golden accumulation
    obs_w0 = [0.0078125, 0.0625, 0.5]
    obs_w1 = [0.0625, 0.09375, 2.0, 0.0078125]
    for v in obs_w0:
        lat[0].child().observe(v)
    for v in obs_w1:
        lat[1].child().observe(v)
    for v in obs_w0 + obs_w1:
        lat[2].child().observe(v)
    ops[0].labels(kind="a").inc(3)
    ops[1].labels(kind="a").inc(4)
    ops[1].labels(kind="b").inc(2)
    ops[2].labels(kind="a").inc(7)
    ops[2].labels(kind="b").inc(2)
    return w0, w1, golden


def test_merge_counters_sum_and_histograms_bucketwise_golden():
    w0, w1, golden = _seeded_registries()
    merged = merge_states([(0, w0.dump_state()), (1, w1.dump_state())])
    # byte-for-byte: the merged exposition IS the single-process one
    assert render_state(merged) == golden.render_prometheus()


def test_merge_percentiles_rederive_exactly():
    w0, w1, golden = _seeded_registries()
    merged = merge_states([(0, w0.dump_state()), (1, w1.dump_state())])
    fam = next(f for f in merged["families"]
               if f["name"] == "m_lat_seconds")
    h = fam["children"][0]["hist"]
    # rebuild a histogram from the merged buckets and compare the
    # derived percentiles against the single-process instrument
    ref = golden.histogram("m_lat_seconds", "lat").child()
    snap = {"counts": h["counts"], "sum": h["sum"], "count": h["count"]}
    for q in (50, 95, 99):
        assert ref.percentile(q) == pytest.approx(
            ref.percentile(q, snap), abs=0.0,
        )


def test_merge_gauges_labeled_per_worker():
    regs = []
    for w in range(2):
        r = MetricsRegistry()
        r.gauge("m_depth", "d").child().set(10 * (w + 1))
        regs.append((w, r.dump_state()))
    text = render_state(merge_states(regs))
    assert 'm_depth{worker="0"} 10' in text
    assert 'm_depth{worker="1"} 20' in text


def test_merge_bucket_mismatch_raises():
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    r0.histogram("m_h", "h", buckets=(0.1, 1.0)).child().observe(0.5)
    r1.histogram("m_h", "h", buckets=(0.2, 2.0)).child().observe(0.5)
    with pytest.raises(ValueError, match="bucket ladder"):
        merge_states([(0, r0.dump_state()), (1, r1.dump_state())])


def test_merge_exemplars_keep_newest():
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    for r, ex in ((r0, "t-old"), (r1, "t-new")):
        r.histogram("m_h", "h", buckets=(1.0,)).child().observe(
            0.5, exemplar=ex
        )
        time.sleep(0.01)
    text = render_state(
        merge_states([(0, r0.dump_state()), (1, r1.dump_state())])
    )
    assert 't-new' in text and 't-old' not in text


# -- publisher / aggregator --------------------------------------------------


def test_aggregator_merges_live_local_plus_published(tmp_path):
    local, remote = MetricsRegistry(), MetricsRegistry()
    for r in (local, remote):
        r.counter("agg_total", "t")
    local.counter("agg_total", "t").child().inc(5)
    remote.counter("agg_total", "t").child().inc(7)
    pub = tower.RegistryPublisher(tmp_path, worker=1, registry=remote)
    pub.publish()
    agg = tower.ClusterAggregator(tmp_path, local_worker=0,
                                  registry=local)
    assert agg.workers_seen() == [0, 1]
    text = agg.render()
    assert "agg_total 12" in text
    # local keeps moving between scrapes; remote stays at its snapshot
    local.counter("agg_total", "t").child().inc(1)
    assert "agg_total 13" in agg.render()


def test_aggregator_dead_worker_keeps_last_snapshot(tmp_path):
    local, remote = MetricsRegistry(), MetricsRegistry()
    for r in (local, remote):
        r.counter("agg2_total", "t")
    remote.counter("agg2_total", "t").child().inc(3)
    tower.RegistryPublisher(tmp_path, worker=1, registry=remote).publish()
    agg = tower.ClusterAggregator(tmp_path, local_worker=0,
                                  registry=local)
    assert "agg2_total 3" in agg.render()
    # "death": the file goes unreadable — the cached snapshot stands
    (tmp_path / "tower-metrics-w1.json").write_text("{torn")
    assert "agg2_total 3" in agg.render()


def test_spawn_workers_publish_merge_with_mid_run_death(tmp_path):
    """Two REAL processes publish per-cycle snapshots through the
    coordination dir; worker 1 dies hard after 2 of 5 cycles.  The
    merged aggregate must equal worker 0's full traffic plus worker
    1's last published state — exact, not approximate."""
    sys.path.insert(0, str(REPO_ROOT / "tools"))
    from multihost_harness import spawn_workers

    coord = tmp_path / "coord"
    results = spawn_workers(
        2,
        lambda p: [p, 2, coord, 5, 1, 2],
        worker=REPO_ROOT / "tests" / "_tower_worker.py",
        timeout=120,
    )
    assert results[0].ok, (results[0].stdout, results[0].stderr)
    assert not results[1].ok  # died on purpose, no WORKER_OK marker
    snaps = {}
    for f in sorted(coord.glob("tower-metrics-w*.json")):
        doc = json.loads(f.read_text())
        snaps[doc["worker"]] = doc
    assert set(snaps) == {0, 1}
    assert snaps[0]["seq"] == 5 and snaps[1]["seq"] == 2
    merged = merge_states([
        (w, snaps[w]["state"]) for w in sorted(snaps)
    ])
    fam = next(f for f in merged["families"]
               if f["name"] == "tower_test_ops_total")
    # worker 0: 5 cycles x 1; worker 1: 2 cycles x 2 before dying
    assert fam["children"][0]["value"] == 5 * 1 + 2 * 2
    hist = next(f for f in merged["families"]
                if f["name"] == "tower_test_lat_seconds")
    assert hist["children"][0]["hist"]["count"] == 7
    gauges = {
        dict(tuple(kv) for kv in c["labels"])["worker"]: c["value"]
        for f in merged["families"] if f["name"] == "tower_test_depth"
        for c in f["children"]
    }
    assert gauges == {"0": 5.0, "1": 102.0}


# -- sweep telemetry + watchdog ---------------------------------------------


def test_sweep_telemetry_manifest_complete():
    before = tower.TRAIN_SWEEPS_TOTAL.child().value()
    view = _train(iid="sweeps")
    assert tower.TRAIN_SWEEPS_TOTAL.child().value() == before + 4
    assert len(view["sweeps"]) == 4
    for s in view["sweeps"]:
        total = sum(s["phases"].values())
        assert total == pytest.approx(s["seconds"], rel=0.05)
        assert s["loss"] is not None
        assert s["compileDelta"] >= 0
    # loss trajectory is monotone-ish downward on this tiny problem
    losses = [s["loss"] for s in view["sweeps"]]
    assert losses[-1] < losses[0]
    assert view["final"]["status"] == "completed"
    assert view["final"]["sweepSecondsTotal"] > 0
    # the trainer declared its budget after the header was written
    assert runlog.summarize(view)["sweepsPlanned"] == 4


def test_record_sweep_books_its_own_time(monkeypatch):
    """What `record_sweep` itself takes lies between two sweeps: the
    final record carries it as `bookkeepingSeconds`, so a pause that
    lands there (here: a slow device sampling) is still accounted."""
    import time

    def slow_sampling():
        time.sleep(0.05)

    monkeypatch.setattr(tower, "_device_high_water", slow_sampling)
    view = _train(iid="bookkeeping")
    final = view["final"]
    assert final["bookkeepingSeconds"] >= 4 * 0.05
    assert final["bookkeepingSeconds"] < final["wallSeconds"]


def test_sweep_loss_cadence_and_off():
    from predictionio_tpu.models.als import ALSConfig

    view = _train(cfg=ALSConfig(rank=4, num_iterations=4, lam=0.1,
                                loss_every=2), iid="every2")
    assert [s.get("loss") is not None for s in view["sweeps"]] == [
        False, True, False, True,
    ]
    view = _train(cfg=ALSConfig(rank=4, num_iterations=2, lam=0.1,
                                loss_every=0), iid="lossoff")
    assert all(s.get("loss") is None for s in view["sweeps"])


def test_loss_every_validation():
    from predictionio_tpu.models.als import ALSConfig

    with pytest.raises(ValueError, match="loss_every"):
        ALSConfig(loss_every=-1)


def test_traced_mode_collects_side_qualified_phases(monkeypatch):
    monkeypatch.setenv("PIO_TPU_TRACE_ALS", "1")
    view = _train(iid="traced")
    phases = view["sweeps"][0]["phases"]
    for key in ("user.gather", "user.gram", "user.solve",
                "item.gather", "item.gram", "item.solve"):
        assert key in phases, phases


def test_watchdog_nan_fault_typed_abort():
    from predictionio_tpu.models.als import ALSConfig

    reg = get_registry()
    before = reg.counter(
        "pio_train_aborts_total", "", labels=("reason",)
    ).labels(reason="nan_factors").value()
    faults.arm("train.nan:nth=2,times=1")
    with pytest.raises(tower.ConvergenceError) as ei:
        _train(cfg=ALSConfig(rank=4, num_iterations=6, lam=0.1),
               iid="nanrun")
    assert ei.value.reason == "nan_factors"
    view = runlog.read_manifest(runlog.runs_root() / "nanrun")
    assert view["final"]["status"] == "aborted"
    assert view["final"]["reason"] == "nan_factors"
    assert len(view["sweeps"]) == 2  # aborted ON the poisoned sweep
    assert any(e["event"] == "watchdog_abort" for e in view["events"])
    after = reg.counter(
        "pio_train_aborts_total", "", labels=("reason",)
    ).labels(reason="nan_factors").value()
    assert after == before + 1


def test_watchdog_divergence_window():
    wd = tower.Watchdog(divergence_window=3, divergence_ratio=2.0)
    wd.check(1, 0.1, 1.0, True)
    wd.check(2, 0.1, 1.5, True)
    with pytest.raises(tower.ConvergenceError) as ei:
        wd.check(3, 0.1, 2.5, True)  # 3 rising, 2.5x >= 2x
    assert ei.value.reason == "divergence"
    # non-monotone window never trips
    wd2 = tower.Watchdog(divergence_window=3, divergence_ratio=2.0)
    for i, loss in enumerate((1.0, 3.0, 2.9, 3.5, 3.4, 4.0)):
        wd2.check(i, 0.1, loss, True)


def test_watchdog_divergence_resets_per_source():
    """Two candidates' loss sequences must not concatenate into a fake
    ramp (the eval-session case)."""
    s = tower.TowerSession("src", watchdog=tower.Watchdog(
        divergence_window=2, divergence_ratio=1.5)).start()
    try:
        s.record_sweep(0.1, {}, loss=1.0, source="trainer-A")
        # same numbers from a NEW trainer: window must restart
        s.record_sweep(0.1, {}, loss=2.0, source="trainer-B")
        s.record_sweep(0.1, {}, loss=1.0, source="trainer-C")
    finally:
        s.finalize("completed")


def test_watchdog_stall_limit():
    wd = tower.Watchdog(stall_limit_s=0.5)
    wd.check(1, 0.4, None, True)
    with pytest.raises(tower.ConvergenceError) as ei:
        wd.check(2, 0.6, None, True)
    assert ei.value.reason == "stalled_sweep"


def test_watchdog_nan_loss_reason():
    wd = tower.Watchdog()
    with pytest.raises(tower.ConvergenceError) as ei:
        wd.check(1, 0.1, float("nan"), True)
    assert ei.value.reason == "nan_loss"


def test_shard_events_land_in_manifest():
    """Coded-shard degradation (in-process 8-virtual-device mesh) is
    forwarded by ShardHealth into the active session's manifest."""
    import jax

    from predictionio_tpu.models.als import ALSConfig, ALSTrainer
    from predictionio_tpu.parallel import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    u, i, v, n_u, n_i = _tiny_coo(n_u=64, n_i=40)
    mesh = make_mesh()
    faults.arm("dist.shard_delay:nth=3,times=1,shard=1,delay=0.01")
    s = tower.TowerSession("coded").start()
    try:
        tr = ALSTrainer(
            (u, i, v), n_u, n_i,
            ALSConfig(rank=4, num_iterations=4, lam=0.1,
                      factor_placement="sharded", coded_shards=True),
            mesh=mesh,
        )
        tr.train()
        s.finalize("completed")
    except BaseException as e:
        s.finalize_error(e)
        raise
    finally:
        faults.disarm()
    view = runlog.read_manifest(runlog.runs_root() / "coded")
    degr = [e for e in view["events"] if e["event"] == "shard_degraded"]
    assert degr and degr[0]["shard"] == 1
    assert any(s.get("shardEvents") for s in view["sweeps"])


# -- workflow lifecycle ------------------------------------------------------


@pytest.fixture()
def ctx(tmp_path):
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.storage import Storage, reset_storage

    s = Storage(env={"PIO_TPU_HOME": str(tmp_path / "home")})
    reset_storage(s)
    yield WorkflowContext(storage=s, mode="Training")
    reset_storage(None)


def test_run_train_writes_manifest(ctx):
    from fixtures import Algo0, DataSource0, IdParams
    from predictionio_tpu.controller import EngineParams, SimpleEngine
    from predictionio_tpu.workflow import run_train

    e = SimpleEngine(DataSource0, Algo0)
    iid = run_train(e, EngineParams(algorithms=[("", IdParams(id=3))]),
                    ctx=ctx, engine_variant="v1")
    view = runlog.read_manifest(runlog.runs_root() / iid)
    assert view is not None and not view["live"]
    assert view["header"]["runKind"] == "train"
    assert view["header"]["engineVariant"] == "v1"
    assert view["final"]["status"] == "completed"
    assert view["final"]["trainRunSeconds"] > 0
    assert tower.active_session() is None


def test_run_train_failure_finalizes_failed(ctx):
    from fixtures import Algo0, DataSource0, IdParams
    from predictionio_tpu.controller import EngineParams, SimpleEngine
    from predictionio_tpu.workflow import run_train

    e = SimpleEngine(DataSource0, Algo0)
    bad = EngineParams(
        data_source=("", IdParams(id=1, error=True)),
        algorithms=[("", IdParams(id=3))],
    )
    with pytest.raises(ValueError):
        run_train(e, bad, ctx=ctx)
    views = runlog.list_runs()
    assert views and views[0]["final"]["status"] == "failed"
    assert tower.active_session() is None


def test_run_evaluation_candidate_records(ctx):
    from fixtures import (
        Algo0,
        DataSource0,
        IdParams,
        Preparator0,
        Serving0,
    )
    from predictionio_tpu.controller import (
        AverageMetric,
        Engine,
        EngineParams,
        Evaluation,
    )
    from predictionio_tpu.workflow import run_evaluation

    class AlgoIdMetric(AverageMetric):
        def calculate_point(self, q, p, a):
            return float(p.algo_id)

    def params(algo_id):
        return EngineParams(
            data_source=("", IdParams(id=1)),
            preparator=("", IdParams(id=2)),
            algorithms=[("a0", IdParams(id=algo_id))],
            serving=("", IdParams(id=4)),
        )

    engine = Engine(DataSource0, Preparator0, {"a0": Algo0}, Serving0)
    ev = Evaluation(engine, AlgoIdMetric(), output_path=None)
    eval_id, res = run_evaluation(
        ev, [params(3), params(9)], ctx=ctx, fast_eval=False,
    )
    assert res.best_score == 9.0
    view = runlog.read_manifest(runlog.runs_root() / eval_id)
    assert view["header"]["runKind"] == "eval"
    assert len(view["candidates"]) == 2
    assert {c["i"] for c in view["candidates"]} == {0, 1}
    assert {c["score"] for c in view["candidates"]} == {3.0, 9.0}
    assert all(c["seconds"] >= 0 for c in view["candidates"])
    assert view["final"]["status"] == "completed"


# -- surfaces ----------------------------------------------------------------


def test_debug_train_endpoint_and_console(storage_memory):
    import urllib.request

    from predictionio_tpu.server.dashboard import DashboardServer

    m = runlog.RunManifest("surf1", meta={"sweepsPlanned": 2})
    m.sweep(1, 0.5, {"user_half": 0.3, "item_half": 0.2}, loss=2.0)
    m.sweep(2, 0.4, {"user_half": 0.2, "item_half": 0.2}, loss=1.0)
    m.finalize("completed", sweeps=2)
    live = runlog.RunManifest("surf2-live")
    live.sweep(1, 0.1, {"user_half": 0.1})

    srv = DashboardServer(storage_memory, port=0)
    srv.start_background()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(f"{base}/debug/train", timeout=10) as r:
            payload = json.loads(r.read().decode())
        by_id = {r["instanceId"]: r for r in payload["runs"]}
        assert by_id["surf1"]["status"] == "completed"
        assert by_id["surf1"]["firstLoss"] == 2.0
        assert by_id["surf2-live"]["live"] is True
        with urllib.request.urlopen(f"{base}/train.html", timeout=10) as r:
            html = r.read().decode()
        assert "surf1" in html and "training console" in html.lower()
        with urllib.request.urlopen(f"{base}/", timeout=10) as r:
            assert "/train.html" in r.read().decode()
    finally:
        srv.stop()
        live.close()


def test_debug_train_shows_active_session():
    s = tower.TowerSession("live-now", sweeps_planned=10).start()
    try:
        s.record_sweep(0.25, {"user_half": 0.15, "item_half": 0.1},
                       loss=1.2)
        payload = tower.train_payload()
        a = payload["active"]
        assert a["instanceId"] == "live-now"
        assert a["sweep"] == 1 and a["sweepsPlanned"] == 10
        assert a["etaSeconds"] == pytest.approx(0.25 * 9, rel=0.2)
        assert a["lastSweep"]["phases"]["user_half"] == 0.15
    finally:
        s.finalize("completed")
    assert tower.train_payload()["active"] is None


def test_cluster_renderer_on_chief_metrics(tmp_path):
    """A chief session with a coordination dir serves MERGED /metrics
    while live, and restores the local view at finalize."""
    from predictionio_tpu import obs

    remote = MetricsRegistry()
    remote.counter("pio_train_sweeps_total", "x")
    remote.counter("pio_train_sweeps_total", "x").child().inc(100)
    tower.RegistryPublisher(tmp_path, worker=1,
                            registry=remote).publish()
    base = tower.TRAIN_SWEEPS_TOTAL.child().value()
    s = tower.TowerSession("chief", worker=0, n_workers=2,
                           coord_dir=tmp_path).start()
    try:
        text = obs.render_prometheus()
        assert f"pio_train_sweeps_total {base + 100:g}" in text
    finally:
        s.finalize("completed")
    text = obs.render_prometheus()
    assert f"pio_train_sweeps_total {base:g}" in text


def test_runlog_cli(tmp_path, capsys):
    sys.path.insert(0, str(REPO_ROOT / "tools"))
    import runlog as runlog_cli

    for iid in ("cli-A", "cli-B"):
        m = runlog.RunManifest(iid, root=tmp_path)
        m.sweep(1, 0.2, {"user_half": 0.1, "item_half": 0.1}, loss=1.0)
        m.finalize("completed", sweeps=1)
    assert runlog_cli.main(
        ["--root", str(tmp_path), "list"]) == 0
    out = capsys.readouterr().out
    assert "cli-A" in out and "cli-B" in out
    assert runlog_cli.main(
        ["--root", str(tmp_path), "summarize", "cli-A"]) == 0
    assert json.loads(capsys.readouterr().out)["instanceId"] == "cli-A"
    assert runlog_cli.main(
        ["--root", str(tmp_path), "diff", "cli-A", "cli-B", "--json"]
    ) == 0
    d = json.loads(capsys.readouterr().out)
    assert d["sweepMeanRatio"] == pytest.approx(1.0)
    with pytest.raises(SystemExit):
        runlog_cli.main(["--root", str(tmp_path), "summarize", "nope"])


# -- span journal worker stamping -------------------------------------------


def test_span_journal_worker_stamp(tmp_path):
    from predictionio_tpu.obs.trace import Tracer

    t = Tracer(journal_dir=tmp_path)
    t.set_process_index(3)
    t.record("x.span", 0.01)
    t.close()
    path = tmp_path / f"spans-w3-{os.getpid()}.jsonl"
    assert path.exists(), list(tmp_path.iterdir())
    rec = json.loads(path.read_text().splitlines()[0])
    assert rec["worker"] == 3 and rec["name"] == "x.span"


def test_span_journal_env_worker_stamp(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_TPU_PROCESS_INDEX", "2")
    from predictionio_tpu.obs.trace import Tracer

    t = Tracer(journal_dir=tmp_path)
    t.record("y.span", 0.01)
    t.close()
    assert (tmp_path / f"spans-w2-{os.getpid()}.jsonl").exists()
