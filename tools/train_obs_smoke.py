"""pio-tower smoke: the training-observability contract, end to end.

The tower analogue of ``tools/obs_smoke.py`` / ``xray_smoke.py``: runs
a tiny REAL train through ``run_train`` (recommendation template over
in-memory storage) and asserts the evidence chain an operator relies
on when a training run misbehaves:

1. ``manifest_complete``   — the run manifest exists, has one sweep
   record per ALS iteration with per-phase times and a loss value,
   and a ``final`` record with status ``completed``.
2. ``phase_sums_reconcile``— per sweep, the phase decomposition sums
   to the sweep wall time within 2%; and setup + sweeps + tail
   reconcile with the ``train.run`` span wall time within 2% — the
   manifest explains where the train's time went, it doesn't guess.
3. ``watchdog_nan_abort``  — a second train with the ``train.nan``
   fault point armed dies with a TYPED ``ConvergenceError``
   (reason ``nan_factors``), the manifest is finalized as
   ``aborted`` ON the poisoned sweep, and
   ``pio_train_aborts_total{reason}`` is booked.
4. ``cluster_merge``       — a simulated second worker publishes a
   registry snapshot through a coordination dir; the chief session's
   ``/metrics`` rendering shows counters equal to the SUM of both
   expositions and per-worker gauge labels, then reverts at finalize.
5. ``runlog_cli``          — ``tools/runlog.py summarize`` and
   ``diff`` parse the manifests this very run produced.

Usage::

    python tools/train_obs_smoke.py --out train_obs_smoke.json
"""

from __future__ import annotations

import argparse
import datetime as dt
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

UTC = dt.timezone.utc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="train_obs_smoke.json")
    ap.add_argument("--seed", type=int, default=20260805)
    args = ap.parse_args(argv)

    tmp = tempfile.mkdtemp(prefix="pio-tower-smoke-")
    os.environ["PIO_TPU_RUNLOG_DIR"] = str(Path(tmp) / "runs")

    import numpy as np

    from predictionio_tpu import obs
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.obs import runlog, tower
    from predictionio_tpu.obs.registry import MetricsRegistry
    from predictionio_tpu.resilience import faults
    from predictionio_tpu.storage import DataMap, Event
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine,
    )
    from predictionio_tpu.workflow import run_train

    stages: dict[str, float] = {}
    invariants: dict[str, bool] = {}
    detail: dict = {}

    class stage:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            self.t0 = time.perf_counter()

        def __exit__(self, *exc):
            stages[self.name] = round(time.perf_counter() - self.t0, 3)

    storage = Storage(env={
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEMDB",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_SOURCES_MEMDB_TYPE": "memory",
    })
    md = storage.get_metadata()
    app = md.app_insert("towersmoke")
    es = storage.get_event_store()
    es.init_channel(app.id)
    rng = np.random.default_rng(args.seed)
    evs = [
        Event(event="rate", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{i}",
              properties=DataMap({"rating": float(rng.integers(1, 6))}),
              event_time=dt.datetime(2020, 1, 1, tzinfo=UTC))
        for u in range(8) for i in rng.choice(10, size=5, replace=False)
    ]
    es.insert_batch(evs, app_id=app.id)
    ctx = WorkflowContext(storage=storage)
    engine = recommendation_engine()
    n_iter = 4
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "towersmoke"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 4, "numIterations": n_iter, "lambda": 0.1}}],
    })

    iids = []
    with stage("train_twice"):
        for _ in range(2):
            iids.append(run_train(engine, ep, ctx=ctx,
                                  engine_variant="tower.json"))

    with stage("manifest_complete"):
        view = runlog.read_manifest(runlog.runs_root() / iids[0])
        ok = view is not None and not view["live"]
        ok = ok and view["final"]["status"] == "completed"
        ok = ok and len(view["sweeps"]) == n_iter
        ok = ok and all(
            s.get("phases") and s.get("loss") is not None
            for s in view["sweeps"]
        )
        invariants["manifest_complete"] = bool(ok)
        detail["summary"] = runlog.summarize(view)

    with stage("phase_sums_reconcile"):
        worst_sweep = 0.0
        for s in view["sweeps"]:
            gap = abs(sum(s["phases"].values()) - s["seconds"])
            worst_sweep = max(worst_sweep, gap / s["seconds"])
        final = view["final"]
        run_s = final["trainRunSeconds"]
        accounted = (
            final["setupSeconds"] + final["sweepSecondsTotal"]
            + final["bookkeepingSeconds"] + final["tailSeconds"]
        )
        run_gap = abs(accounted - run_s) / run_s
        invariants["sweep_phase_sums_within_2pct"] = worst_sweep <= 0.02
        invariants["train_run_reconciles_within_2pct"] = run_gap <= 0.02
        detail["reconciliation"] = {
            "worstSweepGap": round(worst_sweep, 5),
            "trainRunSeconds": run_s,
            "accountedSeconds": round(accounted, 6),
            "trainRunGap": round(run_gap, 5),
        }

    with stage("watchdog_nan_abort"):
        reg = obs.get_registry()
        aborts = reg.counter(
            "pio_train_aborts_total", "", labels=("reason",)
        ).labels(reason="nan_factors")
        before = aborts.value()
        faults.arm("train.nan:nth=2,times=1")
        typed, generic = False, None
        try:
            run_train(engine, ep, ctx=ctx, engine_variant="tower.json")
        except tower.ConvergenceError as e:
            typed = e.reason == "nan_factors"
        except Exception as e:  # noqa: BLE001 — the smoke reports it
            generic = f"{type(e).__name__}: {e}"
        finally:
            faults.disarm()
        aborted = [
            v for v in runlog.list_runs()
            if (v["final"] or {}).get("status") == "aborted"
        ]
        ok = (
            typed and generic is None and len(aborted) == 1
            and aborted[0]["final"]["reason"] == "nan_factors"
            and len(aborted[0]["sweeps"]) == 2
            and aborts.value() == before + 1
        )
        invariants["watchdog_nan_typed_abort"] = bool(ok)
        if generic:
            detail["watchdogUnexpected"] = generic

    with stage("cluster_merge"):
        coord = Path(tmp) / "coord"
        remote = MetricsRegistry()
        rc = remote.counter("pio_train_sweeps_total", "x")
        rc.child().inc(1000)
        rg = remote.gauge("pio_train_last_sweep_seconds", "x")
        rg.child().set(9.5)
        tower.RegistryPublisher(coord, worker=1,
                                registry=remote).publish()
        local = tower.TRAIN_SWEEPS_TOTAL.child().value()
        session = tower.TowerSession(
            "merge-demo", worker=0, n_workers=2, coord_dir=coord,
        ).start()
        try:
            merged_text = obs.render_prometheus()
        finally:
            session.finalize("completed")
        local_text = obs.render_prometheus()
        want = f"pio_train_sweeps_total {local + 1000:g}"
        invariants["merged_counters_sum_workers"] = want in merged_text
        invariants["merged_gauges_worker_labeled"] = (
            'pio_train_last_sweep_seconds{worker="1"} 9.5' in merged_text
        )
        invariants["local_metrics_restored_after_run"] = (
            f"pio_train_sweeps_total {local:g}" in local_text
        )

    with stage("runlog_cli"):
        env = {**os.environ}
        r1 = subprocess.run(
            [sys.executable, str(ROOT / "tools" / "runlog.py"),
             "summarize", iids[0]],
            capture_output=True, text=True, env=env, timeout=60,
        )
        r2 = subprocess.run(
            [sys.executable, str(ROOT / "tools" / "runlog.py"),
             "diff", iids[0], iids[1], "--json"],
            capture_output=True, text=True, env=env, timeout=60,
        )
        ok = r1.returncode == 0 and r2.returncode == 0
        if ok:
            summ = json.loads(r1.stdout)
            d = json.loads(r2.stdout)
            ok = (
                summ["instanceId"] == iids[0]
                and summ["sweeps"] == n_iter
                and d["sweepMeanRatio"] is not None
                and {r["phase"] for r in d["phases"]}
                >= {"user_half", "item_half"}
            )
        invariants["runlog_cli_summarize_and_diff"] = bool(ok)
        if not ok:
            detail["cliStderr"] = (r1.stderr + r2.stderr)[-500:]

    out = {
        "ok": all(invariants.values()),
        "invariants": invariants,
        "stages": stages,
        "detail": detail,
        "runsRoot": os.environ["PIO_TPU_RUNLOG_DIR"],
    }
    Path(args.out).write_text(json.dumps(out, indent=1) + "\n")
    print(json.dumps({"ok": out["ok"], "invariants": invariants},
                     indent=1))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
