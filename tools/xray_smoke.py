"""pio-xray smoke: the compiler/device observability contract, end to
end through a real deployment.

The x-ray analogue of ``tools/obs_smoke.py``: trains a tiny engine with
``PIO_TPU_TRACE_ALS=1`` (so the per-phase ALS spans exist), boots a
real ``EngineServer``, then **forces a serving-path recompile** (same
fn, new static ``k``) and asserts the whole story an operator relies
on during a shape-churn incident:

1. ``jit_counters``        — ``pio_jit_compiles_total{fn}`` on
   ``/metrics`` increments when the recompile is forced, and training
   booked compiles for the ALS half-iterations.
2. ``recompile_ring``      — ``GET /debug/xray`` parses, and its
   recompile ring contains the forced event with the signature delta
   that triggered it (``k: 2 -> 3``-shaped change).
3. ``device_gauges``       — ``pio_device_memory_bytes`` exists for
   every device even on the CPU backend (live-array fallback).
4. ``flight_recorder``     — the slowest request's flight record links
   a latency-histogram exemplar trace id to its full span tree
   (``serve.query`` present), i.e. /metrics -> flight record is one
   join on the trace id.
5. ``bench_gate``          — ``tools/bench_gate.py`` passes a flat
   synthetic history and fails an injected 3x regression (the gate
   gates, with the real CLI).

Usage::

    python tools/xray_smoke.py --out xray_smoke.json
"""

from __future__ import annotations

import argparse
import datetime as dt
import json
import os
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# must precede any predictionio_tpu/jax import in this process: the
# ALS phase tracer reads it at train time
os.environ.setdefault("PIO_TPU_TRACE_ALS", "1")

UTC = dt.timezone.utc


def _get(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _post_json(url, payload, headers=None, timeout=15):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, dict(r.headers), json.loads(r.read().decode())


def _metric_value(text: str, name: str, **labels) -> float:
    """Sum of samples of ``name`` whose labels include ``labels``."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith("#") or not line.startswith(name):
            continue
        head, _, value = line.rpartition(" ")
        if head.split("{")[0] != name:
            continue
        if all(f'{k}="{v}"' in head for k, v in labels.items()):
            total += float(value)
            seen = True
    return total if seen else float("nan")


def _bench_gate_checks(tmpdir: Path) -> dict:
    """Drive the real bench_gate CLI on synthetic trajectories."""
    hist = tmpdir / "hist.jsonl"
    base = {
        "metric": "smoke_train_seconds", "unit": "s",
        "vs_baseline": None, "platform": "tpu", "scale": 1.0,
        "fenced": True,
        # the CLI stamps candidates with the live core count; history
        # must carry the same nproc or the gate keys them apart
        "nproc": os.cpu_count() or 1,
    }
    with open(hist, "w") as f:
        for v in (100.0, 101.0, 99.5, 100.5, 98.9, 100.2):
            f.write(json.dumps({
                **base, "value": v,
                "recorded_at": "2026-08-01T00:00:00Z",
            }) + "\n")
    flat = tmpdir / "flat.json"
    flat.write_text(json.dumps({**base, "value": 102.0}))
    reg = tmpdir / "reg.json"
    reg.write_text(json.dumps({**base, "value": 300.0}))
    gate = str(ROOT / "tools" / "bench_gate.py")

    def run(*extra):
        return subprocess.run(
            [sys.executable, gate, "--history", str(hist), *extra],
            capture_output=True, text=True, timeout=60,
        ).returncode

    return {
        "bench_gate_flat_passes": run("--check", str(flat)) == 0,
        "bench_gate_3x_fails": run("--check", str(reg)) == 1,
        "bench_gate_empty_allowed": subprocess.run(
            [sys.executable, gate, "--history",
             str(tmpdir / "absent.jsonl"), "--check", "--allow-empty"],
            capture_output=True, text=True, timeout=60,
        ).returncode == 0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="xray_smoke.json")
    ap.add_argument("--seed", type=int, default=20260804)
    args = ap.parse_args(argv)

    import numpy as np

    from predictionio_tpu import obs
    from predictionio_tpu.obs import xray
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.server import EngineServer, ServerConfig
    from predictionio_tpu.storage import DataMap, Event
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine,
    )
    from predictionio_tpu.workflow import run_train

    stages: dict[str, float] = {}
    invariants: dict[str, bool] = {}

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
    app = md.app_insert("xraysmoke")
    es = storage.get_event_store()
    es.init_channel(app.id)

    with stage("train_tiny_engine"):
        rng = np.random.default_rng(args.seed)
        evs = [
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item", target_entity_id=f"i{i}",
                  properties=DataMap(
                      {"rating": float(rng.integers(1, 6))}),
                  event_time=dt.datetime(2020, 1, 1, tzinfo=UTC))
            for u in range(6) for i in rng.choice(8, size=4,
                                                  replace=False)
        ]
        es.insert_batch(evs, app_id=app.id)
        ctx = WorkflowContext(storage=storage)
        engine = recommendation_engine()
        ep = engine.params_from_variant({
            "datasource": {"params": {"appName": "xraysmoke"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "numIterations": 2, "lambda": 0.1}}],
        })
        iid = run_train(engine, ep, ctx=ctx, engine_variant="xray.json")
        # training drove the instrumented ALS halves; with the phase
        # tracer armed, the als.* spans exist for flight records later
        als_stats = {
            fn: st for fn, st in xray.jit_stats().items()
            if fn.startswith("als.")
        }
        invariants["training_tracked_als_jits"] = any(
            st["signatures"] >= 1 for st in als_stats.values()
        )
        invariants["training_booked_backend_compiles"] = any(
            st["backendCompiles"] >= 1 for st in als_stats.values()
        )

    with stage("boot_server"):
        srv = EngineServer(
            engine, ep, iid, ctx=ctx,
            config=ServerConfig(port=0, microbatch="off"),
            engine_variant="xray.json",
        )
        srv.start_background()
        base = f"http://127.0.0.1:{srv.config.port}"

    with stage("forced_recompile"):
        # k is a static arg of the top-k scorers: num=2 then num=3
        # (pow2: k=2 -> 4) is the classic mid-traffic shape churn
        _code, before = _get(f"{base}/metrics")
        scorer = "topk.batch_topk_scores_t"
        n_before = _metric_value(
            before, "pio_jit_compiles_total", fn=scorer
        )
        for k in range(12):
            num = 2 if k < 6 else 3
            code, _hdrs, body = _post_json(
                f"{base}/queries.json",
                {"user": f"u{k % 6}", "num": num},
            )
            assert code == 200 and len(body["itemScores"]) == num
        _code, after = _get(f"{base}/metrics")
        n_after = _metric_value(
            after, "pio_jit_compiles_total", fn=scorer
        )
        invariants["metrics_compile_counter_incremented"] = (
            n_after >= n_before + 1
        )

    with stage("debug_xray"):
        code, text = _get(f"{base}/debug/xray")
        invariants["debug_xray_200"] = code == 200
        payload = json.loads(text)  # parseability IS the assertion
        ring = payload["recompiles"]
        invariants["recompile_ring_parseable"] = isinstance(ring, list)
        forced = [
            e for e in ring
            if e["fn"] == scorer and e["kind"] == "recompile"
        ]
        deltas_ok = False
        for e in forced:
            ch = (e.get("delta") or {}).get("changed", [])
            deltas_ok = deltas_ok or any(
                c["from"] != c["to"] for c in ch
            )
        invariants["forced_recompile_in_ring_with_delta"] = deltas_ok
        invariants["monitoring_installed"] = (
            payload["monitoring"]["installed"]
            and payload["monitoring"]["installError"] is None
        )

    with stage("device_gauges"):
        xray.sample_devices_once()
        _code, text = _get(f"{base}/metrics")
        v = _metric_value(text, "pio_device_memory_bytes")
        invariants["device_memory_gauges_present"] = v == v  # not NaN
        code, text = _get(f"{base}/debug/xray")
        samples = json.loads(text)["devices"]["samples"]
        invariants["device_samples_in_payload"] = (
            len(samples) >= 1 and all(s["stats"] for s in samples)
        )

    with stage("flight_recorder"):
        code, st = _get(f"{base}/")
        status = json.loads(st)
        flight = status["xray"]["flight"]
        exemplars = status["xray"]["latencyExemplars"]
        invariants["flight_records_admitted"] = (
            flight["admissions"] >= 1 and len(flight["worst"]) >= 1
        )
        invariants["exemplars_present"] = len(exemplars) >= 1
        # the cross-link: an exemplar trace id from the latency
        # histogram resolves to a flight record whose span tree holds
        # the serve.query span — /metrics -> flight record, one join
        _code, text = _get(f"{base}/debug/xray")
        records = {
            r["traceId"]: r
            for r in json.loads(text)["flight"]["worst"]
        }
        linked = False
        for ex in exemplars:
            rec = records.get(ex["traceId"])
            if rec and any(
                s["name"] == "serve.query" for s in rec["spans"]
            ):
                linked = True
        invariants["exemplar_links_flight_span_tree"] = linked
        # the EXEMPLAR comment lines make the trace id greppable
        # straight off a /metrics scrape
        _code, text = _get(f"{base}/metrics")
        invariants["exemplar_greppable_on_metrics"] = any(
            ex["traceId"] in text for ex in exemplars
        )

    with stage("bench_gate"):
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            invariants.update(_bench_gate_checks(Path(td)))

    srv.stop()
    obs.get_tracer().close()

    rec = {
        "metric": "xray_smoke",
        "seed": args.seed,
        "stages": stages,
        "invariants": invariants,
        "ok": all(invariants.values()),
    }
    Path(args.out).write_text(json.dumps(rec, indent=2) + "\n")
    print(json.dumps(rec, indent=2))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
