"""pio-pulse smoke: timeline decomposition + loadgen + profiler e2e.

The pulse analogue of `tools/obs_smoke.py`: boots a REAL trained
EngineServer (+ EventServer for the ingest family), fires concurrent
closed-loop load through `tools/loadgen.py` (the same multi-process
workers the QPS@SLO sweep uses), and asserts the decomposition contract
the gate and the operator rely on:

1. ``segments_complete`` — every serving segment (parse/auth/
   queue_wait/batch_wait/device/serialize/write) appears in
   ``/metrics`` with the SAME count (the success path books all seven,
   every time), and the event-ingest family carries its four.
2. ``segments_reconcile`` — the per-segment sums add up to the
   end-to-end latency histogram's sum within tolerance: the timeline
   is an accounting identity, not a sampling estimate (the handler
   window additionally covers body read + socket write, so the segment
   sum sits slightly ABOVE the predict-window sum, never below).
3. ``saturation_metrics`` — the batcher's batch-size histogram and
   leader/follower role counters moved under concurrent load.
4. ``profile_artifact`` — ``GET /debug/profile?seconds=S`` during live
   traffic produces a non-empty jax.profiler trace directory under
   ``$PIO_TPU_HOME/telemetry/profiles/``.
5. ``flight_decomposes`` — the flight recorder's worst-N entries carry
   ``segmentsMs`` + ``modelFreshnessSec`` attrs, so a slow query
   explains itself from ``/status`` alone.

Usage::

    python tools/pulse_smoke.py --out pulse_smoke.json
"""

from __future__ import annotations

import argparse
import datetime as dt
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

UTC = dt.timezone.utc


def _get_json(url, timeout=90):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="pulse_smoke.json")
    ap.add_argument("--seed", type=int, default=20260804)
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--duration", type=float, default=1.5)
    ap.add_argument("--mode", choices=("process", "thread"),
                    default="process")
    ap.add_argument("--profile-seconds", type=float, default=0.6)
    args = ap.parse_args(argv)

    # a smoke must not pollute the operator's real telemetry home
    os.environ.setdefault(
        "PIO_TPU_HOME", tempfile.mkdtemp(prefix="pulse_smoke_home_")
    )

    import numpy as np

    import loadgen
    from predictionio_tpu import obs
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.obs.timeline import (
        EVENT_SEGMENTS,
        EVENTS_SEGMENT_SECONDS,
        MICROBATCH_BATCH_SIZE,
        MICROBATCH_ROLE_TOTAL,
        SERVE_SEGMENTS,
        SERVE_SEGMENT_SECONDS,
    )
    from predictionio_tpu.server import EngineServer, ServerConfig
    from predictionio_tpu.server.event_server import (
        EventServer, EventServerConfig,
    )
    from predictionio_tpu.storage import AccessKey, DataMap, Event
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
    app = md.app_insert("pulsesmoke")
    key = md.access_key_insert(AccessKey(key="", appid=app.id))
    es = storage.get_event_store()
    es.init_channel(app.id)

    with stage("train_tiny_engine"):
        rng = np.random.default_rng(args.seed)
        n_users, n_items = 24, 16
        evs = [
            Event(event="rate", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item", target_entity_id=f"i{i}",
                  properties=DataMap(
                      {"rating": float(rng.integers(1, 6))}),
                  event_time=dt.datetime(2020, 1, 1, tzinfo=UTC))
            for u in range(n_users)
            for i in rng.choice(n_items, size=5, replace=False)
        ]
        es.insert_batch(evs, app_id=app.id)
        ctx = WorkflowContext(storage=storage)
        engine = recommendation_engine()
        ep = engine.params_from_variant({
            "datasource": {"params": {"appName": "pulsesmoke"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "numIterations": 2, "lambda": 0.1}}],
        })
        iid = run_train(engine, ep, ctx=ctx, engine_variant="pulse.json")

    with stage("boot_servers"):
        ev = EventServer(storage, EventServerConfig(port=0))
        ev.start_background()
        ev_base = f"http://127.0.0.1:{ev.config.port}"
        srv = EngineServer(
            engine, ep, iid, ctx=ctx,
            config=ServerConfig(port=0, microbatch="auto"),
            engine_variant="pulse.json",
        )
        srv.start_background()
        q_base = f"http://127.0.0.1:{srv.config.port}"
        invariants["batcher_active"] = srv.batcher is not None

    def seg_counts(family, segments):
        return {
            s: family.labels(segment=s).snapshot() for s in segments
        }

    with stage("concurrent_load"):
        payloads = [
            json.dumps({"user": f"u{u}", "num": 3})
            for u in range(n_users)
        ]
        res = loadgen.run_load(
            f"{q_base}/queries.json", payloads, args.concurrency,
            args.duration, mode=args.mode,
        )
        invariants["load_completed_without_errors"] = (
            res["errors"] == 0 and res["completed"] >= args.concurrency
        )

    with stage("ingest_traffic"):
        for k in range(4):
            req = urllib.request.Request(
                f"{ev_base}/events.json?accessKey={key}",
                data=json.dumps({
                    "event": "rate", "entityType": "user",
                    "entityId": f"u{k}", "targetEntityType": "item",
                    "targetEntityId": "i1",
                    "properties": {"rating": 4.0},
                }).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=15) as r:
                assert r.status == 201

    with stage("segments_complete"):
        # the handler books its timeline AFTER the reply bytes go out;
        # wait for the counts to go quiet before reading them
        prev = None
        for _ in range(100):
            cur = {
                s: SERVE_SEGMENT_SECONDS.labels(segment=s)
                .snapshot()["count"]
                for s in SERVE_SEGMENTS
            }
            if cur == prev:
                break
            prev = cur
            time.sleep(0.05)
        serve_snap = seg_counts(SERVE_SEGMENT_SECONDS, SERVE_SEGMENTS)
        counts = {s: snap["count"] for s, snap in serve_snap.items()}
        invariants["serve_segments_all_present"] = all(
            c > 0 for c in counts.values()
        )
        # the success path books all seven segments, every request
        invariants["serve_segment_counts_equal"] = (
            len(set(counts.values())) == 1
            and counts["parse"] >= res["completed"]
        )
        ev_snap = seg_counts(EVENTS_SEGMENT_SECONDS, EVENT_SEGMENTS)
        invariants["events_segments_all_present"] = all(
            snap["count"] >= 4 for snap in ev_snap.values()
        )

    with stage("segments_reconcile"):
        seg_total = sum(s["sum"] for s in serve_snap.values())
        lat_snap = obs.QUERY_LATENCY.child().snapshot()
        # the handler window (segments) covers the predict window
        # (latency histogram) plus body read + socket write: the sum
        # must sit at or slightly above e2e, never materially below
        invariants["segment_sum_covers_e2e"] = (
            seg_total >= lat_snap["sum"] * 0.95
        )
        # ... and the per-request EXTRA (body read + socket write +
        # handler JSON decode) stays at loopback-overhead scale: a
        # double-booked segment would inflate this by a device-call
        # mean, a leak by seconds
        extra_ms = (
            (seg_total - lat_snap["sum"])
            / max(lat_snap["count"], 1) * 1e3
        )
        invariants["segment_overhead_bounded"] = extra_ms <= 3.0

    with stage("saturation_metrics"):
        bs = MICROBATCH_BATCH_SIZE.child().snapshot()
        roles = {
            dict(k).get("role"): c.value()
            for k, c in MICROBATCH_ROLE_TOTAL.children()
        }
        invariants["batch_size_histogram_moved"] = bs["count"] > 0
        # every request of the event-loop edge is "dispatched": the
        # batcher's dispatcher ran its device call and completed it by
        # callback, no request thread parked
        invariants["roles_cover_requests"] = (
            roles.get("dispatched", 0) >= res["completed"] > 0
        )

    with stage("profile_artifact"):
        # capture during live traffic so the xplane has content: a
        # background thread keeps firing queries over the window
        stop = threading.Event()

        def pepper():
            k = 0
            while not stop.is_set():
                try:
                    req = urllib.request.Request(
                        f"{q_base}/queries.json",
                        data=payloads[k % len(payloads)].encode(),
                        headers={"Content-Type": "application/json"},
                        method="POST",
                    )
                    urllib.request.urlopen(req, timeout=15).read()
                except Exception:
                    pass
                k += 1

        t = threading.Thread(target=pepper, daemon=True)
        t.start()
        try:
            code, prof = _get_json(
                f"{q_base}/debug/profile?seconds={args.profile_seconds}"
            )
        finally:
            stop.set()
        t.join(timeout=10)
        invariants["profile_200"] = code == 200
        pdir = Path(prof.get("dir", ""))
        invariants["profile_artifact_nonempty"] = (
            pdir.is_dir()
            and prof.get("totalBytes", 0) > 0
            and len(prof.get("files", [])) > 0
        )

    with stage("flight_decomposes"):
        _, status = _get_json(f"{q_base}/")
        worst = status["xray"]["flight"]["worst"]
        invariants["flight_has_records"] = len(worst) > 0
        attrs_ok = bool(worst) and all(
            "segmentsMs" in w.get("attrs", {})
            and "modelFreshnessSec" in w.get("attrs", {})
            for w in worst
        )
        invariants["flight_attrs_decompose"] = attrs_ok
        mb = status.get("microbatch", {})
        invariants["status_microbatch_snapshot"] = (
            {"batches", "requests", "maxBatchSeen", "dispatched",
             "queueDepth"} <= set(mb)
        )

    srv.stop()
    ev.stop()

    rec = {
        "metric": "pulse_smoke",
        "seed": args.seed,
        "concurrency": args.concurrency,
        "loadgen_mode": args.mode,
        "completed": res["completed"],
        "qps": round(res["qps"], 1),
        "p99_ms": round(res["p99_ms"], 3),
        "stages": stages,
        "invariants": invariants,
        "ok": all(invariants.values()),
    }
    Path(args.out).write_text(json.dumps(rec, indent=2) + "\n")
    print(json.dumps(rec, indent=2))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
