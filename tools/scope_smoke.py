"""pio-scope smoke: the always-on profiler contract under real load.

Boots a REAL trained `EngineServer` (microbatch on, eventloop edge) on
an ephemeral port, floods it with concurrent queries, and asserts what
an operator debugging "where is the CPU going" relies on:

1. ``roles_present`` — ``GET /debug/pprof`` answers collapsed-stack
   text whose root frames name >= 2 registered thread roles (the
   eventloop and the microbatch dispatcher at minimum): the profile is
   attributed, not an anonymous thread soup.
2. ``lock_wait_nonzero`` — the flood contends the microbatch monitor,
   so ``pio_lock_wait_seconds{lock="microbatch"}`` books a nonzero
   count: the contention lens sees real contention.
3. ``flamegraph_renders`` — the folded text renders to the
   self-contained flamegraph page (the /prof.html + profcat surface).
4. ``flight_join`` — the worst-N flight records carry
   ``dominantStacks`` sampled from each request's wall window: the
   slow-request view joins the profiler ring.
5. ``overhead_budget`` — an interleaved A/B (profiler on vs off,
   alternating rounds over the same live server) keeps the on-arm p50
   within 5% of the off-arm (with a 0.5 ms noise floor — a 1-core CI
   box jitters more than a 67 Hz sampler costs), and the self-measured
   ``pio_profile_overhead_ratio`` stays under 5%.

Usage::

    python tools/scope_smoke.py --out scope_smoke.json
"""

from __future__ import annotations

import argparse
import concurrent.futures
import datetime as dt
import json
import statistics
import sys
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

UTC = dt.timezone.utc


def _get(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def _post_json(url, payload, timeout=15):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="scope_smoke.json")
    ap.add_argument("--seed", type=int, default=20260807)
    ap.add_argument("--flood-s", type=float, default=2.0,
                    help="concurrent-flood window (default 2s)")
    ap.add_argument("--ab-queries", type=int, default=120,
                    help="sequential queries per A/B round")
    ap.add_argument("--ab-rounds", type=int, default=3,
                    help="interleaved on/off round pairs")
    args = ap.parse_args(argv)

    import numpy as np

    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.obs import get_registry, scope
    from predictionio_tpu.server import EngineServer, ServerConfig
    from predictionio_tpu.storage import DataMap, Event
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine,
    )
    from predictionio_tpu.workflow import run_train

    stages: dict[str, float] = {}
    invariants: dict[str, bool] = {}
    detail: dict[str, object] = {}

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
    app = md.app_insert("scopesmoke")
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
            "datasource": {"params": {"appName": "scopesmoke"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 4, "numIterations": 2, "lambda": 0.1}}],
        })
        iid = run_train(engine, ep, ctx=ctx, engine_variant="scope.json")

    with stage("boot_server"):
        # an explicit smoke of the profiler wins over ambient opt-outs
        scope.set_enabled(True)
        srv = EngineServer(
            engine, ep, iid, ctx=ctx,
            config=ServerConfig(port=0, microbatch="on"),
            engine_variant="scope.json",
        )
        srv.start_background()
        base = f"http://127.0.0.1:{srv.config.port}"
        scope.ensure_started()

    def query_once(k: int) -> float:
        t0 = time.perf_counter()
        code, _ = _post_json(f"{base}/queries.json",
                             {"user": f"u{k % 6}", "num": 2})
        assert code == 200
        return time.perf_counter() - t0

    with stage("flood"):
        deadline = time.perf_counter() + args.flood_s
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            def worker(w):
                n = 0
                while time.perf_counter() < deadline:
                    query_once(w * 1000 + n)
                    n += 1
                return n

            completed = sum(pool.map(worker, range(8)))
        detail["flood_queries"] = completed
        assert completed > 0

    with stage("check_roles"):
        code, text = _get(f"{base}/debug/pprof?seconds=60")
        assert code == 200
        folded = scope.parse_folded(text)
        roles = {stack.split(";", 1)[0] for stack in folded}
        detail["roles"] = sorted(roles)
        detail["profile_samples"] = sum(folded.values())
        invariants["roles_present"] = (
            len(roles - {"main", "other"}) >= 2
            and "eventloop" in roles
        )

    with stage("check_lock_wait"):
        snap = scope.LOCK_WAIT_SECONDS.labels(lock="microbatch") \
            .snapshot()
        detail["microbatch_lock_waits"] = int(snap["count"])
        detail["microbatch_lock_wait_s"] = round(snap["sum"], 4)
        invariants["lock_wait_nonzero"] = snap["count"] > 0

    with stage("check_flamegraph"):
        html = scope.flamegraph_html(text, title="scope smoke")
        invariants["flamegraph_renders"] = (
            "<script>" in html and "FOLDED" in html
            and "eventloop" in html
        )

    with stage("check_flight_join"):
        code, body = _get(f"{base}/debug/flight")
        assert code == 200
        worst = json.loads(body)["worst"]
        joined = [w for w in worst if w.get("dominantStacks")]
        detail["flight_records"] = len(worst)
        detail["flight_joined"] = len(joined)
        invariants["flight_join"] = len(joined) > 0
        if joined:
            detail["flight_example"] = joined[0]["dominantStacks"][0]

    with stage("overhead_ab"):
        # interleaved rounds kill drift: a box that slows mid-smoke
        # hits both arms equally.  Medians-of-rounds, not one pooled
        # p50, so one noisy round can't carry the verdict.
        p50_on: list[float] = []
        p50_off: list[float] = []
        for _ in range(args.ab_rounds):
            for arm, acc in (("on", p50_on), ("off", p50_off)):
                if arm == "on":
                    scope.set_enabled(True)
                    scope.ensure_started()
                else:
                    scope.set_enabled(False)  # stops the sampler
                lats = [query_once(k) for k in range(args.ab_queries)]
                acc.append(statistics.median(lats))
        scope.set_enabled(True)
        scope.ensure_started()
        on_ms = statistics.median(p50_on) * 1e3
        off_ms = statistics.median(p50_off) * 1e3
        delta_ms = on_ms - off_ms
        budget_ms = max(0.05 * off_ms, 0.5)  # 5% with a noise floor
        detail["ab_p50_on_ms"] = round(on_ms, 3)
        detail["ab_p50_off_ms"] = round(off_ms, 3)
        detail["ab_delta_ms"] = round(delta_ms, 3)
        detail["ab_budget_ms"] = round(budget_ms, 3)
        invariants["overhead_budget"] = delta_ms <= budget_ms
        ratio = scope.get_profiler().overhead_ratio()
        detail["overhead_ratio"] = round(ratio, 5)
        invariants["overhead_ratio_under_5pct"] = ratio < 0.05

    srv.stop()
    # keep the registry text in the artifact trail: the eager catalog
    # means every family shows even on a quiet process
    families = get_registry().render_prometheus()
    detail["scope_families_present"] = all(
        f in families for f in (
            "pio_cpu_thread_samples_total",
            "pio_profile_overhead_ratio",
            "pio_lock_wait_seconds",
            "pio_lock_hold_seconds",
        )
    )
    invariants["scope_families_present"] = \
        bool(detail["scope_families_present"])

    ok = all(invariants.values())
    doc = {
        "ok": ok,
        "invariants": invariants,
        "stages_s": stages,
        "detail": detail,
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
    }
    Path(args.out).write_text(json.dumps(doc, indent=1) + "\n")
    print(json.dumps({"scope_smoke": "PASS" if ok else "FAIL",
                      **invariants}))
    if not ok:
        print(f"# details in {args.out}", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
