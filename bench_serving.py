"""Serving-path benchmark: query latency + throughput on the deployed
engine hot path (reference tracks avgServingSec/lastServingSec on its
status page but publishes no targets; the working expectation for a rec
server is a sub-100 ms query path, SURVEY §7 hard-part 5).

Measures predict_json end-to-end (JSON decode -> device top-k -> JSON
encode) after warmup.  Single-threaded by default; ``--threads N`` adds
the concurrent-load measurement the reference's per-request-detach
serving model implies (`CreateServer.scala:437,464`): N client threads
hammer the same model and the line reports per-request p50/p99 plus
aggregate QPS — the number that exposes GIL + single-device-queue
serialization.  Prints ONE JSON line per measurement like bench.py.

Percentiles come from the SAME pio-obs latency histograms production
exposes on ``/metrics`` (``predictionio_tpu.obs.Histogram`` — log-
spaced buckets, linear in-bucket interpolation), so a bench number and
a Grafana panel are the same estimator; each line also carries
``exact_p50_ms`` (np.percentile over the raw samples) for cross-run
A/B comparisons at sub-bucket resolution.  The ``--http`` mode
additionally reports the SERVER's own histogram view
(``server_p50_ms`` from the deployed engine's status JSON).

Usage: python bench_serving.py [--items 100000] [--rank 64] [--n 200]
       [--threads 16]
       [--tenants N] [--microbatch-max 64]

The ``--tenants N`` sweep serves N co-resident tenants through the
pio-confluence shared batcher (suffix ``_mt`` on every record, tenant
count in ``scale``); tenants are force-loaded and asserted resident
before measurement, and any mid-sweep eviction stamps the affected
point ``cold_reload`` so a cold reload can never silently pose as a
steady-state number.  Fenced records stamp ``nproc`` — bench_gate
keys rolling baselines on it, so numbers from different box shapes
never judge each other.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--items", type=int, default=100_000)
    ap.add_argument("--users", type=int, default=10_000)
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--n", type=int, default=200, help="timed queries")
    ap.add_argument("--num", type=int, default=10, help="top-k per query")
    ap.add_argument("--batch", type=int, default=0,
                    help="also measure batch_predict at this batch size "
                    "(the eval-path throughput)")
    ap.add_argument("--threads", type=int, default=0,
                    help="also measure under N concurrent client "
                    "threads (p50/p99 per request + aggregate QPS)")
    ap.add_argument("--http", action="store_true",
                    help="with --threads: drive a REAL deployed "
                    "EngineServer over HTTP (full product path: JSON "
                    "-> auth-free route -> micro-batcher -> device -> "
                    "JSON), A/B'ing microbatch on vs off")
    ap.add_argument("--concurrency", type=int, default=0,
                    help="closed-loop load at ONE concurrency point "
                    "via tools/loadgen.py (multi-process workers over "
                    "real HTTP; reports QPS + p50/p99 + per-segment "
                    "breakdown)")
    ap.add_argument("--sweep",
                    help="comma-separated concurrency sweep (e.g. "
                    "1,4,16,64): per-point records plus the "
                    "serving_qps_at_slo summary the bench gate judges")
    ap.add_argument("--duration-s", type=float, default=3.0,
                    help="measured window per sweep point (default 3)")
    ap.add_argument("--slo-ms", type=float, default=25.0,
                    help="p99 SLO for the QPS@SLO summary (default 25)")
    ap.add_argument("--loadgen-mode", choices=("process", "thread"),
                    default="process",
                    help="loadgen worker kind (process = no client "
                    "GIL, the honest default)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    metavar="QPS",
                    help="with --concurrency: open-loop Poisson "
                    "arrivals at this aggregate rate instead of "
                    "closed-loop (coordinated-omission-free "
                    "latencies; see tools/loadgen.py)")
    ap.add_argument("--append-history", action="store_true",
                    help="append the sweep's fenced records to "
                    "BENCH_HISTORY.jsonl (the canonical trajectory "
                    "tools/bench_gate.py gates on)")
    ap.add_argument("--retrieval", choices=("exact", "int8", "ivf"),
                    default="exact",
                    help="pio-scout serving retrieval mode for the "
                    "measured algorithm (two-stage quantized candidate "
                    "+ exact rerank); non-exact modes suffix the "
                    "fenced metric keys so exact and ANN trajectories "
                    "never share a baseline")
    ap.add_argument("--candidate-factor", type=int, default=10,
                    help="ANN shortlist width in units of k")
    ap.add_argument("--nprobe", type=int, default=8,
                    help="ivf: coarse clusters scanned per query")
    ap.add_argument("--clustered-catalog", action="store_true",
                    help="draw item factors from a mixture of "
                    "Gaussians (tools/bench_ann.py's generator — the "
                    "shape trained ALS tables have) instead of pure "
                    "noise; what makes an IVF recall/latency trade "
                    "representative")
    ap.add_argument("--microbatch-max", type=int, default=64,
                    help="claim-size cap for the continuous batcher "
                    "(ServerConfig.microbatch_max).  Smaller caps trade "
                    "a few %% of batching efficiency for smaller turn "
                    "quanta — on a 1-core box the p99 tail is turn-"
                    "aligned, so capping the turn can buy back the SLO")
    ap.add_argument("--tenants", type=int, default=0, metavar="N",
                    help="pio-hive: stage N independent tenant models "
                    "in ONE multi-tenant server and drive the "
                    "--sweep/--concurrency load round-robin across "
                    "them; fenced records get the _mt suffix and are "
                    "keyed by tenant count (scale=N — the same "
                    "record-keying convention --items uses for "
                    "catalog size)")
    ap.add_argument("--profile", action="store_true",
                    help="pio-scope: run the always-on sampling "
                    "profiler through the sweep and stamp each point "
                    "with its per-role CPU split + dominant stacks "
                    "(the server runs in this process, so the split "
                    "is the exact server-side attribution)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()
    if args.http and args.threads <= 0:
        ap.error("--http requires --threads N")

    import jax

    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSModel,
    )

    rng = np.random.default_rng(0)
    if args.clustered_catalog:
        sys.path.insert(0, str(Path(__file__).parent / "tools"))
        from bench_ann import clustered_factors

        item_f = clustered_factors(args.items, args.rank, rng)
    else:
        item_f = rng.normal(size=(args.items, args.rank)).astype(
            np.float32
        )
    model = ALSModel(
        user_factors=rng.normal(size=(args.users, args.rank)).astype(
            np.float32
        ),
        item_factors=item_f,
        users=StringIndex([f"u{i}" for i in range(args.users)]),
        items=StringIndex([f"i{i}" for i in range(args.items)]),
        item_props={},
    )
    algo = ALSAlgorithm()
    if args.retrieval != "exact":
        algo.params = algo.params_class(
            retrieval=args.retrieval,
            candidate_factor=args.candidate_factor,
            nprobe=args.nprobe,
        )
    algo.warmup(model)

    from predictionio_tpu.obs import Histogram
    from predictionio_tpu.templates.recommendation import Query

    # timed loop over random users, observed into the SAME histogram
    # shape serving exports (raw samples kept for the exact cross-check)
    users = rng.integers(0, args.users, args.n)
    hist = Histogram()
    lat = np.empty(args.n)
    for j, u in enumerate(users):
        t0 = time.perf_counter()
        r = algo.predict(model, Query(user=f"u{u}", num=args.num))
        lat[j] = time.perf_counter() - t0
        hist.observe(lat[j])
        assert len(r.item_scores) == args.num
    pcts = hist.percentiles([50, 99])
    p50, p99 = pcts[50], pcts[99]
    exact_p50 = float(np.percentile(lat, 50))
    if args.verbose:
        print(
            f"# {args.items:,} items rank {args.rank}: "
            f"p50 {p50*1e3:.2f}ms p99 {p99*1e3:.2f}ms "
            f"qps {1.0/hist.mean():.0f}",
            file=sys.stderr,
        )
    serving_rec = {
        "metric": "serving_query_p50_ms",
        "value": round(p50 * 1e3, 3),
        "unit": "ms",
        "exact_p50_ms": round(exact_p50 * 1e3, 3),
        "retrieval": args.retrieval,
        "vs_baseline": round(100.0 / (p50 * 1e3), 3),
    }
    print(json.dumps(serving_rec))
    # canonical per-PR summary (tools/bench_gate.py schema): the
    # serving number nests under "serving" so it never clobbers the
    # train record bench.py wrote at the top level.  predict() results
    # are host-materialized per query, so these timings are
    # device-complete (fenced) by construction.
    try:
        sys.path.insert(0, str(Path(__file__).parent / "tools"))
        import bench_gate

        from predictionio_tpu.obs import scope as _scope

        bench_gate.write_pr_summary(
            {
                **serving_rec,
                "platform": jax.default_backend(),
                "scale": None,
                "items": args.items,
                "rank": args.rank,
                "fenced": True,
                "profiler_enabled": _scope.profiler_running(),
            },
            key="serving",
        )
    except Exception as e:
        print(f"# WARNING: could not write bench summary: {e}",
              file=sys.stderr)

    if args.threads > 0 and not args.http:
        import concurrent.futures

        from predictionio_tpu.server.microbatch import MicroBatcher

        per_thread = max(args.n // args.threads, 20)
        users_c = rng.integers(0, args.users, (args.threads, per_thread))

        def run_clients(predict_one):
            def client(tid):
                lats = np.empty(per_thread)
                for j in range(per_thread):
                    t0 = time.perf_counter()
                    r = predict_one(
                        Query(user=f"u{users_c[tid, j]}", num=args.num)
                    )
                    lats[j] = time.perf_counter() - t0
                    assert len(r.item_scores) == args.num
                return lats

            with concurrent.futures.ThreadPoolExecutor(args.threads) as ex:
                # warm the pool/executables: ONE request per thread
                # (not a full untimed workload)
                list(ex.map(
                    lambda t: predict_one(
                        Query(user=f"u{users_c[t, 0]}", num=args.num)
                    ),
                    range(args.threads),
                ))
                if batcher is not None:
                    batcher.reset_stats()  # counters = timed traffic only
                t0 = time.perf_counter()
                lats = np.concatenate(
                    list(ex.map(client, range(args.threads)))
                )
                wall = time.perf_counter() - t0
            return lats, wall

        # A: per-request device dispatch (requests serialize on the
        # single device queue); B: continuous micro-batching (the
        # serving default when the algorithm batch-predicts).  Counters
        # are reset after warmup so the JSON describes timed traffic.
        batcher = None

        def make_modes():
            nonlocal batcher
            yield ("serving_concurrent_query_p99_ms",
                   lambda q: algo.predict(model, q))
            batcher = MicroBatcher(
                lambda qs: algo.batch_predict(model, qs), max_batch=64,
                pad_batches=True,
            )
            # pre-compile the pow2 batch executables the padded batcher
            # can dispatch (the serving warmup obligation)
            bsz = 1
            while bsz <= min(64, args.threads * 2):
                algo.batch_predict(
                    model,
                    [Query(user="u0", num=args.num)] * bsz,
                )
                bsz *= 2
            yield ("serving_microbatched_query_p99_ms", batcher.submit)

        for metric, predict_one in make_modes():
            lats, wall = run_clients(predict_one)
            # locked snapshot: the counters are mutated under the
            # batcher's condition variable
            mb = (batcher.stats()
                  if metric.startswith("serving_microbatched") else None)
            chist = Histogram()
            for v in lats:
                chist.observe(float(v))
            cpcts = chist.percentiles([50, 99])
            cp50, cp99 = cpcts[50], cpcts[99]
            if args.verbose:
                print(
                    f"# {metric} x{args.threads}: p50 {cp50*1e3:.2f}ms "
                    f"p99 {cp99*1e3:.2f}ms qps {len(lats)/wall:.0f}",
                    file=sys.stderr,
                )
            print(
                json.dumps(
                    {
                        "metric": metric,
                        "value": round(cp99 * 1e3, 3),
                        "unit": "ms",
                        "threads": args.threads,
                        "p50_ms": round(cp50 * 1e3, 3),
                        "qps": round(len(lats) / wall, 1),
                        "single_thread_p50_ms": round(p50 * 1e3, 3),
                        **(
                            {"max_batch_seen": mb["maxBatchSeen"],
                             "batches": mb["batches"]}
                            if mb is not None
                            else {}
                        ),
                    }
                )
            )

    if args.batch > 0:
        qs = [Query(user=f"u{int(u)}", num=args.num)
              for u in rng.integers(0, args.users, args.batch)]
        algo.batch_predict(model, qs)  # warm the batched executable
        reps = max(200 // args.batch, 3)
        t0 = time.perf_counter()
        for _ in range(reps):
            rb = algo.batch_predict(model, qs)
        dt = time.perf_counter() - t0
        assert all(len(r.item_scores) == args.num for r in rb)
        print(
            json.dumps(
                {
                    "metric": "serving_batch_queries_per_s",
                    "value": round(reps * args.batch / dt, 1),
                    "unit": "queries/s",
                    "batch": args.batch,
                }
            )
        )

    if args.http:
        _bench_http(args, model, rng)

    if args.sweep or args.concurrency > 0:
        _bench_sweep(args, model, rng)


def _prebuilt_engine(model, algo_params=None):
    """A deployable engine whose 'training' hands back the prebuilt
    synthetic model (what the serving benches measure is the serving
    path, never training).  ``algo_params`` (an engine.json-style
    params dict, e.g. ``{"retrieval": "ivf", "nprobe": 16}``) rides
    the variant so sweep A/Bs measure the product's own param
    threading, not a bench-only side channel."""
    from predictionio_tpu.controller.base import DataSource, WorkflowContext
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, Query as RecQuery,
    )
    from predictionio_tpu.workflow.params import WorkflowParams
    from predictionio_tpu.workflow.train import run_train

    class DS(DataSource):
        def read_training(self, ctx):
            return None

    class PrebuiltALS(ALSAlgorithm):
        """Serve the prebuilt synthetic model (training is not what
        this bench measures).  query_class is explicit because the
        decoder's module-level Query convention resolves against THIS
        module, not the template's."""

        query_class = RecQuery

        def train(self, ctx, data):
            return model

    storage = Storage({
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM2",
        "PIO_STORAGE_SOURCES_MEM2_TYPE": "memory",
    })
    ctx = WorkflowContext(storage=storage)
    engine = SimpleEngine(DS, PrebuiltALS)
    variant = (
        {"algorithms": [{"name": "", "params": dict(algo_params)}]}
        if algo_params else {}
    )
    ep = engine.params_from_variant(variant)
    # save_model=False: deploy "retrains" via PrebuiltALS.train, which
    # hands back the in-memory model — no orphaned ~28 MB pickle in the
    # user's model dir per bench run
    iid = run_train(engine, ep, ctx=ctx, engine_variant="bench.json",
                    workflow_params=WorkflowParams(save_model=False))
    return engine, ep, iid, ctx


def _boot_server(engine, ep, iid, ctx, microbatch,
                 tenants=None, slo_ms=None, microbatch_max=64):
    from predictionio_tpu.server.serving import EngineServer, ServerConfig

    srv = EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(port=0, microbatch=microbatch,
                            slo_ms=slo_ms,
                            microbatch_max=microbatch_max),
        engine_variant="bench.json",
        tenants=tenants,
    )
    srv.start_background()
    return srv


def _prebuilt_tenant_registry(args, model, rng, n, algo_params):
    """N independent prebuilt tenants (tenant 0 reuses the already-
    staged model; the rest draw fresh factor tables) in one
    TenantRegistry — the mixed-tenant serving surface the --tenants
    sweep measures.  Returns (anchor components, registry)."""
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import ALSModel
    from predictionio_tpu.tenancy import TenantRegistry, TenantSpec

    specs = []
    anchor = None
    for i in range(n):
        if i == 0:
            m = model
        else:
            trng = np.random.default_rng(1000 + i)
            m = ALSModel(
                user_factors=trng.normal(
                    size=(args.users, args.rank)
                ).astype(np.float32),
                item_factors=trng.normal(
                    size=(args.items, args.rank)
                ).astype(np.float32),
                users=StringIndex(
                    [f"u{j}" for j in range(args.users)]
                ),
                items=StringIndex(
                    [f"i{j}" for j in range(args.items)]
                ),
                item_props={},
            )
        engine, ep, iid, ctx = _prebuilt_engine(m, algo_params)
        specs.append(TenantSpec(
            f"app{i}", "main", engine=engine, engine_params=ep,
            instance_id=iid, ctx=ctx,
        ))
        if i == 0:
            anchor = (engine, ep, iid, ctx)
    registry = TenantRegistry(specs, memory_budget_bytes=0,
                              salt="bench")
    return anchor, registry


def _warm_batch_ladder(srv, num: int, top: int) -> None:
    """Pre-compile every pow2 batch executable the padded batcher can
    dispatch up to ``top`` (a mid-run first-compile would land in the
    reported p99)."""
    if srv.batcher is None:
        return
    dq = srv.query_decoder({"user": "u0", "num": num})
    bsz = 1
    while bsz <= min(64, top):
        srv.batcher.batch_fn([dq] * bsz)
        bsz *= 2


def _bench_http(args, model, rng) -> None:
    """Full product path under concurrent HTTP load: a deployed
    EngineServer with the recommendation algorithm serving the
    synthetic model, N urllib clients, microbatch on vs off."""
    import concurrent.futures
    import json as _json
    import urllib.request

    engine, ep, iid, ctx = _prebuilt_engine(model)

    per_thread = max(args.n // args.threads, 25)
    users = rng.integers(0, args.users, (args.threads, per_thread))

    def measure(microbatch):
        srv = _boot_server(engine, ep, iid, ctx, microbatch)
        base = f"http://127.0.0.1:{srv.config.port}"

        def one(u):
            req = urllib.request.Request(
                f"{base}/queries.json",
                data=_json.dumps(
                    {"user": f"u{u}", "num": args.num}
                ).encode(),
                headers={"Content-Type": "application/json"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=60) as r:
                body = _json.loads(r.read().decode())
            assert len(body["itemScores"]) == args.num
            return body

        def client(tid):
            lats = np.empty(per_thread)
            for j in range(per_thread):
                t0 = time.perf_counter()
                one(int(users[tid, j]))
                lats[j] = time.perf_counter() - t0
            return lats

        # warm every pow2 batch size the padded batcher can dispatch
        # (a mid-run first-compile would land in the reported p99), then
        # one HTTP round per thread; stats reset so the JSON describes
        # timed traffic only
        _warm_batch_ladder(srv, args.num, args.threads * 2)
        with concurrent.futures.ThreadPoolExecutor(args.threads) as ex:
            list(ex.map(lambda t: one(int(users[t, 0])),
                        range(args.threads)))  # warm
            if srv.batcher is not None:
                srv.batcher.reset_stats()
            t0 = time.perf_counter()
            lats = np.concatenate(list(ex.map(client, range(args.threads))))
            wall = time.perf_counter() - t0
        status = srv.status_json()
        stats = status.get("microbatch")
        srv.stop()
        p50, p99 = np.percentile(lats, [50, 99])
        # the server's own pio-obs histogram view (what /metrics and
        # /status expose) — server-side work only, no HTTP/client time
        server_p50 = status.get("p50ServingSec", 0.0)
        server_p99 = status.get("p99ServingSec", 0.0)
        return p50, p99, server_p50, server_p99, len(lats) / wall, stats

    for mode in ("off", "auto"):
        p50, p99, server_p50, server_p99, qps, stats = measure(mode)
        print(json.dumps({
            "metric": "serving_http_concurrent_p99_ms",
            "value": round(p99 * 1e3, 3),
            "unit": "ms",
            "threads": args.threads,
            "microbatch": mode,
            "p50_ms": round(p50 * 1e3, 3),
            "server_p50_ms": round(server_p50 * 1e3, 3),
            "server_p99_ms": round(server_p99 * 1e3, 3),
            "qps": round(qps, 1),
            **({"max_batch_seen": stats["maxBatchSeen"]} if stats else {}),
        }), flush=True)


def _bench_sweep(args, model, rng) -> None:
    """pio-pulse closed-loop concurrency sweep (``--sweep 1,4,16`` /
    ``--concurrency N``): a real deployed EngineServer, multi-process
    loadgen workers over real HTTP, per-point QPS + exact p50/p99 +
    per-segment decomposition (registry deltas around each window), a
    ``serving_qps_at_slo`` summary the bench gate judges upward, and
    the sweep artifact ``/pulse.html`` renders.

    Timings are host-complete by construction (every response is fully
    drained by the closed-loop worker before its latency is recorded),
    hence ``fenced: true`` on the records."""
    import jax

    sys.path.insert(0, str(Path(__file__).parent / "tools"))
    import bench_gate
    import loadgen

    from predictionio_tpu.obs import scope, telemetry_home
    from predictionio_tpu.obs.timeline import (
        SERVE_SEGMENTS, SERVE_SEGMENT_SECONDS,
    )

    if args.profile:
        # --profile forces the pio-scope sampler on for the sweep even
        # when the environment opted out (PIO_TPU_SCOPE=0): an explicit
        # profiling request wins over an ambient default
        scope.set_enabled(True)
        scope.ensure_started()

    points_c = (
        [int(x) for x in args.sweep.split(",")] if args.sweep
        else [args.concurrency]
    )
    algo_params = None
    if args.retrieval != "exact":
        algo_params = {
            "retrieval": args.retrieval,
            "candidateFactor": args.candidate_factor,
            "nprobe": args.nprobe,
        }
    tenants_n = max(getattr(args, "tenants", 0) or 0, 0)
    registry = None
    if tenants_n > 1:
        (engine, ep, iid, ctx), registry = _prebuilt_tenant_registry(
            args, model, rng, tenants_n, algo_params
        )
    else:
        engine, ep, iid, ctx = _prebuilt_engine(model, algo_params)
    # pio-lens: the sweep's server runs with the SLO armed, so each
    # point also reads the error-budget burn rate the fleet alerting
    # would see (the 1m window covers a sweep point's duration)
    srv = _boot_server(engine, ep, iid, ctx, microbatch="auto",
                       tenants=registry,
                       slo_ms=args.slo_ms,
                       microbatch_max=args.microbatch_max)
    # fenced-record keying (pio-scout satellite): the catalog size
    # rides the record's ``scale`` field — part of bench_gate's
    # baseline key — so a 1M-item sweep never shares a rolling
    # baseline with the 100k default (which keeps scale None for
    # continuity with the pre-scout history).  Non-exact retrieval
    # additionally suffixes the metric name: exact and ANN
    # trajectories are separate lines, judged separately.  Multi-
    # tenant sweeps (pio-hive) get the _mt suffix AND scale = tenant
    # count — a 4-tenant QPS@SLO never shares a baseline with the
    # single-tenant line.
    rec_scale = float(args.items) if args.items != 100_000 else None
    suffix = f"_{args.retrieval}" if args.retrieval != "exact" else ""
    if tenants_n > 1:
        suffix += "_mt"
        rec_scale = float(tenants_n)
    base = f"http://127.0.0.1:{srv.config.port}"
    _warm_batch_ladder(srv, args.num, max(points_c) * 2)
    if registry is not None:
        # force-load + warm every tenant BEFORE the measured window: a
        # lazy first-query load (seconds of XLA warmup) inside a sweep
        # point would be measured as tail latency, which is a cold-
        # start number, not the steady-state the sweep claims
        dq = srv.query_decoder({"user": "u0", "num": args.num})
        for key in [s.key for s in registry.specs()]:
            rt = registry.get_runtime(key)
            if rt.batcher is not None:
                bsz = 1
                while bsz <= min(64, max(points_c) * 2):
                    rt.batcher.batch_fn([dq] * bsz)
                    bsz *= 2
        # an `_mt` record that measured a mid-sweep budget eviction +
        # lazy reload is a cold-start number wearing a steady-state
        # label — assert full residency up front and re-check after
        # every point; a point that raced an eviction is stamped
        # cold_reload and excluded from the qps_at_slo summary
        expected_keys = {s.key for s in registry.specs()}
        missing0 = expected_keys - set(registry.resident_keys())
        if missing0:
            print(
                f"# WARNING: {len(missing0)} tenant(s) not resident "
                f"after force-load (budget evicted them): "
                f"{sorted('/'.join(k) for k in missing0)} — _mt "
                "points will measure lazy reloads",
                file=sys.stderr,
            )
    payloads = [
        json.dumps({
            "user": f"u{int(u)}", "num": args.num,
            **({"app": f"app{j % tenants_n}"} if tenants_n > 1 else {}),
        })
        for j, u in enumerate(rng.integers(0, args.users, 256))
    ]

    def seg_snapshot():
        return {
            s: SERVE_SEGMENT_SECONDS.labels(segment=s).snapshot()
            for s in SERVE_SEGMENTS
        }

    platform = jax.default_backend()
    points = []
    for c in points_c:
        before = seg_snapshot()
        ev_before = registry.evictions if registry is not None else 0
        t_start = time.time()
        res = loadgen.run_load(
            f"{base}/queries.json", payloads, c, args.duration_s,
            mode=args.loadgen_mode, arrival_rate=args.arrival_rate,
        )
        t_end = time.time()
        after = seg_snapshot()
        # mean per-segment share of this window's requests: the server
        # and bench share one process, so the registry deltas are the
        # exact server-side decomposition of the window's traffic
        segments_ms = {}
        for s in SERVE_SEGMENTS:
            dc = after[s]["count"] - before[s]["count"]
            ds = after[s]["sum"] - before[s]["sum"]
            segments_ms[s] = round(ds / dc * 1e3, 4) if dc else 0.0
        point = {
            "concurrency": c,
            "qps": round(res["qps"], 1),
            "p50_ms": round(res["p50_ms"], 3),
            "p99_ms": round(res["p99_ms"], 3),
            "completed": res["completed"],
            "errors": res["errors"],
            "truncated": res["truncated"],
            "segments_ms": segments_ms,
        }
        if srv._burn is not None:
            point["burn_rate_1m"] = round(srv._burn.rate(60.0), 4)
        if args.profile and scope.profiler_running():
            # the server runs IN this process, so the ring's window
            # over [t_start, t_end] is the exact server-side CPU
            # attribution for this point: which role burned the
            # samples, and the stacks that dominated on-CPU time
            prof = scope.get_profiler()
            point["profile"] = {
                "overhead_ratio": round(prof.overhead_ratio(), 5),
                "roles": prof.role_totals(t_end - t_start),
                "dominant_stacks": prof.dominant_stacks(
                    t_start, t_end, top=5
                ),
            }
        if registry is not None:
            ev_delta = registry.evictions - ev_before
            missing = expected_keys - set(registry.resident_keys())
            if ev_delta or missing:
                point["cold_reload"] = True
                point["evictions_during"] = ev_delta
                point["tenants_missing"] = sorted(
                    "/".join(k) for k in missing
                )
                print(
                    f"# WARNING: c={c} point raced a budget eviction "
                    f"({ev_delta} eviction(s), missing: "
                    f"{point['tenants_missing']}) — measured a lazy "
                    "reload, excluded from qps_at_slo",
                    file=sys.stderr,
                )
        points.append(point)
        rec = {
            "metric": f"serving_p99_ms_c{c}{suffix}",
            "value": point["p99_ms"],
            "unit": "ms",
            "direction": "down",
            "platform": platform,
            "scale": rec_scale,
            "nproc": os.cpu_count() or 1,
            "fenced": True,
            "profiler_enabled": scope.profiler_running(),
            "retrieval": args.retrieval,
            "qps": point["qps"],
            "p50_ms": point["p50_ms"],
            "duration_s": args.duration_s,
            "loadgen_mode": args.loadgen_mode,
            "errors": res["errors"],
            "items": args.items,
            "rank": args.rank,
            **({"tenants": tenants_n} if tenants_n > 1 else {}),
            "segments_ms": segments_ms,
            **({"arrival_rate": args.arrival_rate,
                "service_p99_ms": round(res["service_p99_ms"], 3)}
               if args.arrival_rate else {}),
            **({"cold_reload": True,
                "evictions_during": point["evictions_during"],
                "tenants_missing": point["tenants_missing"]}
               if point.get("cold_reload") else {}),
        }
        print(json.dumps(rec), flush=True)
        if args.append_history:
            bench_gate.append_history(bench_gate.DEFAULT_HISTORY, rec)
    mb = srv.batcher.stats() if srv.batcher is not None else None
    srv.stop()

    sweep_doc = {
        "recorded_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
        "slo_ms": args.slo_ms,
        "platform": platform,
        "items": args.items,
        "rank": args.rank,
        "retrieval": args.retrieval,
        "profiler_enabled": scope.profiler_running(),
        **({"tenants": tenants_n} if tenants_n > 1 else {}),
        "points": points,
        **({"microbatch": mb} if mb else {}),
    }
    ok_points = [
        p for p in points
        if p["p99_ms"] <= args.slo_ms and p["errors"] == 0
        and not p.get("cold_reload")
    ]
    if ok_points:
        best = max(ok_points, key=lambda p: p["qps"])
        sweep_doc["qps_at_slo"] = best["qps"]
        sweep_doc["concurrency_at_slo"] = best["concurrency"]
        rec = {
            "metric": f"serving_qps_at_slo{suffix}",
            "value": best["qps"],
            "unit": "qps",
            "direction": "up",
            "platform": platform,
            "scale": rec_scale,
            "nproc": os.cpu_count() or 1,
            "fenced": True,
            "profiler_enabled": scope.profiler_running(),
            "retrieval": args.retrieval,
            "slo_ms": args.slo_ms,
            "concurrency": best["concurrency"],
            "p99_ms": best["p99_ms"],
            "sweep": [p["concurrency"] for p in points],
            "duration_s": args.duration_s,
            "loadgen_mode": args.loadgen_mode,
            "items": args.items,
            "rank": args.rank,
            **({"tenants": tenants_n} if tenants_n > 1 else {}),
        }
        print(json.dumps(rec), flush=True)
        if args.append_history:
            bench_gate.append_history(bench_gate.DEFAULT_HISTORY, rec)
        try:
            bench_gate.write_pr_summary(
                rec,
                key="serving_sweep_mt" if tenants_n > 1
                else "serving_sweep",
            )
        except Exception as e:
            print(f"# WARNING: could not write bench summary: {e}",
                  file=sys.stderr)
    else:
        # no record is written: a 0-QPS "measurement" would poison the
        # rolling baseline; the operator sees WHY instead
        print(
            f"# WARNING: no sweep point met the p99 SLO of "
            f"{args.slo_ms} ms; no serving_qps_at_slo record written",
            file=sys.stderr,
        )
    # the /pulse.html sweep artifact (dashboard renders the latest)
    sweep_dir = telemetry_home() / "sweeps"
    try:
        sweep_dir.mkdir(parents=True, exist_ok=True)
        (sweep_dir / "latest.json").write_text(
            json.dumps(sweep_doc, indent=1) + "\n"
        )
    except OSError as e:
        print(f"# WARNING: could not write sweep artifact: {e}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
