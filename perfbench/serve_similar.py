"""Serving driver for the `similarproduct` engine: what `serve.py` does for
`recommendation`, for queries that carry seed items and a blackList.

An `EngineServer` in this process over a `SimilarALSModel` made from the
seed (unit rows, drawn and normalised on the device); a pool of queries,
each a JSON body (`items`, `num`, `blackList`), the same for every seed in
another order; the load generator `loadgen_similar.py` as a child process;
the server's own spans and counters read before and after the window; and a
sample of the served answers held against `reference/similar_ref.py`.

It reuses `serve.py`'s heartbeat, batch spans, counter snapshots and
generator handle, and `loadgen.py`'s schedule and percentiles; it edits
neither.
"""

from __future__ import annotations

import gc
import json
import subprocess
import sys
import time

import numpy as np

from . import harness, loadgen, serve
from .cells import BENCH_DIR

DRAWS_A_QUERY = 64     # Zipf draws from which a query takes its distinct ids


def make_items(cfg: dict, seed: int):
    """The item table `[M, R]`, float32 unit rows, on the device as it was
    made from the seed."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        x = jax.random.normal(key, (cfg["n_items"], cfg["rank"]), jnp.float32)
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    return draw(harness.seed_key(seed, stream=5))


def make_pool(cfg: dict, traffic: dict) -> list:
    """The pool of queries, each `{"seeds": [...], "blacklist": [...]}` of
    item indices: `seeds_min`..`seeds_max` seeds and `blacklist_min`..
    `blacklist_max` blackListed ids (both uniform), all distinct, each
    drawn with P(popularity rank k) ~ k**-exponent over the whole
    catalogue (rank k is item k-1).  Drawn from `base_seed`: the same pool
    for every seed, which the seed only re-orders."""
    rng = np.random.default_rng(traffic["base_seed"])
    count, n_items = traffic["query_pool"], cfg["n_items"]
    cum = np.cumsum(np.arange(1, n_items + 1, dtype=np.float64)
                    ** -traffic["item_zipf_exponent"])

    def draw(shape):
        picks = np.searchsorted(cum, rng.random(shape) * cum[-1])
        return np.minimum(picks, n_items - 1)

    n_seeds = rng.integers(traffic["seeds_min"], traffic["seeds_max"] + 1,
                           count)
    n_black = rng.integers(traffic["blacklist_min"],
                           traffic["blacklist_max"] + 1, count)
    draws = draw((count, DRAWS_A_QUERY))
    pool = []
    for j in range(count):
        want = int(n_seeds[j] + n_black[j])
        ids = list(dict.fromkeys(draws[j].tolist()))
        while len(ids) < want:      # a head this heavy repeats itself
            ids = list(dict.fromkeys(ids + draw(DRAWS_A_QUERY).tolist()))
        pool.append({"seeds": ids[:n_seeds[j]],
                     "blacklist": ids[n_seeds[j]:want]})
    return pool


def body_of(query: dict, num: int) -> str:
    body = {"items": [f"i{ix}" for ix in query["seeds"]], "num": num}
    if query["blacklist"]:
        body["blackList"] = [f"i{ix}" for ix in query["blacklist"]]
    return json.dumps(body)


def build_server(cfg: dict, table: np.ndarray, spans: serve.BatchSpans):
    """(server, model): a deployed `EngineServer` (event-loop edge, shared
    batcher, every `ServerConfig` value at its default but the port and
    `microbatch_max`) over the seeded `SimilarALSModel`."""
    import jax

    from predictionio_tpu.controller.base import DataSource, WorkflowContext
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.templates.similarproduct import (
        Query, SimilarALSModel, SimilarProductAlgorithm,
    )
    from predictionio_tpu.workflow.params import WorkflowParams
    from predictionio_tpu.workflow.train import run_train

    model = SimilarALSModel(
        item_factors=table,
        items=StringIndex([f"i{j}" for j in range(len(table))]),
        item_props={},
    )

    class Source(DataSource):
        def read_training(self, ctx):
            return None

    class SeededSimilar(SimilarProductAlgorithm):
        query_class = Query

        def train(self, ctx, data):
            return model

        def batch_predict(self, mdl, queries):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.batch_fn"):
                out = super().batch_predict(mdl, queries)
            spans.add(t0, time.perf_counter(), len(queries))
            return out

    storage = Storage({
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM2",
        "PIO_STORAGE_SOURCES_MEM2_TYPE": "memory",
    })
    ctx = WorkflowContext(storage=storage)
    engine = SimpleEngine(Source, SeededSimilar)
    ep = engine.params_from_variant({})
    iid = run_train(engine, ep, ctx=ctx, engine_variant="perfbench.json",
                    workflow_params=WorkflowParams(save_model=False))
    srv = EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(port=0, microbatch_max=cfg["microbatch_max"]),
        engine_variant="perfbench.json",
    )
    srv.start_background()
    return srv, model


def program_counters() -> dict:
    """The program's filter counters as they stand: dispatched rows by the
    form their batch's filters took (`pio_filter_rows_total{filter}`), the
    filter builds' summed seconds and count (`pio_filter_build_seconds`),
    the scorer's calls by path (`pio_topk_path_total{path}`) and the
    executables built so far by entry point."""
    from predictionio_tpu.ops.topk import TOPK_PATH
    from predictionio_tpu.templates import _common

    build = _common.FILTER_BUILD_SECONDS.snapshot()
    return {
        "filter_rows": {dict(key)["filter"]: child.value()
                        for key, child in _common.FILTER_ROWS.children()},
        "filter_build": (build["sum"], build["count"]),
        "topk_paths": {dict(key)["path"]: child.value()
                       for key, child in TOPK_PATH.children()},
        "compiles": compiles_by_fn(),
    }


def compiles_by_fn() -> dict:
    """Executables built or fetched so far, by the program's entry point
    (obs/xray; "untracked" is jax's own small programs)."""
    from predictionio_tpu.obs import xray

    return {name: st["backendCompiles"]
            for name, st in xray.jit_stats().items()}


def program_counters_delta(before: dict, after: dict) -> dict:
    out = {
        name: {key: n - before[name].get(key, 0.0)
               for key, n in after[name].items()}
        for name in ("filter_rows", "topk_paths", "compiles")
    }
    out["filter_build"] = tuple(
        now - was for now, was in zip(after["filter_build"],
                                      before["filter_build"]))
    return out


class Generator(serve.Generator):
    """`serve.Generator` over `loadgen_similar.py`."""

    def __init__(self, spec: dict):
        self.proc = subprocess.Popen(
            [sys.executable, str(BENCH_DIR / "loadgen_similar.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        self.proc.stdin.write(json.dumps(spec) + "\n")
        self.proc.stdin.flush()
        ready = self.proc.stdout.readline()
        if '"ready"' not in ready:
            self.close()
            raise RuntimeError(f"the load generator did not start: {ready!r}")


def parse_sample(sample: list) -> tuple:
    """(pool indices, served items a query, served scores a query)."""
    picks = [s["user"] for s in sample]
    items, scores = [], []
    for s in sample:
        served = json.loads(s["body"])["itemScores"]
        items.append([int(x["item"][1:]) for x in served])
        scores.append([x["score"] for x in served])
    return picks, items, scores


def compare_sample(table: np.ndarray, pool: list, sample: list,
                   num: int) -> dict:
    """The numbers `correct` compares for the sampled answers: see
    `reference/similar_ref.compare`."""
    import jax.numpy as jnp

    from .reference import similar_ref

    picks, items, scores = parse_sample(sample)
    out = similar_ref.compare(table, jnp.asarray(table),
                              [pool[j] for j in picks], items, scores, num)
    return {name: out[name] for name in (
        "rank_gap", "score_err", "answers_with_repeats",
        "answers_with_excluded")}


def release_device_tables(model) -> None:
    """Drop the model's device copies, so that the reference's own copy of
    the table fits beside nothing."""
    for name in [n for n in vars(model) if n.startswith("_dev_")]:
        delattr(model, name)


def run(cell, opts, mode: str) -> dict:
    # fails here, at once, on a program without filters as data (which
    # would answer five of the cell's requests a second)
    from predictionio_tpu.templates._common import batch_filter  # noqa: F401

    cfg, traffic, clock = cell.config, cell.traffic, opts["clock"]
    seed, seconds, log = opts["seed"], opts["seconds"], opts["log"]
    num = int(traffic["num"])
    with clock.phase("data_build_s"):
        t0 = time.perf_counter()
        table = np.array(make_items(cfg, seed))   # the server uploads its own
        log(f"items made in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        pool = make_pool(cfg, traffic)
        bodies = [body_of(query, num) for query in pool]
        log(f"pool of {len(pool)} queries in {time.perf_counter() - t0:.1f}s")
    spans = serve.BatchSpans()
    with clock.phase("warmup_s"):
        srv, model = build_server(cfg, table, spans)
        order = np.random.default_rng(
            [seed & 0x7FFFFFFF, seed >> 31, 17]).permutation(len(pool))
        spec = {
            "host": "127.0.0.1", "port": srv.config.port,
            "path": "/queries.json", "mode": mode, "num": num,
            "seconds": seconds, "users": order.tolist(), "bodies": bodies,
            "connections": traffic["connections"],
            "sample": cfg["check"]["answers"], "sample_seed": seed,
        }
        if mode == "open":
            spec["arrivals"] = loadgen.arrival_offsets(
                traffic["rate_per_s"], seconds, traffic["base_seed"], seed,
            )
        gen = Generator(spec)
    try:
        gc.collect()
        gc.freeze()
        tracer = harness.Tracer(cell.name) if opts["trace"] else None
        before = serve.server_counters(srv)
        program_before = program_counters()
        heartbeat = serve.Heartbeat()
        heartbeat.start()
        t_open = clock.window_opens()
        gen.go()
        if tracer is not None:
            lead = min(traffic["trace_after_s"], max(seconds / 2 - 1.0, 0.0))
            time.sleep(lead)
            tracer.start()
            time.sleep(min(traffic["trace_seconds"], max(seconds - lead, 0.5)))
            tracer.stop()
        result = gen.result()
        t_close = t_open + seconds
        heartbeat_late_s = heartbeat.stop()
        after = serve.server_counters(srv)
        program_after = program_counters()
    finally:
        gen.close()
        srv.stop()
    peak = harness.memory_peak_bytes()
    peak_in_use = harness.memory_peak_in_use_bytes()
    delta = serve.counters_delta(before, after)
    program = program_counters_delta(program_before, program_after)
    in_window = spans.within(t_open, t_close)
    in_trace = spans.within(tracer.t0, tracer.t1) if tracer else []
    log(f"window: {result['answered']} answered of {result['attempted']}, "
        f"{delta['batches']} batches, rows by filter "
        f"{program.get('filter_rows')}, calls by path "
        f"{program.get('topk_paths')}")
    del srv
    release_device_tables(model)
    gc.unfreeze()
    gc.collect()

    t0 = time.perf_counter()
    numbers = compare_sample(table, pool, result["sample"], num)
    log(f"reference over {len(result['sample'])} answers "
        f"{time.perf_counter() - t0:.1f}s")
    lat = loadgen.latency_summary(result["latencies_s"], result["failed"])
    if mode == "closed":
        end_to_end = {"serve_rps": result["answered"] / seconds}
    else:
        end_to_end = {"serve_p95_ms": lat["p95_ms"]}
    late = sorted(result["late_s"])
    excluded = max(len(q["seeds"]) + len(q["blacklist"]) for q in pool)
    info = {
        "client_p50_ms": lat["p50_ms"], "client_p95_ms": lat["p95_ms"],
        "answered_per_s": result["answered"] / seconds,
        "generator_wall_s": result["wall_s"],
        "memory_peak_in_use_bytes": peak_in_use,
        # where a stall sat: inside the scorer's call, or between two
        # calls (batcher, edge, or a host that was not run)
        "longest_batch_fn_ms": 1e3 * max(
            (t1 - t0 for t0, t1, _ in in_window), default=0.0),
        "longest_gap_between_batches_ms": 1e3 * max(
            (b[0] - a[1] for a, b in zip(in_window, in_window[1:])),
            default=0.0),
        "server_heartbeat_worst_late_ms": 1e3 * heartbeat_late_s,
        "generator_longest_silence_ms": 1e3 * result["longest_silence_s"],
        "generator_worst_late_ms": 1e3 * max(late, default=0.0),
        "reference_s": time.perf_counter() - t0,
        # what compiled inside the window, if anything did
        "compiles_by_fn_in_window": {
            name: n for name, n in program["compiles"].items() if n},
        "rows_by_filter_in_window": program["filter_rows"],
        "calls_by_path_in_window": program["topk_paths"],
    }
    return {
        "attempted": result["attempted"], "failed": result["failed"],
        "end_to_end": end_to_end, "numbers": numbers, "info": info,
        "memory_peak_bytes": peak,
        "window_s": seconds,
        "run": {
            "kind": "serve", "mode": mode, "window_s": seconds,
            "answered": result["answered"],
            "compiles_in_window": delta["compiles"],
            "segments": delta["segments"],
            "batches": delta["batches"], "requests": delta["requests"],
            "batch_spans": in_window, "traced_batch_spans": in_trace,
            "late_p95_ms": (loadgen.percentile(late, 95) * 1e3
                            if late else None),
            "shape": {"n_items": cfg["n_items"], "rank": cfg["rank"],
                      "k": 1 << (num - 1).bit_length(),
                      "excluded": excluded},
            "filter_rows": program["filter_rows"],
            "filter_build": program["filter_build"],
        },
        "tracer": tracer,
    }
