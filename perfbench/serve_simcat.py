"""Serving driver for the `similarproduct` engine under `categories`: what
`serve_similar.py` does for seeds and a blackList, for queries that also name
categories and a model that holds a category index.

An `EngineServer` in this process over a `SimilarALSModel` made from the
seed: unit rows drawn on the device, and a `CategoryIndex` made through the
program's own constructor from the items' categories, which
`reference/simcat_ref.item_categories` draws from the configuration and the
seed (the program is handed memberships, never bit rows); a pool of
queries, each a JSON body (`items`, `num`, `blackList`, `categories`), the
same for every seed in another order, sent by `loadgen_similar.py` unedited;
the server's own spans and counters read before and after the window; and
a sample of the served answers, the pool's narrowest and widest allowed sets
among them, held against `reference/simcat_ref.py`, which makes the
categories again by itself.

It reuses `serve.py`'s heartbeat, batch spans and counter snapshots,
`serve_similar.py`'s item table, query pool, generator handle and filter
counters, `serve_unseen.py`'s send order and sample choice, and
`loadgen.py`'s schedule and percentiles; it edits none of them.
"""

from __future__ import annotations

import gc
import json
import sys
import time

import numpy as np

from . import harness, loadgen, serve, serve_similar, serve_unseen
from .reference import simcat_ref

SENT_FIRST = 1024           # the narrowest and widest sets are sent among these


def require_categories_on_the_device() -> None:
    """Exit 2, at once, on a program that cannot test a query's categories
    on the device: its engine would build a `[B, M]` float32 mask on the
    host for every batch (2.4 GB at 64 rows over 9.35 M items, 330 ms a row
    to find a category's items) and answer a few requests a second."""
    try:
        from predictionio_tpu.ops.topk import CATEGORY_SLOTS  # noqa: F401
        from predictionio_tpu.templates._common import (  # noqa: F401
            CategoryIndex,
        )
    except ImportError:
        print("perfbench: this program keeps no category index and cannot "
              "test a query's categories on the device "
              "(templates/_common.CategoryIndex, ops/topk.CATEGORY_SLOTS); "
              "it cannot run this cell", file=sys.stderr)
        raise SystemExit(2)


def make_pool(cfg: dict, traffic: dict, item_cats: np.ndarray) -> list:
    """`serve_similar.make_pool`'s queries (seeds and a blackList, from
    `base_seed`: the same for every seed), each with `categories`: by a
    fair coin (`department_share`) the first seed's department alone, else
    one or two (equally likely) of the first seed's two sub-categories.
    Which, comes from `base_seed`; the names' numbers from the seed's
    categories of that item."""
    pool = serve_similar.make_pool(cfg, traffic)
    rng = np.random.default_rng([traffic["base_seed"], 29])
    department = rng.random(len(pool)) < traffic["department_share"]
    both = rng.integers(1, traffic["subcategories_max"] + 1, len(pool)) > 1
    which = rng.integers(1, 3, len(pool))
    for j, query in enumerate(pool):
        mine = item_cats[query["seeds"][0]]
        if department[j]:
            query["categories"] = [int(mine[0])]
        elif both[j]:
            query["categories"] = [int(mine[1]), int(mine[2])]
        else:
            query["categories"] = [int(mine[which[j]])]
    return pool


def body_of(query: dict, num: int, names: list) -> str:
    body = json.loads(serve_similar.body_of(query, num))
    body["categories"] = [names[c] for c in query["categories"]]
    return json.dumps(body)


def allowed_sizes(pool: list, item_cats: np.ndarray) -> np.ndarray:
    """For each query the sum of its categories' sizes: the size of its
    allowed set (an upper end where it names two)."""
    sizes = np.bincount(item_cats.reshape(-1))
    return np.array([sum(int(sizes[c]) for c in q["categories"])
                     for q in pool])


def build_server(cfg: dict, table: np.ndarray, item_cats: np.ndarray,
                 names: list, spans: serve.BatchSpans):
    """(server, model): a deployed `EngineServer` (event-loop edge, shared
    batcher, every `ServerConfig` value at its default but the port and
    `microbatch_max`) over the seeded `SimilarALSModel` with its category
    index."""
    import jax

    from predictionio_tpu.controller.base import DataSource, WorkflowContext
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates._common import CategoryIndex
    from predictionio_tpu.templates.similarproduct import (
        Query, SimilarALSModel, SimilarProductAlgorithm,
    )
    from predictionio_tpu.workflow.params import WorkflowParams
    from predictionio_tpu.workflow.train import run_train

    n_items, depth = item_cats.shape
    model = SimilarALSModel(
        item_factors=table,
        items=StringIndex([f"i{j}" for j in range(n_items)]),
        item_props={},
        category_index=CategoryIndex.from_memberships(
            names, item_cats.reshape(-1),
            np.repeat(np.arange(n_items, dtype=np.int64), depth)),
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

    ctx = WorkflowContext(storage=serve_unseen.memory_storage())
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
    """`serve_similar.program_counters` and what the categories add:
    category numbers dispatched (`pio_filter_category_ids_total`)."""
    from predictionio_tpu.templates import _common

    out = serve_similar.program_counters()
    out["category_ids"] = _common.FILTER_CATEGORY_IDS.value()
    return out


def program_counters_delta(before: dict, after: dict) -> dict:
    out = serve_similar.program_counters_delta(before, after)
    out["category_ids"] = after["category_ids"] - before["category_ids"]
    return out


def compare_sample(cfg: dict, seed: int, table: np.ndarray, pool: list,
                   sample: list, num: int) -> dict:
    """The numbers `correct` compares for the sampled answers: see
    `reference/simcat_ref.compare`.  The reference draws the items'
    categories again, by itself."""
    import jax.numpy as jnp

    picks, items, scores = serve_similar.parse_sample(sample)
    out = simcat_ref.compare(
        table, jnp.asarray(table), simcat_ref.item_categories(cfg, seed),
        [pool[j] for j in picks], items, scores, num)
    return {name: out[name] for name in cfg["limits"]}


def run(cell, opts, mode: str) -> dict:
    cfg, traffic, clock = cell.config, cell.traffic, opts["clock"]
    seed, seconds, log = opts["seed"], opts["seconds"], opts["log"]
    num = int(traffic["num"])
    with clock.phase("data_build_s"):
        t0 = time.perf_counter()
        table = np.array(serve_similar.make_items(cfg, seed))
        log(f"items made in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        item_cats = simcat_ref.item_categories(cfg, seed)
        names = simcat_ref.category_names(cfg)
        log(f"{item_cats.size} memberships of {len(names)} categories in "
            f"{time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        pool = make_pool(cfg, traffic, item_cats)
        bodies = [body_of(query, num, names) for query in pool]
        sizes = allowed_sizes(pool, item_cats)
        log(f"pool of {len(pool)} queries in {time.perf_counter() - t0:.1f}s;"
            f" allowed sets {sizes.min()} to {sizes.max()} items, median "
            f"{int(np.median(sizes))}")
    spans = serve.BatchSpans()
    with clock.phase("warmup_s"):
        srv, model = build_server(cfg, table, item_cats, names, spans)
        index = model.category_index
        index_facts = {
            "categories": len(index), "memberships": index.memberships,
            "categoryIndexBytes": int(model.device_category_rows().nbytes),
            "category_index_host_bytes": index.nbytes,
        }
        del item_cats
        check = cfg["check"]
        by_size = np.argsort(sizes, kind="stable")
        must = np.concatenate([by_size[:check["narrowest"]],
                               by_size[len(by_size) - check["widest"]:]])
        arrivals = None
        if mode == "open":
            arrivals = loadgen.arrival_offsets(
                traffic["rate_per_s"], seconds, traffic["base_seed"], seed)
        n_first = min(SENT_FIRST, len(arrivals) if arrivals else SENT_FIRST)
        order = serve_unseen.send_order(np.arange(len(pool)), must, seed,
                                        n_first)
        spec = {
            "host": "127.0.0.1", "port": srv.config.port,
            "path": "/queries.json", "mode": mode, "num": num,
            "seconds": seconds, "users": order.tolist(), "bodies": bodies,
            "connections": traffic["connections"],
            # every kept answer comes back: the sample is chosen here, to
            # hold the narrowest and the widest allowed sets
            "sample": 1 << 30, "sample_seed": seed,
        }
        if arrivals is not None:
            spec["arrivals"] = arrivals
        gen = serve_similar.Generator(spec)
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
        f"{program.get('filter_rows')}, category numbers "
        f"{program['category_ids']}, calls by path "
        f"{program.get('topk_paths')}")
    del srv
    serve_similar.release_device_tables(model)
    gc.unfreeze()
    gc.collect()

    t0 = time.perf_counter()
    sample = serve_unseen.choose_sample(
        result["sample"], set(must.tolist()), check["answers"], seed)
    numbers = compare_sample(cfg, seed, table, pool, sample, num)
    sampled = sizes[[s["user"] for s in sample]] if sample else np.zeros(1)
    log(f"reference over {len(sample)} answers "
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
        "longest_gc_pause_ms_and_generation":
            serve_unseen.longest_gc_pause(t_open, t_close),
        "generator_longest_silence_ms": 1e3 * result["longest_silence_s"],
        "generator_worst_late_ms": 1e3 * max(late, default=0.0),
        "reference_s": time.perf_counter() - t0,
        # what compiled inside the window, if anything did
        "compiles_by_fn_in_window": {
            name: n for name, n in program["compiles"].items() if n},
        "rows_by_filter_in_window": program["filter_rows"],
        "category_ids_in_window": program["category_ids"],
        "calls_by_path_in_window": program["topk_paths"],
        "narrowest_and_widest_sampled_set": [int(sampled.min()),
                                             int(sampled.max())],
        **index_facts,
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
