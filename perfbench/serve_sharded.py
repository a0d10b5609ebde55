"""Serving driver for the recommendation engine over an item table that no
chip can hold: what `serve.py` does for `rec-yambda-r64`, with the item
table drawn shard by shard on the host's chips and `distributedTopk` on.

An `EngineServer` in this process over an `ALSModel` whose item table is a
`jax.Array` sharded `P("data")` over every chip of the host, drawn there
from the seed (each chip makes only its own rows), and whose user table is
a float32 host array drawn by host threads from the seed.  The engine's
`ShardedTopK` takes the table as it lies (`ops/distributed_topk.
place_rows`).  Ids are numbered, `u<j>` and `i<j>`, and looked up by
number (`NumberedIds`).  The load generator `loadgen.py`, unedited, sends
`{"user", "num"}` for users drawn Zipf from a pool; the server's own spans
and counters are read before and after the window; and a sample of the
served answers is held against `reference/sharded_topk_ref.py`, which
scores every item where its row lies.

Set-up, the window and the check are functions of their own (`setup`,
`window`, `check`), so that a measurement can hold several windows over one
set-up.  It reuses `serve.py`'s heartbeat, batch spans, counter snapshots,
generator handle and sample parser, and `loadgen.py`'s schedule and
percentiles; it edits none of them.
"""

from __future__ import annotations

import functools
import gc
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

from . import harness, loadgen, serve

USER_ROWS_A_DRAW = 1 << 18     # host rows one thread draws at a time


def require_table_stationary_scan() -> None:
    """Exit 2, at once, on a program that cannot take an item table that
    lies sharded on the chips as it lies: its engine would put the whole
    24.67 GB table on one chip at warm-up."""
    try:
        from predictionio_tpu.ops.distributed_topk import (  # noqa: F401
            place_rows,
        )
    except ImportError:
        print("perfbench: this program cannot serve an item table that "
              "lies sharded on the chips as it lies "
              "(ops/distributed_topk.place_rows); it cannot run this cell",
              file=sys.stderr)
        raise SystemExit(2)


class NumberedIds:
    """The id map of a catalogue whose ids are `<prefix><number>`, number
    below `n`: what a `StringIndex` of those ids answers to the serving
    path (`get`, `decode`, `len`), found by reading the number.  A
    `StringIndex` of the 54.51 M users and 48.19 M items would take
    minutes of set-up and over 10 GB to build; a request's look-up is one
    parse here where it is one hash there."""

    def __init__(self, prefix: str, n: int):
        self.prefix = prefix
        self.n = int(n)

    def __len__(self) -> int:
        return self.n

    def get(self, s, default: int = -1) -> int:
        if not isinstance(s, str) or not s.startswith(self.prefix):
            return default
        tail = s[len(self.prefix):]
        if not tail.isdigit() or (len(tail) > 1 and tail[0] == "0"):
            return default
        ix = int(tail)
        return ix if ix < self.n else default

    def decode(self, ixs) -> np.ndarray:
        ixs = np.asarray(ixs)
        return np.array([f"{self.prefix}{int(i)}" for i in ixs.reshape(-1)],
                        dtype=object).reshape(ixs.shape)


def make_items(cfg: dict, seed: int, mesh):
    """The item table ``[M, R]`` float32, N(0, 1)/sqrt(R), sharded
    ``P("data")`` over `mesh`: drawn on the chips from the seed, each chip
    its own rows (JAX's partitionable PRNG: the same table on any mesh)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    rank = cfg["rank"]

    @functools.partial(jax.jit,
                       out_shardings=NamedSharding(mesh, P("data", None)))
    def draw(key):
        return jax.random.normal(key, (cfg["n_items"], rank),
                                 jnp.float32) * (1.0 / rank ** 0.5)

    return jax.block_until_ready(draw(harness.seed_key(seed, stream=5)))


def make_users(cfg: dict, seed: int) -> np.ndarray:
    """The user table ``[N, R]`` float32, N(0, 1)/sqrt(R), on the host:
    blocks of `USER_ROWS_A_DRAW` rows drawn by threads, each block from its
    own stream of the seed (the same table whatever the thread count)."""
    n, rank = cfg["n_users"], cfg["rank"]
    out = np.empty((n, rank), np.float32)
    scale = np.float32(1.0 / rank ** 0.5)

    def fill(lo: int) -> None:
        part = out[lo:lo + USER_ROWS_A_DRAW]
        np.random.default_rng([seed, 3, lo]).standard_normal(
            dtype=np.float32, out=part)
        part *= scale

    with ThreadPoolExecutor(max_workers=min(32, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, range(0, n, USER_ROWS_A_DRAW)))
    return out


def user_pool(n_users: int, exponent: float, count: int, base_seed: int,
              seed: int) -> list:
    """`count` user numbers with P(rank k) ~ k**-exponent over `n_users`
    (`loadgen.zipf_users`' law, in numpy: its Python loop over 54.51 M
    users takes a minute).  The same draws for every seed, in another
    order."""
    cum = np.cumsum(np.arange(1, n_users + 1, dtype=np.float64) ** -exponent)
    rng = np.random.default_rng(base_seed)
    picks = np.minimum(np.searchsorted(cum, rng.random(count) * cum[-1]),
                       n_users - 1)
    np.random.default_rng([seed, 19]).shuffle(picks)
    return picks.tolist()


def build_server(cfg: dict, users: np.ndarray, items, spans: serve.BatchSpans):
    """(server, model): a deployed `EngineServer` (event-loop edge, shared
    batcher, every `ServerConfig` value at its default but the port and
    `microbatch_max`) over the seeded `ALSModel`, its algorithm's params
    `distributedTopk` true."""
    import jax

    from predictionio_tpu.controller.base import DataSource, WorkflowContext
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSModel, Query,
    )
    from predictionio_tpu.workflow.params import WorkflowParams
    from predictionio_tpu.workflow.train import run_train

    model = ALSModel(
        user_factors=users, item_factors=items,
        users=NumberedIds("u", cfg["n_users"]),
        items=NumberedIds("i", cfg["n_items"]),
        item_props={},
    )

    class Source(DataSource):
        def read_training(self, ctx):
            return None

    class SeededALS(ALSAlgorithm):
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
    engine = SimpleEngine(Source, SeededALS)
    ep = engine.params_from_variant({"algorithms": [{"name": "", "params": {
        "rank": cfg["rank"], "distributedTopk": cfg["distributedTopk"],
        "retrieval": cfg["retrieval"]}}]})
    # the model is handed in, not trained: the deploy's own load checks it
    iid = run_train(engine, ep, ctx=ctx, engine_variant="perfbench.json",
                    workflow_params=WorkflowParams(save_model=False,
                                                   skip_sanity_check=True))
    srv = EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(port=0, microbatch_max=cfg["microbatch_max"]),
        engine_variant="perfbench.json",
    )
    srv.start_background()
    return srv, model


@dataclass
class Served:
    """What `setup` made and `window` and `check` use."""
    cfg: dict
    traffic: dict
    users: np.ndarray
    items: object
    pool: list
    srv: object
    model: object
    spans: serve.BatchSpans = field(default_factory=serve.BatchSpans)


def setup(cell, opts) -> Served:
    """The tables, the pool of users and the deployed server (warmed)."""
    from predictionio_tpu.parallel import make_mesh

    cfg, traffic, clock = cell.config, cell.traffic, opts["clock"]
    seed, log = opts["seed"], opts["log"]
    with clock.phase("data_build_s"):
        t0 = time.perf_counter()
        items = make_items(cfg, seed, make_mesh())
        log(f"items {items.shape} on {len(items.sharding.device_set)} chips "
            f"in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        users = make_users(cfg, seed)
        pool = user_pool(cfg["n_users"], traffic["user_zipf_exponent"],
                         traffic["user_pool"], traffic["base_seed"], seed)
        log(f"users {users.shape} and a pool of {len(pool)} in "
            f"{time.perf_counter() - t0:.1f}s")
    spans = serve.BatchSpans()
    with clock.phase("warmup_s"):
        t0 = time.perf_counter()
        srv, model = build_server(cfg, users, items, spans)
        log(f"server up and warm in {time.perf_counter() - t0:.1f}s: "
            f"{model.sharded_topk_index().summary()}")
    return Served(cfg, traffic, users, items, pool, srv, model, spans)


def window(served: Served, opts, mode: str, seconds: float, trace: bool,
           connections: int, name: str = "sharded") -> dict:
    """One window of `seconds` of traffic: its generator's result, the
    server's counters over it, its batch spans, and its tracer."""
    traffic, seed = served.traffic, opts["seed"]
    spec = {
        "host": "127.0.0.1", "port": served.srv.config.port,
        "path": "/queries.json", "mode": mode, "num": int(traffic["num"]),
        "seconds": seconds, "users": served.pool, "connections": connections,
        "sample": served.cfg["check"]["answers"], "sample_seed": seed,
    }
    if mode == "open":
        spec["arrivals"] = loadgen.arrival_offsets(
            traffic["rate_per_s"], seconds, traffic["base_seed"], seed)
    gen = serve.Generator(spec)
    try:
        gc.collect()
        gc.freeze()
        tracer = harness.Tracer(name) if trace else None
        before = serve.server_counters(served.srv)
        heartbeat = serve.Heartbeat()
        heartbeat.start()
        t_open = opts["clock"].window_opens()
        gen.go()
        if tracer is not None:
            lead = min(traffic["trace_after_s"], max(seconds / 2 - 1.0, 0.0))
            time.sleep(lead)
            tracer.start()
            time.sleep(min(traffic["trace_seconds"], max(seconds - lead, 0.5)))
            tracer.stop()
        result = gen.result()
        heartbeat_late_s = heartbeat.stop()
        after = serve.server_counters(served.srv)
    finally:
        gen.close()
        gc.unfreeze()
    t_close = t_open + seconds
    return {
        "result": result, "delta": serve.counters_delta(before, after),
        "in_window": served.spans.within(t_open, t_close),
        "in_trace": (served.spans.within(tracer.t0, tracer.t1)
                     if tracer else []),
        "heartbeat_late_s": heartbeat_late_s, "tracer": tracer,
    }


def check(served: Served, sample: list) -> dict:
    """The numbers `correct` compares for served answers: see
    `reference/sharded_topk_ref.compare`; and how many answers repeat an
    item."""
    from .reference import sharded_topk_ref

    num = int(served.traffic["num"])
    users, items, scores = serve.parse_sample(sample, num)
    out = sharded_topk_ref.compare(served.users[users], served.items, items,
                                   scores, served.cfg["n_items"])
    repeats = sum(len(set(row)) != num for row in items.tolist())
    return {"rank_gap": out["rank_gap"], "score_err": out["score_err"],
            "answers_with_repeats": float(repeats)}


def run(cell, opts, mode: str) -> dict:
    cfg, traffic, seconds = cell.config, cell.traffic, opts["seconds"]
    served = setup(cell, opts)
    try:
        w = window(served, opts, mode, seconds, opts["trace"],
                   traffic["connections"], cell.name)
    finally:
        served.srv.stop()
    peak = harness.memory_peak_bytes()
    peak_in_use = harness.memory_peak_in_use_bytes()
    result, delta = w["result"], w["delta"]
    in_window = w["in_window"]
    index = served.model.sharded_topk_index().summary()
    opts["log"](f"window: {result['answered']} answered of "
                f"{result['attempted']}, {delta['batches']} batches")
    t0 = time.perf_counter()
    numbers = check(served, result["sample"])
    opts["log"](f"reference over {len(result['sample'])} answers "
                f"{time.perf_counter() - t0:.1f}s")
    lat = loadgen.latency_summary(result["latencies_s"], result["failed"])
    if mode == "closed":
        end_to_end = {"serve_rps": result["answered"] / seconds}
    else:
        end_to_end = {"serve_p95_ms": lat["p95_ms"]}
    late = sorted(result["late_s"])
    num = int(traffic["num"])
    return {
        "attempted": result["attempted"], "failed": result["failed"],
        "end_to_end": end_to_end, "numbers": numbers,
        "info": {"client_p50_ms": lat["p50_ms"], "client_p95_ms": lat["p95_ms"],
                 "answered_per_s": result["answered"] / seconds,
                 "generator_wall_s": result["wall_s"],
                 "memory_peak_in_use_bytes": peak_in_use,
                 "index": index,
                 "longest_batch_fn_ms": 1e3 * max(
                     (t1 - t0 for t0, t1, _ in in_window), default=0.0),
                 "longest_gap_between_batches_ms": 1e3 * max(
                     (b[0] - a[1] for a, b in zip(in_window, in_window[1:])),
                     default=0.0),
                 "server_heartbeat_worst_late_ms":
                     1e3 * w["heartbeat_late_s"],
                 "generator_longest_silence_ms":
                     1e3 * result["longest_silence_s"],
                 "generator_worst_late_ms": 1e3 * max(late, default=0.0),
                 "reference_s": time.perf_counter() - t0},
        "memory_peak_bytes": peak,
        "window_s": seconds,
        "run": {
            "kind": "serve", "mode": mode, "window_s": seconds,
            "answered": result["answered"],
            "compiles_in_window": delta["compiles"],
            "segments": delta["segments"],
            "batches": delta["batches"], "requests": delta["requests"],
            "batch_spans": in_window, "traced_batch_spans": w["in_trace"],
            "late_p95_ms": (loadgen.percentile(late, 95) * 1e3
                            if late else None),
            "shape": {"n_items": cfg["n_items"], "rank": cfg["rank"],
                      "k": 1 << (num - 1).bit_length()},
        },
        "tracer": w["tracer"],
    }
