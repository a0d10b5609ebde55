"""What the two HTTP drivers share: an `EngineServer` in this process
(which holds the chip) over a recommendation model made from the seed, the
load generator as a child process, the server's own spans and counters
read before and after the window, and the comparison of a sample of the
served answers with the plain reference.

The engine is the program's `ALSAlgorithm` with its `ALSModel` tables;
only `train` is replaced (it hands back the seeded model), and
`batch_predict` is wrapped in a host span so that the benchmark's own
files time the call into the scorer.  Copied in idea from
`bench_serving._prebuilt_engine` / `_boot_server`.
"""

from __future__ import annotations

import gc
import json
import subprocess
import sys
import threading
import time

import numpy as np

from . import harness, loadgen
from .cells import BENCH_DIR


def make_tables(cfg: dict, seed: int):
    """(user_factors, item_factors) as the float32 host arrays an `ALSModel`
    holds, drawn on the device from the seed."""
    users, items = harness.seeded_tables(cfg, seed, stream=3)
    return np.asarray(users), np.asarray(items)


class Heartbeat(threading.Thread):
    """Wakes every 50 ms and keeps the longest it overslept: a stall of this
    whole process (or of its host) shows here, one of a single layer does
    not.  Under `info` only; no metric reads it."""

    PERIOD_S = 0.05

    def __init__(self):
        super().__init__(name="perfbench-heartbeat", daemon=True)
        self.halt = threading.Event()
        self.worst_late_s = 0.0

    def run(self) -> None:
        due = time.perf_counter() + self.PERIOD_S
        while not self.halt.wait(max(due - time.perf_counter(), 0.0)):
            now = time.perf_counter()
            self.worst_late_s = max(self.worst_late_s, now - due)
            due = now + self.PERIOD_S

    def stop(self) -> float:
        self.halt.set()
        self.join()
        return self.worst_late_s


class BatchSpans:
    """Host spans round the scorer's batch function, kept in memory."""

    def __init__(self):
        self.lock = threading.Lock()
        self.spans = []     # (t_start, t_end, rows)

    def add(self, t0: float, t1: float, rows: int) -> None:
        with self.lock:
            self.spans.append((t0, t1, rows))

    def within(self, lo: float, hi: float) -> list:
        with self.lock:
            return [s for s in self.spans if lo <= s[0] and s[1] <= hi]


def build_server(cfg: dict, users: np.ndarray, items: np.ndarray,
                 spans: BatchSpans):
    """A deployed `EngineServer` (event-loop edge, shared batcher, every
    `ServerConfig` value at its default but the port and `microbatch_max`)
    over the seeded model."""
    import jax

    from predictionio_tpu.controller.base import DataSource, WorkflowContext
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSModel, Query,
    )
    from predictionio_tpu.workflow.params import WorkflowParams
    from predictionio_tpu.workflow.train import run_train

    model = ALSModel(
        user_factors=users, item_factors=items,
        users=StringIndex([f"u{j}" for j in range(len(users))]),
        items=StringIndex([f"i{j}" for j in range(len(items))]),
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
    ep = engine.params_from_variant({})
    iid = run_train(engine, ep, ctx=ctx, engine_variant="perfbench.json",
                    workflow_params=WorkflowParams(save_model=False))
    srv = EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(port=0, microbatch_max=cfg["microbatch_max"]),
        engine_variant="perfbench.json",
    )
    srv.start_background()
    return srv


def server_counters(srv) -> dict:
    """The program's own spans and counts, as they stand now."""
    from predictionio_tpu.obs.timeline import (
        SERVE_SEGMENT_SECONDS, SERVE_SEGMENTS,
    )

    segments = {}
    for seg in SERVE_SEGMENTS:
        snap = SERVE_SEGMENT_SECONDS.labels(segment=seg).snapshot()
        segments[seg] = (snap["sum"], snap["count"])
    stats = srv.batcher.stats() if srv.batcher is not None else {}
    return {
        "segments": segments,
        "batches": stats.get("batches", 0),
        "requests": stats.get("requests", 0),
        "compiles": harness.compile_count(),
    }


def counters_delta(before: dict, after: dict) -> dict:
    return {
        "segments": {
            seg: (after["segments"][seg][0] - before["segments"][seg][0],
                  after["segments"][seg][1] - before["segments"][seg][1])
            for seg in after["segments"]
        },
        "batches": after["batches"] - before["batches"],
        "requests": after["requests"] - before["requests"],
        "compiles": after["compiles"] - before["compiles"],
    }


class Generator:
    """The load generator's process: started, told to go, read, reaped."""

    def __init__(self, spec: dict):
        self.proc = subprocess.Popen(
            [sys.executable, str(BENCH_DIR / "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        )
        self.proc.stdin.write(json.dumps(spec) + "\n")
        self.proc.stdin.flush()
        ready = self.proc.stdout.readline()
        if '"ready"' not in ready:
            self.close()
            raise RuntimeError(f"the load generator did not start: {ready!r}")

    def go(self) -> None:
        self.proc.stdin.write("go\n")
        self.proc.stdin.flush()

    def result(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError("the load generator ended without a result")
        return json.loads(line)

    def close(self) -> None:
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


def parse_sample(sample: list, k: int) -> tuple:
    """(user indices, served items [Q, k], served scores [Q, k])."""
    users = np.array([s["user"] for s in sample], np.int64)
    items = np.zeros((len(sample), k), np.int32)
    scores = np.zeros((len(sample), k), np.float32)
    for q, s in enumerate(sample):
        served = json.loads(s["body"])["itemScores"]
        items[q] = [int(x["item"][1:]) for x in served]
        scores[q] = [x["score"] for x in served]
    return users, items, scores


def compare_sample(user_table: np.ndarray, item_table, sample: list,
                   k: int) -> dict:
    """The numbers `correct` compares for served answers: see
    `reference/topk_ref.compare`; and how many answers repeat an item."""
    from .reference import topk_ref

    users, items, scores = parse_sample(sample, k)
    out = topk_ref.compare(user_table[users], item_table, items, scores)
    repeats = sum(len(set(row)) != k for row in items.tolist())
    return {
        "rank_gap": out["rank_gap"], "score_err": out["score_err"],
        "answers_with_repeats": float(repeats),
    }


def run(cell, opts, mode: str) -> dict:
    import jax.numpy as jnp

    cfg, traffic, clock = cell.config, cell.traffic, opts["clock"]
    seed, seconds, log = opts["seed"], opts["seconds"], opts["log"]
    num = int(traffic["num"])
    with clock.phase("data_build_s"):
        user_table, item_table = make_tables(cfg, seed)
        users = loadgen.zipf_users(
            cfg["n_users"], traffic["user_zipf_exponent"],
            traffic["user_pool"], traffic["base_seed"], seed,
        )
    spans = BatchSpans()
    with clock.phase("warmup_s"):
        srv = build_server(cfg, user_table, item_table, spans)
        spec = {
            "host": "127.0.0.1", "port": srv.config.port,
            "path": "/queries.json", "mode": mode, "num": num,
            "seconds": seconds, "users": users,
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
        before = server_counters(srv)
        heartbeat = Heartbeat()
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
        after = server_counters(srv)
    finally:
        gen.close()
        srv.stop()
    peak = harness.memory_peak_bytes()
    peak_in_use = harness.memory_peak_in_use_bytes()
    delta = counters_delta(before, after)
    in_window = spans.within(t_open, t_close)
    in_trace = spans.within(tracer.t0, tracer.t1) if tracer else []
    log(f"window: {result['answered']} answered of {result['attempted']}, "
        f"{delta['batches']} batches")
    del srv
    gc.unfreeze()
    gc.collect()

    t0 = time.perf_counter()
    numbers = compare_sample(user_table, jnp.asarray(item_table),
                             result["sample"], num)
    log(f"reference over {len(result['sample'])} answers "
        f"{time.perf_counter() - t0:.1f}s")
    lat = loadgen.latency_summary(result["latencies_s"], result["failed"])
    if mode == "closed":
        end_to_end = {"serve_rps": result["answered"] / seconds}
    else:
        end_to_end = {"serve_p95_ms": lat["p95_ms"]}
    late = sorted(result["late_s"])
    return {
        "attempted": result["attempted"], "failed": result["failed"],
        "end_to_end": end_to_end, "numbers": numbers,
        "info": {"client_p50_ms": lat["p50_ms"], "client_p95_ms": lat["p95_ms"],
                 "generator_wall_s": result["wall_s"],
                 "memory_peak_in_use_bytes": peak_in_use,
                 # where a stall sat: inside the scorer's call, or between
                 # two calls (batcher, edge, or a host that was not run)
                 "longest_batch_fn_ms": 1e3 * max(
                     (t1 - t0 for t0, t1, _ in in_window), default=0.0),
                 "longest_gap_between_batches_ms": 1e3 * max(
                     (b[0] - a[1] for a, b in zip(in_window, in_window[1:])),
                     default=0.0),
                 "server_heartbeat_worst_late_ms": 1e3 * heartbeat_late_s,
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
            "batch_spans": in_window, "traced_batch_spans": in_trace,
            "late_p95_ms": (loadgen.percentile(late, 95) * 1e3
                            if late else None),
            "shape": {"n_items": cfg["n_items"], "rank": cfg["rank"],
                      "k": 1 << (num - 1).bit_length()},
        },
        "tracer": tracer,
    }
