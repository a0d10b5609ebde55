"""Serving driver for the `ecommercerecommendation` engine with `unseenOnly`:
what `serve.py` does for `recommendation`, for an engine that reads each
batch's users' histories from a live event store inside the turn.

An `EngineServer` in this process over an `ECommModel` made from the seed
(unit item rows drawn on the device; the user table whole, on the host) and
a `MemoryEventStore` that holds the `buy` events of the query pool's users
and one `$set` of `constraint/unavailableItems`; a pool of `{"user", "num"}`
queries, the same for every seed in another order, sent by `loadgen.py`
unedited; the server's own spans and counters read before and after the
window; a sample of the served answers held against
`reference/ecomm_ref.py`, which reads the store itself; and, after the
window, one more `buy` a sampled query and one more `$set`, each of which
the next answer has to honour.

It reuses `serve.py`'s heartbeat, batch spans, counter snapshots and
generator handle, `serve_similar.py`'s item table and filter counters,
`loadgen.py`'s schedule and percentiles, and `train_sweeps.py`'s degrees;
it edits none of them.
"""

from __future__ import annotations

import datetime as dt
import gc
import http.client
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import harness, loadgen, serve, serve_similar
from .drivers import train_sweeps

APP_ID = 1
GOLDEN = 0.6180339887498949
TABLE_CHUNK = 1 << 20       # user rows drawn at a time, a thread each
SENT_FIRST = 1024           # the longest histories are sent among these


def require_ids_on_the_device(cfg: dict, traffic: dict) -> None:
    """Exit 2, at once, on a program that cannot keep this pool's longest
    list of excluded ids on the device path: its e-commerce engine would
    build a `[B, M]` float32 mask on the host for every batch (2.4 GB at
    64 rows over 9.35 M items) and answer a handful of requests a second."""
    longest = int(traffic["history_max"]) + int(cfg["unavailable_items"])
    try:
        from predictionio_tpu.ops.topk import exclude_width
        from predictionio_tpu.templates import ecommerce

        fits = (exclude_width(longest) >= longest
                and hasattr(ecommerce, "batch_filter"))
    except ImportError:
        fits = False
    if not fits:
        print(f"perfbench: this program cannot keep a list of {longest} "
              "excluded ids on the device path (ops/topk.EXCLUDE_LADDER "
              "ends below it, or templates/ecommerce.py masks on the "
              "host); it cannot run this cell", file=sys.stderr)
        raise SystemExit(2)


# -- the deployment's data, from the seed ------------------------------------


def degrees(n: int, exponent: float, total: int, cap: int) -> np.ndarray:
    """`ials-amazon14-r128-x4`'s degrees: every row one event, the rest by
    `train_sweeps.capped_power_law`; the same for every seed."""
    return 1 + train_sweeps.capped_power_law(n, exponent, total - n, cap - 1)


def make_pool(traffic: dict, counts_u: np.ndarray) -> np.ndarray:
    """`query_pool` user indices, each drawn with probability proportional
    to the user's count of events among the users with at most
    `history_max`; from `base_seed`: the same pool for every seed."""
    rng = np.random.default_rng(traffic["base_seed"])
    weight = np.where(counts_u <= traffic["history_max"], counts_u, 0)
    cum = np.cumsum(weight, dtype=np.float64)
    draws = rng.random(traffic["query_pool"]) * cum[-1]
    return np.minimum(np.searchsorted(cum, draws, side="right"),
                      len(counts_u) - 1)


def histories(cfg: dict, seed: int, counts_u: np.ndarray,
              users: np.ndarray) -> tuple:
    """(offsets, items): the events of each of `users` (distinct, sorted),
    oldest first, `items[offsets[j]:offsets[j + 1]]` those of `users[j]`.
    Event p of the table (in user order) takes the item at place
    (a*p + b) mod n of the item column sorted by item:
    `train_sweeps.make_ratings`' walk, a and b from the seed, computed for
    these users' events alone."""
    n = cfg["n_events"]
    counts_i = degrees(cfg["n_items"], cfg["item_exponent"], n,
                       cfg["item_max_events"])
    rng = np.random.default_rng(seed)
    stride = int(n * GOLDEN) + int(rng.integers(0, max(n // 64, 1)))
    while np.gcd(stride, n) != 1:
        stride += 1
    shift = int(rng.integers(0, n))
    mine = counts_u[users].astype(np.int64)
    offsets = np.concatenate(([0], np.cumsum(mine)))
    first = np.cumsum(counts_u, dtype=np.int64)[users] - mine
    p = (np.repeat(first - offsets[:-1], mine)
         + np.arange(offsets[-1], dtype=np.int64))
    place = (p * stride + shift) % n
    items = np.searchsorted(np.cumsum(counts_i, dtype=np.int64), place,
                            side="right")
    return offsets, items.astype(np.int32)


def make_user_table(cfg: dict, seed: int, item_table: np.ndarray,
                    users: np.ndarray, offsets: np.ndarray,
                    items: np.ndarray) -> np.ndarray:
    """The whole user table `[n_users, R]`, float32, on the host:
    N(0, 1)/sqrt(R) rows drawn a chunk a thread, and for the pool's users
    `sum_j 2**-j v(s_j)` over the user's `recent_items` most recent items
    s_0, s_1, ... (what a trained implicit model does roughly: the user
    lies near the user's items, so seen items rank first)."""
    n, rank = cfg["n_users"], cfg["rank"]
    table = np.empty((n, rank), np.float32)
    scale = np.float32(1.0 / np.sqrt(rank))

    def draw(lo: int) -> None:
        part = table[lo:lo + TABLE_CHUNK]
        np.random.default_rng(
            [seed & 0x7FFFFFFF, seed >> 31, 11, lo]
        ).standard_normal(dtype=np.float32, out=part)
        part *= scale

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(draw, range(0, n, TABLE_CHUNK)))
    counts = np.diff(offsets)
    rows = np.zeros((len(users), rank), np.float32)
    for j in range(cfg["recent_items"]):
        has = counts > j
        rows[has] += np.float32(2.0 ** -j) * item_table[
            items[offsets[1:][has] - 1 - j]]
    table[users] = rows
    return table


def make_unavailable(cfg: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed & 0x7FFFFFFF, seed >> 31, 13])
    return rng.choice(cfg["n_items"], cfg["unavailable_items"],
                      replace=False)


def fill_store(store, users: np.ndarray, offsets: np.ndarray,
               items: np.ndarray, unavailable: np.ndarray) -> int:
    """The pool's users' `buy` events, oldest first, and the one `$set` of
    `constraint/unavailableItems`, through the store's own insert."""
    from predictionio_tpu.storage import DataMap, Event

    store.init_channel(APP_ID)
    t0 = dt.datetime(2014, 7, 1, tzinfo=dt.timezone.utc)
    none = DataMap()

    def events():
        n = 0
        for j, user in enumerate(users.tolist()):
            uid = f"u{user}"
            for age, item in enumerate(items[offsets[j]:offsets[j + 1]]
                                       .tolist()):
                yield Event(
                    event="buy", entity_type="user", entity_id=uid,
                    target_entity_type="item", target_entity_id=f"i{item}",
                    properties=none, event_time=t0 + dt.timedelta(seconds=age),
                    creation_time=t0, event_id=f"b{n}")
                n += 1

    store.insert_batch(events(), APP_ID, validate=False)
    set_unavailable(store, unavailable.tolist(), t0)
    return int(offsets[-1])


def set_unavailable(store, item_ixs: list, when=None) -> None:
    from predictionio_tpu.storage import DataMap, Event
    from predictionio_tpu.storage.event import now_utc

    store.insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": [f"i{ix}" for ix in item_ixs]}),
        event_time=when or now_utc()), APP_ID)


def build_server(cfg: dict, user_table: np.ndarray, item_table: np.ndarray,
                 storage, spans: serve.BatchSpans):
    """(server, model): a deployed `EngineServer` (event-loop edge, shared
    batcher, every `ServerConfig` value at its default but the port and
    `microbatch_max`) over the seeded `ECommModel`, the engine's parameters
    as the documented engine.json has them."""
    import jax

    from predictionio_tpu.controller.base import DataSource, WorkflowContext
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.ecommerce import ECommAlgorithm, ECommModel
    from predictionio_tpu.templates.recommendation import Query
    from predictionio_tpu.workflow.params import WorkflowParams
    from predictionio_tpu.workflow.train import run_train

    model = ECommModel(
        user_factors=user_table, item_factors=item_table,
        users=StringIndex([f"u{j}" for j in range(len(user_table))]),
        items=StringIndex([f"i{j}" for j in range(len(item_table))]),
        item_props={}, app_id=APP_ID,
    )

    class Source(DataSource):
        def read_training(self, ctx):
            return None

    class SeededEComm(ECommAlgorithm):
        query_class = Query

        def train(self, ctx, data):
            return model

        def batch_predict(self, mdl, queries):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.batch_fn"):
                out = super().batch_predict(mdl, queries)
            spans.add(t0, time.perf_counter(), len(queries))
            return out

    ctx = WorkflowContext(storage=storage)
    engine = SimpleEngine(Source, SeededEComm)
    ep = engine.params_from_variant({"algorithms": [{"name": "", "params": {
        "rank": cfg["rank"], "unseenOnly": cfg["unseenOnly"],
        "seenEvents": cfg["seenEvents"]}}]})
    iid = run_train(engine, ep, ctx=ctx, engine_variant="perfbench.json",
                    workflow_params=WorkflowParams(save_model=False))
    srv = EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(port=0, microbatch_max=cfg["microbatch_max"]),
        engine_variant="perfbench.json",
    )
    srv.start_background()
    return srv, model


def memory_storage():
    from predictionio_tpu.storage.registry import Storage

    return Storage({
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM2",
        "PIO_STORAGE_SOURCES_MEM2_TYPE": "memory",
    })


# -- the program's counters ---------------------------------------------------


def program_counters() -> dict:
    """`serve_similar.program_counters` and what this engine adds: the
    seen reads' summed seconds and count (`pio_seen_read_seconds`), ids
    read, failed reads, batches by the ids array's width
    (`pio_filter_exclude_width_total{width}`) and ids dispatched."""
    from predictionio_tpu.templates import _common, ecommerce

    read = ecommerce.SEEN_READ_SECONDS.snapshot()
    out = serve_similar.program_counters()
    out.update({
        "seen_read": (read["sum"], read["count"]),
        "seen_events": ecommerce.SEEN_EVENTS.value(),
        "seen_read_failures": ecommerce.SEEN_READ_FAILURES.value(),
        "exclude_width_batches": {
            dict(key)["width"]: child.value()
            for key, child in _common.FILTER_EXCLUDE_WIDTH.children()},
        "excluded_ids": _common.FILTER_EXCLUDED_IDS.value(),
    })
    return out


def program_counters_delta(before: dict, after: dict) -> dict:
    out = serve_similar.program_counters_delta(before, after)
    out["seen_read"] = tuple(
        now - was for now, was in zip(after["seen_read"],
                                      before["seen_read"]))
    for name in ("seen_events", "seen_read_failures", "excluded_ids"):
        out[name] = after[name] - before[name]
    out["exclude_width_batches"] = {
        width: n - before["exclude_width_batches"].get(width, 0.0)
        for width, n in after["exclude_width_batches"].items()}
    return out


# -- the sample, the reference, and the store after the window ---------------


def send_order(pool: np.ndarray, longest: np.ndarray, seed: int,
               n_first: int) -> np.ndarray:
    """The pool in the seed's order, with the `longest` queries (indices
    into the pool) moved to places the seed draws among the first
    `n_first` sent, so that the window answers them."""
    rng = np.random.default_rng([seed & 0x7FFFFFFF, seed >> 31, 17])
    order = rng.permutation(len(pool))
    n_first = max(min(n_first, len(pool)), len(longest))
    where = {int(q): at for at, q in enumerate(order.tolist())}
    for q, to in zip(longest.tolist(),
                     rng.choice(n_first, len(longest), replace=False)):
        at = where[q]
        other = int(order[to])
        order[to], order[at] = q, other
        where[q], where[other] = int(to), at
    return order


def choose_sample(kept: list, must: set, take: int, seed: int) -> list:
    """`take` of the answers the window kept: one for each user of `must`
    that was answered, the rest drawn from the seed."""
    rng = np.random.default_rng([seed & 0x7FFFFFFF, seed >> 31, 19])
    chosen, seen = [], set()
    for j, s in enumerate(kept):
        if s["user"] in must and s["user"] not in seen:
            seen.add(s["user"])
            chosen.append(j)
    taken = set(chosen)
    rest = [j for j in range(len(kept)) if j not in taken]
    more = max(min(take, len(kept)) - len(chosen), 0)
    chosen += rng.choice(rest, more, replace=False).tolist() if more else []
    return [kept[j] for j in sorted(chosen[:take])]


def served(body: str) -> tuple:
    scores = json.loads(body)["itemScores"]
    return ([int(x["item"][1:]) for x in scores],
            [x["score"] for x in scores])


def ask(port: int, user: int, num: int):
    """One query over HTTP, outside every timed number: the served items,
    or None where the answer is not status 200 with `num` of them."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/queries.json",
                     json.dumps({"user": f"u{user}", "num": num}),
                     {"Content-Type": "application/json"})
        response = conn.getresponse()
        body = response.read().decode()
    finally:
        conn.close()
    if response.status != 200:
        return None
    try:
        items = served(body)[0]
    except (ValueError, KeyError, TypeError):
        return None
    return items if len(items) == num else None


def count_stale(store, port: int, sample: list, unavailable: np.ndarray,
                num: int) -> int:
    """After the window: for each sampled query one `buy` of its answer's
    first item through the store's insert, then the query again: the item
    has to be gone.  Then, once, `unavailableItems` is `$set` to its list
    and the first item of the last answer, and that query asked again.  A
    query asked again that is not answered with `num` items counts as
    stale: nothing came back to show the write was honoured."""
    from predictionio_tpu.storage import Event

    stale, last = 0, None
    for s in sample:
        items = served(s["body"])[0]
        if not items:
            continue
        store.insert(Event(
            event="buy", entity_type="user", entity_id=f"u{s['user']}",
            target_entity_type="item", target_entity_id=f"i{items[0]}"),
            APP_ID)
        again = ask(port, s["user"], num)
        stale += again is None or items[0] in again
        if again:
            last = (s["user"], again)
    if last is not None:
        set_unavailable(store, unavailable.tolist() + [last[1][0]])
        final = ask(port, last[0], num)
        stale += final is None or last[1][0] in final
    return stale


def longest_gc_pause(lo: float, hi: float) -> list:
    """`[ms, generation]` of the longest collection that began inside the
    window, from the program's own hook."""
    from predictionio_tpu.obs.gcpause import pauses

    dt, generation = max(((dt, g) for t0, dt, g in pauses() if lo <= t0 <= hi),
                         default=(0.0, None))
    return [1e3 * dt, generation]


def reference_lists(store, cfg: dict, sample: list) -> list:
    """What the reference finds excluded for each sampled query, read by
    itself from the store as it stands (before `count_stale` writes)."""
    from .reference import ecomm_ref

    users = [f"u{s['user']}" for s in sample]
    seen, unavailable = ecomm_ref.read_store(store, APP_ID, users,
                                             cfg["seenEvents"])
    gone = {int(i[1:]) for i in unavailable}
    return [gone | {int(i[1:]) for i in seen[user]} for user in users]


def compare_sample(user_table: np.ndarray, item_table: np.ndarray,
                   sample: list, excluded: list, num: int) -> dict:
    import jax.numpy as jnp

    from .reference import ecomm_ref

    items, scores = zip(*(served(s["body"]) for s in sample)) \
        if sample else ((), ())
    out = ecomm_ref.compare(
        user_table[[s["user"] for s in sample]], jnp.asarray(item_table),
        excluded, list(items), list(scores), num)
    return {name: out[name] for name in (
        "rank_gap", "score_err", "answers_with_repeats",
        "answers_with_excluded", "answers_filter_blind")}


def run(cell, opts, mode: str) -> dict:
    cfg, traffic, clock = cell.config, cell.traffic, opts["clock"]
    seed, seconds, log = opts["seed"], opts["seconds"], opts["log"]
    num = int(traffic["num"])
    storage = memory_storage()
    store = storage.get_event_store()
    with clock.phase("data_build_s"):
        t0 = time.perf_counter()
        item_table = np.array(serve_similar.make_items(cfg, seed))
        log(f"items made in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        counts_u = degrees(cfg["n_users"], cfg["user_exponent"],
                           cfg["n_events"], cfg["user_max_events"])
        pool = make_pool(traffic, counts_u)
        users = np.unique(pool)
        offsets, items = histories(cfg, seed, counts_u, users)
        lengths = counts_u[pool]
        log(f"pool of {len(pool)} queries, {len(users)} users, "
            f"{len(items)} events in {time.perf_counter() - t0:.1f}s; "
            f"history mean {lengths.mean():.1f}, median "
            f"{np.median(lengths):.0f}, p90 {np.percentile(lengths, 90):.0f}"
            f", p99 {np.percentile(lengths, 99):.0f}, max {lengths.max()}")
        t0 = time.perf_counter()
        user_table = make_user_table(cfg, seed, item_table, users, offsets,
                                     items)
        log(f"user table in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        unavailable = make_unavailable(cfg, seed)
        n_events = fill_store(store, users, offsets, items, unavailable)
        log(f"{n_events} events stored in {time.perf_counter() - t0:.1f}s")
        del offsets, items
    spans = serve.BatchSpans()
    with clock.phase("warmup_s"):
        srv, model = build_server(cfg, user_table, item_table, storage, spans)
        n_check = cfg["check"]["longest"]
        longest = np.argsort(-lengths, kind="stable")[:n_check]
        arrivals = None
        if mode == "open":
            arrivals = loadgen.arrival_offsets(
                traffic["rate_per_s"], seconds, traffic["base_seed"], seed)
        n_first = min(SENT_FIRST, len(arrivals) if arrivals else SENT_FIRST)
        order = send_order(pool, longest, seed, n_first)
        spec = {
            "host": "127.0.0.1", "port": srv.config.port,
            "path": "/queries.json", "mode": mode, "num": num,
            "seconds": seconds, "users": pool[order].tolist(),
            "connections": traffic["connections"],
            # every kept answer comes back: the sample is chosen here, to
            # hold the longest histories
            "sample": 1 << 30, "sample_seed": seed,
        }
        if arrivals is not None:
            spec["arrivals"] = arrivals
        gen = serve.Generator(spec)
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
        # outside every timed number: the reference's own read of the
        # store as the window left it, then the writes that the next
        # answers have to honour
        t0 = time.perf_counter()
        sample = choose_sample(result["sample"], set(pool[longest].tolist()),
                               cfg["check"]["answers"], seed)
        excluded = reference_lists(store, cfg, sample)
        stale = count_stale(store, srv.config.port, sample, unavailable, num)
        # every read of the process: warm requests, window, and these
        failures = program_counters()["seen_read_failures"]
        log(f"store read by the reference, {len(sample)} queries asked "
            f"again in {time.perf_counter() - t0:.1f}s: {stale} stale")
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
        f"{program.get('filter_rows')}, batches by width "
        f"{program['exclude_width_batches']}, calls by path "
        f"{program.get('topk_paths')}")
    del srv
    serve_similar.release_device_tables(model)
    gc.unfreeze()
    gc.collect()

    t0 = time.perf_counter()
    numbers = compare_sample(user_table, item_table, sample, excluded, num)
    numbers["answers_stale"] = float(stale)
    numbers["seen_read_failures"] = float(failures)
    log(f"reference over {len(sample)} answers "
        f"{time.perf_counter() - t0:.1f}s")
    lat = loadgen.latency_summary(result["latencies_s"], result["failed"])
    if mode == "closed":
        end_to_end = {"serve_rps": result["answered"] / seconds}
    else:
        end_to_end = {"serve_p95_ms": lat["p95_ms"]}
    late = sorted(result["late_s"])
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
        # every Python thread waits a collection out: was the stall one
        "longest_gc_pause_ms_and_generation": longest_gc_pause(t_open,
                                                                t_close),
        "generator_longest_silence_ms": 1e3 * result["longest_silence_s"],
        "generator_worst_late_ms": 1e3 * max(late, default=0.0),
        "reference_s": time.perf_counter() - t0,
        # what compiled inside the window, if anything did
        "compiles_by_fn_in_window": {
            name: n for name, n in program["compiles"].items() if n},
        "rows_by_filter_in_window": program["filter_rows"],
        "batches_by_exclude_width_in_window":
            program["exclude_width_batches"],
        "excluded_ids_in_window": program["excluded_ids"],
        "seen_events_read_in_window": program["seen_events"],
        "calls_by_path_in_window": program["topk_paths"],
        "events_stored": n_events,
        "longest_sampled_list": max(map(len, excluded), default=0),
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
                      "k": 1 << (num - 1).bit_length()},
            "filter_rows": program["filter_rows"],
            "filter_build": program["filter_build"],
            "seen_read": program["seen_read"],
            "exclude_width_batches": program["exclude_width_batches"],
        },
        "tracer": tracer,
    }
