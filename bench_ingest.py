"""Event-server ingest benchmark: REST path events/s (single + batch-50).

The reference's event server is its highest-traffic surface (spray/akka
on HBase); this measures ours end-to-end — HTTP parse -> auth -> validate
-> sqlite insert — plus the offline importer for contrast.  Prints one
JSON line per mode.

Usage: python bench_ingest.py [--n 2000] [--threads 16]

``--threads N`` adds the concurrent-writer measurement: N clients
hammering ``POST /events.json`` simultaneously.  (A store-level write
coalescer — insert_batch across concurrent requests, the serving
micro-batcher's shape — was built and MEASURED SLOWER here: at 16
clients the wall is per-request HTTP+JSON handling under the GIL, not
the WAL commit, so it was removed.  Throughput writers should use
``/batch/events.json`` — amortizes the whole request path — or the
offline importer.)
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
import urllib.error
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--threads", type=int, default=0,
                    help="also measure N concurrent single-event writers")
    ap.add_argument("--shards", default="",
                    help="comma list of shard counts (e.g. '1,2,4'): "
                    "measure store-level concurrent bulk-write "
                    "throughput per count (the region-parallel write "
                    "analogue; VERDICT r4 #9)")
    ap.add_argument("--append-history", action="store_true",
                    help="append ONE canonical fenced "
                    "ingest_events_per_s record (the batch-50 REST "
                    "path, direction up) to BENCH_HISTORY.jsonl and "
                    "nest it into BENCH_PR<k>.json under 'ingest' — "
                    "tools/bench_gate.py then judges ingest "
                    "throughput like QPS/freshness/recall")
    ap.add_argument("--wal", action="store_true",
                    help="run the server with the pio-levee group-"
                    "commit ingest WAL (ack = WAL fsync, sqlite "
                    "drains in the background) — the --workers fleet "
                    "write path, measured single-process")
    ap.add_argument("--workers", type=int, default=0,
                    help="also measure the multi-process path: N "
                    "shard-owner worker subprocesses behind the "
                    "ingest router, batch-50 through the router "
                    "(separate fenced ingest_multiworker_events_per_s "
                    "record; per-worker scaling recorded honestly "
                    "with nproc)")
    args = ap.parse_args()

    from predictionio_tpu.server.event_server import (
        EventServer, EventServerConfig,
    )
    from predictionio_tpu.storage.registry import Storage

    tmp = tempfile.mkdtemp(prefix="pio_ingest_bench_")
    storage = Storage({"PIO_TPU_HOME": tmp})
    from predictionio_tpu.storage.metadata import AccessKey

    md = storage.get_metadata()
    app = md.app_insert("bench")
    key = md.access_key_insert(AccessKey(key="", appid=app.id))
    server = EventServer(storage, EventServerConfig(
        port=0,
        wal_dir=str(Path(tmp) / "wal") if args.wal else None,
    ))
    server.start_background()
    base = f"http://127.0.0.1:{server.config.port}"
    retried = {"n": 0}

    def post(path, payload):
        """One POST; a structured 503 + Retry-After (pio-levee
        degradation answer) is honored with a backoff-and-retry and
        BOOKED SEPARATELY — never folded into a failure, so a
        transiently degraded shard cannot abort the throughput read."""
        req = urllib.request.Request(
            f"{base}{path}?accessKey={key}",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        for _ in range(10):
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, json.loads(r.read().decode())
            except urllib.error.HTTPError as e:
                ra = e.headers.get("Retry-After")
                if e.code == 503 and ra is not None:
                    retried["n"] += 1
                    time.sleep(min(float(ra), 2.0))
                    continue
                raise
        raise RuntimeError("retry budget exhausted on structured 503s")

    def ev(k):
        return {
            "event": "rate", "entityType": "user", "entityId": f"u{k % 997}",
            "targetEntityType": "item", "targetEntityId": f"i{k % 313}",
            "properties": {"rating": float(k % 5 + 1)},
        }

    # warm + single-event path
    post("/events.json", ev(0))
    t0 = time.perf_counter()
    for k in range(args.n):
        post("/events.json", ev(k))
    dt = time.perf_counter() - t0
    single_v = round(args.n / dt, 1)
    print(json.dumps({
        "metric": "ingest_single_events_per_s",
        "value": single_v, "unit": "events/s",
    }), flush=True)

    # batch path (reference cap: 50/request); the endpoint replies 200
    # with PER-EVENT statuses, so throughput must be self-checking —
    # otherwise rejected events would be counted as ingested
    t0 = time.perf_counter()
    batches = max(args.n // 50, 1)
    for b in range(batches):
        _, body = post(
            "/batch/events.json", [ev(b * 50 + j) for j in range(50)]
        )
        assert all(item.get("status") == 201 for item in body), body[:3]
    dt = time.perf_counter() - t0
    batch_v = round(batches * 50 / dt, 1)
    print(json.dumps({
        "metric": "ingest_batch50_events_per_s",
        "value": batch_v, "unit": "events/s",
    }), flush=True)

    if args.threads > 0:
        import concurrent.futures

        per_thread = max(args.n // args.threads, 25)

        def client(tid):
            for j in range(per_thread):
                post("/events.json", ev(tid * per_thread + j))

        with concurrent.futures.ThreadPoolExecutor(args.threads) as ex:
            list(ex.map(client, range(min(args.threads, 2))))  # warm
            t0 = time.perf_counter()
            list(ex.map(client, range(args.threads)))
            dt = time.perf_counter() - t0
        print(json.dumps({
            "metric": "ingest_concurrent_events_per_s",
            "value": round(args.threads * per_thread / dt, 1),
            "unit": "events/s",
            "threads": args.threads,
        }), flush=True)

    server.stop()

    # offline importer on the same store, for contrast
    from predictionio_tpu.tools.import_export import import_events

    path = Path(tmp) / "bulk.jsonl"
    with open(path, "w") as f:
        for k in range(args.n * 5):
            f.write(json.dumps({**ev(k),
                                "eventTime": "2020-01-01T00:00:00.000Z"})
                    + "\n")
    es = storage.get_event_store()
    t0 = time.perf_counter()
    n = import_events(path, es, app.id)
    dt = time.perf_counter() - t0
    import_v = round(n / dt, 1)
    print(json.dumps({
        "metric": "import_bulk_events_per_s",
        "value": import_v, "unit": "events/s",
    }), flush=True)

    if args.append_history:
        # the canonical gate record: the batch-50 REST path — the
        # documented throughput-writer route is the number production
        # ingest lives or dies by.  Wall time here is device-free and
        # HTTP-round-trip complete, so the timing is fenced by
        # construction.
        sys.path.insert(0, str(Path(__file__).parent / "tools"))
        import bench_gate

        rec = {
            "metric": "ingest_events_per_s",
            "value": batch_v,
            "unit": "events/s",
            "platform": "cpu",
            "scale": float(args.n),
            "fenced": True,
            "direction": "up",
            "mode": "batch50",
            "single_events_per_s": single_v,
            "import_bulk_events_per_s": import_v,
            "store": "sqlite+wal" if args.wal else "sqlite",
            "retried_503": retried["n"],
        }
        bench_gate.append_history(bench_gate.DEFAULT_HISTORY, rec)
        path_out = bench_gate.write_pr_summary(rec, key="ingest")
        print(json.dumps({"appended": "ingest_events_per_s",
                          "pr_summary": str(path_out)}), flush=True)

    if args.workers > 0:
        _bench_multiworker(args, key)

    if args.shards:
        _bench_shard_scaling(args, tmp)


def _bench_multiworker(args, key) -> None:
    """The pio-levee multi-process path: N shard-owner worker
    subprocesses (each with its own ingest WAL) behind the router,
    batch-50 POSTed through the router.  Recorded under its OWN fenced
    metric (``ingest_multiworker_events_per_s``) with worker count and
    ``nproc`` — on a one-core box the workers serialize on the CPU and
    the number says so; the 50k+ ROADMAP target needs real cores."""
    import os as _os
    import tempfile as _tempfile

    from predictionio_tpu.server.ingest_router import (
        IngestRouterConfig, boot_ingest_fleet,
    )

    tmp = _tempfile.mkdtemp(prefix="pio_ingest_fleet_bench_")
    n_shards = max(4, args.workers)
    env = dict(_os.environ)
    env.update({
        "PIO_TPU_HOME": tmp,
        "PIO_STORAGE_SOURCES_LEVEE_TYPE": "sqlite-sharded",
        "PIO_STORAGE_SOURCES_LEVEE_PATH": f"{tmp}/events",
        "PIO_STORAGE_SOURCES_LEVEE_SHARDS": str(n_shards),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "LEVEE",
        "JAX_PLATFORMS": "cpu",
    })
    from predictionio_tpu.storage.metadata import AccessKey
    from predictionio_tpu.storage.registry import Storage

    st = Storage(env)
    st.get_metadata().access_key_insert(
        AccessKey(key=str(key),
                  appid=st.get_metadata().app_insert("bench-fleet").id)
    )
    st.close()
    router, spawned = boot_ingest_fleet(
        args.workers, n_shards, f"{tmp}/coord",
        config=IngestRouterConfig(host="127.0.0.1", port=0,
                                  n_shards=n_shards),
        env=env, respawn=False,
    )
    router.start_background()
    base = f"http://127.0.0.1:{router.port}"

    def ev(k):
        return {
            "event": "rate", "entityType": "user",
            "entityId": f"u{k % 997}",
            "targetEntityType": "item", "targetEntityId": f"i{k % 313}",
            "properties": {"rating": float(k % 5 + 1)},
        }

    def post_batch(items):
        req = urllib.request.Request(
            f"{base}/batch/events.json?accessKey={key}",
            data=json.dumps(items).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read().decode())

    try:
        post_batch([ev(j) for j in range(50)])  # warm
        batches = max(args.n // 50, 1)
        t0 = time.perf_counter()
        for b in range(batches):
            body = post_batch([ev(b * 50 + j) for j in range(50)])
            assert all(item.get("status") == 201 for item in body), \
                body[:3]
        dt = time.perf_counter() - t0
        fleet_v = round(batches * 50 / dt, 1)
    finally:
        router.stop()
        for s in spawned:
            if s["proc"].poll() is None:
                s["proc"].terminate()
        for s in spawned:
            try:
                s["proc"].wait(timeout=10)
            except Exception:
                s["proc"].kill()
    rec = {
        "metric": "ingest_multiworker_events_per_s",
        "value": fleet_v, "unit": "events/s",
        "platform": "cpu", "scale": float(args.n),
        "fenced": True, "direction": "up", "mode": "batch50-router",
        "workers": args.workers, "shards": n_shards,
        "nproc": _os.cpu_count(), "store": "sqlite-sharded+wal",
    }
    print(json.dumps(rec), flush=True)
    if args.append_history:
        sys.path.insert(0, str(Path(__file__).parent / "tools"))
        import bench_gate

        bench_gate.append_history(bench_gate.DEFAULT_HISTORY, rec)
        print(json.dumps(
            {"appended": "ingest_multiworker_events_per_s"}
        ), flush=True)


def _bench_shard_scaling(args, tmp: str) -> None:
    """Store-level concurrent write throughput vs shard count.

    Measures what sharding actually changes — the WRITER LOCK: N
    threads hammer ``insert_raw_rows`` (pre-built rows, minimal python
    per batch, so the per-shard lock + WAL commit is the visible cost)
    against 1..K shard files.  The REST path is deliberately excluded:
    per-request HTTP+JSON under the GIL is its wall (CPU builder
    measurement), and sharding the store cannot amortize that
    from below.  On a single-core host thread-scaling is GIL-bound —
    the ``nproc`` field rides every line so a flat curve reads as the
    environment, not the design."""
    import concurrent.futures
    import os as _os
    import time as _time

    from predictionio_tpu.storage import (
        ShardedSQLiteEventStore, SQLiteEventStore,
    )
    from predictionio_tpu.storage.event import new_event_ids

    writers = max(args.threads, 4)
    n_batches = 40
    rows_per = 1000
    now = int(_time.time() * 1000)

    def rows_for(tid, b):
        base = (tid * n_batches + b) * rows_per
        ids = new_event_ids(rows_per)
        return [
            (ids[j], "rate", "user", f"u{(base + j) % 9973}",
             "item", f"i{(base + j) % 313}", '{"rating":4.0}',
             now + base + j, "[]", None, now)
            for j in range(rows_per)
        ]

    for k in [int(x) for x in args.shards.split(",")]:
        if k == 1:
            store = SQLiteEventStore(Path(tmp) / "scale-1.db")
        else:
            store = ShardedSQLiteEventStore(
                Path(tmp) / f"scale-{k}", n_shards=k
            )
        store.init_channel(1)

        def writer(tid):
            for b in range(n_batches):
                store.insert_raw_rows(rows_for(tid, b), app_id=1)

        with concurrent.futures.ThreadPoolExecutor(writers) as ex:
            list(ex.map(writer, [99]))  # warm: tables + first WAL
            t0 = time.perf_counter()
            list(ex.map(writer, range(writers)))
            dt = time.perf_counter() - t0
        total = writers * n_batches * rows_per
        print(json.dumps({
            "metric": "ingest_sharded_store_events_per_s",
            "value": round(total / dt, 1), "unit": "events/s",
            "shards": k, "writers": writers,
            "nproc": _os.cpu_count(),
        }), flush=True)
        store.close()


if __name__ == "__main__":
    main()
