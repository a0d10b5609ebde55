#!/usr/bin/env python
"""pio-lens end-to-end smoke: fleet observability over real processes
(`tests/test_fleet_smoke.py` runs it inside the gate).

Boots TWO real replica subprocesses (full `pio-tpu deploy`, event-loop
edge, --slo-ms armed, span journaling on) behind an in-process
RouterServer, then proves the fleet-lens contract:

* ``merged_exposition``  — the router's ``GET /metrics`` is a
  grammar-valid merged exposition (parsed by the STRICT
  ``fleet.parse_prometheus``) whose ``pio_queries_total`` equals the
  sum of the replicas' own expositions, with per-replica burn-rate
  gauges present.
* ``tail_attribution``   — one replica is SIGSTOPped mid-load; every
  client request still answers 200 (failover masks the stall), and the
  router flight recorder's worst-N names the stalled replica as the
  one that ate the tail (``failedReplicas`` / segment split), while
  the merged exposition stays parseable and MONOTONE through the
  stall (stale snapshot stands; ``pio_replica_scrape_errors_total``
  books the failed scrapes).
* ``tracecat_stitches``  — one trace id stitches into a SINGLE tree
  spanning the router's ``router.request``/``router.forward`` spans
  and the replica's ``serve.query`` span, across two processes'
  journals, via ``tools/tracecat.py``.

Usage::

    python tools/fleet_smoke.py --out fleet_smoke.json
"""

from __future__ import annotations

import argparse
import concurrent.futures
import datetime as dt
import json
import os
import signal
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))

UTC = dt.timezone.utc


def _post(url, payload, timeout=30, headers=None):
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read().decode())


def _get(url, timeout=30, raw=False):
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout) as r:
        body = r.read().decode()
        return r.status, (body if raw else json.loads(body))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="fleet_smoke.json")
    ap.add_argument("--seed", type=int, default=20260805)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        # one process per chip: this one trains in-process (taking the
        # chip) and THEN spawns replica processes that would need it
        print("fleet_smoke: trains in this process, then spawns replica "
              "processes; on an accelerator the parent would hold the "
              "chip its replicas need.  Run it with JAX_PLATFORMS=cpu.",
              file=sys.stderr)
        return 2

    home = tempfile.mkdtemp(prefix="pio_fleet_smoke_")
    telemetry = os.path.join(home, "telemetry")
    storage_env = {
        "PIO_TPU_HOME": home,
        "PIO_TPU_TELEMETRY_DIR": telemetry,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQLITE",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQLITEMD",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "LOCALFS",
        "PIO_STORAGE_SOURCES_SQLITE_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQLITE_PATH": os.path.join(home, "events.db"),
        "PIO_STORAGE_SOURCES_SQLITEMD_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQLITEMD_PATH": os.path.join(home, "md.db"),
        "PIO_STORAGE_SOURCES_LOCALFS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_LOCALFS_PATH": os.path.join(home, "models"),
    }
    # the router process (THIS process) must journal its spans too —
    # set before the first predictionio_tpu import resolves the tracer
    os.environ["PIO_TPU_TELEMETRY_DIR"] = telemetry

    import numpy as np

    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.obs import fleet
    from predictionio_tpu.server.router import (
        Replica, RouterConfig, RouterServer, spawn_replica,
        wait_for_port_file,
    )
    from predictionio_tpu.storage import DataMap, Event
    from predictionio_tpu.storage.registry import Storage
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine,
    )
    from predictionio_tpu.workflow import run_train

    import tracecat

    stages: dict[str, float] = {}
    invariants: dict[str, bool] = {}

    def stage(name):
        class _T:
            def __enter__(self):
                self.t0 = time.time()

            def __exit__(self, *exc):
                stages[name] = round(time.time() - self.t0, 3)

        return _T()

    storage = Storage(env=storage_env)
    md = storage.get_metadata()
    app = md.app_insert("fleetsmoke")
    es = storage.get_event_store()
    es.init_channel(app.id)

    engine_dir = Path(home) / "engine"
    engine_dir.mkdir()
    engine_json = engine_dir / "engine.json"
    variant = {
        "id": "fleet",
        "engineFactory":
            "predictionio_tpu.templates.recommendation."
            "recommendation_engine",
        "datasource": {"params": {"appName": "fleetsmoke"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 8, "numIterations": 5, "lambda": 0.05}}],
    }
    engine_json.write_text(json.dumps(variant, indent=1))

    with stage("train"):
        rng = np.random.default_rng(args.seed)
        evs = []
        for u in range(8):
            group = u % 2
            for i in range(8):
                if rng.random() < (0.9 if (i % 2) == group else 0.2):
                    evs.append(Event(
                        event="rate", entity_type="user",
                        entity_id=f"u{u}",
                        target_entity_type="item",
                        target_entity_id=f"i{i}",
                        properties=DataMap(
                            {"rating": 5.0 if (i % 2) == group else 1.0}
                        ),
                        event_time=dt.datetime(2020, 1, 1, tzinfo=UTC),
                    ))
        es.insert_batch(evs, app_id=app.id)
        ctx = WorkflowContext(storage=storage)
        engine = recommendation_engine()
        ep = engine.params_from_variant(variant)
        run_train(engine, ep, ctx=ctx, engine_id="fleet",
                  engine_variant=str(engine_json))

    child_env = dict(os.environ)
    child_env.update(storage_env)
    child_env["JAX_PLATFORMS"] = "cpu"
    coord = Path(home) / "fleet"
    procs = []
    with stage("spawn_fleet"):
        for i in range(2):
            procs.append(spawn_replica(
                engine_json, i, coord, env=child_env,
                extra_args=["--microbatch", "auto",
                            "--slo-ms", "50"],
            ))
        replicas = []
        for s in procs:
            port = wait_for_port_file(s, timeout_s=240.0)
            replicas.append(
                Replica(f"replica-{s['index']}", "127.0.0.1", port)
            )
        router = RouterServer(replicas, RouterConfig(
            host="127.0.0.1", port=0, health_interval_s=0.25,
            health_timeout_s=0.75, forward_timeout_s=1.5,
            slo_ms=50.0,
        ))
        router.start_background()
        base = f"http://127.0.0.1:{router.port}"
        deadline = time.time() + 60
        up = 0
        while time.time() < deadline:
            try:
                _, snap = _get(base + "/")
                up = snap["healthyReplicas"]
                if up == 2:
                    break
            except Exception:
                pass
            time.sleep(0.2)
        assert up == 2, "replicas never became healthy"

    def merged_ok_total():
        _, text = _get(base + "/metrics", raw=True)
        state = fleet.parse_prometheus(text)  # raises on bad grammar
        return fleet.state_counter_total(
            state, "pio_queries_total", where={"status": "ok"}
        ), text

    rc = 1
    stopped_pid = None
    try:
        # ---- merged exposition == sum of the replicas' ------------------
        with stage("merged_exposition"):
            n_queries = 24
            for k in range(n_queries):
                code, _ = _post(
                    base + "/queries.json",
                    {"user": f"u{k % 8}", "num": 3},
                    headers={"X-PIO-Trace": f"t-fleetsmoke-{k}"},
                )
                assert code == 200
            deadline = time.time() + 20
            total = 0.0
            while time.time() < deadline:
                total, text = merged_ok_total()
                if total >= n_queries:
                    break
                time.sleep(0.25)
            replica_sum = 0.0
            for r in replicas:
                _, rtext = _get(r.url + "/metrics", raw=True)
                replica_sum += fleet.state_counter_total(
                    fleet.parse_prometheus(rtext),
                    "pio_queries_total", where={"status": "ok"},
                )
            burn_ok = "pio_slo_burn_rate" in text and \
                'window="1m"' in text
            invariants["merged_exposition"] = (
                total == replica_sum == float(n_queries) and burn_ok
            )

        # ---- SIGSTOP one replica: the tail names it ---------------------
        with stage("tail_attribution"):
            totals = [merged_ok_total()[0]]
            stopped = procs[0]["proc"]
            stopped_pid = stopped.pid
            stop_flag = threading.Event()
            results = []

            def client(wid):
                k = 0
                while not stop_flag.is_set():
                    try:
                        code, _ = _post(
                            base + "/queries.json",
                            {"user": f"u{wid}", "num": 3}, timeout=30,
                        )
                        results.append(code)
                    except Exception as e:
                        results.append(f"exc:{type(e).__name__}")
                    k += 1

            with concurrent.futures.ThreadPoolExecutor(4) as ex:
                futs = [ex.submit(client, w) for w in range(4)]
                time.sleep(0.5)
                os.kill(stopped_pid, signal.SIGSTOP)
                t_end = time.time() + 4.0
                while time.time() < t_end:
                    totals.append(merged_ok_total()[0])
                    time.sleep(0.5)
                stop_flag.set()
                for f in futs:
                    f.result(60)
            totals.append(merged_ok_total()[0])
            monotone = all(a <= b for a, b in zip(totals, totals[1:]))
            _, doc = _get(base + "/debug/fleet")
            worst = doc.get("worst", [])
            named = [
                w for w in worst
                if "replica-0" in (w.get("attrs", {})
                                   .get("failedReplicas") or [])
                or w.get("attrs", {}).get("replica") == "replica-0"
            ]
            tail_named = bool(named) and any(
                w["durationSec"] >= 1.0 for w in named
            )
            all_served = (
                len(results) > 10
                and all(c == 200 for c in results)
            )
            scrapes_booked = doc.get("scrapeErrors", 0) >= 1
            stages["tail_detail"] = {  # debuggability: which leg broke
                "allServed": all_served,
                "tailNamed": tail_named,
                "monotone": monotone,
                "scrapesBooked": scrapes_booked,
                "results": len(results),
                "non200": [c for c in results if c != 200][:5],
                "worstTop": worst[:2],
                "totals": totals,
            }
            invariants["tail_attribution"] = (
                all_served and tail_named and monotone
                and scrapes_booked
            )

        # ---- tracecat: one stitched tree across processes ---------------
        with stage("tracecat_stitches"):
            ok = False
            for k in range(n_queries):
                tid = f"t-fleetsmoke-{k}"
                spans = tracecat.collect_spans(tid, Path(telemetry))
                if len(spans) < 2:
                    continue
                pids = {s.get("pid") for s in spans}
                roots = tracecat.build_tree(spans)
                names_in_tree = set()

                def walk(n):
                    names_in_tree.add(n["name"])
                    for c in n["children"]:
                        walk(c)

                for r in roots:
                    walk(r)
                if (len(roots) == 1
                        and roots[0]["name"] == "router.request"
                        and "serve.query" in names_in_tree
                        and len(pids) >= 2):
                    # the CLI renders the same stitched tree
                    text = tracecat.render_tree(
                        tid, roots, len(spans), len(pids))
                    ok = ("router.request" in text
                          and "serve.query" in text)
                    if ok:
                        print(text)
                        break
            invariants["tracecat_stitches"] = ok

        rc = 0 if all(invariants.values()) and len(invariants) == 3 \
            else 1
    finally:
        if stopped_pid is not None:
            try:
                os.kill(stopped_pid, signal.SIGCONT)
            except OSError:
                pass
        try:
            router.stop()
        except Exception:
            pass
        for s in procs:
            if s["proc"].poll() is None:
                s["proc"].terminate()
        for s in procs:
            try:
                s["proc"].wait(timeout=10)
            except Exception:
                s["proc"].kill()
        out = {
            "metric": "fleet_smoke",
            "seed": args.seed,
            "stages": stages,
            "invariants": invariants,
            "ok": all(invariants.values()) and len(invariants) == 3,
        }
        Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
        print(json.dumps(out, indent=2))
    return rc


if __name__ == "__main__":
    sys.exit(main())
