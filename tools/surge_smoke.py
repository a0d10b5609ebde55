#!/usr/bin/env python
"""pio-surge end-to-end smoke: router + replica fleet over real
processes (`tests/test_surge_smoke.py` runs it inside the gate).

Boots TWO real replica subprocesses (each a full `pio-tpu deploy` on
the event-loop edge, announcing its ephemeral port through a port
file) behind an in-process RouterServer over sqlite-backed storage,
then proves the fleet contract:

* ``fleet_serves``            — queries through the router answer 200
  and BOTH replicas take a share (round-robin is real).
* ``rolling_push_freshens``   — events for an unseen user + one
  fold-in cycle + ``POST /admin/push-foldin``: both replicas answer
  non-fallback predictions for the new user with **zero** ``/reload``
  calls and unchanged instance ids (the delta applied in place,
  rolling across the fleet).
* ``kill_masked``             — one replica is SIGKILLed mid-load;
  every in-flight and subsequent client request still answers 200
  (failover masks the death) and the router status shows exactly one
  healthy replica.

Usage::

    python tools/surge_smoke.py --out surge_smoke.json
"""

from __future__ import annotations

import argparse
import concurrent.futures
import datetime as dt
import http.client
import json
import os
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

UTC = dt.timezone.utc


def _post(url, payload, timeout=30):
    import urllib.request

    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
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
    ap.add_argument("--out", default="surge_smoke.json")
    ap.add_argument("--seed", type=int, default=20260805)
    args = ap.parse_args(argv)
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        # one process per chip: this one trains in-process (taking the
        # chip) and THEN spawns replica processes that would need it
        print("surge_smoke: trains in this process, then spawns replica "
              "processes; on an accelerator the parent would hold the "
              "chip its replicas need.  Run it with JAX_PLATFORMS=cpu.",
              file=sys.stderr)
        return 2

    import numpy as np

    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.live import FoldInRunner
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

    stages: dict[str, float] = {}
    invariants: dict[str, bool] = {}

    def stage(name):
        class _T:
            def __enter__(self):
                self.t0 = time.time()

            def __exit__(self, *exc):
                stages[name] = round(time.time() - self.t0, 3)

        return _T()

    home = tempfile.mkdtemp(prefix="pio_surge_smoke_")
    storage_env = {
        "PIO_TPU_HOME": home,
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
    storage = Storage(env=storage_env)
    md = storage.get_metadata()
    app = md.app_insert("surgesmoke")
    es = storage.get_event_store()
    es.init_channel(app.id)

    engine_dir = Path(home) / "engine"
    engine_dir.mkdir()
    engine_json = engine_dir / "engine.json"
    variant = {
        "id": "surge",
        "engineFactory":
            "predictionio_tpu.templates.recommendation."
            "recommendation_engine",
        "datasource": {"params": {"appName": "surgesmoke"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 8, "numIterations": 5, "lambda": 0.05}}],
    }
    engine_json.write_text(json.dumps(variant, indent=1))

    # ---- train a tiny model WITHOUT the cold-start user ------------------
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
        iid = run_train(engine, ep, ctx=ctx,
                        engine_id="surge",
                        engine_variant=str(engine_json))

    # ---- spawn 2 REAL replica processes + the router --------------------
    child_env = dict(os.environ)
    child_env.update(storage_env)
    child_env["JAX_PLATFORMS"] = "cpu"
    coord = Path(home) / "fleet"
    procs = []
    with stage("spawn_fleet"):
        for i in range(2):
            procs.append(spawn_replica(
                engine_json, i, coord, env=child_env,
                extra_args=["--microbatch", "auto"],
            ))
        replicas = []
        for s in procs:
            port = wait_for_port_file(s, timeout_s=240.0)
            replicas.append(
                Replica(f"replica-{s['index']}", "127.0.0.1", port)
            )
        router = RouterServer(replicas, RouterConfig(
            host="127.0.0.1", port=0, health_interval_s=0.25,
        ))
        router.start_background()
        base = f"http://127.0.0.1:{router.port}"
        # wait for both replicas to actually answer through the router
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

    rc = 1
    try:
        # ---- both replicas take traffic through the router --------------
        with stage("fleet_serves"):
            statuses = []
            for k in range(24):
                code, _ = _post(base + "/queries.json",
                                {"user": f"u{k % 8}", "num": 3})
                statuses.append(code)
            _, snap = _get(base + "/")
            shares = {r["name"]: r["forwarded"] for r in snap["replicas"]}
            invariants["fleet_serves"] = (
                all(c == 200 for c in statuses)
                and min(shares.values()) >= 6
            )

        # ---- fold-in delta + rolling push across the fleet --------------
        with stage("rolling_push_freshens"):
            before = {}
            for r in replicas:
                _, st = _get(r.url + "/")
                before[r.name] = st["engineInstanceId"]
            # cold: both replicas fall back for the unseen user
            cold_ok = True
            for r in replicas:
                _, cold = _post(r.url + "/queries.json",
                                {"user": "fresh_user", "num": 3})
                cold_ok = cold_ok and cold.get("itemScores") == []
            for i in (1, 3, 5, 7):
                es.insert(Event(
                    event="rate", entity_type="user",
                    entity_id="fresh_user",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": 5.0}),
                    event_time=dt.datetime.now(UTC),
                ), app_id=app.id)
            runner = FoldInRunner(
                storage, engine, ep, iid,
                ctx=WorkflowContext(storage=storage, mode="Serving"),
                from_now=False,
            )
            stats = runner.cycle()
            assert stats and stats["appendedUsers"] >= 1, stats
            code, pushed = _post(base + "/admin/push-foldin", {})
            applied = {p["replica"]: p.get("applied", 0)
                       for p in pushed["pushed"]}
            fresh_ok = True
            zero_reloads = True
            for r in replicas:
                _, ans = _post(r.url + "/queries.json",
                               {"user": "fresh_user", "num": 3})
                fresh_ok = fresh_ok and len(ans.get("itemScores", [])) > 0
                _, st = _get(r.url + "/")
                fresh_ok = fresh_ok and (
                    st["engineInstanceId"] == before[r.name]
                )
                _, metrics = _get(r.url + "/metrics", raw=True)
                for ln in metrics.splitlines():
                    if ln.startswith("pio_reloads_total") \
                            and not ln.endswith(" 0"):
                        zero_reloads = False
            invariants["rolling_push_freshens"] = (
                cold_ok and code == 200
                and all(v == 1 for v in applied.values())
                and fresh_ok and zero_reloads
            )

        # ---- kill one replica mid-load: the router masks it -------------
        with stage("kill_masked"):
            stop = threading.Event()
            results = []

            def client(wid):
                c = http.client.HTTPConnection(
                    "127.0.0.1", router.port, timeout=30)
                while not stop.is_set():
                    try:
                        c.request(
                            "POST", "/queries.json",
                            json.dumps({"user": f"u{wid}",
                                        "num": 3}).encode(),
                            headers={"Content-Type": "application/json"},
                        )
                        r = c.getresponse()
                        r.read()
                        results.append(r.status)
                    except Exception as e:
                        results.append(f"exc:{type(e).__name__}")
                        c.close()
                        c = http.client.HTTPConnection(
                            "127.0.0.1", router.port, timeout=30)
                c.close()

            with concurrent.futures.ThreadPoolExecutor(4) as ex:
                futs = [ex.submit(client, w) for w in range(4)]
                time.sleep(0.5)
                procs[0]["proc"].kill()  # SIGKILL, mid-traffic
                time.sleep(1.5)
                stop.set()
                for f in futs:
                    f.result(30)
            _, snap = _get(base + "/")
            invariants["kill_masked"] = (
                len(results) > 20
                and all(r == 200 for r in results)
                and snap["healthyReplicas"] == 1
            )

        rc = 0 if all(invariants.values()) else 1
    finally:
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
            "metric": "surge_smoke",
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
