#!/usr/bin/env python3
"""chip_smoke.py — the main path, end to end, on the chip.

The quickest proof that the system still starts on the accelerator: the
recommendation engine (the flagship, and the model every other factor
engine shares code with) goes import -> train -> deploy -> query through
the normal `pio-tpu` entry points, as child processes, ONE AT A TIME.
This parent never imports jax — a parent that touched jax would hold the
chip its children need.

  1. ratings from a seed, shaped like ML-20M's tables (`bench.synth_ml20m`
     recipe): rank 64, all 138,493 user rows, all 26,744 item rows,
     >= 2,000,000 distinct ratings — the size at which `ALSTrainer`
     takes the device-staging path an ML-20M user gets.  Depth is cut
     (3 iterations); widths are not.
  2. `app new`, `import`, `train` on an engine.json from `template get
     recommendation` (default solver, "auto": on the chip the
     `ops/solve.py` Cholesky kernel, which the summary's `solve_path`
     must say).
  3. one more `train`, `"solver": "pallas"`: the same kernel forced at
     rank 64, compiled, not interpreted, not degraded.
  4. `deploy --port 0 --port-file`: single queries, one filtered query
     (`blackList`, the exact-scan branch), one concurrent burst wide
     enough for the shared batcher to dispatch a batched call; every
     answer HTTP 200 with `num` finite-scored items that agree with a
     float32 numpy reference over the persisted factors; `POST /stop`.
  5. with more than one chip visible, 2 and 4 once more with
     `"factorPlacement": "sharded", "distributedTopk": true` (sharded
     ALS and the sharded top-k, each chip scanning its own shard; the
     filtered query, which keeps the local scorer, is sent after the
     count of compiles).  The device count selects this, no flag.

It FAILS (non-zero exit, no result line) when jax finds no TPU, when any
child fails, when a train or the server reports another platform than
tpu, when a log carries a fallback / degrade / `warmup failed` line, when
the queries compile anything after warm-up, or when fewer devices hold
staged training data than the host has chips.

A pass prints two JSON lines on stdout.  The last is the result, with
exactly these keys and the device as jax reports it:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
The line before it is the summary: versions, `native_available`, each
train's resolved solver and seconds, serving checks, the compile-cache
directory with hit/miss counts, seconds per stage, `"claim": null`.

`--dry-run-cpu` runs the same plumbing at a tiny size under
`JAX_PLATFORMS=cpu` (the tier-1 test); its lines say `"platform": "cpu"`.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import importlib.metadata
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent

FULL = dict(users=138_493, items=26_744, ratings=2_000_000, rank=64,
            iters=3, burst=64)
TINY = dict(users=300, items=120, ratings=6_000, rank=8, iters=2, burst=16)

# log lines that mean a child gave way quietly instead of failing
BAD_LOG = re.compile(
    r"falling back|fallback|degrad|unfused path|warmup failed", re.I
)

# reference check, run in a child pinned to the CPU: load the persisted
# model through the repo's own deploy path, score with float32 numpy.
# The server's matmuls run at the MXU's default precision (operands
# rounded to bfloat16), hence the tolerances.
REFERENCE = r"""
import json, sys
import numpy as np
from predictionio_tpu.cli.main import load_engine_from_variant
from predictionio_tpu.controller.base import WorkflowContext
from predictionio_tpu.workflow.train import prepare_deploy_components

engine_json, iid, answers_path = sys.argv[1:4]
engine, ep, _ = load_engine_from_variant(engine_json)
_, models, _ = prepare_deploy_components(
    engine, ep, iid, WorkflowContext(mode="Serving"))
model = models[0]
U = np.asarray(model.user_factors, np.float32)
V = np.asarray(model.item_factors, np.float32)
bad = []
for rec in json.load(open(answers_path)):
    q, items = rec["query"], rec["itemScores"]
    ref = V @ U[model.users.get(q["user"])]
    for name in q.get("blackList", ()):
        ref[model.items.get(name)] = -np.inf
    got_ix = np.array([model.items.get(s["item"]) for s in items])
    got = np.array([s["score"] for s in items], np.float32)
    if (got_ix < 0).any() or len(set(got_ix.tolist())) != len(items):
        bad.append({"query": q, "why": "unknown or repeated item"})
        continue
    tol = 2e-2 * np.abs(ref[got_ix]) + 2e-2
    if (np.abs(got - ref[got_ix]) > tol).any():
        bad.append({"query": q, "why": "scores differ from reference",
                    "got": got.tolist(), "ref": ref[got_ix].tolist()})
    kth = np.sort(ref)[-len(items)]
    if (ref[got_ix] < kth - (2e-2 * abs(kth) + 2e-2)).any():
        bad.append({"query": q, "why": "a clearly better item was missed",
                    "kth_best": float(kth), "ref": ref[got_ix].tolist()})
print("REFERENCE=" + json.dumps({"checked": True, "bad": bad,
                                 "users": int(U.shape[0]),
                                 "items": int(V.shape[0]),
                                 "rank": int(U.shape[1])}))
"""


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


class Smoke:
    def __init__(self, size: dict, dry_run: bool, work: Path):
        self.size = size
        self.dry_run = dry_run
        self.work = work
        self.want_platform = "cpu" if dry_run else "tpu"
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = str(ROOT) + (
            os.pathsep + self.env["PYTHONPATH"]
            if self.env.get("PYTHONPATH") else ""
        )
        self.env["PIO_TPU_HOME"] = str(work / "home")
        self.logs: list[Path] = []
        self.procs: list[subprocess.Popen] = []
        self.stages: dict[str, float] = {}
        self.cache = {"dir": None, "hit": 0, "miss": 0}
        self.trains: list[dict] = []
        self.serves: list[dict] = []

    # -- children --------------------------------------------------------
    def spawn(self, name: str, *args: str):
        """Start one `pio-tpu` child, output to `<name>.log`."""
        log = self.work / f"{name}.log"
        self.logs.append(log)
        with open(log, "w") as f:
            proc = subprocess.Popen(
                [sys.executable, "-m", "predictionio_tpu", *args],
                stdout=f, stderr=subprocess.STDOUT, env=self.env,
                cwd=self.work, start_new_session=True,
            )
        self.procs.append(proc)
        return proc, log

    def pio(self, name: str, *args: str, timeout: float = 900) -> str:
        """One `pio-tpu` command to completion (`python -m
        predictionio_tpu` with the checkout on PYTHONPATH — what
        `bin/pio-tpu` execs); returns its output."""
        t0 = time.time()
        proc, log = self.spawn(name, *args)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{name}: no exit within {timeout}s\n{_tail(log)}"
            ) from None
        self.stages[name] = round(time.time() - t0, 1)
        out = log.read_text(errors="replace")
        if rc != 0:
            raise SmokeFailure(f"{name}: exit code {rc}\n{_tail(log)}")
        say(f"{name}: ok in {self.stages[name]}s")
        return out

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()

    # -- phases ----------------------------------------------------------
    def probe(self) -> dict:
        """`pio-tpu status`: its bounded child reports jax's devices."""
        out = self.pio("status", "status", timeout=300)
        m = re.search(
            r"JAX devices: platform=(\S+) kind='([^']*)' count=(\d+)", out
        )
        if m is None:
            raise SmokeFailure(f"status reported no jax devices:\n{out}")
        device = {"platform": m.group(1), "kind": m.group(2),
                  "count": int(m.group(3))}
        if device["platform"] != self.want_platform:
            raise SmokeFailure(
                f"jax found platform {device['platform']!r}, this run "
                f"needs {self.want_platform!r}"
                + ("" if self.dry_run else
                   ": chip_smoke.py proves the system on the accelerator "
                   "and refuses to pass without one")
            )
        return device

    def make_events(self) -> Path:
        t0 = time.time()
        path = self.work / "ratings.jsonl"
        n = write_events(path, self.size, seed=0)
        self.stages["generate"] = round(time.time() - t0, 1)
        say(f"generated {n:,} distinct ratings in "
            f"{self.stages['generate']}s")
        return path

    def engine_dir(self, name: str, algo_params: dict) -> Path:
        target = self.work / name
        self.pio(f"template-{name}", "template", "get", "recommendation",
                 str(target))
        path = target / "engine.json"
        variant = json.loads(path.read_text())
        variant["datasource"]["params"]["appName"] = "smoke"
        variant["datasource"]["params"]["eventNames"] = ["rate"]
        variant["algorithms"][0]["params"].update(
            {"rank": self.size["rank"], "numIterations": self.size["iters"],
             **algo_params}
        )
        path.write_text(json.dumps(variant, indent=2))
        return target

    def train(self, name: str, engine: Path, device: dict) -> str:
        out = self.pio(f"train-{name}", "train", "--engine-json",
                       str(engine / "engine.json"), timeout=900)
        m = re.search(r"Engine instance id: (\w+)", out)
        if m is None:
            raise SmokeFailure(f"train-{name} printed no instance id")
        iid = m.group(1)
        manifest = self.work / "home" / "telemetry" / "runs" / iid / \
            "run.jsonl"
        recs = [json.loads(ln) for ln in manifest.read_text().splitlines()]
        header = next(r for r in recs if r.get("kind") == "header")
        staged = next(r for r in recs if r.get("event") == "als_staged")
        final = next(r for r in recs if r.get("kind") == "final")
        info = {
            "name": name, "instance": iid,
            "platform": header["platform"], "kind": header["deviceKind"],
            "devices": header["nDevices"],
            "solver": staged["solver"], "solve_path": staged["solvePath"],
            "staging": staged["staging"],
            "placement": staged["placement"],
            "devices_with_data": staged["devicesWithData"],
            "sweeps": final["sweeps"],
            # the child's whole wall, then the manifest's own split of
            # the train.run span: set-up (scan, staging, compiles),
            # then each sweep
            "seconds": self.stages[f"train-{name}"],
            "train_run_seconds": round(final["trainRunSeconds"], 1),
            "setup_seconds": round(final["setupSeconds"], 1),
            "sweep_seconds": [
                round(r["seconds"], 2) for r in recs
                if r.get("kind") == "sweep"
            ],
        }
        self.trains.append(info)
        self.add_cache(final["compileCache"])
        line = (f"JAX devices: platform={device['platform']} "
                f"kind={device['kind']!r} count={device['count']}")
        if line not in out:
            raise SmokeFailure(
                f"train-{name} did not print the device line {line!r}")
        if (info["platform"], info["devices"]) != (
                device["platform"], device["count"]):
            raise SmokeFailure(f"train-{name} ran on {info}, not {device}")
        if info["devices_with_data"] < device["count"]:
            raise SmokeFailure(
                f"train-{name}: {info['devices_with_data']} of "
                f"{device['count']} devices hold staged training data")
        if final["status"] != "completed" or \
                info["sweeps"] != self.size["iters"]:
            raise SmokeFailure(f"train-{name} manifest: {final}")
        return iid

    def serve(self, name: str, engine: Path, iid: str,
              device: dict, sharded_topk: bool = False) -> None:
        """Deploy, query, check, stop."""
        port_file = self.work / f"{name}.port"
        t0 = time.time()
        proc, log = self.spawn(
            f"deploy-{name}", "deploy",
            "--engine-json", str(engine / "engine.json"),
            "--engine-instance-id", iid, "--ip", "127.0.0.1",
            "--port", "0", "--port-file", str(port_file))
        while not (port_file.exists() and port_file.read_text().strip()):
            if proc.poll() is not None:
                raise SmokeFailure(
                    f"deploy-{name} exited {proc.returncode} before "
                    f"announcing a port\n{_tail(log)}")
            if time.time() - t0 > 600:
                raise SmokeFailure(
                    f"deploy-{name}: no port within 600s\n{_tail(log)}")
            time.sleep(0.2)
        base = f"http://127.0.0.1:{int(port_file.read_text())}"
        _wait_http(base + "/", proc, log)
        ready_s = round(time.time() - t0, 1)
        say(f"deploy-{name}: serving on {base} after {ready_s}s")

        before = _get(base + "/debug/xray")
        users = [f"u{k}" for k in (0, 1, self.size["users"] - 1)]
        answers = []
        for user in users:
            answers.append(_query(base, {"user": user, "num": 10}))
        black = [s["item"] for s in answers[0]["itemScores"][:3]]

        def ask_filtered():
            filtered = _query(
                base, {"user": users[0], "num": 10, "blackList": black})
            if set(black) & {s["item"] for s in filtered["itemScores"]}:
                raise SmokeFailure(
                    f"deploy-{name}: blacklisted items were returned")
            answers.append(filtered)

        if not sharded_topk:
            ask_filtered()
        # concurrent bursts, every client released at once, until the
        # batcher has coalesced one (requests that land while a device
        # call is in flight ride the next one together)
        width = self.size["burst"]
        gate = threading.Barrier(width)

        def client(user):
            gate.wait(timeout=60)
            return _query(base, {"user": user, "num": 10})

        with concurrent.futures.ThreadPoolExecutor(width) as pool:
            for attempt in range(8):
                burst_users = [
                    f"u{(7 * k + attempt) % self.size['users']}"
                    for k in range(width)
                ]
                answers += list(pool.map(client, burst_users))
                status = _get(base + "/")
                if status.get("microbatch", {}).get(
                        "maxBatchSeen", 0) >= 2:
                    break
        after = _get(base + "/debug/xray")
        if sharded_topk:
            # under distributedTopk a filtered query keeps the local
            # scorer, whose one-chip table the warm-up does not build (the
            # table may be one no chip holds): its first batch compiles,
            # after the count of what the warm-up covers
            ask_filtered()
        try:
            urllib.request.urlopen(urllib.request.Request(
                base + "/stop", method="POST"), timeout=10).read()
        except (urllib.error.URLError, OSError):
            pass  # the server may close the socket as it stops
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"deploy-{name} still running 60s after POST /stop"
            ) from None
        self.stages[f"deploy-{name}"] = round(time.time() - t0, 1)

        platforms = {
            s["device"].split(":")[0] for s in after["devices"]["samples"]
        }
        if platforms != {device["platform"]} or \
                len(after["devices"]["samples"]) != device["count"]:
            raise SmokeFailure(
                f"deploy-{name} /debug/xray devices: "
                f"{after['devices']['samples']}")
        compiled = _new_compiles(before, after)
        batch_seen = status.get("microbatch", {}).get("maxBatchSeen", 0)
        info = {
            "name": name, "ready_seconds": ready_s,
            "queries": len(answers),
            "compiles_after_warmup": sum(compiled.values()),
            "max_batch_seen": batch_seen,
            "devices": sorted(
                s["device"] for s in after["devices"]["samples"]),
        }
        self.serves.append(info)
        self.add_cache(after["compileCache"])
        if compiled:
            raise SmokeFailure(
                f"deploy-{name}: compiles after warm-up, by entry point: "
                f"{compiled}")
        if batch_seen < 2:
            raise SmokeFailure(
                f"deploy-{name}: bursts of {width} never dispatched a "
                f"batched call: {status.get('microbatch')}")
        info["reference"] = self.reference(name, engine, iid, answers)

    def reference(self, name: str, engine: Path, iid: str,
                  answers: list) -> dict:
        path = self.work / f"answers-{name}.json"
        path.write_text(json.dumps(answers))
        proc = subprocess.run(
            [sys.executable, "-c", REFERENCE, str(engine / "engine.json"),
             iid, str(path)],
            env={**self.env, "JAX_PLATFORMS": "cpu"}, cwd=self.work,
            capture_output=True, text=True, timeout=600,
        )
        m = re.search(r"^REFERENCE=(.*)$", proc.stdout, re.M)
        if proc.returncode != 0 or m is None:
            raise SmokeFailure(
                f"reference-{name}: exit {proc.returncode}\n"
                f"{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}")
        ref = json.loads(m.group(1))
        if ref["bad"]:
            raise SmokeFailure(
                f"deploy-{name}: answers disagree with the float32 "
                f"reference: {json.dumps(ref['bad'][:3])}")
        want = (self.size["users"], self.size["items"], self.size["rank"])
        if (ref["users"], ref["items"], ref["rank"]) != want:
            raise SmokeFailure(
                f"{name}: model tables are {ref}, wanted {want}")
        return {"answers": len(answers), "agree": True}

    def add_cache(self, cache: dict) -> None:
        self.cache["dir"] = cache["dir"]
        self.cache["hit"] += cache["events"].get("hit", 0)
        self.cache["miss"] += cache["events"].get("miss", 0)

    def check_logs(self) -> None:
        for log in self.logs:
            for ln in log.read_text(errors="replace").splitlines():
                if BAD_LOG.search(ln):
                    raise SmokeFailure(f"{log.name}: {ln.strip()}")

    # -- the run ---------------------------------------------------------
    def run(self) -> dict:
        device = self.probe()
        events = self.make_events()
        out = self.pio("app-new", "app", "new", "smoke")
        app_id = re.search(r"\(id (\d+)\)", out).group(1)
        out = self.pio("import", "import", "--appid", app_id,
                       "--input", str(events), timeout=900)
        imported = int(re.search(r"Imported (\d+) events", out).group(1))
        if imported < self.size["ratings"]:
            raise SmokeFailure(f"imported {imported} events")

        auto = self.engine_dir("auto", {})
        iid = self.train("auto", auto, device)
        self.train("pallas", self.engine_dir("pallas", {"solver": "pallas"}),
                   device)
        self.serve("auto", auto, iid, device)
        if device["count"] > 1:
            # more than one chip: sharded ALS and the sharded top-k, once
            sharded = self.engine_dir("sharded", {
                "factorPlacement": "sharded", "distributedTopk": True})
            sharded_iid = self.train("sharded", sharded, device)
            if self.trains[-1]["placement"] != "sharded":
                raise SmokeFailure(f"sharded train: {self.trains[-1]}")
            self.serve("sharded", sharded, sharded_iid, device,
                       sharded_topk=True)
        self.check_logs()
        for want, got in zip(("auto", "pallas"), self.trains):
            if got["solver"] != want:
                raise SmokeFailure(f"train {want} ran solver {got}")
        on_chip = device["platform"] == "tpu"
        if self.trains[0]["solve_path"] != ("kernel" if on_chip else "lax"):
            raise SmokeFailure(f"default train solved by {self.trains[0]}")
        # the same library the children built under this PIO_TPU_HOME
        os.environ["PIO_TPU_HOME"] = self.env["PIO_TPU_HOME"]
        from predictionio_tpu.native import native_available

        return {
            "device": device,
            **({"dry_run": True} if self.dry_run else {}),
            "versions": {
                pkg: _version(pkg) for pkg in ("jax", "jaxlib", "libtpu")
            },
            "native_available": bool(native_available()),
            "size": {**self.size, "imported": imported},
            "trains": self.trains,
            "serving": self.serves,
            "compile_cache": self.cache,
            "stage_seconds": self.stages,
            "claim": None,
        }


# -- helpers ---------------------------------------------------------------


def write_events(path: Path, size: dict, seed: int) -> int:
    """`bench.synth_ml20m`'s recipe (power-law user activity and item
    popularity, half-star ratings) as import-ready JSON lines, with two
    additions the smoke needs: every user and every item appears (the
    factor tables get their full heights), and (user, item) pairs are
    distinct (the datasource keeps the last rating of a pair, and the
    staging switch counts what survives)."""
    import numpy as np

    n_users, n_items, want = size["users"], size["items"], size["ratings"]
    rng = np.random.default_rng(seed)
    w_u = 1.0 / np.arange(1, n_users + 1) ** 0.8
    w_u /= w_u.sum()
    w_i = 1.0 / np.arange(1, n_items + 1) ** 1.0
    w_i /= w_i.sum()
    # coverage: one rating for every user, one for every item
    u = np.concatenate([np.arange(n_users),
                        rng.integers(0, n_users, n_items)])
    i = np.concatenate([rng.integers(0, n_items, n_users),
                        np.arange(n_items)])
    key = np.unique(u.astype(np.int64) * n_items + i)
    while len(key) < want:
        draw = int((want - len(key)) * 1.3) + 1024
        du = rng.choice(n_users, size=draw, p=w_u)
        di = rng.choice(n_items, size=draw, p=w_i)
        key = np.unique(np.concatenate(
            [key, du.astype(np.int64) * n_items + di]))
    key = rng.permutation(key)
    u, i = key // n_items, key % n_items
    v = rng.integers(1, 11, size=len(key)) * 0.5
    head = ('{"event":"rate","entityType":"user","entityId":"u%d",'
            '"targetEntityType":"item","targetEntityId":"i%d",'
            '"properties":{"rating":%.1f},'
            '"eventTime":"2024-01-01T00:00:00.000Z"}\n')
    with open(path, "w") as f:
        for lo in range(0, len(key), 200_000):
            f.write("".join(
                head % row for row in zip(
                    u[lo:lo + 200_000].tolist(),
                    i[lo:lo + 200_000].tolist(),
                    v[lo:lo + 200_000].tolist())
            ))
    return len(key)


def _tail(log: Path, n: int = 4000) -> str:
    return log.read_text(errors="replace")[-n:]


def _version(pkg: str):
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return None


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def _wait_http(url: str, proc: subprocess.Popen, log: Path) -> None:
    deadline = time.time() + 600
    while True:
        try:
            _get(url)
            return
        except (urllib.error.URLError, OSError):
            if proc.poll() is not None or time.time() > deadline:
                raise SmokeFailure(
                    f"server at {url} never answered\n{_tail(log)}"
                ) from None
            time.sleep(0.2)


def _query(base: str, query: dict) -> dict:
    req = urllib.request.Request(
        base + "/queries.json", data=json.dumps(query).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            body = json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise SmokeFailure(
            f"query {query}: HTTP {e.code} {e.read()[:500]!r}") from None
    scores = body.get("itemScores", [])
    if len(scores) != query["num"] or not all(
            isinstance(s["score"], (int, float))
            and math.isfinite(s["score"]) for s in scores):
        raise SmokeFailure(f"query {query}: answer {body}")
    return {"query": query, "itemScores": scores}


def _new_compiles(before: dict, after: dict) -> dict:
    """Entry point -> new jit signatures + backend compiles between two
    `/debug/xray` documents ("untracked": jits xray does not wrap)."""
    out = {}
    for name, fn in after["jit"].items():
        was = before["jit"].get(name, {"signatures": 0,
                                       "backendCompiles": 0})
        new = (fn["signatures"] - was["signatures"]
               + fn["backendCompiles"] - was["backendCompiles"])
        if new:
            out[name] = new
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--dry-run-cpu", action="store_true",
        help="tiny plumbing check under JAX_PLATFORMS=cpu (tier-1); "
        "proves nothing about the chip and says platform cpu")
    args = ap.parse_args()
    if not (ROOT / "predictionio_tpu" / "__init__.py").exists():
        print("chip_smoke.py: no predictionio_tpu package beside this "
              "script; it drives the repo's own program",
              file=sys.stderr)
        return 2
    if args.dry_run_cpu and os.environ.get(
            "JAX_PLATFORMS", "").split(",")[0] != "cpu":
        print("chip_smoke.py: --dry-run-cpu needs JAX_PLATFORMS=cpu",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    work = Path(tempfile.mkdtemp(prefix="pio-chip-smoke-"))
    smoke = Smoke(TINY if args.dry_run_cpu else FULL, args.dry_run_cpu,
                  work)
    t0 = time.time()
    try:
        summary = smoke.run()
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED after {time.time() - t0:.0f}s: {e}",
              file=sys.stderr)
        return 1
    finally:
        smoke.stop_all()
        shutil.rmtree(work, ignore_errors=True)
    summary["total_seconds"] = round(time.time() - t0, 1)
    print(json.dumps(summary))
    # the result line: these keys and no others, last on stdout
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
