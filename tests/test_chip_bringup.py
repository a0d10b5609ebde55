"""Chip bring-up contracts (PR 21): nothing on the main path hides the
device.  The compile cache can be placed from outside and never moves;
a backend that cannot initialize fails the command instead of running it
on the CPU; a chip serves one process; and `chip_smoke.py` — the proof
that the system starts on the accelerator — refuses to pass without one.
"""

import importlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


# -- compile cache placement ------------------------------------------------


@pytest.fixture()
def cache_config():
    """Restore jax's cache config after a test repoints it."""
    import jax

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield jax
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved[1])


def test_cache_dir_env_set_is_left_untouched(cache_config, monkeypatch,
                                             tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the code sets NO directory — jax
    keeps the one it read from the environment — and xray is told the
    operator's directory."""
    from predictionio_tpu.obs import xray
    from predictionio_tpu.parallel import mesh

    jax = cache_config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    sentinel = "/set/by/jax/from/the/environment"
    jax.config.update("jax_compilation_cache_dir", sentinel)
    assert mesh.enable_compilation_cache() == str(tmp_path / "x")
    assert jax.config.jax_compilation_cache_dir == sentinel
    assert xray.compile_cache_summary()["dir"] == str(tmp_path / "x")
    assert not (tmp_path / "x").exists()  # jax creates it on first write


def test_cache_dir_unset_is_fixed_inside_the_checkout(
        cache_config, monkeypatch, tmp_path):
    """Unset: `<repo>/.jax_cache`, a path that is the same in every
    process of every run — and never under PIO_TPU_HOME, which smokes
    and benches point at a fresh temp dir each time."""
    from predictionio_tpu.parallel import mesh

    jax = cache_config
    # the real constant: inside this checkout, and git ignores it
    assert mesh._REPO_CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    # a stand-in checkout, so the test does not write into this one
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("PIO_TPU_HOME", str(tmp_path / "home"))
    fixed = tmp_path / "checkout" / ".jax_cache"
    monkeypatch.setattr(mesh, "_REPO_CACHE_DIR", fixed)
    assert mesh.enable_compilation_cache() == str(fixed)
    assert jax.config.jax_compilation_cache_dir == str(fixed)
    assert fixed.is_dir() and not (tmp_path / "home").exists()
    # an installed package has no checkout to write into: the failure
    # names the variable that places the cache
    (tmp_path / "file").write_text("")
    monkeypatch.setattr(mesh, "_REPO_CACHE_DIR",
                        tmp_path / "file" / ".jax_cache")
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        mesh.enable_compilation_cache()


def test_cache_keeps_small_compiles(cache_config, monkeypatch, tmp_path):
    """The serving warm-up ladder is many sub-second compiles: no
    minimum compile time keeps them out of the cache."""
    from predictionio_tpu.parallel import mesh

    jax = cache_config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    mesh.enable_compilation_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    # ... unless the operator set one: then theirs stands
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    mesh.enable_compilation_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 2.0


# -- no fallback that hides the device -------------------------------------


def test_make_mesh_propagates_a_backend_error(monkeypatch):
    """A backend that cannot initialize (chip absent, or held by another
    process) fails `make_mesh` with jax's own error; nothing rewrites
    the platform to carry on elsewhere."""
    import jax

    from predictionio_tpu.controller.base import WorkflowContext
    from predictionio_tpu.parallel import mesh

    def held():
        raise RuntimeError(
            "Unable to initialize backend 'tpu': ABORTED: The TPU is "
            "already in use by process with pid 1678.")

    before = jax.config.jax_platforms
    monkeypatch.setattr(jax, "devices", held)
    with pytest.raises(RuntimeError, match="already in use"):
        mesh.make_mesh()
    with pytest.raises(RuntimeError, match="already in use"):
        WorkflowContext(storage=object())
    assert jax.config.jax_platforms == before
    assert not hasattr(mesh, "fence")


def test_describe_devices_is_what_jax_reports():
    import jax

    from predictionio_tpu.parallel.mesh import describe_devices

    d = describe_devices()
    assert d == {"platform": "cpu",
                 "kind": jax.devices()[0].device_kind,
                 "count": len(jax.devices())}


def test_no_platform_override_left_in_the_package():
    """`JAX_PLATFORMS=cpu` is the one way to ask for the CPU: no code
    path sets the platform, and none catches a backend-initialisation
    error to continue."""
    hits = []
    for path in (ROOT / "predictionio_tpu").rglob("*.py"):
        if "analysis" in path.parts:
            continue  # the linter names patterns, it does not use them
        text = path.read_text()
        # spelled in halves so that this file does not itself match the
        # tree-wide grep the same names are checked with
        for needle in ('"jax_' + 'platforms"', "PIO_TPU_" + "PLATFORM",
                       "force_" + "platform",
                       "jax.experimental." + "shard_map",
                       "check_" + "rep", "pltpu." + "ANY"):
            if needle in text:
                hits.append((path.name, needle))
    assert hits == []


def test_train_and_status_say_which_devices_they_have(tmp_path, capsys,
                                                      monkeypatch):
    """`train` prints platform, device_kind and count once at start
    (with the compile-cache directory), and `status` prints the same
    from its bounded probe child."""
    cli = importlib.import_module("predictionio_tpu.cli.main")
    from predictionio_tpu.storage import Storage, reset_storage

    s = Storage(env={"PIO_TPU_HOME": str(tmp_path)})
    reset_storage(s)
    try:
        cli._start_jax()
        line = capsys.readouterr().out.strip()
        assert line.startswith(
            "JAX devices: platform=cpu kind='cpu' count=8; "
            "compile cache: /tmp/pio_tpu_test_jax_cache")
        # the default an operator gets: long enough for a cold
        # four-chip host (30 s was not, on the chip)
        assert cli.build_parser().parse_args(
            ["status"]).probe_timeout == 120.0
        assert cli.main(["status"], storage=s) == 0
        assert "JAX devices: platform=cpu kind='cpu' count=8" in \
            capsys.readouterr().out
        monkeypatch.setattr(
            cli, "_probe_devices",
            lambda t: (None, "Unable to initialize backend 'tpu'"))
        assert cli.main(["status"], storage=s) == 0
        assert "Warning: JAX backend unavailable: Unable to initialize" \
            in capsys.readouterr().out
    finally:
        reset_storage(None)


def test_train_manifest_records_device_solver_and_cache(storage_memory,
                                                        tmp_path,
                                                        monkeypatch):
    """What `chip_smoke.py` reads back from a train: the manifest names
    the platform and device kind, the staged solver/placement and how
    many devices hold data, and the compile-cache counts."""
    import numpy as np

    from predictionio_tpu.controller.base import WorkflowContext
    from predictionio_tpu.obs import runlog
    from predictionio_tpu.storage import Event
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine,
    )
    from predictionio_tpu.workflow.train import run_train

    monkeypatch.setenv("PIO_TPU_RUNLOG_DIR", str(tmp_path / "runs"))
    app = storage_memory.get_metadata().app_insert("bringup", "")
    es = storage_memory.get_event_store()
    es.init_channel(app.id)
    rng = np.random.default_rng(0)
    es.insert_batch([
        Event(event="rate", entity_type="user", entity_id=f"u{u}",
              target_entity_type="item", target_entity_id=f"i{i}",
              properties={"rating": float(rng.integers(1, 6))})
        for u in range(24) for i in rng.choice(16, 5, replace=False)
    ], app.id)
    engine = recommendation_engine()
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "bringup"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 4, "numIterations": 2, "solver": "pallas"}}],
    })
    iid = run_train(engine, ep, ctx=WorkflowContext(
        storage=storage_memory))
    view = runlog.read_manifest(tmp_path / "runs" / iid / "run.jsonl")
    assert view["header"]["platform"] == "cpu"
    assert view["header"]["deviceKind"] == "cpu"
    assert view["header"]["nDevices"] == 8
    staged = [e for e in view["events"] if e["event"] == "als_staged"]
    assert len(staged) == 1
    assert staged[0]["solver"] == "pallas"
    assert staged[0]["placement"] == "replicated"
    assert staged[0]["devices"] == staged[0]["devicesWithData"] == 8
    cache = view["final"]["compileCache"]
    assert cache["dir"] == "/tmp/pio_tpu_test_jax_cache" or \
        cache["dir"] is None  # None: no command placed a cache here
    assert isinstance(cache["events"], dict)


# -- one process for each chip ----------------------------------------------


def _fleet_args(tmp_path, replicas):
    from predictionio_tpu.cli.main import build_parser

    return build_parser().parse_args([
        "deploy", "--engine-json", str(tmp_path / "engine.json"),
        "--replicas", str(replicas), "--port", "0",
    ])


def test_deploy_replicas_above_the_chip_count_is_refused(
        tmp_path, monkeypatch, capsys):
    """N replicas on fewer than N chips would race for a chip (and the
    losers fail to initialize): refused before anything is spawned."""
    cli = importlib.import_module("predictionio_tpu.cli.main")
    from predictionio_tpu.server import router

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(cli, "_probe_devices", lambda t: (
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}, None))
    spawned = []
    monkeypatch.setattr(router, "spawn_replica",
                        lambda *a, **k: spawned.append(k) or {})
    assert cli._deploy_fleet(_fleet_args(tmp_path, 2)) == 1
    out = capsys.readouterr().out
    assert "--replicas 2 exceeds the 1 TPU v5 lite chip(s)" in out
    assert spawned == []
    # a backend that does not answer is an error, not a CPU fleet
    monkeypatch.setattr(cli, "_probe_devices",
                        lambda t: (None, "The TPU is already in use"))
    assert cli._deploy_fleet(_fleet_args(tmp_path, 2)) == 1
    assert "already in use" in capsys.readouterr().out and not spawned


def test_deploy_replicas_pins_one_chip_each(tmp_path, monkeypatch):
    """On a TPU host replica i gets chip i; under JAX_PLATFORMS=cpu (the
    operator's word) nothing is probed or pinned."""
    cli = importlib.import_module("predictionio_tpu.cli.main")
    from predictionio_tpu.server import router

    class Stop(Exception):
        pass

    chips = []

    def spawn(engine_json, index, coord_dir, **kw):
        chips.append(kw.get("chip"))
        if len(chips) == 4:
            raise Stop
        return {"index": index}

    monkeypatch.setattr(router, "spawn_replica", spawn)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setattr(cli, "_probe_devices", lambda t: (
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}, None))
    with pytest.raises(Stop):
        cli._deploy_fleet(_fleet_args(tmp_path, 4))
    assert chips == [0, 1, 2, 3]

    chips.clear()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")

    def no_probe(t):
        raise AssertionError("probed under JAX_PLATFORMS=cpu")

    monkeypatch.setattr(cli, "_probe_devices", no_probe)
    with pytest.raises(Stop):
        cli._deploy_fleet(_fleet_args(tmp_path, 4))
    assert chips == [None] * 4


def test_spawn_replica_sets_the_pin_before_the_child_starts(tmp_path):
    """The pin is in the child's environment at exec — libtpu reads it
    when it loads, so it cannot be set after the child imports jax."""
    from predictionio_tpu.server.router import chip_pin_env, spawn_replica

    assert chip_pin_env(2) == {
        "TPU_VISIBLE_CHIPS": "2",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
    dump = tmp_path / "dump.py"
    dump.write_text(
        "import json, os, sys\n"
        "port_file = sys.argv[sys.argv.index('--port-file') + 1]\n"
        "print(json.dumps({k: v for k, v in os.environ.items()\n"
        "                  if k.startswith('TPU_')}))\n"
    )
    wrapper = tmp_path / "py"
    wrapper.write_text(f"#!/bin/sh\nexec {sys.executable} {dump} \"$@\"\n")
    wrapper.chmod(0o755)
    env = {k: v for k, v in os.environ.items() if not k.startswith("TPU_")}
    for chip, want in ((3, chip_pin_env(3)), (None, {})):
        s = spawn_replica(tmp_path / "engine.json", 0, tmp_path / "coord",
                          python=str(wrapper), chip=chip, env=env)
        assert s["proc"].wait(timeout=60) == 0
        assert json.loads(Path(s["log_path"]).read_text()) == want


def test_straggler_ab_refuses_without_a_mesh(tmp_path):
    """One visible device: the coded-sweep A/B exits 2 instead of
    re-executing itself onto virtual CPU devices and measuring those."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               PIO_TPU_HOME=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench.py"), "--straggler-ab",
         "--scale", "0.001", "--rank", "4", "--iters", "2"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "needs a multi-device mesh" in proc.stderr
    assert "als_sweep_straggler_overhead_ratio" not in proc.stdout


# -- chip_smoke.py -----------------------------------------------------------


def _smoke(tmp_path, *args, script=ROOT / "chip_smoke.py", **env):
    return subprocess.run(
        [sys.executable, str(script), *args], capture_output=True,
        text=True, timeout=600, cwd=tmp_path,
        env={**os.environ, "XLA_FLAGS": "", **env},
    )


def test_chip_smoke_refuses_to_pass_on_the_cpu(tmp_path):
    """The default invocation under JAX_PLATFORMS=cpu: non-zero exit, a
    clear message, and NO result line — it cannot be mistaken for a
    chip run."""
    proc = _smoke(tmp_path, JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "jax found platform 'cpu', this run needs 'tpu'" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """Without the program beside it the script has nothing to prove."""
    import shutil

    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _smoke(tmp_path, script=tmp_path / "chip_smoke.py")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no predictionio_tpu package" in proc.stderr
    # and the dry run cannot be slipped past a TPU-default environment
    proc = _smoke(tmp_path, "--dry-run-cpu", JAX_PLATFORMS="tpu,cpu")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "--dry-run-cpu needs JAX_PLATFORMS=cpu" in proc.stderr


def test_chip_smoke_cpu_dry_run_checks_the_plumbing(tmp_path):
    """The explicit tiny dry run: import -> two trains (auto, pallas)
    -> deploy -> singles, a filtered query, a batched burst -> reference
    check, all through the CLI, with both lines saying cpu.  Four
    virtual devices, so the device count also selects the sharded-ALS /
    sharded-top-k variant, as it does on the four-chip host."""
    proc = _smoke(tmp_path, "--dry-run-cpu", JAX_PLATFORMS="cpu",
                  XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-4000:]
    summary, result = proc.stdout.strip().splitlines()
    # the last line of stdout is the result: these keys and no others
    assert json.loads(result) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    assert list(json.loads(result)) == ["ok", "device"]
    rec = json.loads(summary)
    assert rec["dry_run"] is True and "ok" not in rec
    assert rec["claim"] is None
    assert rec["device"] == json.loads(result)["device"]
    assert [(t["solver"], t["placement"]) for t in rec["trains"]] == [
        ("auto", "replicated"), ("pallas", "replicated"),
        ("auto", "sharded")]
    # the default resolves from the backend: lax here, the kernel on a chip
    assert [t["solve_path"] for t in rec["trains"]] == [
        "lax", "kernel", "lax"]
    assert all(t["platform"] == "cpu" and t["devices_with_data"] == 4
               and len(t["sweep_seconds"]) == t["sweeps"] == 2
               for t in rec["trains"])
    assert [s["name"] for s in rec["serving"]] == ["auto", "sharded"]
    for serve in rec["serving"]:
        assert serve["compiles_after_warmup"] == 0
        assert serve["max_batch_seen"] >= 2
        assert len(serve["devices"]) == 4
        assert serve["reference"] == {"answers": serve["queries"],
                                      "agree": True}
    assert rec["compile_cache"]["dir"] == "/tmp/pio_tpu_test_jax_cache"
    assert rec["compile_cache"]["hit"] + rec["compile_cache"]["miss"] > 0
    assert set(rec["versions"]) == {"jax", "jaxlib", "libtpu"}
    # nothing left behind: every child stopped, the work dir removed
    assert not list(Path("/tmp").glob("pio-chip-smoke-*/deploy-auto.log"))


def test_chip_smoke_event_recipe(tmp_path):
    """Every user and item row appears, pairs are distinct, and at least
    the asked-for number of ratings survives the datasource's
    last-rating-per-pair dedup."""
    sys.path.insert(0, str(ROOT))
    import chip_smoke

    size = dict(users=500, items=90, ratings=4000)
    n = chip_smoke.write_events(tmp_path / "e.jsonl", size, seed=0)
    rows = [json.loads(ln) for ln in open(tmp_path / "e.jsonl")]
    assert n == len(rows) >= 4000
    pairs = {(r["entityId"], r["targetEntityId"]) for r in rows}
    assert len(pairs) == n
    assert {r["entityId"] for r in rows} == {f"u{k}" for k in range(500)}
    assert {r["targetEntityId"] for r in rows} == {
        f"i{k}" for k in range(90)}
    assert {r["properties"]["rating"] for r in rows} <= {
        k * 0.5 for k in range(1, 11)}
    assert chip_smoke.FULL["ratings"] >= 2_000_000
    assert chip_smoke.FULL["users"] >= 138_000
    assert chip_smoke.FULL["items"] >= 26_000
    assert chip_smoke.FULL["rank"] == 64
