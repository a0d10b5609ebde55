"""`pio`-equivalent CLI console.

Re-expression of reference `tools/console/Console.scala:128-737` +
`console/App.scala` + `console/AccessKey.scala` on argparse.  Subcommands:

  app new|list|show|delete|data-delete|channel-new|channel-delete
  accesskey new|list|delete
  template list|get
  train | deploy | undeploy | foldin | eval | eventserver | adminserver
  dashboard
  build | unregister | run | import | export | status | upgrade | version

There is no sbt: `build` validates the engine variant and registers an
EngineManifest (RegisterEngine analogue), and engine factories are Python
callables resolved by dotted path (`WorkflowUtils.getEngine` reflection
analogue, `workflow/WorkflowUtils.scala:60-77`).
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path
from typing import Any, Optional

from .. import __version__
from ..storage.metadata import AccessKey
from ..storage.registry import Storage, get_storage

__all__ = ["main", "resolve_attr", "load_engine_from_variant"]


def resolve_attr(path: str) -> Any:
    """'package.module.attr' -> attr (the reflection-loader analogue)."""
    mod_name, _, attr = path.rpartition(".")
    if not mod_name:
        raise ValueError(f"invalid dotted path: {path!r}")
    mod = importlib.import_module(mod_name)
    try:
        return getattr(mod, attr)
    except AttributeError as e:
        raise ValueError(f"{attr!r} not found in module {mod_name}") from e


def _engine_dir_on_path(variant_path: str | Path, factory_path: str) -> None:
    """Make a scaffolded engine dir importable: its ``engine.py`` is the
    factory module when engineFactory is ``engine.<attr>`` (the
    `template get` layout).  Evicts a stale ``engine`` module loaded from
    a different engine dir."""
    engine_dir = str(Path(variant_path).resolve().parent)
    top = factory_path.split(".", 1)[0]
    candidate = Path(engine_dir) / f"{top}.py"
    if not candidate.exists():
        return
    if engine_dir not in sys.path:
        sys.path.insert(0, engine_dir)
    mod = sys.modules.get(top)
    if mod is not None and getattr(mod, "__file__", None) != str(candidate):
        del sys.modules[top]


def load_engine_from_variant(
    variant_path: str | Path,
    engine_factory: Optional[str] = None,
    return_factory: bool = False,
):
    """engine.json -> (engine, engine_params, variant dict).

    Two dispatch forms: ``engineFactory`` (dotted path, the classic
    reflection-loader analogue) or ``engine`` (a pio-forge registry
    name — the engine.json of a one-file engine is just
    ``{"engine": "myengine"}`` plus optional component overrides; the
    spec's default params fill the gaps).  ``return_factory=True``
    appends the factory object (an EngineFactory instance, or the bare
    callable) so callers needing factory-level API like
    ``engine_params(key)`` don't re-resolve/instantiate it."""
    variant = json.loads(Path(variant_path).read_text())
    factory_path = engine_factory or variant.get("engineFactory")
    if not factory_path:
        name = variant.get("engine")
        if name:
            from .. import engines

            try:
                spec = engines.get_engine_spec(name)
            except KeyError:
                # an engine.json inside a not-yet-discovered engine dir:
                # load THAT dir (the --engine-json form must work
                # without PIO_TPU_ENGINE_PATH)
                engines.discovery.load_engine_dir(
                    Path(variant_path).resolve().parent
                )
                spec = engines.get_engine_spec(name)
            merged = spec.default_variant()
            merged.update(variant)
            engine = spec.build()
            out = (engine, engine.params_from_variant(merged), merged)
            return (*out, spec.factory) if return_factory else out
        raise ValueError(
            "engine.json must declare 'engineFactory' or 'engine' "
            "(or pass --engine-factory)"
        )
    _engine_dir_on_path(variant_path, factory_path)
    factory = resolve_attr(factory_path)
    obj = factory() if isinstance(factory, type) else factory
    if not hasattr(obj, "apply") and callable(obj):
        obj = obj()  # plain function factory -> Engine (or EngineFactory)
    if hasattr(obj, "apply"):  # EngineFactory object
        engine = obj.apply()
        factory_obj = obj
    else:
        engine = obj
        factory_obj = factory
    out = (engine, engine.params_from_variant(variant), variant)
    return (*out, factory_obj) if return_factory else out


def _out(msg: str) -> None:
    print(msg)


def _apply_obs_flags(args) -> None:
    """Wire the pio-obs/pio-xray knobs shared by the server/workflow
    commands: ``--telemetry-dir`` (span JSONL journal location),
    ``--no-metrics`` (404 the /metrics + /debug/xray mounts),
    ``--xray-sample-s`` (device sampler cadence) and
    ``--flight-capacity`` (slow-query flight recorder depth)."""
    from ..obs import configure, get_flight_recorder, xray

    configure(
        journal_dir=getattr(args, "telemetry_dir", None),
        metrics=(False if getattr(args, "no_metrics", False) else None),
    )
    sample_s = getattr(args, "xray_sample_s", None)
    if sample_s is not None:
        xray.set_sample_period(sample_s)
    flight_n = getattr(args, "flight_capacity", None)
    if flight_n is not None:
        get_flight_recorder().set_capacity(flight_n)
    if getattr(args, "no_profiler", False):
        # pio-scope opt-out: the servers' ensure_started() becomes a
        # no-op; the TimedLock contention lens keeps booking (its cost
        # is per-contended-acquire, not per-sample)
        from ..obs import scope

        scope.set_enabled(False)


# how long a probe child may take to bring the backend up.  The first
# process on a fresh four-chip v5e host needed 26 s, and once more than
# 30 s, to answer (chip runs, PR 21); a probe that gives up before a
# healthy host answers reports a working backend as unavailable.
_PROBE_TIMEOUT_S = 120.0


def _probe_devices(timeout_s: float):
    """jax's devices as a short-lived child process sees them:
    ``({"platform", "kind", "count"}, None)`` or ``(None, error)``.

    A child, because the caller must not take the chip itself: `status`
    has to answer when backend init hangs, and the fleet router's
    replicas need the chips the router would otherwise hold."""
    import subprocess

    code = (
        "import json, jax, jax.numpy as jnp\n"
        "x = jnp.ones((8, 8))\n"
        "assert float((x @ x)[0, 0]) == 8.0\n"
        "d = jax.devices()\n"
        "print('DEVICES=' + json.dumps({'platform': d[0].platform, "
        "'kind': d[0].device_kind, 'count': len(d)}))\n"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return None, f"backend init did not answer within {timeout_s}s"
    for line in proc.stdout.splitlines():
        if line.startswith("DEVICES="):
            return json.loads(line[len("DEVICES="):]), None
    lines = proc.stderr.strip().splitlines()
    # the raised error, not jax's traceback-filter notice
    errs = [ln for ln in lines if "Error" in ln or "error" in ln]
    return None, (errs or lines or ["backend init failed"])[-1]


def _device_line(d: dict) -> str:
    return (f"JAX devices: platform={d['platform']} kind={d['kind']!r} "
            f"count={d['count']}")


def _start_jax() -> None:
    """What every command that computes does first: place the compile
    cache, then say ONCE which devices the run has, so the platform a
    train or a server used is never a guess.  Initializes the backend
    — a chip that is absent or held by another process fails here,
    with jax's own error, before any work starts."""
    from ..parallel.mesh import describe_devices, enable_compilation_cache

    cache_dir = enable_compilation_cache()
    _out(f"{_device_line(describe_devices())}; compile cache: {cache_dir}")


def _add_obs_args(p) -> None:
    p.add_argument("--telemetry-dir", metavar="DIR",
                   help="journal pio-obs spans as JSON lines to "
                   "DIR/spans-<pid>.jsonl (size-capped rotated "
                   "segments; default: in-memory ring only; "
                   "PIO_TPU_TELEMETRY=1 journals under "
                   "$PIO_TPU_HOME/telemetry)")
    p.add_argument("--no-metrics", action="store_true",
                   help="disable the GET /metrics Prometheus "
                   "exposition and GET /debug/xray (recording still "
                   "happens; only the endpoints answer 404)")
    p.add_argument("--xray-sample-s", type=float, default=None,
                   metavar="SEC",
                   help="pio-xray device-memory sampler period "
                   "(default: $PIO_TPU_XRAY_SAMPLE_S or 10; <= 0 "
                   "disables the sampler)")
    p.add_argument("--no-profiler", action="store_true",
                   help="disable the pio-scope always-on sampling "
                   "profiler (GET /debug/pprof then answers an empty "
                   "profile; the lock-contention lens stays on; "
                   "PIO_TPU_SCOPE=0 is the env equivalent)")


# --------------------------------------------------------------------------
# app / accesskey ops (console/App.scala:34-498, console/AccessKey.scala)
# --------------------------------------------------------------------------


def _resolve_channel(md, app_id: int, name: str):
    """Channel name -> Channel for an app, or None if absent."""
    for c in md.channel_get_by_app(app_id):
        if c.name == name:
            return c
    return None


def cmd_app(args, storage: Storage) -> int:
    md = storage.get_metadata()
    es = storage.get_event_store()
    if args.app_command == "new":
        if md.app_get_by_name(args.name):
            _out(f"Error: app '{args.name}' already exists.")
            return 1
        app = md.app_insert(args.name, args.description)
        es.init_channel(app.id)
        key = md.access_key_insert(
            AccessKey(key=args.access_key or "", appid=app.id)
        )
        _out(f"Created app '{app.name}' (id {app.id}).")
        _out(f"Access key: {key}")
        return 0
    if args.app_command == "list":
        for app in md.app_get_all():
            keys = md.access_key_get_by_app(app.id)
            _out(f"{app.id:>6}  {app.name}  keys={len(keys)}")
        return 0
    if args.app_command == "show":
        app = md.app_get_by_name(args.name)
        if app is None:
            _out(f"Error: app '{args.name}' not found.")
            return 1
        _out(f"App: {app.name} (id {app.id})")
        _out(f"Description: {app.description or ''}")
        for k in md.access_key_get_by_app(app.id):
            events = ",".join(k.events) if k.events else "(all)"
            _out(f"Access key: {k.key} events={events}")
        for c in md.channel_get_by_app(app.id):
            _out(f"Channel: {c.name} (id {c.id})")
        return 0
    if args.app_command == "delete":
        app = md.app_get_by_name(args.name)
        if app is None:
            _out(f"Error: app '{args.name}' not found.")
            return 1
        for c in md.channel_get_by_app(app.id):
            es.remove_channel(app.id, c.id)
            md.channel_delete(c.id)
        es.remove_channel(app.id)
        for k in md.access_key_get_by_app(app.id):
            md.access_key_delete(k.key)
        md.app_delete(app.id)
        _out(f"Deleted app '{args.name}'.")
        return 0
    if args.app_command == "data-delete":
        app = md.app_get_by_name(args.name)
        if app is None:
            _out(f"Error: app '{args.name}' not found.")
            return 1
        if args.channel:
            chan = _resolve_channel(md, app.id, args.channel)
            if chan is None:
                _out(f"Error: channel '{args.channel}' not found.")
                return 1
            es.remove_channel(app.id, chan.id)
            es.init_channel(app.id, chan.id)
        else:
            es.remove_channel(app.id)
            es.init_channel(app.id)
        _out(f"Deleted event data of app '{args.name}'.")
        return 0
    if args.app_command == "trim":
        from ..storage.event import parse_time
        from ..tools.trim import trim_events

        app = md.app_get_by_name(args.name)
        if app is None:
            _out(f"Error: app '{args.name}' not found.")
            return 1
        channel_id = 0
        if args.channel:
            chan = _resolve_channel(md, app.id, args.channel)
            if chan is None:
                _out(f"Error: channel '{args.channel}' not found.")
                return 1
            channel_id = chan.id
        try:
            before = parse_time(args.before) if args.before else None
        except ValueError as e:
            _out(f"Error: invalid --before time: {e}")
            return 1
        try:
            n = trim_events(
                es, app.id, channel_id,
                before=before,
                event_names=args.event or None,
                keep_special=not args.all,
            )
        except ValueError as e:
            _out(f"Error: {e}")
            return 1
        _out(f"Trimmed {n} events from app '{args.name}'.")
        if args.compact:
            es.compact()
            _out("Compacted the event store (space reclaimed).")
        return 0
    if args.app_command == "compact":
        es.compact()
        _out("Compacted the event store (space reclaimed).")
        return 0
    if args.app_command == "channel-new":
        app = md.app_get_by_name(args.name)
        if app is None:
            _out(f"Error: app '{args.name}' not found.")
            return 1
        try:
            c = md.channel_insert(args.channel, app.id)
        except ValueError as e:
            _out(f"Error: {e}")
            return 1
        es.init_channel(app.id, c.id)
        _out(f"Created channel '{c.name}' (id {c.id}).")
        return 0
    if args.app_command == "channel-delete":
        app = md.app_get_by_name(args.name)
        if app is None:
            _out(f"Error: app '{args.name}' not found.")
            return 1
        chan = _resolve_channel(md, app.id, args.channel)
        if chan is None:
            _out(f"Error: channel '{args.channel}' not found.")
            return 1
        es.remove_channel(app.id, chan.id)
        md.channel_delete(chan.id)
        _out(f"Deleted channel '{args.channel}'.")
        return 0
    raise AssertionError(args.app_command)


def cmd_accesskey(args, storage: Storage) -> int:
    md = storage.get_metadata()
    if args.ak_command == "new":
        app = md.app_get_by_name(args.app_name)
        if app is None:
            _out(f"Error: app '{args.app_name}' not found.")
            return 1
        key = md.access_key_insert(
            AccessKey(key="", appid=app.id, events=args.events or [])
        )
        _out(f"Access key: {key}")
        return 0
    if args.ak_command == "list":
        keys = md.access_key_get_all()
        if args.app_name:
            app = md.app_get_by_name(args.app_name)
            if app is None:
                _out(f"Error: app '{args.app_name}' not found.")
                return 1
            keys = [k for k in keys if k.appid == app.id]
        for k in keys:
            events = ",".join(k.events) if k.events else "(all)"
            _out(f"{k.key}  appid={k.appid}  events={events}")
        return 0
    if args.ak_command == "delete":
        md.access_key_delete(args.key)
        _out(f"Deleted access key {args.key}.")
        return 0
    raise AssertionError(args.ak_command)


# --------------------------------------------------------------------------
# train / deploy / eval / servers
# --------------------------------------------------------------------------


def _load_engine_for_args(args, return_factory: bool = False):
    """ONE resolution path for every workflow command: ``--engine NAME``
    (pio-forge registry dispatch — no engine.json file needed) or
    ``--engine-json PATH``.  Returns ``(engine, ep, variant,
    variant_key[, factory])`` where ``variant_key`` is the
    engine-variant string instances are registered/looked up under."""
    from ..tools.template_gallery import verify_template_min_version

    name = getattr(args, "engine", None)
    if name:
        from .. import engines

        spec = engines.get_engine_spec(name)
        engine, ep, variant = engines.resolve(name)
        out = (engine, ep, variant, spec.instance_variant_key())
        return (*out, spec.factory) if return_factory else out
    verify_template_min_version(Path(args.engine_json).parent)
    loaded = load_engine_from_variant(
        args.engine_json, args.engine_factory, return_factory=return_factory
    )
    out = (*loaded[:3], str(args.engine_json))
    return (*out, loaded[3]) if return_factory else out


def _resolve_instance_id(md, engine_id: str, variant_key: str,
                         explicit: Optional[str]):
    """The deploy/foldin glue, deduplicated: an explicit instance id is
    verified, else the latest COMPLETED instance for (engine_id,
    variant_key) wins.  Returns ``(iid, error_message)``."""
    if explicit:
        if md.engine_instance_get(explicit) is None:
            return None, f"engine instance '{explicit}' not found."
        return explicit, None
    latest = md.engine_instance_get_latest_completed(
        engine_id, "1", variant_key
    )
    if latest is None:
        return None, ("no completed engine instance found; "
                      "run train first.")
    return latest.id, None


def cmd_engines(args, storage: Storage) -> int:
    """pio-forge registry view: every engine one registration away from
    `train/deploy/eval --engine NAME` — built-ins plus anything on
    PIO_TPU_ENGINE_PATH."""
    from .. import engines

    if args.engines_command == "list":
        specs = engines.list_engine_specs()
        for spec in specs:
            src = "" if spec.source == "builtin" else f"  [{spec.source}]"
            _out(f"{spec.name:<26} {spec.description}{src}")
        _out(f"({len(specs)} engines registered)")
        return 0
    if args.engines_command == "describe":
        try:
            spec = engines.get_engine_spec(args.name)
        except KeyError as e:
            _out(f"Error: {e.args[0]}")
            return 1
        _out(json.dumps(spec.describe(), indent=2))
        return 0
    raise AssertionError(args.engines_command)


def cmd_train(args, storage: Storage) -> int:
    from ..controller.base import WorkflowContext
    from ..workflow.params import WorkflowParams
    from ..workflow.train import run_train

    if getattr(args, "scan_cache", False):
        import os

        os.environ["PIO_TPU_SCAN_CACHE"] = "1"
    if args.coordinator or args.num_processes is not None:
        # multi-host bring-up: each host runs the same `pio-tpu train`
        # with its own --process-id; collectives then span hosts
        from ..parallel.mesh import distributed_init

        distributed_init(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    _start_jax()
    engine, ep, variant, variant_key, factory = _load_engine_for_args(
        args, return_factory=True
    )
    if args.engine_params_key:
        # programmatic params override: EngineFactory.engine_params(key)
        # (reference CreateWorkflow --engine-params-key)
        if not hasattr(factory, "engine_params"):
            _out("Error: --engine-params-key needs an EngineFactory with "
                 "engine_params(key).")
            return 1
        try:
            ep = factory.engine_params(args.engine_params_key)
        except KeyError as e:
            _out(f"Error: unknown engine params key: {e}")
            return 1
    ctx = WorkflowContext(storage=storage, mode="Training", batch=args.batch)
    wp = WorkflowParams(
        batch=args.batch,
        skip_sanity_check=args.skip_sanity_check,
        stop_after_read=args.stop_after_read,
        stop_after_prepare=args.stop_after_prepare,
    )
    iid = run_train(
        engine, ep, ctx=ctx, workflow_params=wp,
        engine_id=variant.get("id", "default"),
        engine_variant=variant_key,
        engine_factory=args.engine_factory or variant.get("engineFactory", ""),
    )
    _out(f"Training completed. Engine instance id: {iid}")
    return 0


def cmd_deploy(args, storage: Storage) -> int:
    from ..controller.base import WorkflowContext
    from ..server.serving import EngineServer, ServerConfig

    if getattr(args, "replicas", 0) and args.replicas > 1:
        # pio-surge fleet mode: N replica processes + one router
        return _deploy_fleet(args)
    _start_jax()
    if getattr(args, "scan_cache", False):
        import os

        os.environ["PIO_TPU_SCAN_CACHE"] = "1"
    # pio-hive: `deploy --multi tenants.json` boots ONE server hosting
    # every tenant in the manifest.  Tenant 0 is the anchor (loaded
    # eagerly as the server's own components, pinned); the rest load
    # lazily on first query under the registry's memory budget.
    tenants = None
    if getattr(args, "multi", None):
        tenants = _build_tenant_registry(args, storage)
        anchor = tenants.spec(tenants.anchor_key)
        if anchor.engine_name:
            args.engine = anchor.engine_name
        else:
            args.engine_json = anchor.engine_json
        if anchor.instance_id and not args.engine_instance_id:
            args.engine_instance_id = anchor.instance_id
    engine, ep, variant, variant_key = _load_engine_for_args(args)
    md = storage.get_metadata()
    engine_id = variant.get("id", "default")
    iid, err = _resolve_instance_id(
        md, engine_id, variant_key, args.engine_instance_id
    )
    if err:
        _out(f"Error: {err}")
        return 1
    ctx = WorkflowContext(storage=storage, mode="Serving")
    server = EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(
            host=args.ip, port=args.port,
            feedback=args.feedback,
            event_server_url=args.event_server_url,
            access_key=args.accesskey,
            log_url=args.log_url,
            log_prefix=args.log_prefix,
            microbatch=args.microbatch,
            query_timeout_s=args.query_timeout,
            feedback_capacity=args.feedback_capacity,
            breaker_failures=args.breaker_failures,
            breaker_reset_s=args.breaker_reset,
            foldin_poll_s=args.foldin_poll,
            max_connections=args.max_connections,
            slo_ms=getattr(args, "slo_ms", None),
        ),
        engine_id=engine_id,
        engine_variant=variant_key,
        tenants=tenants,
    )
    # undeploy a stale server holding the port (CreateServer.scala:266-288)
    import urllib.error
    import urllib.request

    stale_host = "127.0.0.1" if args.ip == "0.0.0.0" else args.ip
    try:
        with urllib.request.urlopen(
            urllib.request.Request(
                f"http://{stale_host}:{args.port}/stop", method="POST"
            ),
            timeout=2,
        ):
            _out(f"Undeployed stale engine server on port {args.port}.")
            import time

            time.sleep(0.5)
    except (urllib.error.URLError, OSError):
        pass
    if args.port_file:
        # bind now so the announced port is real (--port 0 = ephemeral);
        # the replica spawner (deploy --replicas) reads this file
        server._bind()
        pf = Path(args.port_file)
        pf.parent.mkdir(parents=True, exist_ok=True)
        pf.write_text(f"{server.port}\n")
    _out(f"Deploying engine instance {iid} on {args.ip}:{server.port}")
    server.serve_forever()
    return 0


def _build_tenant_registry(args, storage):
    """Parse ``--multi`` tenants.json into a TenantRegistry, resolving
    each tenant's app id + access key from metadata (attribution and
    accessKey-routing need them; absent apps just lose conversion
    scanning, loudly)."""
    from ..tenancy import TenantRegistry, load_tenant_manifest

    specs, opts = load_tenant_manifest(args.multi)
    for spec in specs:
        if spec.engine_json is None and spec.engine_name is None:
            _out(f"Error: tenant {spec.key_str} has no engineJson or "
                 "engine name.")
            raise SystemExit(1)
    if getattr(args, "memory_budget", None) is not None:
        opts["memory_budget_bytes"] = args.memory_budget
    # pio-pilot: `--autopilot` wins over the manifest's "autopilot"
    # block; "on"/"1" enables with defaults, anything else is a JSON
    # knob dict (alpha/beta/minLift/minSamples/maxStep/minWeight/...)
    ap = getattr(args, "autopilot", None)
    if ap:
        if ap.strip().lower() in ("1", "on", "true"):
            opts["autopilot"] = {}
        else:
            try:
                opts["autopilot"] = json.loads(ap)
            except json.JSONDecodeError as e:
                _out(f"Error: --autopilot is neither 'on' nor valid "
                     f"JSON: {e}")
                raise SystemExit(1)
    md = storage.get_metadata()
    for spec in specs:
        app = md.app_get_by_name(spec.app)
        if app is None:
            _out(f"Warning: tenant app '{spec.app}' not found in "
                 "metadata; accessKey routing and online-eval "
                 "conversion scanning are off for it.")
            continue
        spec.app_id = app.id
        if spec.access_key is None:
            keys = md.access_key_get_by_app(app.id)
            if keys:
                spec.access_key = keys[0].key
    return TenantRegistry(specs, **opts)


def _deploy_fleet(args) -> int:
    """``deploy --replicas N``: spawn N single-replica deploy
    subprocesses on ephemeral ports, then run the router in THIS
    process on the requested port.  Ctrl-C / POST /stop tears the
    whole fleet down."""
    import atexit
    import tempfile

    from ..server.router import (
        Replica, ReplicaSupervisor, RouterConfig, RouterServer,
        spawn_replica, wait_for_port_file,
    )

    # a chip serves ONE process: on a TPU host each replica is pinned to
    # its own chip and N may not exceed the chips.  The count comes from
    # a short-lived child — this process is the router and must never
    # take a chip itself.  JAX_PLATFORMS=cpu is the operator putting the
    # replicas on the CPU, where processes do not contend for a device.
    import os

    pin = False
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() != "cpu":
        info, err = _probe_devices(_PROBE_TIMEOUT_S)
        if info is None:
            _out(f"Error: cannot start replicas: JAX backend "
                 f"unavailable: {err}")
            return 1
        pin = info["platform"] == "tpu"
        if pin and args.replicas > info["count"]:
            _out(f"Error: --replicas {args.replicas} exceeds the "
                 f"{info['count']} {info['kind']} chip(s) of this host; "
                 "a chip serves one process.")
            return 1
        _out(_device_line(info)
             + ("; one replica per chip" if pin else ""))
    coord_dir = Path(tempfile.mkdtemp(prefix="pio-surge-fleet-"))
    extra = []
    for flag, val in (
        ("--engine-factory", args.engine_factory),
        ("--engine-instance-id", args.engine_instance_id),
        ("--microbatch", args.microbatch),
        # pio-hive: every replica hosts the same tenant manifest, so
        # the fleet multiplexes N tenants x N replicas
        ("--multi", getattr(args, "multi", None)),
    ):
        if val:
            extra += [flag, str(val)]
    for flag, val in (
        ("--query-timeout", args.query_timeout),
        ("--foldin-poll", args.foldin_poll),
        ("--max-connections", args.max_connections),
        ("--memory-budget", getattr(args, "memory_budget", None)),
        # pio-lens: every replica arms its own burn-rate gauges too —
        # the router's merged /metrics then shows them per replica
        ("--slo-ms", getattr(args, "slo_ms", None)),
    ):
        if val is not None:
            extra += [flag, str(val)]
    if getattr(args, "scan_cache", False):
        extra.append("--scan-cache")
    if getattr(args, "no_profiler", False):
        extra.append("--no-profiler")
    def spawner(i):
        return spawn_replica(args.engine_json, i, coord_dir,
                             extra_args=extra,
                             engine_name=getattr(args, "engine", None),
                             chip=i if pin else None)

    spawned = [spawner(i) for i in range(args.replicas)]
    supervisor = (
        ReplicaSupervisor(spawner)
        if not getattr(args, "no_respawn", False) else None
    )

    def reap():
        # the supervisor may have replaced boot-time processes with
        # respawns — reap whatever is CURRENTLY tracked, plus the boot
        # list (dead originals reap as no-ops)
        procs = [s["proc"] for s in spawned]
        if supervisor is not None:
            procs += supervisor.live_procs()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()

    atexit.register(reap)
    replicas = []
    for s in spawned:
        port = wait_for_port_file(s)
        _out(f"Replica {s['index']} up on 127.0.0.1:{port} "
             f"(log: {s['log_path']})")
        replica = Replica(
            f"replica-{s['index']}", "127.0.0.1", port,
            breaker_failures=args.breaker_failures,
        )
        if supervisor is not None:
            supervisor.attach(replica, s)
        replicas.append(replica)
    router = RouterServer(replicas, RouterConfig(
        host=args.ip, port=args.port,
        health_interval_s=args.health_interval,
        max_connections=args.max_connections,
        push_foldin_s=args.push_foldin,
        slo_ms=getattr(args, "slo_ms", None),
    ), supervisor=supervisor)
    if args.port_file:
        router._bind()
        pf = Path(args.port_file)
        pf.parent.mkdir(parents=True, exist_ok=True)
        pf.write_text(f"{router.port}\n")
    _out(f"Router fronting {len(replicas)} replicas on "
         f"{args.ip}:{args.port}")
    try:
        router.serve_forever()
    finally:
        reap()
    return 0


def cmd_foldin(args, storage: Storage) -> int:
    """pio-live: incremental ALS fold-in (one-shot or --watch daemon).

    Scans the event store past the per-(app, channel) watermark, solves
    the touched/new factor rows against the frozen opposite table, and
    publishes delta links that a deployed engine server
    (``deploy --foldin-poll``) patches in live — fresh events become
    fresh predictions without ``pio train`` or ``/reload``."""
    from ..controller.base import WorkflowContext
    from ..live import FoldInRunner

    _start_jax()
    engine, ep, variant, variant_key = _load_engine_for_args(args)
    md = storage.get_metadata()
    engine_id = variant.get("id", "default")
    iid, err = _resolve_instance_id(
        md, engine_id, variant_key, args.engine_instance_id
    )
    if err:
        _out(f"Error: {err}")
        return 1
    ctx = WorkflowContext(storage=storage, mode="Serving")
    try:
        runner = FoldInRunner(
            storage, engine, ep, iid, channel_id=args.channel, ctx=ctx,
            from_now=args.from_now,
        )
    except ValueError as e:
        _out(f"Error: {e}")
        return 1
    _out(f"Fold-in on instance {iid} (app {runner.app_id}, "
         f"watermark rowid {runner.cursor}, chain seq {runner.seq})")
    if args.watch:
        _out(f"Watching for events every {args.interval}s "
             "(Ctrl-C to stop)...")
        try:
            runner.watch(
                interval_s=args.interval,
                max_cycles=args.max_cycles,
                on_cycle=lambda s: _out(json.dumps(s)),
            )
        except KeyboardInterrupt:
            _out("Stopped.")
        return 0
    stats = runner.cycle()
    if stats is None:
        _out(f"No new events past watermark rowid {runner.cursor}; "
             "nothing to fold in.")
    else:
        _out(json.dumps(stats))
    return 0


def cmd_eval(args, storage: Storage) -> int:
    from ..controller.base import WorkflowContext
    from ..workflow.evaluate import run_evaluation

    _start_jax()
    if getattr(args, "scan_cache", False):
        import os

        os.environ["PIO_TPU_SCAN_CACHE"] = "1"
    if getattr(args, "engine", None):
        # pio-forge: `eval --engine NAME` dispatches the spec's
        # declared evaluation — no dotted path to remember
        from .. import engines

        spec = engines.get_engine_spec(args.engine)
        if spec.evaluation is None:
            _out(f"Error: engine '{spec.name}' declares no evaluation; "
                 "pass a dotted evaluation path instead.")
            return 1
        evaluation = spec.evaluation
    elif args.evaluation:
        evaluation = resolve_attr(args.evaluation)
    else:
        _out("Error: pass an evaluation dotted path or --engine NAME.")
        return 1
    if callable(evaluation) and not hasattr(evaluation, "engine"):
        evaluation = evaluation()
    params_list = None
    if args.engine_params_generator:
        gen = resolve_attr(args.engine_params_generator)
        if callable(gen) and not hasattr(gen, "engine_params_list"):
            gen = gen()
        params_list = list(gen.engine_params_list)
    ctx = WorkflowContext(storage=storage, mode="Evaluation", batch=args.batch)
    eval_class = args.evaluation
    if not eval_class and getattr(args, "engine", None):
        from .. import engines

        eval_class = engines.get_engine_spec(args.engine).evaluation_path
    eval_id, result = run_evaluation(
        evaluation, params_list, ctx=ctx,
        evaluation_class=eval_class or "",
        engine_params_generator_class=args.engine_params_generator or "",
        parallelism=args.parallelism,
    )
    _out(result.to_one_liner())
    _out(f"Evaluation completed. Instance id: {eval_id}")
    return 0


def cmd_eventserver(args, storage: Storage) -> int:
    if getattr(args, "workers", 0) and args.workers > 1:
        return _eventserver_fleet(args, storage)
    from ..server.event_server import EventServer, EventServerConfig

    owned = None
    if getattr(args, "owned_shards", None):
        owned = [int(s) for s in args.owned_shards.split(",") if s != ""]
    elif getattr(args, "worker_index", None) is not None:
        # shard-owner worker (pio-levee): stripe ownership by index
        from ..server.ingest_router import shards_for_worker

        es = storage.get_event_store()
        owned = shards_for_worker(
            args.worker_index, args.worker_count,
            getattr(es, "n_shards", 1),
        )
    server = EventServer(
        storage, EventServerConfig(
            host=args.ip, port=args.port,
            stats=args.stats,
            write_retries=args.write_retries,
            write_backoff_s=args.write_backoff,
            max_connections=args.max_connections,
            wal_dir=getattr(args, "wal_dir", None),
            wal_fsync=not getattr(args, "no_wal_fsync", False),
            owned_shards=owned,
            ttl_s=getattr(args, "ttl", None),
            compact_interval_s=getattr(args, "compact_interval", None),
            slo_ms=getattr(args, "slo_ms", None),
        )
    )
    if getattr(args, "port_file", None):
        # bind first so the announced port is real (--port 0 =
        # ephemeral); the ingest-router spawner reads this file
        server._bind()
        pf = Path(args.port_file)
        pf.parent.mkdir(parents=True, exist_ok=True)
        pf.write_text(f"{server.port}\n")
    role = f" (shard owner: {owned})" if owned is not None else ""
    _out(f"Event server running on {args.ip}:{server.port}{role}")
    server.serve_forever()
    return 0


def _eventserver_fleet(args, storage: Storage) -> int:
    """pio-levee: ``eventserver --workers N`` — spawn N shard-owner
    worker processes (each owning ``shard % N == index`` of the sharded
    store, each with its own ingest WAL) and run the ingest router in
    THIS process on the requested port."""
    import atexit
    import tempfile

    from ..server.ingest_router import (
        IngestRouterConfig, boot_ingest_fleet,
    )

    es = storage.get_event_store()
    n_shards = getattr(es, "n_shards", 1)
    if args.workers > n_shards:
        _out(f"error: --workers {args.workers} exceeds the store's "
             f"{n_shards} shards; extra workers would own nothing")
        return 1
    coord_dir = Path(tempfile.mkdtemp(prefix="pio-levee-fleet-"))
    wal_root = Path(args.wal_dir) if getattr(args, "wal_dir", None) \
        else coord_dir / "wal"
    extra = []
    for flag, val in (
        ("--write-retries", args.write_retries),
        ("--write-backoff", args.write_backoff),
        ("--max-connections", args.max_connections),
        ("--ttl", getattr(args, "ttl", None)),
        ("--compact-interval", getattr(args, "compact_interval", None)),
        # each worker arms its own write-SLO burn gauges; the router's
        # merged /metrics shows them per worker
        ("--slo-ms", getattr(args, "slo_ms", None)),
    ):
        if val is not None:
            extra += [flag, str(val)]
    if getattr(args, "no_wal_fsync", False):
        extra.append("--no-wal-fsync")
    if getattr(args, "no_profiler", False):
        extra.append("--no-profiler")
    router, spawned = boot_ingest_fleet(
        args.workers, n_shards, coord_dir,
        config=IngestRouterConfig(
            host=args.ip, port=args.port,
            max_connections=args.max_connections,
        ),
        wal_root=wal_root, extra_args=extra,
        respawn=not getattr(args, "no_respawn", False),
    )
    for w, s in zip(router.workers, spawned):
        _out(f"Ingest worker {w.index} up on 127.0.0.1:{w.port} "
             f"owning shards {w.shards} (log: {s['log_path']})")

    def reap():
        procs = [s["proc"] for s in spawned]
        if router.supervisor is not None:
            procs += router.supervisor.live_procs()
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()

    atexit.register(reap)
    if getattr(args, "port_file", None):
        router._bind()
        pf = Path(args.port_file)
        pf.parent.mkdir(parents=True, exist_ok=True)
        pf.write_text(f"{router.port}\n")
    _out(f"Ingest router fronting {args.workers} shard-owner workers "
         f"({n_shards} shards) on {args.ip}:{args.port}")
    try:
        router.serve_forever()
    finally:
        reap()
    return 0


def cmd_adminserver(args, storage: Storage) -> int:
    from ..server.admin import AdminServer

    server = AdminServer(storage, host=args.ip, port=args.port)
    _out(f"Admin server running on {args.ip}:{args.port}")
    server.serve_forever()
    return 0


def cmd_dashboard(args, storage: Storage) -> int:
    from ..server.dashboard import DashboardServer

    server = DashboardServer(storage, host=args.ip, port=args.port)
    _out(f"Dashboard running on {args.ip}:{args.port}")
    server.serve_forever()
    return 0


def cmd_import(args, storage: Storage) -> int:
    from ..tools.import_export import import_events

    es = storage.get_event_store()
    es.init_channel(args.appid, args.channel)
    # import_events infers the format (extension or content magic) and
    # routes to the JSON-lines / columnar / parquet reader itself
    n = import_events(args.input, es, args.appid, args.channel)
    _out(f"Imported {n} events.")
    return 0


def cmd_export(args, storage: Storage) -> int:
    from ..tools.import_export import columnar_path, export_events

    es = storage.get_event_store()
    es.init_channel(args.appid, args.channel)
    n = export_events(args.output, es, args.appid, args.channel,
                      fmt=args.format)
    from ..tools.import_export import infer_format

    fmt = args.format or infer_format(args.output)
    written = columnar_path(args.output) if fmt == "columnar" else args.output
    _out(f"Exported {n} events to {written}.")
    return 0


def cmd_template(args, storage: Storage) -> int:
    """Offline gallery (`console/Template.scala:130-427` analogue)."""
    import http.client
    import urllib.error

    from ..tools.template_gallery import (
        TemplateVersionError, fetch_index, list_templates, scaffold,
        scaffold_from_archive, scaffold_from_index, scaffold_from_url,
    )

    if args.template_command == "list":
        if args.index_url:
            # remote gallery browse (Template.scala:130-170 analogue)
            try:
                entries = fetch_index(args.index_url)
            except (ValueError, urllib.error.URLError, OSError,
                    http.client.HTTPException) as e:
                _out(f"Error: {e}")
                return 1
            for e in entries:
                _out(f"{e['name']:<26} {e.get('description', '')}")
            return 0
        for t in list_templates():
            _out(f"{t.name:<26} {t.description}")
        return 0
    if args.template_command == "get":
        try:
            if args.from_archive:
                target = scaffold_from_archive(
                    args.from_archive, args.directory or args.name
                )
            elif args.from_url:
                target = scaffold_from_url(
                    args.from_url, args.directory or args.name
                )
            elif args.index_url:
                target = scaffold_from_index(
                    args.name, args.directory or args.name, args.index_url
                )
            else:
                target = scaffold(args.name, args.directory or args.name)
        except (KeyError, FileExistsError, FileNotFoundError, ValueError,
                TemplateVersionError, urllib.error.URLError, OSError,
                http.client.HTTPException) as e:
            # HTTPException covers truncated/garbage responses
            # (IncompleteRead, BadStatusLine) that are not OSErrors
            _out(f"Error: {e}")
            return 1
        _out(f"Engine template '{args.name}' created at {target}/")
        return 0
    raise AssertionError(args.template_command)


def cmd_build(args, storage: Storage) -> int:
    """Validate the engine variant and register its manifest.

    The reference `build` runs sbt then `RegisterEngine` (Console.scala:
    772-802); with Python engines the build step reduces to import-checking
    the factory and upserting the `EngineManifest`.
    """
    from ..storage.metadata import EngineManifest
    from ..tools.template_gallery import verify_template_min_version

    verify_template_min_version(Path(args.engine_json).parent)
    try:
        engine, ep, variant = load_engine_from_variant(
            args.engine_json, args.engine_factory
        )
    except Exception as e:
        _out(f"Error: engine variant failed to load: {e}")
        return 1
    engine_id = variant.get("id", Path(args.engine_json).resolve().parent.name)
    storage.get_metadata().manifest_upsert(
        EngineManifest(
            id=engine_id,
            version=args.engine_version,
            name=engine_id,
            description=variant.get("description"),
            files=[str(Path(args.engine_json).resolve())],
            engine_factory=args.engine_factory
            or variant.get("engineFactory", ""),
        )
    )
    _out(f"Engine '{engine_id}' built and registered "
         f"(version {args.engine_version}).")
    return 0


def cmd_unregister(args, storage: Storage) -> int:
    variant = json.loads(Path(args.engine_json).read_text())
    engine_id = variant.get("id", Path(args.engine_json).resolve().parent.name)
    storage.get_metadata().manifest_delete(engine_id, args.engine_version)
    _out(f"Engine '{engine_id}' unregistered.")
    return 0


def cmd_run(args, storage: Storage) -> int:
    """Run an arbitrary dotted-path main under the framework env
    (Console `run` analogue — there it spark-submits a user class)."""
    fn = resolve_attr(args.main_class)
    if not callable(fn):
        _out(f"Error: {args.main_class} resolved to a non-callable "
             f"{type(fn).__name__}.")
        return 1
    rv = fn(*args.args)
    return int(rv) if isinstance(rv, int) else 0


def cmd_undeploy(args, storage: Storage) -> int:
    """POST /stop to a deployed engine server (Console.scala undeploy)."""
    import urllib.error
    import urllib.request

    url = f"http://{args.ip}:{args.port}/stop"
    try:
        with urllib.request.urlopen(
            urllib.request.Request(url, method="POST"), timeout=5
        ) as r:
            r.read()
    except (urllib.error.URLError, OSError) as e:
        _out(f"Error: cannot undeploy {args.ip}:{args.port}: {e}")
        return 1
    _out(f"Undeployed engine server at {args.ip}:{args.port}.")
    return 0


def cmd_upgrade(args, storage: Storage) -> int:
    """The reference phones home for new versions (WorkflowUtils.scala:
    220-225); this build is offline, so report the installed version."""
    _out(f"pio-tpu {__version__} — no network egress; upgrade checks "
         "are disabled in this environment.")
    return 0


def cmd_status(args, storage: Storage) -> int:
    """Sanity-check env + storage (console/Console.scala:1028-1085)."""
    _out(f"predictionio_tpu {__version__}")
    # probe the backend in a BOUNDED subprocess: `status` is the command
    # an operator runs to diagnose a backend that hangs or fails at
    # init, so it must answer — and it must not hold the chip
    if args.probe_timeout <= 0:
        _out("JAX devices: probe skipped (--probe-timeout 0)")
    else:
        info, err = _probe_devices(args.probe_timeout)
        if info is not None:
            _out(_device_line(info))
        else:
            _out(f"Warning: JAX backend unavailable: {err}")
    try:
        storage.verify_all_data_objects()
        _out("Storage: OK (metadata, event store, model data verified)")
    except Exception as e:
        _out(f"Error: storage verification failed: {e}")
        return 1
    # optional accelerators: absent ones degrade to slower pure-Python
    # paths, never to failures — status reports which are active
    from ..native import native_available

    caps = [f"native C++ runtime: {'OK' if native_available() else 'absent'}"]
    for mod, what in (("pandas", "hash-based id dictionaries"),
                      ("pyarrow", "Parquet import/export")):
        try:
            __import__(mod)
            caps.append(f"{mod}: OK ({what})")
        except Exception:
            caps.append(f"{mod}: absent ({what} falls back)")
    _out("Optional fast paths: " + "; ".join(caps))
    _out("Ready.")
    return 0


# --------------------------------------------------------------------------
# parser
# --------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio-tpu",
        description="predictionio_tpu console "
        "(the `pio` command, rebuilt TPU-native)",
    )
    p.add_argument("--version", action="version",
                   version=f"pio-tpu {__version__}")
    p.add_argument("--verbose", action="store_true",
                   help="chatty logging (WorkflowUtils.modifyLogging)")
    p.add_argument("--debug", action="store_true",
                   help="debug logging")
    sub = p.add_subparsers(dest="command", required=True)

    ap = sub.add_parser("app", help="manage apps")
    aps = ap.add_subparsers(dest="app_command", required=True)
    x = aps.add_parser("new")
    x.add_argument("name")
    x.add_argument("--description")
    x.add_argument("--access-key")
    aps.add_parser("list")
    x = aps.add_parser("show")
    x.add_argument("name")
    x = aps.add_parser("delete")
    x.add_argument("name")
    x = aps.add_parser("data-delete")
    x.add_argument("name")
    x.add_argument("--channel")
    x = aps.add_parser("trim", help="delete old events")
    x.add_argument("name")
    x.add_argument("--before", help="delete events before this ISO8601 time")
    x.add_argument("--event", action="append",
                   help="restrict to these event names (repeatable)")
    x.add_argument("--channel")
    x.add_argument("--all", action="store_true",
                   help="also delete $set/$unset/$delete property events")
    x.add_argument("--compact", action="store_true",
                   help="reclaim freed space afterwards (sqlite VACUUM)")
    aps.add_parser("compact",
                   help="reclaim space freed by trims/deletes")
    x = aps.add_parser("channel-new")
    x.add_argument("name")
    x.add_argument("channel")
    x = aps.add_parser("channel-delete")
    x.add_argument("name")
    x.add_argument("channel")

    ak = sub.add_parser("accesskey", help="manage access keys")
    aks = ak.add_subparsers(dest="ak_command", required=True)
    x = aks.add_parser("new")
    x.add_argument("app_name")
    x.add_argument("events", nargs="*")
    x = aks.add_parser("list")
    x.add_argument("app_name", nargs="?")
    x = aks.add_parser("delete")
    x.add_argument("key")

    en = sub.add_parser("engines",
                        help="pio-forge engine registry (built-in "
                        "templates + PIO_TPU_ENGINE_PATH dirs)")
    ens = en.add_subparsers(dest="engines_command", required=True)
    ens.add_parser("list", help="list every registered engine")
    x = ens.add_parser("describe",
                       help="JSON spec of one registered engine")
    x.add_argument("name")

    t = sub.add_parser("train", help="train an engine")
    _add_obs_args(t)
    t.add_argument("--engine-json", default="engine.json")
    t.add_argument("--engine", metavar="NAME",
                   help="train a REGISTERED engine by name (pio-forge "
                   "registry dispatch, no engine.json needed; see "
                   "`pio-tpu engines list`)")
    t.add_argument("--engine-factory")
    t.add_argument("--batch", default="")
    t.add_argument("--skip-sanity-check", action="store_true")
    t.add_argument("--stop-after-read", action="store_true")
    t.add_argument("--stop-after-prepare", action="store_true")
    t.add_argument("--engine-params-key",
                   help="use EngineFactory.engine_params(<key>) instead of "
                   "the engine.json params")
    t.add_argument("--coordinator",
                   help="multi-host: coordinator address host:port")
    t.add_argument("--num-processes", type=int)
    t.add_argument("--process-id", type=int)
    t.add_argument("--scan-cache", action="store_true",
                   help="snapshot columnar event scans to npz keyed by a "
                   "table write-version (storage/scan_cache.py); repeat "
                   "trains on an unchanged table skip the sqlite scan")

    d = sub.add_parser("deploy", help="deploy an engine server")
    _add_obs_args(d)
    d.add_argument("--scan-cache", action="store_true",
                   help="snapshot columnar event scans to npz keyed by a "
                   "table write-version (storage/scan_cache.py)")
    d.add_argument("--engine-json", default="engine.json")
    d.add_argument("--engine", metavar="NAME",
                   help="deploy a REGISTERED engine by name (serves "
                   "the latest instance trained with `train --engine "
                   "NAME`)")
    d.add_argument("--engine-factory")
    d.add_argument("--engine-instance-id")
    d.add_argument("--ip", default="0.0.0.0")
    d.add_argument("--port", type=int, default=8000)
    d.add_argument("--feedback", action="store_true")
    d.add_argument("--event-server-url")
    d.add_argument("--accesskey")
    d.add_argument("--log-url",
                   help="ship serving errors to this URL via POST "
                   "(reference CreateServer remoteLog)")
    d.add_argument("--log-prefix", default="",
                   help="string prepended to each shipped log payload")
    d.add_argument("--microbatch", choices=("auto", "on", "off"),
                   default="auto",
                   help="coalesce concurrent queries into one batched "
                   "device call (auto: when the algorithm batch-"
                   "predicts; off restores bitwise per-request "
                   "determinism)")
    d.add_argument("--query-timeout", type=float, default=None,
                   metavar="SEC",
                   help="per-request time budget: expiry answers a "
                   "structured 503 + Retry-After instead of queueing "
                   "device work behind a client that gave up "
                   "(per-request override: /queries.json?timeout=SEC)")
    d.add_argument("--feedback-capacity", type=int, default=1024,
                   help="bounded feedback/remote-log delivery queue "
                   "size; overflow drops the OLDEST entry and counts "
                   "it in the status JSON")
    d.add_argument("--breaker-failures", type=int, default=5,
                   help="consecutive delivery failures that open the "
                   "circuit breaker for a dead event server / log "
                   "collector")
    d.add_argument("--breaker-reset", type=float, default=10.0,
                   metavar="SEC",
                   help="seconds an open breaker waits before letting "
                   "one probe through")
    d.add_argument("--flight-capacity", type=int, default=None,
                   metavar="N",
                   help="slow-query flight recorder keeps the N "
                   "slowest requests' full span trees (default: "
                   "$PIO_TPU_XRAY_FLIGHT_N or 16; see /debug/xray)")
    d.add_argument("--foldin-poll", type=float, default=None,
                   metavar="SEC",
                   help="pio-live: poll for fold-in delta links every "
                   "SEC seconds and patch them into the serving model "
                   "in place (factor rows + top-k index, no "
                   "stop-the-world reload); pair with a `pio-tpu "
                   "foldin --watch` daemon")
    d.add_argument("--max-connections", type=int, default=512,
                   help="concurrent-connection cap; connection "
                   "attempts past it get a structured 503 and are "
                   "closed (slow-loris guard)")
    d.add_argument("--slo-ms", type=float, default=None, metavar="MS",
                   help="pio-lens: latency SLO in milliseconds — arms "
                   "the pio_slo_burn_rate{window} error-budget gauges "
                   "on this server's latency histogram (fleet mode: "
                   "on the router's forward histogram AND every "
                   "replica's serving histogram)")
    d.add_argument("--replicas", type=int, default=0, metavar="N",
                   help="pio-surge fleet mode: spawn N replica "
                   "processes on ephemeral ports and run a router on "
                   "--port fanning out over them with health checks, "
                   "failover masking, and rolling fold-in delta push")
    d.add_argument("--health-interval", type=float, default=1.0,
                   metavar="SEC",
                   help="fleet mode: router health-check period")
    d.add_argument("--push-foldin", type=float, default=None,
                   metavar="SEC",
                   help="fleet mode: run a rolling fold-in delta push "
                   "across the replicas every SEC seconds (each "
                   "replica applies in place, one at a time — "
                   "availability never drops below N-1)")
    d.add_argument("--port-file", metavar="PATH",
                   help="announce the BOUND port (after --port 0 "
                   "resolution) by writing it to PATH — how fleet "
                   "replicas report in")
    d.add_argument("--no-respawn", action="store_true",
                   help="fleet mode: disable the replica-respawn "
                   "supervisor (default: a dead replica process is "
                   "respawned with capped exponential backoff and "
                   "booked in pio_replica_respawns_total)")
    d.add_argument("--multi", metavar="TENANTS_JSON",
                   help="pio-hive: host EVERY tenant of this manifest "
                   "in one process (or one fleet with --replicas): "
                   "lazy load + LRU eviction under a device-memory "
                   "budget, per-tenant breakers/quotas/metrics, and "
                   "weighted sticky A/B variant routing; tenant 0 is "
                   "the pinned anchor")
    d.add_argument("--memory-budget", type=float, default=None,
                   metavar="BYTES",
                   help="override the manifest's memoryBudgetBytes "
                   "(0 = unbounded): resident tenant models are "
                   "LRU-evicted to stay under it; pinned and "
                   "in-flight tenants are never evicted")
    d.add_argument("--autopilot", metavar="ON|JSON",
                   help="pio-pilot: run the SPRT auto-weight "
                   "controller on this registry's experiments "
                   "('on' for defaults, or a JSON knob dict — "
                   "alpha/beta/minLift/minSamples/maxStep/minWeight/"
                   "burnThreshold; requires --multi); every decision "
                   "lands in a pio-tower manifest and at "
                   "GET /debug/experiments")

    fi = sub.add_parser(
        "foldin",
        help="pio-live: fold new events into the deployed model "
        "incrementally (no full retrain)",
    )
    _add_obs_args(fi)
    fi.add_argument("--engine-json", default="engine.json")
    fi.add_argument("--engine", metavar="NAME",
                    help="fold into a REGISTERED engine by name")
    fi.add_argument("--engine-factory")
    fi.add_argument("--engine-instance-id",
                    help="fold into this instance (default: latest "
                    "completed)")
    fi.add_argument("--channel", type=int, default=0)
    fi.add_argument("--watch", action="store_true",
                    help="keep running: poll the event-store watermark "
                    "and fold in whenever it advances")
    fi.add_argument("--interval", type=float, default=5.0,
                    metavar="SEC",
                    help="watch-mode poll period (default 5s)")
    fi.add_argument("--max-cycles", type=int, default=None,
                    help="stop --watch after N non-empty fold-in "
                    "cycles (smoke/bench harnesses)")
    fi.add_argument("--from-now", action="store_true",
                    help="on the FIRST run (no watermark, no chain): "
                    "start the cursor at the store's current high-water "
                    "mark instead of re-folding the history the full "
                    "train already saw")

    e = sub.add_parser("eval", help="run an evaluation sweep")
    _add_obs_args(e)
    e.add_argument("evaluation", nargs="?",
                   help="dotted path to an Evaluation (or factory); "
                   "omit with --engine NAME to run the registered "
                   "engine's declared evaluation")
    e.add_argument("--engine", metavar="NAME",
                   help="run the evaluation a REGISTERED engine "
                   "declares in its spec")
    e.add_argument("engine_params_generator", nargs="?",
                   help="dotted path to an EngineParamsGenerator")
    e.add_argument("--batch", default="")
    e.add_argument("--parallelism", type=int, default=1,
                   help="candidates scored concurrently (>1 disables "
                        "FastEval prefix caching)")
    e.add_argument("--scan-cache", action="store_true",
                   help="snapshot columnar event scans to npz keyed by a "
                   "table write-version (storage/scan_cache.py)")

    ev = sub.add_parser("eventserver", help="run the event server")
    _add_obs_args(ev)
    ev.add_argument("--ip", default="0.0.0.0")
    ev.add_argument("--port", type=int, default=7070)
    ev.add_argument("--stats", action="store_true", default=True)
    ev.add_argument("--write-retries", type=int, default=3,
                    help="attempts (first try included) for a transient "
                    "storage failure before the route answers 503 + "
                    "Retry-After")
    ev.add_argument("--write-backoff", type=float, default=0.05,
                    metavar="SEC",
                    help="base backoff between storage retries "
                    "(decorrelated jitter grows it toward a 10x cap)")
    ev.add_argument("--max-connections", type=int, default=512,
                    help="concurrent-connection cap; attempts past it "
                    "get a structured 503 and are closed")
    # pio-levee: fault-isolated multi-process ingest
    ev.add_argument("--workers", type=int, default=0, metavar="N",
                    help="boot N shard-owner worker processes (each "
                    "owning shard %% N == index of the sharded store, "
                    "each with its own ingest WAL) behind an ingest "
                    "router in this process; 0/1 = single process")
    ev.add_argument("--wal-dir", metavar="DIR",
                    help="group-commit ingest WAL root: events are "
                    "fsynced here before the 2xx and drained to sqlite "
                    "in the background; a crash replays the tail on "
                    "next boot (off by default: ack = sqlite commit)")
    ev.add_argument("--no-wal-fsync", action="store_true",
                    help="skip the per-group fsync (faster, but a HOST "
                    "crash may lose the last commit interval; a mere "
                    "process crash still replays everything)")
    ev.add_argument("--ttl", type=float, metavar="SEC",
                    help="purge events older than SEC on a maintenance "
                    "timer (bounded live window)")
    ev.add_argument("--compact-interval", type=float, metavar="SEC",
                    help="VACUUM owned shard files every SEC (reclaims "
                    "TTL-purged space; off by default)")
    ev.add_argument("--owned-shards", metavar="CSV",
                    help="restrict writes to these shard indexes "
                    "(shard-owner worker mode; e.g. 0,2,4)")
    ev.add_argument("--worker-index", type=int, metavar="I",
                    help="this worker's index in a --workers fleet "
                    "(stripes ownership: shard %% count == I)")
    ev.add_argument("--worker-count", type=int, default=1, metavar="N",
                    help="fleet size for --worker-index striping")
    ev.add_argument("--port-file", metavar="PATH",
                    help="write the bound port here after bind "
                    "(--port 0 = ephemeral; the fleet spawner reads it)")
    ev.add_argument("--no-respawn", action="store_true",
                    help="with --workers: do not respawn dead workers "
                    "(the chaos suite wants corpses to stay dead)")
    ev.add_argument("--slo-ms", type=float, default=None, metavar="MS",
                    help="event-write latency SLO: arms the multi-"
                    "window pio_slo_burn_rate gauges over the event-"
                    "write histogram (with --workers, each shard owner "
                    "arms its own)")

    ad = sub.add_parser("adminserver", help="run the admin API server")
    _add_obs_args(ad)
    ad.add_argument("--ip", default="127.0.0.1")
    ad.add_argument("--port", type=int, default=7071)

    db = sub.add_parser("dashboard", help="run the evaluation dashboard")
    _add_obs_args(db)
    db.add_argument("--ip", default="127.0.0.1")
    db.add_argument("--port", type=int, default=9000)

    im = sub.add_parser("import",
                        help="import events (JSON-lines, .npz columnar, "
                        "or .parquet)")
    im.add_argument("--appid", type=int, required=True)
    im.add_argument("--channel", type=int, default=0)
    im.add_argument("--input", required=True)

    ex = sub.add_parser("export", help="export events to a file")
    ex.add_argument("--appid", type=int, required=True)
    ex.add_argument("--channel", type=int, default=0)
    ex.add_argument("--output", required=True)
    ex.add_argument("--format", choices=["json", "columnar", "parquet"],
                    help="default: json; columnar if output is .npz, "
                    "parquet if .parquet")

    tp = sub.add_parser("template", help="engine template gallery")
    tps = tp.add_subparsers(dest="template_command", required=True)
    tl = tps.add_parser("list")
    tl.add_argument("--index-url", metavar="URL",
                    help="browse a REMOTE JSON template index instead "
                    "of the built-in gallery (Template.scala:130-170 "
                    "analogue)")
    x = tps.add_parser("get")
    x.add_argument("name")
    x.add_argument("directory", nargs="?")
    x.add_argument("--from-archive", metavar="PATH",
                   help="scaffold from a local zip/tar engine archive "
                   "instead of the built-in gallery")
    x.add_argument("--from-url", metavar="URL",
                   help="download a zip/tar engine archive over "
                   "http(s) and scaffold from it (the remote half of "
                   "the reference's template download, "
                   "Template.scala:171-300)")
    x.add_argument("--index-url", metavar="URL",
                   help="look NAME up in a remote JSON template index "
                   "and download its archive")

    b = sub.add_parser("build", help="validate + register an engine")
    b.add_argument("--engine-json", default="engine.json")
    b.add_argument("--engine-factory")
    b.add_argument("--engine-version", default="1")

    ur = sub.add_parser("unregister", help="remove an engine manifest")
    ur.add_argument("--engine-json", default="engine.json")
    ur.add_argument("--engine-version", default="1")

    rn = sub.add_parser("run", help="run a dotted-path main under the env")
    rn.add_argument("main_class")
    rn.add_argument("args", nargs="*")

    ud = sub.add_parser("undeploy", help="stop a deployed engine server")
    ud.add_argument("--ip", default="127.0.0.1")
    ud.add_argument("--port", type=int, default=8000)

    sub.add_parser("upgrade", help="check for framework upgrades")
    stp = sub.add_parser("status", help="check environment and storage")
    stp.add_argument("--probe-timeout", type=float,
                     default=_PROBE_TIMEOUT_S,
                     help="seconds to wait for jax backend init "
                     "before reporting it unreachable (status must "
                     "never hang on it)")
    sub.add_parser("version")
    sub.add_parser("help", help="show this help")
    return p


_DISPATCH = {
    "app": cmd_app,
    "accesskey": cmd_accesskey,
    "engines": cmd_engines,
    "train": cmd_train,
    "deploy": cmd_deploy,
    "foldin": cmd_foldin,
    "eval": cmd_eval,
    "eventserver": cmd_eventserver,
    "adminserver": cmd_adminserver,
    "dashboard": cmd_dashboard,
    "import": cmd_import,
    "export": cmd_export,
    "template": cmd_template,
    "build": cmd_build,
    "unregister": cmd_unregister,
    "run": cmd_run,
    "undeploy": cmd_undeploy,
    "upgrade": cmd_upgrade,
    "status": cmd_status,
}


def main(argv: Optional[list[str]] = None,
         storage: Optional[Storage] = None) -> int:
    args = build_parser().parse_args(argv)
    from ..tools.template_gallery import TemplateVersionError
    from ..utils.logging import setup_logging

    setup_logging(verbose=args.verbose, debug=args.debug)
    if args.command == "version":
        _out(f"pio-tpu {__version__}")
        return 0
    if args.command == "help":
        build_parser().print_help()
        return 0
    _apply_obs_flags(args)
    storage = storage or get_storage()
    try:
        return _DISPATCH[args.command](args, storage)
    except TemplateVersionError as e:
        _out(f"Error: {e}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
