"""pio-xray: compiler + device observability.

The layer below pio-obs's request metrics — the XLA compiler and the
device — fails silently: a shape-churn recompile or an OOM-adjacent
allocator shows up only as a mysteriously slow histogram bucket (the
exact failure mode ALX calls out for TPU-resident factorization).
This module makes both visible:

* **Compile observability.**  ``install()`` hooks ``jax.monitoring``:
  every backend compile lands in ``pio_jit_compile_seconds`` and
  increments ``pio_jit_compiles_total{fn}``; compilation-cache events
  (hit / miss / request) land in ``pio_compile_cache_events_total
  {kind}`` so cold-start and warm-start deploys are distinguishable on
  ``/metrics``.  Attribution of a compile to a *function* rides a
  thread-local set by :func:`instrument`-wrapped entry points (the
  repo's jitted ALS halves and the top-k scorers); compiles outside
  any tracked call book under ``fn="untracked"``.
* **Recompilation detector.**  :func:`instrument` wraps a jitted
  callable and fingerprints every call's arg signature (shapes /
  dtypes / static kwargs).  A signature never seen before means XLA is
  about to compile; the event — including the **delta** against the
  previous signature (which arg changed, from what, to what) and the
  current trace id — is recorded into a bounded ring surfaced at
  ``GET /debug/xray``.  "Why did my query recompile?" is answered by
  one curl instead of an XLA log safari.
* **Device observability.**  :func:`sample_devices_once` reads
  ``device.memory_stats()`` per device (bytes-in-use / peak / limit)
  into ``pio_device_memory_bytes{device,stat}``; backends without
  allocator stats (CPU) fall back to summing live-array bytes per
  device, so the gauges exist on every backend.
  :func:`start_sampler` runs it on a daemon thread, registered at
  server/workflow boot the way the delivery-queue breaker gauges are.
* **Optional cost analysis.**  With ``PIO_TPU_XRAY_COST=1``, each new
  signature of an instrumented fn is AOT-lowered once for
  ``cost_analysis()`` FLOP/byte estimates
  (``pio_jit_fn_cost{fn,kind}``) — opt-in because it duplicates the
  trace work.

No module-level jax import: ``obs`` stays importable from jax-free
processes (piolint, the event server); jax loads lazily inside
``install()`` / the sampler / the wrappers' first use, all of which
only run in processes that already traced something.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Callable, Optional

from . import get_registry, log_buckets
from .trace import current_trace_id

__all__ = [
    "device_high_water",
    "install",
    "instrument",
    "jit_stats",
    "note_compilation_cache",
    "compile_cache_summary",
    "recompile_events",
    "sample_devices_once",
    "set_sample_period",
    "start_sampler",
    "stop_sampler",
    "total_backend_compiles",
    "xray_payload",
]

_registry = get_registry()

JIT_COMPILES = _registry.counter(
    "pio_jit_compiles_total",
    "XLA backend compiles attributed to the instrumented entry point "
    "that dispatched them (fn=\"untracked\" for compiles outside any "
    "tracked call)",
    labels=("fn",),
)
JIT_COMPILE_SECONDS = _registry.histogram(
    "pio_jit_compile_seconds",
    "XLA backend compile wall time per compile "
    "(/jax/core/compile/backend_compile_duration)",
    buckets=log_buckets(1e-3, 1000.0, per_decade=4),
)
COMPILE_CACHE_EVENTS = _registry.counter(
    "pio_compile_cache_events_total",
    "jax persistent-compilation-cache events (request/hit/miss): "
    "hit/request ~= 1 is a warm start, ~= 0 a cold one",
    labels=("kind",),
)
DEVICE_MEMORY = _registry.gauge(
    "pio_device_memory_bytes",
    "Per-device memory from device.memory_stats() (stat=bytes_in_use/"
    "peak_bytes_in_use/bytes_limit) or, on backends without allocator "
    "stats, summed live-array bytes (stat=live_bytes)",
    labels=("device", "stat"),
)
JIT_FN_COST = _registry.gauge(
    "pio_jit_fn_cost",
    "cost_analysis() estimate for the most recent compile of an "
    "instrumented fn (kind=flops/bytes_accessed; PIO_TPU_XRAY_COST=1)",
    labels=("fn", "kind"),
)

# the full schema appears on every process's first scrape (pio-obs
# contract); the unlabeled histogram child must exist for its ladder
JIT_COMPILE_SECONDS.child()

_CACHE_EVENT_KINDS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/compile_requests_use_cache": "request",
    "/jax/compilation_cache/tasks_using_cache": "task_using_cache",
    "/jax/compilation_cache/task_disabled_cache": "task_disabled",
}
_COMPILE_DURATION_EVENT = "/jax/core/compile/backend_compile_duration"


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


# -- call-signature fingerprinting ----------------------------------------


def _key_leaf(x):
    """Hashable structural key for one argument (cheap — runs on every
    instrumented call)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("arr", tuple(shape), str(dtype))
    if isinstance(x, (tuple, list)):
        return tuple(_key_leaf(v) for v in x)
    if isinstance(x, dict):
        return tuple((k, _key_leaf(v)) for k, v in sorted(x.items()))
    if isinstance(x, (int, float, bool, str, bytes, type(None))):
        return x
    return (type(x).__name__, repr(x)[:64])


def _sig_key(args: tuple, kwargs: dict) -> tuple:
    return (
        tuple(_key_leaf(a) for a in args),
        tuple((k, _key_leaf(v)) for k, v in sorted(kwargs.items())),
    )


def _describe_leaf(x) -> str:
    """Human descriptor for the recompile ring (runs only on new
    signatures)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}[{','.join(str(d) for d in shape)}]"
    if isinstance(x, (tuple, list)):
        inner = ",".join(_describe_leaf(v) for v in x)
        return f"({inner})"
    if isinstance(x, dict):
        inner = ",".join(
            f"{k}={_describe_leaf(v)}" for k, v in sorted(x.items())
        )
        return f"{{{inner}}}"
    r = repr(x)
    return r if len(r) <= 64 else r[:61] + "..."


def _describe_call(args: tuple, kwargs: dict) -> tuple:
    """``((label, descriptor), ...)`` — positional args by index,
    static/keyword args by name."""
    out = [(f"arg{i}", _describe_leaf(a)) for i, a in enumerate(args)]
    out += [(k, _describe_leaf(v)) for k, v in sorted(kwargs.items())]
    return tuple(out)


def signature_delta(old: Optional[tuple], new: tuple) -> Optional[dict]:
    """What changed between two described signatures — the payload an
    operator reads to learn which arg's shape churned."""
    if old is None:
        return None
    od, nd = dict(old), dict(new)
    changed = [
        {"arg": k, "from": od[k], "to": nd[k]}
        for k in nd if k in od and od[k] != nd[k]
    ]
    added = [{"arg": k, "value": nd[k]} for k in nd if k not in od]
    removed = [{"arg": k, "value": od[k]} for k in od if k not in nd]
    return {"changed": changed, "added": added, "removed": removed}


# -- state ------------------------------------------------------------------

_tl = threading.local()  # .fn = name of the instrumented call in flight


class _XrayState:
    """All mutable pio-xray bookkeeping under one lock (none of it is
    on a sub-microsecond path; compiles and new signatures are rare)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._installed = False
        self._install_error: Optional[str] = None
        self._fns: dict[str, dict] = {}
        self._ring: collections.deque = collections.deque(
            maxlen=_env_int("PIO_TPU_XRAY_RING", 64)
        )
        self._cache_events: dict[str, int] = {}
        self._cache_dir: Optional[str] = None
        self._devices: list = []
        self._devices_at: Optional[float] = None
        self._sampler: Optional[threading.Thread] = None
        self._sampler_stop: Optional[threading.Event] = None
        self._sample_period = _env_float("PIO_TPU_XRAY_SAMPLE_S", 10.0)

    # -- fn tracking -------------------------------------------------------
    def _fn_state_locked(self, name: str) -> dict:
        st = self._fns.get(name)
        if st is None:
            st = {
                "calls": 0,
                "signatures": set(),
                "last_described": None,
                "backend_compiles": 0,
                "compile_seconds_total": 0.0,
                "last_compile_seconds": None,
                "cost": None,
            }
            self._fns[name] = st
        return st

    def observe_call(self, name: str, key: tuple) -> bool:
        """Count one call; True when the signature is (probably) new —
        the caller then builds the pretty descriptors and calls
        :meth:`register_signature`, which re-checks atomically."""
        with self._lock:
            st = self._fn_state_locked(name)
            st["calls"] += 1
            return key not in st["signatures"]

    def register_signature(self, name: str, key: tuple,
                           described: tuple) -> Optional[dict]:
        """Atomically admit a new signature; returns the ring entry
        (None when a concurrent call already registered it)."""
        with self._lock:
            st = self._fn_state_locked(name)
            if key in st["signatures"]:
                return None
            prev = st["last_described"]
            st["signatures"].add(key)
            st["last_described"] = described
            nth = len(st["signatures"])
            entry = {
                "fn": name,
                "at": time.time(),
                "traceId": current_trace_id(),
                "kind": "compile" if nth == 1 else "recompile",
                "nthSignature": nth,
                "signature": [
                    {"arg": k, "value": v} for k, v in described
                ],
                "delta": signature_delta(prev, described),
            }
            self._ring.append(entry)
            return entry

    def note_backend_compile(self, name: Optional[str],
                             duration_s: float) -> None:
        with self._lock:
            st = self._fn_state_locked(name or "untracked")
            st["backend_compiles"] += 1
            st["compile_seconds_total"] += duration_s
            st["last_compile_seconds"] = duration_s

    def set_cost(self, name: str, cost: dict) -> None:
        with self._lock:
            self._fn_state_locked(name)["cost"] = dict(cost)

    # -- misc notes --------------------------------------------------------
    def note_cache_event(self, kind: str) -> None:
        with self._lock:
            self._cache_events[kind] = self._cache_events.get(kind, 0) + 1

    def note_cache_dir(self, cache_dir: Optional[str]) -> None:
        with self._lock:
            self._cache_dir = cache_dir

    def set_devices(self, devices: list) -> None:
        with self._lock:
            self._devices = list(devices)
            self._devices_at = time.time()

    def set_sample_period(self, period_s: float) -> None:
        with self._lock:
            self._sample_period = float(period_s)

    # -- install / sampler lifecycle --------------------------------------
    def claim_install(self) -> bool:
        """True when this call won the (single) install slot."""
        with self._lock:
            if self._installed:
                return False
            self._installed = True
            return True

    def set_install_error(self, error: Optional[str]) -> None:
        with self._lock:
            self._install_error = error

    def installed(self) -> bool:
        with self._lock:
            return self._installed and self._install_error is None

    def sampler_slot(self) -> Optional[threading.Event]:
        """Claim the sampler slot; None when one is already running or
        sampling is disabled (period <= 0)."""
        with self._lock:
            if self._sampler is not None and self._sampler.is_alive():
                return None
            if self._sample_period <= 0:
                return None
            self._sampler_stop = threading.Event()
            return self._sampler_stop

    def set_sampler(self, thread: Optional[threading.Thread]) -> None:
        with self._lock:
            self._sampler = thread

    def sampler_state(self) -> tuple:
        with self._lock:
            return self._sampler_stop, self._sample_period

    # -- snapshots ---------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            fns = {
                name: {
                    "calls": st["calls"],
                    "signatures": len(st["signatures"]),
                    "backendCompiles": st["backend_compiles"],
                    "compileSecondsTotal": round(
                        st["compile_seconds_total"], 6),
                    "lastCompileSeconds": st["last_compile_seconds"],
                    **({"cost": st["cost"]} if st["cost"] else {}),
                }
                for name, st in self._fns.items()
            }
            return {
                "installed": self._installed,
                "installError": self._install_error,
                "fns": fns,
                "recompiles": list(self._ring),
                "cacheEvents": dict(self._cache_events),
                "cacheDir": self._cache_dir,
                "devices": list(self._devices),
                "devicesSampledAt": self._devices_at,
            }

    def reset_for_tests(self) -> None:
        with self._lock:
            self._fns = {}
            self._ring.clear()
            self._cache_events = {}


_STATE = _XrayState()


# -- jax.monitoring listeners ----------------------------------------------


def _on_duration_event(event: str, duration_s: float, **kw) -> None:
    if event != _COMPILE_DURATION_EVENT:
        return
    fn = getattr(_tl, "fn", None)
    JIT_COMPILE_SECONDS.child().observe(duration_s)
    JIT_COMPILES.labels(fn=fn or "untracked").inc()
    _STATE.note_backend_compile(fn, duration_s)


def _on_event(event: str, **kw) -> None:
    kind = _CACHE_EVENT_KINDS.get(event)
    if kind is not None:
        COMPILE_CACHE_EVENTS.labels(kind=kind).inc()
        _STATE.note_cache_event(kind)


def install() -> bool:
    """Register the jax.monitoring listeners (idempotent, thread-safe).
    Returns True when monitoring is active after the call.  A jax
    without the monitoring API degrades gracefully: instrumented
    wrappers then count their own new-signature compiles."""
    if not _STATE.claim_install():
        return _STATE.installed()
    try:
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(
            _on_duration_event
        )
        monitoring.register_event_listener(_on_event)
        return True
    except Exception as e:  # pragma: no cover - jax API drift guard
        _STATE.set_install_error(f"{type(e).__name__}: {e}")
        return False


# -- instrumented jit entry points -----------------------------------------


def _cost_enabled() -> bool:
    return os.environ.get("PIO_TPU_XRAY_COST") == "1"


def _analyze_cost(name: str, fn, args: tuple, kwargs: dict) -> None:
    """Opt-in AOT cost analysis for a freshly-seen signature.  Never
    raises: estimates are advisory, and some backends/fns don't
    support lowering outside a trace."""
    try:
        analysis = fn.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        flops = float(analysis.get("flops", 0.0))
        nbytes = float(analysis.get("bytes accessed", 0.0))
        JIT_FN_COST.labels(fn=name, kind="flops").set(flops)
        JIT_FN_COST.labels(fn=name, kind="bytes_accessed").set(nbytes)
        _STATE.set_cost(name, {"flops": flops, "bytesAccessed": nbytes})
    except Exception:
        pass


class _Instrumented:
    """Callable wrapper around a jitted fn: fingerprints each call,
    feeds the recompile detector, and attributes any backend compile
    fired during the call to ``name`` via a thread-local.  Unknown
    attributes (``_cache_size``, ``lower`` ...) delegate to the wrapped
    jit object, so AOT APIs and cache introspection keep working."""

    __slots__ = ("_fn", "_name", "__wrapped__")

    def __init__(self, fn: Callable, name: str):
        self._fn = fn
        self._name = name
        self.__wrapped__ = fn

    def __call__(self, *args, **kwargs):
        name = self._name
        if _STATE.observe_call(name, key := _sig_key(args, kwargs)):
            entry = _STATE.register_signature(
                name, key, _describe_call(args, kwargs)
            )
            if entry is not None:
                if not _STATE.installed():
                    # no monitoring hook: the wrapper itself is the
                    # compile counter (a new jit signature compiles)
                    JIT_COMPILES.labels(fn=name).inc()
                if _cost_enabled():
                    _analyze_cost(name, self._fn, args, kwargs)
        prev = getattr(_tl, "fn", None)
        _tl.fn = name
        try:
            return self._fn(*args, **kwargs)
        finally:
            _tl.fn = prev

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self) -> str:
        return f"<xray.instrument({self._name!r}) of {self._fn!r}>"


def instrument(name: str) -> Callable[[Callable], Callable]:
    """Decorator: ``instrument("als.half")(jax.jit(f))``.  Installing
    the monitoring listeners rides along — by the time an instrumented
    fn exists, the process is a jax process.

    Instrumented seams (grep for ``xray.instrument(`` to re-derive):
    ``als.half_iteration`` / ``als.phase_probe`` / ``als.sharded_half``
    / ``als.sweep_half`` / ``als.expand_sides`` / ``als.sq_err_sum``
    (models/als.py), ``topk.*`` (ops/topk.py), and ``live.foldin_solve``
    (live/foldin.py — a steady fold-in daemon must show one signature
    per padded (B, K) rung, not one per cycle)."""

    def deco(fn: Callable) -> Callable:
        install()
        return _Instrumented(fn, name)

    return deco


# -- device sampling --------------------------------------------------------

_MEM_STATS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def _live_bytes_by_device() -> dict:
    """Fallback accounting: sum live jax array bytes per device (the
    CPU backend exposes no allocator stats)."""
    import jax

    out: dict = {}
    for a in jax.live_arrays():
        try:
            shards = getattr(a, "addressable_shards", None)
            if shards:
                for sh in shards:
                    out[sh.device] = (
                        out.get(sh.device, 0) + int(sh.data.nbytes)
                    )
            else:
                d = next(iter(a.devices()))
                out[d] = out.get(d, 0) + int(a.nbytes)
        except Exception:
            continue
    return out


def sample_devices_once() -> list:
    """One sampling pass over ``jax.devices()``; sets the
    ``pio_device_memory_bytes`` gauges and caches the snapshot for
    ``/debug/xray``.  Safe to call from tests and scrape handlers."""
    import jax

    out = []
    live = None
    for d in jax.devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            picked = {
                k: int(stats[k]) for k in _MEM_STATS if k in stats
            }
            source = "memory_stats"
        else:
            if live is None:
                live = _live_bytes_by_device()
            picked = {"live_bytes": int(live.get(d, 0))}
            source = "live_arrays"
        label = f"{d.platform}:{d.id}"
        for stat, v in picked.items():
            DEVICE_MEMORY.labels(device=label, stat=stat).set(float(v))
        out.append({
            "device": label,
            "kind": str(getattr(d, "device_kind", d.platform)),
            "source": source,
            "stats": picked,
        })
    _STATE.set_devices(out)
    return out


def set_sample_period(period_s: float) -> None:
    """Sampler cadence; <= 0 disables future :func:`start_sampler`
    calls (running samplers stop at their next tick)."""
    _STATE.set_sample_period(period_s)
    if period_s <= 0:
        stop_sampler()


def start_sampler(period_s: Optional[float] = None) -> bool:
    """Start the daemon device sampler (idempotent — one per process,
    registered at server/workflow boot like the breaker gauges).
    Returns True when a sampler is running after the call."""
    if period_s is not None:
        _STATE.set_sample_period(period_s)
    stop = _STATE.sampler_slot()
    if stop is None:
        _stop, period = _STATE.sampler_state()
        return period > 0 and _stop is not None and not _stop.is_set()

    def loop():
        while True:
            try:
                sample_devices_once()
            except Exception:
                pass  # a flaky backend must not kill the sampler
            _ignored, period = _STATE.sampler_state()
            if period <= 0 or stop.wait(max(period, 0.05)):
                return

    t = threading.Thread(
        target=loop, name="pio-xray-sampler", daemon=True
    )
    _STATE.set_sampler(t)
    t.start()
    return True


def stop_sampler() -> None:
    stop, _period = _STATE.sampler_state()
    if stop is not None:
        stop.set()
    _STATE.set_sampler(None)


# -- mesh / cache hook ------------------------------------------------------


def note_compilation_cache(cache_dir: Optional[str]) -> None:
    """Called by ``parallel.mesh.enable_compilation_cache`` so the
    /debug/xray payload names the active cache directory."""
    install()
    _STATE.note_cache_dir(cache_dir)


# -- read side --------------------------------------------------------------


def jit_stats() -> dict:
    return _STATE.snapshot()["fns"]


def total_backend_compiles() -> int:
    """Backend compiles booked so far, all fns + untracked — pio-tower
    diffs this per sweep to surface mid-train recompile churn (a sweep
    that recompiled is a sweep whose wall time lies about steady
    state)."""
    snap = _STATE.snapshot()
    return sum(st["backendCompiles"] for st in snap["fns"].values())


def device_high_water() -> Optional[int]:
    """Max bytes across devices from the most recent sample:
    ``peak_bytes_in_use`` where the allocator reports it, else the
    current in-use/live figure — the single high-water number a run
    manifest records per sweep."""
    snap = _STATE.snapshot()
    best: Optional[int] = None
    for s in snap["devices"]:
        stats = s.get("stats") or {}
        v = stats.get("peak_bytes_in_use")
        if v is None:
            v = stats.get("bytes_in_use", stats.get("live_bytes"))
        if v is not None and (best is None or v > best):
            best = int(v)
    return best


def recompile_events() -> list:
    return _STATE.snapshot()["recompiles"]


def compile_cache_summary() -> dict:
    """``{"dir", "events"}``: the persistent compile cache this process
    used and its hit/miss/request counts so far (the same numbers as
    ``pio_compile_cache_events_total{kind}``)."""
    snap = _STATE.snapshot()
    return {"dir": snap["cacheDir"], "events": snap["cacheEvents"]}


def xray_payload() -> dict:
    """The ``GET /debug/xray`` document (docs/ARCHITECTURE.md "X-ray"
    lists the schema).  Builds from cached state only — serving a
    scrape never imports jax or touches a device."""
    from .flight import get_flight_recorder

    snap = _STATE.snapshot()
    exemplars = [
        {"le": le, "traceId": ex, "value": v, "at": ts}
        for le, ex, v, ts in _query_latency_exemplars()
    ]
    return {
        "monitoring": {
            "installed": snap["installed"],
            "installError": snap["installError"],
        },
        "jit": snap["fns"],
        "recompiles": snap["recompiles"],
        "compileCache": {
            "dir": snap["cacheDir"],
            "events": snap["cacheEvents"],
        },
        "devices": {
            "sampledAt": snap["devicesSampledAt"],
            "samples": snap["devices"],
        },
        "flight": get_flight_recorder().summary(spans=True),
        "latencyExemplars": exemplars,
    }


def _query_latency_exemplars() -> list:
    from . import QUERY_LATENCY

    return QUERY_LATENCY.child().exemplar_items()
