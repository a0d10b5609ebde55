"""What every driver shares: the look for a chip, the compile cache inside
the checkout, the clock of set-up, the traced sub-window, the program's
compile counters, and the comparison of numbers with their limits.
"""

from __future__ import annotations

import os
import shutil
import time
from contextlib import contextmanager

from .cells import ROOT

WORK_DIR = ROOT / ".perfbench_work"     # traces; emptied by every run
CACHE_DIR = ROOT / ".jax_cache"         # the program's own default place


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def place_compile_cache() -> str:
    """Fix the persistent compile cache at one path inside the checkout
    (the path is part of the cache's key), unless the caller placed it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CACHE_DIR.mkdir(exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def require_chips(chips: int) -> dict:
    """The device as JAX reports it; raises NoChip where a run cannot
    stand for the cell.  Nothing falls back to the CPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    platform = devices[0].platform
    if platform == "cpu":
        raise NoChip("JAX found no accelerator (platform cpu)")
    if len(devices) < chips:
        raise NoChip(
            f"the cell asks for {chips} chips and JAX found {len(devices)}"
        )
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def seed_key(seed: int, stream: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    import jax

    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def seeded_tables(cfg: dict, seed: int, stream: int) -> tuple:
    """(user table, item table) of the configuration's sizes, float32,
    N(0, 1)/sqrt(rank), made on the device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    rank = cfg["rank"]

    @jax.jit
    def draw(key):
        ku, ki = jax.random.split(key)
        scale = 1.0 / (rank ** 0.5)
        return (
            jax.random.normal(ku, (cfg["n_users"], rank), jnp.float32) * scale,
            jax.random.normal(ki, (cfg["n_items"], rank), jnp.float32) * scale,
        )

    return draw(seed_key(seed, stream))


def memory_peak_bytes() -> int:
    """Peak bytes the process held on the fullest chip: the allocator's
    peak in use (arguments, results, live arrays) plus the peak it reserved
    for the compiled programs' temporaries, which `peak_bytes_in_use` leaves
    out (0 where the backend keeps no statistics, as the CPU's)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_peak_in_use_bytes() -> int:
    """The allocator's `peak_bytes_in_use` alone, on the fullest chip."""
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()
    )


def compile_count() -> int:
    """Executables the program built or fetched from its cache so far
    (obs/xray books one per `backend_compile_duration` event)."""
    from predictionio_tpu.obs import xray

    xray.install()
    return xray.total_backend_compiles()


class SetupClock:
    """Seconds of set-up by phase; `setup_s` runs from the process's start
    to the first measured request or sweep."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.phases: dict = {}
        self.setup_s = None

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = (
                self.phases.get(name, 0.0) + time.perf_counter() - t0
            )

    def window_opens(self) -> float:
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        return now


class Tracer:
    """A `jax.profiler` trace of a sub-window, marked on the host plane by
    a `bench.window` span, and reduced once the window has closed."""

    def __init__(self, name: str):
        self.dir = WORK_DIR / f"trace-{name}"
        self.t0 = self.t1 = None
        self._span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # the host's Python is not traced
        options.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.t1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        from . import tracereduce

        try:
            return tracereduce.reduce_file(
                tracereduce.find_xplane(self.dir),
                window_ns=int((self.t1 - self.t0) * 1e9),
            )
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, [{"name", "value", "limit"}]): every number compared has
    a limit of its own in the configuration's file; one without a limit,
    or a limit without its number, is an error of the files, not a pass."""
    missing = sorted(set(numbers) ^ set(limits))
    if missing:
        raise KeyError(f"numbers compared and limits differ: {missing}")
    compared = [
        {"name": k, "value": float(numbers[k]), "limit": float(limits[k])}
        for k in sorted(numbers)
    ]
    correct = all(
        c["value"] == c["value"] and c["value"] <= c["limit"]
        for c in compared
    )
    return correct, compared
