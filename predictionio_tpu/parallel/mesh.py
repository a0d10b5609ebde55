"""Device mesh construction + sharding helpers.

The TPU-native replacement for the reference's SparkContext factory
(`/root/reference/core/src/main/scala/io/prediction/workflow/WorkflowContext.scala:25-44`):
where every reference workflow entered distribution by constructing a
SparkContext, every workflow here enters it by constructing a
`jax.sharding.Mesh` over the visible devices.  Single-chip runs get a 1-device
mesh and the same code path (XLA elides trivial collectives).

Multi-host: call :func:`distributed_init` once per process before
:func:`make_mesh`; `jax.devices()` then spans all hosts and collectives ride
ICI within a slice / DCN across slices — the NCCL/MPI-free equivalent of the
reference's Spark executor fabric (SURVEY §2.7).
"""

from __future__ import annotations

import math
import os
from pathlib import Path
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "make_mesh",
    "distributed_init",
    "enable_compilation_cache",
    "describe_devices",
    "data_sharding",
    "replicated",
    "shard_put",
    "pad_to_multiple",
    "DATA_AXIS",
    "MODEL_AXIS",
]


DATA_AXIS = "data"
MODEL_AXIS = "model"


def distributed_init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up (`jax.distributed.initialize`); no-op when args
    are absent and the env provides no cluster spec."""
    if coordinator_address is None and num_processes is None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


# the one compile-cache location the code itself ever picks: inside the
# checkout, so it is the same path in every process of every run (the
# path is part of the cache key — a directory that moves never hits)
_REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Persist XLA executables across processes; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it into
    ``jax_compilation_cache_dir``, so the operator's placement stands and
    no directory is set in code.  Unset: the fixed ``<repo>/.jax_cache``
    — never under ``PIO_TPU_HOME`` (smokes and benches point that at a
    fresh temp dir every run).  A pip-installed package has no checkout
    to write into: there the variable is required, and the failure says
    so.

    No minimum compile time unless the operator set one
    (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``): the serving
    warm-up ladder is dozens of sub-second compiles, and a deploy that
    re-pays all of them on every boot is the cold start the cache
    exists to remove.

    Hit/miss/request counts surface as
    ``pio_compile_cache_events_total{kind}`` (pio-xray hooks
    ``jax.monitoring``), so a deploy's cold-start vs warm-start is
    readable straight off ``/metrics``.
    """
    from ..obs import xray

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(_REPO_CACHE_DIR)
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError as e:
            raise RuntimeError(
                f"cannot create the compile cache at {cache_dir} ({e}); "
                "set JAX_COMPILATION_CACHE_DIR to a writable directory "
                "(required when predictionio_tpu is installed rather "
                "than run from a checkout)"
            ) from e
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the listeners must exist BEFORE the first compile books a cache
    # event, or cold-start counts undercount
    xray.note_compilation_cache(cache_dir)
    return cache_dir


def describe_devices() -> dict:
    """``{"platform", "kind", "count"}`` as jax reports the default
    backend — what `train`/`deploy`/`foldin` print once at start so the
    device a run used is never a guess."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def make_mesh(
    n_devices: Optional[int] = None,
    axis_names: Sequence[str] = (DATA_AXIS,),
    shape: Optional[Sequence[int]] = None,
) -> Mesh:
    """Build a mesh over up to ``n_devices`` visible devices.

    Default: 1-D mesh named ``data`` over all devices.  ``shape`` gives an
    explicit per-axis split (product must divide the device count).
    """
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if shape is None:
        shape = [n] + [1] * (len(axis_names) - 1)
    if math.prod(shape) != n:
        raise ValueError(f"mesh shape {shape} != device count {n}")
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, axis_names=tuple(axis_names))


def data_sharding(mesh: Mesh, ndim: int = 1, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard leading dim over the data axis, replicate the rest."""
    spec = P(axis, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_put(arr, mesh: Mesh, spec) -> "jax.Array":
    """``device_put`` onto a sharding that may span processes.

    Single-process meshes take the plain ``device_put`` fast path.  On a
    multi-process mesh, ``device_put`` rejects shardings with
    non-addressable devices, so each ADDRESSABLE shard is sliced from the
    host array and the global array assembled with
    ``make_array_from_single_device_arrays`` — every process must hold a
    consistent full host copy (fine for the small index vectors and
    factor inits this serves; bulk data uses per-shard construction
    directly, see ``models/als.ALSTrainer.distributed``).
    """
    sh = NamedSharding(mesh, spec)
    if all(
        d.process_index == jax.process_index() for d in mesh.devices.flat
    ):
        return jax.device_put(arr, sh)
    arr = np.asarray(arr)
    parts = [
        jax.device_put(arr[idx], d)
        for d, idx in sh.addressable_devices_indices_map(arr.shape).items()
    ]
    return jax.make_array_from_single_device_arrays(arr.shape, sh, parts)


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of ``m`` >= ``n`` (static-shape padding budgets)."""
    return ((n + m - 1) // m) * m
