"""Gather forms the ALS hot loop can use, as timeable probes.

What the v5e compiler accepts was settled in PR 21 (CHANGES.md): of the
in-kernel forms once arbitrated here only the row-DMA loop compiles, so
there is nothing left to rank at run time.  What remains is what ROADMAP
S3 still has to TIME against each other on the chip, at identical
shapes:

  * ``dma_row_gather`` — the fused kernel's in-kernel gather
    (`ops/fused_als.py`): rolling-window ``pltpu.make_async_copy`` row
    copies, indices scalar-prefetched to SMEM, table in HBM.  Float32
    only, rows lane-padded to 128 (Mosaic slices an HBM ref in whole
    128-lane, 32-bit rows).
  * ``xla_take`` — the XLA ``jnp.take`` baseline (what the unfused path
    pays); the bar the Pallas form must beat.
  * ``probe_xla_grouped_take`` — the tile-slab gather behind
    ``ALSConfig(gather_mode="grouped")``, with its lane-slab control.

On the CPU the Pallas form runs through the interpreter
(`ops.solve.pallas_interpret`): that validates shapes and math (the
gate's smoke) and says nothing about speed.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .solve import pallas_interpret

__all__ = [
    "dma_row_gather",
    "probe_dma",
    "probe_xla_grouped_take",
    "probe_xla_take",
    "smoke",
    "xla_take",
]

_DMA_WINDOW = 16


def _bench(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


# ------------------------------------------------------ row DMA --

def _dma_kernel(idx_ref, table_ref, out_ref, sem):
    # idx_ref is scalar-prefetched (SMEM); issue one row DMA per output
    # row with a rolling window of _DMA_WINDOW outstanding copies.
    nout = out_ref.shape[0]
    window = _DMA_WINDOW

    def issue(k):
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(idx_ref[k], 1)],
            out_ref.at[pl.ds(k, 1)],
            sem.at[k % window],
        )

    def body(k, _):
        @pl.when(k >= window)
        def _wait():
            issue(k - window).wait()  # same (src, dst, sem) triple

        issue(k).start()
        return 0

    jax.lax.fori_loop(0, nout, body, 0)

    def drain(k, _):
        issue(nout - window + k).wait()
        return 0

    jax.lax.fori_loop(0, window, drain, 0)


@functools.partial(jax.jit, static_argnames=("nout",))
def dma_row_gather(table, idx, *, nout):
    """Rolling-window async row-copy gather: ``table [M, R]`` (float32)
    stays in HBM, ``idx [nout]`` is scalar-prefetched to SMEM, one
    ``make_async_copy`` per output row."""
    _, r = table.shape
    # whole-lane rows, like the fused kernel's table
    r128 = -(-r // 128) * 128
    table = jnp.pad(table, ((0, 0), (0, r128 - r)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_DMA_WINDOW,))],
    )
    return pl.pallas_call(
        _dma_kernel,
        out_shape=jax.ShapeDtypeStruct((nout, r128), table.dtype),
        grid_spec=grid_spec,
        interpret=pallas_interpret(),
    )(idx, table)[:, :r]


def probe_dma(m, nout, r) -> dict:
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(m, r)).astype(np.float32))
    rows = rng.integers(0, m, size=(nout,)).astype(np.int32)
    idx = jnp.asarray(rows)
    dt, out = _bench(
        functools.partial(dma_row_gather, nout=nout), table, idx
    )
    good = bool(
        np.allclose(np.asarray(out), np.asarray(table)[rows], atol=1e-2)
    )
    return dict(metric="dma_row_gather", m=m, nout=nout, r=r,
                ok=good, seconds=dt, ns_per_row=dt / nout * 1e9)


# ------------------------------------------------- grouped take --

def probe_xla_grouped_take(m, nout, r, dtype, group=None) -> list[dict]:
    """Grouped slab gather, BOTH layouts, vs the plain row take.

    Hypothesis for the measured ~17 GB/s of the plain row gather: each
    rank-64 row is 256 B but the memory system moves (8,128)/(16,128)
    tiles, a 16-32x waste.  Returns TWO records per call:

    - ``xla_grouped3d_take`` — the PRODUCTION form
      (`ALSConfig(gather_mode="grouped")`): gather [G, R] slices of the
      3D view [M/G, G, R], whose trailing dims are the tiled ones, so
      one gathered slice is whole tiles.
    - ``xla_grouped_take`` — the 2D lane-slab [M/G, G*R] CONTROL arm:
      its slab rows are 1 sublane tall, so the tile-height waste
      remains; it should NOT beat the baseline.

    ``group`` defaults to the dtype's tile sublane count (8 f32 /
    16 bf16), matching production's ``grp`` exactly."""
    if group is None:
        group = 8 * (4 // jnp.dtype(dtype).itemsize)
    mg = -(-m // group) * group
    rng = np.random.default_rng(0)
    table = jnp.asarray(
        rng.normal(size=(mg, r)).astype(np.float32)
    ).astype(dtype)
    idx = jnp.asarray(rng.integers(0, m, size=(nout,)).astype(np.int32))

    def grouped_lanes(t, i):
        # 2D lane-slab form [M/G, G*R]: the G rows lie along LANES, so
        # one slab row is 1 sublane tall — kept as the control arm that
        # should NOT beat the tile-height waste
        g = jnp.take(t.reshape(mg // group, group * r), i // group, axis=0)
        sel = jnp.broadcast_to((i % group)[:, None, None], (nout, 1, r))
        return jnp.take_along_axis(
            g.reshape(nout, group, r), sel, axis=1
        )[:, 0, :]

    def grouped_tiles(t, i):
        # 3D tile-slab form [M/G, G, R] (same bytes): trailing (G, R)
        # dims are the tiled ones, so a gathered [G, R] slice is whole
        # tiles — the production ALSConfig(gather_mode="grouped") form
        g = jnp.take(t.reshape(mg // group, group, r), i // group, axis=0)
        sel = jnp.broadcast_to((i % group)[:, None, None], (nout, 1, r))
        return jnp.take_along_axis(g, sel, axis=1)[:, 0, :]

    ref = jax.jit(lambda t, i: jnp.take(t, i, axis=0))
    want = np.asarray(ref(table, idx), np.float32)
    bytes_useful = nout * r * table.dtype.itemsize
    out = []
    for name, fn in (("xla_grouped_take", grouped_lanes),
                     ("xla_grouped3d_take", grouped_tiles)):
        dt, got = _bench(jax.jit(fn), table, idx)
        good = bool(
            np.allclose(np.asarray(got, np.float32), want, atol=1e-2)
        )
        out.append(dict(metric=name, m=m, nout=nout, r=r, group=group,
                        dtype=table.dtype.name, ok=good, seconds=dt,
                        ns_per_row=dt / nout * 1e9,
                        useful_gbps=bytes_useful / dt / 1e9))
    return out


# ----------------------------------------------------- XLA take --

def xla_take(table, idx):
    """The XLA row-take baseline on identical shapes."""
    return jnp.take(table, idx, axis=0)


def probe_xla_take(m, nout, r, dtype) -> dict:
    rng = np.random.default_rng(0)
    table = jnp.asarray(
        rng.normal(size=(m, r)).astype(np.float32)
    ).astype(dtype)
    idx = jnp.asarray(rng.integers(0, m, size=(nout,)).astype(np.int32))
    take = jax.jit(xla_take)
    dt, _ = _bench(take, table, idx)
    bytes_moved = nout * r * table.dtype.itemsize
    return dict(metric="xla_take", m=m, nout=nout, r=r,
                dtype=table.dtype.name, seconds=dt,
                ns_per_row=dt / nout * 1e9,
                effective_gbps=bytes_moved / dt / 1e9)


def smoke(r: int = 16) -> list[dict]:
    """Small-shape run of every probe form: CPU interpret-mode shape and
    logic validation (the gate.sh step), no lowering claims.  Returns
    the records; a form whose math is wrong carries ok=False."""
    recs = [
        probe_xla_take(512, 256, r, jnp.float32),
        probe_dma(512, 256, r),
    ]
    recs.extend(probe_xla_grouped_take(512, 256, r, jnp.float32))
    return recs
