"""Batched SPD solve as a Pallas TPU kernel (augmented Gauss-Jordan).

The ALS hot loop solves hundreds of thousands of small (R<=128) SPD
normal-equation systems per half-iteration (`models/als.py`).  XLA lowers
``lax.linalg.cholesky`` + two ``triangular_solve`` calls on TPU to
loop-heavy code (the 2026-07-30 phase split in docs/ARCHITECTURE.md put
it at ~13 GFLOP/s; not re-measured on the current toolchain).  This
kernel instead keeps a tile of systems resident in
VMEM and runs **augmented Gauss-Jordan elimination** lock-step across the
batch:

* the augmented matrix ``[A | b]`` lives in one ``[TB, R, R+1]`` VMEM
  scratch (the +1 column is free: Mosaic pads the lane dimension to 128
  anyway for R <= 127);
* each of the R pivot steps is a handful of `[TB, R]`/`[TB, R, W]`
  vector ops (one-hot row/column extraction via broadcasted-iota masks,
  one fused rank-1 update) — no substitution phases, no dynamic slicing,
  only ops Mosaic lowers everywhere;
* after R steps the b-column IS the solution.

Gauss-Jordan without pivoting is numerically safe here because ALS always
solves ``A = Gram + reg·I`` with ``reg > 0`` — symmetric positive definite
and diagonally loaded, the textbook no-pivot case.  A previous revision
factorized via lock-step Cholesky + masked substitutions; Jordan
elimination does the same O(R^3) work per system but needs no
back-substitution passes, which both halves the step count and removes
the row-extraction traffic the substitutions paid.

Used by ``ALSConfig(solver="pallas")`` — for the full R×R normal
equations in ``solver_mode="full"`` AND for the B×B subsystems of the
iALS++ subspace sweep (``solver_mode="subspace"``, `models/als.py
_subspace_sweep`): the tile sizing (`_tile_rows`) packs MORE systems
per VMEM tile as R shrinks, so the kernel gets faster per system at
block sizes, not bypassed.  ``interpret=True`` runs the same kernel
through the Pallas interpreter; :func:`pallas_interpret` selects it only
for a process the operator put on the CPU (the test suite, dry runs).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "spd_solve_batched",
    "cholesky_solve_batched",
    "pallas_interpret",
    "solver_smem_budget",
    "solver_vmem_budget",
    "solver_tile_footprint",
]

_EPS = 1e-20


def pallas_interpret() -> bool:
    """Whether the Pallas TPU kernels run through the interpreter.

    Only when the process is on the CPU backend, which takes
    ``JAX_PLATFORMS=cpu`` — the operator's word.  Every other backend
    gets the Mosaic compile, and its error if there is one.
    """
    return jax.default_backend() == "cpu"


def _gj_kernel(a_ref, b_ref, x_ref, m_scr):
    """One batch tile: augmented Gauss-Jordan over [A | b] in VMEM."""
    R = a_ref.shape[-1]
    W = R + 1
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)   # [1, W]
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)    # [1, R]
    m_scr[:, :, :R] = a_ref[:]
    m_scr[:, :, R:W] = b_ref[:][:, :, None]

    def gj_step(j, _):
        M = m_scr[:]                                   # [TB, R, W]
        ohr = (rows == j).astype(M.dtype)              # [1, R] pivot row
        ohc = (lanes == j).astype(M.dtype)             # [1, W] pivot col
        pr = jnp.sum(M * ohr[:, :, None], axis=1)      # [TB, W] row j
        d = jnp.sum(pr * ohc, axis=-1)                 # [TB] pivot value
        prn = pr / jnp.where(jnp.abs(d) > _EPS, d, _EPS)[:, None]
        col = jnp.sum(M * ohc[:, None, :], axis=-1)    # [TB, R] col j
        colz = jnp.where(rows == j, 0.0, col)          # zero at pivot row
        # fused: eliminate col j everywhere else + normalize the pivot row
        upd = M - colz[:, :, None] * prn[:, None, :]
        m_scr[:] = jnp.where(ohr[:, :, None] > 0, prn[:, None, :], upd)
        return 0

    jax.lax.fori_loop(0, R, gj_step, 0)
    x_ref[:] = m_scr[:, :, R]


def solver_vmem_budget() -> int:
    """Per-core VMEM budget (bytes) the tile sizing works against.

    There is no public query API for scoped VMEM; Mosaic's default
    scoped limit is 16 MiB per core, and the tiles `_tile_rows` derives
    from it compile on v5e at ranks 10/16/64/128 (CHANGES.md, PR 21).
    ``PIO_TPU_VMEM_BYTES`` overrides for a future generation or a
    deliberately tighter/looser budget.
    """
    env = os.environ.get("PIO_TPU_VMEM_BYTES")
    if env:
        return int(env)
    return 16 << 20


def solver_smem_budget() -> int:
    """Per-core SMEM budget (bytes) for scalar-prefetched operands.

    The fused kernel (`ops/fused_als.py`) prefetches a batch tile's
    ``[TB, Kpad]`` int32 index block to SMEM
    (``PrefetchScalarGridSpec``); SMEM is the scalar core's memory and
    far smaller than VMEM, with no public query API either.  256 KiB is
    a deliberately conservative planning default;
    ``PIO_TPU_SMEM_BYTES`` overrides it the same way
    ``PIO_TPU_VMEM_BYTES`` overrides the VMEM budget.
    """
    env = os.environ.get("PIO_TPU_SMEM_BYTES")
    if env:
        return int(env)
    return 256 << 10


def solver_tile_footprint(tb: int, r: int) -> int:
    """Worst-case VMEM bytes the kernel occupies for a ``tb``-row tile.

    Counts the PADDED footprints (Mosaic tiles f32 values to (8, 128) on
    the trailing two dims) of everything resident at once: the
    ``[TB, R, R+1]`` augmented scratch, the ``[TB, R, R]`` input A block
    and ``[TB, R]`` b block (double-buffered by the pipeline), and the
    ``[TB, R]`` output block (also double-buffered).
    """
    r8 = max(-(-r // 8) * 8, 8)
    r128 = max(-(-r // 128) * 128, 128)
    w128 = max(-(-(r + 1) // 128) * 128, 128)
    scratch = tb * r8 * w128 * 4
    a_blk = tb * r8 * r128 * 4
    vec_blk = max(-(-tb // 8) * 8, 8) * r128 * 4  # [TB, R] b/x blocks
    return scratch + 2 * a_blk + 4 * vec_blk


def _tile_rows(r: int) -> int:
    """Largest power-of-two batch tile whose total footprint fits in half
    the VMEM budget (headroom for Mosaic's own temporaries): a 64-row
    tile at R=64, 16 rows at R=128."""
    budget = solver_vmem_budget() // 2
    tb = 512
    while tb > 8 and solver_tile_footprint(tb, r) > budget:
        tb //= 2
    return tb


@functools.partial(jax.jit, static_argnames=("interpret",))
def _solve_padded(A, b, *, interpret: bool):
    B, R, _ = A.shape
    tb = _tile_rows(R)
    grid = (pl.cdiv(B, tb),)
    return pl.pallas_call(
        _gj_kernel,
        out_shape=jax.ShapeDtypeStruct((B, R), A.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, R, R), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tb, R), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tb, R), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((tb, R, R + 1), jnp.float32),
        ],
        interpret=interpret,
    )(A, b)


def spd_solve_batched(A, b, interpret: bool | None = None):
    """Solve ``A[i] x[i] = b[i]`` for a batch of SPD systems.

    A: [B, R, R] float32, b: [B, R] float32 -> x: [B, R] float32.
    ``interpret=None`` follows :func:`pallas_interpret`.
    """
    if interpret is None:
        interpret = pallas_interpret()
    B = A.shape[0]
    tb = _tile_rows(A.shape[-1])
    pad = (-B) % tb
    if pad:
        # padded systems are identity/zero -> solution 0, sliced away
        eye = jnp.broadcast_to(
            jnp.eye(A.shape[-1], dtype=A.dtype), (pad, *A.shape[1:])
        )
        A = jnp.concatenate([A, eye], axis=0)
        b = jnp.concatenate(
            [b, jnp.zeros((pad, b.shape[-1]), b.dtype)], axis=0
        )
    x = _solve_padded(A, b, interpret=bool(interpret))
    return x[:B]


# historical name (the first revision of this kernel factorized via
# Cholesky); ALSConfig docs and tests may refer to either
cholesky_solve_batched = spd_solve_batched
