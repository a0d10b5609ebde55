"""Fused gather+Gram+solve ALS half-iteration as one Pallas TPU kernel.

The unfused ALS hot loop (`models/als._solve_buckets`) materializes the
``[B, K, R]`` gathered factor expansion in HBM and feeds it to the Gram
einsums.  This kernel never materializes it: per batch tile it copies
the needed opposite-table rows HBM -> VMEM itself, accumulates the
normal equations on the MXU, and solves in place, so HBM sees only the
``[TB, KC]`` index/weight blocks and the row reads.

The in-kernel gather is a rolling window of ``pltpu.make_async_copy``
row copies: the indices are scalar-prefetched to SMEM
(``PrefetchScalarGridSpec``), the table stays in HBM (``pl.ANY``), and
each needed row is one async copy with ``_DMA_WINDOW`` outstanding.
Mosaic only slices a 32-bit HBM ref in whole 128-lane rows, so the
table is lane-padded to a multiple of 128 and must be float32 (what the
v5e compiler said about the alternatives is in CHANGES.md, PR 21: a
``take_along_axis`` gather needs its whole source in one vreg, and a
one-row slice of a bf16 ref is not tile-aligned).

Per chunk: the row copies, then ``A += (cw·rows)ᵀ rows`` on the MXU and
``b += Σ bw·rows`` on the VPU, accumulated in fp32 VMEM scratch; on the
last chunk the kernel regularizes and solves in place by augmented
Gauss-Jordan (its tile has the batch on the major axis; ``ops/solve.py``'s
Cholesky wants it on the lanes), writing only ``x[TB, R]``.

``models/als._solve_buckets`` routes a bucket through the kernel when
``fused_tile_plan`` finds a tile for its width; wider buckets keep the
XLA path.  The jit entry is wrapped ``xray.instrument("als.fused")`` so
a new tile plan or precision shows up as a recompile at ``/debug/xray``.

Reference provenance: this fuses what MLlib ALS does in separate stages
per block (gather factors, accumulate YtY·normal equations, solve —
`org.apache.spark.ml.recommendation.ALS` NormalEquation add/solve), the
way a TPU wants it: one pass, VMEM-resident working set, MXU
contractions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import xray
from .solve import (
    pallas_interpret,
    solver_smem_budget,
    solver_vmem_budget,
)

__all__ = [
    "fused_gather_gram_solve",
    "fused_tile_plan",
]

# the smallest pivot the elimination divides by
_EPS = 1e-20


def _pad8(n: int) -> int:
    return max(-(-n // 8) * 8, 8)


def _pad128(n: int) -> int:
    return max(-(-n // 128) * 128, 128)


# rolling window of outstanding row DMAs
_DMA_WINDOW = 16


def fused_tile_plan(r: int, k: int):
    """Choose ``(TB, KC)`` so the working set fits VMEM and SMEM.

    VMEM holds the PADDED footprints (Mosaic tiles the trailing two dims
    of f32 values to (8, 128)) of the ``[TB, R, R]`` + ``[TB, R, R+1]``
    + ``[TB, R]`` f32 scratches, the ``[TB*KC, R128]`` landing pad of
    the row copies, and the double-buffered ``[TB, KC]`` weight /
    ``[TB, R]`` output blocks.  SMEM (``solver_smem_budget``) must hold
    one batch tile's scalar-prefetched ``[TB, Kpad]`` int32 index block.
    The table itself stays in HBM, so its height does not enter.

    The plan fills only half the VMEM budget: the chunk's weighted
    copy and the MXU operands Mosaic stages are stack temporaries of
    the same order as the landing pad
    (v5e: a plan at 13 of 16 MiB compiled to 18.7 MiB and was refused).

    Returns ``None`` when no tile fits (the caller keeps the XLA path
    for that bucket).
    """
    budget = solver_vmem_budget() // 2
    r8, r128, w128 = _pad8(r), _pad128(r), _pad128(r + 1)
    for tb in (64, 32, 16, 8):
        for kc in (512, 256, 128):
            kc_eff = min(kc, _pad128(k))
            a_scr = tb * r8 * r128 * 4
            m_scr = tb * r8 * w128 * 4
            b_scr = _pad8(tb) * r128 * 4
            rows = tb * kc_eff * r128 * 4
            io = 2 * 2 * _pad8(tb) * _pad128(kc_eff) * 4  # cw/bw x2
            out = 2 * _pad8(tb) * r128 * 4
            gram0 = r8 * r128 * 4
            fixed = a_scr + m_scr + b_scr + rows + io + out + gram0
            kp = -(-k // kc_eff) * kc_eff
            if fixed <= budget and tb * kp * 4 <= solver_smem_budget():
                return tb, kc_eff
    return None


def _gj_solve_writeback(a_scr, b_scr, m_scr, reg_ref, x_ref):
    """Regularize + augmented Gauss-Jordan in place; write x[TB, R].

    No pivoting (safe: ALS always solves ``Gram + reg·I ≻ 0``), on the
    fp32 accumulators.
    """
    tb, r, _ = a_scr.shape
    w = r + 1
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    rows_i = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    eye = (
        jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)
        == jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
    ).astype(jnp.float32)
    m_scr[:, :, :r] = a_scr[:] + reg_ref[:][:, :, None] * eye[None]
    m_scr[:, :, r:w] = b_scr[:][:, :, None]

    def gj_step(p, _):
        M = m_scr[:]
        ohr = (rows_i == p).astype(M.dtype)
        ohc = (lanes == p).astype(M.dtype)
        pr = jnp.sum(M * ohr[:, :, None], axis=1)
        d = jnp.sum(pr * ohc, axis=-1)
        prn = pr / jnp.where(jnp.abs(d) > _EPS, d, _EPS)[:, None]
        col = jnp.sum(M * ohc[:, None, :], axis=-1)
        colz = jnp.where(rows_i == p, 0.0, col)
        upd = M - colz[:, :, None] * prn[:, None, :]
        m_scr[:] = jnp.where(ohr[:, :, None] > 0, prn[:, None, :], upd)
        return 0

    jax.lax.fori_loop(0, r, gj_step, 0)
    x_ref[:] = m_scr[:, :, r]


def _accumulate(rows, cw, bw, a_scr, b_scr, precision):
    """Normal-equation accumulation over one ``[TB, KC, R]`` chunk.

    The Gram update is a batched MXU contraction over the chunk dim.
    The rhs update is a VPU multiply + sublane reduction: as a
    ``dot_general`` it has no lhs non-contracting dim, which Mosaic's
    dot-dimension attribute cannot express (v5e, jax 0.9.0: "failed to
    parse TPU_DotDimensionNumbersAttr parameter
    'lhs_non_contracting_dims'").
    """
    rw = rows * cw[:, :, None]
    a_scr[:] += jax.lax.dot_general(
        rw, rows, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=precision,
    )
    b_scr[:] += jnp.sum(rows * bw[:, :, None], axis=1)


def _fused_kernel(
    idx_sref,    # [Bp, Kp] int32, scalar-prefetched to SMEM
    gram0_ref,   # [R, R] f32 (YtY for implicit mode; zeros otherwise)
    table_ref,   # [M, R128] FULL lane-padded table in HBM; rows by DMA
    cw_ref,      # [TB, KC] f32 Gram weights (0 at masked entries)
    bw_ref,      # [TB, KC] f32 rhs weights (0 at masked entries)
    reg_ref,     # [TB, 1] f32 ridge diagonal
    x_ref,       # [TB, R] f32 out
    rows_scr,    # [TB*KC, R128] f32 landing pad for the row DMAs
    a_scr,       # [TB, R, R] f32 normal-equation accumulator
    b_scr,       # [TB, R] f32 rhs accumulator
    m_scr,       # [TB, R, R+1] f32 augmented Gauss-Jordan scratch
    sem,         # DMA semaphores, rolling window
    *,
    precision,   # lax.Precision for the MXU contraction — the same
                 # knob the unfused Gram einsums honor
):
    i, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)
    tb, kc = cw_ref.shape
    r = gram0_ref.shape[0]
    n = tb * kc
    window = _DMA_WINDOW

    @pl.when(j == 0)
    def _init():
        a_scr[:] = jnp.broadcast_to(
            gram0_ref[:][None], (tb, r, r)
        ).astype(jnp.float32)
        b_scr[:] = jnp.zeros((tb, r), jnp.float32)

    # one row DMA per (tile-row, chunk-col) with a rolling window of
    # outstanding copies; wait re-materializes the same (src, dst, sem)
    # triple
    def issue(k):
        row = idx_sref[i * tb + k // kc, j * kc + k % kc]
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(row, 1)],
            rows_scr.at[pl.ds(k, 1)],
            sem.at[k % window],
        )

    def body(k, _):
        @pl.when(k >= window)
        def _wait():
            issue(k - window).wait()

        issue(k).start()
        return 0

    jax.lax.fori_loop(0, n, body, 0)

    def drain(k, _):
        issue(n - window + k).wait()
        return 0

    jax.lax.fori_loop(0, window, drain, 0)

    rows = rows_scr[:, :r].reshape(tb, kc, r)
    # masked entries carry zero weights (idx contract: they point at
    # row 0, which the DMA really fetches)
    _accumulate(rows, cw_ref[:], bw_ref[:], a_scr, b_scr, precision)

    @pl.when(j == nj - 1)
    def _solve():
        _gj_solve_writeback(a_scr, b_scr, m_scr, reg_ref, x_ref)


@xray.instrument("als.fused")
@functools.partial(
    jax.jit, static_argnames=("tb", "kc", "interpret", "precision")
)
def _fused_padded(
    gram0, table, idx, cw, bw, reg, *, tb, kc, interpret, precision
):
    bp, kp = idx.shape
    r = gram0.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bp // tb, kp // kc),
        in_specs=[
            pl.BlockSpec((r, r), lambda i, j, idx_s: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((tb, kc), lambda i, j, idx_s: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tb, kc), lambda i, j, idx_s: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tb, 1), lambda i, j, idx_s: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tb, r), lambda i, j, idx_s: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((tb * kc, table.shape[1]), jnp.float32),
            pltpu.VMEM((tb, r, r), jnp.float32),
            pltpu.VMEM((tb, r), jnp.float32),
            pltpu.VMEM((tb, r, r + 1), jnp.float32),
            pltpu.SemaphoreType.DMA((_DMA_WINDOW,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fused_kernel, precision=precision),
        out_shape=jax.ShapeDtypeStruct((bp, r), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(idx, gram0, table, cw, bw, reg)


def fused_gather_gram_solve(
    table,          # [M, R] float32 opposite factor table
    idx,            # [B, K] int32 opposite ids, masked entries point at 0
    cw,             # [B, K] f32 Gram weights (0 where masked)
    bw,             # [B, K] f32 rhs weights (0 where masked)
    reg,            # [B]    f32 ridge diagonal
    gram0=None,     # [R, R] f32 base Gram (implicit YtY); zeros if None
    interpret: bool | None = None,
    precision=None,
):
    """One fused normal-equation build + solve for a bucket of rows.

    Returns ``x[B, R]`` solving ``(gram0 + Σₖ cwₖ·vₖvₖᵀ + reg·I) x =
    Σₖ bwₖ·vₖ`` with ``vₖ = table[idx[:, k]]``.  Masking rides the
    weights: a masked entry's ``cw = bw = 0`` makes its gathered row
    irrelevant (``idx`` must point at a valid row, conventionally 0 —
    the kernel really fetches it).

    ``precision`` is the MXU precision for the in-kernel Gram
    contraction — the same ``lax.Precision`` knob the unfused Gram
    einsums honor (``ALSConfig.matmul_precision``).  ``None`` means
    HIGHEST: RMSE parity is the default contract.  ``interpret=None``
    follows :func:`ops.solve.pallas_interpret`.
    """
    if table.dtype != jnp.float32:
        raise ValueError(
            f"fused ALS kernel needs a float32 table, got {table.dtype}: "
            "its row DMAs slice the HBM table one row at a time, which "
            "Mosaic only aligns for 32-bit rows"
        )
    if precision is None:
        precision = jax.lax.Precision.HIGHEST
    else:
        precision = jax.lax.Precision(precision)
    if interpret is None:
        interpret = pallas_interpret()
    b, k = idx.shape
    m, r = table.shape
    plan = fused_tile_plan(r, k)
    if plan is None:
        raise ValueError(
            f"fused ALS kernel: no tile plan for rank {r}, bucket width "
            f"{k} within the VMEM/SMEM budgets "
            f"({solver_vmem_budget()} / {solver_smem_budget()} B)"
        )
    tb, kc = plan
    bp = -(-b // tb) * tb
    kp = -(-k // kc) * kc
    if gram0 is None:
        gram0 = jnp.zeros((r, r), jnp.float32)
    # whole-lane rows: a row DMA slices [1, R128] of the HBM table
    table = jnp.pad(table, ((0, 0), (0, _pad128(r) - r)))
    idx = jnp.pad(idx, ((0, bp - b), (0, kp - k)))
    cw = jnp.pad(cw.astype(jnp.float32), ((0, bp - b), (0, kp - k)))
    bw = jnp.pad(bw.astype(jnp.float32), ((0, bp - b), (0, kp - k)))
    # padded rows solve I·x = 0 -> sliced away
    reg = jnp.pad(
        reg.astype(jnp.float32), (0, bp - b), constant_values=1.0
    )[:, None]
    gram0 = gram0.astype(jnp.float32)
    # the scalar-prefetched [bs, Kp] index slab must fit SMEM: slice
    # the batch dim so each pallas_call's slab stays under budget
    # (equal tb-multiple slices share one compiled executable)
    bs = max(tb, (solver_smem_budget() // max(kp * 4, 1)) // tb * tb)
    outs = [
        _fused_padded(
            gram0, table, idx[lo:lo + bs], cw[lo:lo + bs],
            bw[lo:lo + bs], reg[lo:lo + bs],
            tb=tb, kc=kc, interpret=bool(interpret),
            precision=precision,
        )
        for lo in range(0, bp, bs)
    ]
    x = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return x[:b]
