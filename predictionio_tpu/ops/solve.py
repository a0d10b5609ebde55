"""Batched SPD solve as a Pallas TPU kernel (Cholesky, batch on the lanes).

The ALS hot loop solves hundreds of thousands of small (R<=128) SPD
normal-equation systems per half-iteration (`models/als.py`).  XLA lowers
``lax.linalg.cholesky`` + two ``triangular_solve`` calls on TPU to
column loops over whole ``[B, R, R]`` arrays in HBM: 264 ms for
``[32768, 64, 64]`` on a v5e, 12-13 GFLOP/s.  This kernel keeps a tile of
systems resident in VMEM, factors ``A = U^T U``, substitutes forward and
backward in the same pass and writes only ``x [B, R]``: 8 ms for the same
batch alone, 5.3 ms a call inside a sweep (PERF.md, PR 32).

* **The batch is the lane axis.**  The tile lives as ``S[i, j, b]`` in a
  ``[R, R+8, TB]`` scratch, TB a multiple of 128: element ``(i, j)`` of
  128 systems is one lane vector, row ``i`` of 128 systems is ``R/8``
  vector registers with ``j`` on the sublanes.  Every step of the
  factorisation is then an elementwise multiply / subtract / ``rsqrt``
  over lane vectors: no masks over the matrix, no one-hot extraction, no
  cross-lane reduction, and what a lane computes never reaches another
  lane (a ragged last tile's padding lanes hold garbage that is never
  written back).
* **``A`` arrives as ``[B, R, R]``** (what the Gram einsum emits; no
  second copy in HBM) in blocks of 8 rows of every system of the tile;
  each row ``[TB, R]`` is transposed to ``[R, TB]`` in VMEM as it
  arrives, and the 8 rows are factored while the next block is in
  flight.
* **Row-wise (left-looking) Cholesky**: row ``k`` of ``U`` is
  ``(A[k, :] - sum_{m<k} U[m, k] * U[m, :]) / sqrt(pivot)``, zeroed left
  of the diagonal.  ``b`` rides along as column ``R`` of the same slab,
  so the forward substitution ``y = U^-T b`` is the same dot products
  and costs no pass of its own.  The products of 8 earlier rows are summed as a tree
  before they leave the accumulator: half the rounding error of a
  running subtraction (float64 comparison in `tests/test_solve.py`),
  which is what keeps the tables inside the benchmark's limits against
  XLA's own Cholesky.
* **Width classes** (`_slab_classes`, from the width alone): at R = 128
  the block-rows fall in four classes, and a row works only the columns
  from its class's first on, half the products of the full ``[R+8,
  TB]`` slab, with ``x`` the same to the bit: ``[4096, 128, 128]`` 4.00
  -> 2.74 ms a call on a v5e, for 0.27 s more lowering a shape (PERF.md,
  PR 43).  Under 128 one class, the full width: there the kernel is a
  few % of a sweep and every one of dozens of bucket shapes lowers it
  in every process.
* **Back substitution** reads the rows of ``U`` in reverse; the dot of
  a row with the solved tail is a sum over sublane blocks and one
  sublane reduction.

No pivoting: ALS always solves ``A = Gram + reg*I`` with ``reg > 0``.

Used by ``ALSConfig.solver`` ``"auto"`` on a TPU backend and
``"pallas"`` everywhere — for the full R×R normal equations in
``solver_mode="full"`` AND for the B×B subsystems of the iALS++ subspace
sweep (``solver_mode="subspace"``, `models/als.py _subspace_sweep`): the
tile sizing (`_tile_rows`) packs MORE systems per VMEM tile as R
shrinks.  ``interpret=True`` runs the same kernel through the Pallas
interpreter; :func:`pallas_interpret` selects it only for a process the
operator put on the CPU (the test suite, dry runs).
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
    "slab_work_share",
    "pallas_interpret",
    "solver_vmem_budget",
    "solver_tile_footprint",
]

_LANES = 128
_SUB = 8   # float32 sublanes of a vector register; rows of A a grid step


def pallas_interpret() -> bool:
    """Whether the Pallas TPU kernels run through the interpreter.

    Only when the process is on the CPU backend, which takes
    ``JAX_PLATFORMS=cpu`` — the operator's word.  Every other backend
    gets the Mosaic compile, and its error if there is one.
    """
    return jax.default_backend() == "cpu"


def _tree_sum(terms):
    """Pairwise sum of equal-shaped arrays (a balanced tree)."""
    while len(terms) > 1:
        terms = [
            terms[i] + terms[i + 1] if i + 1 < len(terms) else terms[i]
            for i in range(0, len(terms), 2)
        ]
    return terms[0]


def _slab_classes(r: int) -> tuple[int, ...]:
    """The block-rows at which the kernel's width classes start, for
    systems of padded width ``r``: one class, today's full-width body,
    under 128; four at 128, where the products are most of the kernel
    (PERF.md, PR 43).  A class from block-row ``c`` works the columns
    from ``8c`` on."""
    if r < 128:
        return (0,)
    return tuple(r // _SUB * j // 4 for j in range(4))


def slab_work_share(r: int) -> float:
    """The share of the full-width slab products the kernel does for
    systems of width ``r``: the products of row ``k`` are ``k`` times
    the columns its class works."""
    r = _round_up(r, _SUB)
    starts = _slab_classes(r)
    work = sum(
        k * (r + _SUB - _SUB * max(s for s in starts if s <= k // _SUB))
        for k in range(r)
    )
    return work / (r * (r - 1) // 2 * (r + _SUB))


def _cholesky_kernel(a_ref, b_ref, x_ref, s_ref, d_ref, *, starts):
    """Grid step (tile t, block-row c): rows 8c..8c+7 of the tile's
    systems arrive in ``a_ref [TB, 8, R]``, are laid into ``s_ref[i, j,
    b]`` and factored; the last block-row's step substitutes back and
    fills ``x_ref [R, TB]``.  ``d_ref [R, TB]`` keeps 1/U[k, k].

    A row of block-row ``c`` is worked from column ``lo``, the first
    of its width class (``starts``, `_slab_classes`), to ``b`` in
    column R: the columns left of ``lo`` are zeros of U no later row
    reads, since a later row's ``lo`` is no less and ``U[m, k]`` sits
    at ``k >= lo``.  Every column that is worked sees the operations of
    the full-width body in its order, so ``x`` is the same to the bit.
    Each class is a copy of the row body (about 150 operations) that
    every bucket shape traces and lowers in every process, compile
    cache or not (PERF.md, PR 32): one class, the full width, under
    R = 128.
    """
    tb, _, r = a_ref.shape
    c = pl.program_id(1)
    c0 = pl.multiple_of(c * _SUB, _SUB)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, tb), 0)

    for i in range(_SUB):
        s_ref[c0 + i, :r, :] = a_ref[:, i, :].T
        s_ref[c0 + i, r:, :] = jnp.where(
            sub == 0, b_ref[pl.ds(c0 + i, 1), :], 0.0
        )

    def factor(lo):
        col = lo + jax.lax.broadcasted_iota(jnp.int32, (r + _SUB - lo, tb), 0)

        def row(i, carry):
            k = c0 + i

            def products(m, live=None):
                u_mk = s_ref[m, pl.ds(k, 1), :]               # [1, TB]
                if live is not None:
                    u_mk = jnp.where(live, u_mk, 0.0)
                return u_mk * s_ref[m, lo:, :]                # [R + 8 - lo, TB]

            def earlier_block(g, acc):
                return acc - _tree_sum(
                    [products(g * _SUB + u) for u in range(_SUB)]
                )

            acc = jax.lax.fori_loop(0, c, earlier_block, s_ref[k, lo:, :])
            # this block's rows above k; the rows from k down still hold A
            acc = acc - _tree_sum(
                [products(c0 + u, u < i) for u in range(_SUB - 1)]
            )
            s_ref[k, lo:, :] = acc
            pivot = s_ref[k, pl.ds(k, 1), :]
            dinv = jax.lax.rsqrt(pivot)
            # one Newton step: the hardware's rsqrt is an approximation
            dinv = dinv * (1.5 - 0.5 * pivot * dinv * dinv)
            d_ref[pl.ds(k, 1), :] = dinv
            # the columns left of the diagonal are zeros of U
            s_ref[k, lo:, :] = jnp.where(col >= k, acc * dinv, 0.0)
            return carry

        jax.lax.fori_loop(0, _SUB, row, 0)

    if len(starts) == 1:
        factor(0)
    else:
        for first, end in zip(starts, starts[1:] + (r // _SUB,)):
            pl.when((c >= first) & (c < end))(
                functools.partial(factor, _SUB * first))

    @pl.when(c == r // _SUB - 1)
    def _():
        x_ref[...] = jnp.zeros_like(x_ref)

        def row_up(j, carry):
            k = r - 1 - j
            # x is still zero at k and above it, so the columns left of
            # k (U's zeros, or A's entries left of a class's first
            # column) add nothing
            dot = jnp.sum(s_ref[k, :r, :] * x_ref[...], axis=0, keepdims=True)
            y_k = s_ref[k, r:r + 1, :]
            x_ref[pl.ds(k, 1), :] = (y_k - dot) * d_ref[pl.ds(k, 1), :]
            return carry

        jax.lax.fori_loop(0, r, row_up, 0)


def solver_vmem_budget() -> int:
    """Per-core VMEM budget (bytes) the tile sizing works against.

    There is no public query API for scoped VMEM; Mosaic's default
    scoped limit is 16 MiB per core, and the tiles `_tile_rows` derives
    from it compile on v5e at ranks 10/16/64/128 (PERF.md, PR 32).
    ``PIO_TPU_VMEM_BYTES`` overrides for a future generation or a
    deliberately tighter/looser budget.
    """
    env = os.environ.get("PIO_TPU_VMEM_BYTES")
    if env:
        return int(env)
    return 16 << 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def solver_tile_footprint(tb: int, r: int) -> int:
    """VMEM bytes the kernel occupies for a tile of ``tb`` systems.

    Counts the PADDED footprints (Mosaic tiles f32 values to (8, 128) on
    the trailing two dims) of everything resident at once: the
    ``[R, R+8, TB]`` scratch of the tile, the ``[R, TB]`` scratch of the
    pivots, the ``[TB, 8, R]`` block of A (its lanes padded to 128;
    double-buffered by the pipeline) and the ``[R, TB]`` blocks of b and
    x (also double-buffered).
    """
    r = _round_up(r, _SUB)
    tile = r * (r + _SUB) * tb * 4
    vec = r * tb * 4
    a_blk = tb * _SUB * _round_up(r, _LANES) * 4
    return tile + vec + 2 * a_blk + 4 * vec


def _tile_rows(r: int) -> int:
    """Systems a tile: the largest of 512, 256 and 128 lanes whose
    footprint fits three quarters of the VMEM budget (the rest is
    Mosaic's own temporaries): 256 at R=64, 128 at R=128.  Never under
    one register's 128 lanes."""
    budget = solver_vmem_budget() * 3 // 4
    tb = 4 * _LANES
    while tb > _LANES and solver_tile_footprint(tb, r) > budget:
        tb //= 2
    return tb


@functools.partial(jax.jit, static_argnames=("tb", "starts", "interpret"))
def _solve(A, b, *, tb: int, starts: tuple[int, ...], interpret: bool):
    B, r0, _ = A.shape
    r = _round_up(r0, _SUB)
    if r != r0:
        # pad to whole sublane blocks with identity rows: their x is 0
        A = jnp.pad(A, ((0, 0), (0, r - r0), (0, r - r0)))
        A = A + jnp.diag(jnp.arange(r) >= r0).astype(A.dtype)
    n_tiles = pl.cdiv(B, tb)
    bt = jnp.pad(b.T, ((0, r - r0), (0, n_tiles * tb - B)))
    xt = pl.pallas_call(
        functools.partial(_cholesky_kernel, starts=starts),
        out_shape=jax.ShapeDtypeStruct((r, n_tiles * tb), A.dtype),
        grid=(n_tiles, r // _SUB),
        in_specs=[
            pl.BlockSpec((tb, _SUB, r), lambda t, c: (t, c, 0)),
            pl.BlockSpec((r, tb), lambda t, c: (0, t)),
        ],
        out_specs=pl.BlockSpec((r, tb), lambda t, c: (0, t)),
        scratch_shapes=[
            pltpu.VMEM((r, r + _SUB, tb), jnp.float32),
            pltpu.VMEM((r, tb), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(A, bt)
    return xt[:r0, :B].T


def spd_solve_batched(A, b, interpret: bool | None = None):
    """Solve ``A[i] x[i] = b[i]`` for a batch of SPD systems.

    A: [B, R, R] float32, b: [B, R] float32 -> x: [B, R] float32.
    ``interpret=None`` follows :func:`pallas_interpret`.
    """
    if interpret is None:
        interpret = pallas_interpret()
    B = A.shape[0]
    tb = _tile_rows(A.shape[-1])
    if B < tb:
        # a block may not be wider than its array, and every batch under
        # one tile then shares one traced and lowered kernel (an ALS half
        # has a dozen such buckets); a ragged LAST tile of a larger batch
        # needs no padding: its lanes are not written back
        A = jnp.pad(A, ((0, tb - B), (0, 0), (0, 0)))
        b = jnp.pad(b, ((0, tb - B), (0, 0)))
    starts = _slab_classes(_round_up(A.shape[-1], _SUB))
    return _solve(A, b, tb=tb, starts=starts,
                  interpret=bool(interpret))[:B]


# the name `models/als.py` and the tests call it by
cholesky_solve_batched = spd_solve_batched
