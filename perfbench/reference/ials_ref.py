"""Plain reference of implicit-feedback ALS (Hu, Koren, Volinsky, ICDM
2008) with the count-weighted regulariser the configuration states.

For every row u of the side being solved, with Omega_u its entries, r_uj
their values, c_uj = 1 + alpha * r_uj the confidence, and Y the WHOLE
opposite table:

    A_u = Y^T Y + sum_{j in Omega_u} (c_uj - 1) y_j y_j^T + reg_u * I
    b_u = sum_{j in Omega_u} c_uj * y_j
    x_u = A_u^{-1} b_u,      reg_u = lambda * max(|Omega_u|, 1)  (weighted)
                                     lambda                       (plain)

in straightforward `jax.numpy`, float32, every contraction at `precision`
(the configuration states "highest"), a Cholesky solve.  It imports
nothing of the program.  It is given the opposite rows themselves: `Y^T Y`
is summed over blocks of the table that the caller hands in one at a time
(a block lives wherever the caller keeps it; the [R, R] partial sums are
added on the host in float64), and a row's entries arrive as the rows
`y_j` already fetched, `entry_rows[starts[u] : starts[u] + counts[u]]`.
Rows are solved in blocks padded to a power of two, widest first.

`precision="high"` is the control: three bf16 passes, written out by hand
(hi*hi + hi*lo + lo*hi) so that it computes the same thing on the CPU,
where XLA ignores the precision flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.scipy.linalg
import numpy as np

ENTRIES_PER_BLOCK = 1 << 20   # B*K of one block: [B, K, R] f32 is 512 MiB at R=128
ROWS_PER_BLOCK = 4096         # B of one block: [B, R, R] f32 is 256 MiB at R=128
TABLE_BLOCK_ROWS = 1 << 20    # rows handed to one call of the YtY
GRAM_ROWS = 4096              # rows of one float32 partial Gram


def round_to_bf16(x):
    """float32 values rounded (to nearest, ties to even) to the nearest
    bfloat16, still as float32.  Done on the bits: a float32 -> bfloat16 ->
    float32 round trip is one that XLA may remove as "excess precision"."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split_bf16(x):
    hi = round_to_bf16(x)
    lo = round_to_bf16(x - hi)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _contract(spec: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, a_lo = _split_bf16(a)
    b_hi, b_lo = _split_bf16(b)

    def one(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    return one(a_hi, b_hi) + one(a_hi, b_lo) + one(a_lo, b_hi)


@functools.partial(jax.jit, static_argnames=("precision",))
def _partial_grams(block, *, precision: str):
    """[m, R] -> [m / GRAM_ROWS, R, R]: the Gram of every GRAM_ROWS rows
    (the block's tail padded with zero rows, which add nothing)."""
    m, r = block.shape
    parts = -(-m // GRAM_ROWS)
    block = jnp.pad(block, ((0, parts * GRAM_ROWS - m), (0, 0)))
    block = block.reshape(parts, GRAM_ROWS, r)
    return _contract("pmr,pms->prs", block, block, precision)


def gram(blocks, precision: str = "highest") -> np.ndarray:
    """Y^T Y of a table handed in as an iterable of [m, R] blocks: float32
    contractions over GRAM_ROWS rows at a time, the partial Grams added in
    float64 on the host.  (One float32 contraction over a million rows
    loses digits to its own running sum: each row's squares are added to
    a sum thousands of times their size.)"""
    total = None
    for block in blocks:
        for lo in range(0, block.shape[0], TABLE_BLOCK_ROWS):
            parts = np.asarray(
                _partial_grams(block[lo:lo + TABLE_BLOCK_ROWS],
                               precision=precision), np.float64)
            part = parts.sum(axis=0)
            total = part if total is None else total + part
    return total.astype(np.float32)


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _accumulate(entry_rows, entry_vals, starts, counts, offset, alpha, *,
                k: int, precision: str):
    """sum (c - 1) y y^T and sum c y over entries [offset, offset + k) of
    each row of one block."""
    n = entry_rows.shape[0]
    iota = offset + jnp.arange(k, dtype=jnp.int32)
    pos = jnp.minimum(starts[:, None] + iota[None, :], n - 1)
    valid = iota[None, :] < counts[:, None]
    y = entry_rows[pos] * valid[..., None].astype(entry_rows.dtype)  # [B,K,R]
    extra = jnp.where(valid, alpha * entry_vals[pos], 0.0)           # c - 1
    weighted = y * extra[..., None]
    a = _contract("bkr,bks->brs", weighted, y, precision)
    b = _contract("bk,bkr->br", extra + valid.astype(jnp.float32), y,
                  precision)
    return a, b


@functools.partial(jax.jit, static_argnames=("weighted",))
def _solve(yty, a, b, counts, lam, *, weighted: bool):
    n = counts.astype(jnp.float32)
    reg = lam * jnp.maximum(n, 1.0) if weighted else jnp.full_like(n, lam)
    r = a.shape[-1]
    full = yty[None] + a + reg[:, None, None] * jnp.eye(r, dtype=jnp.float32)
    # A is symmetric positive definite: Cholesky, then two triangular solves
    chol = jnp.linalg.cholesky(full)
    y = jax.scipy.linalg.solve_triangular(chol, b[..., None], lower=True)
    return jax.scipy.linalg.solve_triangular(
        chol, y, lower=True, trans=1)[..., 0]


def solve_rows(yty, entry_rows, entry_vals, starts: np.ndarray,
               counts: np.ndarray, lam: float, alpha: float,
               weighted: bool = True,
               precision: str = "highest") -> np.ndarray:
    """x_u for each of the len(counts) rows, as a host array [n, R].

    `yty` is the whole opposite table's Gram ([R, R]); `entry_rows` [E, R]
    and `entry_vals` [E] hold the rows' entries back to back (row u's at
    `starts[u] : starts[u] + counts[u]`).  Rows go widest first, in blocks
    of B rows padded to K entries with B*K <= ENTRIES_PER_BLOCK and
    B <= ROWS_PER_BLOCK; a row wider than a block is summed over chunks."""
    starts = np.asarray(starts, np.int64)
    counts = np.asarray(counts, np.int64)
    entry_rows = jnp.asarray(entry_rows, jnp.float32)
    entry_vals = jnp.asarray(entry_vals, jnp.float32)
    yty = jnp.asarray(yty, jnp.float32)
    out = np.zeros((len(counts), entry_rows.shape[1]), np.float32)
    by_width = np.argsort(-counts, kind="stable")
    lam_t, alpha_t = jnp.float32(lam), jnp.float32(alpha)
    at = 0
    while at < len(counts):
        widest = max(int(counts[by_width[at]]), 8)
        k_row = 1 << (widest - 1).bit_length()
        k = min(k_row, ENTRIES_PER_BLOCK)
        b = min(ENTRIES_PER_BLOCK // k, ROWS_PER_BLOCK)
        take = by_width[at:at + b]
        if k_row > 8:
            # keep to rows more than half as wide: padding stays under 2x
            wide = int((counts[take] > k_row // 2).sum())
            take = take[: max(wide, 1)]
        st = np.zeros(b, np.int32)
        ct = np.zeros(b, np.int32)
        st[: len(take)] = starts[take]
        ct[: len(take)] = counts[take]
        st_d, ct_d = jnp.asarray(st), jnp.asarray(ct)
        a_sum = b_sum = None
        for offset in range(0, k_row, k):
            a, rhs = _accumulate(entry_rows, entry_vals, st_d, ct_d,
                                 jnp.int32(offset), alpha_t, k=k,
                                 precision=precision)
            a_sum = a if a_sum is None else a_sum + a
            b_sum = rhs if b_sum is None else b_sum + rhs
        x = np.asarray(_solve(yty, a_sum, b_sum, ct_d, lam_t,
                              weighted=weighted))
        out[take] = x[: len(take)]
        at += len(take)
    return out
