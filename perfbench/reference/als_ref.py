"""Plain reference of explicit ALS with weighted lambda (ALS-WR).

For every row r of the side being solved, with Omega_r its ratings:

    A_r = sum_{j in Omega_r} y_j y_j^T + lambda * max(|Omega_r|, 1) * I
    b_r = sum_{j in Omega_r} rating_rj * y_j
    x_r = A_r^{-1} b_r

in straightforward `jax.numpy`, float32, Gram at `precision` (the
configuration states "highest").  It imports nothing of the program and is
given only the inputs: the ratings and the seed's initial tables.  Rows are
solved in blocks padded to a power of two, so that a block fits the device.

`precision="high"` is the control: the Gram and the right-hand side in
three bf16 passes, written out by hand (hi*hi + hi*lo + lo*hi) so that it
computes the same thing on the CPU, where XLA ignores the precision flag.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import jax.scipy.linalg
import numpy as np

ENTRIES_PER_BLOCK = 2 << 20   # B*K of one block: [B, K, R] f32 is 512 MiB at R=64


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


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _accumulate(opp, cols, vals, starts, counts, offset, *, k: int,
                precision: str):
    """Gram and right-hand side of entries [offset, offset+k) of each row
    of one block; `starts`/`counts` index the row-sorted COO."""
    nnz = cols.shape[0]
    iota = offset + jnp.arange(k, dtype=jnp.int32)
    pos = jnp.minimum(starts[:, None] + iota[None, :], nnz - 1)
    valid = iota[None, :] < counts[:, None]
    idx = jnp.where(valid, cols[pos], 0)
    val = jnp.where(valid, vals[pos], 0.0).astype(jnp.float32)
    y = opp[idx] * valid[..., None].astype(opp.dtype)        # [B, K, R]
    gram = _contract("bkr,bks->brs", y, y, precision)
    rhs = _contract("bk,bkr->br", val, y, precision)
    return gram, rhs


@jax.jit
def _solve(gram, rhs, counts, lam):
    reg = lam * jnp.maximum(counts.astype(jnp.float32), 1.0)
    r = gram.shape[-1]
    a = gram + reg[:, None, None] * jnp.eye(r, dtype=jnp.float32)
    # A is symmetric positive definite: Cholesky, then two triangular solves
    chol = jnp.linalg.cholesky(a)
    y = jax.scipy.linalg.solve_triangular(chol, rhs[..., None], lower=True)
    return jax.scipy.linalg.solve_triangular(
        chol, y, lower=True, trans=1)[..., 0]


def solve_rows(opp, cols_sorted, vals_sorted, starts: np.ndarray,
               counts: np.ndarray, rows: np.ndarray, lam: float,
               precision: str = "highest", timing: dict = None) -> np.ndarray:
    """x_r for each r in `rows`, as a host array [len(rows), R].

    `cols_sorted`/`vals_sorted` are the COO's opposite ids and ratings in
    row order (device arrays); `starts`/`counts` are per row (host).  Rows
    go widest first, in blocks of B rows padded to K entries with B*K =
    ENTRIES_PER_BLOCK; a row wider than that is summed over chunks."""
    rows = np.asarray(rows, np.int64)
    out = np.zeros((len(rows), opp.shape[1]), np.float32)
    by_width = np.argsort(-counts[rows], kind="stable")
    lam_t = jnp.float32(lam)
    at = 0
    while at < len(rows):
        widest = max(int(counts[rows[by_width[at]]]), 8)
        k_row = 1 << (widest - 1).bit_length()
        k = min(k_row, ENTRIES_PER_BLOCK)
        b = ENTRIES_PER_BLOCK // k
        take = by_width[at:at + b]
        if k_row > 8:
            # keep to rows more than half as wide: padding stays under 2x
            wide = int((counts[rows[take]] > k_row // 2).sum())
            take = take[: max(wide, 1)]
        st = np.zeros(b, np.int32)
        ct = np.zeros(b, np.int32)
        st[: len(take)] = starts[rows[take]]
        ct[: len(take)] = counts[rows[take]]
        st_d, ct_d = jnp.asarray(st), jnp.asarray(ct)
        t0 = time.perf_counter()
        gram = rhs = None
        for offset in range(0, k_row, k):
            g, h = _accumulate(opp, cols_sorted, vals_sorted, st_d, ct_d,
                               jnp.int32(offset), k=k, precision=precision)
            gram = g if gram is None else gram + g
            rhs = h if rhs is None else rhs + h
        if timing is not None:
            gram.block_until_ready()
            t1 = time.perf_counter()
            timing["gram_s"] = timing.get("gram_s", 0.0) + t1 - t0
        x = np.asarray(_solve(gram, rhs, ct_d, lam_t))
        if timing is not None:
            timing["solve_s"] = (timing.get("solve_s", 0.0)
                                 + time.perf_counter() - t1)
            timing["blocks"] = timing.get("blocks", 0) + 1
        out[take] = x[: len(take)]
        at += len(take)
    return out


@jax.jit
def _sq_err(u_rows, v_rows, r):
    pred = jnp.einsum("nr,nr->n", u_rows, v_rows,
                      precision=jax.lax.Precision.HIGHEST)
    return jnp.sum((pred - r) ** 2)


def rmse(user_table, item_table, u, i, r) -> float:
    """Root mean squared error over the given (u, i, r) triples."""
    u, i = jnp.asarray(u), jnp.asarray(i)
    total = _sq_err(jnp.asarray(user_table)[u], jnp.asarray(item_table)[i],
                    jnp.asarray(r, jnp.float32))
    return float(np.sqrt(float(total) / len(r)))
