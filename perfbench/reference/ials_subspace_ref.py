"""Plain reference of ONE iALS++ block sweep (Rendle, Krichene, Zhang,
Koren, arXiv 2110.14044, Alg. 2) of implicit-feedback ALS in the
Hu-Koren-Volinsky form with the count-weighted regulariser the
configuration states.

For every row u of the side being solved, with Omega_u its entries, r_uj
their values, c_uj = 1 + alpha * r_uj, Y the WHOLE opposite table, x0 the
row as the half finds it, and the rank axis cut into blocks S of `block`
coordinates in ascending order:

    A_u = Y^T Y + sum_{j in Omega_u} (c_uj - 1) y_j y_j^T + reg_u * I
    b_u = sum_{j in Omega_u} c_uj * y_j
    for S in blocks:   x_S <- x_S - (A_u[S, S])^{-1} (A_u x - b_u)_S

The program never forms A_u: it keeps a prediction and an `x Y^T Y` cache
and advances them by rank-`block` updates.  Here the row's WHOLE normal
equations are formed once and the gradient is recomputed from them at
every block, no caches: the same mathematics by another route, in
straightforward `jax.numpy`, float32, every contraction at `precision`
(the configuration states "highest"), a Cholesky solve of each block.  It
imports nothing of the program.

`Y^T Y` is summed over blocks of the table that the caller hands in one
at a time (float32 contractions over GRAM_ROWS rows, the partial Grams
added on the host in float64, as `ials_ref.py` does); a row's entries
arrive as the rows `y_j` already fetched, `entry_rows[starts[u] :
starts[u] + counts[u]]`.  Rows are solved widest first in batches of at
most ROWS_PER_BATCH, padded to a power of two of entries.

`precision="high"` is the control: three bf16 passes, written out by hand
(hi*hi + hi*lo + lo*hi) so that it computes the same thing on the CPU,
where XLA ignores the precision flag (`ials_ref._contract`, shared with
the full-solve reference, as is the sum of a batch's `(c - 1) y y^T` and
`c y` over its entries, `ials_ref._accumulate`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.scipy.linalg
import numpy as np

# the contractions at a stated precision (`high` written out on the bits)
# and a batch's sums over its entries are the full-solve reference's own
from perfbench.reference.ials_ref import _accumulate, _contract

ENTRIES_PER_BATCH = 1 << 16   # B*K of one call: [B, K, R] f32 is 512 MiB at R=2048
ROWS_PER_BATCH = 32           # B of one call: [B, R, R] f32 is 512 MiB at R=2048
MIN_ENTRIES = 256             # narrowest pad width: few shapes to compile
GRAM_ROWS = 4096              # rows, or a row's entries, of one float32 partial Gram
GRAMS_PER_CALL = 8            # partial Grams of one call: 128 MiB at R=2048


@functools.partial(jax.jit, static_argnames=("precision",))
def _partial_grams(rows, *, precision: str):
    """[p * GRAM_ROWS, R] -> [p, R, R]: the Gram of every GRAM_ROWS rows."""
    m, r = rows.shape
    rows = rows.reshape(m // GRAM_ROWS, GRAM_ROWS, r)
    return _contract("pmr,pms->prs", rows, rows, precision)


def gram(blocks, precision: str = "highest") -> np.ndarray:
    """Y^T Y of a table handed in as an iterable of [m, R] blocks: float32
    contractions over GRAM_ROWS rows at a time, the partial Grams added
    in float64 on the host.  (One float32 contraction over half a
    million rows loses digits to its own running sum.)"""
    total = None
    step = GRAM_ROWS * GRAMS_PER_CALL
    for block in blocks:
        m, r = block.shape
        for lo in range(0, m, step):
            rows = block[lo:lo + step]
            short = -rows.shape[0] % GRAM_ROWS
            if short:   # zero rows add nothing
                rows = jnp.pad(rows, ((0, short), (0, 0)))
            part = np.asarray(
                _partial_grams(rows, precision=precision), np.float64
            ).sum(axis=0)
            total = part if total is None else total + part
    return total.astype(np.float32)


@functools.partial(jax.jit,
                   static_argnames=("block", "weighted", "precision"))
def _walk_blocks(yty, a, b, counts, x, lam, *, block: int, weighted: bool,
                 precision: str):
    """The blocks in ascending order, each an exact Newton step on its
    coordinates from the WHOLE normal equations: the gradient A x - b is
    recomputed from scratch before every block."""
    n = counts.astype(jnp.float32)
    reg = lam * jnp.maximum(n, 1.0) if weighted else jnp.full_like(n, lam)
    r = a.shape[-1]
    full = yty[None] + a + reg[:, None, None] * jnp.eye(r, dtype=jnp.float32)
    for s in range(0, r, block):
        e = min(s + block, r)
        g = (_contract("brs,bs->br", full, x, precision) - b)[:, s:e]
        chol = jnp.linalg.cholesky(full[:, s:e, s:e])
        t = jax.scipy.linalg.solve_triangular(chol, g[..., None], lower=True)
        d = jax.scipy.linalg.solve_triangular(
            chol, t, lower=True, trans=1)[..., 0]
        x = x.at[:, s:e].add(-d)
    return x


def sweep_rows(yty, entry_rows, entry_vals, starts: np.ndarray,
               counts: np.ndarray, x0, lam: float, alpha: float, block: int,
               weighted: bool = True,
               precision: str = "highest") -> np.ndarray:
    """Each of the len(counts) rows after ONE block sweep from `x0`, as a
    host array [n, R].

    `yty` is the whole opposite table's Gram ([R, R]); `entry_rows` [E, R]
    and `entry_vals` [E] hold the rows' entries back to back (row u's at
    `starts[u] : starts[u] + counts[u]`); `x0` [n, R] the rows as the half
    finds them.  Rows go widest first, B to a batch padded to K entries
    with B*K <= ENTRIES_PER_BATCH and B <= ROWS_PER_BATCH; a row of more
    than GRAM_ROWS entries is summed over chunks of GRAM_ROWS, each a
    float32 contraction of its own, as the table's Gram is."""
    starts = np.asarray(starts, np.int64)
    counts = np.asarray(counts, np.int64)
    entry_rows = jnp.asarray(entry_rows, jnp.float32)
    entry_vals = jnp.asarray(entry_vals, jnp.float32)
    yty = jnp.asarray(yty, jnp.float32)
    x0 = np.asarray(x0, np.float32)
    out = np.zeros_like(x0)
    by_width = np.argsort(-counts, kind="stable")
    lam_t, alpha_t = jnp.float32(lam), jnp.float32(alpha)
    at = 0
    while at < len(counts):
        widest = max(int(counts[by_width[at]]), MIN_ENTRIES)
        k_row = 1 << (widest - 1).bit_length()
        k = min(k_row, GRAM_ROWS)
        b = min(ENTRIES_PER_BATCH // k, ROWS_PER_BATCH)
        take = by_width[at:at + b]
        st = np.zeros(b, np.int32)
        ct = np.zeros(b, np.int32)
        xb = np.zeros((b, x0.shape[1]), np.float32)
        st[: len(take)] = starts[take]
        ct[: len(take)] = counts[take]
        xb[: len(take)] = x0[take]
        st_d, ct_d = jnp.asarray(st), jnp.asarray(ct)
        a_sum = b_sum = None
        for offset in range(0, k_row, k):
            a, rhs = _accumulate(entry_rows, entry_vals, st_d, ct_d,
                                 jnp.int32(offset), alpha_t, k=k,
                                 precision=precision)
            a_sum = a if a_sum is None else a_sum + a
            b_sum = rhs if b_sum is None else b_sum + rhs
        x = np.asarray(_walk_blocks(
            yty, a_sum, b_sum, ct_d, jnp.asarray(xb), lam_t, block=block,
            weighted=weighted, precision=precision))
        out[take] = x[: len(take)]
        at += len(take)
    return out
