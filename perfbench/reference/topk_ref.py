"""Plain reference of exact top-k retrieval: every item's score is the
float32 inner product of the user's row and the item's row, and the answer
is the k items with the highest scores, best first.

Straightforward `jax.numpy`, the product at `Precision.HIGHEST`; it imports
nothing of the program and is given only the seed's tables.

`precision="fp8"` is the control.  The configuration states that the served
product rounds its float32 operands to bfloat16 (the TPU's default matmul
precision), so the nearest precision below is fp8: both operands rounded to
float8_e4m3fn, then multiplied exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _round_bits(x, drop: int):
    """float32 rounded to nearest (ties to even) with the low `drop` bits of
    the mantissa cleared.  On the bits, because XLA may remove a convert
    to a narrower type and back as "excess precision" (on the v5e the fp8
    round trip read exactly as bfloat16)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    half = jnp.uint32((1 << (drop - 1)) - 1)
    bits = (bits + half + ((bits >> drop) & jnp.uint32(1))) \
        & jnp.uint32((0xFFFFFFFF >> drop) << drop)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _round(x, precision: str):
    if precision == "highest":
        return x
    if precision == "bf16":
        return _round_bits(x, 16)                 # 7 bits of mantissa
    if precision == "fp8":
        # float8_e4m3fn: 3 bits of mantissa down to 2**-6, below that steps
        # of 2**-9, and nothing over 448
        normal = _round_bits(x, 20)
        small = jnp.round(x * 512.0) / 512.0
        out = jnp.where(jnp.abs(x) < 2.0 ** -6, small, normal)
        return jnp.clip(out, -448.0, 448.0)
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("precision",))
def scores(user_rows, item_table, *, precision: str = "highest"):
    """[Q, R] x [M, R] -> [Q, M] float32 scores."""
    return jnp.einsum(
        "qr,mr->qm", _round(user_rows, precision),
        _round(item_table, precision),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("k",))
def _top(s, k: int):
    return jax.lax.top_k(s, k)


def compare(user_rows, item_table, served_items: np.ndarray,
            served_scores: np.ndarray, block: int = 16) -> dict:
    """Hold served answers against the reference.

    `served_items`/`served_scores` are [Q, k], best first.  Per query, with
    s the reference's scores, t_1 >= ... >= t_k its k best and sigma the
    spread of s:

      rank_gap   max_j (t_j - s[served_j]) / sigma: how far the j-th served
                 item's true score lies below the true j-th best (0 when
                 the served list is the exact one);
      score_err  max_j |served_score_j - s[served_j]| / sigma.

    Returns the widest of each over the queries, and per-query values."""
    q, k = served_items.shape
    rank_gap = np.zeros(q)
    score_err = np.zeros(q)
    for lo in range(0, q, block):
        hi = min(lo + block, q)
        s = scores(jnp.asarray(user_rows[lo:hi]), item_table)
        best, _ = _top(s, k)
        sigma = np.asarray(jnp.std(s, axis=1))
        idx = jnp.asarray(served_items[lo:hi])
        s_served = np.asarray(jnp.take_along_axis(s, idx, axis=1))
        best = np.asarray(best)
        rank_gap[lo:hi] = ((best - s_served) / sigma[:, None]).max(axis=1)
        score_err[lo:hi] = (
            np.abs(served_scores[lo:hi] - s_served) / sigma[:, None]
        ).max(axis=1)
    return {
        "rank_gap": float(rank_gap.max()),
        "score_err": float(score_err.max()),
        "per_query": {"rank_gap": rank_gap, "score_err": score_err},
    }


def answer(user_rows, item_table, k: int, precision: str,
           block: int = 16) -> tuple:
    """(items [Q, k], scores [Q, k]) as the reference would serve them at
    `precision`: what the control puts in the program's place."""
    items, vals = [], []
    for lo in range(0, len(user_rows), block):
        s = scores(jnp.asarray(user_rows[lo:lo + block]), item_table,
                   precision=precision)
        v, ix = _top(s, k)
        items.append(np.asarray(ix))
        vals.append(np.asarray(v))
    return np.concatenate(items), np.concatenate(vals)
