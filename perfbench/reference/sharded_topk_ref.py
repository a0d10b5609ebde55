"""Plain reference of exact top-k retrieval over an item table whose rows
lie sharded over the chips of a mesh: `topk_ref.py`'s semantics (every
item's score the float32 inner product of the user's row and the item's row
at `Precision.HIGHEST`, the k best first; `precision="fp8"` the control),
computed where the rows lie.  Each chip scores its own rows in blocks of
`BLOCK_ROWS`, keeps its k best and the sums that give the spread of the
scores; the chips' k best are then merged.  No chip holds another's rows
or a `[Q, M]` score matrix.

Straightforward `jax.numpy` under `jax.shard_map`; it imports nothing of
the program and is given only the seed's tables.  Rows past `n_items` (a
table padded to the mesh) count for nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import topk_ref

BLOCK_ROWS = 1 << 20


def _layout(table):
    """(mesh, axis) over which `table`'s rows lie."""
    sharding = table.sharding
    return sharding.mesh, sharding.spec[0]


@functools.lru_cache(maxsize=None)
def _best(mesh, axis: str, k: int, precision: str, n_items: int):
    """Jitted: ``(values [Q, k], ids [Q, k], sum [Q], sum of squares [Q])``
    of the scores of every item, the k best over all chips."""

    def body(users, shard):
        me = jax.lax.axis_index(axis)
        rows = shard.shape[0]
        step = min(BLOCK_ROWS, rows)
        q = users.shape[0]

        def block(carry, lo, size):
            best_v, best_i, total, squares = carry
            part = jax.lax.dynamic_slice_in_dim(shard, lo, size)
            s = topk_ref.scores(users, part, precision=precision)
            ids = me * rows + lo + jnp.arange(size, dtype=jnp.int32)
            valid = ids < n_items
            kept = jnp.where(valid, s, 0.0)
            total = total + kept.sum(axis=1)
            squares = squares + (kept * kept).sum(axis=1)
            v, pos = jax.lax.top_k(jnp.where(valid, s, -jnp.inf),
                                   min(k, size))
            cat_v = jnp.concatenate([best_v, v], axis=1)
            cat_i = jnp.concatenate([best_i, ids[pos]], axis=1)
            best_v, pos = jax.lax.top_k(cat_v, k)
            return (best_v, jnp.take_along_axis(cat_i, pos, axis=1), total,
                    squares)

        carry = (jnp.full((q, k), -jnp.inf, jnp.float32),
                 jnp.zeros((q, k), jnp.int32),
                 jnp.zeros((q,), jnp.float32), jnp.zeros((q,), jnp.float32))
        carry = jax.lax.fori_loop(
            0, rows // step, lambda b, c: block(c, b * step, step), carry)
        if rows % step:
            carry = block(carry, rows - rows % step, rows % step)
        best_v, best_i, total, squares = carry
        all_v = jax.lax.all_gather(best_v, axis, axis=1, tiled=True)
        all_i = jax.lax.all_gather(best_i, axis, axis=1, tiled=True)
        v, pos = jax.lax.top_k(all_v, k)
        return (v, jnp.take_along_axis(all_i, pos, axis=1),
                jax.lax.psum(total, axis), jax.lax.psum(squares, axis))

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(axis, None)),
        out_specs=(P(), P(), P(), P()), check_vma=False))


@functools.lru_cache(maxsize=None)
def _rows_of(mesh, axis: str):
    """Jitted: the rows ``[Q, k, R]`` of the items `ids` ``[Q, k]``, each
    read on the chip that holds it."""

    def body(ids, shard):
        rows = shard.shape[0]
        local = ids - jax.lax.axis_index(axis) * rows
        mine = (local >= 0) & (local < rows)
        got = shard[jnp.where(mine, local, 0)]
        return jax.lax.psum(jnp.where(mine[..., None], got, 0.0), axis)

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(axis, None)), out_specs=P(),
        check_vma=False))


def _served_scores(users, table, ids):
    rows = _rows_of(*_layout(table))(jnp.asarray(ids, jnp.int32), table)
    return jnp.einsum("qr,qkr->qk", users, rows,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def compare(user_rows, item_table, served_items: np.ndarray,
            served_scores: np.ndarray, n_items: int,
            block: int = 16) -> dict:
    """`topk_ref.compare` over a sharded table: per query, with s the
    reference's scores of all `n_items` items, t_1 >= ... >= t_k its k
    best and sigma the spread of s, `rank_gap` = max_j (t_j - s[served_j])
    / sigma and `score_err` = max_j |served_score_j - s[served_j]| / sigma;
    the widest of each over the queries, and per-query values."""
    q, k = served_items.shape
    mesh, axis = _layout(item_table)
    best_of = _best(mesh, axis, k, "highest", n_items)
    rank_gap = np.zeros(q)
    score_err = np.zeros(q)
    for lo in range(0, q, block):
        hi = min(lo + block, q)
        users = jnp.asarray(user_rows[lo:hi], jnp.float32)
        best, _, total, squares = (np.asarray(a) for a in
                                   best_of(users, item_table))
        mean = total / n_items
        sigma = np.sqrt(np.maximum(squares / n_items - mean * mean, 0.0))
        s_served = np.asarray(_served_scores(users, item_table,
                                             served_items[lo:hi]))
        rank_gap[lo:hi] = ((best - s_served) / sigma[:, None]).max(axis=1)
        score_err[lo:hi] = (
            np.abs(served_scores[lo:hi] - s_served) / sigma[:, None]
        ).max(axis=1)
    return {
        "rank_gap": float(rank_gap.max()),
        "score_err": float(score_err.max()),
        "per_query": {"rank_gap": rank_gap, "score_err": score_err},
    }


def answer(user_rows, item_table, k: int, precision: str, n_items: int,
           block: int = 16) -> tuple:
    """(items [Q, k], scores [Q, k]) as the reference would serve them at
    `precision`: what the control puts in the program's place."""
    best_of = _best(*_layout(item_table), k, precision, n_items)
    items, vals = [], []
    for lo in range(0, len(user_rows), block):
        v, ix, _, _ = best_of(
            jnp.asarray(user_rows[lo:lo + block], jnp.float32), item_table)
        items.append(np.asarray(ix))
        vals.append(np.asarray(v))
    return np.concatenate(items), np.concatenate(vals)
