"""Plain reference of similar-item retrieval with exclusions: the query
vector is the mean of the seed items' unit rows, re-normalised; every
item's score is the float32 inner product of that vector and the item's
row; an item that is a seed or is on the query's blackList is left out by
its id; the answer is the `num` best of the rest, best first.

Straightforward `jax.numpy`, the product at `Precision.HIGHEST`, in blocks
of queries; it imports nothing of the program and is given only the seed's
table and the queries as lists of item indices.

`precision="fp8"` is the control, as in `topk_ref`: both operands rounded to
float8_e4m3fn on the bits, one precision below what the configuration
states (float32 operands rounded to bfloat16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .topk_ref import _round


def query_vectors(item_table: np.ndarray, seeds: list) -> np.ndarray:
    """[Q, R]: the mean of each query's seed rows, re-normalised."""
    out = np.zeros((len(seeds), item_table.shape[1]), np.float32)
    for row, ids in enumerate(seeds):
        vec = np.asarray(item_table[np.asarray(ids)], np.float32).mean(axis=0)
        out[row] = vec / np.linalg.norm(vec)
    return out


@functools.partial(jax.jit, static_argnames=("precision",))
def scores(query_rows, item_table, *, precision: str = "highest"):
    """[Q, R] x [M, R] -> [Q, M] float32 scores, nothing left out."""
    return jnp.einsum(
        "qr,mr->qm", _round(query_rows, precision),
        _round(item_table, precision),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("k",))
def _top_allowed(s, rows, ids, k: int):
    """The k best scores and ids of each row of `s` once the listed
    (row, id) pairs are left out (an id past the row is no pair)."""
    return jax.lax.top_k(s.at[rows, ids].set(-jnp.inf, mode="drop"), k)


def excluded_pairs(queries: list, n_items: int, width: int = 32) -> tuple:
    """(rows, ids) of every (query, excluded item) pair of a block, `width`
    pairs a query (`n_items`, an id past the row, fills the rest), so that
    blocks of one size share one compiled program."""
    width = max([width] + [len(q["seeds"]) + len(q["blacklist"])
                           for q in queries])
    ids = np.full((len(queries), width), n_items, np.int32)
    for row, query in enumerate(queries):
        out = list(query["seeds"]) + list(query["blacklist"])
        ids[row, :len(out)] = out
    rows = np.repeat(np.arange(len(queries), dtype=np.int32), width)
    return rows, ids.reshape(-1)


def compare(item_table_host: np.ndarray, item_table, queries: list,
            served_items: list, served_scores: list, num: int,
            block: int = 16) -> dict:
    """Hold served answers against the reference.

    `queries[q]` is `{"seeds": [...], "blacklist": [...]}` (item indices);
    `served_items[q]` / `served_scores[q]` are what it was served, best
    first.  With s the reference's scores of query q over every item,
    t_1 >= t_2 >= ... the scores of its allowed items in order and sigma
    the spread of s:

      rank_gap   max_j (t_j - s[served_j]) / sigma
      score_err  max_j |served_score_j - s[served_j]| / sigma

    as `topk_ref.compare` defines them, and exact counts over the queries:
    `answers_with_repeats` (an item served twice) and
    `answers_with_excluded` (a served item that is a seed or blackListed)."""
    n_q = len(queries)
    qvecs = query_vectors(item_table_host, [q["seeds"] for q in queries])
    rank_gap = np.zeros(n_q)
    score_err = np.zeros(n_q)
    repeats = excluded = 0
    for lo in range(0, n_q, block):
        hi = min(lo + block, n_q)
        s = scores(jnp.asarray(qvecs[lo:hi]), item_table)
        sigma = np.asarray(jnp.std(s, axis=1))
        rows, ids = excluded_pairs(queries[lo:hi], len(item_table_host))
        best = np.asarray(_top_allowed(s, rows, ids, num)[0])
        served = np.zeros((hi - lo, num), np.int64)
        for row in range(hi - lo):
            served[row, :len(served_items[lo + row])] = served_items[lo + row]
        s_served = np.asarray(
            jnp.take_along_axis(s, jnp.asarray(served), axis=1))
        for row in range(hi - lo):
            q = lo + row
            items = list(served_items[q])
            vals = np.asarray(served_scores[q], np.float32)
            out = set(queries[q]["seeds"]) | set(queries[q]["blacklist"])
            repeats += len(set(items)) != len(items)
            excluded += bool(out & set(items))
            if items:
                own = s_served[row, :len(items)]
                rank_gap[q] = ((best[row, :len(items)] - own)
                               / sigma[row]).max()
                score_err[q] = (np.abs(vals - own) / sigma[row]).max()
    return {
        "rank_gap": float(rank_gap.max()),
        "score_err": float(score_err.max()),
        "answers_with_repeats": float(repeats),
        "answers_with_excluded": float(excluded),
        "per_query": {"rank_gap": rank_gap, "score_err": score_err},
    }


def answer(item_table_host: np.ndarray, item_table, queries: list, k: int,
           precision: str, block: int = 16) -> tuple:
    """(items [Q, k], scores [Q, k]) as the reference would serve them at
    `precision`: what the control puts in the program's place."""
    qvecs = query_vectors(item_table_host, [q["seeds"] for q in queries])
    items, vals = [], []
    for lo in range(0, len(queries), block):
        s = scores(jnp.asarray(qvecs[lo:lo + block]), item_table,
                   precision=precision)
        rows, ids = excluded_pairs(queries[lo:lo + block],
                                   len(item_table_host))
        v, ix = _top_allowed(s, rows, ids, k)
        items.append(np.asarray(ix))
        vals.append(np.asarray(v))
    return np.concatenate(items), np.concatenate(vals)
