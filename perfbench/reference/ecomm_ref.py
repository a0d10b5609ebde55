"""Plain reference of the E-Commerce Recommendation engine with
`unseenOnly`: for one query, the user's row times every item's row in
float32; -inf at every item the user has a seen event for, at every item
of the latest `$set` of `constraint/unavailableItems`, and at the query's
blackList; a stable descending sort; the first `num`.  It also gives the
UNFILTERED answer, so that a run can say how many of its sampled answers
the filter changed at all.

Straightforward `jax.numpy` under `jax.default_matmul_precision("highest")`,
the product in blocks of items so that it fits beside the table.  It reads
what is excluded ITSELF from the event store's contents, by one pass over
`find()` of every event (`read_store`): no entity index, no columnar read,
nothing of `ops/topk.py` or `templates/_common.py`.

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

ITEM_BLOCK = 1 << 20


def read_store(store, app_id: int, users, seen_events) -> tuple:
    """(seen, unavailable) as the store's contents have them now: `seen`
    maps each of `users` to the set of target ids of its events named in
    `seen_events`; `unavailable` is the `items` list of the latest `$set`
    on `constraint/unavailableItems` (by event time).  One pass over every
    event of the app."""
    wanted = {user: set() for user in users}
    names = set(seen_events)
    unavailable, latest = [], None
    for e in store.find(app_id=app_id):
        if e.entity_type == "user":
            mine = wanted.get(e.entity_id)
            if mine is not None and e.event in names and e.target_entity_id:
                mine.add(e.target_entity_id)
        elif (e.entity_type == "constraint" and e.event == "$set"
              and e.entity_id == "unavailableItems"
              and (latest is None or e.event_time >= latest)):
            latest = e.event_time
            unavailable = list(e.properties.get("items", []))
    return wanted, unavailable


@functools.partial(jax.jit, static_argnames=("precision",))
def _block_scores(user_rows, item_block, *, precision: str):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum(
            "qr,mr->qm", _round(user_rows, precision),
            _round(item_block, precision),
            preferred_element_type=jnp.float32,
        )


def scores(user_rows, item_table, precision: str = "highest"):
    """[Q, R] x [M, R] -> [Q, M] float32 scores, nothing left out, the
    product computed `ITEM_BLOCK` items at a time."""
    user_rows = jnp.asarray(user_rows, jnp.float32)
    parts = [
        _block_scores(user_rows, item_table[lo:lo + ITEM_BLOCK],
                      precision=precision)
        for lo in range(0, item_table.shape[0], ITEM_BLOCK)
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


@functools.partial(jax.jit, static_argnames=("num",))
def _first(s, rows, ids, num: int):
    """The first `num` of a stable descending sort of each row of `s`,
    once with the listed (row, id) pairs at -inf and once with nothing
    left out: (values, ids, unfiltered ids)."""
    def first(x):
        order = jnp.argsort(-x, axis=1, stable=True)[:, :num]
        return jnp.take_along_axis(x, order, axis=1), order

    vals, order = first(s.at[rows, ids].set(-jnp.inf, mode="drop"))
    return vals, order, first(s)[1]


def _pairs(excluded: list, n_items: int) -> tuple:
    """(rows, ids) of every (query, excluded item) pair of a block, padded
    to one width a block with an id past the row (no pair)."""
    width = max([1] + [len(ex) for ex in excluded])
    width = 1 << (width - 1).bit_length()     # few compiled widths
    ids = np.full((len(excluded), width), n_items, np.int32)
    for row, ex in enumerate(excluded):
        ids[row, :len(ex)] = sorted(ex)
    rows = np.repeat(np.arange(len(excluded), dtype=np.int32), width)
    return rows, ids.reshape(-1)


def answer(user_rows, item_table, excluded: list, num: int,
           precision: str = "highest", block: int = 8) -> tuple:
    """(items [Q, num], scores [Q, num], unfiltered items [Q, num]) as the
    reference serves them at `precision`: at "fp8", what the control puts
    in the program's place."""
    items, vals, blind = [], [], []
    for lo in range(0, len(excluded), block):
        s = scores(user_rows[lo:lo + block], item_table, precision)
        rows, ids = _pairs(excluded[lo:lo + block], item_table.shape[0])
        v, ix, unfiltered = _first(s, rows, ids, num)
        items.append(np.asarray(ix))
        vals.append(np.asarray(v))
        blind.append(np.asarray(unfiltered))
    return (np.concatenate(items), np.concatenate(vals),
            np.concatenate(blind))


def compare(user_rows, item_table, excluded: list, served_items: list,
            served_scores: list, num: int, block: int = 8) -> dict:
    """Hold served answers against the reference.

    `user_rows[q]` is query q's user row, `excluded[q]` the set of item
    indices the reference itself found excluded for it (`read_store`, the
    query's blackList), `served_items[q]` / `served_scores[q]` what it was
    served, best first.  With s the reference's scores of query q over
    every item, t_1 >= t_2 >= ... the scores of its allowed items in order
    and sigma the spread of s:

      rank_gap   max_j (t_j - s[served_j]) / sigma
      score_err  max_j |served_score_j - s[served_j]| / sigma

    as `topk_ref.compare` defines them, and over the queries: the counts
    `answers_with_repeats` (an item served twice), `answers_with_excluded`
    (a served item that the reference found excluded), and
    `answers_filter_blind`, the share of the queries whose unfiltered
    reference answer equals the filtered one (there the filter changed
    nothing, and the answer proves nothing about it)."""
    n_q = len(excluded)
    rank_gap = np.zeros(n_q)
    score_err = np.zeros(n_q)
    repeats = with_excluded = blind = 0
    for lo in range(0, n_q, block):
        hi = min(lo + block, n_q)
        s = scores(user_rows[lo:hi], item_table)
        sigma = np.asarray(jnp.std(s, axis=1))
        rows, ids = _pairs(excluded[lo:hi], item_table.shape[0])
        best, allowed, unfiltered = (
            np.asarray(x) for x in _first(s, rows, ids, num))
        served = np.zeros((hi - lo, num), np.int64)
        for row in range(hi - lo):
            served[row, :len(served_items[lo + row])] = served_items[lo + row]
        s_served = np.asarray(
            jnp.take_along_axis(s, jnp.asarray(served), axis=1))
        for row in range(hi - lo):
            q = lo + row
            items = list(served_items[q])
            vals = np.asarray(served_scores[q], np.float32)
            repeats += len(set(items)) != len(items)
            with_excluded += bool(set(excluded[q]) & set(items))
            blind += bool((allowed[row] == unfiltered[row]).all())
            if items:
                own = s_served[row, :len(items)]
                rank_gap[q] = ((best[row, :len(items)] - own)
                               / sigma[row]).max()
                score_err[q] = (np.abs(vals - own) / sigma[row]).max()
    return {
        "rank_gap": float(rank_gap.max()),
        "score_err": float(score_err.max()),
        "answers_with_repeats": float(repeats),
        "answers_with_excluded": float(with_excluded),
        "answers_filter_blind": blind / max(n_q, 1),
        "per_query": {"rank_gap": rank_gap, "score_err": score_err},
    }
