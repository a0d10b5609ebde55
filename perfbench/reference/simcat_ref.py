"""Plain reference of similar-item retrieval under `categories`: the query
vector is the mean of the seed items' unit rows, re-normalised; every
item's score is the float32 inner product of that vector and the item's
row; an item is allowed if it carries at least one of the query's
categories; a seed or a blackListed item is left out by its id; the answer
is the first `num` of the allowed items, best first.

Straightforward `jax.numpy` under `jax.default_matmul_precision("highest")`,
the table scored `ITEM_BLOCK` items at a time.  It imports nothing of the
program and makes the items' categories ITSELF from the configuration and
the seed (`item_categories`): it never reads the program's category index,
its bit rows or anything of `ops/topk.py` or `templates/_common.py`.  What
it needs of `similar_ref.py` (the query vectors, the excluded pairs) is
copied here.

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


# -- the deployment's categories, from the configuration and the seed -------


def largest_remainder(weights, total: int, floor: int = 0) -> np.ndarray:
    """`total` dealt in whole numbers in proportion to `weights`, at least
    `floor` each: the floors first, the rest by the largest remainder."""
    weights = np.asarray(weights, np.float64)
    rest = total - floor * len(weights)
    quota = weights / weights.sum() * rest
    out = np.floor(quota).astype(np.int64)
    short = rest - int(out.sum())
    order = np.argsort(-(quota - out), kind="stable")
    out[order[:short]] += 1
    return out + floor


def department_items(cfg: dict) -> np.ndarray:
    """Items a department: the source's per-department product counts
    scaled to the catalogue by the largest remainder."""
    counts = [d["products"] for d in cfg["departments"]]
    return largest_remainder(counts, cfg["n_items"])


def department_subcategories(cfg: dict) -> np.ndarray:
    """Sub-category names a department: `subcategories` names dealt in
    proportion to the departments' items, at least
    `subcategories_min` each."""
    return largest_remainder(department_items(cfg), cfg["subcategories"],
                             cfg["subcategories_min"])


def category_names(cfg: dict) -> list:
    """The vocabulary, a category's number its place: the departments,
    then each department's sub-categories."""
    names = [d["name"] for d in cfg["departments"]]
    for dept, n_sub in zip(cfg["departments"], department_subcategories(cfg)):
        names += [f"{dept['name']}/s{j}" for j in range(int(n_sub))]
    return names


def item_categories(cfg: dict, seed: int) -> np.ndarray:
    """`[n_items, 3]` int32: each item's department and its two
    sub-categories, as category numbers (`category_names`).  Items are
    dealt to departments by a permutation of the item indices drawn from
    the seed; an item's two sub-categories are distinct draws from its
    department's, P(j-th) ~ 1 / j (the second drawn again until it
    differs)."""
    rng = np.random.default_rng([seed & 0x7FFFFFFF, seed >> 31, 23])
    n_items = cfg["n_items"]
    items = department_items(cfg)
    subs = department_subcategories(cfg)
    order = rng.permutation(n_items)
    out = np.empty((n_items, 3), np.int32)
    first_sub = len(items) + np.concatenate(([0], np.cumsum(subs)[:-1]))
    lo = 0
    for dept, (n, n_sub) in enumerate(zip(items.tolist(), subs.tolist())):
        cum = np.cumsum(1.0 / np.arange(1, n_sub + 1))

        def draw(count):
            return np.minimum(
                np.searchsorted(cum, rng.random(count) * cum[-1]), n_sub - 1)

        one, two = draw(n), draw(n)
        again = np.flatnonzero(two == one)
        while len(again):
            two[again] = draw(len(again))
            again = again[two[again] == one[again]]
        mine = order[lo:lo + n]
        out[mine, 0] = dept
        out[mine, 1] = first_sub[dept] + one
        out[mine, 2] = first_sub[dept] + two
        lo += n
    return out


# -- the answer -----------------------------------------------------------------


def query_vectors(item_table: np.ndarray, seeds: list) -> np.ndarray:
    """[Q, R]: the mean of each query's seed rows, re-normalised."""
    out = np.zeros((len(seeds), item_table.shape[1]), np.float32)
    for row, ids in enumerate(seeds):
        vec = np.asarray(item_table[np.asarray(ids)], np.float32).mean(axis=0)
        out[row] = vec / np.linalg.norm(vec)
    return out


@functools.partial(jax.jit, static_argnames=("precision",))
def _block_scores(query_rows, item_block, *, precision: str):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum(
            "qr,mr->qm", _round(query_rows, precision),
            _round(item_block, precision),
            preferred_element_type=jnp.float32,
        )


def scores(query_rows, item_table, precision: str = "highest"):
    """[Q, R] x [M, R] -> [Q, M] float32 scores, nothing left out, the
    product computed `ITEM_BLOCK` items at a time."""
    query_rows = jnp.asarray(query_rows, jnp.float32)
    parts = [
        _block_scores(query_rows, item_table[lo:lo + ITEM_BLOCK],
                      precision=precision)
        for lo in range(0, item_table.shape[0], ITEM_BLOCK)
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def excluded_pairs(queries: list, n_items: int, width: int = 32) -> tuple:
    """(rows, ids) of every (query, excluded item) pair of a block, `width`
    pairs a query (`n_items`, an id past the row, fills the rest)."""
    width = max([width] + [len(q["seeds"]) + len(q["blacklist"])
                           for q in queries])
    ids = np.full((len(queries), width), n_items, np.int32)
    for row, query in enumerate(queries):
        out = list(query["seeds"]) + list(query["blacklist"])
        ids[row, :len(out)] = out
    rows = np.repeat(np.arange(len(queries), dtype=np.int32), width)
    return rows, ids.reshape(-1)


def named_categories(queries: list, width: int = 4) -> np.ndarray:
    """`[Q, width]` int32: the category numbers each query names, -1 for
    the rest (-1 is no item's category); a query that names none is a row
    of -2, which `_first` reads as "every item allowed"."""
    width = max([width] + [len(q["categories"]) for q in queries])
    out = np.full((len(queries), width), -1, np.int32)
    for row, query in enumerate(queries):
        if query["categories"]:
            out[row, :len(query["categories"])] = query["categories"]
        else:
            out[row] = -2
    return out


@functools.partial(jax.jit, static_argnames=("num",))
def _first(s, item_cats, named, rows, ids, num: int):
    """The `num` best of each row of `s` among the items that carry one of
    the row's named categories, the listed (row, id) pairs left out
    (ties to the lower id): (values, ids, how many are allowed in all,
    which items the categories allow), and the ids of the same answer with
    the categories IGNORED."""
    carries = jnp.broadcast_to(named[:, :1] == -2, s.shape)
    for column in range(item_cats.shape[1]):    # [Q, M] at a time
        for slot in range(named.shape[1]):
            carries = carries | (item_cats[None, :, column]
                                 == named[:, slot, None])
    kept = s.at[rows, ids].set(-jnp.inf, mode="drop")
    allowed = jnp.where(carries, kept, -jnp.inf)
    vals, order = jax.lax.top_k(allowed, num)
    return (vals, order, jnp.isfinite(allowed).sum(axis=1), carries,
            jax.lax.top_k(kept, num)[1])


def answer(item_table_host: np.ndarray, item_table, item_cats, queries: list,
           num: int, precision: str = "highest", block: int = 8) -> tuple:
    """(items [Q, num], scores [Q, num]) as the reference serves them at
    `precision` (an answer shorter than `num` is filled with -inf
    scores): at "fp8", what the control puts in the program's place."""
    qvecs = query_vectors(item_table_host, [q["seeds"] for q in queries])
    item_cats = jnp.asarray(item_cats)
    items, vals = [], []
    for lo in range(0, len(queries), block):
        part = queries[lo:lo + block]
        s = scores(qvecs[lo:lo + block], item_table, precision)
        rows, ids = excluded_pairs(part, len(item_table_host))
        v, ix, *_ = _first(s, item_cats, jnp.asarray(named_categories(part)),
                           rows, ids, num)
        items.append(np.asarray(ix))
        vals.append(np.asarray(v))
    return np.concatenate(items), np.concatenate(vals)


def compare(item_table_host: np.ndarray, item_table, item_cats,
            queries: list, served_items: list, served_scores: list,
            num: int, block: int = 8) -> dict:
    """Hold served answers against the reference.

    `queries[q]` is `{"seeds": [...], "blacklist": [...], "categories":
    [...]}` (item indices; category numbers of `category_names`);
    `item_cats` is `item_categories` of the run's configuration and seed;
    `served_items[q]` / `served_scores[q]` are what the query was served,
    best first.  With s the reference's scores of query q over every item,
    t_1 >= t_2 >= ... the scores of its allowed items in order and sigma
    the spread of s:

      rank_gap   max_j (t_j - s[served_j]) / sigma
      score_err  max_j |served_score_j - s[served_j]| / sigma

    as `topk_ref.compare` defines them, and exact counts over the queries:
    `answers_with_repeats` (an item served twice), `answers_with_excluded`
    (a served item that is a seed or blackListed),
    `answers_outside_categories` (a served item that carries none of the
    query's categories), `answers_short` (fewer items served than `num`
    and than the reference finds allowed) and `answers_filter_blind` (the
    reference's own answer is the same with the categories ignored: there
    the answer proves nothing about the filter)."""
    n_q = len(queries)
    qvecs = query_vectors(item_table_host, [q["seeds"] for q in queries])
    item_cats = jnp.asarray(item_cats)
    rank_gap = np.zeros(n_q)
    score_err = np.zeros(n_q)
    repeats = excluded = outside = short = blind = 0
    allowed_in_all = np.zeros(n_q, np.int64)
    for lo in range(0, n_q, block):
        hi = min(lo + block, n_q)
        s = scores(qvecs[lo:hi], item_table)
        sigma = np.asarray(jnp.std(s, axis=1))
        rows, ids = excluded_pairs(queries[lo:hi], len(item_table_host))
        best, order, n_allowed, carries, ignored = _first(
            s, item_cats, jnp.asarray(named_categories(queries[lo:hi])),
            rows, ids, num)
        served = np.zeros((hi - lo, num), np.int64)
        for row in range(hi - lo):
            got = served_items[lo + row][:num]
            served[row, :len(got)] = got
        at = jnp.asarray(served)
        s_served = np.asarray(jnp.take_along_axis(s, at, axis=1))
        carried = np.asarray(jnp.take_along_axis(carries, at, axis=1))
        best, order, n_allowed, ignored = (
            np.asarray(x) for x in (best, order, n_allowed, ignored))
        allowed_in_all[lo:hi] = n_allowed
        for row in range(hi - lo):
            q = lo + row
            items = list(served_items[q])
            vals = np.asarray(served_scores[q], np.float32)
            out = set(queries[q]["seeds"]) | set(queries[q]["blacklist"])
            repeats += len(set(items)) != len(items)
            excluded += bool(out & set(items))
            outside += not carried[row, :len(items)].all()
            short += len(items) < min(num, int(n_allowed[row]))
            blind += bool((order[row] == ignored[row]).all())
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
        "answers_outside_categories": float(outside),
        "answers_short": float(short),
        "answers_filter_blind": float(blind),
        "per_query": {"rank_gap": rank_gap, "score_err": score_err,
                      "allowed": allowed_in_all},
    }
