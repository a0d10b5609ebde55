"""Excluded ids on the device (`ops.topk.batch_topk_scores_t(exclude=)`),
at the ladder's first rung (a blackList; the wider rungs, a whole history,
are `tests/test_topk_listed.py`'s):
the blocked path with `k + E` chosen blocks and the exclusions applied to
the gathered candidates equals the dense masked top-k id for id; what the
shapes do not allow stays dense, with the ids scattered into the scores;
and the templates hand their filters over as ids (`_common.batch_filter`),
never as an array of the catalogue's length."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import topk
from predictionio_tpu.templates import _common

M = 100_003
WIDTH = topk.EXCLUDE_LADDER[0]
LAST = topk.EXCLUDE_LADDER[-1]


def _unit_rows(m, r, seed=0):
    rows = np.random.default_rng(seed).normal(size=(m, r)).astype(np.float32)
    return rows / np.linalg.norm(rows, axis=1, keepdims=True)


def _tables(rows):
    """What `DeviceTableMixin.device_item_tables` hands over: packed rows
    with the transposed table, or at a rank of whole lines the row-major
    table alone."""
    rows = jnp.asarray(rows)
    if topk.rows_per_line(rows.shape[1]) == 1:
        return topk.ItemTables(None, rows)
    return topk.ItemTables(jnp.asarray(rows.T), topk.pack_rows(rows))


def _masked_dense(q, rows, k, exclude):
    """`lax.top_k` of the whole product under the `[B, M]` additive mask
    that the ids stand for."""
    mask = np.zeros((len(q), len(rows)), np.float32)
    for row, ids in enumerate(exclude):
        mask[row, ids[ids >= 0]] = -np.inf
    vals, ixs = jax.lax.top_k(jnp.asarray(q) @ jnp.asarray(rows).T + mask, k)
    return np.asarray(vals), np.asarray(ixs)


def _exclude_the_best(q, rows, e, width=WIDTH):
    """`[B, width]` ids: each row's e best items (the query's own seed
    first, each the maximum of its block), the first listed twice where
    there is room, -1 for the rest."""
    out = np.full((len(q), width), -1, np.int32)
    best = np.argsort(-(q @ rows.T), axis=1)[:, :e]
    out[:, :e] = best
    if 0 < e < width:
        out[:, e] = best[:, 0]
    return out


@pytest.mark.parametrize("rank", [64, 128])
@pytest.mark.parametrize("k", [1, 10, 16])
@pytest.mark.parametrize("e", [0, 1, 19, 32])
def test_device_filtered_equals_the_dense_masked_top_k(e, k, rank):
    rows = _unit_rows(M, rank)
    seeds = np.random.default_rng(e + k).integers(0, M, 5)
    q = rows[seeds]                       # each query's best hit is itself
    exclude = _exclude_the_best(q, rows, e)
    if e:
        assert (exclude[:, 0] == seeds).all()
    tables = _tables(rows)
    assert topk.topk_path(q, tables, k, None, exclude) == "blocked"
    vals, ixs = topk.batch_topk_scores_t(q, tables, k, exclude=exclude)
    want_vals, want_ixs = _masked_dense(q, rows, k, exclude)
    np.testing.assert_array_equal(np.asarray(ixs), want_ixs)
    np.testing.assert_allclose(np.asarray(vals), want_vals, atol=1e-6)
    for row, ids in zip(np.asarray(ixs), exclude):
        assert not set(row.tolist()) & set(ids[ids >= 0].tolist())


@pytest.mark.parametrize("b,k,e,rank", [(1, 16, 3, 128), (8, 16, 19, 128),
                                        (3, 4, 32, 64)])
def test_device_filtered_with_the_kernel_and_the_tpus_rounding(b, k, e, rank,
                                                               monkeypatch):
    """What the chip runs: the scan kernel (through the interpreter; at
    rank 128 over the row-major table) with bfloat16 operands."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    rows = _unit_rows(40_001, rank, seed=2)
    q = rows[np.random.default_rng(b).integers(0, len(rows), b)]

    def rounded(x):
        return np.asarray(jax.lax.reduce_precision(jnp.asarray(x), 8, 7))

    exclude = _exclude_the_best(rounded(q), rounded(rows), e)
    blk = topk.block_items(b, len(rows), rank, k, n_exclude=WIDTH)
    assert blk
    vals, ixs = jax.jit(functools.partial(topk._blocked_topk, k=k, blk=blk))(
        jnp.asarray(q), _tables(rows), exclude=jnp.asarray(exclude))
    want_vals, want_ixs = _masked_dense(rounded(q), rounded(rows), k, exclude)
    np.testing.assert_array_equal(np.asarray(ixs), want_ixs)
    np.testing.assert_allclose(np.asarray(vals), want_vals, atol=1e-6)


@pytest.mark.parametrize("b,blk,m", [(1, 64, 20_011), (8, 64, 3 * 64 * 128),
                                     (64, 32, 33_000), (3, 8, 5_000)])
def test_scan_kernel_over_the_row_major_table(b, blk, m):
    """`block_maxima(row_major=True)` reads `[TM, 128]` tiles of item rows
    and keeps the same maxima as the scan of the transposed table."""
    rows = _unit_rows(m, 128, seed=5)
    q = _unit_rows(b, 128, seed=6)
    got = np.asarray(topk.block_maxima(jnp.asarray(q), jnp.asarray(rows), blk,
                                       interpret=True, row_major=True))
    want = np.asarray(topk.block_maxima(jnp.asarray(q), jnp.asarray(rows.T),
                                        blk, interpret=True))
    np.testing.assert_allclose(got, want, atol=1e-6)
    plain = np.asarray(topk.block_maxima_jnp(
        jnp.asarray(q), jnp.asarray(rows), blk, row_major=True))
    np.testing.assert_allclose(plain[:, :got.shape[1]],
                               got[:, :plain.shape[1]], atol=1e-6)


@pytest.mark.parametrize("case", ["short_catalogue", "no_packed_rows",
                                  "rank_that_packs_into_no_line",
                                  "with_a_mask_too"])
def test_what_does_not_fit_stays_dense_with_the_ids_applied(case):
    m, r = (3_000, 16) if case == "short_catalogue" else (M, 16)
    if case == "rank_that_packs_into_no_line":
        r = 48
    rows = _unit_rows(m, r, seed=3)
    q = rows[[5, 77, 1234]]
    exclude = _exclude_the_best(q, rows, 7)
    bare = case in ("no_packed_rows", "rank_that_packs_into_no_line")
    tables = jnp.asarray(rows.T) if bare else _tables(rows)
    mask = None
    if case == "with_a_mask_too":
        mask = np.zeros((3, m), np.float32)
        mask[:, ::3] = -np.inf
    assert topk.topk_path(q, tables, 16, mask, exclude) == "dense"
    vals, ixs = topk.batch_topk_scores_t(q, tables, 16, mask=mask,
                                         exclude=exclude)
    both = np.zeros((3, m), np.float32) if mask is None else mask.copy()
    for row, ids in enumerate(exclude):
        both[row, ids[ids >= 0]] = -np.inf
    want_vals, want_ixs = jax.lax.top_k(
        jnp.asarray(q) @ jnp.asarray(rows).T + both, 16)
    np.testing.assert_array_equal(np.asarray(ixs), np.asarray(want_ixs))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(want_vals),
                               atol=1e-6)


@pytest.mark.parametrize("b,k,rank,width,want", [
    (64, 16, 64, 0, 64), (64, 16, 64, 32, 64), (8, 16, 128, 32, 64),
    (64, 16, 128, 32, 64), (64, 16, 128, 0, 32), (64, 64, 128, 32, 32),
    (64, 512, 128, 32, 0)])
def test_block_size_with_excluded_ids(b, k, rank, width, want):
    """`k + E` blocks are chosen, within the larger budget that excluded
    ids get (a `TopK` over four times the maxima costs more than the
    gather it saves)."""
    assert topk.block_items(b, 9_350_000, rank, k, n_exclude=width) == want


def test_ladder_of_widths_and_the_counters_label():
    assert topk.exclude_width(0) == 0
    assert topk.exclude_width(1) == topk.exclude_width(WIDTH) == WIDTH
    assert topk.exclude_width(WIDTH + 1) == topk.EXCLUDE_LADDER[1]
    assert topk.exclude_width(LAST) == LAST
    assert topk.exclude_width(LAST + 1) == 0
    assert list(topk.EXCLUDE_LADDER) == sorted(set(topk.EXCLUDE_LADDER))
    rows = _unit_rows(M, 128)
    q, tables = rows[:4], _tables(rows)

    def count(path):
        return topk.TOPK_PATH.labels(path=path).value()

    before = count("blocked"), count("blocked_ids"), count("dense")
    topk.batch_topk_scores_t(q, tables, 16)
    topk.batch_topk_scores_t(q, tables, 16,
                             exclude=np.full((4, WIDTH), -1, np.int32))
    topk.batch_topk_scores_t(q, tables, 16,
                             mask=np.zeros((4, M), np.float32))
    assert (count("blocked"), count("blocked_ids"), count("dense")) == (
        before[0] + 1, before[1] + 1, before[2] + 1)


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, [getattr(v.aval, "shape", ())
                                   for v in eqn.outvars]
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


def test_filtered_blocked_path_writes_nothing_of_the_catalogues_width(
        monkeypatch):
    """The chip's form (scan kernel): one `top_k`, over the block maxima;
    no value with an axis of the catalogue's length anywhere."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    b, k, r = 8, 16, 128
    blk = topk.block_items(b, M, r, k, n_exclude=WIDTH)
    jaxpr = jax.make_jaxpr(
        functools.partial(topk._blocked_topk, k=k, blk=blk)
    )(jnp.zeros((b, r)), _tables(np.zeros((M, r), np.float32)),
      exclude=jnp.full((b, WIDTH), -1, jnp.int32))
    prims = list(_primitives(jaxpr.jaxpr))
    assert [name for name, _ in prims].count("top_k") == 1
    widest = max(max(shape, default=0) for _, shapes in prims
                 for shape in shapes)
    assert widest < M // 4, widest


# -- filters as data: `_common.batch_filter` ---------------------------------


class _Items:
    """An id map that counts its lookups and has no `ids` array to scan."""

    def __init__(self, n):
        self.n, self.lookups = n, 0

    def __len__(self):
        return self.n

    def get(self, item_id, default=-1):
        self.lookups += 1
        ix = int(item_id[1:])
        return ix if item_id[0] == "i" and ix < self.n else default


def test_batch_filter_resolves_ids_by_lookup_and_builds_no_wide_array(
        monkeypatch):
    monkeypatch.setattr(_common, "filter_bias_mask", lambda *a, **k: 1 / 0)
    items = _Items(9_350_000)
    rows = [
        _common.RowFilter(blacklist=("i7", "i9", "i7", "x1"),
                          exclude_ix=(3, 9)),
        None,
        _common.RowFilter(exclude_ix=(5,)),
    ]
    flt = _common.batch_filter(items, {}, rows)
    assert flt.kind == "ids" and flt.mask is None
    assert flt.exclude.shape == (3, WIDTH) and flt.exclude.dtype == np.int32
    assert flt.exclude[0].tolist()[:4] == [3, 9, 7, -1]
    assert (flt.exclude[1] == -1).all() and flt.exclude[2, 0] == 5
    assert items.lookups == 4, "one hash lookup an id, no pass over the ids"
    assert flt.scorer_kwargs().keys() == {"mask", "exclude"}


def test_batch_filter_kinds_and_counters():
    from predictionio_tpu.storage.bimap import StringIndex

    items = StringIndex([f"i{j}" for j in range(LAST + 50)])
    rows_of = _common.FILTER_ROWS.labels

    def rows(kind):
        return rows_of(filter=kind).value()

    before = {kind: rows(kind) for kind in ("none", "ids", "mask")}
    built = _common.FILTER_BUILD_SECONDS.snapshot()["count"]
    none = _common.batch_filter(items, {}, [None, _common.RowFilter()])
    assert none == _common.BatchFilter("none")
    assert none.scorer_kwargs() == {"mask": None}
    ids = _common.batch_filter(items, {}, [
        _common.RowFilter(blacklist=("i1",)), None, None])
    assert ids.kind == "ids"
    assert ids.width == WIDTH and none.width == 0
    listed = _common.batch_filter(items, {}, [_common.RowFilter(
        blacklist=tuple(f"i{j}" for j in range(WIDTH + 1)))])
    assert listed.kind == "ids" and listed.width == topk.EXCLUDE_LADDER[1]
    wide = _common.batch_filter(items, {}, [_common.RowFilter(
        blacklist=tuple(f"i{j}" for j in range(LAST + 1)))])
    assert wide.kind == "mask" and wide.exclude is None and wide.width == 0
    assert np.isneginf(wide.mask[0, :LAST + 1]).all()
    assert (wide.mask[0, LAST + 1:] == 0).all()
    assert {kind: rows(kind) - n for kind, n in before.items()} == {
        "none": 2, "ids": 4, "mask": 1}
    assert _common.FILTER_BUILD_SECONDS.snapshot()["count"] == built + 4


# -- the templates: warm-up, one path for one row, no wide host array --------


def _similar_model(m=30_000, r=16, seed=4):
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates import similarproduct as smod

    return smod.SimilarALSModel(
        item_factors=_unit_rows(m, r, seed),
        items=StringIndex([f"i{j}" for j in range(m)]),
        item_props={f"i{j}": {"categories": ["even" if j % 2 == 0 else "odd"]}
                    for j in range(0, m, 3)})


def _brute_force(model, query):
    """The engine's contract in plain numpy: cosine of the mean of the seed
    rows against every item, seeds and filtered items out, best first."""
    table = model.item_factors
    seeds = [model.items.get(i) for i in query.items]
    vec = table[seeds].mean(axis=0)
    scores = table @ (vec / np.linalg.norm(vec))
    out = set(seeds) | {model.items.get(i) for i in query.blacklist or ()}
    allowed = np.ones(len(table), bool)
    allowed[[ix for ix in out if ix >= 0]] = False
    if query.whitelist:
        keep = np.zeros(len(table), bool)
        keep[[model.items.get(i) for i in query.whitelist]] = True
        allowed &= keep
    if query.categories:
        keep = np.zeros(len(table), bool)
        for item_id, props in model.item_props.items():
            if set(props["categories"]) & set(query.categories):
                keep[model.items.get(item_id)] = True
        allowed &= keep
    order = np.argsort(-np.where(allowed, scores, -np.inf), kind="stable")
    return [f"i{ix}" for ix in order[:min(query.num, int(allowed.sum()))]]


def test_similarproduct_serves_by_ids_and_warms_what_it_dispatches(
        monkeypatch):
    """After `warmup`, batches of every rung and a lone `predict` compile
    nothing; `predict` and `batch_predict` answer one query alike; a lone
    request takes the batch's path; and `filter_bias_mask` is never
    called for seeds and blackLists."""
    from predictionio_tpu.obs import xray
    from predictionio_tpu.templates import similarproduct as smod

    model = _similar_model()
    algo = smod.SimilarProductAlgorithm()
    xray.install()
    algo.warmup(model, max_batch=4)
    monkeypatch.setattr(_common, "filter_bias_mask", lambda *a, **k: 1 / 0)
    queries = [
        smod.Query(items=("i5",), num=10),
        smod.Query(items=("i7", "i11", "i13"), num=10,
                   blacklist=tuple(f"i{j}" for j in range(100, 116))),
        smod.Query(items=("nope",), num=10),              # unanswerable
        smod.Query(items=("i2",), num=3, blacklist=("i2", "unknown")),
    ]
    # i5's own best neighbours blackListed: the list changes the answer
    near = _brute_force(model, queries[0])
    queries[0] = smod.Query(items=("i5",), num=10,
                            blacklist=(near[0], near[4]))
    compiled = xray.total_backend_compiles()
    ids_calls = topk.TOPK_PATH.labels(path="blocked_ids").value()
    for n in (4, 2, 1):          # the sizes the batcher pads to
        got = algo.batch_predict(model, queries[:n])
        for query, result in zip(queries[:n], got):
            want = _brute_force(model, query) if query.items != ("nope",) \
                else []
            assert [s.item for s in result.item_scores] == want
    alone = algo.predict(model, queries[1])
    again = algo.batch_predict(model, [queries[1]])[0]
    assert alone == again
    assert algo.predict(model, queries[3]).item_scores[0].item != "i2"
    assert xray.total_backend_compiles() == compiled
    assert topk.TOPK_PATH.labels(path="blocked_ids").value() == ids_calls + 6


@pytest.mark.parametrize("kind", ["categories", "whitelist", "both",
                                  "long_blacklist"])
def test_similarproduct_wide_filters_keep_their_answers(kind):
    """A `whiteList` and a list past the ladder's last rung still take the
    `[B, M]` mask; `categories` alone ride as numbers of the index that a
    model built from property dicts gets at first use; the answers are the
    contract's."""
    from predictionio_tpu.templates import similarproduct as smod

    model = _similar_model()
    algo = smod.SimilarProductAlgorithm()
    white = tuple(f"i{j}" for j in range(0, 3000, 7))
    query = {
        "categories": smod.Query(items=("i6",), num=8, categories=("odd",)),
        "whitelist": smod.Query(items=("i7",), num=8, whitelist=white),
        "both": smod.Query(items=("i7", "i9"), num=8, whitelist=white,
                           categories=("even",), blacklist=("i0", "i42")),
        "long_blacklist": smod.Query(
            items=("i1",), num=8,
            blacklist=tuple(f"i{j}" for j in range(2, 2 + LAST))),
    }[kind]
    form = "cats" if kind == "categories" else "mask"
    rows = _common.FILTER_ROWS.labels(filter=form).value()
    plain = smod.Query(items=("i3",), num=8)
    got = algo.batch_predict(model, [plain, query])
    assert _common.FILTER_ROWS.labels(filter=form).value() == rows + 2
    assert [s.item for s in got[0].item_scores] == _brute_force(model, plain)
    assert [s.item for s in got[1].item_scores] == _brute_force(model, query)
    alone = algo.predict(model, query)     # a batch of one: ulps apart
    assert [s.item for s in alone.item_scores] == \
        [s.item for s in got[1].item_scores]
    np.testing.assert_allclose([s.score for s in alone.item_scores],
                               [s.score for s in got[1].item_scores],
                               atol=1e-6)


def test_recommendation_warms_the_rungs_with_ids():
    from predictionio_tpu.obs import xray
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates import recommendation as rmod

    m, r = 30_000, 16
    model = rmod.ALSModel(
        user_factors=_unit_rows(6, r, seed=8), item_factors=_unit_rows(m, r),
        users=StringIndex([f"u{i}" for i in range(6)]),
        items=StringIndex([f"i{i}" for i in range(m)]), item_props={})
    algo = rmod.ALSAlgorithm()
    algo.params = rmod.ALSAlgorithmParams(rank=r)
    xray.install()
    algo.warmup(model, max_batch=4)
    compiled = xray.total_backend_compiles()
    plain = [rmod.Query(user=f"u{i}", num=10) for i in range(4)]
    listed = plain[:3] + [rmod.Query(user="u3", num=10,
                                     blacklist=("i1", "i2"))]
    for queries in (plain, plain[:2], listed, listed[2:]):
        algo.batch_predict(model, queries)
    assert xray.total_backend_compiles() == compiled
