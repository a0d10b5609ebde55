"""The blocked path of `ops.topk.batch_topk_scores_t`: exact top-k without
the `[B, M]` score matrix (block scan, `top_k` over block maxima, rescoring
of the chosen blocks).  CPU: the off-TPU `jnp` scan, and the Pallas scan
kernel through the interpreter with the TPU's operand rounding."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import topk

SB = 64 * 128       # items in a super-block of 64-item blocks


def _rows(m, r=16, seed=0):
    return (np.random.default_rng(seed).normal(size=(m, r)) / 8).astype(
        np.float32)


def _queries(b, r=16, seed=1):
    return (np.random.default_rng(seed).normal(size=(b, r)) / 8).astype(
        np.float32)


def _tables(rows):
    rows = jnp.asarray(rows)
    return topk.ItemTables(jnp.asarray(rows.T), topk.pack_rows(rows))


def _dense(q, rows, k):
    vals, ixs = jax.lax.top_k(jnp.asarray(q) @ jnp.asarray(rows).T, k)
    return np.asarray(vals), np.asarray(ixs)


def _check_exact(vals, ixs, q, rows, k, ids_as_sets=True):
    """Against `lax.top_k` of the whole product: values to 1e-6 in
    descending order, ids as sets per row; with ties only what any exact
    answer has: distinct ids whose own scores are the values."""
    vals, ixs = np.asarray(vals), np.asarray(ixs)
    ref_vals, ref_ixs = _dense(q, rows, k)
    assert vals.shape == ixs.shape == (len(q), k)
    assert vals.dtype == np.float32 and ixs.dtype == np.int32
    np.testing.assert_allclose(vals, ref_vals, atol=1e-6, rtol=0)
    assert (np.diff(vals, axis=1) <= 0).all()
    if ids_as_sets:
        assert (np.sort(ixs, axis=1) == np.sort(ref_ixs, axis=1)).all()
    else:
        assert ((0 <= ixs) & (ixs < len(rows))).all()
        for row in ixs:
            assert len(set(row.tolist())) == k
        own = np.einsum("bkr,br->bk", np.asarray(rows)[ixs], np.asarray(q))
        np.testing.assert_allclose(own, vals, atol=1e-6, rtol=0)


# -- the blocked path equals the dense one -----------------------------------


@pytest.mark.parametrize("m", [100_003, 12 * SB], ids=["ragged", "multiple"])
@pytest.mark.parametrize("k", [1, 16, 64])
@pytest.mark.parametrize("b", [1, 8, 64])
def test_blocked_equals_top_k_of_the_whole_product(b, k, m):
    rows, q = _rows(m), _queries(b)
    tables = _tables(rows)
    assert topk.topk_path(q, tables, k) == "blocked"
    vals, ixs = topk.batch_topk_scores_t(q, tables, k)
    _check_exact(vals, ixs, q, rows, k)


def _adversarial(name, k, m=3 * SB + 517, r=16, b=8):
    """(rows, queries, ids_as_sets) with the winners placed by hand."""
    rows = _rows(m, r, seed=7)
    q = np.tile(_queries(1, r, seed=8), (b, 1))
    q += _queries(b, r, seed=9) * 1e-3
    u = q[0] / np.linalg.norm(q[0])
    if name == "top_k_in_one_block":
        # block 5 of super-block 1: items SB + 5 + 128 g
        for g in range(k + 4):
            rows[SB + 5 + 128 * g] = u * (1.0 + 0.01 * g)
        return rows, q, True
    if name == "one_winner_per_block":
        for lane in range(k + 4):
            rows[2 * SB + lane + 128 * (lane % 64)] = u * (1.0 + 0.01 * lane)
        return rows, q, True
    if name == "duplicated_rows":
        rows = rows[np.arange(m) % 500]
        return rows, q, False
    if name == "all_equal_scores":
        q[3] = 0.0
        return rows, q, False
    raise AssertionError(name)


@pytest.mark.parametrize("k", [4, 16])
@pytest.mark.parametrize("name", ["top_k_in_one_block", "one_winner_per_block",
                                  "duplicated_rows", "all_equal_scores"])
def test_blocked_is_exact_on_adversarial_tables(name, k):
    rows, q, ids_as_sets = _adversarial(name, k)
    tables = _tables(rows)
    assert topk.topk_path(q, tables, k) == "blocked"
    vals, ixs = topk.batch_topk_scores_t(q, tables, k)
    _check_exact(vals, ixs, q, rows, k, ids_as_sets)
    again = topk.batch_topk_scores_t(q, tables, k)
    assert (np.asarray(again[1]) == np.asarray(ixs)).all(), "deterministic"


# -- the scan kernel ---------------------------------------------------------


@pytest.mark.parametrize("b,blk,m,r", [
    (1, 64, 20_011, 16), (8, 64, 3 * SB, 16), (64, 64, 2 * SB + 1, 64),
    (8, 16, 20_011, 16), (3, 8, 5_000, 32), (64, 32, 33_000, 128),
])
def test_scan_kernel_keeps_each_blocks_best_score(b, blk, m, r):
    """The Pallas kernel (through the interpreter) against the definition:
    block j of super-block s holds items s*blk*128 + j + 128 g."""
    rows, q = _rows(m, r), _queries(b, r)
    got = np.asarray(topk.block_maxima(jnp.asarray(q), jnp.asarray(rows.T),
                                       blk, interpret=True))
    sb = blk * 128
    n_sb = -(-m // sb)
    scores = np.full((b, n_sb * sb), -np.inf, np.float32)
    scores[:, :m] = q @ rows.T
    want = scores.reshape(b, n_sb, blk, 128).max(axis=2).reshape(b, -1)
    assert got.shape[0] == b and got.shape[1] >= want.shape[1]
    np.testing.assert_allclose(got[:, :want.shape[1]], want, atol=1e-6)
    assert np.isneginf(got[:, want.shape[1]:]).all(), "past the table"
    plain = np.asarray(topk.block_maxima_jnp(jnp.asarray(q),
                                             jnp.asarray(rows.T), blk))
    np.testing.assert_allclose(plain, want, atol=1e-6)


@pytest.mark.parametrize("b,k,m,r", [(8, 16, 100_003, 16), (64, 16, 2 * SB, 64),
                                     (1, 4, 70_001, 32)])
def test_blocked_with_the_kernel_and_the_tpus_rounding(b, k, m, r,
                                                       monkeypatch):
    """What the chip runs: the scan kernel with bfloat16 operands and the
    rescoring on operands rounded the same way equal `top_k` of the product
    of the rounded operands."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    rows, q = _rows(m, r), _queries(b, r)
    blk = topk.block_items(b, m, r, k)
    assert blk
    vals, ixs = jax.jit(functools.partial(topk._blocked_topk, k=k, blk=blk))(
        jnp.asarray(q), _tables(rows))

    def rounded(x):
        return np.asarray(jax.lax.reduce_precision(jnp.asarray(x), 8, 7))

    _check_exact(vals, ixs, rounded(q), rounded(rows), k)


# -- the shape rule ----------------------------------------------------------


def _dense_cases():
    big = 100_003
    return {
        "short_catalogue": dict(m=8 * 16 * 64 - 1, k=16),
        "large_k": dict(m=big, k=1024, b=64, r=64),
        "masked": dict(m=big, k=16, masked=True),
        "no_packed_rows": dict(m=big, k=16, bare=True),
        "rank_that_packs_into_no_line": dict(m=big, k=16, r=48),
    }


@pytest.mark.parametrize("case", sorted(_dense_cases()))
def test_shape_rule_sends_the_rest_to_the_dense_path(case):
    c = _dense_cases()[case]
    m, k, b, r = c["m"], c["k"], c.get("b", 4), c.get("r", 16)
    rows, q = _rows(m, r), _queries(b, r)
    bare = c.get("bare") or not topk.rows_per_line(r)
    tables = jnp.asarray(rows.T) if bare else _tables(rows)
    mask = None
    if c.get("masked"):
        mask = np.zeros((b, m), np.float32)
        mask[:, ::3] = -np.inf
    assert topk.topk_path(q, tables, k, mask) == "dense"
    vals, ixs = topk.batch_topk_scores_t(q, tables, k, mask=mask)
    scores = jnp.asarray(q) @ jnp.asarray(rows).T
    ref_vals, ref_ixs = jax.lax.top_k(
        scores if mask is None else scores + mask, k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ref_vals))
    np.testing.assert_array_equal(np.asarray(ixs), np.asarray(ref_ixs))


@pytest.mark.parametrize("b,k,want", [(64, 16, 64), (64, 64, 16), (1, 16, 64),
                                      (64, 128, 8), (64, 256, 0)])
def test_block_size_follows_the_rescoring_budget(b, k, want):
    assert topk.block_items(b, 9_390_623, 64, k) == want
    if want:
        assert b * k * want * 64 * 4 <= topk._RESCORE_BYTES


# -- what the benchmark's readers assume -------------------------------------


def _primitives(jaxpr):
    """(primitive name, operand shapes) of every equation, nested ones too."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, [getattr(v.aval, "shape", ())
                                   for v in eqn.invars]
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("form", ["jnp_scan", "kernel"])
def test_blocked_path_holds_one_top_k_and_it_is_not_m_wide(form, monkeypatch):
    """`scorer_device_ms` / `scorer_roofline` count batches as device ops
    named `TopK`: one a batch, over the block maxima."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: form == "kernel")
    m, k, b, r = 100_003, 16, 64, 64
    blk = topk.block_items(b, m, r, k)
    jaxpr = jax.make_jaxpr(
        functools.partial(topk._blocked_topk, k=k, blk=blk)
    )(jnp.zeros((b, r)), _tables(np.zeros((m, r), np.float32)))
    prims = list(_primitives(jaxpr.jaxpr))
    top_ks = [shapes for name, shapes in prims if name == "top_k"]
    assert len(top_ks) == 1
    assert all(max(shape, default=0) <= 2 * m // blk
               for shape in top_ks[0]), top_ks
    assert not [name for name, _ in prims if name in ("sort", "approx_top_k")]
    assert ("pallas_call" in {name for name, _ in prims}) == (form == "kernel")


def test_path_counter_counts_blocked_and_dense():
    rows, q = _rows(100_003), _queries(4)
    tables = _tables(rows)

    def count(path):
        return topk.TOPK_PATH.labels(path=path).value()

    before = count("blocked"), count("dense")
    topk.batch_topk_scores_t(q, tables, 16)
    assert (count("blocked"), count("dense")) == (before[0] + 1, before[1])
    topk.batch_topk_scores_t(q, tables, 16,
                             mask=np.zeros((4, 100_003), np.float32))
    assert (count("blocked"), count("dense")) == (before[0] + 1,
                                                  before[1] + 1)
    topk.batch_topk_scores(q, jnp.asarray(rows), 16)
    topk.topk_scores(q[0], jnp.asarray(rows), 16)
    assert count("dense") == before[1] + 3


# -- the tables the templates hand over --------------------------------------


@pytest.mark.parametrize("m,r", [(1000, 16), (1027, 64), (256, 32), (130, 128),
                                 (255, 64)])
def test_pack_rows_in_pieces_is_the_row_major_table(m, r, monkeypatch):
    """Pieces of 256 items, whole and ragged, against pad + reshape."""
    monkeypatch.setattr(topk, "_PACK_ITEMS", 256)
    rows = _rows(m, r)
    p = topk.rows_per_line(r)
    got = np.asarray(jax.jit(topk.pack_rows.__wrapped__)(jnp.asarray(rows)))
    want = np.pad(rows, ((0, -m % p), (0, 0))).reshape(-1, p * r)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("m,r", [(6, 8), (127, 16), (128, 64), (261, 32),
                                 (50, 128), (9, 48)])
def test_packed_rows_follow_a_patched_model(m, r):
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import ALSModel

    rng = np.random.default_rng(m + r)
    model = ALSModel(
        user_factors=rng.normal(size=(3, r)).astype(np.float32),
        item_factors=rng.normal(size=(m, r)).astype(np.float32),
        users=StringIndex([f"u{i}" for i in range(3)]),
        items=StringIndex([f"i{i}" for i in range(m)]),
        item_props={},
    )

    def check(host):
        tables = model.device_item_tables()
        p = topk.rows_per_line(r)
        if not p:   # no packed form, no third copy: the table alone
            np.testing.assert_array_equal(np.asarray(tables), host.T)
            return
        if p == 1:   # whole lines: the row-major table alone, scanned too
            assert tables.t is None
        else:
            np.testing.assert_array_equal(np.asarray(tables.t), host.T)
        packed = np.asarray(tables.packed)
        assert packed.shape == (-(-len(host) // p), p * r)
        np.testing.assert_array_equal(
            packed.reshape(-1, r)[:len(host)], host)
        assert tables.shape == (r, len(host))

    check(model.item_factors)
    ixs = sorted({0, m // 2, m - 1})
    new_rows = rng.normal(size=(len(ixs), r)).astype(np.float32)
    appended = rng.normal(size=(5, r)).astype(np.float32)
    host = np.concatenate([model.item_factors, appended], axis=0)
    host[ixs] = new_rows
    model.item_factors = host
    model.patch_device_item_rows(ixs, new_rows, appended)
    check(host)


@pytest.mark.parametrize("filtered", ["unmasked", "blacklist", "whitelist"])
def test_batch_predict_takes_the_path_its_batch_allows(filtered):
    """`ALSAlgorithm.batch_predict` over a catalogue long enough for the
    blocked path: the same answers as `predict`, the path on the counter
    and, with the filter's form, on the `pio.turn.dispatch` annotation.
    A blackList rides as ids on the blocked path; a whiteList still
    takes the `[B, M]` mask and the dense form."""
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates import recommendation as rmod

    m, r = 40_000, 16
    rng = np.random.default_rng(3)
    model = rmod.ALSModel(
        user_factors=(rng.normal(size=(5, r)) / 4).astype(np.float32),
        item_factors=(rng.normal(size=(m, r)) / 4).astype(np.float32),
        users=StringIndex([f"u{i}" for i in range(5)]),
        items=StringIndex([f"i{i}" for i in range(m)]),
        item_props={},
    )
    algo = rmod.ALSAlgorithm()
    algo.params = rmod.ALSAlgorithmParams(rank=r)
    queries = [rmod.Query(user=f"u{i}", num=10) for i in range(4)]
    if filtered == "blacklist":
        # the user's own best items, so that the list changes the answer
        best = [s.item for s in algo.predict(model, queries[1]).item_scores]
        queries[1] = rmod.Query(user="u1", num=10,
                                blacklist=(best[0], best[3], "unknown"))
    if filtered == "whitelist":
        queries[1] = rmod.Query(user="u1", num=10, whitelist=tuple(
            f"i{i}" for i in range(0, m, 7)))
    seen = []
    real = rmod.annotate

    def spy(name, **meta):
        seen.append((name, meta))
        return real(name, **meta)

    path, counted, kind, width = {
        "unmasked": ("blocked", "blocked", "none", 0),
        "blacklist": ("blocked", "blocked_ids", "ids",
                      topk.EXCLUDE_LADDER[0]),
        "whitelist": ("dense", "dense", "mask", 0),
    }[filtered]
    before = topk.TOPK_PATH.labels(path=counted).value()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(rmod, "annotate", spy)
        got = algo.batch_predict(model, queries)
    assert topk.TOPK_PATH.labels(path=counted).value() == before + 1
    assert ("pio.turn.dispatch", {"path": path, "filter": kind,
                                  "exclude_width": width,
                                  "categories": 0}) in seen
    for query, result in zip(queries, got):
        solo = algo.predict(model, query)
        assert [s.item for s in result.item_scores] == \
            [s.item for s in solo.item_scores]
        np.testing.assert_allclose([s.score for s in result.item_scores],
                                   [s.score for s in solo.item_scores],
                                   atol=1e-6)
