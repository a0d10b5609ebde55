"""Excluded ids at the ladder's wider rungs
(`ops.topk._blocked_topk_listed`): a list too long to compare pairwise has
its own blocks reduced again and k blocks chosen.  Exact for any input: the
very best e items excluded, e up to the widest rung, equals the dense
masked top-k id for id; the host lays a list out by block
(`listed_order`: any order in, an id twice in, distinct ids by lane out) and
the device sorts nothing; a row that is not laid out so answers nothing,
never a listed item; nothing of the catalogue's width and no
candidates-by-ids compare is written; and the first rung's program is the
one `similarproduct` compiled before the ladder grew."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import topk

M = 60_013
LADDER = topk.EXCLUDE_LADDER
LISTED = [w for w in LADDER if w > topk._PAIRWISE_EXCLUDE]


def _unit_rows(m, r, seed=0):
    rows = np.random.default_rng(seed).normal(size=(m, r)).astype(np.float32)
    return rows / np.linalg.norm(rows, axis=1, keepdims=True)


def _tables(rows):
    rows = jnp.asarray(rows)
    if topk.rows_per_line(rows.shape[1]) == 1:
        return topk.ItemTables(None, rows)
    return topk.ItemTables(jnp.asarray(rows.T), topk.pack_rows(rows))


def _masked_dense(q, rows, k, exclude):
    mask = np.zeros((len(q), len(rows)), np.float32)
    for row, ids in enumerate(exclude):
        mask[row, ids[ids >= 0]] = -np.inf
    vals, ixs = jax.lax.top_k(jnp.asarray(q) @ jnp.asarray(rows).T + mask, k)
    return np.asarray(vals), np.asarray(ixs)


def _exclude_the_best(q, rows, e, width, rng):
    """`[B, width]` ids: each row's e best items, -1 for the rest; at the
    first rung in an order of the rng's, at the listed form's rungs as
    `_common.batch_filter` lays them out (`listed_order`)."""
    out = np.full((len(q), width), -1, np.int32)
    best = np.argsort(-(q @ rows.T), axis=1)[:, :e]
    for row, ids in zip(out, best):
        if width > topk._PAIRWISE_EXCLUDE:
            row[:e] = topk.listed_order(rng.permutation(ids))
        else:
            row[:e] = rng.permutation(ids)
    return out


@functools.lru_cache(maxsize=None)
def _catalogue(rank):
    rows = _unit_rows(M, rank)
    return rows, _tables(rows)


def _cases():
    """(width, e): at each rung no id, one, the rung's width, and one more
    than the rung below holds (the shortest list that takes this rung)."""
    out = []
    for below, width in zip((0,) + LADDER, LADDER):
        for e in sorted({0, 1, below + 1, width}):
            out.append((width, e))
    return out


@pytest.mark.parametrize("rank", [64, 128])
@pytest.mark.parametrize("width,e", _cases())
def test_the_best_e_items_excluded_at_every_rung(width, e, rank):
    rows, tables = _catalogue(rank)
    rng = np.random.default_rng(width + e)
    q = rows[rng.integers(0, M, 3)] + 0.25 * _unit_rows(3, rank, seed=e)
    k = 16
    exclude = _exclude_the_best(q, rows, e, width, rng)
    assert topk.exclude_width(e) == (width if e else 0) or e <= 1
    assert topk.topk_path(q, tables, k, None, exclude) == "blocked"
    vals, ixs = topk.batch_topk_scores_t(q, tables, k, exclude=exclude)
    want_vals, want_ixs = _masked_dense(q, rows, k, exclude)
    np.testing.assert_array_equal(np.asarray(ixs), want_ixs)
    np.testing.assert_allclose(np.asarray(vals), want_vals, atol=1e-6)
    for row, ids in zip(np.asarray(ixs), exclude):
        assert not set(row.tolist()) & set(ids[ids >= 0].tolist())


def test_listed_order_is_by_lane_then_by_line_and_distinct():
    ids = np.array([300, 5, 133, 5, 261, 4, 128 * 9 + 5, 0, 300])
    got = topk.listed_order(ids)
    assert got.dtype == np.int32
    # lane 0: 0; lane 4: 4; lane 5: 5, 133, 261, 1157; lane 44: 300
    assert got.tolist() == [0, 4, 5, 133, 261, 128 * 9 + 5, 300]
    assert topk.listed_order([]).tolist() == []
    big = np.random.default_rng(0).integers(0, 2**31 - 1, 5000)
    out = topk.listed_order(big)
    assert sorted(out.tolist()) == sorted(set(big.tolist()))
    for blk in topk._BLOCK_ITEMS_LISTED:
        block = (out // 128 // blk) * 128 + out % 128
        # a block's ids lie together: each block is one run
        runs = 1 + int((block[1:] != block[:-1]).sum())
        assert runs == len(set(block.tolist()))


@pytest.mark.parametrize("width", LISTED)
def test_a_row_out_of_order_answers_nothing_and_the_others_stand(width):
    """The device sorts nothing and trusts no one: a row whose list is not
    in `listed_order` (here reversed, and one with an id twice) reads NaN
    scores, which the templates' decode drops; the rows beside it are
    answered."""
    rows, tables = _catalogue(128)
    q = rows[[11, 4242, 77]]
    e = width // 2
    best = np.argsort(-(q @ rows.T), axis=1)[:, :e]
    exclude = np.full((3, width), -1, np.int32)
    exclude[0, :e] = topk.listed_order(best[0])[::-1]
    exclude[1, :e] = topk.listed_order(best[1])
    exclude[2, :e] = topk.listed_order(best[2])
    exclude[2, e] = exclude[2, e - 1]
    vals, ixs = topk.batch_topk_scores_t(q, tables, 16, exclude=exclude)
    vals = np.asarray(vals)
    assert np.isnan(vals[0]).all() and np.isnan(vals[2]).all()
    want_vals, want_ixs = _masked_dense(q[1:2], rows, 16, exclude[1:2])
    np.testing.assert_array_equal(np.asarray(ixs)[1], want_ixs[0])
    np.testing.assert_allclose(vals[1], want_vals[0], atol=1e-6)
    # padding before an id is disorder too
    exclude[1, 0], exclude[1, e] = -1, exclude[1, 0]
    vals, _ = topk.batch_topk_scores_t(q, tables, 16, exclude=exclude)
    assert np.isnan(np.asarray(vals)[1]).all()


@pytest.mark.parametrize("b,k,width,rank", [(1, 16, 128, 128),
                                            (8, 16, 512, 128),
                                            (3, 4, 2048, 64),
                                            (2, 16, LADDER[-1], 128)])
def test_listed_form_with_the_kernel_and_the_tpus_rounding(b, k, width, rank,
                                                           monkeypatch):
    """What the chip runs: the scan kernel (through the interpreter; at
    rank 128 over the row-major table) with bfloat16 operands, the whole
    rung excluded."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    rows = _unit_rows(40_001, rank, seed=2)
    q = rows[np.random.default_rng(b).integers(0, len(rows), b)]

    def rounded(x):
        return np.asarray(jax.lax.reduce_precision(jnp.asarray(x), 8, 7))

    exclude = _exclude_the_best(rounded(q), rounded(rows), width, width,
                                np.random.default_rng(7))
    blk = topk.block_items(b, len(rows), rank, k, n_exclude=width)
    assert blk in topk._BLOCK_ITEMS_LISTED
    vals, ixs = jax.jit(functools.partial(
        topk._blocked_topk_listed, k=k, blk=blk))(
        jnp.asarray(q), _tables(rows), exclude=jnp.asarray(exclude))
    want_vals, want_ixs = _masked_dense(rounded(q), rounded(rows), k, exclude)
    np.testing.assert_array_equal(np.asarray(ixs), want_ixs)
    np.testing.assert_allclose(np.asarray(vals), want_vals, atol=1e-6)


def test_every_item_of_a_row_listed_answers_nothing():
    rows = _unit_rows(9_000, 128, seed=3)
    tables = _tables(rows)
    width = LADDER[-1]
    q = rows[[5, 6]]
    assert topk.block_items(2, len(rows), 128, 16, n_exclude=width)
    far = np.argsort(q @ rows.T, axis=1)[:, :len(rows) - width]
    exclude = np.stack([topk.listed_order(ids) for ids in np.argsort(
        -(q @ rows.T), axis=1)[:, :width]])
    vals, ixs = topk.batch_topk_scores_t(q, tables, 16, exclude=exclude)
    want_vals, want_ixs = _masked_dense(q, rows, 16, exclude)
    np.testing.assert_array_equal(np.asarray(ixs), want_ixs)
    assert set(np.asarray(ixs)[0].tolist()) <= set(far[0].tolist())


@pytest.mark.parametrize("b,k,rank,width,want", [
    (64, 16, 128, 32, 64), (16, 16, 128, 128, 32), (64, 16, 128, 128, 16),
    (16, 16, 128, 512, 16), (64, 16, 128, 512, 8), (16, 16, 128, 2048, 8),
    (64, 16, 128, 4224, 8), (1, 16, 128, 4224, 32), (64, 16, 64, 4224, 8),
    (64, 512, 128, 4224, 0)])
def test_block_size_of_the_listed_form(b, k, rank, width, want):
    """k blocks are chosen whatever the list's length; the block is the
    largest whose `(k + E)` gathered blocks a row fit the ids' budget, and
    the smallest where none does and the gather stays under a quarter of
    the table."""
    assert topk.block_items(b, 9_350_000, rank, k, n_exclude=width) == want
    if want and width > topk._PAIRWISE_EXCLUDE:
        assert b * (k + width) * want * rank * 4 <= max(
            topk._RESCORE_BYTES_IDS, 9_350_000 * rank * 4 // 4)


def test_a_short_catalogue_stays_dense_under_a_long_list():
    rows = _unit_rows(1_000, 16, seed=4)
    tables = _tables(rows)
    q = rows[[1, 2]]
    exclude = _exclude_the_best(q, rows, 300, 512, np.random.default_rng(0))
    assert topk.topk_path(q, tables, 16, None, exclude) == "dense"
    vals, ixs = topk.batch_topk_scores_t(q, tables, 16, exclude=exclude)
    want_vals, want_ixs = _masked_dense(q, rows, 16, exclude)
    np.testing.assert_array_equal(np.asarray(ixs), want_ixs)


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, [getattr(v.aval, "shape", ())
                                   for v in eqn.outvars]
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("width", LISTED)
def test_listed_form_compares_no_candidate_with_every_id(width, monkeypatch):
    """The chip's form: ONE `top_k`, of k lines of 128 block maxima; no
    value with an axis of the catalogue's length; and no value that holds
    a (candidate, listed id) pair: nothing has both the `E * blk` gathered
    candidates and the E ids as axes."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    b, k, r, m = 8, 16, 128, 9_350_000
    blk = topk.block_items(b, m, r, k, n_exclude=width)
    jaxpr = jax.make_jaxpr(
        functools.partial(topk._blocked_topk_listed, k=k, blk=blk)
    )(jax.ShapeDtypeStruct((b, r), jnp.float32),
      topk.ItemTables(None, jax.ShapeDtypeStruct((m, r), jnp.float32)),
      exclude=jax.ShapeDtypeStruct((b, width), jnp.int32))
    prims = list(_primitives(jaxpr.jaxpr))
    top_ks = [shapes for name, shapes in prims if name == "top_k"]
    assert len(top_ks) == 1 and top_ks[0][0] == (b, k)
    shapes = [shape for _, shapes in prims for shape in shapes]
    # nothing of the catalogue's width a row; the one flat array is the
    # batch's block maxima, an eighth of a row's scores or less
    assert max(max(shape, default=0) for shape in shapes
               if len(shape) > 1) < m // 4
    assert max(shape[0] for shape in shapes if len(shape) == 1) < \
        1.05 * b * m / blk
    pairwise = [shape for shape in shapes
                if shape[-1:] != (r,)          # not the gathered rows
                and int(np.prod(shape or (1,))) >= b * width * blk * width]
    assert not pairwise, pairwise
    # what the final compare holds: chosen blocks by listed ids
    assert (b, k, width) in shapes


def _program_digest(width):
    """A digest of the first rung's traced program: every primitive and
    the shapes it writes, in order."""
    b, k, r = 16, 16, 128
    blk = topk.block_items(b, 9_350_000, r, k, n_exclude=width)
    jaxpr = jax.make_jaxpr(
        functools.partial(topk._blocked_topk, k=k, blk=blk)
    )(jax.ShapeDtypeStruct((b, r), jnp.float32),
      topk.ItemTables(None, jax.ShapeDtypeStruct((9_350_000, r),
                                                 jnp.float32)),
      exclude=jax.ShapeDtypeStruct((b, width), jnp.int32))
    text = repr(list(_primitives(jaxpr.jaxpr)))
    return hashlib.sha256(text.encode()).hexdigest()[:16], text


def test_the_first_rungs_program_is_the_one_similarproduct_compiled(
        monkeypatch):
    """`similarproduct`'s every turn: the 32-rung keeps PR 29's form, op
    for op (k + 32 blocks chosen, every candidate compared with the 32
    ids; no sort, no scatter), at the block size it had.  The digest was
    taken from the parent commit's `_blocked_topk` at these shapes."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    assert LADDER[0] == 32 == topk._PAIRWISE_EXCLUDE
    assert topk.block_items(16, 9_350_000, 128, 16, n_exclude=32) == 64
    digest, text = _program_digest(32)
    names = [name for name, _ in eval(text)]    # noqa: S307 - our own repr
    assert "sort" not in names and "scatter" not in names
    assert names.count("top_k") == 1
    assert digest == PARENT_DIGEST, digest


def test_the_listed_form_sorts_nothing_on_the_device(monkeypatch):
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    b, k, r, width = 16, 16, 128, LADDER[-1]
    jaxpr = jax.make_jaxpr(
        functools.partial(topk._blocked_topk_listed, k=k, blk=8)
    )(jax.ShapeDtypeStruct((b, r), jnp.float32),
      topk.ItemTables(None, jax.ShapeDtypeStruct((9_350_000, r),
                                                 jnp.float32)),
      exclude=jax.ShapeDtypeStruct((b, width), jnp.int32))
    names = [name for name, _ in _primitives(jaxpr.jaxpr)]
    assert "sort" not in names and names.count("scatter") == 1


PARENT_DIGEST = "281938c497abb6c9"
