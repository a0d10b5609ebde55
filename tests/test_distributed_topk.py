"""Sharded top-k over a mesh-sharded item table vs dense single-device reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops.distributed_topk import sharded_topk_scores
from predictionio_tpu.parallel import make_mesh
from predictionio_tpu.parallel.mesh import data_sharding, replicated


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _place(mesh, q, v):
    return (
        jax.device_put(q, replicated(mesh)),
        jax.device_put(v, data_sharding(mesh, 2)),
    )


def test_matches_dense_topk(mesh):
    rng = np.random.default_rng(0)
    B, M, R, k = 6, 64, 8, 5
    q = rng.normal(size=(B, R)).astype(np.float32)
    v = rng.normal(size=(M, R)).astype(np.float32)
    vals, ixs = sharded_topk_scores(*_place(mesh, q, v), k=k, mesh=mesh)
    vals, ixs = np.asarray(vals), np.asarray(ixs)

    dense = q @ v.T
    ref_ix = np.argsort(-dense, axis=1)[:, :k]
    ref_val = np.take_along_axis(dense, ref_ix, axis=1)
    np.testing.assert_allclose(vals, ref_val, rtol=1e-5, atol=1e-5)
    # indices must point at rows achieving those scores
    np.testing.assert_allclose(
        np.take_along_axis(dense, ixs, axis=1), ref_val,
        rtol=1e-5, atol=1e-5,
    )


def test_k_larger_than_shard(mesh):
    """k spanning multiple shards exercises the running-merge."""
    rng = np.random.default_rng(1)
    B, M, R = 3, 32, 4
    k = 12  # > M/d = 4
    q = rng.normal(size=(B, R)).astype(np.float32)
    v = rng.normal(size=(M, R)).astype(np.float32)
    vals, ixs = sharded_topk_scores(*_place(mesh, q, v), k=k, mesh=mesh)
    dense = q @ v.T
    ref = np.sort(dense, axis=1)[:, ::-1][:, :k]
    np.testing.assert_allclose(np.asarray(vals), ref, rtol=1e-5, atol=1e-5)


def test_validation(mesh):
    q = np.zeros((2, 4), np.float32)
    with pytest.raises(ValueError, match="divisible"):
        sharded_topk_scores(q, np.zeros((30, 4), np.float32), 4, mesh)
    with pytest.raises(ValueError, match="k="):
        sharded_topk_scores(q, np.zeros((32, 4), np.float32), 64, mesh)


def test_row_bias_excludes_rows(mesh):
    """-inf-biased rows can never win — the padding contract
    ShardedTopK relies on."""
    rng = np.random.default_rng(3)
    B, M, R, k = 4, 32, 6, 6
    q = rng.normal(size=(B, R)).astype(np.float32)
    v = rng.normal(size=(M, R)).astype(np.float32)
    bias = np.zeros(M, np.float32)
    bias[24:] = -np.inf  # last shard's rows masked out
    vals, ixs = sharded_topk_scores(
        *_place(mesh, q, v), k=k, mesh=mesh,
        row_bias=jax.device_put(
            bias, data_sharding(mesh, 1)
        ),
    )
    assert int(np.asarray(ixs).max()) < 24
    dense = q @ v[:24].T
    ref = np.sort(dense, axis=1)[:, ::-1][:, :k]
    np.testing.assert_allclose(np.asarray(vals), ref, rtol=1e-5,
                               atol=1e-5)


def test_parity_reconstruction_matches_dense(mesh):
    """With a shard marked dead, its block is reconstructed from the
    other d-1 plus parity inside the ring — the result is exactly the
    clean top-k while parity is current."""
    from predictionio_tpu.parallel.coded import (
        ShardHealth, build_parity_fn,
    )

    rng = np.random.default_rng(4)
    d = mesh.shape["data"]
    B, M, R, k = 3, 8 * d, 5, 6
    q = rng.normal(size=(B, R)).astype(np.float32)
    v = rng.normal(size=(M, R)).astype(np.float32)
    qd, vd = _place(mesh, q, v)
    parity = build_parity_fn(mesh)(vd)
    health = ShardHealth(d, op="topk.ring")
    health.killed.add(1)  # pre-degraded: shard 1 is gone
    vals, ixs = sharded_topk_scores(
        qd, vd, k=k, mesh=mesh, parity=parity, health=health,
    )
    dense = q @ v.T
    ref_ix = np.argsort(-dense, axis=1)[:, :k]
    ref_val = np.take_along_axis(dense, ref_ix, axis=1)
    np.testing.assert_allclose(np.asarray(vals), ref_val, rtol=1e-5,
                               atol=1e-5)
    assert health.degraded_polls == 1


def test_stale_parity_serves_last_published_rows(mesh):
    """A stale parity (built before the table moved) serves the dead
    shard's LAST PUBLISHED rows — degraded-but-bounded recall, never
    garbage."""
    from predictionio_tpu.parallel.coded import (
        ShardHealth, build_parity_fn,
    )

    rng = np.random.default_rng(5)
    d = mesh.shape["data"]
    B, M, R, k = 2, 4 * d, 4, 5
    q = rng.normal(size=(B, R)).astype(np.float32)
    v_old = rng.normal(size=(M, R)).astype(np.float32)
    v_new = v_old.copy()
    rows = M // d
    v_new[rows:2 * rows] += 0.25  # shard 1 moved after parity was built
    qd, vd_new = _place(mesh, q, v_new)
    parity_stale = build_parity_fn(mesh)(_place(mesh, q, v_old)[1])
    health = ShardHealth(d, op="topk.ring")
    health.killed.add(1)
    vals, ixs = sharded_topk_scores(
        qd, vd_new, k=k, mesh=mesh, parity=parity_stale, health=health,
    )
    # the reconstruction equals the OLD shard-1 rows + the new rest
    v_served = v_new.copy()
    v_served[rows:2 * rows] = v_old[rows:2 * rows]
    dense = q @ v_served.T
    ref = np.sort(dense, axis=1)[:, ::-1][:, :k]
    np.testing.assert_allclose(np.asarray(vals), ref, rtol=1e-4,
                               atol=1e-4)


def test_works_under_jit(mesh):
    rng = np.random.default_rng(2)
    q = rng.normal(size=(4, 8)).astype(np.float32)
    v = rng.normal(size=(40, 8)).astype(np.float32)

    fn = jax.jit(
        lambda q, v: sharded_topk_scores(q, v, 7, mesh), static_argnums=()
    )
    vals, ixs = fn(*_place(mesh, q, v))
    dense = q @ v.T
    ref = np.sort(dense, axis=1)[:, ::-1][:, :7]
    np.testing.assert_allclose(np.asarray(vals), ref, rtol=1e-5, atol=1e-5)


# -- the table-stationary scan: each chip its own shard, one all-gather ------


def _numpy_topk(q, v, k):
    """Plain exact top-k: float64 scores, ties to the lower row."""
    s = q.astype(np.float64) @ v.astype(np.float64).T
    ix = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(s, ix, axis=1), ix


def _sub_mesh(d):
    return make_mesh(n_devices=d)


@pytest.mark.parametrize("d", [1, 2, 4, 8])
@pytest.mark.parametrize("n_items,rank,k", [
    (12_288 + 37, 128, 16),   # blocked per shard, a ragged last shard
    (21, 4, 9),               # k past a shard's rows, dense per shard
    (5, 8, 5),                # fewer rows than chips at d = 8
])
def test_index_matches_numpy_at_every_mesh_size(d, n_items, rank, k):
    from predictionio_tpu.ops.distributed_topk import ShardedTopK

    rng = np.random.default_rng(11 + d)
    v = rng.normal(size=(n_items, rank)).astype(np.float32)
    q = rng.normal(size=(6, rank)).astype(np.float32)
    idx = ShardedTopK(v, _sub_mesh(d))
    vals, ixs = (np.asarray(a) for a in idx(q, k))
    ref_val, ref_ix = _numpy_topk(q, v, k)
    assert vals.shape == ixs.shape == (6, k)
    assert int(ixs.max()) < n_items, "a padding row never wins"
    np.testing.assert_array_equal(ixs, ref_ix)
    np.testing.assert_allclose(vals, ref_val, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("d", [1, 2, 4, 8])
def test_tied_scores_resolve_to_the_lower_id_as_on_one_chip(d):
    """Rows repeated across shards tie exactly: the answer takes the lower
    id first, as the one-chip scorer's stable select does."""
    from predictionio_tpu.ops.distributed_topk import ShardedTopK
    from predictionio_tpu.ops.topk import batch_topk_scores

    rng = np.random.default_rng(3)
    base = rng.normal(size=(5, 8)).astype(np.float32)
    v = np.tile(base, (8, 1))          # row j ties with j + 5, j + 10, ...
    q = rng.normal(size=(3, 8)).astype(np.float32)
    vals, ixs = ShardedTopK(v, _sub_mesh(d))(q, 12)
    one_vals, one_ixs = batch_topk_scores(q, v, 12)
    np.testing.assert_array_equal(np.asarray(ixs), np.asarray(one_ixs))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(one_vals))


def test_per_shard_candidates_merged_are_the_uncut_answer():
    """The shares add up: each shard's own k best (the per-chip scan, run
    on the shard alone, ids offset by its first row), merged over all
    shards, are the uncut table's k best."""
    from predictionio_tpu.ops.distributed_topk import _local_topk

    rng = np.random.default_rng(5)
    d, rows, rank, k = 4, 3_072, 128, 16
    v = rng.normal(size=(d * rows, rank)).astype(np.float32)
    q = rng.normal(size=(8, rank)).astype(np.float32)
    parts = [_local_topk(jnp.asarray(q), jnp.asarray(v[s * rows:(s + 1) *
                                                      rows]), k)
             for s in range(d)]
    vals = np.concatenate([np.asarray(p[0]) for p in parts], axis=1)
    ids = np.concatenate([np.asarray(p[1]) + s * rows
                          for s, p in enumerate(parts)], axis=1)
    order = np.lexsort((ids, -vals), axis=1)[:, :k]
    ref_val, ref_ix = _numpy_topk(q, v, k)
    np.testing.assert_array_equal(np.take_along_axis(ids, order, 1), ref_ix)
    np.testing.assert_allclose(np.take_along_axis(vals, order, 1), ref_val,
                               rtol=1e-4, atol=1e-4)


def test_host_rows_are_placed_chip_by_chip_bit_for_bit(mesh):
    from predictionio_tpu.ops.distributed_topk import place_rows

    d = mesh.shape["data"]
    rng = np.random.default_rng(6)
    host = rng.normal(size=(10 * d + 3, 16)).astype(np.float32)
    placed = place_rows(host, mesh)
    assert placed.shape == (11 * d, 16)
    for shard in placed.addressable_shards:
        lo = shard.index[0].start or 0
        got = np.asarray(shard.data)
        want = host[lo:lo + len(got)]
        np.testing.assert_array_equal(got[:len(want)], want)
        assert not got[len(want):].any(), "padding rows are zeros"
    # rows already sharded on the mesh are taken as they are
    drawn = jax.device_put(host[:8 * d], data_sharding(mesh, 2))
    assert place_rows(drawn, mesh) is drawn


@pytest.fixture()
def small_chunks(monkeypatch):
    """Parity built and rebuilt five rows at a time (and a ragged rest):
    the chunked loops, not one whole-shard sum."""
    from predictionio_tpu.ops import distributed_topk
    from predictionio_tpu.parallel import coded

    monkeypatch.setattr(coded, "PARITY_CHUNK_BYTES", 5 * 8 * 4)
    distributed_topk._sharded_callable.cache_clear()
    yield
    distributed_topk._sharded_callable.cache_clear()


def test_chunked_parity_is_the_block_sum(mesh, small_chunks):
    from predictionio_tpu.parallel.coded import build_parity_fn, row_chunks

    d = mesh.shape["data"]
    rng = np.random.default_rng(7)
    v = rng.normal(size=(17 * d, 8)).astype(np.float32)
    assert row_chunks(v[:17]) == (5, 3, 2)
    parity = np.asarray(build_parity_fn(mesh)(
        jax.device_put(v, data_sharding(mesh, 2))))
    np.testing.assert_allclose(parity, v.reshape(d, 17, 8).sum(axis=0),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("fault", [
    "dist.shard_delay:shard=2,delay=30.0,times=1",   # late past its budget
    "dist.worker_kill:shard=2,times=1",              # dead
])
def test_rebuilt_shard_by_chunks_answers_exactly(mesh, small_chunks, fault):
    from predictionio_tpu.ops.distributed_topk import ShardedTopK
    from predictionio_tpu.resilience import (
        Deadline, deadline_scope, faults,
    )

    d = mesh.shape["data"]
    rng = np.random.default_rng(8)
    v = rng.normal(size=(17 * d - 3, 8)).astype(np.float32)
    q = rng.normal(size=(4, 8)).astype(np.float32)
    idx = ShardedTopK(v, mesh)
    ref_val, ref_ix = _numpy_topk(q, v, 7)
    faults.arm(fault)
    try:
        with deadline_scope(Deadline.after(0.4)):
            vals, ixs = idx(q, 7)
    finally:
        faults.disarm()
    np.testing.assert_array_equal(np.asarray(ixs), ref_ix)
    np.testing.assert_allclose(np.asarray(vals), ref_val, rtol=1e-4,
                               atol=1e-4)
    assert idx.summary()["degradedPolls"] == 1


def test_int8_variant_is_a_per_shard_stage_over_a_ragged_table(mesh):
    """The quantized candidate stage per shard, no rotation: a shortlist
    covering each shard answers the exact top-k, padding rows dropped."""
    from predictionio_tpu.ops.distributed_topk import ShardedTopK

    d = mesh.shape["data"]
    rng = np.random.default_rng(9)
    v = rng.normal(size=(9 * d - 5, 8)).astype(np.float32)
    q = rng.normal(size=(3, 8)).astype(np.float32)
    idx = ShardedTopK(v, mesh, retrieval="int8", candidate_factor=64)
    vals, ixs = idx(q, 6)
    ref_val, ref_ix = _numpy_topk(q, v, 6)
    np.testing.assert_array_equal(np.asarray(ixs), ref_ix)
    np.testing.assert_allclose(np.asarray(vals), ref_val, rtol=1e-4,
                               atol=1e-4)


def test_clean_program_moves_no_shard(mesh):
    """The clean program's only collective is the all-gather of every
    chip's [B, k] candidates (values and ids as one [2, B, d * k] array):
    no collective-permute, no all-reduce, no shard-sized transfer."""
    import re

    from predictionio_tpu.ops.distributed_topk import (
        _sharded_callable, place_rows,
    )

    d = mesh.shape["data"]
    rows, rank, batch, k = 2_048, 128, 64, 16
    table = place_rows(np.zeros((d * rows, rank), np.float32), mesh)
    q = jnp.zeros((batch, rank), jnp.float32)
    text = _sharded_callable(mesh, "data", k, False).lower(
        q, table, n_valid=d * rows).compile().as_text()
    assert "collective-permute" not in text
    for op in ("all-reduce", "reduce-scatter", "all-to-all"):
        assert f" {op}(" not in text and f" {op}-start(" not in text, op
    gathers = re.findall(r"= (\S+) all-gather(?:-start)?\(", text)
    assert gathers, text[:2000]
    for shape in gathers:
        assert shape.startswith(f"s32[2,{batch},{d * k}]"), shape


def test_template_under_distributed_topk_never_makes_the_one_chip_table(
        mesh):
    """`warmup` and an unfiltered `batch_predict` under distributedTopk
    leave no one-chip device table on the model."""
    from predictionio_tpu.controller.base import instantiate
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSModel, Query, recommendation_engine,
    )

    p = recommendation_engine().params_from_variant({
        "datasource": {"params": {"app_name": "x"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 4, "distributedTopk": True}}],
    })
    algo = instantiate(ALSAlgorithm, p.algorithms[0][1])
    rng = np.random.default_rng(10)
    model = ALSModel(
        user_factors=rng.normal(size=(9, 4)).astype(np.float32),
        item_factors=rng.normal(size=(43, 4)).astype(np.float32),
        users=StringIndex.from_values([f"u{i}" for i in range(9)]),
        items=StringIndex.from_values([f"i{i}" for i in range(43)]),
        item_props={},
    )
    algo.warmup(model, max_batch=8)
    out = algo.batch_predict(
        model, [Query(user=f"u{i}", num=10) for i in range(5)])
    assert all(len(r.item_scores) == 10 for r in out)
    made = [name for name in vars(model) if name.startswith("_dev_item")]
    assert made == [], made
    summary = model.sharded_topk_index().summary()
    d = mesh.shape["data"]
    assert summary["shardRows"] == -(-43 // d)
    assert summary["shardBytes"] == summary["shardRows"] * 4 * 4
    assert summary["parityBytes"] == (summary["shardBytes"] if d > 1 else 0)


def test_model_check_reads_a_sharded_table_where_it_lies(mesh):
    """`ALSModel.sanity_check` tests a table on the mesh there and a host
    table a block of rows at a time: a non-finite entry is found either
    way, past the first block too."""
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import (
        ALSModel, _all_finite,
    )

    d = mesh.shape["data"]
    rng = np.random.default_rng(12)
    users = rng.normal(size=(50, 8)).astype(np.float32)
    items = rng.normal(size=(4 * d, 8)).astype(np.float32)

    def model(u, v):
        return ALSModel(
            user_factors=u,
            item_factors=jax.device_put(v, data_sharding(mesh, 2)),
            users=StringIndex.from_values([f"u{i}" for i in range(50)]),
            items=StringIndex.from_values([f"i{i}" for i in range(4 * d)]),
            item_props={},
        )

    model(users, items).sanity_check()
    bad_users = users.copy()
    bad_users[37, 3] = np.nan
    assert _all_finite(users, rows_at_a_time=16)
    assert not _all_finite(bad_users, rows_at_a_time=16)
    with pytest.raises(ValueError, match="user factors"):
        model(bad_users, items).sanity_check()
    bad_items = items.copy()
    bad_items[-1, 0] = np.inf
    with pytest.raises(ValueError, match="item factors"):
        model(users, bad_items).sanity_check()
