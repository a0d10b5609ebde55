"""The sharded ALS half that never holds the opposite table, the bucket
chunks bounded by the bytes of their Gram, and the chunks of one shape
run as one loop, and the padded blocks expanded once at staging on the
devices that hold the shards: on the CPU's virtual devices
(`tests/conftest.py` forces 8)."""

import importlib.util
import re
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from predictionio_tpu.models import als
from predictionio_tpu.models.als import (
    ALSConfig, ALSTrainer, _assemble_buckets, _chunk_groups,
    exchange_chunk_entries, gram_chunk_rows,
)
from predictionio_tpu.parallel import make_mesh
from predictionio_tpu.parallel.collectives import ShardedRows, shard_map

ROOT = Path(__file__).resolve().parent.parent


def _ratings(n_users=70, n_items=33, density=0.35, seed=5, positive=False):
    rng = np.random.default_rng(seed)
    mask = rng.random((n_users, n_items)) < density
    u, i = np.nonzero(mask)
    v = rng.normal(size=len(u)).astype(np.float32)
    if positive:
        v = np.abs(v) + 1.0
    return u.astype(np.int32), i.astype(np.int32), v, n_users, n_items


def _sweeps(cfg, data, mesh=None, n=2, **run_kw):
    u, i, v, nu, ni = data
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh)
    U, V = tr.init_factors()
    U, V = tr.run(U, V, n, **run_kw)
    return tr, np.asarray(U)[:nu], np.asarray(V)[:ni]


# -- (a) the bounded half equals the replicated half ------------------------


@pytest.mark.parametrize("shards", [4, 8])
@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_sharded_half_matches_replicated(implicit, shards):
    """70 users and 33 items divide by neither 4 nor 8: the tables are
    padded with zero rows, which no device may count."""
    data = _ratings(positive=implicit)
    base = dict(rank=6, lam=0.05, implicit=implicit, alpha=2.0,
                min_bucket_k=4)
    _, U0, V0 = _sweeps(ALSConfig(**base), data)
    tr, U1, V1 = _sweeps(
        ALSConfig(**base, factor_placement="sharded"), data,
        mesh=make_mesh(shards),
    )
    assert tr.sharded and tr.mesh.size == shards
    np.testing.assert_allclose(U1, U0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(V1, V0, rtol=1e-5, atol=1e-5)


def test_sharded_rows_are_the_tables_own_bits():
    """`ShardedRows`: every device asks for rows by global id and gets
    the bits `table[idx]` reads, with zeros in the invalid slots."""
    mesh = make_mesh(4)
    rng = np.random.default_rng(0)
    table = rng.normal(size=(24, 5)).astype(np.float32)
    idx = rng.integers(0, 24, size=(16, 3)).astype(np.int32)
    valid = rng.random((16, 3)) < 0.7

    def body(shard, idx, valid):
        rows = ShardedRows("data", shard.shape[0])
        local, mine = rows.spread(idx, valid)
        return rows.collect(shard[local] * mine[..., None])

    got = shard_map(
        body, mesh=mesh, in_specs=(P("data", None), P("data"), P("data")),
        out_specs=P("data"),
    )(table, idx, valid)
    np.testing.assert_array_equal(
        np.asarray(got), table[idx] * valid[..., None])


def test_table_gram_sums_blocks_and_tail(monkeypatch):
    """`_table_gram`: whole blocks through the loop, the tail beside
    them, the same Y^T Y; and a table under one block is the one
    contraction it always was."""
    monkeypatch.setattr(als, "_GRAM_BLOCK_ROWS", 16)
    import jax

    rng = np.random.default_rng(2)
    for rows in (7, 16, 50, 64):
        table = rng.normal(size=(rows, 5)).astype(np.float32)
        got = als._table_gram(jnp.asarray(table), jax.lax.Precision.HIGHEST)
        want = table.astype(np.float64).T @ table.astype(np.float64)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("k", [8, 64, 128, 256])
def test_expand_bucket_gathers_narrow_rows_and_slices_wide_ones(k):
    """Under `_SLICE_MIN_K` entries a row the block is one gather, from
    there up B slices: the same block either way."""
    rng = np.random.default_rng(k)
    counts = rng.integers(0, k + 1, size=20).astype(np.int32)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1])).astype(np.int32)
    col = rng.integers(0, 1000, size=int(counts.sum())).astype(np.int32)
    val = rng.normal(size=len(col)).astype(np.float32)
    idx, v = als._expand_bucket(jnp.asarray(col), jnp.asarray(val),
                                jnp.asarray(starts), jnp.asarray(counts), k)
    for b in range(20):
        n, s0 = counts[b], starts[b]
        np.testing.assert_array_equal(np.asarray(idx)[b, :n], col[s0:s0 + n])
        np.testing.assert_array_equal(np.asarray(v)[b, :n], val[s0:s0 + n])
        assert not np.asarray(idx)[b, n:].any()
        assert not np.asarray(v)[b, n:].any()
    import jax

    text = jax.jit(als._expand_bucket, static_argnums=4).lower(
        jnp.asarray(col), jnp.asarray(val), jnp.asarray(starts),
        jnp.asarray(counts), k).as_text()
    # a gather of whole K-wide slices, or of single elements
    assert (f"slice_sizes = array<i64: {k}>" in text) == (
        k >= als._SLICE_MIN_K)
    assert ("slice_sizes = array<i64: 1>" in text) == (
        k < als._SLICE_MIN_K)
    # what `_gathers_from_a_column` looks for in a half, found here
    assert _gathers_from_a_column(text)


# -- (b) no device holds the whole opposite table ---------------------------


def _lowered_half(tr, side_name):
    """A sharded half lowered on what `ALSTrainer._half` hands it: the
    tables, the coded half's parity and mask, lambda, alpha and every
    group's staged `(rows, idx, val, counts)` and owners' lists."""
    side = tr._user_side if side_name == "user" else tr._item_side
    fn = (tr._sharded_user_half if side_name == "user"
          else tr._sharded_item_half)
    U, V = tr.init_factors()
    upd, opp = (U, V) if side_name == "user" else (V, U)
    flat = tr._sharded_operands(side)
    args = [upd, opp]
    if tr.coded:
        args += [tr._parity_fn(opp),
                 jnp.ones(tr.mesh.size, jnp.float32)]
    args += [jnp.float32(0.1), jnp.float32(1.0), *flat]
    return fn.lower(*args)


def _compiled_half_text(tr, side_name):
    return _lowered_half(tr, side_name).compile().as_text()


def _tensor_dims(stablehlo_text):
    """Every array dimension that appears in a lowered module."""
    return {
        int(n)
        for shape in re.findall(r"tensor<((?:\d+x)+)", stablehlo_text)
        for n in shape.split("x") if n
    }


def _gathers_from_a_column(stablehlo_text):
    """The gathers of a lowered module whose operand is a
    one-dimensional array: how `_expand_bucket` reads the ratings'
    columns, by element or by slice."""
    return re.findall(
        r'"stablehlo\.gather"[^\n]*: \(tensor<\d+x[a-z]\w*>, ', stablehlo_text)


def _dims(hlo_text):
    """Every array dimension that appears in a compiled module."""
    return {
        int(n)
        for shape in re.findall(r"[a-z]\d*\[([\d,]+)\]", hlo_text)
        for n in shape.split(",")
    }


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_no_op_of_a_sharded_half_has_the_opposite_tables_rows(implicit):
    """The property the exchange exists for: in the compiled, per-device
    module of a half, no operation's result (or operand) has the
    opposite table's row count, padded or not.  1,003 users and 517
    items on 4 devices: 1,004 and 520 rows, 251 and 130 a shard; no
    bucket dimension comes near them."""
    nu, ni = 1003, 517
    data = _ratings(nu, ni, density=0.02, seed=3, positive=implicit)
    cfg = ALSConfig(rank=6, implicit=implicit, min_bucket_k=4,
                    factor_placement="sharded")
    tr = ALSTrainer(data[:3], nu, ni, cfg, mesh=make_mesh(4))
    for side, opp_rows, shard_rows in (("user", (517, 520), 130),
                                       ("item", (1003, 1004), 251)):
        dims = _dims(_compiled_half_text(tr, side))
        assert shard_rows in dims          # the check reads real shapes
        assert not dims & set(opp_rows), (side, sorted(dims))


def test_the_check_sees_a_whole_table_where_a_half_gathers_one():
    """Positive control: the coded half reconstructs a late shard inside
    the gathered table, keeps the all-gather, and the same reading of
    the compiled module finds the opposite table's rows in it."""
    nu, ni = 1003, 517
    data = _ratings(nu, ni, density=0.02, seed=3)
    cfg = ALSConfig(rank=6, min_bucket_k=4, factor_placement="sharded",
                    coded_shards=True)
    tr = ALSTrainer(data[:3], nu, ni, cfg, mesh=make_mesh(4))
    assert 1004 in _dims(_compiled_half_text(tr, "item"))
    assert tr.opp_transient_bytes["item"] == 1004 * 6 * 4


# -- (b') the padded blocks: expanded once, where the shards lie ------------


def _staged_shards(monkeypatch):
    """Record what `_stage_chunk_groups` is handed: the shard-local
    columns, which the trainer drops, the chunks and their shard-local
    starts."""
    seen = []
    stage = ALSTrainer._stage_chunk_groups

    def spy(self, columns, shard_len, buckets, local_starts, table_rows):
        seen.append(([np.asarray(c) for c in columns], shard_len, buckets,
                     local_starts))
        return stage(self, columns, shard_len, buckets, local_starts,
                     table_rows)

    monkeypatch.setattr(ALSTrainer, "_stage_chunk_groups", spy)
    return seen


@pytest.mark.parametrize("shards", [4, 8])
@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_staged_blocks_are_each_shards_own_expansion(monkeypatch, implicit,
                                                     shards):
    """Every staged group's `idx` / `val` are, bit for bit,
    `_expand_bucket` of the owning device's shard-local columns at the
    chunk's shard-local starts, read by element under `_SLICE_MIN_K`
    and by slice from it up; a device's shard of a block is its own
    `[n, B/d]` rows, and the devices' shards add up to the padded
    entries, not to d times them."""
    monkeypatch.setattr(als, "_SLICE_MIN_K", 16)
    monkeypatch.setattr(als, "MAX_ENTRIES_PER_BUCKET", 256)
    seen = _staged_shards(monkeypatch)
    data = _ratings(positive=implicit)
    u, i, v, nu, ni = data
    cfg = ALSConfig(rank=6, implicit=implicit, min_bucket_k=4,
                    factor_placement="sharded")
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=make_mesh(shards))
    assert len(seen) == 2
    widths = set()
    for side, (columns, L, buckets, local_starts) in zip(
            (tr._user_side, tr._item_side), seen):
        assert "c_sorted" not in side and "v_sorted" not in side
        assert side["shard_len"] == L
        c_sh, v_sh = (col.reshape(shards, L) for col in columns)
        runs = _chunk_groups(buckets)
        assert len(runs) == len(side["buckets"])
        held = 0
        for run, (rows, idx, val, counts), k in zip(
                runs, side["buckets"], side["ks"]):
            n, b = rows.shape
            assert (n, k) == (len(run), buckets[run[0]].k)
            assert idx.shape == val.shape == (n, b, k)
            assert idx.dtype == jnp.int32 and val.dtype == jnp.float32
            widths.add(k)
            per = b // shards
            for block in (idx, val):
                assert len(block.addressable_shards) == shards
                for s in block.addressable_shards:
                    assert s.data.shape == (n, per, k)
                    assert s.index[1].stop - (s.index[1].start or 0) == per
            held += sum(s.data.size for s in idx.addressable_shards)
            got_i, got_v = np.asarray(idx), np.asarray(val)
            for c, j in enumerate(run):
                np.testing.assert_array_equal(
                    np.asarray(rows)[c], buckets[j].rows)
                np.testing.assert_array_equal(
                    np.asarray(counts)[c], buckets[j].counts)
                for dev in range(shards):
                    mine = slice(dev * per, (dev + 1) * per)
                    want_i, want_v = als._expand_bucket(
                        jnp.asarray(c_sh[dev]), jnp.asarray(v_sh[dev]),
                        jnp.asarray(local_starts[j][mine]),
                        jnp.asarray(buckets[j].counts[mine]), k)
                    assert got_i[c, mine].tobytes() \
                        == np.asarray(want_i).tobytes()
                    assert got_v[c, mine].tobytes() \
                        == np.asarray(want_v).tobytes()
        assert held == side["padded_entries"] == sum(
            len(b.rows) * b.k for b in buckets)
        assert side["padded_bytes"] == 8 * held
        assert side["expand_s"] >= 0
        # every rating is in a block, once
        assert sum(int(np.count_nonzero(np.asarray(b[2])))
                   for b in side["buckets"]) == len(v)
    assert min(widths) < als._SLICE_MIN_K <= max(widths)


HALF_MODES = {
    "full": dict(rank=6),
    "lowrank": dict(rank=32, implicit=True),
    "block_sweep": dict(rank=6, solver_mode="subspace", subspace_size=2),
    "coded": dict(rank=6, coded_shards=True),
}


@pytest.mark.parametrize("mode", sorted(HALF_MODES))
def test_no_sharded_half_reads_the_shards_coo(mode):
    """The lowered half, whatever its mode, is handed the staged blocks
    and nothing of the shard's COO: no argument and no operand has the
    columns' length (a shard's L, the whole d * L), and no gather reads
    a one-dimensional array.  The staging program is where both are."""
    nu, ni = 1003, 517
    cfg = ALSConfig(min_bucket_k=4, factor_placement="sharded",
                    **HALF_MODES[mode])
    data = _ratings(nu, ni, density=0.02, seed=3, positive=cfg.implicit)
    d = 4
    tr = ALSTrainer(data[:3], nu, ni, cfg, mesh=make_mesh(d))
    assert tr.coded == (mode == "coded")
    assert tr.sweeps_blocks == (mode == "block_sweep")
    assert bool(sum(tr.lowrank_systems["user"].values())) \
        == (mode == "lowrank")
    for name, side in (("user", tr._user_side), ("item", tr._item_side)):
        L = side["shard_len"]
        lowered = _lowered_half(tr, name)
        coo = {L, d * L} | {L + k for k in side["ks"]}
        flat = tr._sharded_operands(side)
        assert len(flat) == 6 * len(side["ks"])
        avals = lowered.in_avals[0]
        assert len(avals) == (6 if tr.coded else 4) + len(flat)
        assert not any(set(a.shape) & coo for a in avals)
        text = lowered.as_text()
        dims = _tensor_dims(text)
        assert dims and not dims & coo, (name, sorted(dims & coo))
        assert "stablehlo.gather" in text           # the factor rows
        assert not _gathers_from_a_column(text)
    # positive control: the staging program holds the columns and reads
    # them as the half no longer does
    staging = als._expand_side_sharded.lower(
        jnp.zeros(d * L, jnp.int32), jnp.zeros(d * L, jnp.float32),
        tuple((b[0], b[3]) for b in side["buckets"]),
        mesh=tr.mesh, ks=side["ks"],
    ).as_text()
    assert f"tensor<{d * L}xi32>" in staging and f"tensor<{L}xi32>" in staging
    assert _gathers_from_a_column(staging)


# -- (b'') the write-back: a shard scatters the rows it owns, by a list ------


def _parents_half(tr, side):
    """The sharded half with the PARENT's write-back, kept here as a
    plain reference: a chunk's solved rows and their ids all-gathered,
    every one of the B rows handed to every shard's scatter, the other
    owners' (and the batch padding) sent to the sentinel and dropped.
    Chunks unrolled; everything before the write is `_solve_buckets`."""
    import jax

    cfg, ks = tr.cfg, side["ks"]
    prec = jax.lax.Precision(cfg.matmul_precision)

    def body(upd, opp, lam, alpha, *flat):
        shard_n = upd.shape[0]
        lo = (jax.lax.axis_index("data") * shard_n).astype(jnp.int32)
        gram = None
        if cfg.implicit:
            gram = jax.lax.psum(als._table_gram(opp, prec), "data")
        table = upd
        for g, k in enumerate(ks):
            group = flat[4 * g: 4 * g + 4]
            for c in range(group[0].shape[0]):

                def write(acc, rows, x, table=table):
                    acc = table if acc is None else acc
                    xg = jax.lax.all_gather(x, "data", axis=0, tiled=True)
                    rg = jax.lax.all_gather(rows, "data", axis=0, tiled=True)
                    local = rg - lo
                    inside = (local >= 0) & (local < shard_n)
                    return acc.at[jnp.where(inside, local, shard_n)].set(
                        xg.astype(acc.dtype), mode="drop")

                table = als._solve_buckets(
                    write, opp, (tuple(a[c] for a in group),), lam, alpha,
                    ks=(k,), implicit=cfg.implicit,
                    weighted_lambda=cfg.weighted_lambda,
                    precision=cfg.matmul_precision, solver=cfg.solver,
                    gram=gram, exchange=ShardedRows("data", opp.shape[0]))
        return table

    table, rep = P("data", None), P()
    group = (P(None, "data"), P(None, "data", None), P(None, "data", None),
             P(None, "data"))
    half = jax.jit(shard_map(
        body, mesh=tr.mesh, in_specs=(table, table, rep, rep) + group * len(ks),
        out_specs=table))
    flat = [a for b in side["buckets"] for a in b]
    return lambda upd, opp: half(
        upd, opp, jnp.float32(cfg.lam), jnp.float32(cfg.alpha), *flat)


WRITE_MODES = {
    "explicit": dict(rank=6),
    "implicit": dict(rank=6, implicit=True),
    "lowrank": dict(rank=32, implicit=True),
}


@pytest.mark.parametrize("mode", sorted(WRITE_MODES))
def test_dealt_rows_written_by_list_bitwise_as_the_parents_write(
        monkeypatch, mode):
    """A row's solution depends neither on the chunk it shares nor on
    who scatters it: two sweeps over dealt buckets, written by the
    owners' lists, are bit for bit the same buckets in id order through
    the parent's write."""
    monkeypatch.setattr(als, "MAX_ENTRIES_PER_BUCKET", 128)
    cfg = ALSConfig(lam=0.05, alpha=2.0, min_bucket_k=4,
                    factor_placement="sharded", **WRITE_MODES[mode])
    data = _ratings(210, 45, density=0.1, positive=cfg.implicit)
    u, i, v, nu, ni = data
    mesh = make_mesh(4)
    dealt, U1, V1 = _sweeps(cfg, data, mesh=mesh)
    assert bool(sum(dealt.lowrank_systems["user"].values())) \
        == (mode == "lowrank")
    # the dealing engaged: a looped group's chunks hold every shard's rows
    caps = dealt.write_caps["user"]
    assert min(cap / b for cap, b in caps) < 0.5
    # staged as the parent staged: a pad width's rows in id order
    monkeypatch.setattr(als, "_deal_order",
                        lambda owner: np.arange(len(owner)))
    plain = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh)
    assert [b[0].shape for b in plain._user_side["buckets"]] \
        == [b[0].shape for b in dealt._user_side["buckets"]]
    assert any(
        not np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
        for a, b in zip(plain._user_side["buckets"],
                        dealt._user_side["buckets"]))
    user_half = _parents_half(plain, plain._user_side)
    item_half = _parents_half(plain, plain._item_side)
    U, V = plain.init_factors()
    for _ in range(2):
        U = user_half(U, V)
        V = item_half(V, U)
    np.testing.assert_array_equal(U1, np.asarray(U)[:nu])
    np.testing.assert_array_equal(V1, np.asarray(V)[:ni])


def _check_lists(rows, own_pos, own_row, shard_n, d):
    """Every real row of the group is in exactly one owner's list,
    exactly once, and it is its owner's; a list's padding carries
    distinct ids past the shard's rows; `cap` is the most any shard
    owns of any chunk, rounded up to the mesh."""
    n, b = rows.shape
    assert own_pos.shape == own_row.shape and own_pos.shape[:2] == (n, d)
    cap = own_pos.shape[2]
    most = 0
    for c in range(n):
        seen = np.zeros(b, np.int64)
        for s in range(d):
            real = own_row[c, s] < shard_n
            most = max(most, int(real.sum()))
            pos = own_pos[c, s][real]
            seen[pos] += 1
            # the place's row is this shard's, under its local id
            np.testing.assert_array_equal(
                rows[c, pos], own_row[c, s][real] + s * shard_n)
            pad = own_row[c, s][~real]
            assert len(set(pad.tolist())) == len(pad)
            assert (pad >= shard_n).all()
            assert len(set(own_row[c, s].tolist())) == cap
            assert ((own_pos[c, s] >= 0) & (own_pos[c, s] < b)).all()
        np.testing.assert_array_equal(seen, rows[c] < d * shard_n)
    assert cap == -(-max(most, 1) // d) * d
    return cap


@pytest.mark.parametrize("shards", [4, 8])
@pytest.mark.parametrize("layout", ["dealt", "one_shard", "id_order"])
def test_owner_lists_hold_every_real_row_once(layout, shards):
    rng = np.random.default_rng(shards)
    shard_n, b, n = 1000, 64, 5
    table_rows = shards * shard_n
    if layout == "one_shard":
        ids = np.sort(rng.choice(shard_n, n * b - 9, replace=False)) + shard_n
    else:
        ids = np.sort(rng.choice(table_rows, n * b - 9, replace=False))
    if layout == "dealt":
        ids = ids[als._deal_order(ids // shard_n)]
    rows = table_rows + np.tile(np.arange(b), n)        # batch padding
    rows[: len(ids)] = ids
    rows = rows.reshape(n, b).astype(np.int32)
    own_pos, own_row = als._owner_lists(rows, shard_n, shards)
    assert own_pos.dtype == own_row.dtype == np.int32
    cap = _check_lists(rows, own_pos, own_row, shard_n, shards)
    if layout == "dealt":
        assert cap <= b // shards + shards
    if layout == "one_shard":
        assert cap == b


@pytest.mark.parametrize("shards", [4, 8])
def test_deal_order_gives_every_chunk_each_owners_share(shards):
    """Every run of B consecutive rows in the dealt order holds each
    owner's rows in its share of the bucket, to a row or two, whatever
    the shares; an owner's rows keep their order."""
    rng = np.random.default_rng(7)
    held = rng.integers(0, 4000, size=shards)
    held[1] = 0                                  # a shard with no row
    owner = np.repeat(np.arange(shards), held)
    order = als._deal_order(owner)
    assert sorted(order.tolist()) == list(range(len(owner)))
    dealt = owner[order]
    for s in range(shards):
        assert (np.diff(order[dealt == s]) > 0).all()
    b = 256
    for lo in range(0, len(owner) - b, b):
        got = np.bincount(dealt[lo: lo + b], minlength=shards)
        assert (np.abs(got - b * held / held.sum()) <= 2).all()
    np.testing.assert_array_equal(
        als._deal_order(np.zeros(50, np.int64)), np.arange(50))


@pytest.mark.parametrize("shards", [4, 8])
def test_staged_lists_are_the_groups_owners(monkeypatch, shards):
    """What `_stage_chunk_groups` stages beside every group: the lists
    of THAT group's rows, a shard its own `[n, 1, cap]`."""
    monkeypatch.setattr(als, "MAX_ENTRIES_PER_BUCKET", 64)
    u, i, v, nu, ni = _ratings()
    cfg = ALSConfig(rank=6, min_bucket_k=4, factor_placement="sharded")
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=make_mesh(shards))
    for side, pad in ((tr._user_side, tr._pad_users),
                      (tr._item_side, tr._pad_items)):
        assert len(side["owners"]) == len(side["buckets"])
        caps = []
        for (rows, *_), (own_pos, own_row) in zip(side["buckets"],
                                                  side["owners"]):
            for a in (own_pos, own_row):
                assert {s.data.shape for s in a.addressable_shards} \
                    == {(a.shape[0], 1, a.shape[2])}
            caps.append([_check_lists(
                np.asarray(rows), np.asarray(own_pos), np.asarray(own_row),
                pad // shards, shards), rows.shape[1]])
        name = "user" if side is tr._user_side else "item"
        assert tr.write_caps[name] == caps
        assert tr.write_rows[name] == sum(
            b[0].shape[0] * cap for b, (cap, _) in zip(side["buckets"], caps))


def test_a_group_one_shard_owns_is_written_whole_and_solved(monkeypatch):
    """Users 0-29 (all in shard 0 of 4 x 30) rate 9-16 items, the other
    90 rate 1-4: the K = 16 group's rows all live in one shard, its
    `cap` is B, and every one of its rows is solved as the replicated
    half solves it."""
    rng = np.random.default_rng(11)
    nu, ni = 120, 40
    per_user = np.concatenate([rng.integers(9, 17, 30),
                               rng.integers(1, 5, 90)])
    u = np.repeat(np.arange(nu), per_user).astype(np.int32)
    i = np.concatenate([rng.choice(ni, c, replace=False)
                        for c in per_user]).astype(np.int32)
    v = rng.normal(size=len(u)).astype(np.float32)
    data = (u, i, v, nu, ni)
    base = dict(rank=6, lam=0.05, min_bucket_k=4)
    _, U0, V0 = _sweeps(ALSConfig(**base), data)
    tr, U1, V1 = _sweeps(ALSConfig(**base, factor_placement="sharded"),
                         data, mesh=make_mesh(4))
    by_k = dict(zip(tr._user_side["ks"], tr.write_caps["user"]))
    assert by_k[16][0] == by_k[16][1]             # cap == B: one owner
    assert by_k[4][0] <= by_k[4][1] // 3 + 4      # dealt over three owners
    np.testing.assert_allclose(U1, U0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(V1, V0, rtol=1e-5, atol=1e-5)
    U_init = np.asarray(tr.init_factors()[0])[:nu]
    assert (np.abs(U1 - U_init).max(axis=1) > 0).all()


def test_the_coded_half_still_freezes_a_masked_shards_rows():
    """The coded half runs the same `solve_core` and write: with shard 2
    masked, that shard's own rows stay as they were, and the others'
    are the rows the unmasked half writes from the reconstructed
    table (parity is current, so the reconstruction is exact)."""
    u, i, v, nu, ni = _ratings()
    cfg = ALSConfig(rank=6, min_bucket_k=4, factor_placement="sharded",
                    coded_shards=True)
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=make_mesh(4))
    assert tr.coded
    U, V = tr.init_factors()
    flat = tr._sharded_operands(tr._user_side)

    def half(ok):
        return tr._sharded_user_half(
            jnp.array(U), V, tr._parity_fn(V), jnp.asarray(ok, jnp.float32),
            jnp.float32(0.1), jnp.float32(1.0), *flat)[0]

    clean = np.asarray(half([1, 1, 1, 1]))
    masked = np.asarray(half([1, 1, 0, 1]))
    shard_n = tr._pad_users // 4
    mine = slice(2 * shard_n, 3 * shard_n)
    np.testing.assert_array_equal(masked[mine], np.asarray(U)[mine])
    assert np.abs(clean[mine] - np.asarray(U)[mine]).max() > 0
    others = np.r_[0: 2 * shard_n, 3 * shard_n: 4 * shard_n]
    np.testing.assert_allclose(masked[others], clean[others],
                               rtol=1e-4, atol=1e-4)


def test_write_rows_read_a_quarter_of_a_dealt_sides_rows(monkeypatch):
    """`writeRows`, `writeCaps` and `pio_als_write_rows_total{side}`: on
    four shards a chip scatters a quarter (and the lists' padding) of a
    side whose rows are dealt, where the parent scattered all of them."""
    from predictionio_tpu.obs import ALS_WRITE_ROWS_TOTAL, tower

    events = []
    monkeypatch.setattr(
        tower, "note_event", lambda name, **f: events.append((name, f)))
    monkeypatch.setattr(als, "MAX_ENTRIES_PER_BUCKET", 1024)
    rng = np.random.default_rng(4)
    nu, ni = 4000, 3000
    per_user = rng.integers(1, 4, nu)
    u = np.repeat(np.arange(nu), per_user).astype(np.int32)
    i = rng.integers(0, ni, len(u)).astype(np.int32)
    v = rng.normal(size=len(u)).astype(np.float32)
    cfg = ALSConfig(rank=4, min_bucket_k=4, factor_placement="sharded")
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=make_mesh(4))
    (_, staged), = events
    assert staged["writeRows"] == tr.write_rows
    assert staged["writeCaps"] == tr.write_caps
    for name, side in (("user", tr._user_side), ("item", tr._item_side)):
        padded = sum(int(b[0].size) for b in side["buckets"])
        assert 0.25 <= staged["writeRows"][name] / padded < 0.3, name
        for (cap, b), (rows, *_) in zip(staged["writeCaps"][name],
                                        side["buckets"]):
            assert b == rows.shape[1]
            if rows.shape[0] > 1:                 # a looped, dealt group
                assert b // 4 <= cap <= b // 4 + 8
    counters = {s: ALS_WRITE_ROWS_TOTAL.labels(side=s)
                for s in ("user", "item")}
    before = {s: c.value() for s, c in counters.items()}
    U, V = tr.init_factors()
    tr.run(U, V, 2)
    for s, c in counters.items():
        assert c.value() - before[s] == 2 * staged["writeRows"][s] > 0


@pytest.mark.parametrize("mesh_size", [None, 4])
def test_replicated_placement_stages_rows_in_id_order(monkeypatch, mesh_size):
    """The dealing is the sharded staging's alone: a replicated trainer,
    on one device or over a mesh, never asks for it, its pad widths'
    rows ascend through their chunks as they did, and it stages no
    owners' lists and scatters by none."""
    def never(owner):
        raise AssertionError("replicated placement dealt a bucket's rows")

    monkeypatch.setattr(als, "_deal_order", never)
    monkeypatch.setattr(als, "MAX_ENTRIES_PER_BUCKET", 64)
    u, i, v, nu, ni = _ratings()
    mesh = make_mesh(mesh_size) if mesh_size else None
    tr = ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=6, min_bucket_k=4),
                    mesh=mesh)
    assert "owners" not in tr._chunk_caps(mesh_size or 1)
    counts = np.bincount(u, minlength=nu)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    for side, n in ((tr._user_side, nu), (tr._item_side, ni)):
        assert "owners" not in side
        last = {}
        for (rows, *_), k in zip(side["buckets"], side["ks"]):
            real = np.asarray(rows)
            real = real[real < n]
            assert (np.diff(real) > 0).all()
            assert real[0] > last.get(k, -1)
            last[k] = real[-1]
    assert tr.write_rows == {"user": 0, "item": 0}
    assert tr.write_caps == {"user": [], "item": []}
    want = _assemble_buckets(counts, starts, nu, 4, 0, mesh_size or 1,
                             **tr._chunk_caps(mesh_size or 1))
    assert _shapes(want) == [
        (k, rows.shape[0])
        for (rows, *_), k in zip(tr._user_side["buckets"],
                                 tr._user_side["ks"])]
    for b, (rows, *_) in zip(want, tr._user_side["buckets"]):
        np.testing.assert_array_equal(b.rows, np.asarray(rows))


# -- (c) chunks bounded by the bytes of their Gram --------------------------


def _shapes(buckets):
    return [(b.k, len(b.rows)) for b in buckets]


def test_no_chunks_gram_exceeds_the_bound_at_rank_128(monkeypatch):
    monkeypatch.setattr(als, "_device_memory_bytes", lambda: int(16.9e9))
    rows = gram_chunk_rows(128, 4)
    assert rows == 4 * 8192
    entries = exchange_chunk_entries(128, 4)
    assert entries == 4 * 262144
    rng = np.random.default_rng(1)
    counts = rng.integers(1, 9, size=300_000).astype(np.int64)
    counts[:700] = rng.integers(200, 5000, size=700)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    buckets = _assemble_buckets(counts, starts, len(counts), 8, 0, 4,
                                entries, starts_dtype=np.int64,
                                max_rows=rows)
    bound = int(16.9e9) // 16
    for b in buckets:
        per_dev = len(b.rows) // 4
        assert per_dev * 128 * 128 * 4 <= bound
        assert (4 + 1) * per_dev * b.k * 128 * 4 <= bound
    assert sum(int((b.rows < len(counts)).sum()) for b in buckets) \
        == len(counts)
    # the entry cap alone would have staged one K=8 chunk of 34 GB of Gram
    unbounded = _assemble_buckets(counts, starts, len(counts), 8, 0, 4,
                                  starts_dtype=np.int64)
    assert max(len(b.rows) for b in unbounded) > 8 * rows


def test_the_bound_leaves_small_ranks_and_one_device_alone():
    assert gram_chunk_rows(10, 1) >= als.MAX_ENTRIES_PER_BUCKET // 8
    assert exchange_chunk_entries(10, 8) == als.MAX_ENTRIES_PER_BUCKET
    assert gram_chunk_rows(64, 1) in (32768, 65536)


def _netflix_degrees():
    spec = importlib.util.spec_from_file_location(
        "train_sweeps_for_degrees",
        ROOT / "perfbench" / "drivers" / "train_sweeps.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    import json

    cfg = json.loads(
        (ROOT / "perfbench" / "configs" / "rec-netflix-r64.json").read_text())
    n = cfg["n_ratings"]
    return (
        module.capped_power_law(cfg["n_users"], cfg["user_exponent"], n,
                                cfg["user_max_ratings"]),
        module.capped_power_law(cfg["n_items"], cfg["item_exponent"], n,
                                cfg["item_max_ratings"]),
    )


# what `rec-netflix-r64.train` staged at commit 0dd1ecb (PR 32), from its
# configuration's own degrees: (pad width, rows) of every bucket chunk
NETFLIX_USER_SHAPES = (
    [(128, 32768)] * 8 + [(128, 28276)] + [(256, 16384)] * 7 + [(256, 4386)]
    + [(512, 8192)] * 5 + [(512, 3436)] + [(1024, 4096)] * 4 + [(1024, 138)]
    + [(2048, 2048)] * 3 + [(4096, 1024)] * 2 + [(4096, 235)]
    + [(8192, 512), (8192, 337), (16384, 256), (16384, 59), (32768, 128),
       (32768, 58)]
)


def test_netflix_degrees_stage_the_shapes_they_staged(monkeypatch):
    """`rec-netflix-r64.train` must not move: from the configuration's
    own degrees, at its full size, the bound cuts no chunk (on a 16.9 GB
    v5e and on a backend that reports nothing), and the user side's
    shapes are the pinned ones."""
    counts_u, counts_i = _netflix_degrees()
    for memory in (int(16.9e9), 16 << 30):
        monkeypatch.setattr(als, "_device_memory_bytes", lambda m=memory: m)
        for counts in (counts_u, counts_i):
            starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
            old = _assemble_buckets(counts, starts, len(counts), 8, 0, 1)
            new = _assemble_buckets(counts, starts, len(counts), 8, 0, 1,
                                    max_rows=gram_chunk_rows(64, 1))
            assert _shapes(new) == _shapes(old)
    starts = np.concatenate(([0], np.cumsum(counts_u)[:-1]))
    staged = _assemble_buckets(counts_u, starts, len(counts_u), 8, 0, 1,
                               max_rows=gram_chunk_rows(64, 1))
    assert _shapes(staged) == NETFLIX_USER_SHAPES


# -- (d) the looped chunks give bitwise what the unrolled ones gave ---------


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_looped_chunks_bitwise_as_unrolled(monkeypatch, implicit):
    monkeypatch.setattr(als, "MAX_ENTRIES_PER_BUCKET", 64)
    data = _ratings(positive=implicit)
    cfg = ALSConfig(rank=6, lam=0.05, implicit=implicit, alpha=2.0,
                    min_bucket_k=4, factor_placement="sharded")
    mesh = make_mesh(4)
    looped, U1, V1 = _sweeps(cfg, data, mesh=mesh)
    assert looped.chunks_looped["user"] >= 4
    assert looped.chunks_looped["item"] >= 2
    assert any(b[0].shape[0] > 1 for b in looped._user_side["buckets"])
    monkeypatch.setattr(
        als, "_chunk_groups", lambda buckets: [[j] for j in range(len(buckets))])
    unrolled, U2, V2 = _sweeps(cfg, data, mesh=mesh)
    assert unrolled.chunks_looped == {"user": 0, "item": 0}
    assert all(b[0].shape[0] == 1 for b in unrolled._user_side["buckets"])
    np.testing.assert_array_equal(U1, U2)
    np.testing.assert_array_equal(V1, V2)


def test_chunk_groups_are_runs_of_one_shape():
    counts = np.array([3] * 20 + [30] * 3, np.int64)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    buckets = _assemble_buckets(counts, starts, len(counts), 4, 0, 2,
                                max_entries=32)
    assert _shapes(buckets) == [(4, 8), (4, 8), (4, 4), (32, 2), (32, 2)]
    assert _chunk_groups(buckets) == [[0, 1], [2], [3, 4]]


# -- (e) counters, the staged event, and the caller's arrays ----------------


def test_the_tracing_carries_the_exchange(monkeypatch):
    from predictionio_tpu.obs import ALS_EXCHANGE_BYTES_TOTAL, tower

    events = []
    monkeypatch.setattr(
        tower, "note_event", lambda name, **f: events.append((name, f)))
    monkeypatch.setattr(als, "MAX_ENTRIES_PER_BUCKET", 64)
    data = _ratings(positive=True)
    u, i, v, nu, ni = data
    cfg = ALSConfig(rank=6, implicit=True, min_bucket_k=4,
                    factor_placement="sharded")
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=make_mesh(4))
    (name, staged), = events
    assert name == "als_staged"
    assert staged["placement"] == "sharded" and staged["shards"] == 4
    d, r = 4, 6
    for which, side in (("user", tr._user_side), ("item", tr._item_side)):
        want = 2 * (d - 1) * r * r * 4 // d          # YtY, all-reduced
        transient = 0
        for (rows, *_), k in zip(side["buckets"], side["ks"]):
            n, b = rows.shape[0], rows.shape[1] // d
            # ids + partial rows in, solved rows back (their ids are
            # staged: the owners' lists)
            want += n * (d - 1) * b * (k * (4 + r * 4) + r * 4)
            transient = max(transient, (d + 1) * b * k * r * 4)
        assert staged["exchangeBytes"][which] == want > 0
        assert staged["oppTransientBytes"][which] == transient
    widest = max(b[0].shape[1] // d for side in (tr._user_side, tr._item_side)
                 for b in side["buckets"])
    assert staged["gramChunkBytes"] == widest * r * r * 4
    assert staged["chunksLooped"] == tr.chunks_looped
    assert staged["chunksLooped"]["user"] > 0
    counters = {s: ALS_EXCHANGE_BYTES_TOTAL.labels(side=s)
                for s in ("user", "item")}
    before = {s: c.value() for s, c in counters.items()}
    U, V = tr.init_factors()
    tr.run(U, V, 3)
    for s, c in counters.items():
        assert c.value() - before[s] == 3 * staged["exchangeBytes"][s]


def test_replicated_placement_reports_no_exchange(monkeypatch):
    from predictionio_tpu.obs import tower

    events = []
    monkeypatch.setattr(
        tower, "note_event", lambda name, **f: events.append((name, f)))
    u, i, v, nu, ni = _ratings()
    ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=6, min_bucket_k=4))
    (_, staged), = events
    assert staged["placement"] == "replicated" and staged["shards"] == 1
    assert staged["exchangeBytes"] == {"user": 0, "item": 0}
    assert staged["oppTransientBytes"] == {"user": 0, "item": 0}
    assert staged["chunksLooped"] == {"user": 0, "item": 0}
    assert staged["gramChunkBytes"] > 0


@pytest.mark.parametrize("placement", ["replicated", "sharded"])
def test_a_donating_run_consumes_the_callers_tables(placement):
    """`run(..., donate=True)`: no second copy of the tables, the same
    sweep, and the caller's arrays are gone; without it they survive."""
    data = _ratings()
    u, i, v, nu, ni = data
    mesh = make_mesh(4) if placement == "sharded" else None
    cfg = ALSConfig(rank=6, min_bucket_k=4, factor_placement=placement)
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh)
    U0, V0 = tr.init_factors()
    U1, V1 = tr.run(U0, V0, 1)
    assert not U0.is_deleted() and not V0.is_deleted()
    U2, V2 = tr.run(U0, V0, 1, donate=True)
    assert U0.is_deleted() and V0.is_deleted()
    np.testing.assert_array_equal(np.asarray(U1), np.asarray(U2))
    np.testing.assert_array_equal(np.asarray(V1), np.asarray(V2))
