"""Rows that hold a large share of the opposite table are staged DENSE
(`models/als.dense_min_count`): their normal equations are a blocked
matmul over the whole opposite table, not a gather of its rows.  The
dense half equals the gathered one, the blocked sum holds its digits,
the rule and the memory budget choose the rows they say, the tracing
says how often the path engages, and the modes that consume gathered
rows stage none."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import als as als_mod
from predictionio_tpu.models.als import (
    DENSE_K, ALSConfig, ALSTrainer, sweep_train_als,
)


def _ratings(n_users=120, n_items=30, seed=5, repeats=40, stars=0.5):
    """A table with a head: item j is rated by a share of the users that
    falls from 0.9 to 0.03, and a few pairs are held twice; ratings up
    to 5 in steps of ``stars``."""
    rng = np.random.default_rng(seed)
    share = np.linspace(0.9, 0.03, n_items)
    u, i = np.nonzero(rng.random((n_users, n_items)) < share[None, :])
    u = np.concatenate([u, u[:repeats]]).astype(np.int32)
    i = np.concatenate([i, i[:repeats]]).astype(np.int32)
    v = rng.integers(1, round(5 / stars) + 1, size=len(u)) * stars
    v = v.astype(np.float32)
    return u, i, v, n_users, n_items


@pytest.fixture
def low_floor(monkeypatch):
    """The rule's floor in absolute ratings brought down to the tests'
    tables: rows with 24 ratings or a 32nd of the opposite table."""
    monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 24)


def _dense_rows(side) -> int:
    return sum(int((np.asarray(counts) > 0).sum())
               for (_, _, _, counts), k in zip(side["buckets"], side["ks"])
               if k == DENSE_K)


def _halves(cfg, mesh=None, stars=0.5):
    """(user half, item half) of one sweep from the trainer's own start."""
    u, i, v, nu, ni = _ratings(stars=stars)
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh)
    U0, V0 = tr.init_factors()
    U1 = tr._half(jnp.array(U0, copy=True), V0, tr._user_side)
    V1 = tr._half(jnp.array(V0, copy=True), U1, tr._item_side)
    return tr, np.asarray(U1), np.asarray(V1)


@pytest.mark.parametrize("mesh_devices", [1, 2], ids=["one-device", "mesh-2"])
@pytest.mark.parametrize("mode", [
    # a plain lambda as large as the weighted one is on a wide row: the
    # comparison is of two roundings, not of the systems' conditioning
    dict(),
    dict(weighted_lambda=False, lam=2.0),
    dict(implicit=True, alpha=1.5),
    dict(implicit=True, alpha=1.5, weighted_lambda=False, lam=2.0),
    dict(max_ratings_per_row=50),
], ids=["explicit", "explicit-plain-lambda", "implicit",
        "implicit-plain-lambda", "capped-rows"])
def test_a_half_with_dense_rows_equals_the_gathered_half(
        mode, mesh_devices, low_floor, monkeypatch):
    from predictionio_tpu.parallel import make_mesh

    mesh = make_mesh(mesh_devices) if mesh_devices > 1 else None
    cfg = ALSConfig(**{"rank": 8, "lam": 0.05, "seed": 2, "solver": "xla",
                       **mode})
    tr, U_dense, V_dense = _halves(cfg, mesh)
    assert _dense_rows(tr._user_side) > 0 and _dense_rows(tr._item_side) > 0
    assert any(k != DENSE_K for k in tr._item_side["ks"])
    monkeypatch.setattr(als_mod, "dense_min_count",
                        lambda n, rank, float_weights: None)
    tr, U_gathered, V_gathered = _halves(cfg, mesh)
    assert DENSE_K not in tr._user_side["ks"] + tr._item_side["ks"]
    for dense, gathered in ((U_dense, U_gathered), (V_dense, V_gathered)):
        gap = np.linalg.norm(dense - gathered) / np.linalg.norm(gathered)
        assert gap < 1e-5
        np.testing.assert_allclose(dense, gathered, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_a_20000_entry_row_holds_its_digits(implicit):
    """The blocked sum: one row's 20,000 outer products against float64."""
    rng = np.random.default_rng(3)
    n, r, entries = 50_000, 16, 20_000
    opp = (rng.standard_normal((n, r)) / 4).astype(np.float32)
    cols = rng.choice(n, size=entries, replace=False).astype(np.int32)
    cols[-5:] = cols[:5]                      # five pairs held twice
    vals = rng.integers(1, 6, size=entries).astype(np.float32)
    bucket = als_mod.Bucket(
        k=DENSE_K, rows=np.array([0, 1], np.int32),
        starts=np.array([0, 0], np.int32),
        counts=np.array([entries, 0], np.int32),
    )
    count, rating = als_mod._dense_chunk(
        (jnp.asarray(cols), jnp.asarray(vals)), bucket,
        als_mod.dense_blocks(n), jnp.asarray,
        als_mod.dense_slots(2, True, 1.0, 5.0),
    )
    assert count.dtype == jnp.int8 and int(count.sum()) == entries
    assert rating.dtype == jnp.uint8
    alpha = jnp.float32(0.5)
    A, b = als_mod._dense_normal_equations(
        jnp.asarray(opp), count, rating, alpha, implicit,
        jax.lax.Precision.HIGHEST,
    )
    rows64 = opp[cols].astype(np.float64)
    w = 0.5 * vals if implicit else np.ones(entries)
    bw = 1 + 0.5 * vals if implicit else vals
    A64 = np.einsum("k,kr,ks->rs", w, rows64, rows64)
    b64 = bw @ rows64
    assert np.linalg.norm(np.asarray(A[0]) - A64) < 1e-6 * np.linalg.norm(A64)
    assert np.linalg.norm(np.asarray(b[0]) - b64) < 1e-6 * np.linalg.norm(b64)
    # the chunk's padding row holds nothing
    assert not np.asarray(A[1]).any() and not np.asarray(b[1]).any()


def _one_row_chunk(cols, vals, slots, n_opposite=10_000):
    bucket = als_mod.Bucket(
        k=DENSE_K, rows=np.array([0], np.int32),
        starts=np.array([0], np.int32),
        counts=np.array([len(cols)], np.int32),
    )
    return als_mod._dense_chunk(
        (jnp.asarray(cols), jnp.asarray(vals)), bucket,
        als_mod.dense_blocks(n_opposite), jnp.asarray, slots,
    )


_NARROW = (np.dtype(np.int8), np.dtype(np.uint8))


def _slots_of(cols, vals):
    """`dense_slots` of a one-row COO, from `_slot_stats` as staging
    reckons them (the opposite ids ascending inside the row)."""
    order = np.argsort(cols, kind="stable")
    stats = als_mod._slot_stats(jnp.asarray(cols[order]),
                                jnp.asarray(vals[order]),
                                jnp.zeros(1, jnp.int32))
    widest, whole, least, most = (x.item() for x in stats)
    return als_mod.dense_slots(widest, whole, least, most)


def test_a_pair_held_130_times_keeps_int32_counts():
    """An int8 count would wrap: the stats see the pair held 130 times,
    and the counts are built as int32."""
    cols = np.array([7] * 130 + [3, 9000], np.int32)
    vals = np.ones(len(cols), np.float32)
    slots = _slots_of(cols, vals)
    assert slots == (np.dtype(np.int32), np.dtype(np.uint8))
    count, rating = _one_row_chunk(cols, vals, slots)
    assert count.dtype == jnp.int32 and count.shape == (3, 1, 4096)
    assert int(count[0, 0, 7]) == 130 and int(rating[0, 0, 7]) == 130
    assert int(count[2, 0, 9000 - 8192]) == 1 and int(count.sum()) == 132


def test_a_rating_sum_past_255_is_built_in_float32():
    """A pair held 60 times at 5 stars sums to 300: a uint8 slot would
    wrap, the stats say so, and the ratings are built as float32."""
    cols = np.array([7] * 60 + [3], np.int32)
    vals = np.full(len(cols), 5.0, np.float32)
    slots = _slots_of(cols, vals)
    assert slots == (np.dtype(np.int8), np.dtype(np.float32))
    count, rating = _one_row_chunk(cols, vals, slots)
    assert rating.dtype == jnp.float32 and float(rating[0, 0, 7]) == 300.0
    assert int(count[0, 0, 7]) == 60 and float(rating[0, 0, 3]) == 5.0


@pytest.mark.parametrize("repeats,widest", [
    ((1, 1, 1), 1),
    ((2, 45, 3), 45),
    ((127, 1, 128), 128),
    ((255, 2), 255),
    ((256, 1), 256),
    ((300, 257), 256),
], ids=["no-repeat", "netflix", "past-int8", "uint8-most", "cap", "past-cap"])
def test_the_stats_count_the_widest_pair(repeats, widest):
    """The most times one pair is held, counted to 256; the same id at
    the end of one row and the start of the next is two pairs, not one
    held more often."""
    rng = np.random.default_rng(len(repeats) + widest)
    cols, starts = [], []
    for reps in repeats:
        starts.append(sum(map(len, cols)))
        # each row ends and the next begins with id 9: 9 held 4 and 3 times
        cols.append(np.concatenate([[2] * 3, [5] * reps, [9] * 4]))
        cols.append(np.concatenate([[9] * 3, rng.choice([11, 13], 1)]))
        starts.append(starts[-1] + len(cols[-2]))
    col = np.concatenate(cols).astype(np.int32)
    vals = rng.integers(0, 4, size=len(col)).astype(np.float32)
    got, whole, least, most = als_mod._slot_stats(
        jnp.asarray(col), jnp.asarray(vals), jnp.asarray(starts, jnp.int32))
    assert int(got) == max(widest, 4)
    assert bool(whole) and float(least) == vals.min() and float(most) == vals.max()
    vals[1] = 0.5
    assert not bool(als_mod._slot_stats(
        jnp.asarray(col), jnp.asarray(vals), jnp.asarray(starts, jnp.int32))[1])


@pytest.mark.parametrize("slots", [
    _NARROW, (np.dtype(np.int32), np.dtype(np.float32)),
], ids=["one-byte", "four-byte"])
def test_a_block_holds_the_sums_numpy_adds(slots):
    """Three rows against 12,000 opposite rows, a pair of each row held
    up to 51 times at 5 stars (255, a uint8 slot's most), slots in each
    quarter of a block (the bytes of one packed word) and in every
    block: the block is what numpy's ``add.at`` gives, slot by slot."""
    rng = np.random.default_rng(6)
    n, rows = 12_000, 3
    per_row = [rng.choice(np.arange(6, n), size=k, replace=False)
               for k in (900, 20, 4000)]
    per_row[0] = np.concatenate([per_row[0], [5] * 51])
    per_row[2] = np.concatenate([per_row[2], [4096 + 1024 + 3] * 30,
                                 [4095] * 9])
    cols = np.concatenate(per_row).astype(np.int32)
    vals = rng.integers(1, 6, size=len(cols)).astype(np.float32)
    vals[900:951] = 5.0
    counts = np.array([len(c) for c in per_row], np.int32)
    bucket = als_mod.Bucket(
        k=DENSE_K, rows=np.arange(rows, dtype=np.int32),
        starts=np.concatenate(([0], np.cumsum(counts)[:-1])).astype(np.int32),
        counts=counts,
    )
    blocks = als_mod.dense_blocks(n)
    count, rating = als_mod._dense_chunk(
        (jnp.asarray(cols), jnp.asarray(vals)), bucket, blocks,
        jnp.asarray, slots)
    assert (count.dtype, rating.dtype) == slots
    row = np.repeat(np.arange(rows), counts)
    at = (cols // als_mod._DENSE_BLOCK_ROWS, row,
          cols % als_mod._DENSE_BLOCK_ROWS)
    shape = (blocks, rows, als_mod._DENSE_BLOCK_ROWS)
    want_count, want_rating = np.zeros(shape, np.int64), np.zeros(shape)
    np.add.at(want_count, at, 1)
    np.add.at(want_rating, at, vals)
    assert want_rating.max() == 255 and want_count.max() == 51
    np.testing.assert_array_equal(np.asarray(count, np.int64), want_count)
    np.testing.assert_array_equal(np.asarray(rating, np.float64),
                                  want_rating)


@pytest.mark.parametrize("stats,slots", [
    ((1, True, 1.0, 5.0), ("int8", "uint8")),
    # rec-netflix-r64: pairs held up to 45 times, 45 x 5 = 225
    ((45, True, 1.0, 5.0), ("int8", "uint8")),
    ((52, True, 1.0, 5.0), ("int8", "float32")),
    ((130, True, 0.0, 1.0), ("int32", "uint8")),
    ((300, True, 1.0, 1.0), ("int32", "float32")),
    ((1, False, 0.5, 5.0), ("int8", "float32")),
    ((1, True, -1.0, 1.0), ("int8", "float32")),
], ids=["whole", "netflix", "sum-past-255", "count-past-127",
        "both-past", "half-stars", "negative"])
def test_the_slots_hold_every_sum(stats, slots):
    assert tuple(map(str, als_mod.dense_slots(*stats))) == slots


@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_whole_stars_in_uint8_give_the_float32_blocks_grams_to_the_bit(
        implicit):
    """The same row in uint8 and in float32 rating slots: the same
    values, and the same ``A`` and ``b`` to the bit here (a TPU's MXU
    takes ``b``'s integer weights in three bf16 passes where it takes
    float32 ones in six, and its ``b`` moves in the last digits)."""
    rng = np.random.default_rng(4)
    n, r, entries = 20_000, 16, 6_000
    opp = (rng.standard_normal((n, r)) / 4).astype(np.float32)
    cols = rng.choice(n, size=entries, replace=False).astype(np.int32)
    cols[-40:] = cols[0]                      # one pair held 41 times
    vals = rng.integers(1, 6, size=entries).astype(np.float32)
    wide = (np.dtype(np.int8), np.dtype(np.float32))
    blocks = {slots: _one_row_chunk(cols, vals, slots, n)
              for slots in (_NARROW, wide)}
    narrow, f32 = blocks[_NARROW], blocks[wide]
    assert narrow[1].dtype == jnp.uint8 and f32[1].dtype == jnp.float32
    held = divmod(int(cols[0]), als_mod._DENSE_BLOCK_ROWS)
    assert int(narrow[1][held[0], 0, held[1]]) == vals[0] + vals[-40:].sum()
    np.testing.assert_array_equal(np.asarray(narrow[1], np.float32),
                                  np.asarray(f32[1]))
    grams = [als_mod._dense_normal_equations(
        jnp.asarray(opp), count, rating, jnp.float32(0.5), implicit,
        jax.lax.Precision.HIGHEST) for count, rating in (narrow, f32)]
    for got, want in zip(*grams):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n_opposite,rank,float_weights,widest,dense_from", [
    # rec-netflix-r64's item side: the rows of K = 8,192 and wider are
    # dense, K = 4,096 stays; its user side: the floor decides
    (480_189, 64, False, 232_944, (4_097, 4_097)),
    (17_770, 64, False, 17_653, (4_096, 4_096)),
    # the implicit form's float32 weights: from K = 16,384
    (480_189, 64, True, 232_944, (8_193, 8_193)),
    # ials-amazon14-r128-x4: no row comes near a fortieth of either table
    (20_980_000, 128, False, 30_000, None),
    (9_350_000, 128, False, 40_000, None),
    # a 50 x 20 table: the floor keeps every small table gathered
    (50, 10, False, 50, None),
    (20, 10, False, 20, None),
    # from rank 363 a block's outer products pass 2 GiB: never
    (480_189, 512, False, 480_189, None),
], ids=["netflix-items", "netflix-users", "netflix-items-float-weights",
        "amazon-items", "amazon-users", "small-items", "small-users",
        "rank-512"])
def test_the_rule_at_the_shapes_of_the_cells(n_opposite, rank, float_weights,
                                             widest, dense_from):
    least = als_mod.dense_min_count(n_opposite, rank, float_weights)
    if dense_from is None:
        assert least is None or least > widest
    else:
        assert dense_from[0] <= least <= dense_from[1] <= widest


def test_the_memory_budget_takes_the_widest_rows(monkeypatch):
    counts = np.array([0, 30, 500, 40, 900, 700, 3, 650, 25], np.int64)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1])).astype(np.int32)
    buckets = als_mod._assemble_buckets(
        counts, starts, len(counts), dense_min=400, dense_rows=2,
        batch_multiple=2,
    )
    dense = [b for b in buckets if b.k == DENSE_K]
    assert [b.rows[b.counts > 0].tolist() for b in dense] == [[4, 5]]
    assert dense[0].counts.tolist() == [900, 700] and buckets[-1] is dense[0]
    # the rows the budget left out keep their K buckets
    assert {b.k for b in buckets if set(b.rows) & {2, 7}} == {512, 1024}
    solved = np.concatenate([b.rows[b.counts > 0] for b in buckets])
    assert sorted(solved) == [1, 2, 3, 4, 5, 6, 7, 8]
    # the budget itself: a power of two of rows under a quarter of the
    # device's memory at the slot's bytes: two for whole stars (an int8
    # count, a uint8 sum), five where the ratings need float32, eight
    # with int32 counts
    monkeypatch.setattr(als_mod, "_device_memory_bytes", lambda: 16 << 30)
    assert als_mod.dense_budget_rows(480_189, 2) == 4096
    assert als_mod.dense_budget_rows(480_189, 5) == 1024
    assert als_mod.dense_budget_rows(480_189, 8) == 1024
    assert als_mod.dense_budget_rows(17_770, 8) == 16_384
    monkeypatch.setattr(als_mod, "_device_memory_bytes", lambda: 95 << 30)
    assert als_mod.dense_budget_rows(480_189, 8) == 4096


def test_more_qualifying_rows_than_the_budget_train_to_the_same_tables(
        low_floor, monkeypatch):
    cfg = ALSConfig(rank=8, lam=0.05, seed=2, solver="xla")
    _, U_all, V_all = _halves(cfg)
    monkeypatch.setattr(als_mod, "dense_budget_rows",
                        lambda n, slot_bytes: 3)
    tr, U_three, V_three = _halves(cfg)
    assert _dense_rows(tr._user_side) == _dense_rows(tr._item_side) == 3
    np.testing.assert_allclose(U_three, U_all, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(V_three, V_all, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("stars,rating_dtype", [
    (0.5, "float32"), (1.0, "uint8"),
], ids=["half-stars", "whole-stars"])
@pytest.mark.parametrize("implicit", [False, True],
                         ids=["explicit", "implicit"])
def test_the_slots_follow_the_ratings_and_train_to_the_same_tables(
        stars, rating_dtype, implicit, low_floor, monkeypatch):
    """Whole stars take uint8 rating slots, half stars float32; either
    way a sweep's tables are the bits of the float32 slots' (the form
    every rating took before the narrow one)."""
    cfg = ALSConfig(rank=8, lam=0.05, seed=2, solver="xla",
                    implicit=implicit, alpha=1.5)
    tr, U, V = _halves(cfg, stars=stars)
    dense = [b for b, k in zip(tr._item_side["buckets"], tr._item_side["ks"])
             if k == DENSE_K]
    assert dense and all(str(b[2].dtype) == rating_dtype for b in dense)
    monkeypatch.setattr(als_mod, "dense_slots", lambda *stats: (
        np.dtype(np.int8), np.dtype(np.float32)))
    _, U_f32, V_f32 = _halves(cfg, stars=stars)
    np.testing.assert_array_equal(U, U_f32)
    np.testing.assert_array_equal(V, V_f32)


@pytest.mark.parametrize("stars,rating_dtype", [
    (0.5, "float32"), (1.0, "uint8"),
], ids=["half-stars", "whole-stars"])
def test_the_staged_event_and_the_counter_say_how_often_it_engages(
        stars, rating_dtype, low_floor, monkeypatch):
    from predictionio_tpu.obs import ALS_GRAM_ENTRIES_TOTAL, tower

    events = []
    monkeypatch.setattr(
        tower, "note_event", lambda name, **f: events.append((name, f)))
    u, i, v, nu, ni = _ratings(stars=stars)
    tr = ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=8, solver="xla"))
    (name, staged), = events
    assert name == "als_staged"
    # the rating block's dtype follows the data
    assert staged["denseRatingDtype"] == {"user": rating_dtype,
                                          "item": rating_dtype}
    counters = {
        (path, side): ALS_GRAM_ENTRIES_TOTAL.labels(path=path, side=side)
        for path in ("gathered", "dense") for side in ("user", "item")
    }
    before = {key: c.value() for key, c in counters.items()}
    tr.run(*tr.init_factors(), 2)
    added = {key: c.value() - before[key] for key, c in counters.items()}
    counts_i = np.bincount(i, minlength=ni)
    wide = counts_i >= als_mod.dense_min_count(nu, 8, False)
    assert staged["denseRows"]["item"] == int(wide.sum()) > 0
    assert staged["denseEntries"]["item"] == int(counts_i[wide].sum())
    assert staged["denseChunks"] == {"user": 1, "item": 1}
    for side, held in (("user", ni), ("item", nu)):
        rows = tr._user_side if side == "user" else tr._item_side
        (_, count, rating, _), = [
            b for b, k in zip(rows["buckets"], rows["ks"]) if k == DENSE_K]
        assert count.shape[1:] == rating.shape[1:] == (
            staged["denseRows"][side], als_mod._DENSE_BLOCK_ROWS)
        assert staged["denseBytes"][side] == count.nbytes + rating.nbytes
        assert count.dtype == jnp.int8 and rating.dtype == rating_dtype
        # two sweeps, every rating once a half, by one path or the other
        assert added[("dense", side)] == 2 * staged["denseEntries"][side]
        assert added[("dense", side)] + added[("gathered", side)] == 2 * len(v)


@pytest.mark.parametrize("mode", [
    dict(factor_placement="sharded"),
    dict(solver_mode="subspace", subspace_size=4),
], ids=["sharded", "subspace"])
def test_modes_that_consume_gathered_rows_stage_none(mode, monkeypatch):
    """Sharded placement and the subspace sweep run what they ran: no
    dense bucket, the same bits, wherever the floor stands."""
    from predictionio_tpu.parallel import make_mesh

    mesh = make_mesh(2) if "factor_placement" in mode else None
    cfg = ALSConfig(rank=8, lam=0.05, seed=2, **mode)
    _, U_default, V_default = _halves(cfg, mesh)
    monkeypatch.setattr(als_mod, "_DENSE_MIN_COUNT", 24)
    tr, U_low, V_low = _halves(cfg, mesh)
    assert DENSE_K not in tr._user_side["ks"] + tr._item_side["ks"]
    assert tr._user_side["entries"]["dense"] == 0
    np.testing.assert_array_equal(U_low, U_default)
    np.testing.assert_array_equal(V_low, V_default)


def test_the_vmapped_lambda_sweep_takes_the_dense_buckets(low_floor):
    u, i, v, nu, ni = _ratings()
    cfg = ALSConfig(rank=8, num_iterations=2, seed=2, solver="xla")
    lams = (0.02, 0.3)
    swept = sweep_train_als((u, i, v), nu, ni, cfg, lams=lams)
    tr = ALSTrainer((u, i, v), nu, ni, cfg)
    assert _dense_rows(tr._item_side) > 0
    for lam, got in zip(lams, swept):
        U, V = tr.run(*tr.init_factors(), cfg.num_iterations, lam=lam)
        np.testing.assert_allclose(got.user_factors, np.asarray(U),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got.item_factors, np.asarray(V),
                                   rtol=1e-4, atol=1e-5)
