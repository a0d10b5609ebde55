"""Block-ALS tests: numeric parity with a dense NumPy reference solver,
bucketing correctness, implicit mode, and mesh execution.

The NumPy reference implements the same normal equations MLlib solves
(ALS-WR weighted-λ for explicit, Hu-Koren-Volinsky for implicit), so
matching it is the RMSE-parity contract of BASELINE.md.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models.als import (
    ALSConfig,
    ALSFactors,
    ALSTrainer,
    build_bucket_layout,
    rmse,
    train_als,
)


def _toy(n_users=30, n_items=20, rank_true=3, density=0.4, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, rank_true))
    V = rng.normal(size=(n_items, rank_true))
    R = U @ V.T
    mask = rng.random((n_users, n_items)) < density
    u, i = np.nonzero(mask)
    v = R[u, i].astype(np.float32)
    return u.astype(np.int32), i.astype(np.int32), v, n_users, n_items


def _reference_als_explicit(u, i, v, n_users, n_items, cfg: ALSConfig):
    """Dense NumPy ALS with identical init — THE shared oracle
    (tools/mllib_oracle.py, also used by ``bench.py --parity``)."""
    from tools.mllib_oracle import reference_als

    U, V = reference_als(u, i, v, n_users, n_items, cfg)
    return ALSFactors(user_factors=U, item_factors=V)


def test_oracle_closed_form_rank2():
    """The oracle ITSELF against hand-expanded algebra (VERDICT r4
    weak #4: an oracle bug propagates to both sides of every parity
    artifact; this pins it to something that shares no solver code).

    solve_row must satisfy the ALS-WR normal equations
    ``(YᵀY + λ·n·I) x = Yᵀ r``; for rank 2 the inverse is the explicit
    adjugate ``[[a,b],[c,d]]⁻¹ = [[d,-b],[-c,a]]/(ad-bc)``, written out
    here by hand — no np.linalg involved on the checking side."""
    from tools.mllib_oracle import solve_row

    Y = np.array([[1.0, 2.0], [3.0, -1.0], [0.5, 4.0]])
    r = np.array([2.0, -1.0, 3.5])
    lam = 0.3
    n = 3.0

    got = solve_row(Y, r, lam, weighted=True)

    G = Y.T @ Y
    a, b = G[0, 0] + lam * n, G[0, 1]
    c, d = G[1, 0], G[1, 1] + lam * n
    rhs = Y.T @ r
    det = a * d - b * c
    expect = np.array(
        [(d * rhs[0] - b * rhs[1]) / det,
         (-c * rhs[0] + a * rhs[1]) / det]
    )
    np.testing.assert_allclose(got, expect, rtol=1e-12)

    # unweighted convention: λ·I, not λ·n·I
    got_uw = solve_row(Y, r, lam, weighted=False)
    a, d = G[0, 0] + lam, G[1, 1] + lam
    det = a * d - b * c
    expect_uw = np.array(
        [(d * rhs[0] - b * rhs[1]) / det,
         (-c * rhs[0] + a * rhs[1]) / det]
    )
    np.testing.assert_allclose(got_uw, expect_uw, rtol=1e-12)
    assert not np.allclose(got, got_uw)  # the conventions differ


def test_oracle_exact_recovery_halfstep():
    """For R = U₀V₀ᵀ fully observed with λ=0, the user half-sweep from
    V=V₀ must return exactly U₀ (normal equations become
    V₀ᵀV₀ x = V₀ᵀ V₀ U₀ᵀ-row): an independent functional check of the
    oracle's sweep/bucketing, complementary to the algebraic one."""
    from tools.mllib_oracle import _side_order, _solve_side

    rng = np.random.default_rng(3)
    n_users, n_items, rank = 11, 7, 3
    U0 = rng.normal(size=(n_users, rank))
    V0 = rng.normal(size=(n_items, rank))
    R = U0 @ V0.T
    u, i = np.meshgrid(np.arange(n_users), np.arange(n_items),
                       indexing="ij")
    u, i = u.ravel().astype(np.int32), i.ravel().astype(np.int32)
    v = R[u, i]

    order, bounds = _side_order(u, n_users)
    X = np.zeros((n_users, rank))
    out = _solve_side(X, V0, i[order], v[order], bounds,
                      lam=0.0, weighted=True)
    np.testing.assert_allclose(out, U0, rtol=1e-9, atol=1e-9)


def test_bucket_layout_covers_all_ratings():
    u, i, v, nu, ni = _toy()
    layout = build_bucket_layout(u, i, v, nu, min_k=4)
    # sorted COO is a permutation of the input
    assert len(layout.col_sorted) == len(v)
    np.testing.assert_array_equal(np.sort(layout.val_sorted), np.sort(v))
    seen = 0
    real_rows = []
    for b in layout.buckets:
        assert b.k >= 4 and b.k & (b.k - 1) == 0  # power of two
        assert (b.counts <= b.k).all()
        real = b.rows < nu  # padding rows carry id == n_rows
        assert (b.counts[~real] == 0).all()
        assert (b.counts[real] > 0).all()
        seen += int(b.counts.sum())
        real_rows.append(b.rows[real])
    assert seen == len(v)
    all_rows = np.concatenate(real_rows)
    assert len(np.unique(all_rows)) == len(all_rows)
    # per-row slices land on the row's own ratings
    counts = np.bincount(u, minlength=nu)
    for b in layout.buckets:
        for rid, start, cnt in zip(b.rows, b.starts, b.counts):
            if rid >= nu:
                continue
            assert cnt == min(counts[rid], b.k)


# -- the padded layout: expanded once, at staging -----------------------------


@pytest.mark.parametrize("k", [8, 128, 1024])
def test_expand_bucket_matches_plain_numpy(k):
    """`_expand_bucket` against a loop over the rows: a row of count 0,
    one of count K, and the row whose slice ends at nnz (its padding
    slots lie past the column's end: the clamp)."""
    from predictionio_tpu.models.als import _expand_bucket

    rng = np.random.default_rng(k)
    counts = np.array([k, 0, 1, k // 2, 0, k - 1, k, 3], np.int32)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1])).astype(np.int32)
    nnz = int(counts.sum())
    assert starts[-1] + counts[-1] == nnz and counts[-1] < k
    c = rng.integers(1, 5000, nnz).astype(np.int32)
    v = rng.uniform(1, 5, nnz).astype(np.float32)
    # the rows in another order than the column's, as a bucket has them
    order = rng.permutation(len(counts))
    idx, val = _expand_bucket(
        jnp.asarray(c), jnp.asarray(v), jnp.asarray(starts[order]),
        jnp.asarray(counts[order]), k,
    )
    want_i = np.zeros((len(counts), k), np.int32)
    want_v = np.zeros((len(counts), k), np.float32)
    for b, row in enumerate(order):
        s, n = starts[row], counts[row]
        want_i[b, :n] = c[s : s + n]
        want_v[b, :n] = v[s : s + n]
    assert idx.dtype == jnp.int32 and val.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(idx), want_i)
    np.testing.assert_array_equal(np.asarray(val), want_v)


def _staged_columns(monkeypatch):
    """Record what `_stage_side` is handed: the trainer keeps no copy."""
    seen = []
    stage_side = ALSTrainer._stage_side

    def spy(self, c_sorted, v_sorted, buckets, *rest):
        seen.append((np.asarray(c_sorted), np.asarray(v_sorted), buckets))
        return stage_side(self, c_sorted, v_sorted, buckets, *rest)

    monkeypatch.setattr(ALSTrainer, "_stage_side", spy)
    return seen


@pytest.mark.parametrize("implicit", [False, True])
@pytest.mark.parametrize("staging", ["host", "device"])
def test_staged_blocks_train_bitwise_as_expansion_in_the_sweep(
    monkeypatch, staging, implicit
):
    """Two sweeps of `ALSTrainer.run` against a reference half that
    expands every bucket inside the sweep, as the parent's did and the
    sharded path does: the same tables, bit for bit."""
    import jax

    from predictionio_tpu.models import als

    u, i, v, nu, ni = _toy(n_users=70, n_items=33, density=0.35, seed=5)
    v = np.abs(v) + 1.0 if implicit else v
    cfg = ALSConfig(rank=6, lam=0.05, implicit=implicit, alpha=2.0,
                    min_bucket_k=4)
    seen = _staged_columns(monkeypatch)
    tr = ALSTrainer((u, i, v), nu, ni, cfg, staging=staging)
    assert tr.staging == staging and len(seen) == 2
    for side in (tr._user_side, tr._item_side):
        assert "c_sorted" not in side and "v_sorted" not in side

    @functools.partial(jax.jit, static_argnames=("ks",))
    def reference_half(upd, opp, c, v_, flat, *, ks):
        buckets = tuple(
            (rows, *als._expand_bucket(c, v_, starts, counts, k), counts)
            for (rows, starts, counts), k in zip(flat, ks)
        )
        return als._half_iteration_impl(
            upd, opp, buckets, jnp.float32(cfg.lam),
            jnp.float32(cfg.alpha), ks=ks, implicit=implicit,
            weighted_lambda=True, precision="highest", solver="xla",
        )

    def ref_side(c, v_, buckets):
        flat = tuple(
            (jnp.asarray(b.rows), jnp.asarray(b.starts),
             jnp.asarray(b.counts)) for b in buckets
        )
        ks = tuple(b.k for b in buckets)
        return lambda upd, opp: reference_half(
            upd, opp, jnp.asarray(c), jnp.asarray(v_), flat, ks=ks)

    half_u, half_i = ref_side(*seen[0]), ref_side(*seen[1])
    U0, V0 = tr.init_factors()
    U, V = tr.run(U0, V0, 2)
    Ur, Vr = U0, V0
    for _ in range(2):
        Ur = half_u(Ur, Vr)
        Vr = half_i(Vr, Ur)
    np.testing.assert_array_equal(np.asarray(U), np.asarray(Ur))
    np.testing.assert_array_equal(np.asarray(V), np.asarray(Vr))


def test_staged_blocks_are_padded_to_their_buckets_width():
    """Every bucket arrives as `(rows, idx, val, counts)` with `idx`
    int32 and `val` float32, both `[B, K]`, narrow or wide (the TPU
    keeps a `[B, 8]` block with B minor, unpadded: no flat form)."""
    u, i, v, nu, ni = _toy(n_users=300, n_items=40, density=0.5, seed=2)
    tr = ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=4, min_bucket_k=8))
    widths = set()
    for side in (tr._user_side, tr._item_side):
        for (rows, idx, val, counts), k in zip(side["buckets"], side["ks"]):
            assert idx.shape == val.shape == (len(rows), k)
            assert idx.dtype == jnp.int32 and val.dtype == jnp.float32
            assert counts.shape == rows.shape
            assert int(jnp.sum(val != 0)) <= int(jnp.sum(counts))
            widths.add(k)
    assert min(widths) < 128 <= max(widths)


def test_half_iteration_holds_no_gather_from_the_rating_columns():
    """The lowered sweep reads padded blocks: no operand of it has the
    `[nnz]` columns' length, and the staging program, which has, is
    where the positions are reckoned."""
    from predictionio_tpu.models import als

    u, i, v, nu, ni = _toy(n_users=41, n_items=23, density=0.42, seed=11)
    nnz = len(v)
    tr = ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=4))
    layout = build_bucket_layout(u, i, v, nu)
    padded = {len(b.rows) * b.k for b in layout.buckets}
    assert nnz not in padded | {nu, ni} and nnz > 64
    U, V = tr.init_factors()
    side = tr._user_side
    half = als._half_iteration.lower(
        U, V, side["buckets"], jnp.float32(0.1), jnp.float32(1.0),
        ks=side["ks"], implicit=False, weighted_lambda=True,
        precision="highest", solver="xla",
    ).as_text()
    assert "gather" in half                      # the factor rows
    assert f"tensor<{nnz}x" not in half
    staging = als._expand_side.lower(
        jnp.asarray(layout.col_sorted), jnp.asarray(layout.val_sorted),
        tuple((jnp.asarray(b.starts), jnp.asarray(b.counts))
              for b in layout.buckets),
        ks=side["ks"],
    ).as_text()
    assert f"tensor<{nnz}xi32>" in staging and "gather" in staging


def test_als_staged_event_counts_the_padded_layout(monkeypatch):
    from predictionio_tpu.obs import TRAIN_PHASE_SECONDS, tower

    events = []
    monkeypatch.setattr(
        tower, "note_event", lambda name, **f: events.append((name, f)))
    u, i, v, nu, ni = _toy()
    expand = TRAIN_PHASE_SECONDS.labels(phase="als.expand")
    n0 = expand.snapshot()["count"]
    tr = ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=4, min_bucket_k=4))
    assert expand.snapshot()["count"] == n0 + 2     # one a side
    (name, staged), = events
    assert name == "als_staged"
    for which, side in (("user", tr._user_side), ("item", tr._item_side)):
        entries = sum(
            len(rows) * k
            for (rows, *_), k in zip(side["buckets"], side["ks"])
        )
        assert staged["paddedEntries"][which] == entries >= len(v)
        assert staged["paddedBytes"][which] == 8 * entries
        assert staged["expandSeconds"][which] >= 0.0


def test_bucket_layout_cap_truncates():
    u = np.zeros(100, dtype=np.int32)
    i = np.arange(100, dtype=np.int32)
    v = np.ones(100, dtype=np.float32)
    layout = build_bucket_layout(u, i, v, 1, min_k=4, max_per_row=16)
    (b,) = layout.buckets
    assert b.k == 16 and b.counts[0] == 16


def test_bucket_layout_batch_multiple_padding():
    u, i, v, nu, ni = _toy()
    layout = build_bucket_layout(u, i, v, nu, min_k=4, batch_multiple=8)
    for b in layout.buckets:
        assert len(b.rows) % 8 == 0


def test_explicit_matches_numpy_reference():
    # float32 device solves vs float64 NumPy reference: tolerance covers
    # precision drift over iterations, and the prediction matrix (the
    # quantity RMSE parity actually depends on) must agree tightly.
    u, i, v, nu, ni = _toy()
    cfg = ALSConfig(rank=4, num_iterations=5, lam=0.1, seed=7)
    ours = train_als((u, i, v), nu, ni, cfg)
    ref = _reference_als_explicit(u, i, v, nu, ni, cfg)
    np.testing.assert_allclose(
        ours.user_factors, ref.user_factors, rtol=2e-2, atol=2e-2
    )
    np.testing.assert_allclose(
        ours.item_factors, ref.item_factors, rtol=2e-2, atol=2e-2
    )
    pred_ours = ours.user_factors @ ours.item_factors.T
    pred_ref = ref.user_factors @ ref.item_factors.T
    np.testing.assert_allclose(pred_ours, pred_ref, atol=2e-2)


def test_explicit_single_halfstep_exact():
    """One user-side solve against the NumPy normal equations — tight
    tolerance isolates algorithmic correctness from iteration drift."""
    u, i, v, nu, ni = _toy(seed=5)
    cfg = ALSConfig(rank=4, num_iterations=1, lam=0.1, seed=7)
    ours = train_als((u, i, v), nu, ni, cfg)
    ref = _reference_als_explicit(u, i, v, nu, ni, cfg)
    np.testing.assert_allclose(
        ours.user_factors, ref.user_factors, rtol=3e-4, atol=3e-4
    )
    np.testing.assert_allclose(
        ours.item_factors, ref.item_factors, rtol=3e-4, atol=3e-4
    )


def test_explicit_plain_lambda_matches_reference():
    u, i, v, nu, ni = _toy(seed=3)
    cfg = ALSConfig(rank=4, num_iterations=4, lam=0.5, weighted_lambda=False)
    ours = train_als((u, i, v), nu, ni, cfg)
    ref = _reference_als_explicit(u, i, v, nu, ni, cfg)
    np.testing.assert_allclose(
        ours.user_factors, ref.user_factors, rtol=2e-2, atol=2e-2
    )


def test_fits_training_data():
    u, i, v, nu, ni = _toy(density=0.6)
    cfg = ALSConfig(rank=6, num_iterations=10, lam=0.01)
    f = train_als((u, i, v), nu, ni, cfg)
    err = rmse(f, u, i, v)
    assert err < 0.15, f"train RMSE too high: {err}"


def test_implicit_mode_ranks_observed_higher():
    rng = np.random.default_rng(0)
    nu, ni = 20, 15
    # block structure: users 0-9 interact with items 0-7, users 10-19 with 8-14
    us, its = [], []
    for u_ in range(nu):
        lo, hi = (0, 8) if u_ < 10 else (8, 15)
        for i_ in rng.choice(np.arange(lo, hi), size=5, replace=False):
            us.append(u_)
            its.append(i_)
    u = np.array(us, dtype=np.int32)
    i = np.array(its, dtype=np.int32)
    v = np.ones(len(u), dtype=np.float32)
    cfg = ALSConfig(rank=8, num_iterations=10, lam=0.1, implicit=True, alpha=40.0)
    f = train_als((u, i, v), nu, ni, cfg)
    scores = f.user_factors @ f.item_factors.T
    in_block = scores[:10, :8].mean() + scores[10:, 8:].mean()
    out_block = scores[:10, 8:].mean() + scores[10:, :8].mean()
    assert in_block > out_block + 0.3


def test_zero_rating_rows_stay_at_init():
    # user 3 has no ratings: factors must remain at init, not NaN
    u = np.array([0, 1, 2], dtype=np.int32)
    i = np.array([0, 1, 0], dtype=np.int32)
    v = np.ones(3, dtype=np.float32)
    f = train_als((u, i, v), 5, 2, ALSConfig(rank=3, num_iterations=2))
    assert np.isfinite(f.user_factors).all()
    assert np.isfinite(f.item_factors).all()


def test_runs_on_8_device_mesh():
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy()
    mesh = make_mesh()  # 8 virtual CPU devices from conftest
    assert mesh.size == 8
    cfg = ALSConfig(rank=4, num_iterations=3, lam=0.1)
    sharded = train_als((u, i, v), nu, ni, cfg, mesh=mesh)
    single = train_als((u, i, v), nu, ni, cfg, mesh=None)
    np.testing.assert_allclose(
        sharded.user_factors, single.user_factors, rtol=1e-4, atol=1e-4
    )


def test_sharded_factor_tables_match_replicated():
    """ALX-style block-sharded factor tables (factor_placement='sharded')
    must reproduce the replicated path bit-for-bit-close: same bucket math,
    different placement (tables P('data', None) at rest, opposite table
    all-gathered per half-iteration, shard-local scatter)."""
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy(n_users=37, n_items=23)  # NOT mesh-divisible
    mesh = make_mesh()
    assert mesh.size == 8
    cfg_rep = ALSConfig(rank=4, num_iterations=3, lam=0.1)
    cfg_sh = ALSConfig(rank=4, num_iterations=3, lam=0.1,
                       factor_placement="sharded")
    rep = train_als((u, i, v), nu, ni, cfg_rep, mesh=mesh)
    sh = train_als((u, i, v), nu, ni, cfg_sh, mesh=mesh)
    assert sh.user_factors.shape == (nu, 4)
    assert sh.item_factors.shape == (ni, 4)
    np.testing.assert_allclose(
        sh.user_factors, rep.user_factors, rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        sh.item_factors, rep.item_factors, rtol=1e-4, atol=1e-4
    )


def test_sharded_factor_tables_implicit_match():
    """Implicit-feedback mode: the Gram matrix must not pick up padding-row
    contributions from the sharded tables' zero padding."""
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy(n_users=37, n_items=23)
    v = np.abs(v) + 0.5  # implicit confidence weights are nonnegative
    mesh = make_mesh()
    cfg_rep = ALSConfig(rank=4, num_iterations=3, lam=0.1, implicit=True,
                        alpha=2.0)
    cfg_sh = ALSConfig(rank=4, num_iterations=3, lam=0.1, implicit=True,
                       alpha=2.0, factor_placement="sharded")
    rep = train_als((u, i, v), nu, ni, cfg_rep, mesh=mesh)
    sh = train_als((u, i, v), nu, ni, cfg_sh, mesh=mesh)
    np.testing.assert_allclose(
        sh.user_factors, rep.user_factors, rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        sh.item_factors, rep.item_factors, rtol=1e-4, atol=1e-4
    )


def test_sharded_factors_stay_sharded_on_device():
    """The at-rest layout really is block-sharded: each device holds 1/d of
    each factor table (this is the HBM-scaling property)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy()
    mesh = make_mesh()
    cfg = ALSConfig(rank=4, num_iterations=1, lam=0.1,
                    factor_placement="sharded")
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh)
    U, V = tr.init_factors()
    U2, V2 = tr.run(U, V, 1)
    want = NamedSharding(mesh, P("data", None))
    assert U2.sharding.is_equivalent_to(want, U2.ndim)
    assert V2.sharding.is_equivalent_to(want, V2.ndim)
    # each device holds exactly rows/d of the padded table
    shard_rows = {s.data.shape[0] for s in U2.addressable_shards}
    assert shard_rows == {U2.shape[0] // mesh.size}


def test_bucket_splitting_matches_unsplit(monkeypatch):
    """Capping max entries per bucket chunk must not change results."""
    from predictionio_tpu.models import als as als_mod

    u, i, v, nu, ni = _toy()
    cfg = ALSConfig(rank=4, num_iterations=3, lam=0.1)
    full = train_als((u, i, v), nu, ni, cfg)
    monkeypatch.setattr(als_mod, "MAX_ENTRIES_PER_BUCKET", 64)
    split = train_als((u, i, v), nu, ni, cfg)
    np.testing.assert_allclose(
        split.user_factors, full.user_factors, rtol=1e-5, atol=1e-5
    )


def test_trainer_staged_reuse_matches_fresh():
    """ALSTrainer.run on a staged trainer == fresh train_als."""
    u, i, v, nu, ni = _toy()
    cfg = ALSConfig(rank=4, num_iterations=3, lam=0.1)
    trainer = ALSTrainer((u, i, v), nu, ni, cfg)
    U, V = trainer.init_factors()
    U, V = trainer.run(U, V, 3)
    fresh = train_als((u, i, v), nu, ni, cfg)
    np.testing.assert_allclose(np.asarray(U), fresh.user_factors,
                               rtol=1e-5, atol=1e-5)


def test_trainer_inputs_survive_run():
    """run() must not invalidate the caller's arrays (donation is
    internal): re-running from the same init is the warm-restart
    contract, and sweeping lam must not recompile into wrong results."""
    u, i, v, nu, ni = _toy()
    trainer = ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=4, lam=0.1))
    U0, V0 = trainer.init_factors()
    a, _ = trainer.run(U0, V0, 2)
    b, _ = trainer.run(U0, V0, 2)  # U0/V0 still alive
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    assert np.isfinite(np.asarray(U0)).all()


def test_pallas_solver_matches_xla():
    """solver='pallas' (batch-lane Cholesky kernel) == solver='xla'."""
    u, i, v, nu, ni = _toy()
    base = ALSConfig(rank=8, num_iterations=3, lam=0.1)
    xla = train_als((u, i, v), nu, ni, base)
    pal = train_als(
        (u, i, v), nu, ni,
        ALSConfig(rank=8, num_iterations=3, lam=0.1, solver="pallas"),
    )
    np.testing.assert_allclose(
        pal.user_factors, xla.user_factors, rtol=5e-3, atol=5e-3
    )
    np.testing.assert_allclose(
        pal.item_factors, xla.item_factors, rtol=5e-3, atol=5e-3
    )


def test_lambda_sweep_does_not_recompile():
    """lam/alpha are traced scalars: an eval sweep over regularization
    must reuse the two compiled half-iteration executables."""
    from predictionio_tpu.models import als as als_mod

    u, i, v, nu, ni = _toy()
    train_als((u, i, v), nu, ni, ALSConfig(rank=4, num_iterations=1, lam=0.1))
    size_after_first = als_mod._half_iteration._cache_size()
    for lam in (0.02, 0.5, 1.0):
        train_als((u, i, v), nu, ni,
                  ALSConfig(rank=4, num_iterations=1, lam=lam))
    assert als_mod._half_iteration._cache_size() == size_after_first


def _reference_als_implicit(u, i, v, n_users, n_items, cfg: ALSConfig):
    """Dense NumPy Hu-Koren implicit ALS, identical init: confidence
    c = 1 + alpha*r on observed cells, preference p = 1, full-YtY term for
    the unobserved cells (SURVEY hard part 2: both modes must exist and
    match the MLlib convention)."""
    import jax

    key = jax.random.PRNGKey(cfg.seed)
    ku, ki = jax.random.split(key)
    U = np.asarray(
        jax.random.normal(ku, (n_users, cfg.rank), "float32")
    ) / np.sqrt(cfg.rank)
    V = np.asarray(
        jax.random.normal(ki, (n_items, cfg.rank), "float32")
    ) / np.sqrt(cfg.rank)

    def solve_side(X, Y, rows, cols, vals, n_rows):
        YtY = Y.T @ Y
        for r in range(n_rows):
            sel = rows == r
            n = sel.sum()
            if n == 0:
                continue  # empty rows stay at init, like train_als
            Yr = Y[cols[sel]]
            cw = cfg.alpha * vals[sel]                    # c - 1
            A = YtY + (Yr * cw[:, None]).T @ Yr + cfg.lam * (
                n if cfg.weighted_lambda else 1.0
            ) * np.eye(cfg.rank)
            b = (Yr * (1.0 + cw)[:, None]).sum(axis=0)
            X[r] = np.linalg.solve(A, b)
        return X

    for _ in range(cfg.num_iterations):
        U = solve_side(U, V, u, i, v, n_users)
        V = solve_side(V, U, i, u, v, n_items)
    return ALSFactors(user_factors=U, item_factors=V)


def test_implicit_matches_numpy_reference():
    u, i, v, nu, ni = _toy()
    v = np.abs(v) + 1.0  # implicit counts: positive
    cfg = ALSConfig(rank=4, num_iterations=4, lam=0.1, seed=7,
                    implicit=True, alpha=2.0)
    ours = train_als((u, i, v), nu, ni, cfg)
    ref = _reference_als_implicit(u, i, v, nu, ni, cfg)
    np.testing.assert_allclose(
        ours.user_factors, ref.user_factors, rtol=2e-2, atol=2e-2
    )
    np.testing.assert_allclose(
        ours.item_factors, ref.item_factors, rtol=2e-2, atol=2e-2
    )
    pred_ours = ours.user_factors @ ours.item_factors.T
    pred_ref = ref.user_factors @ ref.item_factors.T
    np.testing.assert_allclose(pred_ours, pred_ref, atol=2e-2)


def test_implicit_single_halfstep_exact():
    u, i, v, nu, ni = _toy(seed=11)
    v = np.abs(v) + 1.0
    cfg = ALSConfig(rank=4, num_iterations=1, lam=0.1, seed=3,
                    implicit=True, alpha=1.0, weighted_lambda=False)
    ours = train_als((u, i, v), nu, ni, cfg)
    ref = _reference_als_implicit(u, i, v, nu, ni, cfg)
    np.testing.assert_allclose(
        ours.user_factors, ref.user_factors, rtol=3e-4, atol=3e-4
    )
    np.testing.assert_allclose(
        ours.item_factors, ref.item_factors, rtol=3e-4, atol=3e-4
    )


def test_grouped_gather_exactly_matches_row_gather():
    """gather_mode='grouped' (tile-aligned slab gather + in-slab select)
    fetches the SAME rows through a different memory access pattern —
    factors must match the row-gather path bitwise-closely in every
    mode combination."""
    u, i, v, nu, ni = _toy(density=0.5)
    for extra in (
        {},                                          # explicit
        {"implicit": True, "alpha": 2.0},            # implicit branch
    ):
        vals = np.abs(v) + 1.0 if extra.get("implicit") else v
        base = dict(rank=6, num_iterations=4, lam=0.05, seed=2, **extra)
        row = train_als((u, i, vals), nu, ni, ALSConfig(**base))
        grp = train_als((u, i, vals), nu, ni,
                        ALSConfig(**base, gather_mode="grouped"))
        np.testing.assert_allclose(
            grp.user_factors, row.user_factors, rtol=1e-5, atol=1e-5,
            err_msg=f"mode combo {extra}",
        )
        np.testing.assert_allclose(
            grp.item_factors, row.item_factors, rtol=1e-5, atol=1e-5,
            err_msg=f"mode combo {extra}",
        )


def test_grouped_gather_table_smaller_than_group():
    """Opposite tables shorter than one slab (M < G) exercise the pad
    path; ids must still resolve to the right rows."""
    u, i, v, nu, ni = _toy(n_users=9, n_items=5, density=0.9)
    base = dict(rank=4, num_iterations=3, lam=0.1, seed=0)
    row = train_als((u, i, v), nu, ni, ALSConfig(**base))
    grp = train_als((u, i, v), nu, ni,
                    ALSConfig(**base, gather_mode="grouped"))
    np.testing.assert_allclose(
        grp.user_factors, row.user_factors, rtol=1e-5, atol=1e-5
    )


def test_grouped_gather_chunked_matches_unchunked(monkeypatch):
    """A slab budget small enough to force many row-chunks must not
    change the result (the [chunk, K, G*R] intermediate is bounded by
    _GROUPED_SLAB_BYTES at full scale)."""
    import predictionio_tpu.models.als as als_mod

    import jax

    u, i, v, nu, ni = _toy(density=0.5)
    base = dict(rank=6, num_iterations=3, lam=0.05, seed=2,
                gather_mode="grouped")
    whole = train_als((u, i, v), nu, ni, ALSConfig(**base))
    monkeypatch.setattr(als_mod, "_GROUPED_SLAB_BYTES", 4096)
    # the slab budget is read at TRACE time; identical shapes + static
    # args would hit the jit cache and silently re-run the unchunked
    # executable — drop the caches so the chunked branch really traces,
    # and again afterwards so no later test inherits the tiny-chunk
    # executable under the production cache key
    jax.clear_caches()
    try:
        chunked = train_als((u, i, v), nu, ni, ALSConfig(**base))
    finally:
        jax.clear_caches()
    np.testing.assert_allclose(
        chunked.user_factors, whole.user_factors, rtol=1e-6, atol=1e-6
    )


def test_grouped_gather_sharded_matches_replicated():
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy()
    cfg = ALSConfig(rank=4, num_iterations=3, lam=0.1,
                    gather_mode="grouped", factor_placement="sharded")
    mesh = make_mesh()
    sharded = train_als((u, i, v), nu, ni, cfg, mesh=mesh)
    single = train_als((u, i, v), nu, ni,
                       ALSConfig(rank=4, num_iterations=3, lam=0.1))
    np.testing.assert_allclose(
        sharded.user_factors, single.user_factors, rtol=2e-4, atol=2e-4
    )


# every combination of the path selectors but the baseline itself: the
# explicit lattice, the implicit form's extreme corner, and the implicit
# kernel on the baseline's other selectors (implicit xla runs sharded
# and grouped in their own tests above)
_KNOB_LATTICE = [
    (False, solver, mode, placement)
    for solver in ("xla", "pallas")
    for mode in ("row", "grouped")
    for placement in ("replicated", "sharded")
    if (solver, mode, placement) != ("xla", "row", "replicated")
] + [(True, "pallas", "grouped", "sharded"),
     (True, "pallas", "row", "replicated")]


@functools.lru_cache(maxsize=None)
def _knob_lattice_reference(implicit: bool) -> tuple:
    """(data, config, predictions) of the plain baseline (row/xla
    /replicated), trained once for each ``implicit``."""
    u, i, v, nu, ni = _toy(density=0.5, seed=11)
    vals = np.abs(v) + 1.0 if implicit else v
    base_kw = dict(rank=4, num_iterations=2, lam=0.1, seed=5,
                   implicit=implicit, **({"alpha": 2.0} if implicit else {}))
    ref = train_als((u, i, vals), nu, ni, ALSConfig(**base_kw))
    return (u, i, vals, nu, ni), base_kw, ref.user_factors @ ref.item_factors.T


@pytest.mark.parametrize(
    "implicit,solver,mode,placement", _KNOB_LATTICE,
    ids=["-".join((["implicit"] if c[0] else []) + list(c[1:]))
         for c in _KNOB_LATTICE])
def test_knob_lattice_consistency(implicit, solver, mode, placement):
    """Every valid combination of the perf knobs must train to the same
    PREDICTIONS as the plain baseline (row/xla/replicated).

    Single-knob A/B tests miss interaction bugs (e.g. grouped x sharded
    x pallas); an interaction bug produces garbage, not epsilon drift,
    so the bound is deliberately looser than the dedicated single-knob
    tests' (and holds on REAL TPU kernels, not just the near-exact
    interpret mode CPU runs them in — kernel f32 needs ~5e-3 at factor
    level: tests/test_als.py pallas bound).  Implicit mode adds its
    extreme corner and its kernel alone: the knob plumbing is
    implicit-agnostic."""
    from predictionio_tpu.parallel import make_mesh

    (u, i, vals, nu, ni), base_kw, pred_ref = _knob_lattice_reference(
        implicit)
    cfg = ALSConfig(**base_kw, solver=solver, gather_mode=mode,
                    factor_placement=placement)
    got = train_als(
        (u, i, vals), nu, ni, cfg,
        mesh=make_mesh() if placement == "sharded" else None,
    )
    assert np.isfinite(got.user_factors).all()
    assert np.isfinite(got.item_factors).all()
    np.testing.assert_allclose(
        got.user_factors @ got.item_factors.T, pred_ref, atol=2e-2)


def test_device_staging_matches_host_staging():
    """staging="device" (compact transfer + on-device sort) must train to
    the same factors as the host counting-sort path, including on a mesh
    and with half-star ratings that take the uint8 encode path."""
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy(n_users=40, n_items=30, density=0.5)
    v = (np.round(np.clip(np.abs(v), 0.5, 5.0) * 2) / 2).astype(np.float32)
    cfg = ALSConfig(rank=4, num_iterations=3, lam=0.1)

    host = ALSTrainer((u, i, v), nu, ni, cfg, staging="host")
    dev = ALSTrainer((u, i, v), nu, ni, cfg, staging="device")
    hU, hV = host.run(*host.init_factors(), cfg.num_iterations)
    dU, dV = dev.run(*dev.init_factors(), cfg.num_iterations)
    np.testing.assert_allclose(np.asarray(hU), np.asarray(dU),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hV), np.asarray(dV),
                               rtol=1e-4, atol=1e-5)

    mesh = make_mesh()
    host_m = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh, staging="host")
    dev_m = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh, staging="device")
    hUm, _ = host_m.run(*host_m.init_factors(), cfg.num_iterations)
    dUm, _ = dev_m.run(*dev_m.init_factors(), cfg.num_iterations)
    np.testing.assert_allclose(np.asarray(hUm), np.asarray(dUm),
                               rtol=1e-4, atol=1e-5)


def test_device_staging_non_halfstar_values():
    """Arbitrary float ratings must skip the uint8 encode and still match."""
    u, i, v, nu, ni = _toy(seed=3)
    cfg = ALSConfig(rank=3, num_iterations=2, lam=0.2)
    host = ALSTrainer((u, i, v), nu, ni, cfg, staging="host")
    dev = ALSTrainer((u, i, v), nu, ni, cfg, staging="device")
    hU, hV = host.run(*host.init_factors(), cfg.num_iterations)
    dU, dV = dev.run(*dev.init_factors(), cfg.num_iterations)
    np.testing.assert_allclose(np.asarray(hU), np.asarray(dU),
                               rtol=1e-4, atol=1e-5)


def test_device_staging_sharded_placement():
    """Device staging composes with ALX-style sharded factor tables."""
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy(n_users=32, n_items=24, density=0.5, seed=1)
    cfg = ALSConfig(rank=4, num_iterations=2, lam=0.1,
                    factor_placement="sharded")
    mesh = make_mesh()
    sh = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh, staging="device")
    rep = ALSTrainer((u, i, v), nu, ni,
                     ALSConfig(rank=4, num_iterations=2, lam=0.1),
                     staging="host")
    sU, _ = sh.run(*sh.init_factors(), cfg.num_iterations)
    rU, _ = rep.run(*rep.init_factors(), cfg.num_iterations)
    np.testing.assert_allclose(np.asarray(sU)[:nu], np.asarray(rU),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("gather_mode", ["row", "grouped"])
def test_sweep_train_matches_independent_trains(gather_mode):
    """vmapped lambda sweep == K independent trains, staging paid once
    — including under the grouped slab gather (the vmap must batch the
    3D tile-slab take correctly)."""
    from predictionio_tpu.models.als import sweep_train_als

    u, i, v, nu, ni = _toy(n_users=25, n_items=15, density=0.5)
    lams = [0.01, 0.1, 1.0]
    cfg = ALSConfig(rank=4, num_iterations=4, lam=-1.0,  # lam overridden
                    gather_mode=gather_mode)
    swept = sweep_train_als((u, i, v), nu, ni, cfg, lams=lams)
    assert len(swept) == 3
    for lam, got in zip(lams, swept):
        solo = train_als((u, i, v), nu, ni,
                         ALSConfig(rank=4, num_iterations=4, lam=lam))
        np.testing.assert_allclose(got.user_factors, solo.user_factors,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(got.item_factors, solo.item_factors,
                                   rtol=2e-4, atol=2e-5)
    # distinct lambdas must yield distinct models
    assert not np.allclose(swept[0].user_factors, swept[2].user_factors)


def test_sweep_train_rejects_unsupported_modes():
    from predictionio_tpu.models.als import sweep_train_als

    u, i, v, nu, ni = _toy()
    # the VMAPPED form needs the XLA solver (Pallas grids don't batch
    # under vmap); sharded placement is no longer rejected — it sweeps
    # sequentially over one staged trainer (see
    # test_sweep_sharded_sequential_matches_vmapped)
    with pytest.raises(ValueError, match="solver"):
        sweep_train_als((u, i, v), nu, ni,
                        ALSConfig(solver="pallas"), lams=[0.1])
    assert sweep_train_als((u, i, v), nu, ni, ALSConfig(), lams=[]) == []


def test_sweep_train_implicit_mode():
    from predictionio_tpu.models.als import sweep_train_als

    u, i, v, nu, ni = _toy(seed=2)
    v = np.abs(v) + 1.0
    cfg = ALSConfig(rank=3, num_iterations=3, implicit=True, alpha=2.0)
    swept = sweep_train_als((u, i, v), nu, ni, cfg, lams=[0.05, 0.5])
    solo = train_als((u, i, v), nu, ni,
                     ALSConfig(rank=3, num_iterations=3, implicit=True,
                               alpha=2.0, lam=0.5))
    np.testing.assert_allclose(swept[1].user_factors, solo.user_factors,
                               rtol=2e-4, atol=2e-5)


def test_sharded_coo_is_actually_sharded():
    """factor_placement='sharded' must shard the RATINGS too (round-3
    verdict item 3): each device holds the padded blocks of the bucket
    rows it solves, ~1/d of the total rating bytes and not a full
    replica — the property that lets nnz scale with mesh HBM.  The
    blocks are what is staged: the shard-local columns they were
    expanded from are gone."""
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy(n_users=200, n_items=80, density=0.3, seed=9)
    mesh = make_mesh()
    assert mesh.size == 8
    cfg = ALSConfig(rank=4, num_iterations=1, factor_placement="sharded")
    tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh)
    assert tr.staging == "sharded"
    nnz = len(v)
    for side in (tr._user_side, tr._item_side):
        assert "c_sorted" not in side and "v_sorted" not in side
        # the columns' padded shard length: close to nnz / 8, not nnz
        L = side["shard_len"]
        assert 8 * L < 1.5 * nnz, (8 * L, nnz)
        assert L < 0.3 * nnz  # one shard is nowhere near a full replica
        held = np.zeros(8, np.int64)
        for (rows, idx, val, counts), k in zip(side["buckets"], side["ks"]):
            n, b = rows.shape
            assert idx.shape == val.shape == (n, b, k)
            assert idx.dtype == jnp.int32 and val.dtype == jnp.float32
            for block in (idx, val):
                shards = block.addressable_shards
                assert len(shards) == 8
                # every device holds its own [n, B/8] rows of the block
                assert {s.data.shape for s in shards} == {(n, b // 8, k)}
                assert sorted(s.index[1].start or 0 for s in shards) \
                    == [d * (b // 8) for d in range(8)]
            for d, s in enumerate(idx.addressable_shards):
                held[d] += s.data.size
        # the devices' blocks add up to the padded entries, not to 8x
        # them, and no device holds more than its eighth
        assert held.sum() == side["padded_entries"]
        assert set(held) == {side["padded_entries"] // 8}
        assert side["padded_bytes"] == 8 * side["padded_entries"]


def test_sharded_coo_slices_land_on_owning_device():
    """Device d's shard must contain exactly the rating values of the
    bucket rows in its chunks (co-partitioning, not just equal split)."""
    from predictionio_tpu.models.als import _plan_shard_layout
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy(n_users=64, n_items=40, seed=3)
    mesh = make_mesh()
    n_dev = mesh.size
    layout = build_bucket_layout(u, i, v, nu, min_k=4,
                                 batch_multiple=n_dev,
                                 starts_dtype=np.int64)
    perm, local_starts, L = _plan_shard_layout(layout.buckets, n_dev)
    # reconstruct every row's ratings from its owning shard and compare
    # against the global row-grouped layout
    counts = np.bincount(u, minlength=nu)
    for b, ls in zip(layout.buckets, local_starts):
        chunk = len(b.rows) // n_dev
        for j, row in enumerate(b.rows):
            if row >= nu:
                continue
            d = j // chunk
            got = layout.val_sorted[perm[d, ls[j]: ls[j] + b.counts[j]]]
            lo = int(np.sum(counts[:row]))
            want = layout.val_sorted[lo: lo + b.counts[j]]
            np.testing.assert_array_equal(got, want)


def test_shard_plan_supports_beyond_int32_nnz():
    """Plan-level smoke past the 2^31 rating ceiling: with the COO
    sharded, only PER-SHARD offsets must fit int32.  Uses synthetic
    per-row counts (no 17 GB array allocation) summing to >2^31."""
    from predictionio_tpu.models.als import (
        _assemble_buckets, _plan_shard_layout,
    )

    n_rows, per_row = 600_000, 4096
    counts = np.full(n_rows, per_row, dtype=np.int64)
    total = int(counts.sum())
    assert total > np.iinfo(np.int32).max  # 2.46e9 > 2^31
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    buckets = _assemble_buckets(
        counts.astype(np.int64), starts, n_rows, min_k=8,
        batch_multiple=8, starts_dtype=np.int64,
    )
    # planning-only (build_perm=False): the full perm would be ~17 GB —
    # exactly the thing only the per-device slices of ever exist at once
    # in a real sharded run; perm correctness itself is covered at small
    # scale by test_sharded_coo_slices_land_on_owning_device
    perm, local_starts, L = _plan_shard_layout(buckets, 8, build_perm=False)
    assert perm is None
    assert L < np.iinfo(np.int32).max          # per-shard fits int32
    assert 8 * L >= total                      # plan covers every rating
    for ls in local_starts:
        assert ls.dtype == np.int32
        assert int(ls.max()) < L


def test_replicated_layout_still_guards_int32():
    """The replicated path's int32 ceiling must still raise, and point at
    the sharded path."""
    with pytest.raises(ValueError, match="sharded"):
        build_bucket_layout(
            np.zeros(0, np.int32), np.zeros(0, np.int32),
            _FakeLen(np.iinfo(np.int32).max), 1,
        )


class _FakeLen:
    """Stands in for a >2^31-element value array (len() only — the guard
    fires before any element access)."""

    def __init__(self, n):
        self._n = n

    def __len__(self):
        return self._n


def test_sweep_sharded_sequential_matches_vmapped():
    """Sharded-placement sweeps reuse one staged trainer sequentially and
    must produce the same per-candidate factors as the vmapped sweep
    (composability of the sweep with the sharded-COO scaling story)."""
    from predictionio_tpu.models.als import sweep_train_als
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy(n_users=32, n_items=24)
    mesh = make_mesh()
    lams = (0.05, 0.5)
    base = dict(rank=4, num_iterations=2)
    vm = sweep_train_als((u, i, v), nu, ni, ALSConfig(**base), lams=lams)
    sh = sweep_train_als(
        (u, i, v), nu, ni,
        ALSConfig(factor_placement="sharded", **base),
        lams=lams, mesh=mesh,
    )
    assert len(vm) == len(sh) == 2
    for a, b in zip(vm, sh):
        np.testing.assert_allclose(
            a.user_factors, b.user_factors, rtol=1e-4, atol=1e-4
        )


def test_config_rejects_typo_knob_values():
    """engine.json-reachable knobs must fail loudly, not silently run
    the default path (the use sites test exact equality)."""
    with pytest.raises(ValueError, match="solver"):
        ALSConfig(solver="Fused")
    # the fused gather+Gram+solve kernel is gone: its name is refused
    # like any other unknown solver
    with pytest.raises(ValueError, match="solver"):
        ALSConfig(solver="fused")
    with pytest.raises(ValueError, match="factor_placement"):
        ALSConfig(factor_placement="Sharded")
    with pytest.raises(ValueError, match="gather_mode"):
        ALSConfig(gather_mode="tiled")


def test_device_expand_sides_reconstruction():
    """`_device_expand_sides` contract: the row side IS the transfer
    order, row ids are rebuilt on device from counts alone (the row-id
    column is never transferred), and the opposite side's per-row
    (row, value) multisets match a host reference grouping."""
    from predictionio_tpu.models.als import _device_expand_sides
    from predictionio_tpu.native import sort_coo_by_row

    rng = np.random.default_rng(11)
    nu, ni, nnz = 17, 13, 300
    u = rng.integers(0, nu, nnz).astype(np.int32)
    i = rng.integers(0, ni, nnz).astype(np.int32)
    v = (rng.integers(1, 11, nnz) * 0.5).astype(np.float32)
    i_by_u, v_by_u, counts, starts = sort_coo_by_row(u, i, v, nu)

    cs_u, vs_u, cs_i, vs_i = _device_expand_sides(
        jnp.asarray(i_by_u.astype(np.uint16)),
        jnp.asarray((v_by_u * 2).astype(np.uint8)),
        jnp.asarray(np.asarray(counts, np.int32)),
        jnp.asarray(0.5, jnp.float32),
    )
    # user side: exactly the transfer order, decoded
    np.testing.assert_array_equal(np.asarray(cs_u), i_by_u)
    np.testing.assert_allclose(np.asarray(vs_u), v_by_u)
    # item side: grouped by item; each item's (user, value) multiset
    # matches the original COO
    cs_i, vs_i = np.asarray(cs_i), np.asarray(vs_i)
    ci2, vi2, counts_i, starts_i = sort_coo_by_row(i, u, v, ni)
    pos = 0
    for r in range(ni):
        n = int(counts_i[r])
        got = sorted(zip(cs_i[pos:pos + n].tolist(),
                         vs_i[pos:pos + n].tolist()))
        want = sorted(zip(ci2[starts_i[r]:starts_i[r] + n].tolist(),
                          vi2[starts_i[r]:starts_i[r] + n].tolist()))
        assert got == want, f"item {r}"
        pos += n


@pytest.mark.parametrize("extra", [
    dict(solver="pallas"),
    dict(solver="pallas", solver_mode="subspace", subspace_size=2),
])
def test_kernel_solvers_run_per_device_on_a_replicated_mesh(
        extra, monkeypatch):
    """Replicated placement on a multi-device mesh hands each Pallas
    kernel its own data shard through `shard_map`: XLA partitions the
    rest of the half-iteration from the input shardings but refuses to
    partition a Mosaic kernel ("Mosaic kernels cannot be automatically
    partitioned", the 2x2 v5e host, PR 21 — interpret mode on the CPU
    never shows it).  The sharded path is already inside a `shard_map`
    body and must not nest another."""
    from predictionio_tpu.models import als as als_mod
    from predictionio_tpu.parallel import make_mesh

    mesh = make_mesh()
    assert mesh.size == 8
    seen = []
    real = als_mod._per_device

    def spy(fn, m, in_specs, out_specs):
        seen.append(m)
        return real(fn, m, in_specs, out_specs)

    monkeypatch.setattr(als_mod, "_per_device", spy)
    u, i, v, nu, ni = _toy(density=0.5, seed=21)
    kw = dict(rank=4, num_iterations=2, lam=0.1, seed=5)
    mode = {k: x for k, x in extra.items() if k != "solver"}
    ref = train_als((u, i, v), nu, ni, ALSConfig(**kw, **mode), mesh=mesh)
    assert seen == []  # the xla solver has no kernel to place
    got = train_als((u, i, v), nu, ni, ALSConfig(**kw, **extra), mesh=mesh)
    assert seen and all(m is mesh for m in seen)
    np.testing.assert_allclose(
        got.user_factors, ref.user_factors, rtol=2e-4, atol=2e-4
    )
    seen.clear()
    train_als(
        (u, i, v), nu, ni,
        ALSConfig(**kw, **extra, factor_placement="sharded"), mesh=mesh,
    )
    assert seen and all(m is None for m in seen)
