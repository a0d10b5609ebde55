"""iALS++ subspace-blocked ALS solver (``ALSConfig.solver_mode``).

Contracts under test (ISSUE 2 acceptance criteria):

* ``subspace_size >= rank`` routes through the EXACT full-solve code
  path — bitwise-identical factors, not merely close;
* one block sweep matches an independent NumPy reference row-by-row,
  including the tail block when R is not divisible by B (explicit AND
  implicit caches);
* quality parity: at equal iteration count the subspace train reaches
  full-solve train RMSE within 1% on the small synthetic harness;
* the mode composes with the existing machinery: Pallas GJ solves,
  sharded (ALX-style) placement, the vmapped λ sweep, and the engine
  params of the recommendation-family templates.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models.als import (
    ALSConfig,
    ALSTrainer,
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


def _toy_implicit(n_users=30, n_items=20, density=0.3, seed=1):
    """Non-negative counts: implicit confidence c = 1 + α·r needs r >= 0."""
    rng = np.random.default_rng(seed)
    u, i = np.nonzero(rng.random((n_users, n_items)) < density)
    v = rng.integers(1, 6, size=len(u)).astype(np.float32)
    return u.astype(np.int32), i.astype(np.int32), v, n_users, n_items


# --------------------------------------------------------------------------
# NumPy reference: one subspace half-iteration, row by row
# --------------------------------------------------------------------------


def _np_subspace_half_explicit(X, Y, u, i, v, lam, block, weighted=True):
    """Block Newton sweep on the ALS-WR per-row objective (float64)."""
    out = X.astype(np.float64).copy()
    Yd = Y.astype(np.float64)
    for r_ in range(X.shape[0]):
        sel = u == r_
        k = int(sel.sum())
        if k == 0:
            continue
        Yr = Yd[i[sel]]
        rv = v[sel].astype(np.float64)
        x = out[r_].copy()
        reg = lam * max(k, 1) if weighted else lam
        e = Yr @ x - rv
        R = Y.shape[1]
        for s in range(0, R, block):
            w = min(block, R - s)
            Vb = Yr[:, s:s + w]
            H = Vb.T @ Vb + reg * np.eye(w)
            g = Vb.T @ e + reg * x[s:s + w]
            d = -np.linalg.solve(H, g)
            x[s:s + w] += d
            e += Vb @ d
        out[r_] = x
    return out


def _np_subspace_half_implicit(X, Y, u, i, v, lam, alpha, block,
                               weighted=True):
    """Implicit (HKV) block sweep with prediction + YtY·x caches."""
    out = X.astype(np.float64).copy()
    Yd = Y.astype(np.float64)
    gram = Yd.T @ Yd
    for r_ in range(X.shape[0]):
        sel = u == r_
        k = int(sel.sum())
        if k == 0:
            continue
        Yr = Yd[i[sel]]
        cw = alpha * v[sel].astype(np.float64)   # c - 1
        x = out[r_].copy()
        reg = lam * max(k, 1) if weighted else lam
        p = Yr @ x
        q = gram @ x
        R = Y.shape[1]
        for s in range(0, R, block):
            w = min(block, R - s)
            Vb = Yr[:, s:s + w]
            H = gram[s:s + w, s:s + w] + Vb.T @ (cw[:, None] * Vb) \
                + reg * np.eye(w)
            g = q[s:s + w] + Vb.T @ (cw * p - (1.0 + cw)) \
                + reg * x[s:s + w]
            d = -np.linalg.solve(H, g)
            x[s:s + w] += d
            p += Vb @ d
            q += gram[:, s:s + w] @ d
        out[r_] = x
    return out


def _one_user_half(cfg, u, i, v, nu, ni):
    """Run exactly one device user-half and return (U0, V0, U1)."""
    tr = ALSTrainer((u, i, v), nu, ni, cfg)
    U0, V0 = tr.init_factors()
    U0n, V0n = np.asarray(U0), np.asarray(V0)
    U1 = np.asarray(tr._half(jnp.array(U0, copy=True), V0, tr._user_side))
    return U0n, V0n, U1


@pytest.mark.parametrize("rank,block", [(8, 4), (10, 4), (6, 5), (12, 1)])
def test_block_sweep_matches_numpy_explicit(rank, block):
    """One half-iteration vs the row-by-row NumPy sweep, covering tail
    blocks (10 % 4 -> widths 4,4,2; 6 % 5 -> 5,1) and B=1."""
    u, i, v, nu, ni = _toy()
    cfg = ALSConfig(rank=rank, num_iterations=1, lam=0.1,
                    solver_mode="subspace", subspace_size=block)
    U0, V0, U1 = _one_user_half(cfg, u, i, v, nu, ni)
    ref = _np_subspace_half_explicit(U0, V0, u, i, v, 0.1, block)
    np.testing.assert_allclose(U1, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rank,block", [(8, 4), (10, 4)])
def test_block_sweep_matches_numpy_implicit(rank, block):
    u, i, v, nu, ni = _toy_implicit()
    cfg = ALSConfig(rank=rank, num_iterations=1, lam=0.1, implicit=True,
                    alpha=2.0, solver_mode="subspace", subspace_size=block)
    U0, V0, U1 = _one_user_half(cfg, u, i, v, nu, ni)
    ref = _np_subspace_half_implicit(U0, V0, u, i, v, 0.1, 2.0, block)
    np.testing.assert_allclose(U1, ref, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("size", [8, 16, 999])
def test_b_equals_r_degenerates_bitwise(size):
    """subspace_size >= rank must take the full-solve branch verbatim:
    bitwise-equal factors, not allclose."""
    u, i, v, nu, ni = _toy()
    full = train_als((u, i, v), nu, ni,
                     ALSConfig(rank=8, num_iterations=6, lam=0.05))
    deg = train_als((u, i, v), nu, ni,
                    ALSConfig(rank=8, num_iterations=6, lam=0.05,
                              solver_mode="subspace", subspace_size=size))
    assert np.array_equal(full.user_factors, deg.user_factors)
    assert np.array_equal(full.item_factors, deg.item_factors)


def test_quality_parity_within_1pct():
    """Acceptance: subspace reaches full-solve train RMSE within 1% on
    the small synthetic harness.  Per-iteration the block sweep makes
    slightly less progress than the full solve (it is one coordinate-
    descent pass); by convergence the gap closes — measured here at 30
    iterations where the ratio is ~1.002 (the per-iteration cost is
    R/B-fold lower, so equal-iteration parity is the conservative
    comparison for the wall-clock claim)."""
    u, i, v, nu, ni = _toy(n_users=60, n_items=40, rank_true=4,
                           density=0.35, seed=3)
    full = train_als((u, i, v), nu, ni,
                     ALSConfig(rank=16, num_iterations=30, lam=0.05))
    sub = train_als((u, i, v), nu, ni,
                    ALSConfig(rank=16, num_iterations=30, lam=0.05,
                              solver_mode="subspace", subspace_size=8))
    r_full = rmse(full, u, i, v)
    r_sub = rmse(sub, u, i, v)
    assert np.isfinite(r_sub)
    assert r_sub <= r_full * 1.01, (r_sub, r_full)


def test_quality_parity_implicit():
    """Implicit mode: the bilinear objective is non-convex, so block CD
    and full ALS may converge to different stationary points — parity
    is judged on the HKV objective value, not factor closeness."""
    u, i, v, nu, ni = _toy_implicit(n_users=50, n_items=30)
    alpha, lam = 2.0, 0.1

    def hkv_loss(f):
        P = np.zeros((nu, ni))
        C = np.ones((nu, ni))
        P[u, i] = 1.0
        C[u, i] = 1.0 + alpha * v
        pred = f.user_factors @ f.item_factors.T
        counts_u = np.bincount(u, minlength=nu)
        counts_i = np.bincount(i, minlength=ni)
        reg = lam * (
            (counts_u * (f.user_factors ** 2).sum(1)).sum()
            + (counts_i * (f.item_factors ** 2).sum(1)).sum()
        )
        return float((C * (pred - P) ** 2).sum() + reg)

    kw = dict(rank=8, num_iterations=30, lam=lam, implicit=True,
              alpha=alpha)
    full = train_als((u, i, v), nu, ni, ALSConfig(**kw))
    sub = train_als((u, i, v), nu, ni,
                    ALSConfig(solver_mode="subspace", subspace_size=4,
                              **kw))
    lf, ls = hkv_loss(full), hkv_loss(sub)
    assert np.isfinite(ls)
    assert ls <= lf * 1.05, (ls, lf)


def test_pallas_solver_composes():
    """solver='pallas' routes the B×B subsystems through the GJ kernel
    (interpret mode on CPU); results match the XLA subspace path."""
    u, i, v, nu, ni = _toy()
    kw = dict(rank=8, num_iterations=3, lam=0.05,
              solver_mode="subspace", subspace_size=4)
    xla = train_als((u, i, v), nu, ni, ALSConfig(solver="xla", **kw))
    pal = train_als((u, i, v), nu, ni, ALSConfig(solver="pallas", **kw))
    np.testing.assert_allclose(
        xla.user_factors, pal.user_factors, rtol=2e-3, atol=2e-3
    )


def test_sharded_subspace_matches_replicated():
    """The ALX-style block-sharded half (which all-gathers the updating
    table for the warm start) matches the replicated subspace result."""
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy(n_users=32, n_items=24)
    mesh = make_mesh()  # 8 virtual CPU devices from conftest
    cfg = dict(rank=8, num_iterations=4, lam=0.05,
               solver_mode="subspace", subspace_size=4)
    rep = train_als((u, i, v), nu, ni, ALSConfig(**cfg))
    sh = train_als((u, i, v), nu, ni,
                   ALSConfig(factor_placement="sharded", **cfg),
                   mesh=mesh)
    np.testing.assert_allclose(
        rep.user_factors, sh.user_factors, rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        rep.item_factors, sh.item_factors, rtol=1e-4, atol=1e-4
    )


def test_vmapped_lambda_sweep_composes():
    from predictionio_tpu.models.als import sweep_train_als

    u, i, v, nu, ni = _toy()
    cfg = ALSConfig(rank=8, num_iterations=3, lam=0.05,
                    solver_mode="subspace", subspace_size=4)
    out = sweep_train_als((u, i, v), nu, ni, cfg, lams=[0.01, 0.1])
    assert len(out) == 2
    # the sweep's per-candidate result equals a single train at that λ
    import dataclasses

    single = train_als((u, i, v), nu, ni,
                       dataclasses.replace(cfg, lam=0.1))
    np.testing.assert_allclose(
        out[1].user_factors, single.user_factors, rtol=1e-4, atol=1e-4
    )


def test_config_validation():
    with pytest.raises(ValueError, match="solver_mode"):
        ALSConfig(solver_mode="blocked")
    with pytest.raises(ValueError, match="subspace_size"):
        ALSConfig(solver_mode="subspace", subspace_size=0)
    # the sweep's block systems go to whichever solver is named
    with pytest.raises(ValueError, match="solver"):
        ALSConfig(solver_mode="subspace", solver="fused")
    assert ALSConfig(solver_mode="subspace", solver="pallas").solver == "pallas"
    # default preserves today's behavior
    assert ALSConfig().solver_mode == "full"


def test_template_engine_params_thread_through():
    """engine.json solverMode/subspaceSize reach the ALSConfig of every
    recommendation-family template."""
    from predictionio_tpu.controller.params import extract_params
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSAlgorithmParams,
    )

    p = extract_params(
        ALSAlgorithmParams,
        {"rank": 8, "solverMode": "subspace", "subspaceSize": 4},
    )
    assert p.solver_mode == "subspace" and p.subspace_size == 4
    algo = ALSAlgorithm.__new__(ALSAlgorithm)
    algo.params = p
    cfg = algo._config()
    assert cfg.solver_mode == "subspace" and cfg.subspace_size == 4

    from predictionio_tpu.templates.ecommerce import ECommAlgorithmParams
    from predictionio_tpu.templates.similarproduct import SimilarALSParams

    for cls in (SimilarALSParams, ECommAlgorithmParams):
        q = extract_params(cls, {"solverMode": "subspace",
                                 "subspaceSize": 8})
        assert q.solver_mode == "subspace" and q.subspace_size == 8


@pytest.mark.slow
def test_subspace_wall_clock_benchmark():
    """Bench-scale wall-clock sanity: rank-64 subspace iterations are
    not slower than full-solve ones.  slow-marked — tier-1's 870 s
    budget excludes it; the recorded acceptance measurement is the
    bench_solver.py / bench.py JSON lines, not this test."""
    import time

    rng = np.random.default_rng(0)
    nu, ni, nnz = 4096, 1024, 400_000
    u = rng.integers(0, nu, size=nnz).astype(np.int32)
    i = rng.integers(0, ni, size=nnz).astype(np.int32)
    v = (rng.integers(1, 11, size=nnz) * 0.5).astype(np.float32)

    def timed(cfg):
        tr = ALSTrainer((u, i, v), nu, ni, cfg)
        U, V = tr.init_factors()
        U, V = tr.run(U, V, 1)          # compile warmup
        t0 = time.perf_counter()
        tr.run(U, V, 3)
        return time.perf_counter() - t0

    t_full = timed(ALSConfig(rank=64, num_iterations=1, lam=0.05))
    t_sub = timed(ALSConfig(rank=64, num_iterations=1, lam=0.05,
                            solver_mode="subspace", subspace_size=16))
    # lenient bound: CI machines are noisy; the claim is "not slower"
    assert t_sub < t_full * 1.2, (t_sub, t_full)


def test_gram_probe_runs_for_subspace():
    """bench.py --phase-probe's stop_after='gram' hook must trace for
    the new mode (it drives the observable gather/Gram/solve split)."""
    import functools

    import jax

    from predictionio_tpu.models.als import _solve_buckets

    u, i, v, nu, ni = _toy()
    cfg = ALSConfig(rank=8, num_iterations=1, lam=0.1,
                    solver_mode="subspace", subspace_size=4)
    tr = ALSTrainer((u, i, v), nu, ni, cfg)
    U0, V0 = tr.init_factors()
    side = tr._user_side

    @functools.partial(jax.jit, static_argnames=("ks", "stop_after"))
    def probe(upd, opp, buckets, lam, alpha, *, ks, stop_after):
        return _solve_buckets(
            None, opp, buckets, lam, alpha,
            ks=ks, implicit=False, weighted_lambda=True,
            precision="highest", solver="xla",
            solver_mode="subspace", subspace_size=4, upd_table=upd,
            stop_after=stop_after,
        )

    lam = jnp.asarray(0.1, jnp.float32)
    alpha = jnp.asarray(1.0, jnp.float32)
    for stop in ("gather", "gram"):
        out = probe(U0, V0, side["buckets"], lam, alpha, ks=side["ks"],
                    stop_after=stop)
        assert np.isfinite(float(out))


# --------------------------------------------------------------------------
# PR 36: the blocks as ONE loop, chunks bounded by the bytes they gather
# and looped, against the benchmark's plain reference
# --------------------------------------------------------------------------


def _unrolled_sweep(Vm, val, maskf, x0, reg, cw, gram, prec, solver, block):
    """The block sweep as it stood before the loop (one Python iteration a
    block, static slices): what the looped sweep has to give bit for bit."""
    import jax

    from predictionio_tpu.models.als import _spd_solve

    f32 = jnp.float32
    r = Vm.shape[-1]
    pred = jnp.einsum("bkr,br->bk", Vm, x0.astype(Vm.dtype),
                      precision=prec, preferred_element_type=f32)
    e = q = None
    if cw is None:
        e = pred - val
    else:
        q = jnp.einsum("bs,sr->br", x0, gram, precision=prec)
    for s in range(0, r, block):
        w = min(block, r - s)
        Vs = jax.lax.slice_in_dim(Vm, s, s + w, axis=2)
        xs = jax.lax.slice_in_dim(x0, s, s + w, axis=1)
        if cw is None:
            H = jnp.einsum("bks,bkt->bst", Vs, Vs, precision=prec,
                           preferred_element_type=f32)
            g = jnp.einsum("bk,bks->bs", e.astype(Vs.dtype), Vs,
                           precision=prec, preferred_element_type=f32)
        else:
            H = gram[s:s + w, s:s + w] + jnp.einsum(
                "bk,bks,bkt->bst", cw.astype(Vs.dtype), Vs, Vs,
                precision=prec, preferred_element_type=f32)
            coef = cw * pred - maskf - cw
            g = q[:, s:s + w] + jnp.einsum(
                "bk,bks->bs", coef.astype(Vs.dtype), Vs,
                precision=prec, preferred_element_type=f32)
        H = H + reg[:, None, None] * jnp.eye(w, dtype=H.dtype)
        g = g + reg[:, None] * xs
        d = -_spd_solve(H, g, solver)
        x0 = jax.lax.dynamic_update_slice_in_dim(x0, xs + d, s, axis=1)
        dp = jnp.einsum("bks,bs->bk", Vs, d.astype(Vs.dtype),
                        precision=prec, preferred_element_type=f32)
        if cw is None:
            e = e + dp
        else:
            pred = pred + dp
            q = q + jnp.einsum("bs,sr->br", d, gram[s:s + w, :],
                               precision=prec)
    return x0


@pytest.mark.parametrize("implicit", [False, True])
@pytest.mark.parametrize("solver", ["xla", "pallas"])
@pytest.mark.parametrize("shape", [(7, 16, 32, 8), (5, 8, 20, 8),
                                   (33, 64, 12, 8)])
def test_looped_blocks_bitwise_as_unrolled(implicit, solver, shape):
    """The whole blocks are one `fori_loop` (one traced body, one lowering
    of the solve kernel) and a narrower last block follows it: 32 = 4 x 8,
    20 = 2 x 8 + 4, 12 = 8 + 4 (no loop at all)."""
    import jax

    from predictionio_tpu.models.als import _subspace_sweep

    B, K, R, block = shape
    rng = np.random.default_rng(B)
    mask = jnp.asarray((rng.random((B, K)) < 0.7).astype(np.float32))
    Vm = jnp.asarray(rng.standard_normal((B, K, R)).astype(np.float32)) \
        * mask[..., None]
    val = jnp.asarray(rng.random((B, K)).astype(np.float32)) * mask
    x0 = jnp.asarray(rng.standard_normal((B, R)).astype(np.float32))
    reg = jnp.asarray(rng.random(B).astype(np.float32)) + 0.1
    Y = rng.standard_normal((100, R)).astype(np.float32)
    gram = jnp.asarray(Y.T @ Y) if implicit else None
    cw = 2.0 * val * mask if implicit else None
    prec = jax.lax.Precision.HIGHEST
    args = (Vm, val, mask, x0, reg, cw, gram)
    looped = jax.jit(
        lambda *a: _subspace_sweep(*a, prec, solver, block))(*args)
    unrolled = jax.jit(
        lambda *a: _unrolled_sweep(*a, prec, solver, block))(*args)
    assert np.array_equal(np.asarray(looped), np.asarray(unrolled))
    assert not np.array_equal(np.asarray(looped), np.asarray(x0))


def test_the_loop_traces_the_solve_once_whatever_the_rank(monkeypatch):
    """16 blocks, one trace of the body: what bounds the lowering of the
    kernel at rank 2,048 (and `warmup_s` with it)."""
    import jax

    from predictionio_tpu.models import als

    calls = []
    real = als._spd_solve
    monkeypatch.setattr(
        als, "_spd_solve",
        lambda *a, **k: calls.append(a[0].shape) or real(*a, **k))
    rng = np.random.default_rng(0)
    B, K, R = 4, 8, 64
    Vm = jnp.asarray(rng.standard_normal((B, K, R)).astype(np.float32))
    ones = jnp.ones((B, K), jnp.float32)
    x0 = jnp.asarray(rng.standard_normal((B, R)).astype(np.float32))
    jax.jit(lambda *a: als._subspace_sweep(
        *a, jax.lax.Precision.HIGHEST, "xla", 4))(
        Vm, ones, ones, x0, jnp.ones(B), None, None)
    assert calls == [(B, 4, 4)]


@pytest.mark.parametrize("rank,block,alpha", [(32, 8, 1.0), (20, 8, 2.5)])
def test_implicit_sweep_row_for_row_as_the_benchmarks_reference(
        rank, block, alpha):
    """`perfbench/reference/ials_subspace_ref.py` forms each row's whole
    normal equations and recomputes the gradient at every block; the
    program keeps caches and never forms them: the same rows."""
    from perfbench.reference import ials_subspace_ref as ref

    u, i, v, nu, ni = _toy_implicit(n_users=60, n_items=40, density=0.4)
    cfg = ALSConfig(rank=rank, num_iterations=1, lam=0.05, implicit=True,
                    alpha=alpha, solver_mode="subspace", subspace_size=block)
    U0, V0, U1 = _one_user_half(cfg, u, i, v, nu, ni)
    order = np.argsort(u, kind="stable")
    counts = np.bincount(u, minlength=nu)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    want = ref.sweep_rows(ref.gram([V0]), V0[i[order]], v[order], starts,
                          counts, U0, 0.05, alpha, block)
    rated = counts > 0
    gap = np.linalg.norm(U1[rated] - want[rated], axis=1) \
        / np.linalg.norm(want[rated], axis=1)
    assert gap.max() < 5e-6
    assert np.array_equal(U1[~rated], U0[~rated])


@pytest.mark.parametrize("rank,block", [(32, 8)])
def test_explicit_sweep_row_for_row_at_rank_32(rank, block):
    u, i, v, nu, ni = _toy(n_users=60, n_items=40)
    cfg = ALSConfig(rank=rank, num_iterations=1, lam=0.1,
                    solver_mode="subspace", subspace_size=block)
    U0, V0, U1 = _one_user_half(cfg, u, i, v, nu, ni)
    want = _np_subspace_half_explicit(U0, V0, u, i, v, 0.1, block)
    np.testing.assert_allclose(U1, want, rtol=2e-4, atol=2e-4)


def test_a_block_as_wide_as_rank_32_is_bitwise_the_full_solve():
    u, i, v, nu, ni = _toy_implicit(n_users=60, n_items=40)
    kw = dict(rank=32, num_iterations=2, lam=0.05, implicit=True)
    full = train_als((u, i, v), nu, ni, ALSConfig(**kw))
    for size in (32, 128):
        deg = train_als((u, i, v), nu, ni, ALSConfig(
            solver_mode="subspace", subspace_size=size, **kw))
        assert np.array_equal(full.user_factors, deg.user_factors)
        assert np.array_equal(full.item_factors, deg.item_factors)


@pytest.mark.parametrize("memory", [16 << 30, int(16.9e9)])
def test_gathered_rows_are_bounded_by_bytes_at_any_rank(memory, monkeypatch):
    """Up to rank 128 the entry cap binds, so those ranks stage the
    shapes they staged (a CPU's 16 GiB and a v5e's bytes_limit alike);
    at rank 2,048 a chunk's [B, K, R] rows stay under a quarter of the
    device's memory where the entry cap alone would gather 34 GB."""
    from predictionio_tpu.models import als

    monkeypatch.setattr(als, "_device_memory_bytes", lambda: memory)
    cap = als.MAX_ENTRIES_PER_BUCKET
    for rank in (8, 64, 100, 128):
        assert als.gather_chunk_entries(rank) == cap
        assert als.gather_chunk_entries(rank, n_dev=4) == cap
    entries = als.gather_chunk_entries(2048)
    # the power of two under a quarter: the quarter itself at 16 GiB
    assert entries == (524_288 if memory == 16 << 30 else 262_144)
    assert cap * 2048 * 4 > 34e9
    assert entries * 2048 * 4 <= memory // 4
    assert als.gather_chunk_entries(2048, n_dev=4) == 4 * entries
    assert als.gather_chunk_entries(4096) == entries // 2


def test_chunk_caps_follow_the_width_of_the_systems(monkeypatch):
    """A half that sweeps blocks of 128 holds [B, 128, 128] Hessians, not
    [B, 2048, 2048] Grams: its chunks may hold 8,192 rows where the full
    solve's bound at that rank would allow 32."""
    from predictionio_tpu.models import als

    monkeypatch.setattr(als, "_device_memory_bytes", lambda: int(16.9e9))
    u, i, v, nu, ni = _toy_implicit()
    sub = ALSTrainer((u, i, v), nu, ni, ALSConfig(
        rank=2048, implicit=True, solver_mode="subspace", subspace_size=128))
    assert sub.system_width == 128
    assert sub._chunk_caps(1) == {"max_rows": 8192, "max_entries": 262_144}
    assert als.gram_chunk_rows(2048) == 32
    full = ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=64))
    assert full.system_width == 64
    assert full._chunk_caps(1) == {
        "max_rows": 32768, "max_entries": als.MAX_ENTRIES_PER_BUCKET}


def _small_chunks(monkeypatch, entries=64):
    from predictionio_tpu.models import als

    monkeypatch.setattr(als, "MAX_ENTRIES_PER_BUCKET", entries)


@pytest.mark.parametrize("implicit", [False, True])
def test_runs_of_chunks_are_staged_stacked_and_looped_bitwise(
        implicit, monkeypatch):
    """Under the block sweep a run of chunks of one shape is ONE stacked
    array and one loop; the result is bit for bit what the same chunks
    give one unrolled step each."""
    from predictionio_tpu.models import als

    _small_chunks(monkeypatch)
    u, i, v, nu, ni = (_toy_implicit if implicit else _toy)(
        n_users=90, n_items=40, density=0.3)
    cfg = ALSConfig(rank=16, num_iterations=2, lam=0.05, implicit=implicit,
                    solver_mode="subspace", subspace_size=4)
    looped = ALSTrainer((u, i, v), nu, ni, cfg)
    assert looped.chunks_looped["user"] > 1
    assert any(b[0].ndim == 2 and b[0].shape[0] > 1
               for b in looped._user_side["buckets"])
    assert any(b[0].ndim == 1 for b in looped._user_side["buckets"])
    for side in (looped._user_side, looped._item_side):
        for (rows, idx, val, counts), k in zip(side["buckets"], side["ks"]):
            assert idx.shape == rows.shape + (k,) == val.shape + ()
            assert counts.shape == rows.shape
    monkeypatch.setattr(als, "_chunk_groups",
                        lambda buckets: [[j] for j in range(len(buckets))])
    unrolled = ALSTrainer((u, i, v), nu, ni, cfg)
    assert unrolled.chunks_looped == {"user": 0, "item": 0}
    assert looped.solve_systems == unrolled.solve_systems
    assert looped.gather_bytes == unrolled.gather_bytes
    U0, V0 = looped.init_factors()
    a = looped.run(U0, V0, 2)
    b = unrolled.run(U0, V0, 2)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    # and the full solve's buckets stay one unrolled step each
    full = ALSTrainer((u, i, v), nu, ni, ALSConfig(
        rank=16, implicit=implicit))
    assert all(b[0].ndim == 1 for b in full._user_side["buckets"])
    assert full.chunks_looped == {"user": 0, "item": 0}


def test_looped_chunks_match_numpy_and_the_phase_probe_runs(monkeypatch):
    _small_chunks(monkeypatch)
    u, i, v, nu, ni = _toy(n_users=90, n_items=40, density=0.3)
    cfg = ALSConfig(rank=10, num_iterations=1, lam=0.1,
                    solver_mode="subspace", subspace_size=4)
    U0, V0, U1 = _one_user_half(cfg, u, i, v, nu, ni)
    want = _np_subspace_half_explicit(U0, V0, u, i, v, 0.1, 4)
    np.testing.assert_allclose(U1, want, rtol=2e-4, atol=2e-4)
    from predictionio_tpu.models.als import _half_phase_probe

    tr = ALSTrainer((u, i, v), nu, ni, cfg)
    side = tr._user_side
    assert tr.chunks_looped["user"] > 1
    sums = {}
    for stop in ("gather", "gram"):
        sums[stop] = float(_half_phase_probe(
            jnp.asarray(U0), jnp.asarray(V0), side["buckets"],
            jnp.float32(0.1), jnp.float32(1.0), ks=side["ks"],
            implicit=False, weighted_lambda=True, precision="highest",
            solver="xla", solver_mode="subspace", subspace_size=4,
            stop_after=stop))
        assert np.isfinite(sums[stop])
    # the probe's sum over the looped chunks is the gathered rows' sum
    gathered = sum(V0[i[u == row]].sum() for row in range(nu))
    assert sums["gather"] == pytest.approx(float(gathered), rel=1e-4)


def test_the_half_holds_no_second_copy_of_the_table_it_updates(monkeypatch):
    """A chunk's warm start is read from the loop's carry, so the donated
    table is updated in place: the compiled half's temporaries stay under
    the table's own bytes (a second copy is what at 571,355 x 2,048 would
    not fit beside the first)."""
    from predictionio_tpu.models import als

    _small_chunks(monkeypatch, entries=4096)
    rng = np.random.default_rng(0)
    nu, ni, nnz = 20000, 50, 60000
    u = rng.integers(0, nu, nnz).astype(np.int32)
    i = rng.integers(0, ni, nnz).astype(np.int32)
    v = np.ones(nnz, np.float32)
    cfg = ALSConfig(rank=64, implicit=True, solver_mode="subspace",
                    subspace_size=16)
    tr = ALSTrainer((u, i, v), nu, ni, cfg)
    assert tr.chunks_looped["user"] > 1
    U0, V0 = tr.init_factors()
    side = tr._user_side
    lowered = als._half_iteration.lower(
        U0, V0, side["buckets"], jnp.float32(0.01), jnp.float32(1.0),
        ks=side["ks"], implicit=True, weighted_lambda=True,
        precision="highest", solver="xla", solver_mode="subspace",
        subspace_size=16)
    memory = lowered.compile().memory_analysis()
    table = nu * 64 * 4
    assert memory.temp_size_in_bytes < table, memory


def test_table_gram_holds_a_bounded_stack_of_partial_grams(monkeypatch):
    """At rank 2,048 the 139 partial Grams of 571,355 rows would be
    2.3 GB at once: they are summed a bounded group at a time; a table
    whose partial Grams fit the bound takes the sum it took."""
    import jax

    from predictionio_tpu.models import als

    monkeypatch.setattr(als, "_GRAM_BLOCK_ROWS", 16)
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((16 * 11 + 5, 8)), jnp.float32)
    prec = jax.lax.Precision.HIGHEST
    whole = np.asarray(als._table_gram(table, prec))
    monkeypatch.setattr(als, "_GRAM_STACK_BYTES", 3 * 4 * 8 * 8)
    grouped = np.asarray(als._table_gram(table, prec))
    want = np.asarray(table, np.float64).T @ np.asarray(table, np.float64)
    np.testing.assert_allclose(grouped, want, rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(grouped, whole, rtol=1e-6, atol=1e-5)
    hlo = jax.jit(lambda t: als._table_gram(t, prec)).lower(table).as_text()
    assert "3x16x8" in hlo and "11x8x8" not in hlo


def test_wide_rows_are_summed_in_blocks_of_entries(monkeypatch):
    import jax

    from predictionio_tpu.models import als

    rng = np.random.default_rng(2)
    V = jnp.asarray(rng.standard_normal((3, 256, 8)), jnp.float32)
    c = jnp.asarray(rng.random((3, 256)), jnp.float32)
    prec = jax.lax.Precision.HIGHEST
    plain = als._sum_over_entries("bk,bks,bkt->bst", c, V, V, prec=prec)
    assert np.array_equal(
        np.asarray(plain),
        np.asarray(jnp.einsum("bk,bks,bkt->bst", c, V, V, precision=prec,
                              preferred_element_type=jnp.float32)))
    monkeypatch.setattr(als, "_GRAM_BLOCK_ROWS", 64)
    for spec, ops in (("bk,bks,bkt->bst", (c, V, V)), ("bk,bks->bs", (c, V)),
                      ("bks,bkt->bst", (V, V))):
        blocked = als._sum_over_entries(spec, *ops, prec=prec)
        want = np.einsum(spec, *(np.asarray(a, np.float64) for a in ops))
        np.testing.assert_allclose(np.asarray(blocked), want, rtol=2e-6,
                                   atol=2e-5)
    hlo = jax.jit(lambda c, V: als._sum_over_entries(
        "bk,bks->bs", c, V, prec=prec)).lower(c, V).as_text()
    assert "3x4x64x8" in hlo


def test_the_tracing_carries_the_block_sweep(monkeypatch):
    from predictionio_tpu.obs import (
        ALS_GATHER_BYTES_TOTAL, ALS_SOLVE_SYSTEMS_TOTAL, tower,
    )

    events = []
    monkeypatch.setattr(
        tower, "note_event", lambda name, **f: events.append((name, f)))
    _small_chunks(monkeypatch)
    u, i, v, nu, ni = _toy_implicit(n_users=90, n_items=40)
    cfg = ALSConfig(rank=16, implicit=True, solver_mode="subspace",
                    subspace_size=4)
    tr = ALSTrainer((u, i, v), nu, ni, cfg)
    (name, staged), = events
    assert name == "als_staged"
    assert (staged["solverMode"], staged["subspaceSize"],
            staged["rankBlocks"]) == ("subspace", 4, 4)
    padded = {name: sum(int(b[1].size) for b in side["buckets"])
              for name, side in (("user", tr._user_side),
                                 ("item", tr._item_side))}
    assert staged["gatherBytes"] == {k: n * 16 * 4 for k, n in padded.items()}
    assert staged["gatherChunkBytes"] <= 64 * 16 * 4
    assert staged["gramChunkBytes"] % (4 * 4 * 4) == 0
    assert staged["chunksLooped"]["user"] > 1
    # a system a row a rank block, batch padding included
    rows = {name: sum(int(b[0].size) for b in side["buckets"])
            for name, side in (("user", tr._user_side),
                               ("item", tr._item_side))}
    assert staged["solveSystems"] == {k: 4 * n for k, n in rows.items()}
    gathered = {s: ALS_GATHER_BYTES_TOTAL.labels(side=s)
                for s in ("user", "item")}
    solved = ALS_SOLVE_SYSTEMS_TOTAL.labels(path="lax")
    before = {s: c.value() for s, c in gathered.items()}, solved.value()
    U, V = tr.init_factors()
    tr.run(U, V, 3)
    for s, c in gathered.items():
        assert c.value() - before[0][s] == 3 * staged["gatherBytes"][s]
    assert solved.value() - before[1] == 3 * 4 * sum(rows.values())
    # the full solve names one block and gathers the same way
    events.clear()
    ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=16, implicit=True))
    assert events[0][1]["rankBlocks"] == 1
    assert events[0][1]["solverMode"] == "full"
    assert events[0][1]["gatherBytes"]["user"] > 0
