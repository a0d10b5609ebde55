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
    with pytest.raises(ValueError, match="fused"):
        ALSConfig(solver_mode="subspace", solver="fused")
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
