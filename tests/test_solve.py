"""The Pallas batched Cholesky solve (`ops/solve.py`: the batch on the
lanes) vs NumPy, through the interpreter on the CPU."""

import numpy as np
import pytest

from predictionio_tpu.ops.solve import cholesky_solve_batched


def _spd_batch(B, R, seed=0):
    rng = np.random.default_rng(seed)
    M = rng.normal(size=(B, R, R)).astype(np.float32)
    A = M @ M.transpose(0, 2, 1) + R * np.eye(R, dtype=np.float32)
    b = rng.normal(size=(B, R)).astype(np.float32)
    return A, b


@pytest.mark.parametrize("B,R", [
    (1, 4), (7, 8), (16, 16), (3, 64),
    # just over one and two registers' lanes: a second tile, ragged
    (129, 8), (257, 8),
])
def test_matches_numpy(B, R):
    A, b = _spd_batch(B, R)
    x = np.asarray(cholesky_solve_batched(A, b))
    ref = np.stack([np.linalg.solve(A[i], b[i]) for i in range(B)])
    np.testing.assert_allclose(x, ref, rtol=2e-4, atol=2e-4)


def test_batch_padding_to_tile():
    # B not a multiple of the tile: the rest of its lanes is padding
    A, b = _spd_batch(13, 8, seed=2)
    x = np.asarray(cholesky_solve_batched(A, b))
    ref = np.stack([np.linalg.solve(A[i], b[i]) for i in range(13)])
    assert x.shape == (13, 8)
    np.testing.assert_allclose(x, ref, rtol=2e-4, atol=2e-4)


def test_well_conditioned_large_batch():
    A, b = _spd_batch(200, 8, seed=3)
    x = np.asarray(cholesky_solve_batched(A, b))
    res = np.einsum("bij,bj->bi", A, x) - b
    assert np.abs(res).max() < 1e-2


@pytest.mark.parametrize("R", [10, 33, 100, 128])
def test_odd_ranks(R):
    """Ranks that are no multiple of 8 are padded to whole sublane
    blocks with identity rows; b rides as column R of the padded slab."""
    A, b = _spd_batch(5, R, seed=4)
    x = np.asarray(cholesky_solve_batched(A, b))
    ref = np.stack([np.linalg.solve(A[i], b[i]) for i in range(5)])
    np.testing.assert_allclose(x, ref, rtol=5e-4, atol=5e-4)


def test_ill_conditioned_regularized():
    """ALS-shaped systems: rank-deficient Gram + lambda*n*I loading.
    Cholesky without pivoting must stay stable at condition ~1e5."""
    rng = np.random.default_rng(5)
    B, R = 16, 32
    # rank-deficient Gram (only 4 contributing vectors) + small ridge
    V = rng.normal(size=(B, 4, R)).astype(np.float32)
    A = np.einsum("bkr,bks->brs", V, V) + 1e-3 * np.eye(R, dtype=np.float32)
    b = rng.normal(size=(B, R)).astype(np.float32)
    x = np.asarray(cholesky_solve_batched(A, b))
    ref = np.stack([
        np.linalg.solve(A[i].astype(np.float64), b[i].astype(np.float64))
        for i in range(B)
    ])
    # relative residual is the honest stability metric at this
    # conditioning (~1e6).  Measured on this fixture: the parent's
    # Gauss-Jordan kernel 2.8e-3, LAPACK's f32 Cholesky 1.1e-3.
    res = np.einsum("bij,bj->bi", A.astype(np.float64), x) - b
    rel = np.abs(res).max() / max(np.abs(b).max(), 1.0)
    assert rel < 1e-2
    # solution-space agreement with the f64 reference is NOT asserted:
    # at condition ~1e6 any f32 solver (Cholesky included) deviates by
    # ~kappa*eps ~ 0.1 relative in x while still solving the system
    del ref


def test_wide_value_range():
    """Pivot magnitudes spanning ~1e-3..1e3 (hot users vs cold users in
    weighted-lambda ALS) must not blow up."""
    rng = np.random.default_rng(6)
    B, R = 8, 16
    scales = np.logspace(-3, 3, B).astype(np.float32)
    M = rng.normal(size=(B, R, R)).astype(np.float32)
    A = (M @ M.transpose(0, 2, 1) + R * np.eye(R, dtype=np.float32))
    A = A * scales[:, None, None]
    b = rng.normal(size=(B, R)).astype(np.float32)
    x = np.asarray(cholesky_solve_batched(A, b))
    ref = np.stack([np.linalg.solve(A[i], b[i]) for i in range(B)])
    np.testing.assert_allclose(x, ref, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Fail-safe + VMEM-derived tile sizing (round-3 verdict item 4)
# ---------------------------------------------------------------------------


def test_tile_sizing_fits_probed_budget(monkeypatch):
    """Every rank's tile footprint must fit the three quarters of the
    VMEM budget the sizing claims to target, in whole registers of 128
    lanes, and shrink under a tighter env budget.  (The tile of a
    [128, 128] system is 8.5 MiB at its narrowest, so the share is no
    longer the parent's half, and the tight budget is 8 MiB, not 4: at
    rank 64 one register's lanes need 3.5 MiB.)"""
    from predictionio_tpu.ops import solve as solve_mod

    for r in (8, 10, 16, 32, 64, 100, 128):
        tb = solve_mod._tile_rows(r)
        assert tb >= 128 and tb % 128 == 0
        assert (
            solve_mod.solver_tile_footprint(tb, r)
            <= solve_mod.solver_vmem_budget() * 3 // 4
        ), f"rank {r}: tile {tb} overruns the budget"
    assert solve_mod._tile_rows(16) > solve_mod._tile_rows(128)
    base_tb = solve_mod._tile_rows(64)
    monkeypatch.setenv("PIO_TPU_VMEM_BYTES", str(8 << 20))
    assert solve_mod.solver_vmem_budget() == 8 << 20
    small_tb = solve_mod._tile_rows(64)
    assert small_tb < base_tb
    assert solve_mod.solver_tile_footprint(small_tb, 64) <= (8 << 20) * 3 // 4


def test_kernel_that_does_not_compile_fails_the_train(monkeypatch):
    """A solve kernel the compiler rejects FAILS a
    solver='pallas' train with the compiler's own message, from the
    first half-iteration's jit — a train never continues on a solver
    the user did not ask for."""
    from predictionio_tpu.models.als import ALSConfig, ALSTrainer
    from predictionio_tpu.ops import solve as solve_mod

    def boom(A, b, interpret=None):
        raise RuntimeError("Mosaic lowering failed (injected)")

    monkeypatch.setattr(solve_mod, "cholesky_solve_batched", boom)
    rng = np.random.default_rng(0)
    u = rng.integers(0, 30, 200).astype(np.int32)
    i = rng.integers(0, 20, 200).astype(np.int32)
    v = rng.uniform(1, 5, 200).astype(np.float32)
    # ranks no other test traces, so the jit cannot answer from cache
    for cfg in (
        ALSConfig(rank=7, num_iterations=2, solver="pallas"),
        ALSConfig(rank=7, num_iterations=1, solver="pallas",
                  solver_mode="subspace", subspace_size=3),
    ):
        with pytest.raises(RuntimeError, match=r"Mosaic lowering failed"):
            ALSTrainer((u, i, v), 30, 20, cfg).train()


def test_interpreter_only_on_the_cpu_backend(monkeypatch):
    """The Pallas interpreter is chosen on the CPU backend and nowhere
    else: on the chip a kernel compiles or the run fails."""
    import jax

    from predictionio_tpu.ops import solve as solve_mod

    assert solve_mod.pallas_interpret()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not solve_mod.pallas_interpret()


def test_error_against_float64_in_units_of_eps_cond():
    """The forward error of a backward-stable solve is a small multiple
    of eps * cond(A).  Systems shaped like an ALS-WR half's (a Gram of n
    rows of N(0, 1/R) plus 0.01 n I) at R = 64, the Frobenius norm over
    a degree's systems: a running subtraction (one rounding at the
    accumulator's size for every earlier row) reads 0.40-0.66 of
    eps * cond in a float32 NumPy model of the kernel, the tree of eight
    0.20-0.34, and the interpreter 0.19-0.33."""
    rng = np.random.default_rng(9)
    R, per = 64, 8
    eps = np.finfo(np.float32).eps
    for n in (40, 90, 300):
        Y = (rng.normal(size=(per, n, R)) / 8).astype(np.float32)
        stars = rng.integers(1, 6, size=(per, n)).astype(np.float32)
        Y64 = Y.astype(np.float64)
        A = (np.einsum("bkr,bks->brs", Y64, Y64)
             + 0.01 * n * np.eye(R)).astype(np.float32)
        b = np.einsum("bk,bkr->br", stars.astype(np.float64), Y64).astype(
            np.float32)
        x = np.asarray(cholesky_solve_batched(A, b), np.float64)
        A64 = A.astype(np.float64)
        ref = np.linalg.solve(A64, b.astype(np.float64)[..., None])[..., 0]
        cond = np.median(np.linalg.cond(A64))
        fro = np.linalg.norm(x - ref) / np.linalg.norm(ref)
        worst = (np.linalg.norm(x - ref, axis=1)
                 / np.linalg.norm(ref, axis=1)).max()
        assert fro < 0.4 * eps * cond, (n, fro / (eps * cond))
        assert worst < 0.6 * eps * cond, (n, worst / (eps * cond))


def _classes(r, n):
    """``n`` width classes over the ``r / 8`` block-rows, at any width
    (the rule engages at 128 alone); ``n = 0`` the exact triangle."""
    rows = r // 8
    n = n or rows
    return tuple(sorted({rows * j // n for j in range(n)}))


@pytest.mark.parametrize("n", [4, 0], ids=["four", "triangle"])
@pytest.mark.parametrize("B", [1, 129, 300])
@pytest.mark.parametrize("R", [16, 64, 100, 128])
def test_width_classes_solve_to_the_bit_of_the_full_width_body(
        R, B, n, monkeypatch):
    """A row worked from its class's first column gives the x of the
    one full-width body to the bit: the same products, tree groups and
    subtractions in every column that is worked.  Ragged last tiles
    (129, 300) and identity-padded ranks (100) included."""
    from predictionio_tpu.ops import solve as solve_mod

    A, b = _spd_batch(B, R, seed=R + B)
    monkeypatch.setattr(solve_mod, "_slab_classes", lambda r: (0,))
    full = np.asarray(cholesky_solve_batched(A, b))
    monkeypatch.setattr(solve_mod, "_slab_classes", lambda r: _classes(r, n))
    trimmed = np.asarray(cholesky_solve_batched(A, b))
    assert np.isfinite(full).all()
    assert np.array_equal(trimmed, full)


def test_the_classes_follow_the_width_alone():
    """Four classes at 128, where the products are most of the kernel;
    today's one body under it.  The slab work is the share of the
    full-width products the kernel does."""
    from predictionio_tpu.ops import solve as solve_mod

    assert solve_mod._slab_classes(128) == (0, 4, 8, 12)
    for r in (8, 16, 64, 104, 120):
        assert solve_mod._slab_classes(r) == (0,)
        assert solve_mod.slab_work_share(r) == 1.0
    # 551,424 of 1,105,408 products a system (ISSUE 43's table)
    assert solve_mod.slab_work_share(128) == 551_424 / 1_105_408
