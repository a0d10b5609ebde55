"""Which implementation solves an ALS half's systems (`models/als.
_solve_path`): the `ops/solve.py` kernel and `lax.linalg` agree, the
default resolves from the backend, the dtype and the width, and the
tracing says which one a train took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import als as als_mod
from predictionio_tpu.models.als import ALSConfig, ALSTrainer


def _ratings(n_users=40, n_items=24, density=0.4, seed=11):
    rng = np.random.default_rng(seed)
    mask = rng.random((n_users, n_items)) < density
    u, i = np.nonzero(mask)
    v = rng.integers(1, 6, size=len(u)).astype(np.float32)
    return u.astype(np.int32), i.astype(np.int32), v, n_users, n_items


@pytest.mark.parametrize("mode", [
    dict(),
    dict(implicit=True, alpha=2.0),
    dict(solver_mode="subspace", subspace_size=3),
    dict(implicit=True, solver_mode="subspace", subspace_size=4),
    # plain lambda: the same reg for every row, whatever its count
    dict(weighted_lambda=False),
    dict(implicit=True, alpha=2.0, weighted_lambda=False),
], ids=["explicit", "implicit", "subspace", "implicit-subspace",
        "unweighted", "implicit-unweighted"])
def test_one_half_agrees_between_the_kernel_and_lax(mode):
    u, i, v, nu, ni = _ratings()
    halves = {}
    for solver in ("pallas", "xla"):
        cfg = ALSConfig(rank=8, lam=0.05, seed=2, solver=solver, **mode)
        tr = ALSTrainer((u, i, v), nu, ni, cfg)
        U, V = tr.init_factors()
        halves[solver] = np.asarray(tr._half(U, V, tr._user_side))
        assert tr.solve_path == {"pallas": "kernel", "xla": "lax"}[solver]
    assert np.isfinite(halves["pallas"]).all()
    np.testing.assert_allclose(
        halves["pallas"], halves["xla"], rtol=1e-5, atol=1e-5
    )


def test_the_default_resolves_from_backend_dtype_and_width(monkeypatch):
    assert ALSConfig().solver == "auto"
    # the tier-1 tests run on the CPU backend: the kernel would be the
    # Pallas interpreter
    assert als_mod._solve_path("auto", 64) == "lax"
    assert als_mod._solve_path("pallas", 64) == "kernel"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert als_mod._solve_path("auto", 64) == "kernel"
    assert als_mod._solve_path("auto", 10) == "kernel"
    assert als_mod._solve_path("auto", 128, jnp.float32) == "kernel"
    assert als_mod._solve_path("auto", 129) == "lax"
    assert als_mod._solve_path("auto", 64, jnp.bfloat16) == "lax"
    assert als_mod._solve_path("auto", 64, jnp.float64) == "lax"
    assert als_mod._solve_path("xla", 64) == "lax"


def test_auto_reaches_the_kernel_where_the_backend_reads_tpu(monkeypatch):
    from predictionio_tpu.ops import solve as solve_mod

    seen = []

    def spy(A, b, interpret=None):
        seen.append((A.shape, A.dtype))
        return jnp.zeros_like(b)

    monkeypatch.setattr(solve_mod, "cholesky_solve_batched", spy)
    u, i, v, nu, ni = _ratings()
    # a rank no other test traces, so the jit cannot answer from cache
    cfg = ALSConfig(rank=6, num_iterations=1)
    ALSTrainer((u, i, v), nu, ni, cfg).train()
    assert seen == []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tr = ALSTrainer((u, i, v), nu, ni, ALSConfig(rank=5, num_iterations=1))
    assert tr.solve_path == "kernel"
    tr.train()
    assert seen and all(
        shape[1:] == (5, 5) and dtype == jnp.float32 for shape, dtype in seen
    )


def test_the_vmapped_sweep_keeps_lax_under_the_default(monkeypatch):
    """`sweep_train_als` batches the half under `vmap`, which a Pallas
    grid does not follow: the default must not hand it the kernel even
    where the backend reads tpu."""
    from predictionio_tpu.models.als import sweep_train_als
    from predictionio_tpu.ops import solve as solve_mod

    def boom(A, b, interpret=None):
        raise AssertionError("the vmapped sweep reached the kernel")

    monkeypatch.setattr(solve_mod, "cholesky_solve_batched", boom)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    u, i, v, nu, ni = _ratings()
    swept = sweep_train_als(
        (u, i, v), nu, ni, ALSConfig(rank=3, num_iterations=1),
        lams=[0.05, 0.5],
    )
    assert len(swept) == 2
    assert all(np.isfinite(f.user_factors).all() for f in swept)


@pytest.mark.parametrize("solver,mode,path", [
    ("auto", dict(), "lax"),
    ("pallas", dict(), "kernel"),
    ("pallas", dict(solver_mode="subspace", subspace_size=3), "kernel"),
])
def test_the_tracing_carries_the_path_and_the_systems(
        solver, mode, path, monkeypatch):
    from predictionio_tpu.obs import ALS_SOLVE_SYSTEMS_TOTAL, tower

    events = []
    monkeypatch.setattr(
        tower, "note_event", lambda name, **f: events.append((name, f)))
    u, i, v, nu, ni = _ratings()
    cfg = ALSConfig(rank=8, min_bucket_k=4, solver=solver, **mode)
    tr = ALSTrainer((u, i, v), nu, ni, cfg)
    (name, staged), = events
    assert name == "als_staged"
    assert staged["solvePath"] == path
    per_row = 3 if mode else 1        # ceil(8 / 3) blocks a row
    want = {
        which: per_row * sum(int(rows.shape[0]) for rows, *_ in side["buckets"])
        for which, side in (("user", tr._user_side), ("item", tr._item_side))
    }
    assert staged["solveSystems"] == want
    assert want["user"] >= per_row * nu and want["item"] >= per_row * ni
    counters = {
        p: ALS_SOLVE_SYSTEMS_TOTAL.labels(path=p) for p in ("kernel", "lax")
    }
    before = {p: c.value() for p, c in counters.items()}
    U, V = tr.init_factors()
    tr.run(U, V, 2)
    other = "lax" if path == "kernel" else "kernel"
    assert counters[path].value() - before[path] == 2 * (
        want["user"] + want["item"])
    assert counters[other].value() == before[other]


@pytest.mark.parametrize("rank,block,solver,want", [
    # whole blocks of 128: four width classes, half the slab's products
    (256, 128, "pallas", {"128": 551_424 / 1_105_408}),
    # blocks of 64 and a last one of 8: today's full-width body
    (200, 64, "pallas", {"8": 1.0, "64": 1.0}),
    # lax.linalg solves: no kernel, no slab
    (256, 128, "xla", {}),
], ids=["engages", "full-width", "lax"])
def test_the_staged_event_carries_the_kernels_slab_work(
        rank, block, solver, want, monkeypatch):
    from predictionio_tpu.obs import tower

    events = []
    monkeypatch.setattr(
        tower, "note_event", lambda name, **f: events.append((name, f)))
    u, i, v, nu, ni = _ratings()
    ALSTrainer((u, i, v), nu, ni, ALSConfig(
        rank=rank, solver=solver, solver_mode="subspace", subspace_size=block))
    (name, staged), = events
    assert staged["solveSlabWork"] == {"user": want, "item": want}
