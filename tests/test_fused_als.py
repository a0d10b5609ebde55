"""Fused gather+Gram+solve kernel (`ops/fused_als.py`): interpret-mode
parity against the unfused `_solve_buckets` path and a dense float64
reference, per-bucket routing, tile sizing, and a kernel that does not
compile failing the train — including indices that cross (8,128) tile
boundaries, masked entries and tail blocks.  Whether the kernel compiles is answered on
the chip (`chip_smoke.py`); everything here proves the math.
"""

import numpy as np
import pytest

from predictionio_tpu.models.als import ALSConfig, ALSTrainer, train_als
from predictionio_tpu.ops.fused_als import (
    fused_gather_gram_solve,
    fused_tile_plan,
)


def _toy(n_users=40, n_items=25, density=0.4, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, 3))
    V = rng.normal(size=(n_items, 3))
    mask = rng.random((n_users, n_items)) < density
    u, i = np.nonzero(mask)
    v = (U @ V.T)[u, i].astype(np.float32)
    return u.astype(np.int32), i.astype(np.int32), v, n_users, n_items


def test_kernel_matches_dense_reference():
    rng = np.random.default_rng(1)
    M, R, B, K = 200, 12, 9, 21
    table = rng.normal(size=(M, R)).astype(np.float32)
    idx = rng.integers(0, M, size=(B, K)).astype(np.int32)
    mask = (rng.random((B, K)) < 0.7).astype(np.float32)
    val = (rng.random((B, K)) * 4 + 1).astype(np.float32)
    cw = mask
    bw = val * mask
    reg = rng.random(B).astype(np.float32) + 0.5
    gram0 = np.eye(R, dtype=np.float32) * 0.25
    x = np.asarray(fused_gather_gram_solve(
        table, idx, cw, bw, reg, gram0
    ))
    for b in range(B):
        A = gram0.copy()
        rhs = np.zeros(R)
        for k in range(K):
            row = table[idx[b, k]]
            A += cw[b, k] * np.outer(row, row)
            rhs += bw[b, k] * row
        A += reg[b] * np.eye(R)
        np.testing.assert_allclose(
            x[b], np.linalg.solve(A, rhs), rtol=2e-3, atol=2e-3
        )


@pytest.mark.parametrize("implicit", [False, True])
@pytest.mark.parametrize("weighted", [False, True])
def test_fused_train_matches_xla(implicit, weighted):
    """End-to-end ALS with solver='fused' must reproduce the XLA path
    (every toy-scale bucket has a tile plan, so BOTH halves run
    fused)."""
    u, i, v, nu, ni = _toy()
    if implicit:
        v = np.abs(v) + 0.5
    kw = dict(rank=5, num_iterations=3, lam=0.05, implicit=implicit,
              alpha=1.5, weighted_lambda=weighted)
    ref = train_als((u, i, v), nu, ni, ALSConfig(**kw))
    got = ALSTrainer(
        (u, i, v), nu, ni, ALSConfig(solver="fused", **kw)
    ).train()
    np.testing.assert_allclose(
        got.user_factors, ref.user_factors, rtol=5e-4, atol=5e-4
    )
    np.testing.assert_allclose(
        got.item_factors, ref.item_factors, rtol=5e-4, atol=5e-4
    )


def test_fused_refuses_bf16_table():
    """The row DMAs need 32-bit rows (v5e Mosaic: a one-row slice of a
    bf16 ref is not tile-aligned), so the combination is refused at
    config time and at the kernel entry — never run as something
    else."""
    with pytest.raises(ValueError, match="bfloat16"):
        ALSConfig(solver="fused", gather_dtype="bfloat16")
    import jax.numpy as jnp

    table, idx, cw, bw, reg = _parity_case()
    with pytest.raises(ValueError, match="float32 table"):
        fused_gather_gram_solve(
            jnp.asarray(table).astype(jnp.bfloat16), idx, cw, bw, reg
        )


def test_fused_routes_per_bucket_when_a_width_has_no_plan(monkeypatch):
    """Per-bucket routing: a bucket too wide for the SMEM index block
    keeps the XLA path while narrower buckets of the same side fuse —
    chosen from the bucket's static width, never from a failure."""
    from predictionio_tpu.ops import fused_als as fmod

    u, i, v, nu, ni = _toy(seed=7)
    real_plan = fmod.fused_tile_plan
    seen = []

    def gated(r, k):
        plan = real_plan(r, k) if k <= 8 else None
        seen.append((k, plan is not None))
        return plan

    monkeypatch.setattr(fmod, "fused_tile_plan", gated)
    ref = train_als((u, i, v), nu, ni,
                    ALSConfig(rank=5, num_iterations=3, lam=0.05))
    got = train_als((u, i, v), nu, ni,
                    ALSConfig(rank=5, num_iterations=3, lam=0.05,
                              solver="fused"))
    # both kinds of bucket occurred
    assert {ok for _, ok in seen} == {True, False}
    np.testing.assert_allclose(
        got.user_factors, ref.user_factors, rtol=5e-4, atol=5e-4
    )


def test_fused_sharded_placement_matches():
    """solver='fused' inside the shard_map body (sharded factor tables +
    sharded COO) on the 8-device mesh."""
    from predictionio_tpu.parallel import make_mesh

    u, i, v, nu, ni = _toy(seed=3)
    mesh = make_mesh()
    assert mesh.size == 8
    kw = dict(rank=4, num_iterations=2, lam=0.1)
    ref = train_als((u, i, v), nu, ni, ALSConfig(**kw), mesh=mesh)
    got = train_als(
        (u, i, v), nu, ni,
        ALSConfig(solver="fused", factor_placement="sharded", **kw),
        mesh=mesh,
    )
    np.testing.assert_allclose(
        got.user_factors, ref.user_factors, rtol=5e-4, atol=5e-4
    )


def test_fused_tile_plan_respects_budget(monkeypatch):
    tb, kc = fused_tile_plan(64, 4096)
    assert tb >= 8 and kc >= 128
    # the table stays in HBM: only rank and bucket width enter the plan,
    # and the index block of one batch tile fits SMEM
    assert tb * 4096 * 4 <= 256 << 10
    # a width whose index block cannot fit SMEM at any tile has no plan
    assert fused_tile_plan(64, 1 << 14) is None
    # a tiny VMEM budget rejects everything
    monkeypatch.setenv("PIO_TPU_VMEM_BYTES", str(1 << 20))
    assert fused_tile_plan(64, 4096) is None


def test_fused_kernel_that_does_not_compile_fails_the_train(monkeypatch):
    """A fused kernel the compiler rejects FAILS the train with the
    compiler's message; nothing trains on another solver."""
    from predictionio_tpu.ops import fused_als as fmod

    def boom(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel (injected)")

    monkeypatch.setattr(fmod, "fused_gather_gram_solve", boom)
    u, i, v, nu, ni = _toy(seed=11)
    # a rank no other test traces, so the jit cannot answer from cache
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        ALSTrainer((u, i, v), nu, ni,
                   ALSConfig(rank=7, num_iterations=2, solver="fused")).train()


# -- parity suite -------------------------------------------------------------


def _dense_solve(table, idx, cw, bw, reg, gram0=None):
    """Float64 per-row dense reference for the kernel's math."""
    B, K = idx.shape
    M, R = table.shape
    t64 = np.asarray(table, np.float64)
    out = np.zeros((B, R))
    for b in range(B):
        A = (np.zeros((R, R)) if gram0 is None
             else np.asarray(gram0, np.float64).copy())
        rhs = np.zeros(R)
        for k in range(K):
            row = t64[idx[b, k]]
            A += float(cw[b, k]) * np.outer(row, row)
            rhs += float(bw[b, k]) * row
        A += float(reg[b]) * np.eye(R)
        out[b] = np.linalg.solve(A, rhs)
    return out


def _parity_case(seed=0, M=300, R=8, B=11, K=24):
    """Well-conditioned case with deliberately nasty index structure:
    ids pinned onto (8,128) memory-tile boundaries (rows 0/7/8/127/128/
    255/256/M-1 — the sublane- and lane-tile seams of the padded
    table), plus masked entries whose weights are zero and whose ids
    point at row 0 per the kernel contract.  B=11/K=24 are NOT
    tile-multiples, so batch and K tails are always exercised."""
    rng = np.random.default_rng(seed)
    table = rng.normal(size=(M, R)).astype(np.float32)
    idx = rng.integers(0, M, size=(B, K)).astype(np.int32)
    boundary = np.array([0, 7, 8, 9, 127, 128, 129, 255, 256, M - 1],
                        np.int32)
    idx[:, : len(boundary)] = boundary[None, :]
    mask = (rng.random((B, K)) < 0.8).astype(np.float32)
    mask[:, -2:] = 0.0                      # guaranteed masked tail
    idx = np.where(mask > 0, idx, 0).astype(np.int32)
    val = (rng.random((B, K)) * 2 + 0.5).astype(np.float32)
    cw = mask
    bw = (val * mask).astype(np.float32)
    reg = (rng.random(B).astype(np.float32) + 2.0)  # well-conditioned
    return table, idx, cw, bw, reg


def test_kernel_matches_dense_on_tile_boundaries():
    """The kernel reproduces the dense normal-equation solve to 1e-5,
    tile-boundary ids and masked entries included."""
    table, idx, cw, bw, reg = _parity_case()
    x = np.asarray(fused_gather_gram_solve(table, idx, cw, bw, reg))
    want = _dense_solve(table, idx, cw, bw, reg)
    np.testing.assert_allclose(x, want, rtol=1e-5, atol=1e-5)


def test_kernel_multi_chunk_width():
    """A bucket wider than one K chunk accumulates across the second
    grid axis (init on the first chunk, solve on the last)."""
    table, idx, cw, bw, reg = _parity_case(seed=3, K=700)
    tb, kc = fused_tile_plan(table.shape[1], idx.shape[1])
    assert -(-idx.shape[1] // kc) > 1  # really multi-chunk
    x = np.asarray(fused_gather_gram_solve(table, idx, cw, bw, reg))
    want = _dense_solve(table, idx, cw, bw, reg)
    np.testing.assert_allclose(x, want, rtol=1e-5, atol=1e-5)


def test_fused_train_rmse_within_1pct_of_unfused():
    """End-to-end ALS: the fused train must land within the 1% RMSE
    parity bound vs the unfused reference (the acceptance bound
    ROADMAP S3 gates against)."""
    from predictionio_tpu.models.als import rmse

    u, i, v, nu, ni = _toy(seed=13)
    kw = dict(rank=5, num_iterations=4, lam=0.05)
    ref = train_als((u, i, v), nu, ni, ALSConfig(**kw))
    rmse_ref = rmse(ref, u, i, v)
    got = train_als((u, i, v), nu, ni, ALSConfig(solver="fused", **kw))
    rmse_got = rmse(got, u, i, v)
    assert abs(rmse_got - rmse_ref) <= 0.01 * max(rmse_ref, 1e-9), (
        rmse_ref, rmse_got,
    )


def test_smem_budget_slices_batches(monkeypatch):
    """A tight SMEM budget must slice the batch dim (each pallas_call's
    scalar-prefetch slab under budget) without changing results; an
    impossibly tight one must kill the plan entirely."""
    table, idx, cw, bw, reg = _parity_case(seed=17, B=24)
    ref = np.asarray(fused_gather_gram_solve(table, idx, cw, bw, reg))
    # 8 rows x 128 padded K x 4 B = 4096 B per tile: a 4 KiB budget
    # forces bs == tb == 8, i.e. 3 slices for B=24
    monkeypatch.setenv("PIO_TPU_SMEM_BYTES", str(4096))
    plan = fused_tile_plan(table.shape[1], idx.shape[1])
    assert plan is not None and plan[0] == 8
    sliced = np.asarray(fused_gather_gram_solve(table, idx, cw, bw, reg))
    np.testing.assert_allclose(sliced, ref, rtol=1e-6, atol=1e-6)
    monkeypatch.setenv("PIO_TPU_SMEM_BYTES", str(64))
    assert fused_tile_plan(table.shape[1], idx.shape[1]) is None
    with pytest.raises(ValueError, match="no tile plan"):
        fused_gather_gram_solve(table, idx, cw, bw, reg)


def test_fused_recompiles_land_in_xray_ring():
    """The fused entry is xray-instrumented as "als.fused": a new shape
    and a precision change must each register a new signature at
    /debug/xray."""
    from predictionio_tpu.obs import xray

    # shapes unique to THIS test: signatures are structural, so reusing
    # another test's shapes would register nothing under -p no:randomly
    table, idx, cw, bw, reg = _parity_case(seed=23, M=320, R=6, B=13,
                                           K=26)
    before = xray.jit_stats().get("als.fused", {}).get("signatures", 0)
    fused_gather_gram_solve(table, idx, cw, bw, reg)
    fused_gather_gram_solve(table, idx, cw, bw, reg, precision="default")
    stats = xray.jit_stats().get("als.fused")
    assert stats is not None, "als.fused never registered with xray"
    assert stats.get("signatures", 0) >= before + 2
    fused_events = [
        e for e in xray.recompile_events() if e.get("fn") == "als.fused"
    ]
    assert fused_events, "no als.fused recompile ring entries"


@pytest.mark.parametrize("r", [96, 128])
def test_fused_kernel_high_ranks(r):
    """Ranks up to 128 (the GJ augmented column rides lane padding only
    below 128, so 128 exercises the widened [TB, R, R+1] scratch) must
    plan within budget and match the dense solve."""
    assert fused_tile_plan(r, 64) is not None
    rng = np.random.default_rng(0)
    M, B, K = 500, 5, 9
    table = rng.normal(size=(M, r)).astype(np.float32)
    idx = rng.integers(0, M, size=(B, K)).astype(np.int32)
    w = np.ones((B, K), np.float32)
    reg = np.ones(B, np.float32)
    x = np.asarray(fused_gather_gram_solve(table, idx, w, w, reg))
    A = sum(np.outer(table[j], table[j]) for j in idx[0]) + np.eye(r)
    b = sum(table[j] for j in idx[0])
    np.testing.assert_allclose(
        x[0], np.linalg.solve(A, b), rtol=3e-3, atol=3e-3
    )
