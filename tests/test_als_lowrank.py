"""The K x K form of an implicit bucket whose pad width is far under the
rank (`models/als._lowrank_form`, `_lowrank_solve`, `_gram_base`): the
same rows as the full R x R normal equations give, held against a
float64 solve of those equations; the rule that picks the buckets; the
counter and the `als_staged` split; replicated against sharded
placement; the fold-in."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import als
from predictionio_tpu.models.als import (
    ALSConfig, ALSTrainer, DENSE_K, _lowrank_form, _solve_buckets,
)
from predictionio_tpu.parallel import make_mesh

LAM = 0.01
ROWS = 48          # rows of the bucket under test
TABLE_ROWS = 2048  # rows of the opposite table


# -- the form against float64 and against the full path ---------------------


def _opposite_table(r: int, cond: float, seed: int) -> np.ndarray:
    """A float32 table whose ``YtY`` has eigenvalues spread over about
    ``cond`` (1: as even as a random table's are), in no special basis,
    rows of norm about 1 as a seed's N(0, 1)/sqrt(R) rows."""
    rng = np.random.default_rng(seed)
    scales = np.logspace(0.0, -0.5 * np.log10(cond), r)
    basis = np.linalg.qr(rng.standard_normal((r, r)))[0]
    table = (rng.standard_normal((TABLE_ROWS, r)) * scales) @ basis
    return (table / np.sqrt(r)).astype(np.float32)


def _bucket(k: int, seed: int):
    """(ids, vals, counts, rows): counts mixed inside the bucket from 0
    to k, so padded slots in most rows; row 0 a batch-padding row (no
    entry, a row id past the table), row 1 full, row 2 one id repeated."""
    rng = np.random.default_rng(seed + 1000 * k)
    counts = rng.integers(0, k + 1, size=ROWS).astype(np.int32)
    counts[0], counts[1], counts[2] = 0, k, k
    ids = rng.integers(0, TABLE_ROWS, size=(ROWS, k)).astype(np.int32)
    ids[2, :] = ids[2, 0]
    vals = rng.integers(1, 5, size=(ROWS, k)).astype(np.float32)
    valid = np.arange(k)[None, :] < counts[:, None]
    rows = np.arange(ROWS, dtype=np.int32)
    rows[0] = 1 << 20
    return (np.where(valid, ids, 0).astype(np.int32),
            np.where(valid, vals, 0.0).astype(np.float32), counts, rows)


@functools.lru_cache(maxsize=None)
def _bucket_solver(k: int, weighted: bool, lowrank: bool):
    """One compiled solve of a lone implicit bucket of width ``k``
    through `_solve_buckets`, in the K x K form or in the full one."""
    def solve(opp, ids, vals, counts, rows, alpha):
        return _solve_buckets(
            lambda acc, rows_, x: x, opp, ((rows, ids, vals, counts),),
            jnp.float32(LAM), alpha, ks=(k,), implicit=True,
            weighted_lambda=weighted, precision="highest", solver="xla",
        )

    def traced(*args):
        # the rule is read while the bucket is traced
        rule = als._lowrank_form
        als._lowrank_form = lambda *a: lowrank
        try:
            return solve(*args)
        finally:
            als._lowrank_form = rule

    return jax.jit(traced)


def _float64_rows(opp, gram, ids, vals, counts, alpha, weighted):
    """The full normal equations, float64, from the float32 table and the
    float32 ``YtY`` the program holds."""
    o, g = opp.astype(np.float64), np.asarray(gram, np.float64)
    r = o.shape[1]
    out = np.zeros((len(counts), r))
    for j, n in enumerate(counts):
        y = o[ids[j, :n]]
        c1 = alpha * vals[j, :n].astype(np.float64)
        reg = LAM * max(int(n), 1) if weighted else LAM
        A = g + (y.T * c1) @ y + reg * np.eye(r)
        out[j] = np.linalg.solve(A, ((1.0 + c1)[:, None] * y).sum(axis=0))
    return out


def _errors(x, ref):
    """(Frobenius gap over the reference's norm, worst row's gap over
    that row's norm); rows the reference solves to zero count as 1."""
    norms = np.linalg.norm(ref, axis=1)
    norms[norms == 0] = 1.0
    return (np.linalg.norm(x - ref) / np.linalg.norm(ref),
            (np.linalg.norm(x - ref, axis=1) / norms).max())


def _both_forms(k, r, cond, alpha, weighted, seed=1):
    opp = _opposite_table(r, cond, seed)
    ids, vals, counts, rows = _bucket(k, seed)
    gram = als._table_gram(jnp.asarray(opp), jax.lax.Precision.HIGHEST)
    ref = _float64_rows(opp, gram, ids, vals, counts, alpha, weighted)
    got = {
        lowrank: np.asarray(_bucket_solver(k, weighted, lowrank)(
            jnp.asarray(opp), jnp.asarray(ids), jnp.asarray(vals),
            jnp.asarray(counts), jnp.asarray(rows), jnp.float32(alpha)))
        for lowrank in (True, False)
    }
    assert np.isfinite(got[True]).all()
    # a batch-padding row and a row without entries solve to zero
    assert not got[True][counts == 0].any()
    lam = np.linalg.eigvalsh(np.asarray(gram, np.float64))
    cond_b = (lam[-1] + LAM) / (max(lam[0], 0.0) + LAM)
    return _errors(got[True], ref), _errors(got[False], ref), cond_b


@pytest.mark.parametrize("cond", [1, 1e2, 1e4])
@pytest.mark.parametrize("r", [32, 64, 128])
@pytest.mark.parametrize("k", [1, 3, 8, 16, 32])
def test_the_form_is_no_further_from_float64_than_twice_the_full_path(
        k, r, cond):
    """ISSUE 37's bound: over bases of condition 1 to 1e4 the K x K
    form's error against a float64 solve of the full normal equations is
    no more than twice the full float32 path's, by the table and by the
    worst row.  Weighted lambda, counts mixed inside the bucket."""
    (fro, worst), (full_fro, full_worst), cond_b = _both_forms(
        k, r, cond, alpha=1.0, weighted=True)
    assert fro <= 2.0 * full_fro
    assert worst <= 2.0 * full_worst
    # and near float32's own floor for a system of this condition
    assert fro <= 3e-7 * max(1.0, cond_b / 30.0)


@pytest.mark.parametrize("cond", [1, 1e2, 1e4])
@pytest.mark.parametrize("alpha", [1.0, 40.0])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
@pytest.mark.parametrize("k,r", [(8, 64), (8, 128)])
def test_the_form_holds_at_alpha_40_and_with_unweighted_lambda(
        k, r, weighted, alpha, cond):
    """At alpha 40 a row's prediction is near 1 and the plain Woodbury
    difference would cancel digits; the form as written does not."""
    (fro, worst), (full_fro, full_worst), _ = _both_forms(
        k, r, cond, alpha=alpha, weighted=weighted, seed=2)
    assert fro <= 2.0 * full_fro
    assert worst <= 2.0 * full_worst


def test_a_real_entry_of_rating_zero_keeps_its_place():
    """c = 1 on an entry of rating 0: no weight, so no row of the K x K
    system, but its p = 1 is in the right-hand side."""
    k, r = 8, 32
    opp = _opposite_table(r, 1, 4)
    ids, vals, counts, rows = _bucket(k, 4)
    vals[:, ::2] = 0.0
    gram = als._table_gram(jnp.asarray(opp), jax.lax.Precision.HIGHEST)
    ref = _float64_rows(opp, gram, ids, vals, counts, 2.0, True)
    got = _bucket_solver(k, True, True)(
        jnp.asarray(opp), jnp.asarray(ids), jnp.asarray(vals),
        jnp.asarray(counts), jnp.asarray(rows), jnp.float32(2.0))
    fro, worst = _errors(np.asarray(got), ref)
    assert fro < 5e-7 and worst < 1e-6


def test_the_base_describes_the_gram_in_hand_to_1e7():
    """`_gram_base`: ``q`` orthonormal and ``q^T G q = diag(lam) + rest``
    to 1e-7 of ``G`` (a plain float32 product reads 3e-7 to 5e-7, the
    whole of the four-chip cell's room), whatever ``eigh`` left off the
    diagonal."""
    for cond in (1, 1e3):
        opp = _opposite_table(128, cond, 3)
        gram = als._table_gram(jnp.asarray(opp), jax.lax.Precision.HIGHEST)
        base = jax.jit(als._gram_base)(gram)
        q = np.asarray(base.q, np.float64)
        lam = np.asarray(base.lam, np.float64)
        rest = np.asarray(base.rest, np.float64)
        t = q.T @ np.asarray(gram, np.float64) @ q
        assert np.linalg.norm(q.T @ q - np.eye(128), 2) < 1.5e-7
        assert np.linalg.norm(t - np.diag(lam) - rest, 2) < 1e-7 * lam.max()
        assert (lam >= 0).all()


def test_a_lowrank_bucket_builds_no_batch_of_rank_by_rank_matrices():
    """What the form is for: no ``[B, R, R]`` in the traced half."""
    k, r = 8, 64
    args = (
        jax.ShapeDtypeStruct((TABLE_ROWS, r), jnp.float32),
        jax.ShapeDtypeStruct((ROWS, k), jnp.int32),
        jax.ShapeDtypeStruct((ROWS, k), jnp.float32),
        jax.ShapeDtypeStruct((ROWS,), jnp.int32),
        jax.ShapeDtypeStruct((ROWS,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    shape = f"tensor<{ROWS}x{r}x{r}xf32>"
    assert shape not in _bucket_solver(k, True, True).lower(*args).as_text()
    assert shape in _bucket_solver(k, True, False).lower(*args).as_text()


# -- the rule ---------------------------------------------------------------


@pytest.mark.parametrize("k,r,implicit,mode,block,takes", [
    (8, 128, True, "full", 16, True),
    (16, 128, True, "full", 16, True),
    (32, 128, True, "full", 16, True),
    (64, 128, True, "full", 16, False),      # over a quarter
    (128, 128, True, "full", 16, False),
    (256, 128, True, "full", 16, False),     # k >= r
    (8, 64, True, "full", 16, True),
    (16, 64, True, "full", 16, True),
    (32, 64, True, "full", 16, False),
    (8, 32, True, "full", 16, True),
    (8, 10, True, "full", 16, False),        # the templates' rank
    (8, 128, False, "full", 16, False),      # explicit: no base
    (8, 128, True, "subspace", 16, False),   # the block sweep
    (8, 128, True, "subspace", 128, True),   # ... of one block
    (DENSE_K, 128, True, "full", 16, False),
])
def test_the_rule_is_a_pure_function_of_static_shapes_and_modes(
        k, r, implicit, mode, block, takes):
    assert _lowrank_form(k, r, implicit, mode, block) is takes


def _ratings(n_users=90, n_items=400, mean=3.0, seed=7):
    """A long-tailed implicit table: most users hold a few items."""
    rng = np.random.default_rng(seed)
    counts = np.minimum(1 + rng.geometric(1.0 / mean, size=n_users), 60)
    u = np.repeat(np.arange(n_users), counts).astype(np.int32)
    i = rng.integers(0, n_items, size=len(u)).astype(np.int32)
    v = rng.integers(1, 4, size=len(u)).astype(np.float32)
    return u, i, v, n_users, n_items


def _spy(monkeypatch):
    seen = []
    form = als._lowrank_solve

    def spy(Vm, *args, **kw):
        seen.append(Vm.shape)
        return form(Vm, *args, **kw)

    monkeypatch.setattr(als, "_lowrank_solve", spy)
    return seen


@pytest.mark.parametrize("cfg", [
    dict(implicit=False),
    dict(implicit=True, solver_mode="subspace", subspace_size=8),
    dict(implicit=True, rank=16),
], ids=["explicit", "subspace", "rank-16"])
def test_halves_the_rule_leaves_out_never_reach_the_form(cfg, monkeypatch):
    seen = _spy(monkeypatch)
    u, i, v, nu, ni = _ratings()
    # ranks no other test of this file traces
    tr = ALSTrainer((u, i, v), nu, ni, ALSConfig(
        **{"rank": 36, "num_iterations": 1, **cfg}))
    assert tr.lowrank_systems == {"user": {}, "item": {}}
    U, V = tr.run(*tr.init_factors(), 1)
    assert seen == [] and np.isfinite(np.asarray(U)).all()


def test_a_dense_bucket_keeps_its_own_form(monkeypatch):
    """A dense chunk beside K = 8 buckets that take the K x K form: the
    dense rows go through `_dense_normal_equations`, every other row's
    bucket by the rule."""
    monkeypatch.setattr(als, "dense_min_count",
                        lambda n, rank, float_weights: 100)
    seen = _spy(monkeypatch)
    u, i, v, nu, ni = _ratings(n_users=120)
    # one user who holds 150 of the 400 items
    u = np.concatenate([u, np.zeros(150, np.int32)])
    i = np.concatenate([i, np.arange(150, dtype=np.int32)])
    v = np.concatenate([v, np.ones(150, np.float32)])
    tr = ALSTrainer((u, i, v), nu, ni, ALSConfig(
        rank=40, implicit=True, num_iterations=1))
    assert DENSE_K in tr._user_side["ks"]
    assert 8 in tr.lowrank_systems["user"]
    U, V = tr.run(*tr.init_factors(), 1)
    assert seen and all(shape[1] <= 10 for shape in seen)
    monkeypatch.setattr(als, "_lowrank_form", lambda *a: False)
    full = ALSTrainer((u, i, v), nu, ni, ALSConfig(
        rank=40, implicit=True, num_iterations=1))
    U0, V0 = full.run(*full.init_factors(), 1)
    np.testing.assert_allclose(np.asarray(U), np.asarray(U0),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(V), np.asarray(V0),
                               rtol=1e-4, atol=1e-5)


# -- the counter and the event ----------------------------------------------


@pytest.mark.parametrize("placement", ["replicated", "sharded"])
def test_the_counter_and_the_event_add_up_to_the_staged_rows(
        placement, monkeypatch):
    from predictionio_tpu.obs import (
        ALS_SOLVE_SYSTEMS_TOTAL, TRAIN_PHASE_SECONDS, tower,
    )

    events = []
    monkeypatch.setattr(
        tower, "note_event", lambda name, **f: events.append((name, f)))
    u, i, v, nu, ni = _ratings()
    sharded = placement == "sharded"
    expand = TRAIN_PHASE_SECONDS.labels(phase="als.expand")
    expansions = expand.snapshot()["count"]
    tr = ALSTrainer(
        (u, i, v), nu, ni,
        ALSConfig(rank=32, implicit=True, factor_placement=placement),
        mesh=make_mesh(4) if sharded else None,
    )
    assert expand.snapshot()["count"] == expansions + 2     # one a side
    (name, staged), = events
    assert name == "als_staged"
    assert isinstance(tr.solve_path, str)
    for side_name, side in (("user", tr._user_side),
                            ("item", tr._item_side)):
        rows = {}
        for bucket, k in zip(side["buckets"], side["ks"]):
            rows[k] = rows.get(k, 0) + int(bucket[0].size)
        assert isinstance(tr.solve_systems[side_name], int)
        assert tr.solve_systems[side_name] == sum(rows.values())
        forms = staged["solveForms"][side_name]
        assert forms["lowrank"] + forms["full"] == sum(rows.values())
        # rank 32: the K = 8 bucket alone meets the rule
        assert forms["lowrank"] == rows.get(8, 0) > 0
        assert staged["lowrankWidths"][side_name] == {"8": rows[8]}
    assert staged["solveSystems"] == tr.solve_systems
    # either placement expands its blocks once, at staging, and says so
    assert staged["placement"] == placement
    for side_name, side in (("user", tr._user_side),
                            ("item", tr._item_side)):
        entries = staged["paddedEntries"][side_name]
        assert entries == sum(int(b[1].size) for b in side["buckets"]) > 0
        assert staged["paddedBytes"][side_name] == 8 * entries
        assert staged["expandSeconds"][side_name] > 0
    counters = {p: ALS_SOLVE_SYSTEMS_TOTAL.labels(path=p)
                for p in ("lowrank", tr.solve_path)}
    before = {p: c.value() for p, c in counters.items()}
    tr.run(*tr.init_factors(), 2)
    lowrank = sum(f["lowrank"] for f in staged["solveForms"].values())
    full = sum(f["full"] for f in staged["solveForms"].values())
    assert counters["lowrank"].value() - before["lowrank"] == 2 * lowrank
    assert counters[tr.solve_path].value() - before[tr.solve_path] \
        == 2 * full


# -- placements and the fold-in ---------------------------------------------


def test_sharded_placement_gives_the_replicated_tables(monkeypatch):
    """Four devices: every device decomposes the same psum'd ``YtY``
    once a half and solves its share of each chunk in the K x K form;
    the tolerance is `tests/test_als_exchange.py`'s."""
    seen = _spy(monkeypatch)
    u, i, v, nu, ni = _ratings(n_users=70, n_items=33 * 8)
    base = dict(rank=32, lam=0.05, implicit=True, alpha=2.0)
    tables = []
    for cfg, mesh in ((ALSConfig(**base), None),
                      (ALSConfig(**base, factor_placement="sharded"),
                       make_mesh(4))):
        tr = ALSTrainer((u, i, v), nu, ni, cfg, mesh=mesh)
        assert tr.lowrank_systems["user"] and tr.lowrank_systems["item"]
        U, V = tr.run(*tr.init_factors(), 2)
        tables.append((np.asarray(U)[:nu], np.asarray(V)[:ni]))
    assert seen
    (U0, V0), (U1, V1) = tables
    np.testing.assert_allclose(U1, U0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(V1, V0, rtol=1e-5, atol=1e-5)


def test_the_fold_in_meets_the_rule_and_keeps_its_parity_with_training(
        monkeypatch):
    """A fold-in bucket of K = 8 at rank 32 takes the K x K form, as the
    training half's K = 8 bucket does: a user folded in against the
    trained item table is the row the next half of training solves."""
    from predictionio_tpu.live.foldin import FoldInSolver

    seen = _spy(monkeypatch)
    u, i, v, nu, ni = _ratings()
    cfg = ALSConfig(rank=32, implicit=True, alpha=2.0, num_iterations=2)
    tr = ALSTrainer((u, i, v), nu, ni, cfg)
    U, V = tr.run(*tr.init_factors(), 2)
    U_next = np.asarray(tr._half(U, V, tr._user_side,
                                 lam=jnp.float32(cfg.lam)))
    n_seen = len(seen)
    counts = np.bincount(u, minlength=nu)
    few = np.flatnonzero((counts >= 1) & (counts <= 8))[:5]
    rows = [(i[u == j], v[u == j]) for j in few]
    out = FoldInSolver(cfg).solve(np.asarray(V), rows)
    assert len(seen) > n_seen and seen[-1][1] == 8
    np.testing.assert_allclose(out, U_next[few], rtol=1e-4, atol=1e-6)


def test_unrolled_lowrank_chunks_wait_for_each_other():
    """A replicated half unrolls its chunks; without a ``[B, R, R]`` to
    weigh on the scheduler nothing but the table orders them, and a v5e's
    compiler planned 41 GB of temporaries for 640 chunks at once (PR 37).
    Each K x K chunk's ids pass a barrier with the table the last chunk
    wrote; the full form's chunks carry none, as before."""
    k, r = 8, 32
    opp = jnp.asarray(_opposite_table(r, 1, 0))
    upd = jnp.zeros((2 * ROWS, r), jnp.float32)
    chunks = []
    for j in range(2):
        ids, vals, counts, _ = _bucket(k, j)
        rows = np.arange(j * ROWS, (j + 1) * ROWS, dtype=np.int32)
        chunks.append(tuple(jnp.asarray(a) for a in (rows, ids, vals, counts)))
    kw = dict(ks=(k, k), weighted_lambda=True, precision="highest",
              solver="xla")
    lam, alpha = jnp.float32(LAM), jnp.float32(1.0)
    half = jax.jit(als._half_iteration_impl, static_argnames=(
        "ks", "implicit", "weighted_lambda", "precision", "solver"))
    text = half.lower(upd, opp, tuple(chunks), lam, alpha, implicit=True,
                      **kw).as_text()
    assert text.count("optimization_barrier") == 1
    assert "optimization_barrier" not in half.lower(
        upd, opp, tuple(chunks), lam, alpha, implicit=False, **kw).as_text()
    # and the two chunks' rows are what each gives alone
    got = np.asarray(half(upd, opp, tuple(chunks), lam, alpha, implicit=True,
                          **kw))
    for j, (rows, ids, vals, counts) in enumerate(chunks):
        alone = _bucket_solver(k, True, True)(opp, ids, vals, counts, rows,
                                              alpha)
        np.testing.assert_allclose(got[j * ROWS:(j + 1) * ROWS],
                                   np.asarray(alone), rtol=1e-6, atol=1e-9)
