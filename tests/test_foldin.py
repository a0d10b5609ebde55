"""pio-live fold-in suite: watermark cursor, row-solve parity with the
training solver and a from-scratch retrain, delta apply semantics, the
serving update path (no stop-the-world reload), and daemon crash/replay
behavior."""

import datetime as dt
import json

import numpy as np
import pytest

from predictionio_tpu.live import (
    FoldInRunner,
    FoldInSolver,
    ScanBatch,
    Watermark,
    WatermarkStore,
    apply_model_delta,
    compute_foldin,
    scan_new_ratings,
)
from predictionio_tpu.models.als import ALSConfig, ALSFactors, rmse, \
    train_als
from predictionio_tpu.storage import DataMap, Event, SQLiteEventStore
from predictionio_tpu.storage.bimap import StringIndex
from predictionio_tpu.workflow import model_io as mio

UTC = dt.timezone.utc


def _t(m, d=1):
    return dt.datetime(2021, 6, d, 0, m % 60, tzinfo=UTC)


def _rate(u, i, r, m=0, d=1):
    return Event(
        event="rate", entity_type="user", entity_id=u,
        target_entity_type="item", target_entity_id=i,
        properties=DataMap({"rating": float(r)}), event_time=_t(m, d),
    )


# ---------------------------------------------------------------------------
# watermark store
# ---------------------------------------------------------------------------


def test_watermark_roundtrip_and_monotonicity(tmp_path):
    ws = WatermarkStore(tmp_path / "wm.json")
    assert ws.get(1).rowid == 0 and ws.get(1).seq == 0
    ws.advance(Watermark(1, 0, rowid=42, seq=3))
    got = ws.get(1)
    assert got.rowid == 42 and got.seq == 3
    # second (app, channel) is independent
    ws.advance(Watermark(2, 1, rowid=7, seq=1))
    assert ws.get(1).rowid == 42 and ws.get(2, 1).rowid == 7
    with pytest.raises(ValueError, match="backwards"):
        ws.advance(Watermark(1, 0, rowid=41, seq=4))


def test_watermark_torn_file_resets_not_crashes(tmp_path):
    p = tmp_path / "wm.json"
    ws = WatermarkStore(p)
    ws.advance(Watermark(1, 0, rowid=10, seq=1))
    p.write_text("{torn")
    assert ws.get(1).rowid == 0  # re-scan window, not an exception
    ws.advance(Watermark(1, 0, rowid=11, seq=2))
    assert ws.get(1).rowid == 11


# ---------------------------------------------------------------------------
# watermark scan
# ---------------------------------------------------------------------------


@pytest.fixture()
def es(tmp_path):
    s = SQLiteEventStore(tmp_path / "ev.db")
    s.init_channel(1)
    yield s
    s.close()


def test_scan_explicit_last_wins_and_cursor(es):
    es.insert_batch(
        [_rate("u1", "i1", 4.0, 0), _rate("u1", "i1", 2.0, 1),
         _rate("u2", "i2", 5.0, 2)],
        app_id=1,
    )
    batch = scan_new_ratings(es, 1, cursor=0)
    assert batch.n_events == 3
    got = dict(zip(zip(batch.user_ids, batch.item_ids),
                   batch.values.tolist()))
    assert got[("u1", "i1")] == 2.0  # last wins within the window
    assert got[("u2", "i2")] == 5.0
    assert batch.new_cursor == es.max_rowid(1)
    # nothing new -> empty batch
    again = scan_new_ratings(es, 1, cursor=batch.new_cursor)
    assert again.n_events == 0 and again.user_ids == []


def test_scan_implicit_counts(es):
    es.insert_batch(
        [Event(event="view", entity_type="user", entity_id="u1",
               target_entity_type="item", target_entity_id="i1",
               event_time=_t(m)) for m in range(3)],
        app_id=1,
    )
    batch = scan_new_ratings(
        es, 1, cursor=0, event_names=("view",), rating_property=None,
    )
    assert batch.values.tolist() == [3.0]


def test_scan_skips_foreign_and_propertyless(es):
    es.insert_batch(
        [
            _rate("u1", "i1", 4.0, 0),
            # wrong entity type
            Event(event="rate", entity_type="robot", entity_id="r1",
                  target_entity_type="item", target_entity_id="i1",
                  properties=DataMap({"rating": 1.0}),
                  event_time=_t(1)),
            # no target
            Event(event="rate", entity_type="user", entity_id="u3",
                  event_time=_t(2)),
            # no rating property
            Event(event="rate", entity_type="user", entity_id="u4",
                  target_entity_type="item", target_entity_id="i2",
                  event_time=_t(3)),
        ],
        app_id=1, validate=False,
    )
    batch = scan_new_ratings(es, 1, cursor=0)
    assert batch.user_ids == ["u1"]
    # skipped events still advance the cursor: the watermark is a
    # storage cursor, not a rating counter
    assert batch.new_cursor == es.max_rowid(1)


# ---------------------------------------------------------------------------
# solver parity
# ---------------------------------------------------------------------------


def _ref_solve_explicit(Y, ixs, vals, lam, weighted=True):
    Ys = Y[ixs]
    n = len(ixs)
    reg = lam * max(n, 1) if weighted else lam
    A = Ys.T @ Ys + reg * np.eye(Y.shape[1])
    return np.linalg.solve(A, Ys.T @ vals)


def test_solver_matches_normal_equations_explicit():
    rng = np.random.default_rng(0)
    Y = rng.normal(size=(37, 6)).astype(np.float32)
    cfg = ALSConfig(rank=6, lam=0.07)
    s = FoldInSolver(cfg)
    rows = [
        (np.arange(5, dtype=np.int32),
         rng.uniform(1, 5, 5).astype(np.float32)),
        (np.asarray([30, 31, 36], np.int32),
         rng.uniform(1, 5, 3).astype(np.float32)),
    ]
    out = s.solve(Y, rows)
    for j, (ixs, vals) in enumerate(rows):
        ref = _ref_solve_explicit(Y, ixs, vals, cfg.lam)
        np.testing.assert_allclose(out[j], ref, rtol=1e-4, atol=1e-5)


def test_solver_matches_normal_equations_implicit():
    rng = np.random.default_rng(1)
    Y = rng.normal(size=(20, 4)).astype(np.float32)
    cfg = ALSConfig(rank=4, lam=0.1, implicit=True, alpha=2.0,
                    weighted_lambda=False)
    s = FoldInSolver(cfg)
    ixs = np.asarray([2, 5, 9], np.int32)
    vals = np.asarray([1.0, 2.0, 1.0], np.float32)
    out = s.solve(Y, [(ixs, vals)])
    # HKV: (YtY + Yt(C-I)Y + lam I) x = Yt C p, p=1 on rated
    C = np.zeros(len(Y))
    C[ixs] = cfg.alpha * vals
    A = Y.T @ Y + (Y.T * C) @ Y + cfg.lam * np.eye(4)
    b = Y[ixs].T @ (1.0 + cfg.alpha * vals)
    ref = np.linalg.solve(A, b)
    np.testing.assert_allclose(out[0], ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("implicit", [False, True])
def test_solved_block_equals_the_flattened_formulation_bitwise(implicit):
    """The kernel hands its padded `[B, K]` ids and values to
    `_solve_buckets` as they are.  Until PR 30 it flattened them and had
    the sweep expand them again from `starts`: the same block, bit for
    bit, for the same ids, vals and counts."""
    import functools

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.als import _expand_bucket, _solve_buckets

    rng = np.random.default_rng(3)
    b, k, r = 8, 16, 6
    opp = jnp.asarray(rng.normal(size=(1024, r)).astype(np.float32))
    counts = np.array([16, 0, 3, 9, 1, 16, 0, 7], np.int32)
    ids = np.zeros((b, k), np.int32)
    vals = np.zeros((b, k), np.float32)
    for j, n in enumerate(counts):
        ids[j, :n] = rng.choice(1000, n, replace=False)
        vals[j, :n] = rng.uniform(1, 5, n)
    cfg = ALSConfig(rank=r, lam=0.07, implicit=implicit, alpha=2.0)
    lam, alpha = jnp.float32(cfg.lam), jnp.float32(cfg.alpha)
    kw = dict(implicit=implicit, weighted_lambda=True, precision="highest",
              solver="xla")
    got = FoldInSolver(cfg)._kernel(
        opp, jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(counts),
        lam, alpha, k=k, **kw)

    @functools.partial(jax.jit, static_argnames=("k",))
    def flattened(opp, ids, vals, counts, lam, alpha, *, k):
        starts = jnp.arange(ids.shape[0], dtype=jnp.int32) * k
        rows = jnp.arange(ids.shape[0], dtype=jnp.int32)
        idx, val = _expand_bucket(
            ids.reshape(-1), vals.reshape(-1), starts, counts, k)
        return _solve_buckets(
            lambda acc, rows_, x: x, opp, ((rows, idx, val, counts),),
            lam, alpha, ks=(k,), **kw)

    want = flattened(opp, jnp.asarray(ids), jnp.asarray(vals),
                     jnp.asarray(counts), lam, alpha, k=k)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()


def test_solver_truncates_to_most_recent_when_over_capacity():
    rng = np.random.default_rng(2)
    Y = rng.normal(size=(64, 4)).astype(np.float32)
    cfg = ALSConfig(rank=4, lam=0.05)
    s = FoldInSolver(cfg, max_k=8)
    ixs = np.arange(20, dtype=np.int32)
    vals = rng.uniform(1, 5, 20).astype(np.float32)
    out = s.solve(Y, [(ixs, vals)])
    ref = _ref_solve_explicit(Y, ixs[-8:], vals[-8:], cfg.lam)
    np.testing.assert_allclose(out[0], ref, rtol=1e-4, atol=1e-5)


def test_solver_compile_cache_stable_across_cycles():
    """The fixed-capacity contract: repeated calls on the same padded
    (B, K) rung reuse ONE executable (the /debug/xray invariant — a
    per-cycle recompile would melt a high-frequency daemon)."""
    rng = np.random.default_rng(3)
    Y = rng.normal(size=(40, 4)).astype(np.float32)
    s = FoldInSolver(ALSConfig(rank=4, lam=0.05))
    for trial in range(4):
        rows = [
            (rng.choice(40, size=rng.integers(1, 8),
                        replace=False).astype(np.int32),
             rng.uniform(1, 5, 1).astype(np.float32))
            for _ in range(int(rng.integers(1, 8)))
        ]
        rows = [(ix, np.full(len(ix), 4.0, np.float32))
                for ix, _ in rows]
        s.solve(Y, rows)
        if trial == 0:
            first = s.cache_size()
    assert s.cache_size() == first == 1
    # a different rung compiles once more, then is stable too
    big = [(np.arange(20, dtype=np.int32),
            np.full(20, 3.0, np.float32))]
    s.solve(Y, big)
    s.solve(Y, big)
    assert s.cache_size() == 2


def test_padded_shape_ladder_is_bounded():
    s = FoldInSolver(ALSConfig(rank=4, min_bucket_k=8))
    assert s.padded_shape(1, 3) == (8, 8)
    assert s.padded_shape(9, 9) == (16, 16)
    assert s.padded_shape(3, 5000) == (8, 4096)  # K capped at max_k


# ---------------------------------------------------------------------------
# compute_foldin + RMSE parity with a from-scratch retrain
# ---------------------------------------------------------------------------


def test_foldin_rows_match_retrain_within_one_percent():
    """Acceptance criterion: folded-in rows match a from-scratch
    retrain's corresponding rows within the existing 1% RMSE-parity
    bound on held-out data (and near-identical row direction)."""
    seed = 7
    rng = np.random.default_rng(seed)
    NU, NI, R = 120, 50, 4
    GU = rng.normal(size=(NU, R))
    GI = rng.normal(size=(NI, R))
    us, its, vs = [], [], []
    for u in range(NU):
        for i in rng.choice(NI, size=30, replace=False):
            us.append(u)
            its.append(i)
            vs.append(float(np.clip(
                GU[u] @ GI[i] + rng.normal(0, 0.3) + 3.0, 1, 5
            )))
    u_all = np.asarray(us, np.int32)
    i_all = np.asarray(its, np.int32)
    v_all = np.asarray(vs, np.float32)
    holds = list(range(NU - 4, NU))
    mask_h = np.isin(u_all, holds)
    h_train, h_eval = [], []
    for h in holds:
        idx = np.nonzero(u_all == h)[0]
        h_train.extend(idx[:10])
        h_eval.extend(idx[10:])
    h_train = np.asarray(h_train)
    h_eval = np.asarray(h_eval)
    cfg = ALSConfig(rank=R, num_iterations=15, lam=0.05, seed=3)

    # model A: never saw the holdout users; fold their rows in
    A = train_als(
        (u_all[~mask_h], i_all[~mask_h], v_all[~mask_h]), NU, NI, cfg
    )
    solver = FoldInSolver(cfg)
    per = []
    for h in holds:
        sel = h_train[u_all[h_train] == h]
        per.append((i_all[sel], v_all[sel]))
    rows = solver.solve(A.item_factors, per)
    Af = ALSFactors(
        user_factors=A.user_factors.copy(),
        item_factors=A.item_factors,
    )
    for h, r in zip(holds, rows):
        Af.user_factors[h] = r

    # model B: from-scratch retrain incl. the holdout users' train part
    mask_b = np.ones(len(u_all), bool)
    mask_b[h_eval] = False
    B = train_als(
        (u_all[mask_b], i_all[mask_b], v_all[mask_b]), NU, NI, cfg
    )
    r_fold = rmse(Af, u_all[h_eval], i_all[h_eval], v_all[h_eval])
    r_retrain = rmse(B, u_all[h_eval], i_all[h_eval], v_all[h_eval])
    assert r_fold <= r_retrain * 1.01, (r_fold, r_retrain)
    for h, r in zip(holds, rows):
        b_row = B.user_factors[h]
        cos = float(
            np.dot(r, b_row)
            / (np.linalg.norm(r) * np.linalg.norm(b_row))
        )
        assert cos > 0.99, (h, cos)


def _mini_model():
    """Tiny trained-ish model triple for compute/apply tests."""
    rng = np.random.default_rng(5)
    uf = rng.normal(size=(4, 3)).astype(np.float32)
    itf = rng.normal(size=(5, 3)).astype(np.float32)
    users = StringIndex([f"u{j}" for j in range(4)])
    items = StringIndex([f"i{j}" for j in range(5)])
    return uf, itf, users, items


def test_compute_foldin_new_user_and_new_item():
    uf, itf, users, items = _mini_model()
    cfg = ALSConfig(rank=3, lam=0.05)
    solver = FoldInSolver(cfg)
    scan = ScanBatch(
        user_ids=["nu", "nu", "u1"],
        item_ids=["i0", "ni", "ni"],
        values=np.asarray([5.0, 4.0, 3.0], np.float32),
        n_events=3, cursor=0, new_cursor=3,
    )
    history = {
        "nu": (["i0", "ni"], np.asarray([5.0, 4.0], np.float32)),
        "u1": (["i2", "ni"], np.asarray([2.0, 3.0], np.float32)),
    }
    plan = compute_foldin(
        solver, uf, itf, users, items, scan, history
    )
    assert plan.new_user_ids == ["nu"]
    assert plan.new_item_ids == ["ni"]
    assert plan.user_rows_ix.tolist() == [users.get("u1")]
    assert plan.base_n_users == 4 and plan.base_n_items == 5
    # indexes were NOT mutated by compute (the apply step owns that)
    assert len(users) == 4 and len(items) == 5
    # the new user's row reflects pass 3 (sees the new item):
    # solve against [itf; new_item_row] with their full history
    itf_grown = np.concatenate([itf, plan.new_item_rows], axis=0)
    ref = _ref_solve_explicit(
        itf_grown, np.asarray([0, 5]), np.asarray([5.0, 4.0]), cfg.lam
    )
    np.testing.assert_allclose(
        plan.new_user_rows[0], ref, rtol=1e-3, atol=1e-4
    )


def test_apply_model_delta_patches_and_appends():
    uf, itf, users, items = _mini_model()

    class M:
        pass

    m = M()
    m.user_factors, m.item_factors = uf.copy(), itf.copy()
    m.users, m.items = users, items
    old_u2 = m.user_factors[2].copy()
    rng = np.random.default_rng(9)
    d = mio.ModelDelta(
        seq=1,
        meta={"baseUsers": 4, "baseItems": 5,
              "watermark": {"appId": 1, "channelId": 0, "rowid": 10}},
        user_rows_ix=np.asarray([1], np.int32),
        user_rows=rng.normal(size=(1, 3)).astype(np.float32),
        new_user_ids=np.asarray(["nu"], np.str_),
        new_user_rows=rng.normal(size=(1, 3)).astype(np.float32),
        item_rows_ix=np.zeros(0, np.int32),
        item_rows=np.zeros((0, 3), np.float32),
        new_item_ids=np.asarray(["ni"], np.str_),
        new_item_rows=rng.normal(size=(1, 3)).astype(np.float32),
    )
    counts = apply_model_delta(m, d)
    assert counts["appendedUsers"] == 1
    assert m.user_factors.shape == (5, 3)
    assert m.item_factors.shape == (6, 3)
    np.testing.assert_array_equal(m.user_factors[1], d.user_rows[0])
    np.testing.assert_array_equal(m.user_factors[2], old_u2)
    np.testing.assert_array_equal(m.user_factors[4], d.new_user_rows[0])
    assert m.users.get("nu") == 4 and m.items.get("ni") == 5
    # double-apply fails loudly (base sizes no longer match)
    with pytest.raises(ValueError, match="expects"):
        apply_model_delta(m, d)


def test_apply_model_delta_patches_device_caches():
    from predictionio_tpu.templates.recommendation import ALSModel

    uf, itf, users, items = _mini_model()
    m = ALSModel(
        user_factors=uf.copy(), item_factors=itf.copy(),
        users=users, items=items, item_props={},
    )
    dev_before = m.device_item_factors()          # materialize caches
    norm_before = m.device_item_factors_normalized()
    assert dev_before.shape == (5, 3)
    rng = np.random.default_rng(11)
    patched_row = rng.normal(size=(1, 3)).astype(np.float32)
    new_row = rng.normal(size=(1, 3)).astype(np.float32)
    d = mio.ModelDelta(
        seq=1,
        meta={"baseUsers": 4, "baseItems": 5},
        user_rows_ix=np.zeros(0, np.int32),
        user_rows=np.zeros((0, 3), np.float32),
        new_user_ids=np.asarray([], np.str_),
        new_user_rows=np.zeros((0, 3), np.float32),
        item_rows_ix=np.asarray([2], np.int32),
        item_rows=patched_row,
        new_item_ids=np.asarray(["ni"], np.str_),
        new_item_rows=new_row,
    )
    apply_model_delta(m, d)
    dev = np.asarray(m.device_item_factors())
    assert dev.shape == (6, 3)
    np.testing.assert_allclose(dev[2], patched_row[0], rtol=1e-6)
    np.testing.assert_allclose(dev[5], new_row[0], rtol=1e-6)
    normed = np.asarray(m.device_item_factors_normalized())
    expect = new_row[0] / (np.linalg.norm(new_row[0]) + 1e-9)
    np.testing.assert_allclose(normed[5], expect, rtol=1e-5)


# ---------------------------------------------------------------------------
# daemon + serving end-to-end (in-process, sqlite-backed)
# ---------------------------------------------------------------------------


@pytest.fixture()
def sqlite_storage(tmp_path):
    from predictionio_tpu.storage import Storage, reset_storage

    s = Storage(env={
        "PIO_TPU_HOME": str(tmp_path),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SQLITE",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQLITEMD",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "LOCALFS",
        "PIO_STORAGE_SOURCES_SQLITE_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQLITE_PATH": str(tmp_path / "ev.db"),
        "PIO_STORAGE_SOURCES_SQLITEMD_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQLITEMD_PATH": str(tmp_path / "md.db"),
        "PIO_STORAGE_SOURCES_LOCALFS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_LOCALFS_PATH": str(tmp_path / "models"),
    })
    reset_storage(s)
    yield s
    reset_storage(None)


def _train_small(storage, app_name="liveapp", **params):
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine,
    )
    from predictionio_tpu.workflow import run_train

    md = storage.get_metadata()
    app = md.app_insert(app_name)
    es = storage.get_event_store()
    es.init_channel(app.id)
    rng = np.random.default_rng(0)
    events = []
    for u in range(10):
        group = u % 2
        for i in range(8):
            if rng.random() < (0.9 if (i % 2) == group else 0.25):
                events.append(_rate(
                    f"u{u}", f"i{i}",
                    5.0 if (i % 2) == group else 1.0, m=u * 8 + i,
                ))
    es.insert_batch(events, app_id=app.id)
    engine = recommendation_engine()
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": app_name}},
        "algorithms": [{"name": "als", "params": {
            "rank": 6, "numIterations": 8, "lambda": 0.05, **params}}],
    })
    ctx = WorkflowContext(storage=storage)
    iid = run_train(engine, ep, ctx=ctx, engine_variant="live.json")
    return engine, ep, iid, app.id, es


def test_runner_cycle_end_to_end(sqlite_storage):
    from predictionio_tpu.controller import WorkflowContext

    engine, ep, iid, app_id, es = _train_small(sqlite_storage)
    runner = FoldInRunner(
        sqlite_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
        from_now=True,
    )
    assert runner.cycle() is None  # from_now: history already trained
    es.insert_batch(
        [_rate("brand_new", f"i{i}", 5.0, d=2) for i in (1, 3, 5)],
        app_id=app_id,
    )
    stats = runner.cycle()
    assert stats is not None
    assert stats["appendedUsers"] == 1
    assert stats["seq"] == 1
    assert runner.cycle() is None  # cursor advanced
    # second window: the SAME user rates more -> patched, not appended
    es.insert_batch([_rate("brand_new", "i7", 5.0, d=3)], app_id=app_id)
    stats2 = runner.cycle()
    assert stats2["appendedUsers"] == 0 and stats2["patchedUsers"] == 1
    assert stats2["seq"] == 2
    # the daemon's own model composed both deltas
    assert runner.model.users.get("brand_new") >= 0


def test_a_record_of_the_fused_solver_folds_in_at_its_own_settings(
        sqlite_storage):
    """A model trained with the fused kernel, which went, holds
    ``solver: "fused"`` in its record.  Its fold-in must still solve the
    record's own form (implicit, alpha, plain lambda), to the rows a
    record of the default solver folds in, not the defaults' explicit
    form."""
    import dataclasses

    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.workflow import run_train

    engine, ep, iid, app_id, es = _train_small(
        sqlite_storage, implicit=True, alpha=4.0, weightedLambda=False)
    iid_fused = run_train(engine, ep,
                          ctx=WorkflowContext(storage=sqlite_storage),
                          engine_variant="live.json")
    md = sqlite_storage.get_metadata()
    rec = md.engine_instance_get(iid_fused)
    ((name, params),) = json.loads(rec.algorithms_params)[0].items()
    md.engine_instance_update(dataclasses.replace(
        rec, algorithms_params=json.dumps(
            [{name: {**params, "solver": "fused"}}])))
    ep_fused = engine.params_from_instance(md.engine_instance_get(iid_fused))
    runners = [
        FoldInRunner(sqlite_storage, engine, p, i, from_now=True,
                     ctx=WorkflowContext(storage=sqlite_storage,
                                         mode="Serving"))
        for p, i in ((ep, iid), (ep_fused, iid_fused))
    ]
    for r in runners:
        assert r.cfg.implicit and r.cfg.alpha == 4.0
        assert not r.cfg.weighted_lambda
        assert r.cycle() is None
    es.insert_batch(
        [_rate("brand_new", f"i{i}", 3.0, d=2) for i in (1, 3, 5)]
        + [_rate("u0", "i7", 2.0, d=2)],
        app_id=app_id,
    )
    rows = []
    for r in runners:
        stats = r.cycle()
        assert stats["appendedUsers"] == 1 and stats["patchedUsers"] == 1
        m = r.model
        rows.append(m.user_factors[[m.users.get("brand_new"),
                                    m.users.get("u0")]])
    np.testing.assert_array_equal(rows[1], rows[0])


def test_runner_restart_replays_chain(sqlite_storage):
    from predictionio_tpu.controller import WorkflowContext

    engine, ep, iid, app_id, es = _train_small(sqlite_storage)
    r1 = FoldInRunner(
        sqlite_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
        from_now=True,
    )
    es.insert_batch(
        [_rate("nuA", f"i{i}", 5.0, d=2) for i in (0, 2)],
        app_id=app_id,
    )
    s1 = r1.cycle()
    assert s1["seq"] == 1
    row_before = r1.model.user_factors[r1.model.users.get("nuA")].copy()
    # a fresh runner (daemon restart) replays the chain and resumes
    r2 = FoldInRunner(
        sqlite_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
    )
    assert r2.seq == 1 and r2.cursor == r1.cursor
    np.testing.assert_allclose(
        r2.model.user_factors[r2.model.users.get("nuA")],
        row_before, rtol=1e-6,
    )
    assert r2.cycle() is None


def test_runner_watermark_crash_replay_is_idempotent(sqlite_storage):
    """Crash between delta publish and watermark advance: the rerun
    re-scans the same window into the NEXT link; the net model state is
    the same rows re-solved to the same values, and ids resolve
    idempotently (StringIndex.append)."""
    from predictionio_tpu.controller import WorkflowContext

    engine, ep, iid, app_id, es = _train_small(sqlite_storage)
    r1 = FoldInRunner(
        sqlite_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
        from_now=True,
    )
    es.insert_batch(
        [_rate("nuB", f"i{i}", 4.0, d=2) for i in (1, 3)],
        app_id=app_id,
    )
    r1.cycle()
    # simulate the crash: roll the watermark FILE back (the delta file
    # survived); a restarted runner resumes from max(file, chain) so
    # the chain rowid still wins — then force the worst case by
    # clearing it from the meta
    wm_path = r1.watermarks.path
    raw = json.loads(wm_path.read_text())
    key = f"{r1.app_id}:{r1.channel_id}"
    raw["cursors"][key]["rowid"] = 0
    raw["cursors"][key]["seq"] = 0
    wm_path.write_text(json.dumps(raw))
    r2 = FoldInRunner(
        sqlite_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
    )
    # chain meta carries the watermark -> no replay needed
    assert r2.cursor == r1.cursor
    assert r2.cycle() is None


def test_serving_applies_deltas_without_reload(sqlite_storage):
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.server.serving import EngineServer, ServerConfig

    engine, ep, iid, app_id, es = _train_small(sqlite_storage)
    srv = EngineServer(
        engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
        config=ServerConfig(port=0, microbatch="off"),
        engine_variant="live.json",
    )
    # pio-live off + no deltas -> fields absent
    st0 = srv.status_json()
    assert "modelFreshnessSec" not in st0
    assert srv.predict_json({"user": "ghost", "num": 3})["itemScores"] \
        == []

    runner = FoldInRunner(
        sqlite_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
        from_now=True,
    )
    es.insert_batch(
        [_rate("ghost", f"i{i}", 5.0, d=2) for i in (1, 3, 5)],
        app_id=app_id,
    )
    assert runner.cycle() is not None
    applied = srv._apply_available_deltas()
    assert applied == 1
    out = srv.predict_json({"user": "ghost", "num": 3})
    assert len(out["itemScores"]) == 3
    st = srv.status_json()
    assert st["modelFreshnessSec"] >= 0.0
    assert st["foldinWatermarkLag"] == 0
    assert st["foldinDeltasApplied"] == 1
    assert st["engineInstanceId"] == iid  # no reload happened
    # watermark lag counts NEW unfolded events
    es.insert_batch([_rate("ghost", "i7", 5.0, d=3)], app_id=app_id)
    assert srv.status_json()["foldinWatermarkLag"] == 1
    # idempotent: nothing new to apply
    assert srv._apply_available_deltas() == 0
    srv._foldin_stop.set()


def test_serving_batched_path_sees_folded_rows(sqlite_storage):
    """The micro-batched predict path closes over the MODEL OBJECT —
    in-place delta apply must be visible through batch_predict too."""
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.server.serving import EngineServer, ServerConfig

    engine, ep, iid, app_id, es = _train_small(sqlite_storage)
    srv = EngineServer(
        engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
        config=ServerConfig(port=0, microbatch="on"),
        engine_variant="live.json",
    )
    assert srv.predict_json({"user": "late", "num": 2})["itemScores"] \
        == []
    runner = FoldInRunner(
        sqlite_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
        from_now=True,
    )
    es.insert_batch(
        [_rate("late", f"i{i}", 5.0, d=2) for i in (0, 2)],
        app_id=app_id,
    )
    runner.cycle()
    srv._apply_available_deltas()
    out = srv.predict_json({"user": "late", "num": 2})
    assert len(out["itemScores"]) == 2
    srv._foldin_stop.set()


def test_serving_torn_delta_keeps_stale_model(sqlite_storage):
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.workflow.model_io import delta_file_name, \
        model_key

    engine, ep, iid, app_id, es = _train_small(sqlite_storage)
    runner = FoldInRunner(
        sqlite_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
        from_now=True,
    )
    es.insert_batch(
        [_rate("tornuser", f"i{i}", 5.0, d=2) for i in (1, 3)],
        app_id=app_id,
    )
    runner.cycle()
    key = model_key(iid, runner.algo_ix, "als")
    p = runner.base_dir / delta_file_name(key, 1)
    raw = p.read_bytes()
    p.write_bytes(raw[: len(raw) // 2])
    srv = EngineServer(
        engine, ep, iid,
        ctx=WorkflowContext(storage=sqlite_storage, mode="Serving"),
        config=ServerConfig(port=0, microbatch="off"),
        engine_variant="live.json",
    )
    # torn link -> zero applied, full model serves, error surfaced
    assert srv.predict_json({"user": "u0", "num": 2})["itemScores"]
    assert srv.predict_json({"user": "tornuser", "num": 2})[
        "itemScores"] == []
    st = srv.status_json()
    assert "lastFoldinError" in st and "unreadable" in st[
        "lastFoldinError"]
    srv._foldin_stop.set()


def test_cli_foldin_once(sqlite_storage, tmp_path, monkeypatch):
    from predictionio_tpu.cli.main import main as cli_main

    engine, ep, iid, app_id, es = _train_small(sqlite_storage)
    variant = {
        "id": "default",
        "engineFactory":
            "predictionio_tpu.templates.recommendation."
            "recommendation_engine",
        "datasource": {"params": {"appName": "liveapp"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 6, "numIterations": 8, "lambda": 0.05}}],
    }
    ej = tmp_path / "live.json"
    ej.write_text(json.dumps(variant))
    es.insert_batch(
        [_rate("cliuser", f"i{i}", 5.0, d=2) for i in (1, 3)],
        app_id=app_id,
    )
    rc = cli_main(
        ["foldin", "--engine-json", str(ej),
         "--engine-instance-id", iid],
        storage=sqlite_storage,
    )
    assert rc == 0
    # the delta chain exists now
    from predictionio_tpu.workflow.model_io import (
        list_model_deltas, model_key,
    )
    base_dir = sqlite_storage.model_data_dir() / iid
    assert list_model_deltas(base_dir, model_key(iid, 0, "als"))


# ---------------------------------------------------------------------------
# per-shard fold-in watermarks (pio-hive satellite: vector cursors)
# ---------------------------------------------------------------------------


def test_watermark_vector_cursor_roundtrip_and_regress(tmp_path):
    """The sharded store's cursor is a JSON shard-vector STRING; the
    watermark file persists it opaquely and the backwards-move refusal
    applies PER SHARD."""
    from predictionio_tpu.live.watermark import (
        cursor_is_zero, cursor_would_regress, merge_cursors,
    )

    ws = WatermarkStore(tmp_path / "wm.json")
    vec = '{"0":5,"1":9,"2":0}'
    ws.advance(Watermark(1, 0, rowid=vec, seq=1))
    got = ws.get(1)
    assert got.rowid == vec and got.seq == 1
    # all components forward (or equal) is fine
    ws.advance(Watermark(1, 0, rowid='{"0":6,"1":9,"2":2}', seq=2))
    # ANY component moving backwards refuses
    with pytest.raises(ValueError, match="backwards"):
        ws.advance(Watermark(1, 0, rowid='{"0":7,"1":8,"2":2}', seq=3))
    # kind change mid-chain refuses too (store backend swapped)
    with pytest.raises(ValueError, match="backwards"):
        ws.advance(Watermark(1, 0, rowid=100, seq=3))
    # cursor algebra
    assert cursor_is_zero('{"0":0}') and cursor_is_zero(0)
    assert not cursor_is_zero(vec)
    assert merge_cursors(0, vec) == vec
    assert merge_cursors('{"0":1,"1":20}', '{"0":9,"1":2}') \
        == '{"0":9,"1":20}'
    assert merge_cursors(3, 7) == 7
    with pytest.raises(ValueError):
        merge_cursors(5, vec)
    assert cursor_would_regress(vec, '{"0":5,"1":8,"2":0}')
    assert not cursor_would_regress(vec, vec)


@pytest.fixture()
def sharded_storage(tmp_path):
    from predictionio_tpu.storage import Storage, reset_storage

    s = Storage(env={
        "PIO_TPU_HOME": str(tmp_path),
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "SHARDS",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQLITEMD",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "LOCALFS",
        "PIO_STORAGE_SOURCES_SHARDS_TYPE": "sqlite-sharded",
        "PIO_STORAGE_SOURCES_SHARDS_PATH": str(tmp_path / "ev-shards"),
        "PIO_STORAGE_SOURCES_SHARDS_SHARDS": "3",
        "PIO_STORAGE_SOURCES_SQLITEMD_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQLITEMD_PATH": str(tmp_path / "md.db"),
        "PIO_STORAGE_SOURCES_LOCALFS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_LOCALFS_PATH": str(tmp_path / "models"),
    })
    reset_storage(s)
    yield s
    reset_storage(None)


def test_runner_cycle_end_to_end_on_sharded_store(sharded_storage):
    """The headline of the satellite: fold-in WORKS on the sharded
    store (daemon.py used to refuse it), with a per-shard vector
    cursor advancing through watermark file + delta metadata."""
    from predictionio_tpu.controller import WorkflowContext

    engine, ep, iid, app_id, es = _train_small(sharded_storage)
    runner = FoldInRunner(
        sharded_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sharded_storage, mode="Serving"),
        from_now=True,
    )
    assert isinstance(runner.cursor, str)  # vector cursor from day one
    assert runner.cycle() is None          # from_now: history consumed
    es.insert_batch(
        [_rate("brand_new", f"i{i}", 5.0, d=2) for i in (1, 3, 5)],
        app_id=app_id,
    )
    assert runner.watermark_lag() == 3
    stats = runner.cycle()
    assert stats is not None and stats["appendedUsers"] == 1
    assert isinstance(stats["watermark"], str)
    assert runner.cycle() is None          # cursor advanced
    assert runner.watermark_lag() == 0
    # a restarted runner resumes from the persisted vector cursor
    r2 = FoldInRunner(
        sharded_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sharded_storage, mode="Serving"),
    )
    assert r2.seq == 1 and r2.cursor == runner.cursor
    assert r2.cycle() is None
    assert r2.model.users.get("brand_new") >= 0


def test_serving_foldin_status_on_sharded_store(sharded_storage):
    """The serving-side watermark-lag gauge understands vector
    cursors (cursor_lag) after a delta apply."""
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.server.serving import EngineServer, ServerConfig

    engine, ep, iid, app_id, es = _train_small(sharded_storage)
    srv = EngineServer(
        engine, ep, iid,
        ctx=WorkflowContext(storage=sharded_storage, mode="Serving"),
        config=ServerConfig(port=0, microbatch="off"),
        engine_variant="live.json",
    )
    runner = FoldInRunner(
        sharded_storage, engine, ep, iid,
        ctx=WorkflowContext(storage=sharded_storage, mode="Serving"),
        from_now=True,
    )
    es.insert_batch(
        [_rate("ghost", f"i{i}", 5.0, d=2) for i in (1, 3, 5)],
        app_id=app_id,
    )
    assert runner.cycle() is not None
    assert srv._apply_available_deltas() == 1
    out = srv.predict_json({"user": "ghost", "num": 3})
    assert len(out["itemScores"]) == 3
    st = srv.status_json()
    assert st["foldinWatermarkLag"] == 0
    # new unfolded rows count as lag, summed across shards
    es.insert_batch([_rate("ghost", "i7", 5.0, d=3),
                     _rate("ghost2", "i2", 4.0, d=3)], app_id=app_id)
    assert srv.status_json()["foldinWatermarkLag"] == 2
    srv._foldin_stop.set()
    srv._eval_stop.set()
