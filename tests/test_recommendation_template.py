"""End-to-end recommendation template test: events -> engine.json -> train ->
persist -> deploy -> predict (the Phase-2 slice of SURVEY §7)."""

import datetime as dt

import numpy as np
import pytest

from predictionio_tpu.controller import WorkflowContext
from predictionio_tpu.storage import DataMap, Event
from predictionio_tpu.templates.recommendation import (
    ALSAlgorithmParams,
    DataSourceParams,
    Query,
    recommendation_engine,
)
from predictionio_tpu.workflow import prepare_deploy, run_train

UTC = dt.timezone.utc


@pytest.fixture()
def ctx(storage_memory):
    md = storage_memory.get_metadata()
    app = md.app_insert("recapp")
    es = storage_memory.get_event_store()
    es.init_channel(app.id)
    rng = np.random.default_rng(0)
    # 12 users x 10 items block structure so recommendations are predictable:
    # users like items of their own group much more
    events = []
    for u in range(12):
        group = u % 2
        for i in range(10):
            in_group = (i % 2) == group
            if rng.random() < (0.8 if in_group else 0.3):
                r = 5.0 if in_group else 1.0
                events.append(
                    Event(
                        event="rate",
                        entity_type="user",
                        entity_id=f"u{u}",
                        target_entity_type="item",
                        target_entity_id=f"i{i}",
                        properties=DataMap({"rating": r}),
                        event_time=dt.datetime(2020, 1, 1, tzinfo=UTC),
                    )
                )
    # item properties for category filtering
    for i in range(10):
        events.append(
            Event(
                event="$set",
                entity_type="item",
                entity_id=f"i{i}",
                properties=DataMap({"categories": ["even" if i % 2 == 0 else "odd"]}),
                event_time=dt.datetime(2020, 1, 1, tzinfo=UTC),
            )
        )
    es.insert_batch(events, app_id=app.id)
    return WorkflowContext(storage=storage_memory, mode="Training")


VARIANT = {
    "id": "default",
    "engineFactory": "predictionio_tpu.templates.recommendation.recommendation_engine",
    "datasource": {
        "params": {"appName": "recapp", "eventNames": ["rate"]}
    },
    "algorithms": [
        {
            "name": "als",
            "params": {"rank": 8, "numIterations": 10, "lambda": 0.05, "seed": 3},
        }
    ],
}


def test_engine_json_camel_case_and_lambda_alias():
    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    ds = ep.data_source[1]
    assert isinstance(ds, DataSourceParams)
    assert ds.app_name == "recapp"
    algo = ep.algorithms[0][1]
    assert isinstance(algo, ALSAlgorithmParams)
    assert algo.num_iterations == 10
    assert algo.lam == 0.05


def test_train_and_predict_end_to_end(ctx):
    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    iid = run_train(e, ep, ctx=ctx, engine_variant="rec.json")
    models = prepare_deploy(e, ep, iid, ctx=ctx)
    algos = e._algorithms(ep)
    model = models[0]
    # group-0 user should prefer even items
    res = algos[0].predict(model, Query(user="u0", num=3))
    assert len(res.item_scores) == 3
    top_items = [s.item for s in res.item_scores]
    evens = sum(1 for it in top_items if int(it[1:]) % 2 == 0)
    assert evens >= 2, f"expected mostly even items for u0, got {top_items}"
    # scores descending
    scores = [s.score for s in res.item_scores]
    assert scores == sorted(scores, reverse=True)


@pytest.mark.parametrize("stored", [
    {"gather_dtype": "float32"},
    {"solver": "fused"},
], ids=["gather-dtype", "fused-solver"])
def test_a_record_written_before_a_knob_went_still_deploys(ctx, stored):
    """`pio deploy` serves with the params an instance's record holds.
    Records written while ``gather_dtype`` was a key hold its float32
    default; a model once trained with the fused kernel holds
    ``solver: "fused"``, read as the default route."""
    import dataclasses
    import json

    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    iid = run_train(e, ep, ctx=ctx, engine_variant="rec.json")
    md = ctx.storage.get_metadata()
    rec = md.engine_instance_get(iid)
    ((name, params),) = json.loads(rec.algorithms_params)[0].items()
    md.engine_instance_update(dataclasses.replace(
        rec, algorithms_params=json.dumps([{name: {**params, **stored}}])))
    deployed = e.params_from_instance(md.engine_instance_get(iid))
    algo_params = deployed.algorithms[0][1]
    assert algo_params.solver == "auto"
    models = prepare_deploy(e, deployed, iid, ctx=ctx)
    res = e._algorithms(deployed)[0].predict(models[0],
                                             Query(user="u0", num=3))
    assert len(res.item_scores) == 3


def test_unknown_user_returns_empty(ctx):
    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    models = e.train(ctx, ep)
    res = e._algorithms(ep)[0].predict(models[0], Query(user="ghost", num=3))
    assert res.item_scores == ()


def test_category_filter(ctx):
    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    models = e.train(ctx, ep)
    algo = e._algorithms(ep)[0]
    res = algo.predict(
        models[0], Query(user="u0", num=4, categories=("odd",))
    )
    assert res.item_scores
    for s in res.item_scores:
        assert int(s.item[1:]) % 2 == 1, f"category filter leaked: {s.item}"


def test_whitelist_blacklist(ctx):
    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    models = e.train(ctx, ep)
    algo = e._algorithms(ep)[0]
    res = algo.predict(
        models[0], Query(user="u0", num=5, whitelist=("i0", "i1"))
    )
    assert {s.item for s in res.item_scores} <= {"i0", "i1"}
    res = algo.predict(models[0], Query(user="u0", num=10, blacklist=("i0",)))
    assert "i0" not in {s.item for s in res.item_scores}


def test_batch_predict_matches_single(ctx):
    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    models = e.train(ctx, ep)
    algo = e._algorithms(ep)[0]
    queries = [Query(user=f"u{u}", num=3) for u in range(4)] + [
        Query(user="ghost", num=3)
    ]
    batch = algo.batch_predict(models[0], queries)
    for q, b in zip(queries, batch):
        single = algo.predict(models[0], q)
        assert [s.item for s in b.item_scores] == [
            s.item for s in single.item_scores
        ]
    assert batch[-1].item_scores == ()


def test_batch_predict_shape_stable_under_invalid_queries(ctx,
                                                          monkeypatch):
    """The device batch size must equal len(queries) even when some
    queries are invalid, and k must round to pow2 — the micro-batcher's
    executable-count bound depends on it (a dropped row would compile a
    fresh (B-1)-sized XLA executable mid-traffic)."""
    from predictionio_tpu.templates import recommendation as rmod

    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    models = e.train(ctx, ep)
    algo = e._algorithms(ep)[0]
    shapes = []
    real = rmod.batch_topk_scores_t

    def spy(vecs, table_t, k, mask=None):
        shapes.append((vecs.shape[0], k))
        return real(vecs, table_t, k, mask=mask)

    monkeypatch.setattr(rmod, "batch_topk_scores_t", spy)
    queries = [Query(user="u0", num=3), Query(user="ghost", num=3),
               Query(user="u1", num=0), Query(user="u2", num=3)]
    out = algo.batch_predict(models[0], queries)
    # full batch went to the device; k=3 rounded up to 4
    assert shapes == [(4, 4)]
    assert out[1].item_scores == () and out[2].item_scores == ()
    assert len(out[0].item_scores) == 3 and len(out[3].item_scores) == 3
    single = algo.predict(models[0], queries[0])
    assert [s.item for s in out[0].item_scores] == [
        s.item for s in single.item_scores
    ]


def test_query_wire_format():
    q = Query.from_json({"user": "u1", "num": 4, "categories": ["a"]})
    assert q.user == "u1" and q.num == 4 and q.categories == ("a",)
    from predictionio_tpu.templates.recommendation import (
        ItemScore,
        PredictedResult,
    )

    r = PredictedResult(item_scores=(ItemScore("i1", 1.5),))
    assert r.to_json() == {"itemScores": [{"item": "i1", "score": 1.5}]}


def test_read_eval_kfold(ctx):
    e = recommendation_engine()
    variant = {
        **VARIANT,
        "datasource": {
            "params": {"appName": "recapp", "evalK": 3}
        },
    }
    ep = e.params_from_variant(variant)
    ds = e._data_source(ep)
    sets = ds.read_eval(ctx)
    assert len(sets) == 3
    total_test = sum(len(qa) for _, _, qa in sets)
    total_train = len(sets[0][0].ratings) + len(sets[0][2])
    # folds partition the data
    all_ratings = ds.read_training(ctx).ratings
    assert total_test == len(all_ratings)
    assert total_train == len(all_ratings)


def test_empty_app_fails_sanity(storage_memory):
    md = storage_memory.get_metadata()
    md.app_insert("emptyapp")
    ctx = WorkflowContext(storage=storage_memory)
    e = recommendation_engine()
    ep = e.params_from_variant(
        {**VARIANT, "datasource": {"params": {"appName": "emptyapp"}}}
    )
    with pytest.raises(ValueError, match="no rating events"):
        e.train(ctx, ep)


def test_batch_predict_honors_filters(ctx):
    """batch_predict must apply the same filters as predict (blacklist)."""
    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    models = e.train(ctx, ep)
    algo = e._algorithms(ep)[0]
    queries = [
        Query(user="u0", num=5, blacklist=("i0", "i2")),
        Query(user="u1", num=3, categories=("odd",)),
        Query(user="u2", num=3),
    ]
    batch = algo.batch_predict(models[0], queries)
    assert not {"i0", "i2"} & {s.item for s in batch[0].item_scores}
    for s in batch[1].item_scores:
        assert int(s.item[1:]) % 2 == 1
    for q, b in zip(queries, batch):
        single = algo.predict(models[0], q)
        assert [s.item for s in b.item_scores] == [
            s.item for s in single.item_scores
        ]


def test_rmse_evaluation_sweep(ctx, tmp_path, monkeypatch):
    """k-fold RMSE sweep over ALS hyperparameters: better rank/iters should
    win, best.json written (the BASELINE 'e2 evaluation workflow' config)."""
    import json
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithmParams,
        recommendation_evaluation,
    )
    from predictionio_tpu.workflow import run_evaluation

    monkeypatch.chdir(tmp_path)
    evaluation = recommendation_evaluation()
    ds = DataSourceParams(app_name="recapp", eval_k=2)
    candidates = [
        EngineParams(
            data_source=("", ds),
            algorithms=[("als", ALSAlgorithmParams(
                rank=r, num_iterations=it, lam=0.1, seed=3))],
        )
        for r, it in [(2, 1), (8, 8)]
    ]
    eval_id, result = run_evaluation(evaluation, candidates, ctx=ctx)
    assert result.metric_header == "RMSE"
    scores = [s for _, s, _ in result.results]
    assert all(np.isfinite(s) for s in scores)
    # the stronger configuration must achieve lower error
    assert result.best_engine_params.algorithms[0][1].rank == 8
    assert result.best_score == min(scores)
    doc = json.loads((tmp_path / "best.json").read_text())
    assert doc["algorithms"][0]["params"]["rank"] == 8


def test_bfloat16_serving_matches_f32_ranking(ctx):
    """serving_dtype=bfloat16 halves scoring reads; the semantics are:
    bf16 may reorder items whose f32 scores are within bf16 rounding of
    each other (near-ties), but must agree with f32 on well-separated
    scores, and every reported score must match f32 within bf16 epsilon
    (training is untouched)."""
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSAlgorithmParams)

    e = recommendation_engine()
    ep = e.params_from_variant(VARIANT)
    models = e.train(ctx, ep)
    model = models[0]

    from predictionio_tpu.controller.base import instantiate

    f32 = instantiate(ALSAlgorithm, ALSAlgorithmParams(rank=8, num_iterations=10))
    bf16 = instantiate(
        ALSAlgorithm,
        ALSAlgorithmParams(rank=8, num_iterations=10,
                           serving_dtype="bfloat16"),
    )
    bf16.warmup(model)
    # rank ALL items so the two results are permutations of each other
    q = Query(user="u1", num=50)
    a = f32.predict(model, q)
    b = bf16.predict(model, q)
    assert {s.item for s in a.item_scores} == {s.item for s in b.item_scores}
    f32_score = {s.item: s.score for s in a.item_scores}
    scale = max(1.0, max(abs(v) for v in f32_score.values()))
    # bf16 has an 8-bit mantissa: relative rounding ~2^-8; allow a few ulp
    tie_tol = 0.04 * scale
    for sa, sb in zip(a.item_scores, b.item_scores):
        if sa.item != sb.item:
            # positional swaps are legal only among near-tied f32 scores
            gap = abs(f32_score[sa.item] - f32_score[sb.item])
            assert gap < tie_tol, (
                f"bf16 reordered well-separated items {sa.item} vs "
                f"{sb.item} (f32 gap {gap:.4f} >= {tie_tol:.4f})"
            )
        # reported score must match the f32 score of the SAME item
        assert abs(sb.score - f32_score[sb.item]) < 0.05 * max(
            1.0, abs(f32_score[sb.item])
        )


def test_engine_json_exposes_scaling_knobs(ctx):
    """solver / factorPlacement / gatherMode ride engine.json params to
    the trainer — the reference's engine.json is the one config surface a
    template user touches, so the scaling story must be reachable there."""
    from predictionio_tpu.templates.recommendation import (
        Query, recommendation_engine,
    )

    engine = recommendation_engine()
    params = engine.params_from_variant({
        "datasource": {"params": {"appName": "recapp",
                                  "eventNames": ["rate"]}},
        "algorithms": [{
            "name": "als",
            "params": {
                "rank": 4, "numIterations": 2, "lambda": 0.1,
                "solver": "pallas", "factorPlacement": "sharded",
                "gatherMode": "grouped",
            },
        }],
    })
    algo_params = params.algorithms[0][1]
    assert algo_params.solver == "pallas"
    assert algo_params.factor_placement == "sharded"
    assert algo_params.gather_mode == "grouped"
    algos, models = engine.train_components(ctx, params)
    model = models[0]
    assert np.isfinite(model.user_factors).all()
    r = algos[0].predict(model, Query(user=model.users.ids[0], num=2))
    assert len(r.item_scores) == 2


def test_coo_local_placement_mismatch_rejected_at_config_time():
    """coo='local' + replicated placement must fail at params
    construction (build/validate time), not minutes into a multi-host
    ingest."""
    from predictionio_tpu.templates.recommendation import (
        recommendation_engine,
    )

    engine = recommendation_engine()
    with pytest.raises(ValueError, match="factorPlacement='sharded'"):
        engine.params_from_variant({
            "datasource": {"params": {"appName": "x", "coo": "local"}},
            "algorithms": [{"name": "als", "params": {"rank": 4}}],
        })
    # the valid pairing still constructs
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "x", "coo": "local"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 4, "factorPlacement": "sharded"}}],
    })
    assert ep.algorithms[0][1].factor_placement == "sharded"


def test_read_training_fused_path_matches_general(tmp_path):
    """The DataSource's fused native read (sqlite find_ratings) must
    produce the SAME TrainingData as the general columnar path (memory
    store): identical id dictionaries, identical deduped COO.  This is
    the user-facing `pio-tpu train` read, so the two storage backends
    must be indistinguishable above the store layer."""
    from predictionio_tpu.storage import Storage, reset_storage
    from predictionio_tpu.templates.recommendation import (
        RecommendationDataSource,
    )

    rng = np.random.default_rng(9)
    events = []
    for _ in range(500):
        events.append(Event(
            event="rate", entity_type="user",
            entity_id=f"u{rng.integers(0, 30)}",
            target_entity_type="item",
            target_entity_id=f"i{rng.integers(0, 12)}",
            properties=DataMap({"rating": float(rng.integers(1, 6))}),
            event_time=dt.datetime(2020, 1, 1,
                                   minute=int(rng.integers(0, 59)),
                                   tzinfo=UTC),
        ))
    # a buy event the rate-only read must ignore
    events.append(Event(event="buy", entity_type="user", entity_id="u0",
                        target_entity_type="item", target_entity_id="i0"))

    results = []
    for kind in ("memory", "sqlite"):
        env = {"PIO_TPU_HOME": str(tmp_path / kind)}
        if kind == "memory":
            env.update({
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "M",
                "PIO_STORAGE_SOURCES_M_TYPE": "memory",
            })
        s = Storage(env=env)
        md = s.get_metadata()
        app = md.app_insert("fusedapp")
        es = s.get_event_store()
        es.init_channel(app.id)
        es.insert_batch(events, app_id=app.id)
        from predictionio_tpu.controller.base import instantiate

        ds = instantiate(
            RecommendationDataSource,
            DataSourceParams(app_name="fusedapp"),
        )
        td = ds.read_training(WorkflowContext(storage=s, mode="Training"))
        results.append(td)
        if kind == "sqlite":
            from predictionio_tpu.native import native_available

            # the fused path must have engaged where the lib exists;
            # hosts without a toolchain legitimately take the fallback
            expected = "native" if native_available() else "python"
            assert es.last_ratings_scan_path == expected
        s.close()
        reset_storage(None)

    a, b = results
    assert list(a.ratings.users.ids) == list(b.ratings.users.ids)
    assert list(a.ratings.items.ids) == list(b.ratings.items.ids)
    ka = np.lexsort((a.ratings.item_ix, a.ratings.user_ix))
    kb = np.lexsort((b.ratings.item_ix, b.ratings.user_ix))
    assert np.array_equal(a.ratings.user_ix[ka], b.ratings.user_ix[kb])
    assert np.array_equal(a.ratings.item_ix[ka], b.ratings.item_ix[kb])
    assert np.allclose(a.ratings.rating[ka], b.ratings.rating[kb])
    assert a.items == b.items


def test_transposed_device_cache_patches_with_deltas():
    """pio-surge x pio-live: the pre-transposed [R, M] serving table
    (the fast batched-matmul layout) must patch column-wise under a
    fold-in delta — patched rows, appended rows, every dtype cache —
    and stay bitwise-equal to a fresh transpose of the patched host
    table."""
    import numpy as np

    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import ALSModel

    rng = np.random.default_rng(11)
    model = ALSModel(
        user_factors=rng.normal(size=(4, 8)).astype(np.float32),
        item_factors=rng.normal(size=(6, 8)).astype(np.float32),
        users=StringIndex([f"u{i}" for i in range(4)]),
        items=StringIndex([f"i{i}" for i in range(6)]),
        item_props={},
    )
    t0 = np.asarray(model.device_item_factors_t())
    assert t0.shape == (8, 6)
    np.testing.assert_array_equal(t0, model.item_factors.T)
    # patch rows 1 and 4, append two new rows
    new_rows = rng.normal(size=(2, 8)).astype(np.float32)
    appended = rng.normal(size=(2, 8)).astype(np.float32)
    host = np.concatenate([model.item_factors, appended], axis=0)
    host[[1, 4]] = new_rows
    model.item_factors = host
    model.patch_device_item_rows([1, 4], new_rows, appended)
    t1 = np.asarray(model.device_item_factors_t())
    assert t1.shape == (8, 8)
    np.testing.assert_array_equal(t1, host.T)
    # the batched scorer over the patched transposed cache agrees with
    # a dense numpy argmax ranking
    from predictionio_tpu.ops.topk import batch_topk_scores_t

    q = rng.normal(size=(2, 8)).astype(np.float32)
    vals, ixs = batch_topk_scores_t(q, model.device_item_factors_t(), 3)
    ref = np.argsort(-(q @ host.T), axis=1)[:, :3]
    np.testing.assert_array_equal(np.asarray(ixs), ref)


@pytest.mark.parametrize("m,r", [
    (8, 8),       # exactly one (8, 128)-class tile row block
    (127, 8),     # one short of the f32 sublane boundary
    (128, 16),    # exactly on it
    (129, 16),    # one past it (tail row)
    (261, 32),    # multi-tile with a ragged tail
])
def test_device_cache_patch_tile_boundary_shapes(m, r):
    """pio-scout satellite: the PR 11 parity test covered ONE shape;
    the column-wise transposed patch (and now the quantized-table
    patch) must hold at tile-boundary and tail sizes too — patched
    rows at the edges, appends crossing the boundary, every cached
    layout bitwise-consistent with a rebuild from the patched host
    table."""
    import numpy as np

    from predictionio_tpu.ops.ann import quantize_rows
    from predictionio_tpu.retrieval import RetrievalConfig
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import ALSModel

    rng = np.random.default_rng(m * 1000 + r)
    model = ALSModel(
        user_factors=rng.normal(size=(3, r)).astype(np.float32),
        item_factors=rng.normal(size=(m, r)).astype(np.float32),
        users=StringIndex([f"u{i}" for i in range(3)]),
        items=StringIndex([f"i{i}" for i in range(m)]),
        item_props={},
    )
    # build every cache the serving path can hold: plain, transposed,
    # normalized, and the quantized ANN index
    model.device_item_factors()
    model.device_item_factors_t()
    model.device_item_factors_normalized()
    cfg = RetrievalConfig(mode="int8", candidate_factor=max(m, 1))
    model.device_ann_index(cfg)

    # patch the first row, a tile-edge row, and the last row; append
    # enough rows to cross the next boundary
    ixs = sorted({0, m // 2, m - 1})
    new_rows = rng.normal(size=(len(ixs), r)).astype(np.float32)
    appended = rng.normal(size=(9, r)).astype(np.float32)
    host = np.concatenate([model.item_factors, appended], axis=0)
    host[ixs] = new_rows
    model.item_factors = host
    model.patch_device_item_rows(ixs, new_rows, appended)
    model.patch_ann_indexes(ixs, new_rows, appended)

    np.testing.assert_array_equal(
        np.asarray(model.device_item_factors()), host
    )
    np.testing.assert_array_equal(
        np.asarray(model.device_item_factors_t()), host.T
    )
    norm = host / (
        np.linalg.norm(host, axis=-1, keepdims=True) + 1e-9
    )
    np.testing.assert_allclose(
        np.asarray(model.device_item_factors_normalized()), norm,
        rtol=1e-6,
    )
    # the quantized table patched in place == quantizing the patched
    # host table from scratch (bitwise: same rounding, same scales)
    idx = model.device_ann_index(cfg)
    assert idx.n_items == m + 9
    q_ref, s_ref = quantize_rows(host)
    np.testing.assert_array_equal(
        np.asarray(idx._state["q_table_t"]), q_ref.T
    )
    np.testing.assert_array_equal(
        np.asarray(idx._state["scale"]), s_ref
    )
