"""similarproduct / classification / ecommerce template tests
(reference `examples/scala-parallel-*` capability checklist, SURVEY §2.6)."""

import datetime as dt

import numpy as np
import pytest

from predictionio_tpu.controller import WorkflowContext
from predictionio_tpu.storage import DataMap, Event
from predictionio_tpu.workflow import prepare_deploy, run_train

UTC = dt.timezone.utc


def _t(m=0):
    return dt.datetime(2021, 1, 1, 0, m, tzinfo=UTC)


def _view(u, i, m=0):
    return Event(event="view", entity_type="user", entity_id=u,
                 target_entity_type="item", target_entity_id=i, event_time=_t(m))


# ---------------------------------------------------------------------------
# similarproduct
# ---------------------------------------------------------------------------


@pytest.fixture()
def similar_ctx(storage_memory):
    md = storage_memory.get_metadata()
    app = md.app_insert("simapp")
    es = storage_memory.get_event_store()
    es.init_channel(app.id)
    rng = np.random.default_rng(0)
    events = []
    # two item clusters: users co-view within a cluster
    for u in range(20):
        cluster = u % 2
        pool = [f"i{j}" for j in range(10) if j % 2 == cluster]
        for i in rng.choice(pool, size=4, replace=False):
            events.append(_view(f"u{u}", i))
    for j in range(10):
        events.append(
            Event(event="$set", entity_type="item", entity_id=f"i{j}",
                  properties=DataMap(
                      {"categories": ["even" if j % 2 == 0 else "odd"]}),
                  event_time=_t())
        )
    es.insert_batch(events, app_id=app.id)
    return WorkflowContext(storage=storage_memory)


SIM_VARIANT = {
    "datasource": {"params": {"appName": "simapp"}},
    "algorithms": [
        {"name": "als",
         "params": {"rank": 8, "numIterations": 10, "lambda": 0.1,
                    "alpha": 10.0}}
    ],
}


def test_similarproduct_end_to_end(similar_ctx):
    from predictionio_tpu.templates.similarproduct import (
        Query,
        similarproduct_engine,
    )

    e = similarproduct_engine()
    ep = e.params_from_variant(SIM_VARIANT)
    iid = run_train(e, ep, ctx=similar_ctx, engine_variant="sim.json")
    models = prepare_deploy(e, ep, iid, ctx=similar_ctx)
    algo = e._algorithms(ep)[0]
    res = algo.predict(models[0], Query(items=("i0",), num=3))
    assert len(res.item_scores) == 3
    items = [s.item for s in res.item_scores]
    assert "i0" not in items  # query item excluded
    evens = sum(1 for i in items if int(i[1:]) % 2 == 0)
    assert evens >= 2, f"expected same-cluster items, got {items}"


def test_similarproduct_custom_persistence_roundtrip(similar_ctx, tmp_path):
    """The npz save/load path (PersistentModel demo) must round-trip."""
    from predictionio_tpu.templates.similarproduct import (
        Query,
        similarproduct_engine,
    )

    e = similarproduct_engine()
    ep = e.params_from_variant(SIM_VARIANT)
    iid = run_train(e, ep, ctx=similar_ctx, engine_variant="sim.json")
    # fresh algorithm instances load from the custom manifest
    models = prepare_deploy(e, ep, iid, ctx=similar_ctx)
    m = models[0]
    assert m.item_factors.dtype == np.float32
    assert len(m.items) == 10
    # the categories persist as the index's arrays, not as a dict an item
    index = m.category_index
    assert sorted(index.names.tolist()) == ["even", "odd"]
    assert index.memberships == 10
    assert m.items.decode(np.flatnonzero(index.allowed(["even"], 10))
                          ).tolist() == ["i0", "i2", "i4", "i6", "i8"]
    # the index is the one owner of the categories, on the trained model
    # as on the deployed one
    trained = e.train(similar_ctx, ep)[0]
    assert m.item_props == trained.item_props == {}, \
        "nothing but `categories` was set"
    assert trained.category_index.names.tolist() == index.names.tolist()
    # model dir contains the npz, not a pickle
    mdir = similar_ctx.storage.model_data_dir() / iid
    assert any(p.suffix == ".npz" for p in mdir.iterdir())


def test_similarproduct_filters(similar_ctx):
    from predictionio_tpu.templates.similarproduct import (
        Query,
        similarproduct_engine,
    )

    e = similarproduct_engine()
    ep = e.params_from_variant(SIM_VARIANT)
    models = e.train(similar_ctx, ep)
    algo = e._algorithms(ep)[0]
    res = algo.predict(
        models[0], Query(items=("i0",), num=5, categories=("odd",))
    )
    for s in res.item_scores:
        assert int(s.item[1:]) % 2 == 1
    res = algo.predict(
        models[0], Query(items=("i0",), num=5, blacklist=("i2", "i4"))
    )
    assert not {"i2", "i4"} & {s.item for s in res.item_scores}
    assert algo.predict(models[0], Query(items=("ghost",), num=3)).item_scores == ()


def test_similarproduct_wire_format():
    from predictionio_tpu.templates.similarproduct import Query

    q = Query.from_json({"items": ["i1"], "num": 2, "whiteList": ["i3"]})
    assert q.items == ("i1",) and q.whitelist == ("i3",)


# ---------------------------------------------------------------------------
# classification
# ---------------------------------------------------------------------------


@pytest.fixture()
def class_ctx(storage_memory):
    md = storage_memory.get_metadata()
    app = md.app_insert("clsapp")
    es = storage_memory.get_event_store()
    es.init_channel(app.id)
    rng = np.random.default_rng(0)
    events = []
    for n in range(60):
        label = n % 2
        # class-distinct proportions (multinomial-NB-separable, like the
        # quickstart's integer attributes)
        probs = [0.7, 0.2, 0.1] if label == 0 else [0.1, 0.2, 0.7]
        counts = rng.multinomial(12, probs)
        events.append(
            Event(
                event="$set", entity_type="user", entity_id=f"u{n}",
                properties=DataMap({
                    "attr0": float(counts[0]),
                    "attr1": float(counts[1]),
                    "attr2": float(counts[2]),
                    "label": str(label),
                }),
                event_time=_t(),
            )
        )
    # one unlabeled user must be skipped
    events.append(
        Event(event="$set", entity_type="user", entity_id="nolabel",
              properties=DataMap({"attr0": 1.0}), event_time=_t())
    )
    es.insert_batch(events, app_id=app.id)
    return WorkflowContext(storage=storage_memory)


CLS_VARIANT = {
    "datasource": {"params": {"appName": "clsapp"}},
    "algorithms": [
        {"name": "naive", "params": {"lambda": 1.0}},
        {"name": "logistic", "params": {"steps": 200, "lr": 0.2}},
    ],
}


def test_classification_multi_algo(class_ctx):
    from predictionio_tpu.templates.classification import (
        Query,
        classification_engine,
    )

    e = classification_engine()
    ep = e.params_from_variant(CLS_VARIANT)
    iid = run_train(e, ep, ctx=class_ctx, engine_variant="cls.json")
    models = prepare_deploy(e, ep, iid, ctx=class_ctx)
    algos = e._algorithms(ep)
    assert len(models) == 2
    for algo, model in zip(algos, models):
        assert algo.predict(model, Query(features=(8.0, 2.0, 1.0))).label == "0"
        assert algo.predict(model, Query(features=(1.0, 2.0, 8.0))).label == "1"


def test_classification_quickstart_wire_format():
    from predictionio_tpu.templates.classification import Query

    q = Query.from_json({"attr0": 2, "attr1": 0, "attr2": 0})
    assert q.features == (2.0, 0.0, 0.0)


def test_classification_single_class_fails_sanity(storage_memory):
    from predictionio_tpu.templates.classification import classification_engine

    md = storage_memory.get_metadata()
    app = md.app_insert("oneclass")
    es = storage_memory.get_event_store()
    es.insert(
        Event(event="$set", entity_type="user", entity_id="u1",
              properties=DataMap({"attr0": 1.0, "attr1": 1.0, "attr2": 1.0,
                                  "label": "only"})),
        app_id=app.id,
    )
    ctx = WorkflowContext(storage=storage_memory)
    e = classification_engine()
    ep = e.params_from_variant(
        {"datasource": {"params": {"appName": "oneclass"}},
         "algorithms": [{"name": "naive"}]}
    )
    with pytest.raises(ValueError, match="two classes"):
        e.train(ctx, ep)


# ---------------------------------------------------------------------------
# ecommerce
# ---------------------------------------------------------------------------


@pytest.fixture()
def ecomm_ctx(storage_memory):
    md = storage_memory.get_metadata()
    app = md.app_insert("ecomm")
    es = storage_memory.get_event_store()
    es.init_channel(app.id)
    rng = np.random.default_rng(0)
    events = []
    for u in range(16):
        cluster = u % 2
        pool = [f"i{j}" for j in range(12) if j % 2 == cluster]
        for i in rng.choice(pool, size=4, replace=False):
            events.append(_view(f"u{u}", i))
    es.insert_batch(events, app_id=app.id)
    return WorkflowContext(storage=storage_memory), app.id


ECOMM_VARIANT = {
    "datasource": {"params": {"appName": "ecomm"}},
    "algorithms": [
        {"name": "ecomm",
         "params": {"rank": 8, "numIterations": 10, "lambda": 0.1,
                    "alpha": 10.0, "unseenOnly": True,
                    "seenEvents": ["view"]}}
    ],
}


def test_ecommerce_filters_seen_and_unavailable(ecomm_ctx):
    from predictionio_tpu.templates.ecommerce import ecommerce_engine
    from predictionio_tpu.templates.recommendation import Query

    ctx, app_id = ecomm_ctx
    es = ctx.storage.get_event_store()
    e = ecommerce_engine()
    ep = e.params_from_variant(ECOMM_VARIANT)
    iid = run_train(e, ep, ctx=ctx, engine_variant="ec.json")
    models = prepare_deploy(e, ep, iid, ctx=ctx)
    algo = e._algorithms(ep)[0]
    algo._ctx = ctx

    # the user's seen items are excluded (unseenOnly)
    seen = {
        ev.target_entity_id
        for ev in es.find(app_id=app_id, entity_type="user", entity_id="u0",
                          event_names=["view"])
    }
    res = algo.predict(models[0], Query(user="u0", num=6))
    rec_items = {s.item for s in res.item_scores}
    assert rec_items and not (rec_items & seen)

    # constraint entity marks items unavailable at serving time
    make_unavailable = sorted(rec_items)[0]
    es.insert(
        Event(event="$set", entity_type="constraint",
              entity_id="unavailableItems",
              properties=DataMap({"items": [make_unavailable]}),
              event_time=_t(1)),
        app_id=app_id,
    )
    res2 = algo.predict(models[0], Query(user="u0", num=6))
    assert make_unavailable not in {s.item for s in res2.item_scores}

    # clearing the constraint restores the item
    es.insert(
        Event(event="$set", entity_type="constraint",
              entity_id="unavailableItems",
              properties=DataMap({"items": []}), event_time=_t(2)),
        app_id=app_id,
    )
    res3 = algo.predict(models[0], Query(user="u0", num=6))
    assert make_unavailable in {s.item for s in res3.item_scores}


def test_ecommerce_unknown_user_empty(ecomm_ctx):
    from predictionio_tpu.templates.ecommerce import ecommerce_engine
    from predictionio_tpu.templates.recommendation import Query

    ctx, _ = ecomm_ctx
    e = ecommerce_engine()
    ep = e.params_from_variant(ECOMM_VARIANT)
    models = e.train(ctx, ep)
    algo = e._algorithms(ep)[0]
    assert algo.predict(models[0], Query(user="ghost", num=3)).item_scores == ()


def test_ecomm_query_camelcase_lists():
    """Reference wire format camelCase whiteList/blackList must decode."""
    from predictionio_tpu.templates.recommendation import Query

    q = Query.from_json({"user": "u1", "num": 4, "blackList": ["i3"],
                         "whiteList": ["i1", "i2"]})
    assert q.blacklist == ("i3",)
    assert q.whitelist == ("i1", "i2")


def test_classification_query_attr10_ordering():
    from predictionio_tpu.templates.classification import Query

    d = {f"attr{i}": float(i) for i in range(12)}
    q = Query.from_json(d)
    assert q.features == tuple(float(i) for i in range(12))


def test_classification_query_custom_attribute_names():
    from predictionio_tpu.templates.classification import Query

    q = Query.from_json({"age": 30, "income": 5.5})
    assert q.features == (30.0, 5.5)


def test_prepare_deploy_components_wires_ctx(ecomm_ctx):
    """prepare_deploy_components attaches the serving ctx so predict-time
    event-store reads hit the deployment's storage."""
    from predictionio_tpu.templates.ecommerce import ecommerce_engine
    from predictionio_tpu.templates.recommendation import Query
    from predictionio_tpu.workflow.train import prepare_deploy_components

    ctx, app_id = ecomm_ctx
    e = ecommerce_engine()
    ep = e.params_from_variant(ECOMM_VARIANT)
    iid = run_train(e, ep, ctx=ctx, engine_variant="ec2.json")
    algos, models, serving = prepare_deploy_components(e, ep, iid, ctx=ctx)
    assert algos[0]._ctx is ctx
    res = algos[0].predict(models[0], Query(user="u0", num=3))
    assert res.item_scores  # reads seen-events from ctx storage, no crash


def test_classification_batch_predict_matches_scalar():
    """All three classification algorithms vectorize batch_predict; the
    eval path must agree exactly with per-query predict."""
    import numpy as np

    from predictionio_tpu.controller.base import instantiate
    from predictionio_tpu.templates.classification import (
        ClassificationTrainingData,
        LogisticAlgorithm,
        LogisticParams,
        NaiveBayesAlgorithm,
        NaiveBayesParams,
        Query,
        RandomForestAlgorithm,
        RandomForestParams,
    )

    rng = np.random.default_rng(0)
    X = np.vstack([
        rng.multinomial(20, [0.8, 0.1, 0.1], size=60),
        rng.multinomial(20, [0.1, 0.1, 0.8], size=60),
    ]).astype(np.float32)
    labels = np.asarray(["a"] * 60 + ["b"] * 60, dtype=object)
    data = ClassificationTrainingData(features=X, labels=labels)
    queries = [Query(features=tuple(row)) for row in X[::7]]
    for cls, params in ((NaiveBayesAlgorithm, NaiveBayesParams()),
                        (LogisticAlgorithm, LogisticParams()),
                        (RandomForestAlgorithm, RandomForestParams())):
        algo = instantiate(cls, params)
        model = algo.train(None, data)
        batch = algo.batch_predict(model, queries)
        singles = [algo.predict(model, q) for q in queries]
        assert [b.label for b in batch] == [s.label for s in singles], cls
        assert algo.batch_predict(model, []) == []


def test_similarproduct_batch_predict_matches_single(similar_ctx):
    """batch_predict (the micro-batched serving + eval path) must match
    per-query predict, honor filters, keep the device batch at
    len(queries) despite unanswerable entries, and round k to pow2."""
    from predictionio_tpu.templates import similarproduct as smod

    engine = smod.similarproduct_engine()
    ep = engine.params_from_variant(SIM_VARIANT)
    models = engine.train(similar_ctx, ep)
    algo = engine._algorithms(ep)[0]
    model = models[0]

    shapes = []
    real = smod.batch_topk_scores_t

    def spy(vecs, tables, k, **filters):
        shapes.append((vecs.shape[0], k))
        # a trained model holds a category index: the batch's categories
        # ride as numbers beside its ids
        assert filters["allow"].numbers.shape == (5, 4)
        return real(vecs, tables, k, **filters)

    import unittest.mock as mock

    queries = [
        smod.Query(items=("i0",), num=3),
        smod.Query(items=("nope",), num=3),          # unanswerable
        smod.Query(items=("i1", "i3"), num=5),
        smod.Query(items=("i2",), num=3, categories=("even",)),
        smod.Query(items=("i4",), num=0),            # unanswerable
    ]
    with mock.patch.object(smod, "batch_topk_scores_t", spy):
        batch = algo.batch_predict(model, queries)
    assert shapes == [(5, 8)]  # full batch; k=5 -> pow2 8
    assert batch[1].item_scores == () and batch[4].item_scores == ()
    for q, b in zip(queries, batch):
        single = algo.predict(model, q)
        assert [s.item for s in b.item_scores] == [
            s.item for s in single.item_scores
        ], q
    # category filter respected in the batched path
    assert all(
        int(s.item[1:]) % 2 == 0 for s in batch[3].item_scores
    )
    # the serving layer now auto-enables the micro-batcher for this algo
    from predictionio_tpu.controller.base import Algorithm

    assert type(algo).batch_predict is not Algorithm.batch_predict


def test_ecommerce_batch_predict_matches_single(ecomm_ctx):
    """Ecommerce batch_predict: the event-store reads stay host work
    (seen items ONE read a batch, unavailable once a batch), their ids
    ride to the device, scoring is one shape-stable batched call of
    `batch_topk_scores_t`; results match per-query predict."""
    from predictionio_tpu.templates import ecommerce as emod

    ctx, app_id = ecomm_ctx
    engine = emod.ecommerce_engine()
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "ecomm"}},
        "algorithms": [{"name": "ecomm", "params": {
            "rank": 6, "numIterations": 5, "lambda": 0.1,
            "unseenOnly": True, "seenEvents": ["view"]}}],
    })
    models = engine.train(ctx, ep)
    algo = engine._algorithms(ep)[0]
    model = models[0]

    shapes = []
    real = emod.batch_topk_scores_t

    def spy(vecs, tables, k, mask=None, exclude=None):
        shapes.append((vecs.shape[0], k))
        assert mask is None and exclude is not None, "filters ride as ids"
        return real(vecs, tables, k, mask=mask, exclude=exclude)

    import unittest.mock as mock

    from predictionio_tpu.templates.recommendation import Query

    queries = [
        Query(user="u0", num=3),
        Query(user="ghost", num=3),       # unknown user
        Query(user="u1", num=5),
        Query(user="u2", num=3, blacklist=("i0", "i2")),
    ]
    with mock.patch.object(emod, "batch_topk_scores_t", spy):
        batch = algo.batch_predict(model, queries)
    assert shapes == [(4, 8)]  # full batch, k=5 -> pow2 8
    assert batch[1].item_scores == ()
    for q, b in zip(queries, batch):
        single = algo.predict(model, q)
        assert [s.item for s in b.item_scores] == [
            s.item for s in single.item_scores
        ], q
    # unseen-only honored in the batched path: u0 viewed items never
    # come back
    seen = set(algo._seen_items(model, ["u0"])[0])
    assert seen and not (
        {s.item for s in batch[0].item_scores} & seen
    )
    assert not {s.item for s in batch[3].item_scores} & {"i0", "i2"}


def test_warmup_ladder_covers_batcher_padding():
    """The warmup ladder must cover EVERY batch size the micro-batcher's
    pow2 padding can dispatch — including the pow2 CEILING of a
    non-pow2 max_batch (a 33..48-item batch under max_batch=48 pads to
    64), and the server must thread its configured microbatch_max into
    the warmup hook (ADVICE r4: sizes skipped by warmup compile
    mid-traffic, the exact p99 spike the padding exists to avoid)."""
    import inspect

    from predictionio_tpu.server.serving import _takes_max_batch
    from predictionio_tpu.templates._common import pow2_ladder

    assert pow2_ladder(64) == [1, 2, 4, 8, 16, 32, 64]
    assert pow2_ladder(48) == [1, 2, 4, 8, 16, 32, 64]
    assert pow2_ladder(1) == [1]
    assert pow2_ladder(0) == []  # no batcher -> no batched warms

    # every template warmup accepts the server's max_batch
    from predictionio_tpu.templates.classification import (
        RandomForestAlgorithm,
    )
    from predictionio_tpu.templates.ecommerce import ECommAlgorithm
    from predictionio_tpu.templates.recommendation import ALSAlgorithm
    from predictionio_tpu.templates.similarproduct import (
        SimilarProductAlgorithm,
    )

    for cls in (ALSAlgorithm, SimilarProductAlgorithm, ECommAlgorithm,
                RandomForestAlgorithm):
        assert "max_batch" in inspect.signature(cls.warmup).parameters, cls

    # the server-side dispatch recognizes old one-arg hooks
    class OldStyle:
        def warmup(self, model):
            pass

    class NewStyle:
        def warmup(self, model, max_batch=64):
            pass

    assert not _takes_max_batch(OldStyle().warmup)
    assert _takes_max_batch(NewStyle().warmup)


# ---------------------------------------------------------------------------
# similarproduct normalized-table migration (pio-lens satellite,
# ROADMAP 2(d))
# ---------------------------------------------------------------------------


def test_similarproduct_normalized_table_score_parity():
    """The migrated scorer (train-time normalized table, inner-product
    scoring) must agree with the OLD path (raw table + query-time
    normalization) wherever the two are mathematically identical:

    * the stored table rows are exactly the old path's normalized rows;
    * single-item queries score IDENTICALLY (one row's direction does
      not depend on when it was normalized);
    * multi-item queries over equal-norm rows score identically (the
      mean of equal-norm rows points where the mean of their unit rows
      does — the general unequal-norm case is the documented semantic
      refinement to itemsimilarity's query-vector convention).
    """
    import jax.numpy as jnp

    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.similarproduct import (
        Query,
        SimilarALSModel,
        SimilarProductAlgorithm,
    )

    rng = np.random.default_rng(11)
    raw = rng.normal(size=(12, 6)).astype(np.float32)
    # rows 0 and 1 share a norm so their mean direction is invariant
    raw[1] *= np.linalg.norm(raw[0]) / np.linalg.norm(raw[1])
    ids = [f"i{j}" for j in range(12)]

    def old_path_scores(query_items):
        # the pre-migration formula verbatim: mean of RAW rows,
        # normalized, against the query-time-normalized table
        known = [ids.index(i) for i in query_items]
        qvec = raw[known].mean(axis=0)
        qn = qvec / (np.linalg.norm(qvec) + 1e-9)
        tbl = jnp.asarray(raw)
        tn = np.asarray(
            tbl / (jnp.linalg.norm(tbl, axis=-1, keepdims=True) + 1e-9)
        )
        return tn @ qn

    from predictionio_tpu.templates._common import normalize_rows

    model = SimilarALSModel(
        item_factors=normalize_rows(raw),
        items=StringIndex(ids),
        item_props={},
    )
    # the stored table IS the old path's normalized table
    tbl = jnp.asarray(raw)
    old_tn = np.asarray(
        tbl / (jnp.linalg.norm(tbl, axis=-1, keepdims=True) + 1e-9)
    )
    np.testing.assert_allclose(model.item_factors, old_tn, atol=1e-6)

    algo = SimilarProductAlgorithm.__new__(SimilarProductAlgorithm)
    for query_items in (("i3",), ("i0", "i1")):
        res = algo.predict(model, Query(items=query_items, num=12))
        got = {s.item: s.score for s in res.item_scores}
        want = old_path_scores(query_items)
        for j, item in enumerate(ids):
            if item in query_items:
                continue  # excluded from results by design (both paths)
            assert item in got
            np.testing.assert_allclose(got[item], want[j], atol=1e-5)


def test_similarproduct_legacy_npz_normalized_on_load(tmp_path):
    """A pre-migration .npz (raw factors, no 'normalized' stamp) loads
    with its rows normalized exactly once; a stamped file is left
    alone (no double normalization — unit rows are a fixpoint, but the
    stamp proves the branch)."""
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates._common import normalize_rows
    from predictionio_tpu.templates.similarproduct import (
        SimilarALSModel,
        SimilarProductAlgorithm,
    )

    rng = np.random.default_rng(5)
    raw = (rng.normal(size=(6, 4)) * 3.0).astype(np.float32)
    ids = np.array([f"i{j}" for j in range(6)], dtype=str)
    legacy = tmp_path / "m-similar.npz"
    np.savez_compressed(legacy, item_factors=raw, item_ids=ids)
    (tmp_path / "m-props.json").write_text("{}")
    algo = SimilarProductAlgorithm.__new__(SimilarProductAlgorithm)
    manifest = {"npz": "m-similar.npz", "props": "m-props.json"}
    m = algo.load_model(None, "m", manifest, tmp_path)
    np.testing.assert_allclose(
        np.linalg.norm(m.item_factors, axis=1), 1.0, atol=1e-5
    )
    np.testing.assert_allclose(
        m.item_factors, normalize_rows(raw), atol=1e-6
    )
    # save_model stamps; loading the stamped file keeps rows bitwise
    model = SimilarALSModel(
        item_factors=normalize_rows(raw),
        items=StringIndex(list(ids)), item_props={},
    )
    out_dir = tmp_path / "stamped"
    manifest2 = algo.save_model(None, "m2", model, out_dir)
    m2 = algo.load_model(None, "m2", manifest2, out_dir)
    np.testing.assert_array_equal(m2.item_factors, model.item_factors)
