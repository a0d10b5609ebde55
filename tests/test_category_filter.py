"""A query's `categories` as numbers on the device
(`ops.topk.batch_topk_scores_t(allow=)`, `_common.CategoryIndex`,
`_common.batch_filter`'s kind "cats"): the blocked top-k with the allowed
bits tested inside the scan and on the chosen blocks equals the plain
reference (`perfbench/reference/simcat_ref.py`) id for id, through
`batch_predict` and through a live `EngineServer`, for narrow, wide, unknown,
several and absent categories, mixed rows in one batch, categories beside
seeds and a 32-id blackList, and a category whose best items are all
excluded; the index round-trips `train` -> persist -> `deploy`; nothing of
the catalogue's length is built on the host or written a row on the device
wider than one bit an item; a warmed server compiles nothing for its first
category query; what still takes the `[B, M]` mask is counted."""

import functools
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import simcat_ref
from predictionio_tpu.ops import topk
from predictionio_tpu.templates import _common
from predictionio_tpu.templates import similarproduct as smod

M, R = 40_009, 32
WIDTH = topk.EXCLUDE_LADDER[0]
SLOTS = topk.CATEGORY_SLOTS


def _unit_rows(m, r, seed=0):
    rows = np.random.default_rng(seed).normal(size=(m, r)).astype(np.float32)
    return rows / np.linalg.norm(rows, axis=1, keepdims=True)


@functools.lru_cache(maxsize=None)
def _catalogue():
    """(table, item_cats `[M, 3]`, names): three departments, twenty
    shelves, four hundred tags, so that a department is wide (a third of
    the items), a tag narrow (some 100 items) and `tiny` holds 4 items."""
    rng = np.random.default_rng(5)
    table = _unit_rows(M, R)
    cats = np.stack([rng.integers(0, 3, M), 3 + rng.integers(0, 20, M),
                     23 + rng.integers(0, 400, M)], axis=1).astype(np.int32)
    cats[[11, 5000, 20000, 39000], 2] = 423       # "tiny"
    names = ([f"dept{j}" for j in range(3)] + [f"shelf{j}" for j in range(20)]
             + [f"tag{j}" for j in range(400)] + ["tiny"])
    return table, cats, names


def _model(with_index=True):
    from predictionio_tpu.storage.bimap import StringIndex

    table, cats, names = _catalogue()
    index = _common.CategoryIndex.from_memberships(
        names, cats.reshape(-1), np.repeat(np.arange(M), 3))
    return smod.SimilarALSModel(
        item_factors=table, items=StringIndex([f"i{j}" for j in range(M)]),
        item_props={}, category_index=index if with_index else None)


def _query(seeds, categories=(), blacklist=(), num=10):
    names = _catalogue()[2]
    return smod.Query(
        items=tuple(f"i{ix}" for ix in seeds), num=num,
        categories=tuple(names[c] if isinstance(c, int) else c
                         for c in categories) or None,
        blacklist=tuple(f"i{ix}" for ix in blacklist) or None)


def _as_reference_query(query):
    names = _catalogue()[2]
    return {"seeds": [int(i[1:]) for i in query.items],
            "blacklist": [int(i[1:]) for i in query.blacklist or ()],
            "categories": [names.index(c) if c in names else 9999
                           for c in query.categories or ()]}


def _held_against_the_reference(queries, results):
    """Each query against the reference at its own `num`; the number of
    queries whose answer is the same with the categories ignored."""
    table, cats, _ = _catalogue()
    blind = 0
    for query, result in zip(queries, results):
        served = [int(s.item[1:]) for s in result.item_scores]
        mine = [_as_reference_query(query)]
        out = simcat_ref.compare(
            table, jnp.asarray(table), cats, mine, [served],
            [[s.score for s in result.item_scores]], query.num)
        assert out["rank_gap"] <= 1e-5 and out["score_err"] <= 1e-5, out
        for name in ("answers_with_repeats", "answers_with_excluded",
                     "answers_outside_categories", "answers_short"):
            assert out[name] == 0, (name, query, out)
        want, _ = simcat_ref.answer(table, jnp.asarray(table), cats, mine,
                                    query.num)
        allowed = int(out["per_query"]["allowed"][0])
        assert served == want[0][:min(query.num, allowed)].tolist(), query
        blind += int(out["answers_filter_blind"])
    return blind


def _cases():
    table, cats, _ = _catalogue()
    near11 = np.argsort(-(table @ table[11]))[:40]
    dept_of_7 = int(cats[7, 0])
    best_in_dept = [int(ix) for ix in np.argsort(-(table @ table[7]))
                    if cats[ix, 0] == dept_of_7 and ix != 7][:WIDTH - 1]
    return {
        "wide": _query([5], [int(cats[5, 0])]),
        "narrow_fewer_than_num": _query([11], ["tiny"]),
        "unknown": _query([12], ["no such category"]),
        "unknown_beside_a_known": _query([12], ["nope", int(cats[12, 1])]),
        "several": _query([13, 14], [int(cats[13, 1]), int(cats[13, 2]),
                                     int(cats[14, 2]), 0]),
        "absent": _query([15]),
        "with_a_32_id_blacklist": _query(
            [16], [int(cats[16, 1])], blacklist=near11[:WIDTH - 1].tolist()),
        # every one of the department's 31 best items is blackListed: the
        # blocks the scan ranks first hold nothing the row may be served
        "best_items_all_excluded": _query([7], [dept_of_7],
                                          blacklist=best_in_dept),
        "three_seeds": _query([17, 18, 19], [int(cats[17, 0])], num=16),
        "one_item": _query([20], [int(cats[20, 2])], num=1),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_a_lone_query_equals_the_reference(name):
    model, algo = _model(), smod.SimilarProductAlgorithm()
    query = _cases()[name]
    result = algo.predict(model, query)
    _held_against_the_reference([query], [result])
    if name == "narrow_fewer_than_num":
        assert len(result.item_scores) == 3, "four items, one the seed"
    if name == "unknown":
        assert result.item_scores == ()


def test_mixed_rows_in_one_batch_equal_the_reference():
    """Rows with and without categories, narrow and wide, in one batch of
    every rung the batcher pads to: each under its own filters; a row
    without `categories` in a batch that has them allows everything."""
    model, algo = _model(), smod.SimilarProductAlgorithm()
    queries = list(_cases().values())
    cats_rows = _common.FILTER_ROWS.labels(filter="cats").value()
    mask_rows = _common.FILTER_ROWS.labels(filter="mask").value()
    numbers = _common.FILTER_CATEGORY_IDS.value()
    calls = topk.TOPK_PATH.labels(path="blocked_cats").value()
    for n in (len(queries), 8, 3, 1):
        blind = _held_against_the_reference(
            queries[:n], algo.batch_predict(model, queries[:n]))
        # the one row that names no category, and no other
        assert blind == sum(not q.categories for q in queries[:n])
    rows = len(queries) + 8 + 3 + 1
    assert _common.FILTER_ROWS.labels(filter="cats").value() == \
        cats_rows + rows
    assert _common.FILTER_ROWS.labels(filter="mask").value() == mask_rows
    assert _common.FILTER_CATEGORY_IDS.value() > numbers
    assert topk.TOPK_PATH.labels(path="blocked_cats").value() == calls + 4


@pytest.mark.parametrize("blk,rank", [(64, 128), (32, 128), (16, 64),
                                      (8, 128)])
def test_every_block_size_with_the_kernel_and_the_tpus_rounding(
        blk, rank, monkeypatch):
    """What the chip runs: the scan kernel (through the interpreter) with
    bfloat16 operands and the bits tested inside it, at every block size
    (a block is 64, 32, 16 or 8 consecutive bits of a lane's words)."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    m = 33_001
    rows = _unit_rows(m, rank, seed=blk)
    rng = np.random.default_rng(blk)
    members = [np.sort(rng.choice(m, size=n, replace=False))
               for n in (3, 700, m // 3, m // 2, 40)]
    offsets = np.concatenate([[0], np.cumsum([len(x) for x in members])])
    index = _common.CategoryIndex(list("abcde"), offsets,
                                  np.concatenate(members))
    holder = type("Holder", (_common.DeviceTableMixin,), {})()
    holder.category_index, holder.item_factors = index, rows
    resident = holder.device_category_rows()
    assert resident.shape == (7, topk.allow_words(m) // 1024, 8, 128)
    seeds = rng.integers(0, m, 9)
    q = rows[seeds]
    numbers = np.full((9, SLOTS), -1, np.int32)
    for b, pick in enumerate([[1], [2, 3], [], [0, 4, 5], [5], [3], [2],
                              [1, 0], [4]]):
        numbers[b, :len(pick)] = pick
    exclude = np.full((9, WIDTH), -1, np.int32)
    exclude[:, 0] = seeds
    tables = topk.ItemTables(None, jnp.asarray(rows)) if rank == 128 else \
        topk.ItemTables(jnp.asarray(rows.T), topk.pack_rows(jnp.asarray(rows)))
    vals, ixs = jax.jit(functools.partial(topk._blocked_topk, k=16, blk=blk))(
        jnp.asarray(q), tables, exclude=jnp.asarray(exclude),
        allow=topk.Allowed(jnp.asarray(numbers), resident))

    def rounded(x):
        return np.asarray(jax.lax.reduce_precision(jnp.asarray(x), 8, 7))

    s = rounded(q) @ rounded(rows).T
    for b in range(9):
        named = [c for c in numbers[b] if c >= 0]
        allowed = np.zeros(m, bool) if named else np.ones(m, bool)
        for c in named:
            if c < 5:
                allowed[members[c]] = True
        allowed[seeds[b]] = False
        order = np.argsort(-np.where(allowed, s[b], -np.inf), kind="stable")
        n = min(16, int(allowed.sum()))
        np.testing.assert_array_equal(np.asarray(ixs)[b, :n], order[:n])
        np.testing.assert_allclose(np.asarray(vals)[b, :n], s[b, order[:n]],
                                   atol=1e-6)
        assert np.isneginf(np.asarray(vals)[b, n:]).all()


def test_bit_rows_are_the_layout_the_scan_tests():
    """Bit g of the word at lane l of line w is item (32 w + g) * 128 + l;
    a row is whole (8, 128) tiles; the last two rows are no item and every
    item."""
    m = 70_001
    ids = np.array([0, 1, 127, 128, 4095, 4096, 32767, 32768, 70_000])
    rows = topk.category_bit_rows(np.array([0, len(ids)]), ids, m, 0, 1)
    assert rows.shape == (1, topk.allow_words(m)) and rows.dtype == np.uint32
    assert topk.allow_words(m) == 3 * 1024 and topk.allow_words(32768) == 1024
    want = np.zeros(topk.allow_words(m), np.uint32)
    for i in ids:
        want[(i // 4096) * 128 + i % 128] |= np.uint32(1) << ((i // 128) % 32)
    np.testing.assert_array_equal(rows[0], want)
    got = np.asarray(topk._allowed_items(jnp.asarray(rows),
                                         jnp.arange(m, dtype=jnp.int32)))
    assert np.flatnonzero(got[0]).tolist() == ids.tolist()
    # a piece of several categories, an empty one among them
    offsets = np.array([0, 2, 2, 5])
    rows = topk.category_bit_rows(offsets, np.array([3, 9, 1, 3, 200]), m, 1, 3)
    assert rows.shape[0] == 2 and not rows[0].any()
    assert int(np.unpackbits(rows[1].view(np.uint8)).sum()) == 3


def test_nothing_wider_than_one_bit_an_item_a_row_is_written(monkeypatch):
    """The chip's form at the cell's size: ONE `top_k`; no float or bool
    value with an axis of the catalogue's length; the widest values a row
    are its allowed words (M / 32 of them) and its block maxima."""
    monkeypatch.setattr(topk, "_mxu_operands", lambda: True)
    b, k, r, m, n = 64, 16, 128, 9_350_000, 4096
    blk = topk.block_items(b, m, r, k, n_exclude=WIDTH)
    jaxpr = jax.make_jaxpr(
        functools.partial(topk._blocked_topk, k=k, blk=blk)
    )(jax.ShapeDtypeStruct((b, r), jnp.float32),
      topk.ItemTables(None, jax.ShapeDtypeStruct((m, r), jnp.float32)),
      exclude=jax.ShapeDtypeStruct((b, WIDTH), jnp.int32),
      allow=topk.Allowed(
          jax.ShapeDtypeStruct((b, SLOTS), jnp.int32),
          jax.ShapeDtypeStruct((n + 2, topk.allow_words(m) // 1024, 8, 128),
                               jnp.uint32)))

    def values(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                yield eqn.primitive.name, v.aval
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) \
                        else [param]:
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from values(inner)

    seen = list(values(jaxpr.jaxpr))
    assert [name for name, _ in seen].count("top_k") == 2   # values, ids
    words = topk.allow_words(m)
    for name, aval in seen:
        shape = getattr(aval, "shape", ())
        size = int(np.prod(shape or (1,)))
        if aval.dtype in (jnp.uint32, jnp.int32) and size >= b * words:
            assert size == b * words, (name, shape)     # the batch's bits
        elif size > b * m // 32 and shape[-1:] != (r,):
            # (the chosen blocks' gathered rows are the parent's); the
            # resident rows pass through the row loop's carry
            assert shape[0] == n + 2, (name, shape)
    assert not any(getattr(aval, "shape", ())[-1:] == (m,)
                   for _, aval in seen)


# -- the host's side: batch_filter, the index, the mask that is left ----------


def test_batch_filter_sends_categories_as_numbers_and_builds_no_wide_array(
        monkeypatch):
    monkeypatch.setattr(_common, "filter_bias_mask", lambda *a, **k: 1 / 0)
    model = _model()
    index = model.category_index
    rows = [
        _common.RowFilter(categories=("dept1", "nope", "dept1", "tiny"),
                          blacklist=("i7", "x1"), exclude_ix=(3,)),
        None,
        _common.RowFilter(exclude_ix=(5,)),
        _common.RowFilter(categories=("shelf2",)),
    ]
    flt = _common.batch_filter(model.items, model.categories(), rows)
    assert flt.kind == "cats" and flt.mask is None
    assert flt.exclude.shape == (4, WIDTH) and flt.width == WIDTH
    assert flt.exclude[0].tolist()[:3] == [3, 7, -1]
    assert flt.categories.shape == (4, SLOTS)
    assert flt.categories.dtype == np.int32
    # distinct numbers, named slots first, an unknown name the number of
    # no item
    assert flt.categories[0].tolist() == [
        index.number("dept1"), len(index), index.number("tiny"), -1]
    assert (flt.categories[1] == -1).all() and (flt.categories[2] == -1).all()
    assert flt.categories[3, 0] == index.number("shelf2")
    assert flt.category_rows == 2
    kwargs = flt.scorer_kwargs(model)
    assert set(kwargs) == {"mask", "exclude", "allow"}
    assert kwargs["allow"].rows is model.device_category_rows()
    # without categories the parent's forms, and its keyword arguments
    ids = _common.batch_filter(model.items, model.categories(), rows[1:3])
    assert ids.kind == "ids" and ids.categories is None
    assert set(ids.scorer_kwargs(model)) == {"mask", "exclude"}
    none = _common.batch_filter(model.items, model.categories(), [None])
    assert none == _common.BatchFilter("none")
    assert none.scorer_kwargs(model) == {"mask": None}


@pytest.mark.parametrize("why", ["whitelist", "wide_list", "many_names",
                                 "no_room_on_the_device"])
def test_what_is_left_on_the_mask_is_counted_and_still_right(
        why, monkeypatch, caplog):
    """A `whiteList`; `categories` beside a list wider than 32 ids; more
    names than `CATEGORY_SLOTS`; an index whose bit rows the device has
    no room for beside the table (it stays on the host and is read
    there): the `[B, M]` mask, counted, the answers the contract's."""
    model = _model()
    if why == "no_room_on_the_device":
        # a model built by hand from its items' property dicts gets its
        # index at first use; its rows (427 x 8 KiB) find 1 MiB free
        table, cats, names = _catalogue()
        model.category_index = None
        model.item_props = {f"i{j}": {"categories": [names[c] for c in
                                                     cats[j]]}
                            for j in range(0, M, 2)}
        monkeypatch.setattr(_common, "_device_free_bytes",
                            lambda: (1 << 20, 16 << 30))
    algo = smod.SimilarProductAlgorithm()
    table, cats, _ = _catalogue()
    query = {
        "whitelist": smod.Query(items=("i7",), num=8, categories=("dept0",),
                                whitelist=tuple(f"i{j}" for j in
                                                range(0, 6000, 7))),
        "wide_list": _query([8], [int(cats[8, 0])],
                            blacklist=range(100, 100 + WIDTH + 1)),
        "many_names": _query([9], list(range(3, 3 + SLOTS + 1))),
        "no_room_on_the_device": _query([10], [int(cats[10, 0])]),
    }[why]
    mask_rows = _common.FILTER_ROWS.labels(filter="mask").value()
    got = algo.batch_predict(model, [_query([3]), query])
    assert _common.FILTER_ROWS.labels(filter="mask").value() == mask_rows + 2
    seeds = [model.items.get(i) for i in query.items]
    vec = table[seeds].mean(axis=0)
    scores = table @ (vec / np.linalg.norm(vec))
    names = _catalogue()[2]
    allowed = np.isin(cats, [names.index(c) for c in query.categories]
                      ).any(axis=1)
    if why == "no_room_on_the_device":
        allowed[1::2] = False       # the dicts hold every other item
        assert model.device_category_rows() is None
        assert "kept on the host" in caplog.text
        algo.warmup(model, max_batch=1)     # warms no category program
    if query.whitelist:
        keep = np.zeros(M, bool)
        keep[[model.items.get(i) for i in query.whitelist]] = True
        allowed &= keep
    allowed[seeds + [model.items.get(i) for i in query.blacklist or ()]] = \
        False
    order = np.argsort(-np.where(allowed, scores, -np.inf), kind="stable")
    assert [s.item for s in got[1].item_scores] == \
        [f"i{ix}" for ix in order[:query.num]]


def test_filter_bias_mask_reads_the_index_not_the_dicts():
    model = _model()
    _, cats, names = _catalogue()

    class NoWalk(dict):
        def items(self):
            raise AssertionError("the property dicts are not walked")

    model.item_props = NoWalk()
    bias = _common.filter_bias_mask(
        model.items, model.categories(), categories=("tiny", "dept2"),
        exclude_ix=(11,))
    want = (cats[:, 0] == 2) | (cats[:, 2] == names.index("tiny"))
    want[11] = False
    np.testing.assert_array_equal(np.isfinite(bias), want)
    # without an index, or with an empty one, no item carries a category
    for none in (None, _common.CategoryIndex()):
        bias = _common.filter_bias_mask(model.items, none, categories=("a",))
        assert not np.isfinite(bias).any()


def test_index_from_the_items_properties():
    from predictionio_tpu.storage.bimap import StringIndex

    items = StringIndex(["a", "b", "c", "d"])
    props = {"a": {"categories": ["x", "y"]}, "b": {"categories": ["y"]},
             "c": {"categories": []}, "d": {"price": 3},
             "gone": {"categories": ["x"]}, "b2": {}}
    props["b"]["categories"] = ["y", "y"]         # a name twice counts once
    index = _common.CategoryIndex.from_props(items, props)
    assert index.names.tolist() == ["x", "y"] and len(index) == 2
    assert index.memberships == 3
    assert index.members[index.offsets[1]:index.offsets[2]].tolist() == [0, 1]
    assert index.number("y") == 1 and index.number("nope") == 2
    assert index.allowed(["x", "nope"], 4).tolist() == [True, False, False,
                                                        False]
    # the catalogue has grown since the train: a new item is in no category
    assert index.allowed(["y"], 6).tolist() == [True, True] + [False] * 4
    assert index.nbytes == (index.names.nbytes + index.offsets.nbytes
                            + index.members.nbytes)
    # every model has an index: an empty one where no item has a category
    for props in ({"a": {"t": 1}}, {}, None):
        empty = _common.CategoryIndex.from_props(items, props)
        assert len(empty) == 0 and empty.memberships == 0
        assert empty.number("x") == 0
        assert not empty.allowed(["x"], 4).any()
        assert len(_common.CategoryIndex.from_arrays(empty.arrays())) == 0
    again = _common.CategoryIndex.from_arrays(index.arrays())
    assert again.names.tolist() == ["x", "y"]
    np.testing.assert_array_equal(again.members, index.members)
    assert _common.CategoryIndex.from_arrays({}) is None
    assert _common.props_without_categories(props := {
        "a": {"categories": ["x"], "price": 3}, "b": {"categories": ["y"]},
        "c": {"title": "t"}}) == {"a": {"price": 3}, "c": {"title": "t"}}
    assert props["a"] == {"categories": ["x"], "price": 3}, "a copy"


def test_resident_bytes_count_the_index():
    from predictionio_tpu.tenancy.registry import model_resident_bytes

    bare, model = _model(with_index=False), _model()
    index = model.category_index
    assert len(bare.categories()) == 0 and bare.device_category_rows() is None
    host = model_resident_bytes([model]) - model_resident_bytes([bare])
    assert host == index.nbytes - bare.categories().nbytes
    rows = model.device_category_rows()
    assert rows.nbytes == (len(index) + 2) * topk.allow_words(M) * 4
    assert model_resident_bytes([model]) - model_resident_bytes([bare]) == \
        host + rows.nbytes
    assert model.device_category_rows() is rows, "built once a model load"


# -- the normal path: train -> persist -> deploy -> HTTP -----------------------


def _events(n_items=60):
    from predictionio_tpu.storage import DataMap, Event

    rng = np.random.default_rng(1)
    events = []
    for u in range(40):
        for i in rng.choice(n_items, size=8, replace=False):
            events.append(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}"))
    for j in range(n_items):
        events.append(Event(
            event="$set", entity_type="item", entity_id=f"i{j}",
            properties=DataMap({"categories": [f"c{j % 3}", f"s{j % 7}"],
                                "title": f"item {j}"})))
    return events


VARIANT = {
    "datasource": {"params": {"appName": "catapp"}},
    "algorithms": [{"name": "als", "params": {
        "rank": 8, "numIterations": 5, "lambda": 0.1, "alpha": 10.0}}],
}


def test_the_index_round_trips_train_persist_deploy_and_serves(
        storage_memory):
    """`pio-tpu train`'s path builds the index from the `$set` events'
    `categories`; the model's files hold it as arrays and the JSON the
    items' OTHER properties alone; `deploy`'s path loads it and a
    `categories` query over HTTP is answered from it, no row on the mask."""
    from predictionio_tpu.controller import WorkflowContext
    from predictionio_tpu.server.serving import EngineServer, ServerConfig
    from predictionio_tpu.workflow.train import prepare_deploy, run_train

    app = storage_memory.get_metadata().app_insert("catapp")
    es = storage_memory.get_event_store()
    es.init_channel(app.id)
    es.insert_batch(_events(), app_id=app.id)
    ctx = WorkflowContext(storage=storage_memory)
    engine = smod.similarproduct_engine()
    ep = engine.params_from_variant(VARIANT)
    iid = run_train(engine, ep, ctx=ctx, engine_variant="cat.json")
    mdir = storage_memory.model_data_dir() / iid
    saved = np.load(next(p for p in mdir.iterdir() if p.suffix == ".npz"))
    assert sorted(saved["category_names"].tolist()) == sorted(
        [f"c{j}" for j in range(3)] + [f"s{j}" for j in range(7)])
    assert len(saved["category_members"]) == 120
    props = json.loads(next(p for p in mdir.iterdir()
                            if p.name.endswith("props.json")).read_text())
    assert props["i4"] == {"title": "item 4"}, "no categories in the JSON"
    model = prepare_deploy(engine, ep, iid, ctx=ctx)[0]
    index = model.category_index
    assert len(index) == 10 and index.memberships == 120
    assert model.item_props["i4"] == {"title": "item 4"}, \
        "a deployed model's categories live in its index alone"
    ix = model.items.decode(np.flatnonzero(index.allowed(["c1"], 60)))
    assert sorted(int(i[1:]) % 3 for i in ix) == [1] * 20
    srv = EngineServer(engine, ep, iid, ctx=ctx,
                       config=ServerConfig(port=0, microbatch_max=4),
                       engine_variant="cat.json")
    srv.start_background()
    try:
        mask_rows = _common.FILTER_ROWS.labels(filter="mask").value()
        cats_rows = _common.FILTER_ROWS.labels(filter="cats").value()

        def ask(body):
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.config.port}/queries.json",
                json.dumps(body).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return [s["item"] for s in json.loads(resp.read())[
                    "itemScores"]]

        got = ask({"items": ["i4"], "num": 5, "categories": ["c2", "s3"],
                   "blackList": ["i5"]})
        assert len(got) == 5 and "i4" not in got and "i5" not in got
        assert all(int(i[1:]) % 3 == 2 or int(i[1:]) % 7 == 3 for i in got)
        assert ask({"items": ["i4"], "num": 5, "categories": ["nope"]}) == []
        assert len(ask({"items": ["i4"], "num": 5})) == 5
        assert _common.FILTER_ROWS.labels(filter="mask").value() == mask_rows
        assert _common.FILTER_ROWS.labels(filter="cats").value() == \
            cats_rows + 2
    finally:
        srv.stop()


def test_a_legacy_model_file_builds_its_index_from_the_json(tmp_path):
    """A model saved before the index (categories in the props JSON, no
    arrays in the npz) loads with one."""
    from predictionio_tpu.storage.bimap import StringIndex

    algo = smod.SimilarProductAlgorithm()
    props = {f"i{j}": {"categories": ["even" if j % 2 == 0 else "odd"],
                       **({"title": "six"} if j == 5 else {})}
             for j in range(6)}
    np.savez_compressed(
        tmp_path / "m1-similar.npz", item_factors=_unit_rows(6, 4),
        item_ids=np.array([f"i{j}" for j in range(6)]),
        normalized=np.array(True))
    (tmp_path / "m1-props.json").write_text(json.dumps(props))
    manifest = {"npz": "m1-similar.npz", "props": "m1-props.json"}
    model = algo.load_model(None, "m1", manifest, tmp_path)
    assert model.category_index.names.tolist() == ["even", "odd"]
    assert model.category_index.allowed(["odd"], 6).tolist() == [
        False, True] * 3
    assert model.item_props == {"i5": {"title": "six"}}
    # a model built by hand with the categories in its dicts: saved with
    # an index of them, loaded like any other
    old = smod.SimilarALSModel(
        item_factors=_unit_rows(6, 4),
        items=StringIndex([f"i{j}" for j in range(6)]), item_props=props)
    manifest = algo.save_model(None, "m2", old, tmp_path)
    assert np.load(tmp_path / manifest["npz"])["category_names"].tolist() \
        == ["even", "odd"]
    again = algo.load_model(None, "m2", manifest, tmp_path)
    assert again.item_props == model.item_props
    np.testing.assert_array_equal(again.category_index.members,
                                  model.category_index.members)


def test_a_warmed_server_compiles_nothing_for_its_first_category_query():
    """With an index the warm-up adds the category programs; without, it
    warms what the parent warmed and a `categories` query takes the mask."""
    from predictionio_tpu.obs import xray

    model, algo = _model(), smod.SimilarProductAlgorithm()
    xray.install()
    algo.warmup(model, max_batch=4)
    compiled = xray.total_backend_compiles()
    cases = _cases()
    for queries in ([cases["wide"]], [cases["absent"], cases["several"]],
                    [cases["wide"], cases["unknown"], cases["absent"],
                     cases["narrow_fewer_than_num"]],
                    [_query([30], [1], num=1)], [_query([31], [2], num=4)]):
        algo.batch_predict(model, queries)
    assert xray.total_backend_compiles() == compiled


# -- a live fold-in appends items: the index follows the table ---------------


@pytest.mark.parametrize("m,new", [(32_700, 100), (40_009, 50)],
                         ids=["past_the_words_end", "inside_the_last_word"])
def test_a_fold_in_that_appends_items_keeps_the_category_paths_right(m, new):
    """`live.apply.apply_model_delta` grows the item table of a model that
    holds an index: the resident bit rows follow it (widened by a tile
    where the new length passes the words' end; an appended item is in no
    category), a row WITHOUT categories in a batch that has them is served
    the appended items, and a `whiteList` beside `categories` builds its
    mask at the new length."""
    from predictionio_tpu.live.apply import apply_model_delta
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates import recommendation as rec
    from predictionio_tpu.workflow.model_io import ModelDelta

    r, rng = 32, np.random.default_rng(m)
    users = rng.normal(size=(6, r)).astype(np.float32)
    cats = np.stack([rng.integers(0, 3, m), 3 + rng.integers(0, 40, m)], 1)
    model = rec.ALSModel(
        user_factors=users, item_factors=_unit_rows(m, r, seed=m),
        users=StringIndex([f"u{j}" for j in range(6)]),
        items=StringIndex([f"i{j}" for j in range(m)]), item_props={},
        category_index=_common.CategoryIndex.from_memberships(
            [f"c{j}" for j in range(43)], cats.reshape(-1),
            np.repeat(np.arange(m), 2)))
    algo = rec.ALSAlgorithm()
    mixed = [rec.Query(user="u0", num=10, categories=("c1", "c7")),
             rec.Query(user="u1", num=10),
             rec.Query(user="u2", num=10, categories=("c0",),
                       blacklist=("i5",))]
    algo.batch_predict(model, mixed)          # the rows are resident
    before = model.device_category_rows()
    assert before.shape[1] == topk.allow_words(m) // 1024
    # the appended items: the best of every user, by far
    best = (users.mean(axis=0) / np.linalg.norm(users.mean(axis=0))
            + users[1] / np.linalg.norm(users[1]))
    fresh = (best[None, :] * (3 + np.arange(new)[:, None])).astype(np.float32)
    z = np.zeros((0, r), np.float32)
    apply_model_delta(model, ModelDelta(
        seq=1, meta={"baseUsers": 6, "baseItems": m}, user_rows_ix=[],
        user_rows=z, new_user_ids=[], new_user_rows=z, item_rows_ix=[],
        item_rows=z, new_item_ids=[f"n{j}" for j in range(new)],
        new_item_rows=fresh))
    assert len(model.items) == len(model.item_factors) == m + new
    rows = model.device_category_rows()
    assert rows.shape == (45, topk.allow_words(m + new) // 1024, 8, 128)
    assert (rows is before) == (topk.allow_words(m + new)
                                == topk.allow_words(m))
    words = np.asarray(rows).reshape(45, -1)
    got = np.asarray(topk._allowed_items(
        jnp.asarray(words), jnp.arange(m + new, dtype=jnp.int32)))
    assert got[44].all(), "the row of every item holds the appended ones"
    assert not got[:44, m:].any(), "an appended item is in no category"

    table = model.item_factors
    cats_rows = _common.FILTER_ROWS.labels(filter="cats").value()
    answers = algo.batch_predict(model, mixed)
    assert _common.FILTER_ROWS.labels(filter="cats").value() == cats_rows + 3
    for query, user, answer in zip(mixed, users[:3], answers):
        allowed = np.ones(m + new, bool)
        if query.categories:
            allowed[:m] = np.isin(
                cats, [int(c[1:]) for c in query.categories]).any(axis=1)
            allowed[m:] = False
        allowed[[model.items.get(i) for i in query.blacklist or ()]] = False
        order = np.argsort(-np.where(allowed, table @ user, -np.inf),
                           kind="stable")
        assert [s.item for s in answer.item_scores] == \
            [model.items.id_of(int(ix)) for ix in order[:10]], query
    assert [s.item for s in answers[1].item_scores] == \
        [f"n{j}" for j in range(new - 1, new - 11, -1)]
    # a whiteList beside categories: the mask, at the table's new length
    listed = rec.Query(user="u3", num=4, categories=("c2",),
                       whitelist=("n0", "n1", *[f"i{j}" for j in range(300)]))
    answer = algo.batch_predict(model, [listed])[0]
    keep = np.flatnonzero(cats[:300, 0] == 2)
    order = keep[np.argsort(-(table[keep] @ users[3]), kind="stable")]
    assert [s.item for s in answer.item_scores] == \
        [f"i{ix}" for ix in order[:4]]
    # rows that have not followed the table are refused, not read past
    with pytest.raises(ValueError, match="have not followed"):
        topk.batch_topk_scores_t(
            jnp.asarray(users[:1]), model.device_item_tables(), 16,
            exclude=jnp.full((1, WIDTH), -1, jnp.int32),
            allow=topk.Allowed(jnp.full((1, SLOTS), -1, jnp.int32),
                               rows[:, :-1]))
