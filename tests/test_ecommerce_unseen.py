"""The e-commerce engine's serving path since PR 40: a batch's users' seen
items read ONCE from the live event store inside the turn
(`EventStore.find_target_ids`), the unavailable items once a batch, both
and the blackList sent to the device as item ids at the ladder's rung
(`_common.batch_filter` -> `ops.topk.batch_topk_scores_t`), held against the
plain reference `perfbench/reference/ecomm_ref.py`, which reads the store
itself.  Small sizes, CPU."""

import types

import numpy as np
import pytest

from perfbench.reference import ecomm_ref
from predictionio_tpu.ops import topk
from predictionio_tpu.storage import DataMap, Event
from predictionio_tpu.storage.bimap import StringIndex
from predictionio_tpu.storage.levents import EventStore, MemoryEventStore
from predictionio_tpu.storage.sqlite_events import SQLiteEventStore
from predictionio_tpu.templates import _common
from predictionio_tpu.templates import ecommerce as emod
from predictionio_tpu.templates.recommendation import Query

APP = 7
M, R = 30_000, 128
LADDER = topk.EXCLUDE_LADDER
N_USERS = 6


def _unit_rows(m, r, seed=0):
    rows = np.random.default_rng(seed).normal(size=(m, r)).astype(np.float32)
    return rows / np.linalg.norm(rows, axis=1, keepdims=True)


def _make_store(kind):
    store = MemoryEventStore() if kind == "memory" else SQLiteEventStore()
    store.init_channel(APP)
    return store


@pytest.fixture(scope="module")
def model():
    items = _unit_rows(M, R)
    rng = np.random.default_rng(1)
    # a user lies near three of its items: seen items rank first
    users = np.stack([
        items[rng.integers(0, M, 3)].T @ np.array([1.0, 0.5, 0.25],
                                                  np.float32)
        for _ in range(N_USERS)]).astype(np.float32)
    return emod.ECommModel(
        user_factors=users, item_factors=items,
        users=StringIndex([f"u{j}" for j in range(N_USERS)]),
        items=StringIndex([f"i{j}" for j in range(M)]),
        item_props={f"i{j}": {"categories": ["even" if j % 2 == 0 else "odd"]}
                    for j in range(0, M, 5)},
        app_id=APP)


def _algo(store, unseen_only=True):
    algo = emod.ECommAlgorithm()
    algo.params = emod.ECommAlgorithmParams(
        rank=R, unseen_only=unseen_only, seen_events=("buy", "view"))
    algo._ctx = types.SimpleNamespace(
        storage=types.SimpleNamespace(get_event_store=lambda: store))
    return algo


def _buy(user, item, name="buy"):
    return Event(event=name, entity_type="user", entity_id=user,
                 target_entity_type="item", target_entity_id=item)


def _best(model, user, e):
    """The user's e best items, best first."""
    scores = model.item_factors @ model.user_factors[model.users.get(user)]
    return [f"i{ix}" for ix in np.argsort(-scores, kind="stable")[:e]]


def _reference(model, store, queries):
    """The plain reference's answers, from its own read of the store."""
    import jax.numpy as jnp

    seen, unavailable = ecomm_ref.read_store(
        store, APP, [q.user for q in queries], ("buy", "view"))
    gone = {int(i[1:]) for i in unavailable}
    excluded = [gone | {int(i[1:]) for i in seen[q.user]}
                | {int(i[1:]) for i in q.blacklist or ()} for q in queries]
    rows = model.user_factors[[model.users.get(q.user) for q in queries]]
    num = max(q.num for q in queries)
    items, vals, blind = ecomm_ref.answer(
        rows, jnp.asarray(model.item_factors), excluded, num)
    return items, vals, blind


def _lengths():
    """(rung the batch takes, the longest row's seen items): nothing, one
    id, each rung's width, one more than it (the next rung), and one more
    than the last (the mask)."""
    cases = [(0, 0), (LADDER[0], 1)]
    for width, wider in zip(LADDER, LADDER[1:] + (0,)):
        cases += [(width, width), (wider, width + 1)]
    return cases


@pytest.mark.parametrize("kind", ["memory", "sqlite"])
@pytest.mark.parametrize("rung,e", _lengths())
def test_batch_predict_equals_the_reference_at_every_rung(model, kind, rung,
                                                          e):
    """The best e items of u0 are bought (half of them also viewed) and two
    more are unavailable: the engine's answers equal the reference's, the
    batch takes the rung its longest row needs, and nothing of the
    catalogue's length is built unless the list passes the last rung."""
    store = _make_store(kind)
    unavailable = _best(model, "u1", 2) if e else []
    n_seen = max(e - len(unavailable), 0) if e else 0
    best = _best(model, "u0", n_seen)
    store.insert_batch([_buy("u0", item) for item in best]
                       + [_buy("u0", item, "view") for item in best[::2]]
                       + [_buy("u1", item) for item in _best(model, "u1", 3)
                          [2:] if e], APP)
    if e:
        store.insert(Event(
            event="$set", entity_type="constraint",
            entity_id="unavailableItems",
            properties=DataMap({"items": unavailable})), APP)
    algo = _algo(store)
    queries = [Query(user="u0", num=10), Query(user="u1", num=10),
               Query(user="ghost", num=10), Query(user="u2", num=4)]
    widths = dict(_common.FILTER_EXCLUDE_WIDTH.children())
    before = {dict(k)["width"]: c.value() for k, c in widths.items()}
    masks = _common.FILTER_ROWS.labels(filter="mask").value()
    got = algo.batch_predict(model, queries)
    want_items, want_vals, _ = _reference(
        model, store, [q for q in queries if q.user != "ghost"])
    asked = [g for q, g in zip(queries, got) if q.user != "ghost"]
    for q, result, items, vals in zip(
            [q for q in queries if q.user != "ghost"], asked, want_items,
            want_vals):
        assert [s.item for s in result.item_scores] == [
            f"i{ix}" for ix in items[:q.num]]
        np.testing.assert_allclose([s.score for s in result.item_scores],
                                   vals[:q.num], atol=2e-6)
    assert got[2].item_scores == ()
    assert not {s.item for s in got[0].item_scores} & set(best)
    for result in asked:
        assert not {s.item for s in result.item_scores} & set(unavailable)
    now = {dict(k)["width"]: c.value()
           for k, c in _common.FILTER_EXCLUDE_WIDTH.children()}
    took = {w for w, n in now.items() if n > before.get(w, 0)}
    assert took == ({str(rung)} if rung else set())
    masked = _common.FILTER_ROWS.labels(filter="mask").value() - masks
    assert masked == (len(queries) if e > LADDER[-1] else 0)


def test_a_lone_request_equals_the_one_row_batch(model):
    store = _make_store("memory")
    store.insert_batch([_buy("u3", item)
                        for item in _best(model, "u3", 40)], APP)
    algo = _algo(store)
    query = Query(user="u3", num=7, blacklist=tuple(_best(model, "u3", 45)
                                                    [40:]))
    alone = algo.predict(model, query)
    assert alone == algo.batch_predict(model, [query])[0]
    assert [s.item for s in alone.item_scores] == \
        _best(model, "u3", 52)[45:]
    assert algo.predict(model, Query(user="ghost", num=3)).item_scores == ()
    assert algo.predict(model, Query(user="u3", num=0)).item_scores == ()


@pytest.mark.parametrize("kind", ["memory", "sqlite"])
def test_an_event_inserted_between_two_queries_is_gone_from_the_second(
        model, kind):
    """No history is cached from one request to the next: a `buy`
    acknowledged before a query is received is out of its answer; so is a
    newer `$set` of unavailableItems, and an emptied one gives it back."""
    store = _make_store(kind)
    algo = _algo(store)
    query = Query(user="u4", num=5)
    first = [s.item for s in algo.predict(model, query).item_scores]
    assert first == _best(model, "u4", 5)
    store.insert(_buy("u4", first[0]), APP)
    second = [s.item for s in algo.predict(model, query).item_scores]
    assert second == _best(model, "u4", 6)[1:]
    store.insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": [second[0]]})), APP)
    third = [s.item for s in algo.predict(model, query).item_scores]
    assert third == _best(model, "u4", 7)[2:]
    store.insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": []})), APP)
    assert [s.item for s in algo.predict(model, query).item_scores] == second
    # another user's purchase of the same item changes nothing here
    store.insert(_buy("u5", second[0]), APP)
    assert [s.item for s in algo.predict(model, query).item_scores] == second


def test_a_failing_store_is_counted_and_still_answered(model, caplog):
    class Broken(MemoryEventStore):
        def find_target_ids(self, *args, **kwargs):
            raise TimeoutError("the store did not answer")

    store = Broken()
    store.init_channel(APP)
    store.insert(_buy("u0", _best(model, "u0", 1)[0]), APP)
    algo = _algo(store)
    failures = emod.SEEN_READ_FAILURES.value()
    reads = emod.SEEN_READ_SECONDS.snapshot()["count"]
    got = algo.batch_predict(model, [Query(user="u0", num=3),
                                     Query(user="u1", num=3)])
    assert emod.SEEN_READ_FAILURES.value() == failures + 1
    assert emod.SEEN_READ_SECONDS.snapshot()["count"] == reads + 1
    assert "error reading seen events" in caplog.text
    # answered as if nothing had been seen
    assert [s.item for s in got[0].item_scores] == _best(model, "u0", 3)
    assert [s.item for s in got[1].item_scores] == _best(model, "u1", 3)


def test_one_read_a_batch_and_its_counters(model):
    calls = []

    class Counting(MemoryEventStore):
        def find_target_ids(self, app_id, entity_type, entity_ids,
                            event_names=None, channel_id=0):
            calls.append((app_id, entity_type, list(entity_ids),
                          list(event_names)))
            return super().find_target_ids(app_id, entity_type, entity_ids,
                                           event_names, channel_id)

        def find(self, *args, **kwargs):
            assert kwargs.get("entity_type") == "constraint", \
                "users' histories are read through find_target_ids alone"
            return super().find(*args, **kwargs)

    store = Counting()
    store.init_channel(APP)
    store.insert_batch([_buy("u0", f"i{j}") for j in range(50)]
                       + [_buy("u1", "i3"), _buy("u1", "nothing-i-know")],
                       APP)
    algo = _algo(store)
    events = emod.SEEN_EVENTS.value()
    ids = _common.FILTER_EXCLUDED_IDS.value()
    algo.batch_predict(model, [Query(user="u0", num=3),
                               Query(user="nobody", num=3),
                               Query(user="u1", num=3)])
    assert calls == [(APP, "user", ["u0", "u1"], ["buy", "view"])]
    assert emod.SEEN_EVENTS.value() == events + 52
    assert _common.FILTER_EXCLUDED_IDS.value() == ids + 51


def test_without_unseen_only_the_store_is_not_read_for_users(model):
    class NoSeen(MemoryEventStore):
        def find_target_ids(self, *args, **kwargs):
            raise AssertionError("unseen_only is off")

    store = NoSeen()
    store.init_channel(APP)
    best = _best(model, "u0", 4)
    store.insert_batch([_buy("u0", item) for item in best], APP)
    store.insert(Event(
        event="$set", entity_type="constraint", entity_id="unavailableItems",
        properties=DataMap({"items": best[:1]})), APP)
    algo = _algo(store, unseen_only=False)
    got = algo.predict(model, Query(user="u0", num=3))
    assert [s.item for s in got.item_scores] == best[1:]


@pytest.mark.parametrize("kind", ["categories", "whitelist"])
def test_categories_ride_as_numbers_and_a_whitelist_takes_the_mask(
        model, kind):
    store = _make_store("memory")
    best = _best(model, "u0", 3)
    store.insert_batch([_buy("u0", item) for item in best], APP)
    algo = _algo(store)
    white = tuple(f"i{j}" for j in range(0, M, 3))
    query = (Query(user="u0", num=5, categories=("even",))
             if kind == "categories" else
             Query(user="u0", num=5, whitelist=white))
    form = "cats" if kind == "categories" else "mask"
    rows = _common.FILTER_ROWS.labels(filter=form).value()
    got = [s.item for s in algo.predict(model, query).item_scores]
    assert _common.FILTER_ROWS.labels(filter=form).value() == rows + 1
    scores = model.item_factors @ model.user_factors[0]
    allowed = np.zeros(M, bool)
    if kind == "categories":
        allowed[[j for j in range(0, M, 5) if j % 2 == 0]] = True
    else:
        allowed[::3] = True
    allowed[[int(i[1:]) for i in best]] = False
    order = np.argsort(-np.where(allowed, scores, -np.inf), kind="stable")
    assert got == [f"i{ix}" for ix in order[:5]]


def test_warmup_names_the_rungs_its_engine_can_be_asked(model, monkeypatch):
    """`unseen_only` warms every rung; without it, and for `similarproduct`
    and `recommendation`, the first rung alone: a wider ladder reaches no
    other engine's server start."""
    from predictionio_tpu.templates import recommendation as rmod
    from predictionio_tpu.templates import similarproduct as smod

    warmed = []

    def spy(vecs, tables, k, mask=None, exclude=None):
        warmed.append((vecs.shape[0], k,
                       0 if exclude is None else exclude.shape[1]))
        assert mask is None

    monkeypatch.setattr(topk, "batch_topk_scores_t", spy)
    small = emod.ECommModel(
        user_factors=model.user_factors[:, :16],
        item_factors=model.item_factors[:, :16], users=model.users,
        items=model.items, item_props={}, app_id=APP)
    _algo(None).warmup(small, max_batch=4)
    rungs = [(b, 16) for b in (1, 2, 4)]
    assert sorted(warmed) == sorted(
        (b, k, w) for b, k in rungs for w in (0,) + LADDER)
    rungs += [(1, 1), (1, 4)]       # a lone "three similar items"
    del warmed[:]
    _algo(None, unseen_only=False).warmup(small, max_batch=4)
    assert {w for _, _, w in warmed} == {0, LADDER[0]}
    del warmed[:]
    smod.SimilarProductAlgorithm().warmup(smod.SimilarALSModel(
        item_factors=small.item_factors, items=model.items, item_props={}),
        max_batch=4)
    assert sorted(warmed) == sorted((b, k, LADDER[0]) for b, k in rungs)
    del warmed[:]
    als = rmod.ALSAlgorithm()
    als.params = rmod.ALSAlgorithmParams(rank=16)
    als.warmup(rmod.ALSModel(
        user_factors=small.user_factors, item_factors=small.item_factors,
        users=model.users, items=model.items, item_props={}), max_batch=4)
    assert {w for _, _, w in warmed} == {0, LADDER[0]}


def test_the_dispatch_span_carries_the_rung(model, monkeypatch):
    store = _make_store("memory")
    store.insert_batch([_buy("u0", item)
                        for item in _best(model, "u0", 100)], APP)
    algo = _algo(store)
    seen = []
    real = emod.annotate

    def spy(name, **meta):
        seen.append((name, meta))
        return real(name, **meta)

    monkeypatch.setattr(emod, "annotate", spy)
    algo.batch_predict(model, [Query(user="u0", num=3),
                               Query(user="u1", num=3)])
    names = [name for name, _ in seen]
    assert names == ["pio.turn.prepare", "pio.seen.read",
                     "pio.turn.dispatch", "pio.turn.fetch",
                     "pio.turn.decode"]
    assert seen[2][1] == {"filter": "ids", "path": "blocked",
                          "exclude_width": LADDER[1], "categories": 0}


# -- the store's read by entity ------------------------------------------------


class _Generic(MemoryEventStore):
    """A backend with no read by entity of its own: the base class's, on
    `find`."""

    find_target_ids = EventStore.find_target_ids


@pytest.mark.parametrize("kind", ["memory", "sqlite", "generic"])
def test_find_target_ids_reads_what_find_reads(kind):
    store = _Generic() if kind == "generic" else _make_store(kind)
    store.init_channel(APP)
    store.insert_batch(
        [_buy("u1", "i1"), _buy("u1", "i2", "view"), _buy("u1", "i1"),
         _buy("u1", "i9", "rate"), _buy("u2", "i3"),
         Event(event="$set", entity_type="user", entity_id="u1",
               properties=DataMap({"a": 1})),
         Event(event="buy", entity_type="shop", entity_id="u1",
               target_entity_type="item", target_entity_id="i7")], APP)
    store.insert(_buy("u1", "i5"), APP, channel_id=0)
    got = store.find_target_ids(APP, "user", ["u2", "nobody", "u1", "u2"],
                                ["buy", "view"])
    assert [sorted(ids) for ids in got] == [
        ["i3"], [], ["i1", "i1", "i2", "i5"], ["i3"]]
    every = store.find_target_ids(APP, "user", ["u1"])
    assert sorted(every[0]) == ["i1", "i1", "i2", "i5", "i9"]
    assert store.find_target_ids(APP, "user", []) == []
    for user, ids in zip(["u2", "u1"], [got[0], got[2]]):
        assert sorted(ids) == sorted(
            e.target_entity_id for e in store.find(
                app_id=APP, entity_type="user", entity_id=user,
                event_names=["buy", "view"]))


def test_memory_stores_entity_index_follows_every_write():
    store = MemoryEventStore()
    store.init_channel(APP)
    eid = store.insert(_buy("u1", "i1"), APP)
    other = store.insert(_buy("u1", "i2"), APP)
    assert sorted(store.find_target_ids(APP, "user", ["u1"])[0]) == [
        "i1", "i2"]
    assert store.delete(eid, APP)
    assert store.find_target_ids(APP, "user", ["u1"]) == [["i2"]]
    # an event id written again under another entity moves
    store.insert(Event(event="buy", entity_type="user", entity_id="u2",
                       target_entity_type="item", target_entity_id="i3",
                       event_id=other), APP)
    assert store.find_target_ids(APP, "user", ["u1", "u2"]) == [[], ["i3"]]
    assert [e.entity_id for e in store.find(app_id=APP)] == ["u2"]
    assert list(store.find(app_id=APP, entity_type="user",
                           entity_id="u1")) == []
    assert store.remove_channel(APP)
    assert store.find_target_ids(APP, "user", ["u2"]) == [[]]
    # by entity the scan is the entity's events, in event-time order
    store.insert_batch([_buy("u3", f"i{j}") for j in range(5)], APP)
    found = list(store.find(app_id=APP, entity_type="user", entity_id="u3",
                            limit=3, reversed=True))
    assert len(found) == 3
    assert [e.event_time for e in found] == sorted(
        (e.event_time for e in found), reverse=True)
