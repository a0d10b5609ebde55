"""Property-based tests (hypothesis) for the invariant-heavy surfaces.

The reference proves these with hand-picked cases (`DataMapSpec`,
`LEventAggregatorSpec`, `BiMapSpec`); generated inputs cover the same
contracts over the whole input space — JSON wire round-trips, the
$set/$unset/$delete fold semantics, and id-index bijection.
"""

import datetime as dt
import json

import pytest

# hypothesis is an optional dev dependency: without the guard this
# module's import error aborts the whole tier-1 collection instead of
# skipping just these property tests
pytest.importorskip("hypothesis")

from hypothesis import given, settings
from hypothesis import strategies as st

from predictionio_tpu.storage.bimap import StringIndex
from predictionio_tpu.storage.event import DataMap, Event, format_time
from predictionio_tpu.storage.aggregate import aggregate_properties_single

UTC = dt.timezone.utc

# JSON-representable property values (reference: DataMap is Map[String,
# JValue]); floats NaN/inf excluded — not valid JSON
_scalar = (
    st.none()
    | st.booleans()
    | st.integers(min_value=-(2**53), max_value=2**53)
    | st.floats(allow_nan=False, allow_infinity=False, width=32)
    | st.text(max_size=20)
)
_json_val = st.recursive(
    _scalar,
    lambda inner: st.lists(inner, max_size=4)
    | st.dictionaries(st.text(max_size=8), inner, max_size=4),
    max_leaves=8,
)
# property keys must not collide with the reserved pio_ prefix
_prop_key = st.text(
    alphabet=st.characters(min_codepoint=97, max_codepoint=122), min_size=1,
    max_size=12,
).filter(lambda s: not s.startswith("pio_"))
_props = st.dictionaries(_prop_key, _json_val, max_size=5)
_entity = st.text(
    alphabet=st.characters(min_codepoint=97, max_codepoint=122), min_size=1,
    max_size=8,
)
_times = st.datetimes(
    min_value=dt.datetime(2000, 1, 1),
    max_value=dt.datetime(2030, 1, 1),
    timezones=st.just(UTC),
).map(lambda t: t.replace(microsecond=(t.microsecond // 1000) * 1000))


@given(props=_props, ent=_entity, t=_times)
@settings(max_examples=60, deadline=None)
def test_event_api_json_round_trip(props, ent, t):
    """Event -> wire JSON -> Event preserves every field, and the wire
    form survives an actual json.dumps/loads cycle (the reference's
    APISerializer contract)."""
    e = Event(
        event="rate", entity_type="user", entity_id=ent,
        target_entity_type="item", target_entity_id=ent,
        properties=DataMap(props), event_time=t, event_id="abc123",
    )
    wire = json.loads(json.dumps(e.to_json()))
    back = Event.from_json(wire)
    assert back.event == e.event
    assert back.entity_id == e.entity_id
    assert back.properties == e.properties
    assert back.event_time == e.event_time
    assert back.target_entity_id == e.target_entity_id
    assert format_time(back.event_time) == format_time(e.event_time)


@given(sets=st.lists(_props, min_size=1, max_size=5))
@settings(max_examples=40, deadline=None)
def test_aggregate_last_set_wins(sets):
    """A sequence of $set events folds to the union with the LAST write
    per key winning (reference LEventAggregator semantics)."""
    base = dt.datetime(2020, 1, 1, tzinfo=UTC)
    evs = [
        Event(event="$set", entity_type="user", entity_id="u",
              properties=DataMap(p),
              event_time=base + dt.timedelta(seconds=i))
        for i, p in enumerate(sets)
    ]
    got = aggregate_properties_single(evs)
    want: dict = {}
    for p in sets:
        want.update(p)
    assert got is not None
    assert got.fields == want
    assert got.first_updated == evs[0].event_time
    assert got.last_updated == evs[-1].event_time


@given(props=_props.filter(lambda p: p), drop=st.data())
@settings(max_examples=40, deadline=None)
def test_aggregate_unset_removes_and_delete_kills(props, drop):
    base = dt.datetime(2020, 1, 1, tzinfo=UTC)
    key = drop.draw(st.sampled_from(sorted(props)))
    evs = [
        Event(event="$set", entity_type="user", entity_id="u",
              properties=DataMap(props), event_time=base),
        Event(event="$unset", entity_type="user", entity_id="u",
              properties=DataMap({key: None}),
              event_time=base + dt.timedelta(seconds=1)),
    ]
    got = aggregate_properties_single(evs)
    remaining = {k: v for k, v in props.items() if k != key}
    if remaining:
        assert got is not None and got.fields == remaining
    # $delete after everything kills the entity regardless of history
    evs.append(
        Event(event="$delete", entity_type="user", entity_id="u",
              event_time=base + dt.timedelta(seconds=2))
    )
    assert aggregate_properties_single(evs) is None


@given(ids=st.lists(_entity, min_size=1, max_size=40))
@settings(max_examples=40, deadline=None)
def test_string_index_bijection(ids):
    """encode/decode round-trips; indexes are a contiguous 0..n-1
    bijection (the BiMap.stringInt contract; this build assigns them in
    SORTED id order — the vectorized dictionary build)."""
    import numpy as np

    ix = StringIndex.from_values(ids)
    uniq = sorted(set(ids))
    assert len(ix) == len(uniq)
    codes = ix.encode(uniq)
    assert sorted(int(c) for c in codes) == list(range(len(uniq)))
    assert list(ix.decode(codes)) == uniq
    for s in uniq:
        assert ix.id_of(ix[s]) == s
    assert ix.get("§never-an-id§") == -1
    np.testing.assert_array_equal(
        ix.decode(ix.encode(ids)), np.asarray(ids)
    )


# -- sharded-store routing + dedup invariants (round 5) -------------------

_entity = st.text(min_size=1, max_size=12)


@given(_entity, _entity, st.integers(min_value=1, max_value=16))
def test_shard_routing_deterministic_and_in_range(etype, eid, n):
    from predictionio_tpu.storage.sharded_events import _shard_ix

    a = _shard_ix(etype, eid, n)
    assert 0 <= a < n
    assert a == _shard_ix(etype, eid, n)  # stable across calls


@given(
    st.lists(
        st.tuples(
            st.integers(min_value=0, max_value=6),   # user code
            st.integers(min_value=0, max_value=4),   # item code
            st.floats(min_value=0.5, max_value=5.0, width=32),
            st.integers(min_value=0, max_value=3),   # coarse time (ties!)
        ),
        min_size=1, max_size=40,
    ),
    st.permutations(range(40)),
    st.sampled_from(["last", "sum"]),
)
@settings(max_examples=60, deadline=None)
def test_dedup_coo_is_scan_order_independent(rows, perm, mode):
    """The deterministic-tiebreak contract: dedup output is a pure
    function of the row MULTISET — any permutation of the scan order
    (python cursor vs native rowid walk vs shard interleave) yields the
    same survivors.  Coarse timestamps force equal-time ties, the case
    the value tie-break exists for."""
    import numpy as np

    from predictionio_tpu.storage.columnar import dedup_coo

    def run(seq):
        u = np.array([r[0] for r in seq], np.int32)
        it = np.array([r[1] for r in seq], np.int32)
        v = np.array([r[2] for r in seq], np.float64)
        t = np.array([r[3] for r in seq], np.int64)
        du, di, dv = dedup_coo(u, it, v, t, n_items=5, dedup=mode)
        order = np.lexsort((di, du))
        # exact comparison is sound here: 'last' returns original
        # values verbatim, 'sum' is exact in float64 for these inputs
        return (du[order].tolist(), di[order].tolist(),
                dv[order].tolist())

    # a true permutation of rows (perm covers range(40); keep the
    # indices that exist)
    shuffled = [rows[p] for p in perm if p < len(rows)]
    assert run(rows) == run(shuffled)
