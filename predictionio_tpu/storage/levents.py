"""Event store contract + hermetic in-memory backend.

The synchronous re-expression of the reference `LEvents` DAO
(`/root/reference/data/src/main/scala/io/prediction/data/storage/LEvents.scala:31-451`).
The reference exposes ``Future``-based methods because it fronts remote HBase
RPC; here backends are embedded (SQLite / memory), so the API is synchronous
and the HTTP servers layer their own thread pools on top.  Filter semantics of
``find`` match the reference exactly, including the tri-state target-entity
filters (``None`` = unrestricted, ``NO_TARGET`` = event must have no target,
a string = must equal).

The in-memory backend exists so the whole contract suite runs hermetically —
an improvement SURVEY §4 calls for over the reference's live-HBase-only specs.
"""

from __future__ import annotations

import abc
import contextlib
import datetime as _dt
import itertools
import threading
from typing import Iterable, Iterator, Optional, Sequence, Union

from .aggregate import aggregate_properties, aggregate_properties_single
from .event import Event, PropertyMap, new_event_id, validate_event

__all__ = ["NO_TARGET", "EventStore", "MemoryEventStore",
           "ShardUnavailableError"]


class ShardUnavailableError(Exception):
    """One shard of a sharded event store cannot serve right now
    (owner worker dead, injected ``store.shard_down``, broken WAL).

    Deliberately NOT a ``sqlite3.OperationalError``: the condition is
    sticky until the owner recovers, so the ingest edge must answer a
    structured 503 + Retry-After immediately instead of burning its
    transient-error retry budget.  ``shard`` names the component a
    degradation-aware caller (vector-cursor scans, the ingest router)
    should stall or reject — never the whole store."""

    def __init__(self, shard: int, reason: str = "shard unavailable"):
        super().__init__(f"shard {shard} unavailable: {reason}")
        self.shard = int(shard)
        self.reason = reason


class _NoTarget:
    """Sentinel: filter for events with no target entity
    (reference ``Some(None)`` in `LEvents.scala:126-138`)."""

    _instance: "_NoTarget | None" = None

    def __new__(cls):
        if cls._instance is None:
            cls._instance = super().__new__(cls)
        return cls._instance

    def __repr__(self) -> str:
        return "NO_TARGET"


NO_TARGET = _NoTarget()

TargetFilter = Union[None, _NoTarget, str]


class EventStore(abc.ABC):
    """Single-record + scan event DAO (the `LEvents` contract)."""

    # -- lifecycle --------------------------------------------------------
    @abc.abstractmethod
    def init_channel(self, app_id: int, channel_id: int = 0) -> bool:
        """Initialize storage for (app, channel); idempotent."""

    @abc.abstractmethod
    def remove_channel(self, app_id: int, channel_id: int = 0) -> bool:
        """Drop all events of (app, channel)."""

    def close(self) -> None:  # noqa: B027 — optional hook
        pass

    def compact(self) -> None:  # noqa: B027 — optional hook
        """Reclaim storage space freed by deletes (`app trim`).

        The reference's trim flow rewrote the event table (a Spark job
        writing a fresh copy minus the window —
        `examples/experimental/scala-parallel-trim-app`), which
        implicitly compacted; embedded stores must offer the same
        reclamation explicitly (sqlite: VACUUM).  Default no-op for
        stores without free-space bookkeeping."""

    # -- writes -----------------------------------------------------------
    @abc.abstractmethod
    def insert(self, event: Event, app_id: int, channel_id: int = 0,
               validate: bool = True) -> str:
        """Persist (validating first unless ``validate=False`` — for
        events that already passed validation, e.g. from
        ``Event.from_json``); returns the assigned event id."""

    def insert_batch(
        self,
        events: Iterable[Event],
        app_id: int,
        channel_id: int = 0,
        validate: bool = True,
    ) -> list[str]:
        """``validate=False`` skips per-event re-validation for events
        that already passed it (e.g. built by ``Event.from_json``) — the
        bulk-import path validated twice otherwise."""
        return [
            self.insert(e, app_id, channel_id, validate=validate)
            for e in events
        ]

    @contextlib.contextmanager
    def bulk(self):
        """Bulk-write scope: transactional backends may defer their
        commit to the end of the scope (one fsync per import instead of
        one per batch).  Base implementation is a no-op."""
        yield self

    # -- point reads ------------------------------------------------------
    @abc.abstractmethod
    def get(
        self, event_id: str, app_id: int, channel_id: int = 0
    ) -> Optional[Event]: ...

    @abc.abstractmethod
    def delete(self, event_id: str, app_id: int, channel_id: int = 0) -> bool: ...

    def delete_batch(
        self, event_ids: Iterable[str], app_id: int, channel_id: int = 0
    ) -> int:
        """Bulk delete; returns the number actually removed.  Backends
        override to avoid per-row commits."""
        return sum(
            bool(self.delete(eid, app_id, channel_id)) for eid in event_ids
        )

    # -- scans ------------------------------------------------------------
    @abc.abstractmethod
    def find(
        self,
        app_id: int,
        channel_id: int = 0,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: TargetFilter = None,
        target_entity_id: TargetFilter = None,
        limit: Optional[int] = None,
        reversed: bool = False,
    ) -> Iterator[Event]:
        """Scan with the reference's filter set (`LEvents.scala:103-138`).

        ``limit=None`` or ``-1`` means all; ``reversed`` returns latest
        events first.  Events are ordered by event_time.
        """

    # -- columnar batch read (PEvents analogue) ---------------------------
    def find_columnar(
        self,
        app_id: int,
        channel_id: int = 0,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        entity_type: Optional[str] = None,
        entity_id: Optional[str] = None,
        event_names: Optional[Sequence[str]] = None,
        target_entity_type: TargetFilter = None,
        target_entity_id: TargetFilter = None,
        float_property: Optional[str] = None,
        float_default: float = float("nan"),
        minimal: bool = False,
        cache: Optional[bool] = None,
    ):
        """Bulk scan into column arrays (the `PEvents` analogue,
        reference `data/.../storage/PEvents.scala:30-138`).

        ``minimal=True`` is an optimization HINT: the caller promises to
        touch only ``entity_id``/``target_entity_id``/``event_time_ms``
        (+ ``value``), letting backends skip the other columns.  This
        generic implementation ignores it (a full frame satisfies the
        contract).  ``cache`` likewise: backends with a snapshot cache
        (sqlite) honor it; others ignore it.

        Generic implementation built on :meth:`find` +
        :func:`~predictionio_tpu.storage.columnar.events_to_frame`, so
        EVERY backend satisfies the columnar contract; backends with a
        native bulk path override it
        (`sqlite_events.SQLiteEventStore.find_columnar` reads straight
        from the cursor).  With ``float_property`` the named property is
        extracted per event into a float64 ``value`` column (missing ->
        ``float_default``) — the training-data hot path.
        """
        from dataclasses import replace

        from .columnar import events_to_frame

        frame = events_to_frame(
            self.find(
                app_id=app_id,
                channel_id=channel_id,
                start_time=start_time,
                until_time=until_time,
                entity_type=entity_type,
                entity_id=entity_id,
                event_names=event_names,
                target_entity_type=target_entity_type,
                target_entity_id=target_entity_id,
            )
        )
        if float_property is not None:
            frame = replace(
                frame,
                value=frame.property_column(float_property, float_default),
                properties=None,
            )
        return frame

    # -- target ids by entity (the read a `predict` makes) -----------------
    def find_target_ids(
        self,
        app_id: int,
        entity_type: str,
        entity_ids: Sequence[str],
        event_names: Optional[Sequence[str]] = None,
        channel_id: int = 0,
    ) -> list[list[str]]:
        """For each of `entity_ids`, the target entity ids of its events
        (those named in `event_names`; every event without), an id once
        an event, in no promised order: :meth:`find` by entity with the
        targets alone, for the serving path that reads a batch's users'
        histories inside the turn (reference ``LEventStore.findByEntity``
        in ``ECommAlgorithm.predict``).  An event acknowledged by
        :meth:`insert` before the call is in the answer.

        Generic implementation on :meth:`find`, one scan an entity;
        backends that index by entity override it and build no
        :class:`Event` (memory: its entity index; sqlite: one SELECT of
        two columns over the ``entity`` index)."""
        names = None if event_names is None else list(event_names)
        return [
            [e.target_entity_id
             for e in self.find(
                 app_id=app_id, channel_id=channel_id,
                 entity_type=entity_type, entity_id=entity_id,
                 event_names=names)
             if e.target_entity_id]
            for entity_id in entity_ids
        ]

    # -- aggregation (built on find, like the reference) ------------------
    def aggregate_properties_of(
        self,
        app_id: int,
        entity_type: str,
        channel_id: int = 0,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
    ) -> dict[str, PropertyMap]:
        events = self.find(
            app_id=app_id,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            event_names=["$set", "$unset", "$delete"],
        )
        result = aggregate_properties(events)
        if required:
            result = {
                k: v
                for k, v in result.items()
                if all(r in v for r in required)
            }
        return result

    def extract_entity_map(
        self,
        extract,
        app_id: int,
        entity_type: str,
        channel_id: int = 0,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
        required: Optional[Sequence[str]] = None,
    ):
        """Typed entity extraction: aggregate ``$set``/``$unset`` state per
        entity, keep entities holding every ``required`` property, and map
        each property bag through ``extract`` into an
        :class:`~predictionio_tpu.storage.bimap.EntityMap` (reference
        ``PEvents.extractEntityMap``, `data/.../PEvents.scala:109-115`)."""
        from .bimap import EntityMap

        props = self.aggregate_properties_of(
            app_id=app_id,
            entity_type=entity_type,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            required=required,
        )
        return EntityMap({k: extract(v) for k, v in props.items()})

    def aggregate_properties_single_entity(
        self,
        app_id: int,
        entity_type: str,
        entity_id: str,
        channel_id: int = 0,
        start_time: Optional[_dt.datetime] = None,
        until_time: Optional[_dt.datetime] = None,
    ) -> Optional[PropertyMap]:
        events = self.find(
            app_id=app_id,
            channel_id=channel_id,
            start_time=start_time,
            until_time=until_time,
            entity_type=entity_type,
            entity_id=entity_id,
            event_names=["$set", "$unset", "$delete"],
        )
        return aggregate_properties_single(events)


def _match(
    e: Event,
    start_time,
    until_time,
    entity_type,
    entity_id,
    event_names,
    target_entity_type,
    target_entity_id,
) -> bool:
    if start_time is not None and e.event_time < start_time:
        return False
    if until_time is not None and e.event_time >= until_time:
        return False
    if entity_type is not None and e.entity_type != entity_type:
        return False
    if entity_id is not None and e.entity_id != entity_id:
        return False
    if event_names is not None and e.event not in event_names:
        return False
    if target_entity_type is not None:
        if target_entity_type is NO_TARGET:
            if e.target_entity_type is not None:
                return False
        elif e.target_entity_type != target_entity_type:
            return False
    if target_entity_id is not None:
        if target_entity_id is NO_TARGET:
            if e.target_entity_id is not None:
                return False
        elif e.target_entity_id != target_entity_id:
            return False
    return True


class MemoryEventStore(EventStore):
    """Hermetic in-memory backend (dict per (app, channel) by event id,
    and beside it the same events by entity, as the sqlite backend's
    ``entity`` index has them; lock-guarded)."""

    def __init__(self, config=None):
        self._lock = threading.RLock()
        self._tables: dict[tuple[int, int], dict[str, Event]] = {}
        # (app, channel) -> (entity_type, entity_id) -> event id -> Event
        self._by_entity: dict[tuple[int, int], dict[tuple, dict]] = {}

    def _table(self, app_id: int, channel_id: int) -> dict[str, Event]:
        key = (app_id, channel_id)
        with self._lock:
            if key not in self._tables:
                self._tables[key] = {}
                self._by_entity[key] = {}
            return self._tables[key]

    def init_channel(self, app_id: int, channel_id: int = 0) -> bool:
        self._table(app_id, channel_id)
        return True

    def remove_channel(self, app_id: int, channel_id: int = 0) -> bool:
        with self._lock:
            self._by_entity.pop((app_id, channel_id), None)
            return self._tables.pop((app_id, channel_id), None) is not None

    def insert(self, event: Event, app_id: int, channel_id: int = 0,
               validate: bool = True) -> str:
        if validate:
            validate_event(event)
        eid = event.event_id or new_event_id()
        if event.event_id != eid:
            event = event.with_id(eid)
        with self._lock:
            table = self._table(app_id, channel_id)
            old = table.get(eid)
            if old is not None:     # an id written again moves entity
                self._unindex(app_id, channel_id, old)
            table[eid] = event
            self._by_entity[app_id, channel_id].setdefault(
                (event.entity_type, event.entity_id), {})[eid] = event
        return eid

    def _unindex(self, app_id: int, channel_id: int, event: Event) -> None:
        entities = self._by_entity[app_id, channel_id]
        key = (event.entity_type, event.entity_id)
        mine = entities.get(key)
        if mine is not None:
            mine.pop(event.event_id, None)
            if not mine:
                del entities[key]

    def get(self, event_id: str, app_id: int, channel_id: int = 0) -> Optional[Event]:
        with self._lock:
            return self._table(app_id, channel_id).get(event_id)

    def delete(self, event_id: str, app_id: int, channel_id: int = 0) -> bool:
        with self._lock:
            old = self._table(app_id, channel_id).pop(event_id, None)
            if old is not None:
                self._unindex(app_id, channel_id, old)
            return old is not None

    def find_target_ids(self, app_id: int, entity_type: str, entity_ids,
                        event_names=None, channel_id: int = 0):
        names = None if event_names is None else set(event_names)
        with self._lock:
            self._table(app_id, channel_id)
            entities = self._by_entity[app_id, channel_id]
            return [
                [e.target_entity_id
                 for e in entities.get((entity_type, entity_id), {}).values()
                 if e.target_entity_id
                 and (names is None or e.event in names)]
                for entity_id in entity_ids
            ]

    def find(
        self,
        app_id: int,
        channel_id: int = 0,
        start_time=None,
        until_time=None,
        entity_type=None,
        entity_id=None,
        event_names=None,
        target_entity_type: TargetFilter = None,
        target_entity_id: TargetFilter = None,
        limit: Optional[int] = None,
        reversed: bool = False,
    ) -> Iterator[Event]:
        with self._lock:
            table = self._table(app_id, channel_id)
            if entity_type is not None and entity_id is not None:
                # one entity's events: by the index, not a pass over all
                table = self._by_entity[app_id, channel_id].get(
                    (entity_type, entity_id), {})
            evs = list(table.values())
        evs.sort(key=lambda e: (e.event_time, e.event_id or ""), reverse=reversed)
        it = (
            e
            for e in evs
            if _match(
                e,
                start_time,
                until_time,
                entity_type,
                entity_id,
                event_names,
                target_entity_type,
                target_entity_id,
            )
        )
        if limit is not None and limit >= 0:
            it = itertools.islice(it, limit)
        return it
