"""Collector pauses: how long the cyclic garbage collector stopped a
thread, and when.

A collection runs on whichever thread's allocation crossed the
threshold, with the interpreter lock held, so every other Python thread
waits it out too: on the batch dispatcher's thread it is host time
between two device calls with the chip idle.  :func:`install` hooks
``gc.callbacks``; each collection's pause lands in
``pio_gc_pause_seconds``, in the record of the dispatcher's turn it fell
into (``gcSec`` of :func:`timeline.batch_turns`), and with its start in
a bounded in-memory deque (:func:`pauses`).

The callback runs inside whatever allocation triggered the collection,
possibly under a lock that the allocating code holds, so it takes none:
it appends to deques, and the histogram is brought up to date by a
registry collect hook, before anything reads it.
"""

from __future__ import annotations

import collections
import gc
import time

from . import get_registry, log_buckets
from .timeline import Turn, current_timeline

__all__ = ["install", "installed", "pauses"]

_registry = get_registry()

GC_PAUSE_SECONDS = _registry.histogram(
    "pio_gc_pause_seconds",
    "Pause of one cyclic garbage collection (any generation), on the "
    "thread whose allocation triggered it; every Python thread waits "
    "it out",
    buckets=log_buckets(1e-5, 10.0, per_decade=4),
)
_m_pause = GC_PAUSE_SECONDS.child()

# (t0 by perf_counter, seconds, generation), newest last: six minutes at
# the 45 collections/s of a server answering 1,190 queries/s
_PAUSES: collections.deque = collections.deque(maxlen=16384)
# pauses the histogram has not seen yet
_unobserved: collections.deque = collections.deque(maxlen=16384)
_t_start = 0.0      # collections do not nest: one start is open at most
_installed = False


def _on_gc(phase: str, info: dict) -> None:
    global _t_start
    if phase == "start":
        _t_start = time.perf_counter()
        return
    t0 = _t_start
    dt = time.perf_counter() - t0
    _PAUSES.append((t0, dt, info["generation"]))
    _unobserved.append(dt)
    tl = current_timeline()
    if isinstance(tl, Turn):
        tl.gc_s += dt


def _flush() -> None:
    while True:
        try:
            _m_pause.observe(_unobserved.popleft())
        except IndexError:      # drained, here or by another scrape
            return


def pauses() -> list:
    """The collections still in memory, oldest first:
    ``(t0, seconds, generation)`` with ``t0`` on ``perf_counter``."""
    return list(_PAUSES)


def installed() -> bool:
    return _installed


def install() -> None:
    """Hook the collector (idempotent).  Servers call this where they
    install xray."""
    global _installed
    if _installed:
        return
    _installed = True
    gc.callbacks.append(_on_gc)
    _registry.add_collect_hook(_flush)
