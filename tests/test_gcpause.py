"""The collector-pause hook (`obs/gcpause.py`): a collection is booked
into `pio_gc_pause_seconds`, into the in-memory deque, and into the
record of the dispatcher's turn it fell into."""

import gc
import time

from predictionio_tpu.obs import gcpause, get_registry
from predictionio_tpu.obs.timeline import Turn, batch_turns, timeline_scope


def _garbage(n=2000):
    for _ in range(n):
        a, b = [], []
        a.append(b)
        b.append(a)


def test_forced_collection_is_booked_with_its_start_and_generation():
    gcpause.install()
    gcpause.install()   # idempotent: one hook
    assert gcpause.installed()
    assert gc.callbacks.count(gcpause._on_gc) == 1
    before = gcpause.GC_PAUSE_SECONDS.child().snapshot()
    _garbage()
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    mine = [p for p in gcpause.pauses() if t0 <= p[0] <= t1]
    assert mine, "the forced collection left no record"
    start, seconds, generation = mine[-1]
    assert generation == 2 and 0 < seconds <= t1 - start
    # the histogram is brought up to date by the scrape, not by the hook
    text = get_registry().render_prometheus()
    assert "pio_gc_pause_seconds_count" in text
    after = gcpause.GC_PAUSE_SECONDS.child().snapshot()
    assert after["count"] >= before["count"] + 1
    assert after["sum"] >= before["sum"] + seconds * 0.99


def test_collection_on_the_leading_thread_lands_in_its_turn():
    gcpause.install()
    turn = Turn()
    with timeline_scope(turn):
        _garbage()
        gc.collect()
    turn.finish()
    outside = Turn()    # not in scope while the collector runs
    gc.collect()
    outside.finish()
    mine, other = batch_turns()[-2:]
    assert mine["turn"] == turn.turn and mine["gcSec"] > 0
    assert other["turn"] == outside.turn and other["gcSec"] == 0.0
