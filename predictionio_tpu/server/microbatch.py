"""Continuous micro-batching for the serving hot path.

The reference detaches one JVM actor per request
(`workflow/CreateServer.scala:437,464`) and each predict is cheap CPU
work, so concurrency alone scales it.  Here every predict is a device
call, and a TPU has ONE execution queue: N concurrent requests that
each dispatch their own top-k matmul serialize on the queue, so
per-request latency grows ~linearly with concurrency while aggregate
QPS stays flat (measured: 8 threads take p50 from ~1 ms to ~7.5 ms at
unchanged QPS, bench_serving.py --threads).

The TPU-shaped fix is to make concurrency *wider, not deeper*: coalesce
the queries that arrive while a device call is in flight into ONE
batched call (`Algorithm.batch_predict` — a [B, R] x [R, M] matmul
costs barely more than the [R] x [R, M] one).  ONE thread leads every
turn: the lazily-started dispatcher claims whatever is pending the
moment the device frees up, runs the batch, and completes its entries.
Two ways in share its pending queue:

* **Continuous** ``submit_nowait(x, on_done, ...)`` — the event-loop
  edge admits requests *into the queue as they arrive* and returns
  immediately; the dispatcher fires per-entry completion callbacks.  No
  thread ever parks per request: the edge stays one loop thread + one
  dispatcher regardless of concurrency.
* **Blocking** ``submit(x)`` — the same admission, then the calling
  thread parks until the dispatcher has completed its entry and returns
  the result (or raises) there.  The in-process callers' form
  (``EngineServer.predict_json``, tests).

Deadline-aware admission (pio-surge): entries may carry a
``resilience.policy.Deadline``.  A claimed entry already past its
deadline is completed with ``DeadlineExceeded`` WITHOUT ever reaching
the device (the device queue is the one resource concurrency shares —
work for a client that gave up is pure stolen capacity), and
:meth:`MicroBatcher.estimate_wait_s` exposes an EWMA-based estimate of
queue+service time so the serving edge can reject a request that
cannot make its SLO *up front* as a structured 503
(:class:`AdmissionRejected`) rather than queue it to die.

What the dispatcher does between two device calls is one
``obs/timeline.Turn`` per claim: every
step below runs under an ``annotate("pio.turn.<segment>")`` (park, claim,
fetch, complete; an engine's ``batch_predict`` may carve prepare /
dispatch / decode out of fetch), which is at once a span in any
profiler session and a wall + thread-CPU segment of the turn's record
(``timeline.batch_turns()``, ``pio_batch_turn_seconds{segment}``).
Inside ``complete`` the turn's ``parts`` say what the callbacks did for
each request (``timeline.mark_part``: book here, serve / observe in
``serving.py``, encode / handoff in the edge's ``Responder``).

Batch size therefore adapts to the arrival rate with no tuning knob
doing latency/throughput trades behind the operator's back
(``max_wait_s`` exists for completeness but defaults to 0).

Determinism note: a batched matmul compiles per batch size, so the same
query served inside different batch compositions can differ at float
ulp scale (different reduction order) — rankings are stable, scores may
wobble ~1e-7.  Deployments that need bitwise per-request determinism
set ``ServerConfig(microbatch="off")``.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Optional, Sequence

from ..obs.scope import TimedCondition, register_thread_role
from ..obs.timeline import (
    MICROBATCH_ADMISSION_TOTAL,
    MICROBATCH_BATCH_SIZE,
    MICROBATCH_QUEUE_DEPTH,
    MICROBATCH_ROLE_TOTAL,
    MICROBATCH_TENANTS_PER_BATCH,
    MICROBATCH_WAIT_SECONDS,
    Turn,
    annotate,
    current_timeline,
    mark_part,
    timeline_scope,
)
from ..resilience.policy import Deadline, DeadlineExceeded

__all__ = [
    "AdmissionRejected",
    "EwmaEstimator",
    "MicroBatcher",
    "SharedBatcher",
    "SharedBatcherView",
    "dispatchable_sizes",
]

logger = logging.getLogger(__name__)

# pulse saturation metrics, children cached at import (labels() is too
# hot for the per-submit path); process-wide like pio_query_latency —
# one serving process hosts one live batcher
_m_queue_depth = MICROBATCH_QUEUE_DEPTH.child()
_m_batch_size = MICROBATCH_BATCH_SIZE.child()
_m_batch_wait = MICROBATCH_WAIT_SECONDS.child()
_m_dispatched = MICROBATCH_ROLE_TOTAL.labels(role="dispatched")
_m_adm_rejected = MICROBATCH_ADMISSION_TOTAL.labels(outcome="rejected")
_m_adm_expired = MICROBATCH_ADMISSION_TOTAL.labels(outcome="expired")
_m_tenants_per_batch = MICROBATCH_TENANTS_PER_BATCH.child()

# distinguishes "no result produced" from a legitimate None result —
# batch_fns whose valid outputs include None must not have them
# clobbered by the aborted-turn guard
_UNSET = object()


class AdmissionRejected(DeadlineExceeded):
    """The serving edge refused to queue a request that could not make
    its deadline (estimated queue+service time exceeds the remaining
    budget).  A subclass of :class:`DeadlineExceeded` so every existing
    503 path handles it; kept distinct so the edge can count sheds
    separately from in-flight expiries."""


class EwmaEstimator:
    """Exponentially-weighted moving average of observed durations —
    the memory behind deadline-aware admission, shared by the
    micro-batcher (device-batch service time) and the fleet router
    (replica round-trip time; pio-scout satellite).  ``0.0`` until the
    first observation, so a cold estimator never sheds: no evidence
    means admit.  Not synchronized itself — callers serialize
    observations (the batcher under its condition variable, the router
    under its round-robin lock)."""

    __slots__ = ("alpha", "value")

    def __init__(self, alpha: float = 0.25):
        self.alpha = alpha
        self.value = 0.0

    def observe(self, dt: float) -> None:
        self.value = (
            dt if self.value <= 0.0
            else self.alpha * dt + (1.0 - self.alpha) * self.value
        )

    def estimate(self) -> float:
        return self.value


def _pad_size(n: int) -> int:
    """The batch size ``n`` items actually dispatch as under pow2
    padding — THE definition; the warmup ladder derives from it."""
    return 1 << (n - 1).bit_length() if n > 1 else 1


def dispatchable_sizes(max_batch: int) -> list[int]:
    """Every batch size a padding batcher with this ``max_batch`` can
    dispatch: 1, 2, 4, ..., _pad_size(max_batch).  Template warmups
    build their compile ladders from THIS (templates/_common.pow2_ladder
    delegates here) so a change to the padding scheme cannot silently
    desynchronize warmup from dispatch.

    ``max_batch <= 0`` means "no batcher at all" (serving passes 0 when
    micro-batching is off or auto-gated off): the ladder is EMPTY —
    every request then runs the per-query predict path, and compiling
    batched executables would be pure wasted XLA work at deploy/reload."""
    if max_batch <= 0:
        return []
    top = _pad_size(max_batch)
    b, sizes = 1, []
    while b <= top:
        sizes.append(b)
        b <<= 1
    return sizes


class _Entry:
    # t_enq/t_claim/t_run0/t_run1 are the pulse timeline stamps: set by
    # whichever thread performs the transition (enqueue by the caller,
    # claim and run bracketing by the dispatcher) and read AFTER
    # ``done`` — the condition variable's release/acquire (blocking
    # path) or the dispatcher's post-batch callback (continuous path)
    # orders the writes before the read
    # tenant/fn are the pio-confluence fields: which tenant the entry
    # belongs to (the WDRR claim key) and which batch_fn executes it
    # (the group key — entries sharing a fn coalesce into ONE device
    # call; None means the owning batcher's own batch_fn).  An entry
    # carries its fn for its whole life, so in-flight queries complete
    # on the model they snapshotted even across a tenant reload.
    __slots__ = ("item", "done", "value", "error", "deadline", "tl",
                 "on_done", "tenant", "fn", "cb_fired", "turn",
                 "t_enq", "t_claim", "t_run0", "t_run1")

    def __init__(self, item, deadline: Optional[Deadline] = None,
                 tl=None, on_done: Optional[Callable] = None,
                 tenant=None, fn: Optional[Callable] = None):
        self.item = item
        self.done = False
        self.cb_fired = False
        self.value = _UNSET
        self.error: Exception | None = None
        self.deadline = deadline
        self.tl = tl
        self.on_done = on_done
        self.tenant = tenant
        self.fn = fn
        self.turn = None    # number of the turn that ran it (timeline.Turn)
        self.t_enq = time.perf_counter()
        self.t_claim = None
        self.t_run0 = None
        self.t_run1 = None


class MicroBatcher:
    """Coalesce concurrent ``submit(x)`` / ``submit_nowait(x, cb)``
    calls into ``batch_fn([x...])``.

    ``batch_fn`` receives a list of items and must return a list of
    results of the same length and order.  An exception from
    ``batch_fn`` fails every request in that batch (callers see the
    same exception a direct call would have raised).
    """

    def __init__(
        self,
        batch_fn: Callable[[Sequence[Any]], Sequence[Any]],
        max_batch: int = 64,
        max_wait_s: float = 0.0,
        pad_batches: bool = False,
    ):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.batch_fn = batch_fn
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        # pad each batch to the next power of two by repeating the last
        # item (results sliced off).  An XLA batch_fn compiles ONE
        # executable per distinct batch size; continuous batching
        # naturally produces every size 1..max_batch, which would pay a
        # compile mid-traffic for each new size — measured as a p99
        # spike on first exposure to load.  Padding bounds the
        # executable count to log2(max_batch)+1.  Only valid when
        # batch_fn is a pure per-item map (duplicated trailing items
        # must be harmless), which predicts are.
        self.pad_batches = pad_batches
        # pio-scope: THE serving hot lock — every submit, claim, and
        # completion passes through this monitor, so its wait
        # histogram is the direct queueing-for-the-batcher evidence
        self._cond = TimedCondition("microbatch")
        self._pending: list[_Entry] = []
        self._closed = False
        self._dispatcher_alive = False
        # a claimed batch is executing: the admission estimate counts
        # it as one batch ahead of a new arrival
        self._in_turn = False
        # EWMA of recent device-batch service time: the admission
        # estimator's input.  Seeded 0 (= "no evidence, admit"), so a
        # cold batcher never sheds; mutated only under _cond.
        self._ewma = EwmaEstimator()
        # full service time of the last turn (all execution groups
        # back-to-back) — what the EWMA observes; written by _run_batch
        # on the dispatcher, read by _lead there under the re-acquired
        # lock
        self._turn_s = 0.0
        # observability: how the batcher is actually coalescing.
        # Mutated only under _cond; read through stats() (bare reads
        # tore under concurrency — serving status JSON and the benches
        # all go through the locked snapshot now)
        self.batches = 0
        self.requests = 0
        self.max_seen = 0
        self.dispatched = 0
        self.expired = 0

    def reset_stats(self) -> None:
        with self._cond:
            self.batches = self.requests = self.max_seen = 0
            self.dispatched = self.expired = 0

    def stats(self) -> dict:
        """Locked snapshot of the coalescing counters plus the live
        queue depth — the ONE way to read them (status JSON, benches,
        /pulse.html)."""
        with self._cond:
            return {
                "batches": self.batches,
                "requests": self.requests,
                "maxBatchSeen": self.max_seen,
                "dispatched": self.dispatched,
                "expired": self.expired,
                "queueDepth": len(self._pending),
                "dispatcher": self._dispatcher_alive,
                "ewmaBatchSec": self._ewma.value,
            }

    # -- admission (pio-surge) ---------------------------------------------
    def estimate_wait_s(self) -> float:
        """Estimated queue + service time a request admitted NOW would
        experience: (in-flight batch + queued batches ahead + its own
        batch) x the EWMA batch service time.  0.0 until the first
        batch completes — no evidence means admit, never shed."""
        with self._cond:
            ew = self._ewma.value
            if ew <= 0.0:
                return 0.0
            ahead = 1.0 if self._in_turn else 0.0
            ahead += len(self._pending) / float(self.max_batch)
            return (ahead + 1.0) * ew

    def check_admission(self, deadline: Optional[Deadline]) -> None:
        """Raise :class:`AdmissionRejected` when ``deadline`` cannot be
        met even optimistically.  The up-front half of deadline-aware
        admission: a request the estimator already knows will die in
        the queue is answered a structured 503 NOW, costing the client
        one RTT instead of its full timeout."""
        if deadline is None:
            return
        remaining = deadline.remaining()
        if remaining <= 0.0:
            _m_adm_rejected.inc()
            raise AdmissionRejected(
                f"query deadline already exceeded its "
                f"{deadline.budget_s:.3f}s budget at admission"
            )
        est = self.estimate_wait_s()
        if est > remaining:
            _m_adm_rejected.inc()
            raise AdmissionRejected(
                f"estimated queue+service time {est * 1e3:.1f}ms exceeds "
                f"the {remaining * 1e3:.1f}ms remaining of the "
                f"{deadline.budget_s:.3f}s deadline"
            )

    # -- submission paths --------------------------------------------------
    def submit(self, item: Any,
               deadline: Optional[Deadline] = None,
               tenant=None, fn: Optional[Callable] = None) -> Any:
        """Blocking submit: admits the entry as :meth:`submit_nowait`
        does, parks the calling thread until the dispatcher's turn has
        completed it, and returns the result (or raises) there.  It
        keeps working after :meth:`close` — a reload swaps batchers
        while in-flight queries still hold the old one.
        ``tenant``/``fn`` are the shared-batcher routing fields (see
        :class:`SharedBatcherView`); plain batchers leave them None."""
        entry = _Entry(item, deadline=deadline, tenant=tenant, fn=fn)
        with self._cond:
            self._admit_locked(entry)
            while not entry.done:
                self._cond.wait()
        # credit the caller's pulse timeline with what this entry
        # actually experienced (error requests decompose too)
        self._book_timeline(entry, current_timeline())
        if entry.error is not None:
            raise entry.error
        return entry.value if entry.value is not _UNSET else None

    def submit_nowait(self, item: Any, on_done: Callable[["_Entry"], None],
                      deadline: Optional[Deadline] = None,
                      timeline=None, tenant=None,
                      fn: Optional[Callable] = None) -> None:
        """Continuous (callback) submit: the entry is admitted into the
        pending queue immediately and ``on_done(entry)`` fires — on the
        dispatcher thread, after the entry's timeline is booked — once
        ``entry.value``/``entry.error`` is set.  The dispatcher claims
        the next batch the moment the device frees up, so arrivals ride
        the NEXT device call rather than waiting out a batch
        boundary."""
        entry = _Entry(item, deadline=deadline, tl=timeline,
                       on_done=on_done, tenant=tenant, fn=fn)
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            self._admit_locked(entry)

    def _admit_locked(self, entry: _Entry) -> None:
        """Queue one entry and see that a dispatcher is there to claim
        it: started lazily, and again by the first admission after one
        has exited (closed and drained, or killed)."""
        self._pending.append(entry)
        _m_queue_depth.set(float(len(self._pending)))
        self._ensure_dispatcher_locked()
        # wake the dispatcher, parked or in its accumulation window
        self._cond.notify_all()

    def _ensure_dispatcher_locked(self) -> None:
        if not self._dispatcher_alive:
            self._dispatcher_alive = True
            threading.Thread(
                target=self._dispatch_loop, daemon=True,
                name="microbatch-dispatch",
            ).start()

    def close(self) -> None:
        """Stop accepting ``submit_nowait`` work and let the dispatcher
        drain what is pending, then exit."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    # -- claim/run core (the dispatcher's turn) ----------------------------
    def _claim_locked(self) -> list[_Entry]:
        batch = self._pending[: self.max_batch]
        del self._pending[: len(batch)]
        now = time.perf_counter()
        for e in batch:
            e.t_claim = now
        _m_queue_depth.set(float(len(self._pending)))
        return batch

    def _dispatch_loop(self) -> None:
        """The one thread that leads turns: claims pending entries
        whenever the device is free, until closed and drained."""
        register_thread_role("microbatch_dispatcher")
        with self._cond:
            try:
                while True:
                    turn = Turn()
                    with timeline_scope(turn):
                        with annotate("pio.turn.park"):
                            while not self._pending and not self._closed:
                                self._cond.wait()
                        if not self._pending:
                            break   # closed and drained
                        with annotate("pio.turn.claim"):
                            batch = self._claim_locked()
                        try:
                            self._lead(batch)
                        except Exception:
                            # _lead's finally already completed the
                            # batch; the dispatcher itself must survive
                            logger.exception("microbatch dispatcher error")
                        finally:
                            turn.finish()
            finally:
                self._dispatcher_alive = False
                if self._pending:
                    # killed by a BaseException with entries still
                    # queued: a successor claims them, or their callers
                    # would wait for a submit that may never come
                    self._ensure_dispatcher_locked()
                self._cond.notify_all()

    def _book_timeline(self, entry: _Entry, tl) -> None:
        """Book queue_wait/batch_wait/device from the entry stamps, and
        the number of the turn that ran it, onto the request's
        timeline: the entry's attached one (continuous path) or the
        calling thread's current one (blocking path).  Residual time
        inside the covered region (condition wake latency, a solo retry
        after a failed batch) is attributed to ``device`` by add_block,
        so the timeline's segment sum still equals wall time."""
        if tl is None:
            return
        tl.turn = entry.turn
        parts = []
        if entry.t_claim is not None:
            parts.append(("queue_wait", entry.t_claim - entry.t_enq))
            if entry.t_run0 is not None:
                parts.append(("batch_wait", entry.t_run0 - entry.t_claim))
                if entry.t_run1 is not None:
                    parts.append(("device", entry.t_run1 - entry.t_run0))
        tl.add_block(parts, residual_to="device")

    def _lead(self, batch: list[_Entry]) -> None:
        """Run one claimed batch on the dispatcher.  Called with the
        lock HELD; releases it around the device call (and around
        continuous-path callbacks) and re-acquires.

        Claim-time deadline enforcement happens here: entries already
        past their deadline are completed with ``DeadlineExceeded`` and
        never reach the device.

        The ENTIRE turn — accumulation window included — sits inside
        one try/finally: a BaseException landing anywhere in it
        (``Condition.wait`` re-acquires the lock before raising, so the
        lock state is consistent) must still mark every claimed entry
        done, or its blocking caller parks forever and its event-loop
        request is never answered."""
        self._in_turn = True
        completed = False
        live: list[_Entry] = []
        n_expired = 0
        with annotate("pio.turn.claim"):
            for e in batch:
                if e.deadline is not None and e.deadline.expired:
                    e.error = DeadlineExceeded(
                        f"query expired in the batch queue after "
                        f"{time.perf_counter() - e.t_enq:.3f}s (budget "
                        f"{e.deadline.budget_s:.3f}s); never dispatched"
                    )
                    n_expired += 1
                else:
                    live.append(e)
            if n_expired:
                _m_adm_expired.inc(n_expired)
        try:
            if self.max_wait_s > 0 and live and len(live) < self.max_batch:
                # optional accumulation window (off by default): give
                # near-simultaneous arrivals a chance to join this batch.
                # Arrivals notify; absorb after EVERY wake (timeout
                # included) so nothing queued during the window is left
                # behind for the next turn.
                deadline = time.monotonic() + self.max_wait_s
                with annotate("pio.turn.park"):
                    while len(live) < self.max_batch:
                        left = deadline - time.monotonic()
                        if left <= 0:
                            break
                        self._cond.wait(left)
                        take = self.max_batch - len(live)
                        absorbed = self._pending[:take]
                        del self._pending[:take]
                        if absorbed:
                            now = time.perf_counter()
                            for e in absorbed:
                                e.t_claim = now
                            live += absorbed
                            batch += absorbed
                            _m_queue_depth.set(float(len(self._pending)))
            if live:
                self._cond.release()
                try:
                    self._run_batch(live)
                finally:
                    self._cond.acquire()
            completed = True
        finally:
            for e in batch:
                if not completed and e.value is _UNSET and e.error is None:
                    # a BaseException (KeyboardInterrupt/SystemExit) tore
                    # through the turn: _exec_group's except clause only
                    # handles Exception, so the batch's callers would
                    # otherwise wake with value=None and serve garbage.
                    # The interrupt ends the dispatcher; they get this.
                    e.error = RuntimeError(
                        "batch turn aborted before producing results"
                    )
                e.done = True
            self._in_turn = False
            if live:
                self.batches += 1
                self.max_seen = max(self.max_seen, len(live))
                # the estimator tracks the FULL turn (every execution
                # group back-to-back), not just the first group's call
                if self._turn_s > 0.0:
                    self._ewma.observe(self._turn_s)
                    self._turn_s = 0.0
            self.requests += len(batch)
            self.expired += n_expired
            # continuous entries: completed by callback on this thread,
            # no request thread parked for them
            n_disp = sum(1 for e in batch if e.on_done is not None)
            if n_disp:
                self.dispatched += n_disp
                _m_dispatched.inc(n_disp)
            self._cond.notify_all()
            # end-of-turn sweep for continuous-path entries whose
            # callbacks did NOT fire per-group in _run_batch: claim-time
            # deadline expiries (never executed) and anything a
            # BaseException tore past.  Inside the finally so even an
            # aborted turn still answers every event-loop request
            # (their entries carry the aborted-turn error by now).
            cbs = [e for e in batch
                   if e.on_done is not None and not e.cb_fired]
            if cbs:
                self._cond.release()
                try:
                    self._fire_callbacks(cbs)
                finally:
                    self._cond.acquire()

    def _group(self, batch: list[_Entry]) -> list:
        """Partition one claimed batch into execution groups
        ``[(batch_fn, entries)]``.  The plain batcher has ONE group —
        its own ``batch_fn`` — so a claim is one device call exactly as
        before.  The shared batcher groups by each entry's carried fn
        (per-tenant model identity): entries sharing a fn coalesce into
        one device call; distinct models run back-to-back inside the
        same dispatcher turn."""
        by_fn: dict = {}
        order = []
        for e in batch:
            k = id(e.fn) if e.fn is not None else 0
            g = by_fn.get(k)
            if g is None:
                g = (e.fn if e.fn is not None else self.batch_fn, [])
                by_fn[k] = g
                order.append(k)
            g[1].append(e)
        return [by_fn[k] for k in order]

    def _run_batch(self, batch: list[_Entry]) -> None:
        """Execute one claimed batch as its execution groups, measuring
        the FULL turn (what the admission estimator predicts).

        Each group's continuous-path callbacks fire the moment THAT
        group's device call returns — before the next group runs.  With
        end-of-turn firing, a multi-model turn made every group-1
        client wait out group-2's device time as pure batch_wait, and
        closed-loop clients locksteped onto whole-turn boundaries
        (measured: 2-tenant QPS@SLO dropped ~25% and p99 grew by a
        full group time).  Runs WITHOUT the lock held."""
        t0 = time.perf_counter()
        with annotate("pio.turn.claim"):
            groups = self._group(batch)
        for fn, entries in groups:
            self._exec_group(fn, entries)
            self._fire_callbacks(entries)
        self._turn_s = max(time.perf_counter() - t0, 0.0)

    def _fire_callbacks(self, entries: list[_Entry]) -> None:
        """Book timelines and fire continuous-path callbacks for
        already-executed entries.  Idempotent per entry (``cb_fired``),
        so the end-of-turn sweep can still answer anything a
        BaseException left unfired.  Must be called WITHOUT the lock —
        callbacks enqueue response bytes to the event loop."""
        turn = current_timeline()   # the dispatcher's Turn
        with annotate("pio.turn.complete"):
            for e in entries:
                if e.on_done is None or e.cb_fired:
                    continue
                e.cb_fired = True
                turn.open_part()
                self._book_timeline(e, e.tl)
                mark_part("book")
                try:
                    e.on_done(e)
                except Exception:
                    logger.exception(
                        "microbatch completion callback failed")

    def _exec_group(self, fn: Callable, batch: list[_Entry]) -> None:
        """Run one device call; on failure, isolate the blast radius.

        A batched device call is all-or-nothing, so one malformed query
        would otherwise fail every innocent request coalesced with it
        (per-request dispatch isolated such failures).  On a batch of
        >1 failing, re-run each item ALONE: good requests succeed, the
        bad one gets its own exception — same outcomes as unbatched
        serving, paid only on the rare failure path.
        """
        try:
            with annotate("pio.turn.claim"):
                items = [e.item for e in batch]
                n = len(items)
                if self.pad_batches and n > 1:
                    items = items + [items[-1]] * (_pad_size(n) - n)
                turn = current_timeline()   # the dispatcher's Turn
                turn.rows += n
                turn.padded += len(items)
                t0 = time.perf_counter()
                for e in batch:
                    e.t_run0 = t0
                    e.turn = turn.turn
                if batch[0].t_claim is not None:
                    # accumulation-window cost: first claim -> dispatch
                    _m_batch_wait.observe(max(t0 - batch[0].t_claim, 0.0))
                _m_batch_size.observe(float(n))
            # all of fn is `fetch` unless it books finer steps of its own
            # (templates/recommendation.py: prepare, dispatch, decode)
            with annotate("pio.turn.fetch", rows=n, padded=len(items)):
                results = fn(items)
            t1 = time.perf_counter()
            for e in batch:
                e.t_run1 = t1
            if len(results) != len(items):
                raise RuntimeError(
                    f"batch_fn returned {len(results)} results "
                    f"for {len(items)} items"
                )
            for e, r in zip(batch, results):
                e.value = r
        except Exception as exc:  # noqa: BLE001 — propagate per caller
            if len(batch) == 1:
                batch[0].error = exc
                return
            for e in batch:
                try:
                    (r,) = fn([e.item])
                    e.value = r
                except Exception as solo:  # noqa: BLE001
                    e.error = solo


class SharedBatcher(MicroBatcher):
    """ONE continuous batcher for every tenant of a server.

    A dispatcher a tenant would mean T threads each coalescing only 1/T
    of the traffic and competing for the single device queue.  This
    class keeps :class:`MicroBatcher`'s claim/run core (one pending
    queue, one lazily-started dispatcher) and changes WHO gets claimed:

    * **Claim-time weighted deficit round-robin across tenants.**  Each
      claim walks the tenants with pending entries in rotation order;
      every round a tenant's deficit grows by its weight (normalized to
      the largest active weight, floored at ``MIN_SHARE`` so even a
      zero-weighted tenant drains) and each whole unit of deficit buys
      one entry into the batch.  A whale tenant flooding the queue
      therefore claims at most its weighted share per turn while every
      other tenant keeps its own share — starvation-free by
      construction, with FIFO order preserved *within* each tenant.
      A claim with only one tenant pending short-circuits to the plain
      FIFO claim (the solo path pays nothing for the machinery).
    * **Group-keyed execution.**  Claimed entries carry their tenant's
      ``batch_fn``; entries sharing a fn (co-resident same-model
      tenants, or many queries of one tenant) coalesce into ONE padded
      device call, distinct models run back-to-back inside the same
      dispatcher turn — one dispatcher, one device queue walk, no
      cross-tenant thread competition.

    Per-tenant deadline admission, token-bucket quota, and breaker
    checks all stay at enqueue (the registry's ``resolve()`` and the
    serving edge's ``check_admission``) — a query that should shed is
    answered before it ever touches this shared state.

    Weights are PULLED at claim time via per-tenant ``weight_fn``
    callbacks (the serving layer points them at the registry's
    experiment weights), so a hot ``POST /tenants/weights`` update
    reshapes the very next claim with no push plumbing.
    """

    # floor on a tenant's relative claim share: even weight-0 tenants
    # accrue deficit at 1/20 of the heaviest, so nothing queued can be
    # starved and the WDRR loop is bounded (<= 20 rounds per claim)
    MIN_SHARE = 0.05

    def __init__(self, max_batch: int = 64, max_wait_s: float = 0.0,
                 pad_batches: bool = True):
        # no default batch_fn: every entry must carry its tenant's fn
        def _no_fn(items):
            raise RuntimeError(
                "SharedBatcher entries must carry a batch_fn "
                "(submit via a SharedBatcherView)"
            )

        super().__init__(_no_fn, max_batch=max_batch,
                         max_wait_s=max_wait_s, pad_batches=pad_batches)
        # all guarded by _cond, like every other mutable field
        self._weights: dict = {}
        self._weight_fns: dict = {}
        self._reg_counts: dict = {}
        self._deficit: dict = {}
        self._rr: list = []
        self.mixed_batches = 0
        self.tenant_claims: dict = {}

    # -- tenant lifecycle --------------------------------------------------
    def register_tenant(self, tenant, weight: float = 1.0,
                        weight_fn: Optional[Callable] = None) -> None:
        """A view's registration.  Registration counts are per tenant
        key: a reload registers the NEW view before closing the old
        one, and the tenant's scheduling state must survive the
        overlap."""
        with self._cond:
            self._reg_counts[tenant] = self._reg_counts.get(tenant, 0) + 1
            self._weights[tenant] = float(weight)
            if weight_fn is not None:
                self._weight_fns[tenant] = weight_fn
            if tenant not in self._rr:
                self._rr.append(tenant)

    def retire_tenant(self, tenant) -> None:
        """Drop a tenant's scheduling state once its LAST view closes
        (eviction/removal).  Entries it already enqueued still complete
        — they carry their own fn."""
        with self._cond:
            n = self._reg_counts.get(tenant, 0) - 1
            if n > 0:
                self._reg_counts[tenant] = n
                return
            self._reg_counts.pop(tenant, None)
            self._weights.pop(tenant, None)
            self._weight_fns.pop(tenant, None)
            self._deficit.pop(tenant, None)
            if tenant in self._rr:
                self._rr.remove(tenant)

    def set_weights(self, weights: dict) -> None:
        """Push-style weight update (tests / non-registry callers; the
        serving layer uses pull via weight_fn)."""
        with self._cond:
            for t, w in weights.items():
                self._weights[t] = float(w)

    def _weight_of_locked(self, tenant) -> float:
        fn = self._weight_fns.get(tenant)
        if fn is not None:
            try:
                w = float(fn())
                if w > 0.0:
                    return w
            except Exception:
                logger.exception("weight_fn for tenant %r failed", tenant)
        w = self._weights.get(tenant, 1.0)
        return w if w > 0.0 else 0.0

    # -- claim policy ------------------------------------------------------
    def _claim_locked(self) -> list[_Entry]:
        pend = self._pending
        if not pend:
            return []
        by_tenant: dict = {}
        order: list = []
        for e in pend:
            q = by_tenant.get(e.tenant)
            if q is None:
                q = by_tenant[e.tenant] = []
                order.append(e.tenant)
            q.append(e)
        if len(by_tenant) == 1:
            # solo-tenant claim: plain FIFO, zero WDRR overhead (the
            # single-tenant server and idle-hive case)
            batch = super()._claim_locked()
            if batch:
                _m_tenants_per_batch.observe(1.0)
                t0 = batch[0].tenant
                self.tenant_claims[t0] = (
                    self.tenant_claims.get(t0, 0) + len(batch)
                )
            return batch
        # rotation order: persistent registration order, rotated one
        # step per claim so no tenant permanently goes first; tenants
        # that only appear in the queue (e.g. already-retired) append
        for t in order:
            if t not in self._rr:
                self._rr.append(t)
        walk = [t for t in self._rr if t in by_tenant]
        # weights normalized to the largest ACTIVE weight, floored —
        # the round count per claim is bounded by 1/MIN_SHARE
        weights = {t: self._weight_of_locked(t) for t in walk}
        wmax = max(weights.values()) or 1.0
        share = {
            t: max(weights[t] / wmax, self.MIN_SHARE) for t in walk
        }
        deficit = self._deficit
        batch: list[_Entry] = []
        room = self.max_batch
        while room > 0 and any(by_tenant[t] for t in walk):
            for t in walk:
                q = by_tenant[t]
                if not q:
                    # classic DRR: an empty queue forfeits its deficit
                    # (banked credit would burst later, not smooth)
                    deficit.pop(t, None)
                    continue
                d = deficit.get(t, 0.0) + share[t]
                while q and room > 0 and d >= 1.0:
                    batch.append(q.pop(0))
                    d -= 1.0
                    room -= 1
                deficit[t] = d
                if room <= 0:
                    break
        # remove claimed entries from pending, preserving FIFO order
        claimed = {id(e) for e in batch}
        self._pending = [e for e in pend if id(e) not in claimed]
        now = time.perf_counter()
        tenants_seen = set()
        for e in batch:
            e.t_claim = now
            tenants_seen.add(e.tenant)
            self.tenant_claims[e.tenant] = (
                self.tenant_claims.get(e.tenant, 0) + 1
            )
        if len(tenants_seen) > 1:
            self.mixed_batches += 1
        if batch:
            _m_tenants_per_batch.observe(float(len(tenants_seen)))
        if self._rr:
            self._rr.append(self._rr.pop(0))
        _m_queue_depth.set(float(len(self._pending)))
        return batch

    # -- observability -----------------------------------------------------
    def reset_stats(self) -> None:
        super().reset_stats()
        with self._cond:
            self.mixed_batches = 0
            self.tenant_claims = {}

    def stats(self) -> dict:
        out = super().stats()
        with self._cond:
            out["shared"] = True
            out["tenantsRegistered"] = len(self._reg_counts)
            out["mixedBatches"] = self.mixed_batches
            out["tenantClaims"] = {
                ("/".join(str(p) for p in k) if isinstance(k, tuple)
                 else str(k)): v
                for k, v in self.tenant_claims.items()
            }
        return out


class SharedBatcherView:
    """One tenant's handle on the server's :class:`SharedBatcher`.

    Exposes the surface the serving edge and benches use on a
    ``MicroBatcher`` (``submit`` / ``submit_nowait`` /
    ``check_admission`` / ``estimate_wait_s`` / ``stats`` /
    ``batch_fn`` / ``close``), stamping every entry with the tenant key
    and the tenant's own ``batch_fn``.  ``close()`` retires only THIS
    tenant's scheduling state — in-flight entries complete on the fn
    they carry, and the shared core (and its dispatcher) lives until
    the server stops."""

    __slots__ = ("core", "tenant", "batch_fn", "_closed")

    def __init__(self, core: SharedBatcher, tenant, batch_fn: Callable,
                 weight: float = 1.0,
                 weight_fn: Optional[Callable] = None):
        self.core = core
        self.tenant = tenant
        self.batch_fn = batch_fn
        self._closed = False
        core.register_tenant(tenant, weight=weight, weight_fn=weight_fn)

    @property
    def max_batch(self) -> int:
        return self.core.max_batch

    @property
    def pad_batches(self) -> bool:
        return self.core.pad_batches

    def estimate_wait_s(self) -> float:
        return self.core.estimate_wait_s()

    def check_admission(self, deadline: Optional[Deadline]) -> None:
        self.core.check_admission(deadline)

    def stats(self) -> dict:
        out = self.core.stats()
        out["tenant"] = str(self.tenant)
        return out

    def reset_stats(self) -> None:
        self.core.reset_stats()

    def submit(self, item: Any,
               deadline: Optional[Deadline] = None) -> Any:
        if self._closed:
            raise RuntimeError("batcher is closed")
        return self.core.submit(item, deadline=deadline,
                                tenant=self.tenant, fn=self.batch_fn)

    def submit_nowait(self, item: Any, on_done: Callable,
                      deadline: Optional[Deadline] = None,
                      timeline=None) -> None:
        # closed-view submits raise the same RuntimeError a closed
        # MicroBatcher does: the event-loop edge's reload-retry path
        # keys on it
        if self._closed:
            raise RuntimeError("batcher is closed")
        self.core.submit_nowait(item, on_done, deadline=deadline,
                                timeline=timeline, tenant=self.tenant,
                                fn=self.batch_fn)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.core.retire_tenant(self.tenant)
