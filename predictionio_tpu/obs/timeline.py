"""pio-pulse: per-request lifecycle timeline decomposition.

The latency histogram says *how slow* a request was; the flight
recorder says *which* requests were slow; this module says **where the
time went** — every served query carries a :class:`Timeline` of
monotonic segment durations

    ``parse -> auth -> queue_wait -> batch_wait -> device -> serialize
    -> write``

captured with cheap ``perf_counter`` stamps threaded through
``server/http_base.py`` (request edge + socket write),
``server/serving.py`` (decode/admission/serialize) and
``server/microbatch.py`` (per-entry enqueue/claim/run stamps — the
batcher credits the caller's timeline with exactly the queue-wait,
accumulation-wait and device time its entry experienced).  Segment
durations aggregate into the ``pio_serve_segment_seconds{segment}``
histogram family (the event-server ingest path gets the parallel
``pio_events_segment_seconds{segment}``: parse/auth/store_write/reply),
and the per-request segment dict rides the ``serve.query`` span attrs,
so a flight-recorder worst-N entry decomposes into *which segment ate
the time* without any extra capture machinery.

Concurrency saturation is first-class: ``pio_serve_inflight`` (requests
between decode and reply), ``pio_microbatch_queue_depth`` (entries
parked behind the in-flight batch), ``pio_microbatch_batch_size`` /
``pio_microbatch_wait_seconds`` histograms together answer "is the
batcher widening concurrency or just queueing it".

Accounting invariant: a finished timeline's segments SUM to the
measured end-to-end wall time of the regions it covered (residual time
inside a composite region — e.g. condition-variable wake latency after
a batched device call — is attributed to the region's final segment,
never dropped), so per-segment means read off ``/metrics`` reconcile
with the end-to-end latency histogram instead of silently leaking tail
time.  ``tests/test_timeline.py`` holds the property test.

The dispatcher's turn has a timeline of its own: the ``batch`` family
(:class:`Turn`, ``pio_batch_turn_seconds{segment}``), one per claim,
whose segments ``park -> claim -> prepare -> dispatch -> fetch ->
decode -> complete`` say what the batcher's thread did between two
device calls, in wall and in thread-CPU seconds.  Finished turns stay
in a bounded in-memory deque (:func:`batch_turns`); each served
request's ``serve.query`` span names its turn (``batchTurn``).  Inside
``complete`` a turn also keeps ``parts``: seconds summed over its
requests by what the completion callback does for each
(:func:`mark_part`: ``book -> serve -> observe -> encode -> handoff``).

The event loop's thread has the ``loop`` family (:class:`LoopRecord`,
``pio_loop_seconds_total{server,phase}``): cumulative wall seconds by
what the thread does between two returns of ``select`` (``poll ->
accept | read | drain | write -> sweep``), its thread-CPU seconds and
those it spent inside ``select``, the answers it wrote, and how long
answers finished on other threads waited for it (hand-off wait).  About
ten times a second the sums are copied as one *beat* into a bounded
deque (:func:`loop_beats`); a reader subtracts two beats.

Profiler bridge: :class:`annotate` enters a
``jax.profiler.TraceAnnotation`` in every process that has imported
jax, whoever started the profiler (``GET /debug/profile`` through
:func:`capture_profile`, ``jax.profiler.start_trace`` /
``start_server``, a benchmark's own session); with no session live the
annotation is the profiler's own cheap no-op.  Used as a ``with`` under
a :class:`Turn`, the same call books the segment, so a span in the
trace and a segment in memory are one call.

Pure stdlib at import; jax is only looked up in ``sys.modules``.
"""

from __future__ import annotations

import collections
import itertools
import os
import sys
import threading
import time
from pathlib import Path
from typing import Optional, Sequence, Tuple

from . import get_registry, log_buckets, telemetry_home

__all__ = [
    "BATCH_SEGMENTS",
    "EVENT_SEGMENTS",
    "LOOP_PHASES",
    "LoopRecord",
    "ProfileBusy",
    "SERVE_SEGMENTS",
    "TURN_PARTS",
    "Timeline",
    "Turn",
    "annotate",
    "batch_turns",
    "capture_profile",
    "current_timeline",
    "loop_beats",
    "mark",
    "mark_part",
    "profiles_dir",
    "register_segment_family",
    "timeline_scope",
]

_registry = get_registry()

# the segment taxonomies (docs/ARCHITECTURE.md "Pulse" lists semantics);
# order here is display order on /pulse.html
SERVE_SEGMENTS = (
    "parse", "auth", "queue_wait", "batch_wait", "device", "serialize",
    "write",
)
EVENT_SEGMENTS = ("parse", "auth", "store_write", "reply")
# the dispatcher's turn, in the order a turn passes through them
BATCH_SEGMENTS = (
    "park", "claim", "prepare", "dispatch", "fetch", "decode", "complete",
)
# what a turn's completion callback does for one request, inside `complete`
TURN_PARTS = ("book", "serve", "observe", "encode", "handoff")
# what the event loop's thread does between two returns of select
LOOP_PHASES = ("poll", "accept", "read", "drain", "write", "sweep")

SERVE_SEGMENT_SECONDS = _registry.histogram(
    "pio_serve_segment_seconds",
    "Per-request serving-path segment durations (parse/auth/queue_wait/"
    "batch_wait/device/serialize/write); per-request segments sum to "
    "the end-to-end handler time",
    labels=("segment",),
)
EVENTS_SEGMENT_SECONDS = _registry.histogram(
    "pio_events_segment_seconds",
    "Per-request event-ingest segment durations "
    "(parse/auth/store_write/reply)",
    labels=("segment",),
)
BATCH_TURN_SECONDS = _registry.histogram(
    "pio_batch_turn_seconds",
    "Wall seconds of one turn of the batch dispatcher's thread by "
    "segment (park/claim/prepare/dispatch/fetch/decode/complete); a "
    "turn's segments sum to its wall time, claim to claim",
    labels=("segment",),
)
LOOP_SECONDS_TOTAL = _registry.counter(
    "pio_loop_seconds_total",
    "Wall seconds of an event loop's thread by phase (poll = inside "
    "select; accept/read/drain/write = the events handled; sweep = the "
    "loop's own housekeeping); the phases sum to the thread's wall "
    "time, so 1 - rate(poll) is the loop's busy share",
    labels=("server", "phase"),
)
LOOP_CPU_SECONDS_TOTAL = _registry.counter(
    "pio_loop_cpu_seconds_total",
    "Thread-CPU seconds of an event loop's thread, select included: "
    "less pio_loop_poll_cpu_seconds_total and over the non-poll wall "
    "seconds, the share of its work time the thread was on a CPU and "
    "not runnable behind the interpreter lock",
    labels=("server",),
)
LOOP_POLL_CPU_SECONDS_TOTAL = _registry.counter(
    "pio_loop_poll_cpu_seconds_total",
    "Thread-CPU seconds an event loop's thread spent inside select (a "
    "blocking system call is not free), estimated from one select in "
    "POLL_CPU_EVERY",
    labels=("server",),
)
LOOP_HANDOFF_WAIT_SECONDS_TOTAL = _registry.counter(
    "pio_loop_handoff_wait_seconds_total",
    "Seconds finished answers waited between being queued off the "
    "loop's thread and the loop taking them up; over "
    "pio_loop_handoffs_total the mean hand-off wait",
    labels=("server",),
)
LOOP_HANDOFFS_TOTAL = _registry.counter(
    "pio_loop_handoffs_total",
    "Answers queued to an event loop from another thread",
    labels=("server",),
)
SERVE_INFLIGHT = _registry.gauge(
    "pio_serve_inflight",
    "Queries currently inside predict_json (decode -> serialize): the "
    "serving edge's concurrency saturation gauge",
)
MICROBATCH_QUEUE_DEPTH = _registry.gauge(
    "pio_microbatch_queue_depth",
    "Entries waiting in the micro-batcher's pending list (parked "
    "behind the in-flight batch)",
)
MICROBATCH_BATCH_SIZE = _registry.histogram(
    "pio_microbatch_batch_size",
    "Dispatched micro-batch sizes (pre-padding: what actually "
    "coalesced)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512),
)
MICROBATCH_WAIT_SECONDS = _registry.histogram(
    "pio_microbatch_wait_seconds",
    "Per-batch wait from first claim to device dispatch (the "
    "accumulation-window cost)",
    buckets=log_buckets(1e-6, 10.0, per_decade=4),
)
MICROBATCH_ROLE_TOTAL = _registry.counter(
    "pio_microbatch_role_total",
    "Requests by batcher role: a dispatched request was admitted by "
    "submit_nowait and completed by callback on the dispatcher (no "
    "request thread parked for it)",
    labels=("role",),
)
MICROBATCH_ADMISSION_TOTAL = _registry.counter(
    "pio_microbatch_admission_total",
    "Deadline-aware admission outcomes (pio-surge): rejected = the "
    "edge answered a structured 503 up front because the estimated "
    "queue+service time exceeded the request deadline; expired = "
    "claimed from the queue already past its deadline and completed "
    "without ever reaching the device",
    labels=("outcome",),
)
MICROBATCH_TENANTS_PER_BATCH = _registry.histogram(
    "pio_microbatch_tenants_per_batch",
    "Distinct tenants coalesced into one shared-batcher dispatcher "
    "claim (pio-confluence): >1 means cross-tenant traffic rode one "
    "dispatcher turn instead of competing per-tenant device queues — "
    "the mixing evidence the hive_smoke gate asserts",
    buckets=(1, 2, 4, 8, 16, 32),
)

# children cached at import: .labels() is a dict build + lock per call
# (~1.5 us), too hot for per-request use — and materializing them keeps
# the /metrics schema complete (zero-valued) from the first scrape
_SEGMENT_CHILDREN = {
    "serve": {
        s: SERVE_SEGMENT_SECONDS.labels(segment=s) for s in SERVE_SEGMENTS
    },
    "events": {
        s: EVENTS_SEGMENT_SECONDS.labels(segment=s)
        for s in EVENT_SEGMENTS
    },
}
def register_segment_family(family: str, histogram_family,
                            segments) -> None:
    """Attach a new timeline family (pio-lens adds ``router``):
    ``Timeline(family)`` instances booked via :meth:`Timeline.finish`
    observe into ``histogram_family{segment=...}`` children, cached
    here once like the serve/events families above."""
    _SEGMENT_CHILDREN[family] = {
        s: histogram_family.labels(segment=s) for s in segments
    }


register_segment_family("batch", BATCH_TURN_SECONDS, BATCH_SEGMENTS)

SERVE_INFLIGHT.child()
MICROBATCH_QUEUE_DEPTH.child()
MICROBATCH_BATCH_SIZE.child()
MICROBATCH_WAIT_SECONDS.child()
MICROBATCH_TENANTS_PER_BATCH.child()
MICROBATCH_ROLE_TOTAL.labels(role="dispatched")
MICROBATCH_ADMISSION_TOTAL.labels(outcome="rejected")
MICROBATCH_ADMISSION_TOTAL.labels(outcome="expired")


class Timeline:
    """Monotonic per-request segment accumulator.

    ``mark(seg)`` closes the region since the previous boundary and
    books it under ``seg``; ``add_block(parts, residual_to)`` closes a
    composite region whose interior was measured elsewhere (the
    batcher's entry stamps), crediting the measured parts and the
    residual — wake latency, lock handoff — to ``residual_to`` so the
    segment sum still equals the region's wall time.  Single-threaded
    by construction (one request, one timeline, marked only from the
    thread carrying the request), hence no lock.
    """

    __slots__ = ("family", "segments", "t0", "_last", "turn")

    def __init__(self, family: str = "serve"):
        self.family = family
        self.segments: dict[str, float] = {}
        self.t0 = self._last = time.perf_counter()
        # number of the dispatcher's turn that served this request (the
        # batcher books it with the entry's segments); a Turn's own
        self.turn: Optional[int] = None

    def mark(self, segment: str) -> None:
        now = time.perf_counter()
        self.segments[segment] = (
            self.segments.get(segment, 0.0) + (now - self._last)
        )
        self._last = now

    def add_block(self, parts: Sequence[Tuple[str, float]],
                  residual_to: str) -> None:
        now = time.perf_counter()
        total = max(now - self._last, 0.0)
        parts = [(seg, max(dur, 0.0)) for seg, dur in parts]
        acc = sum(dur for _, dur in parts)
        if acc > total:
            # interior stamps can only exceed the region by clock
            # jitter (they are taken inside it); scale proportionally
            # so the sum identity holds UNCONDITIONALLY — the identity
            # is what makes /metrics segment means reconcile with e2e
            scale = total / acc if acc > 0 else 0.0
            parts = [(seg, dur * scale) for seg, dur in parts]
            acc = total
        segs = self.segments
        for seg, dur in parts:
            segs[seg] = segs.get(seg, 0.0) + dur
        segs[residual_to] = segs.get(residual_to, 0.0) + (total - acc)
        self._last = now

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def snapshot_ms(self) -> dict:
        """Rounded-ms view for span attrs / flight records (small JSON,
        human-scannable next to durationSec)."""
        return {k: round(v * 1e3, 3) for k, v in self.segments.items()}

    def finish(self) -> dict:
        """Observe every booked segment into this family's histogram
        children and return the raw segment dict (seconds)."""
        children = _SEGMENT_CHILDREN.get(self.family)
        if children is not None:
            for seg, dur in self.segments.items():
                child = children.get(seg)
                if child is not None:
                    child.observe(dur)
        return dict(self.segments)


# -- thread-local scope (the trace_scope pattern) ---------------------------

_local = threading.local()


def current_timeline() -> Optional[Timeline]:
    return getattr(_local, "tl", None)


class timeline_scope:
    """Bind a timeline to this thread for the duration of the block
    (the micro-batcher and nested marks find it via
    :func:`current_timeline`).  Slotted like ``trace_scope``: this
    wraps every served query."""

    __slots__ = ("tl", "_prev")

    def __init__(self, tl: Optional[Timeline]):
        self.tl = tl

    def __enter__(self) -> Optional[Timeline]:
        self._prev = getattr(_local, "tl", None)
        _local.tl = self.tl
        return self.tl

    def __exit__(self, *exc) -> None:
        _local.tl = self._prev


def mark(segment: str) -> None:
    """Mark a boundary on the thread's current timeline; free no-op
    when no timeline is in scope (direct library calls, tests)."""
    tl = getattr(_local, "tl", None)
    if tl is not None:
        tl.mark(segment)


# -- the dispatcher's turn ---------------------------------------------------

# finished turns, newest last: two minutes at the 130 turns/s of a
# lightly loaded server (7.6 ms a turn).  Appended by the thread that
# ran the turn and copied whole by readers; both are one call into the
# deque under the interpreter lock.
_TURNS: collections.deque = collections.deque(maxlen=16384)
_turn_numbers = itertools.count(1)


def batch_turns() -> list:
    """The finished turns still in memory, oldest first: per turn its
    number ``turn``, ``t0`` (``perf_counter``), ``rows`` and ``padded``
    rows sent to the device, per segment ``wall`` and thread-``cpu``
    seconds, ``gcSec`` the collector took on the turn's thread, the
    ``requests`` its completion callbacks answered and ``parts``, the
    wall seconds of ``complete`` summed over them by what was done."""
    return list(_TURNS)


class Turn(Timeline):
    """One turn of a batcher's leading thread, from where it starts to
    wait for work to where its last completion callback has returned.

    Segments are not marked but booked by :class:`annotate` scopes
    named ``pio.turn.<segment>``, each with its own time only (a scope
    inside another takes its time out of the outer one), in wall and in
    thread-CPU seconds: wall minus CPU of a segment that does not wait
    by design is time the thread sat runnable but off the CPU.
    :meth:`finish` books what no scope covered to ``complete``, so the
    segments still sum to the turn's wall time.

    ``parts`` is a second dictionary, not segments: wall seconds inside
    ``complete`` by what the completion callback does for one request,
    summed over the turn's ``requests`` (:meth:`open_part` before each,
    :func:`mark_part` at each step's end, the names :data:`TURN_PARTS`).
    Their sum stays under ``complete``; what is left is the batcher's
    own loop."""

    __slots__ = ("cpu", "rows", "padded", "gc_s", "parts", "requests",
                 "_c0", "_wall", "_cpu", "_part")

    PREFIX = "pio.turn."    # + segment: the scopes that book themselves

    def __init__(self):
        super().__init__("batch")
        self.turn = next(_turn_numbers)
        self.cpu: dict[str, float] = {}
        self.rows = self.padded = 0
        self.gc_s = 0.0
        self.parts = dict.fromkeys(TURN_PARTS, 0.0)
        self.requests = 0
        self._c0 = time.thread_time()
        self._wall = self._cpu = 0.0   # booked so far, all segments
        self._part = self.t0           # where the open part began

    def open_part(self) -> None:
        """One more request's completion begins here."""
        self.requests += 1
        self._part = time.perf_counter()

    def book(self, segment: str, wall: float, cpu: float) -> None:
        self.segments[segment] = self.segments.get(segment, 0.0) + wall
        self.cpu[segment] = self.cpu.get(segment, 0.0) + cpu
        self._wall += wall
        self._cpu += cpu

    def finish(self) -> dict:
        """Close the turn: residual to ``complete``, segments into
        ``pio_batch_turn_seconds``, the record into :func:`batch_turns`."""
        self.book("complete", self.elapsed() - self._wall,
                  time.thread_time() - self._c0 - self._cpu)
        _TURNS.append({
            "turn": self.turn, "t0": self.t0, "rows": self.rows,
            "padded": self.padded, "wall": dict(self.segments),
            "cpu": dict(self.cpu), "gcSec": self.gc_s,
            "requests": self.requests, "parts": dict(self.parts),
        })
        return super().finish()


def mark_part(name: str) -> None:
    """Close the open part of the dispatcher's turn under ``name`` (one
    of :data:`TURN_PARTS`) and open the next; a no-op unless this
    thread's current timeline is a :class:`Turn` (a blocking caller's
    thread, the aux pool, tests)."""
    tl = getattr(_local, "tl", None)
    if isinstance(tl, Turn):
        now = time.perf_counter()
        tl.parts[name] += now - tl._part
        tl._part = now


# -- the event loop's thread ---------------------------------------------------

# beats of every loop in the process, newest last: 13 minutes of one busy
# loop (ten a second); an idle loop beats once a second
_BEATS: collections.deque = collections.deque(maxlen=8192)
_loop_numbers = itertools.count(1)
BEAT_PERIOD_S = 0.1
# one select in so many has the thread's CPU clock read round it: odd, so
# that a loop whose iterations alternate (a read, a drain) has both read
POLL_CPU_EVERY = 7


def loop_beats() -> list:
    """The beats still in memory, oldest first.  A beat is a copy of one
    loop's CUMULATIVE sums at ``t`` (``perf_counter``): ``loop`` (the
    record's number: two servers of one name are two loops) and
    ``server``, ``wall`` seconds by phase, ``cpu`` seconds of the thread
    and ``pollCpu`` those of them inside ``select``, ``responses``
    flushed, ``handoffs`` and ``handoffWaitSec``.  Subtract two beats of
    one loop."""
    return list(_BEATS)


class LoopRecord:
    """The sums one event loop keeps of its own thread, touched by that
    thread alone (no lock): the loop adds to them at the boundaries it
    already has and calls :meth:`beat` at an iteration's end once
    ``BEAT_PERIOD_S`` has passed; only there are the sums copied into
    :func:`loop_beats` and brought to ``/metrics``.

    ``time.thread_time()`` is a system call of 6 us on the chip's host
    (0.2 us where the kernel answers it in user space), so the thread's
    CPU clock is read at the beat alone.  What that holds besides the
    loop's work is ``select`` itself, which burns 100 us of thread-CPU
    there each time it blocks: ``poll_cpu`` estimates it from a pair of
    readings round one ``select`` in ``POLL_CPU_EVERY``, scaled by that
    count, so ``cpu - poll_cpu`` is the CPU of the loop's work at 1.9 us
    an iteration, and exact where ``select`` seldom blocks."""

    __slots__ = ("loop", "server", "wall", "cpu", "poll_cpu", "responses",
                 "handoffs", "handoff_wait", "t_beat", "_cpu_read",
                 "_counters", "_sent")

    def __init__(self, server: str):
        self.loop = next(_loop_numbers)
        self.server = server
        self.wall = dict.fromkeys(LOOP_PHASES, 0.0)
        self.cpu = self.poll_cpu = self.handoff_wait = 0.0
        self.responses = self.handoffs = 0
        self.t_beat = self._cpu_read = 0.0
        # children made here, at the server's start-up, like
        # _SEGMENT_CHILDREN: the schema is whole from the first scrape
        self._counters = {
            p: LOOP_SECONDS_TOTAL.labels(server=server, phase=p)
            for p in LOOP_PHASES
        }
        self._counters["cpu"] = LOOP_CPU_SECONDS_TOTAL.labels(server=server)
        self._counters["pollCpu"] = (
            LOOP_POLL_CPU_SECONDS_TOTAL.labels(server=server))
        self._counters["handoffWaitSec"] = (
            LOOP_HANDOFF_WAIT_SECONDS_TOTAL.labels(server=server))
        self._counters["handoffs"] = LOOP_HANDOFFS_TOTAL.labels(server=server)
        self._sent = dict.fromkeys(self._counters, 0.0)

    def start(self) -> float:
        """The loop's thread begins (or takes up again) here."""
        self._cpu_read = time.thread_time()
        self.t_beat = time.perf_counter()
        return self.t_beat

    def beat(self, now: float) -> None:
        self.t_beat = now
        cpu_read = time.thread_time()
        self.cpu += cpu_read - self._cpu_read
        self._cpu_read = cpu_read
        wall = dict(self.wall)
        _BEATS.append({
            "loop": self.loop, "server": self.server, "t": now, "wall": wall,
            "cpu": self.cpu, "pollCpu": self.poll_cpu,
            "responses": self.responses, "handoffs": self.handoffs,
            "handoffWaitSec": self.handoff_wait,
        })
        sums = dict(wall, cpu=self.cpu, pollCpu=self.poll_cpu,
                    handoffWaitSec=self.handoff_wait,
                    handoffs=float(self.handoffs))
        sent = self._sent
        for key, child in self._counters.items():
            child.inc(sums[key] - sent[key])
            sent[key] = sums[key]


# -- jax.profiler bridge ------------------------------------------------------


class annotate:
    """A named scope on the profiler's clock: enters a
    ``jax.profiler.TraceAnnotation`` whenever this process has imported
    jax, whoever started the profiler (with no session live that is the
    profiler's own no-op; a jax-free process pays one look-up).  The
    name is a fixed string; sizes ride as keyword metadata
    (``rows=``, ``padded=``), which the trace shows as the event's
    stats.

    Named ``pio.turn.<segment>`` under a :class:`Turn`, the same
    ``with`` books the scope's own wall and thread-CPU time as that
    segment; under a request's timeline, or none, it books nothing."""

    __slots__ = ("name", "meta", "_cm", "_turn", "_t0", "_c0", "_w0",
                 "_u0")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta

    def __enter__(self) -> "annotate":
        scope = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                        None)
        self._cm = scope(self.name, **self.meta) if scope else None
        if self._cm is not None:
            self._cm.__enter__()
        tl = getattr(_local, "tl", None)
        if isinstance(tl, Turn) and self.name.startswith(Turn.PREFIX):
            self._turn = tl
            self._w0, self._u0 = tl._wall, tl._cpu
            self._t0, self._c0 = time.perf_counter(), time.thread_time()
        else:
            self._turn = None
        return self

    def __exit__(self, *exc) -> None:
        tl = self._turn
        if tl is not None:
            wall = time.perf_counter() - self._t0
            cpu = time.thread_time() - self._c0
            tl.book(self.name[len(Turn.PREFIX):],
                    wall - (tl._wall - self._w0),
                    cpu - (tl._cpu - self._u0))
        if self._cm is not None:
            self._cm.__exit__(*exc)


# -- on-demand jax.profiler capture ----------------------------------------


class ProfileBusy(RuntimeError):
    """A capture is already in flight (one per process — concurrent
    jax.profiler traces are not supported)."""


_capture_lock = threading.Lock()


def profiles_dir() -> Path:
    return telemetry_home() / "profiles"


def capture_profile(seconds: float,
                    out_dir: Optional[os.PathLike | str] = None) -> dict:
    """Blocking on-demand profiler capture (``GET /debug/profile``).

    Records a ``jax.profiler`` trace for ``seconds`` (clamped to
    [0.05, 60] — a scrape typo must not wedge a handler thread for an
    hour) into a fresh timestamped directory under
    ``telemetry/profiles/``; the program's :class:`annotate` scopes
    (``pio.turn.*``, ``pio.serve.query``, ``pio.als.*``) land in the
    xplane beside the XLA ops they dispatched.  Raises :class:`ProfileBusy`
    when a capture is already running; any profiler failure propagates
    to the caller (the HTTP mount answers 500 — a broken profiler must
    be loud, not an empty artifact)."""
    seconds = min(max(float(seconds), 0.05), 60.0)
    if not _capture_lock.acquire(blocking=False):
        raise ProfileBusy("a profile capture is already running")
    try:
        import jax.profiler

        base = Path(out_dir) if out_dir is not None else profiles_dir()
        base.mkdir(parents=True, exist_ok=True)
        stamp = time.strftime("%Y%m%d-%H%M%S", time.gmtime())
        target = base / f"{stamp}-pid{os.getpid()}"
        jax.profiler.start_trace(str(target))
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        files = sorted(
            str(p.relative_to(target))
            for p in target.rglob("*") if p.is_file()
        )
        total = sum((target / f).stat().st_size for f in files)
        return {
            "dir": str(target),
            "seconds": seconds,
            "files": files,
            "totalBytes": total,
        }
    finally:
        _capture_lock.release()
