"""Reduction of a `jax.profiler` trace (.xplane.pb) to the numbers the
per-layer readers use: the union of the intervals in which an operation
ran on the device, each operation's summed time, and the idle gaps by
what the host was doing in them.

Only JAX reads the file (`jax.profiler.ProfileData`).  Times are
nanoseconds in the trace's own clock, which the device and host planes
share.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# the line of a device plane that holds one event per executed HLO op
OP_LINES = ("XLA Ops",)
WINDOW_SPAN = "bench.window"
NO_HOST_SPAN = "no jax call or benchmark span on the host"
# host events that say what the program was in: jax's own TraceMes and the
# benchmark's TraceAnnotations
HOST_SPAN = re.compile(r"^(bench\.|PjitFunction|np\.asarray|jax|Pjit|"
                       r"TransferTo|TransferFrom|BlockUntilReady|"
                       r"PjRt|block_until_ready|device_put)")
MIN_GAP_NS = 200_000  # gaps under 0.2 ms are the device's own hand-over


@dataclass
class TraceSummary:
    window_ns: int                 # length of the traced window
    busy_ns: float                 # mean over chips of the busy union
    n_devices: int
    ops: list = field(default_factory=list)    # [(name, ns, count)] by time
    gaps: list = field(default_factory=list)   # [(start, ns, host label)]
    lines_seen: dict = field(default_factory=dict)

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def op_seconds(self, pattern: str) -> tuple:
        """(seconds, events) of the ops whose name matches the regex."""
        rx = re.compile(pattern)
        hit = [(ns, n) for name, ns, n in self.ops if rx.search(name)]
        return sum(ns for ns, _ in hit) / 1e9, sum(n for _, n in hit)


def union_ns(intervals) -> tuple:
    """(total covered ns, merged [(start, end)]) of [(start, end)]."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def _host_spans(planes):
    spans = []
    window = None
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif HOST_SPAN.match(name):
                    spans.append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, name)
                    )
    return spans, window


def _label_gap(spans, start, end):
    """The innermost host span that covers the gap's midpoint."""
    mid = (start + end) / 2
    best = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else NO_HOST_SPAN


def reduce_planes(planes, window_ns: int | None = None) -> TraceSummary:
    """Reduce profiler planes.  The window is the `bench.window` host span
    where the trace has one, else `window_ns`, else first to last device
    event."""
    planes = list(planes)
    spans, window = _host_spans(planes)
    device_planes = [p for p in planes if DEVICE_PLANE.match(p.name)]
    lines_seen = {}
    per_device = []
    for plane in device_planes:
        lines = list(plane.lines)
        lines_seen[plane.name] = [ln.name for ln in lines]
        op_lines = [ln for ln in lines if ln.name in OP_LINES]
        events = [
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ln in op_lines for ev in ln.events if ev.duration_ns > 0
        ]
        if events:
            per_device.append(events)
    if not per_device:
        raise ValueError(
            "the trace holds no device operation: planes "
            f"{[p.name for p in planes]}, device lines {lines_seen}"
        )
    if window is None:
        lo = min(s for evs in per_device for s, _, _ in evs)
        hi = max(e for evs in per_device for _, e, _ in evs)
        if window_ns:
            hi = max(hi, lo + window_ns)
        window = (lo, hi)
    lo, hi = window
    busy_total = 0
    op_ns: dict = {}
    op_n: dict = {}
    gaps = []
    for events in per_device:
        covered, merged = union_ns(_clip(((s, e) for s, e, _ in events), lo, hi))
        busy_total += covered
        for s, e, name in events:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_ns[name] = op_ns.get(name, 0) + (e - s)
                op_n[name] = op_n.get(name, 0) + 1
        edges = [lo] + [t for pair in merged for t in pair] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 >= MIN_GAP_NS:
                gaps.append((g0, g1 - g0, _label_gap(spans, g0, g1)))
    ops = sorted(
        ((name, ns, op_n[name]) for name, ns in op_ns.items()),
        key=lambda t: -t[1],
    )
    return TraceSummary(
        window_ns=hi - lo, busy_ns=busy_total / len(per_device),
        n_devices=len(per_device), ops=ops, gaps=gaps, lines_seen=lines_seen,
    )


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_file(path: Path, window_ns: int | None = None) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(path)).planes, window_ns)


_HLO = re.compile(r"^%([A-Za-z_\-]+)[.\d]* = (.*?) ([a-z][a-z\-]*)\(")


def op_kind(name: str) -> str:
    """An HLO event's kind: its instruction's base name, opcode and output
    shape, without the instance's number ("%fusion.12 = f32[64,9]{...}
    fusion(...)" -> "fusion fusion f32[64,9]"), so that the instances of
    one operation are summed together."""
    m = _HLO.match(name)
    if not m:
        return name
    base, shape, opcode = m.groups()
    shape = re.sub(r"\{[^}]*\}", "", shape)      # layouts off
    target = re.search(r"custom_call_target=\"([^\"]+)\"", name)
    if target:
        opcode = f"{opcode}:{target.group(1)}"
    return f"{base} {opcode} {shape}"


def _plain(name: str, limit: int = 64) -> str:
    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", name)[:limit]


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The result line's `breakdown`: the device operations with the most
    time, and the idle gaps by what the host was in."""
    kinds: dict = {}
    for name, ns, _ in summary.ops:
        kind = op_kind(name)
        kinds[kind] = kinds.get(kind, 0) + ns
    device_ops = [
        [_plain(kind), ns / 1e9]
        for kind, ns in sorted(kinds.items(), key=lambda kv: -kv[1])[:top]
    ]
    by_label: dict = {}
    for _, ns, label in summary.gaps:
        n, total = by_label.get(label, (0, 0))
        by_label[label] = (n + 1, total + ns)
    idle = [
        [_plain(f"{n}_gaps_during_{label}"), total / 1e9]
        for label, (n, total) in sorted(
            by_label.items(), key=lambda kv: -kv[1][1]
        )
    ][: top // 2]
    longest = sorted(summary.gaps, key=lambda g: -g[1])[: top - len(idle)]
    idle += [
        [_plain(f"longest_single_gap_during_{label}"), ns / 1e9]
        for _, ns, label in longest
    ]
    return {"device_ops": device_ops, "idle_gaps": idle}
