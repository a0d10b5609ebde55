"""The readers of the dispatcher's turn records and of the collector hook
(`turn_segment_ms`, `turn_offcpu_share`, `gc_pause_ms_per_s`) on hand-made
records, silent where the program keeps none; and both HTTP drivers
rehearsed on the CPU with `--trace 1`, which has to print all six new
metrics of each serving cell, in step with the batcher's own counts."""

import json
import shutil
from pathlib import Path

import pytest

from perfbench import cells, harness, run, tracereduce
from predictionio_tpu.obs import gcpause, timeline

ROOT = Path(__file__).resolve().parents[2]
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SAT = "rec-yambda-r64.serve-saturated"
STEADY = "rec-yambda-r64.serve-steady"
NEW = ("turn_prepare_ms", "turn_fetch_ms", "turn_complete_ms",
       "turn_park_ms", "dispatcher_offcpu_share", "gc_pause_ms_per_s")

# the window runs from 10.0 to 12.0; the first turn ended before it and the
# last began after it, the second began before it and ran into it
RUN = {"batch_spans": [(10.0, 10.5, 64), (11.0, 12.0, 64)]}


def _turn(t0, wall, cpu):
    return {"turn": 1, "t0": t0, "rows": 64, "padded": 64, "wall": wall,
            "cpu": cpu, "gcSec": 0.0}


TURNS = [
    _turn(0.9, {"fetch": 9.0}, {"fetch": 9.0}),
    _turn(9.99, {"park": 3.0, "claim": 0.001, "prepare": 0.003,
                 "fetch": 0.040, "decode": 0.002, "complete": 0.006},
          {"park": 0.0, "claim": 0.001, "prepare": 0.002, "fetch": 0.001,
           "decode": 0.002, "complete": 0.003}),
    _turn(11.5, {"claim": 0.003, "dispatch": 0.001, "fetch": 0.050,
                 "complete": 0.010},
          {"claim": 0.001, "dispatch": 0.001, "fetch": 0.0,
           "complete": 0.002}),
    _turn(12.1, {"fetch": 7.0}, {"fetch": 7.0}),
]


def _read(name, args=None, run_=RUN):
    return cells.load_reader(name)(run_, args or {})


def test_turn_segment_ms_is_the_mean_per_turn_in_the_window(monkeypatch):
    monkeypatch.setattr(timeline, "batch_turns", lambda: TURNS)
    fetch = _read("turn_segment_ms", {"segments": ["fetch"]})
    assert fetch == pytest.approx(45.0)
    prepare = _read("turn_segment_ms",
                    {"segments": ["claim", "prepare", "dispatch"]})
    assert prepare == pytest.approx((1 + 3 + 3 + 1) / 2)
    # the wait of the turn that was parked when the window opened lay
    # before the window
    assert _read("turn_segment_ms", {"segments": ["park"]}) == 0.0


def test_turn_offcpu_share_is_one_less_cpu_over_wall(monkeypatch):
    monkeypatch.setattr(timeline, "batch_turns", lambda: TURNS)
    segs = ["claim", "prepare", "dispatch", "decode", "complete"]
    wall = 0.001 + 0.003 + 0.002 + 0.006 + 0.003 + 0.001 + 0.010
    cpu = 0.001 + 0.002 + 0.002 + 0.003 + 0.001 + 0.001 + 0.002
    got = _read("turn_offcpu_share", {"segments": segs})
    assert got == pytest.approx(100 * (1 - cpu / wall))


def test_gc_pause_ms_per_s_sums_the_window(monkeypatch):
    monkeypatch.setattr(gcpause, "installed", lambda: True)
    monkeypatch.setattr(gcpause, "pauses", lambda: [
        (9.0, 1.0, 2), (10.0, 0.004, 0), (11.9, 0.016, 2), (12.5, 1.0, 2),
    ])
    assert _read("gc_pause_ms_per_s") == pytest.approx(20.0 / 2.0)
    monkeypatch.setattr(gcpause, "pauses", lambda: [])
    assert _read("gc_pause_ms_per_s") == 0.0


@pytest.mark.parametrize("name, args", [
    ("turn_segment_ms", {"segments": ["fetch"]}),
    ("turn_offcpu_share", {"segments": ["claim"]}),
    ("gc_pause_ms_per_s", {}),
])
def test_readers_return_none_where_there_is_nothing_to_read(
        monkeypatch, name, args):
    # a train cell, or a window in which no batch ran
    assert _read(name, args, run_={}) is None
    assert _read(name, args, run_={"batch_spans": []}) is None
    # records there, none of them in the window; no hook installed
    monkeypatch.setattr(timeline, "batch_turns", lambda: TURNS[:1])
    monkeypatch.setattr(gcpause, "installed", lambda: False)
    assert _read(name, args) is None
    # the parent of PR 26: the program has no such records at all
    monkeypatch.delattr(timeline, "batch_turns")
    monkeypatch.delattr(gcpause, "pauses")
    monkeypatch.delattr(gcpause, "installed")
    assert _read(name, args) is None


# -- both HTTP drivers, rehearsed with --trace 1 ------------------------------


def _edit(path: Path, **changes) -> None:
    doc = json.loads(path.read_text())
    doc.update(changes)
    path.write_text(json.dumps(doc))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_turns")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    pb = root / "perfbench"
    _edit(pb / "configs/rec-yambda-r64.json", n_users=2000, n_items=5000,
          rank=8, microbatch_max=8, check={"answers": 16})
    _edit(pb / "traffic/serve-saturated.json", connections=8, user_pool=512,
          trace_after_s=0.1, trace_seconds=0.3)
    # arrivals close enough together to be scored in batches: one query
    # alone goes through `predict`, which the benchmark's spans do not see
    _edit(pb / "traffic/serve-steady.json", connections=16, rate_per_s=1500,
          user_pool=512, trace_after_s=0.1, trace_seconds=0.3)
    return root


@pytest.fixture()
def fake_trace(monkeypatch):
    """The CPU has no device plane: the reduction is made up, the
    program's own records are real."""
    summary = tracereduce.TraceSummary(
        window_ns=1_000_000_000, busy_ns=600_000_000.0, n_devices=1,
        ops=[("%custom-call = custom-call:TopK", 400_000_000, 10),
             ("%fusion = fusion", 200_000_000, 10)],
        gaps=[(0, 400_000_000, tracereduce.NO_HOST_SPAN)],
    )
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: summary)


@pytest.mark.parametrize("name, suffix", [(SAT, ".sat"), (STEADY, ".steady")])
def test_traced_rehearsal_prints_the_turn_metrics(tiny, fake_trace, name,
                                                  suffix):
    r = run.execute(cells.resolve(name, tiny), 2**31 + 7, 1.0, True, CPU,
                    tiny)
    assert r["correct"] is True
    got = {k: v["value"] for k, v in r["metrics"].items()}
    for metric in NEW:
        assert metric + suffix in got, metric
    assert {got[f"turn_{s}_ms{suffix}"] > 0
            for s in ("prepare", "fetch", "complete")} == {True}
    assert got["turn_park_ms" + suffix] >= 0
    assert got["gc_pause_ms_per_s" + suffix] >= 0
    assert got["dispatcher_offcpu_share" + suffix] < 100
    # turns tile the dispatcher's time: the four parts sum to the mean
    # time from one batch to the next, which the batcher's own counts give
    # as window / batches
    turn_ms = sum(got[f"turn_{s}_ms{suffix}"]
                  for s in ("prepare", "fetch", "complete", "park"))
    batches = r["attempted"] / got["batcher_mean_batch" + suffix]
    assert turn_ms == pytest.approx(1e3 * 1.0 / batches, rel=0.2)
