"""The readers of the event loop's record and of a turn's parts
(`loop_share`, `loop_handoff_ms`, `turn_part_us`, `host_cpu_us_per_request`)
on hand-made beats and turns, silent where the program keeps no such record;
name-to-files resolution of the eighteen new metrics; and the three serving
cells rehearsed on the CPU with `--trace 1`, which has to print all six new
metrics of each, in step with the load generator's own count."""

import json
import shutil
from pathlib import Path

import pytest

from perfbench import cells, harness, run, tracereduce
from perfbench.readers import loop_share
from predictionio_tpu.obs import timeline

ROOT = Path(__file__).resolve().parents[2]
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
CELLS = {
    ".sat": ("rec-yambda-r64.serve-saturated", "serve_rps"),
    ".steady": ("rec-yambda-r64.serve-steady", "serve_p95_ms"),
    ".similar": ("sim-amazon14-r128.serve-similar-steady", "serve_p95_ms"),
}
NEW = {
    "loop_busy_share": ("loop_share", "%", "HTTP edge"),
    "loop_offcpu_share": ("loop_share", "%", "HTTP edge"),
    "loop_handoff_ms": ("loop_handoff_ms", "ms", "HTTP edge"),
    "complete_observe_us": ("turn_part_us", "us", "batcher"),
    "complete_respond_us": ("turn_part_us", "us", "batcher"),
    "host_cpu_us_per_request": ("host_cpu_us_per_request", "us",
                                "host process"),
}

# the window runs from 10.0 to 12.0
RUN = {"batch_spans": [(10.0, 10.5, 64), (11.0, 12.0, 64)]}


def _beat(t, loop=1, **sums):
    wall = {p: sums.pop(p, 0.0) for p in timeline.LOOP_PHASES}
    beat = {"loop": loop, "server": "serving", "t": t, "wall": wall,
            "cpu": 0.0, "pollCpu": 0.0, "responses": 0, "handoffs": 0,
            "handoffWaitSec": 0.0}
    beat.update(sums)
    return beat


# cumulative sums: the beats at 10.1 and 11.9 are the first and the last
# inside the window; what lies before and after must not be read
BEATS = [
    _beat(9.0, poll=8.0, read=1.0, cpu=1.0, pollCpu=0.1, responses=50,
          handoffs=50, handoffWaitSec=5.0),
    _beat(10.1, poll=8.5, read=1.6, cpu=1.6, pollCpu=0.2, responses=100,
          handoffs=90, handoffWaitSec=5.2),
    _beat(11.0, poll=8.9, read=1.9, drain=0.2, cpu=2.1, pollCpu=0.3,
          responses=500, handoffs=480, handoffWaitSec=5.6),
    _beat(11.9, poll=9.4, read=2.2, drain=0.3, write=0.1, cpu=2.5,
          pollCpu=0.5, responses=1100, handoffs=1090, handoffWaitSec=7.2),
    _beat(12.4, poll=9.9, read=2.2, drain=0.3, write=0.1, cpu=2.6,
          pollCpu=0.6, responses=1100, handoffs=1090, handoffWaitSec=7.2),
]


def _turn(t0, wall, cpu, rows=0, requests=0, parts=None):
    turn = {"turn": 1, "t0": t0, "rows": rows, "padded": rows, "wall": wall,
            "cpu": cpu, "gcSec": 0.0}
    if parts is not None:
        turn.update(requests=requests, parts=parts)
    return turn


TURNS = [
    _turn(0.9, {"fetch": 9.0}, {"fetch": 9.0}, 64, 64, {"observe": 5.0}),
    _turn(10.2, {"fetch": 0.040, "complete": 0.010},
          {"fetch": 0.001, "complete": 0.006}, 40, 40,
          {"book": 0.0004, "serve": 0.0008, "observe": 0.0030,
           "encode": 0.0012, "handoff": 0.0020}),
    _turn(11.5, {"claim": 0.002, "fetch": 0.050, "complete": 0.006},
          {"claim": 0.001, "fetch": 0.0, "complete": 0.004}, 20, 20,
          {"book": 0.0002, "serve": 0.0004, "observe": 0.0012,
           "encode": 0.0006, "handoff": 0.0012}),
    _turn(12.1, {"fetch": 7.0}, {"fetch": 7.0}, 64, 64, {"observe": 5.0}),
]


def _read(name, args=None, run_=RUN):
    return cells.load_reader(name)(run_, args or {})


@pytest.fixture()
def records(monkeypatch):
    monkeypatch.setattr(timeline, "loop_beats", lambda: BEATS)
    monkeypatch.setattr(timeline, "batch_turns", lambda: TURNS)


# -- the readers on hand-made records -----------------------------------------


def test_loop_shares_subtract_the_first_and_last_beat_in_the_window(records):
    d = loop_share.between_beats(RUN)
    assert d["elapsed"] == pytest.approx(1.8)
    assert d["wall"]["poll"] == pytest.approx(0.9)
    assert d["responses"] == 1000 and d["handoffs"] == 1000
    # 0.9 s of 1.8 s inside select; 0.9 s of CPU, 0.3 of them select's
    # own, so 0.6 in the other 0.9 s
    assert _read("loop_share", {"of": "busy"}) == pytest.approx(50.0)
    assert _read("loop_share", {"of": "offcpu"}) == pytest.approx(
        100 * (1 - 0.6 / 0.9))


def test_loop_handoff_ms_is_the_mean_wait_of_an_answer(records):
    assert _read("loop_handoff_ms") == pytest.approx(1e3 * 2.0 / 1000)


def test_turn_part_us_is_per_request_of_the_windows_turns(records):
    assert _read("turn_part_us", {"parts": ["observe"]}) == pytest.approx(
        1e6 * (0.0030 + 0.0012) / 60)
    assert _read("turn_part_us", {"parts": ["encode", "handoff"]}) == \
        pytest.approx(1e6 * (0.0012 + 0.0020 + 0.0006 + 0.0012) / 60)


def test_host_cpu_us_per_request_adds_the_two_threads(records):
    dispatcher = (0.001 + 0.006 + 0.001 + 0.0 + 0.004) / 60
    loop = 0.6 / 1000
    assert _read("host_cpu_us_per_request") == pytest.approx(
        1e6 * (dispatcher + loop))


def test_two_loops_in_one_process_are_subtracted_each_by_itself(monkeypatch):
    """Two servers of one name (tenants, both "serving") are two loops:
    a beat says which by the record's number."""
    other = [_beat(10.3, loop=2, poll=100.0, responses=7),
             _beat(11.3, loop=2, poll=100.5, read=0.5, responses=9)]
    monkeypatch.setattr(timeline, "loop_beats", lambda: BEATS + other)
    d = loop_share.between_beats(RUN)
    assert d["elapsed"] == pytest.approx(2.8)
    assert d["wall"]["poll"] == pytest.approx(1.4)
    assert d["responses"] == 1002


def test_a_share_is_held_inside_0_and_100_by_one_tick_and_no_more(
        monkeypatch):
    """The chip machine's thread clock ticks at 10 ms: over a stretch it
    can read one tick more CPU than wall, or one less than none.  Further
    out the CPU was booked wrongly, and that must not read as 0 or 100."""
    def beats(**last):
        return [_beat(10.1, poll=1.0),
                _beat(11.9, poll=1.0, read=1.8, responses=9, **last)]
    monkeypatch.setattr(timeline, "loop_beats", lambda: beats(cpu=1.809))
    assert _read("loop_share", {"of": "offcpu"}) == 0.0
    assert _read("loop_share", {"of": "busy"}) == 100.0
    monkeypatch.setattr(timeline, "loop_beats",
                        lambda: beats(cpu=0.061, pollCpu=0.07))
    assert _read("loop_share", {"of": "offcpu"}) == 100.0
    monkeypatch.setattr(timeline, "batch_turns", lambda: TURNS)
    for wrong in (dict(cpu=1.9), dict(cpu=0.0, pollCpu=0.07)):
        monkeypatch.setattr(timeline, "loop_beats", lambda: beats(**wrong))
        assert _read("loop_share", {"of": "offcpu"}) is None
        assert _read("host_cpu_us_per_request") is None
        assert _read("loop_share", {"of": "busy"}) == 100.0
    # a loop that never left select has no work time to take a share of
    beats = [_beat(10.0, poll=1.0), _beat(12.0, poll=3.0)]
    monkeypatch.setattr(timeline, "loop_beats", lambda: beats)
    assert _read("loop_share", {"of": "busy"}) == 0.0
    assert _read("loop_share", {"of": "offcpu"}) is None


@pytest.mark.parametrize("name, args", [
    ("loop_share", {"of": "busy"}),
    ("loop_share", {"of": "offcpu"}),
    ("loop_handoff_ms", {}),
    ("turn_part_us", {"parts": ["observe"]}),
    ("host_cpu_us_per_request", {}),
])
def test_readers_return_none_where_there_is_nothing_to_read(
        monkeypatch, records, name, args):
    # a train cell, or a window in which no batch ran
    assert _read(name, args, run_={}) is None
    assert _read(name, args, run_={"batch_spans": []}) is None
    # ONE beat in the window is no difference: None, never 0
    monkeypatch.setattr(timeline, "loop_beats", lambda: BEATS[:2])
    # turns as the parent keeps them: no parts, no requests
    monkeypatch.setattr(timeline, "batch_turns", lambda: [
        _turn(10.2, {"fetch": 0.04}, {"fetch": 0.001}, 40)])
    assert _read(name, args) is None
    # records there, none of them in the window
    monkeypatch.setattr(timeline, "loop_beats", lambda: BEATS[:1] + BEATS[4:])
    monkeypatch.setattr(timeline, "batch_turns", lambda: TURNS[:1])
    assert _read(name, args) is None
    # the parent of PR 38: the program has no record of the loop at all
    monkeypatch.delattr(timeline, "loop_beats")
    assert _read(name, args) is None


def test_a_part_no_turn_booked_is_none_not_zero(monkeypatch):
    """A part renamed in the program, or a responder that never marks it,
    must not read as 0 us: the parts a turn keeps are `TURN_PARTS`, each
    there from the start, so it is the sum that tells."""
    assert set(TURNS[1]["parts"]) == set(timeline.TURN_PARTS)
    unmarked = [dict(t, parts=dict(t["parts"], encode=0.0)) for t in TURNS]
    monkeypatch.setattr(timeline, "batch_turns", lambda: unmarked)
    assert _read("turn_part_us", {"parts": ["encode", "handoff"]}) is None
    assert _read("turn_part_us", {"parts": ["handoff"]}) == pytest.approx(
        1e6 * (0.0020 + 0.0012) / 60)
    assert _read("turn_part_us", {"parts": ["encoding"]}) is None


def test_nothing_handed_over_is_none_not_zero(monkeypatch):
    beats = [_beat(10.1, poll=1.0, responses=5),
             _beat(11.9, poll=2.0, read=0.8, responses=9)]
    monkeypatch.setattr(timeline, "loop_beats", lambda: beats)
    assert _read("loop_handoff_ms") is None
    assert _read("loop_share", {"of": "busy"}) == pytest.approx(
        100 * (1 - 1.0 / 1.8))


# -- the eighteen metrics and their files -------------------------------------


@pytest.mark.parametrize("suffix", sorted(CELLS))
@pytest.mark.parametrize("name", sorted(NEW))
def test_each_new_metric_resolves_to_a_reader_in_its_cell_alone(name, suffix):
    cell_name, moves = CELLS[suffix]
    reader, unit, layer = NEW[name]
    (m,) = [m for m in cells.resolve(cell_name).per_layer
            if m.name == name + suffix]
    assert (m.reader, m.unit, m.layer, m.moves) == (reader, unit, layer,
                                                    moves)
    assert callable(cells.load_reader(m.reader))
    entry = next(e for e in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"]
        if e["name"] == name + suffix)
    assert entry["workloads"] == [cell_name] and entry["better"] == "lower"
    for other in {c for c, _ in CELLS.values()} - {cell_name}:
        assert name + suffix not in {
            m.name for m in cells.resolve(other).per_layer}


def test_the_train_cells_report_what_they_reported():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = {name + suffix for name in NEW for suffix in CELLS}
    assert new <= {e["name"] for e in manifest["per_layer"][-18:]}
    serving = {c for c, _ in CELLS.values()}
    for w in manifest["workloads"]:
        if w["name"] not in serving:
            assert not new & {
                m.name for m in cells.resolve(w["name"]).per_layer}


# -- the three serving cells, rehearsed with --trace 1 ------------------------


def _edit(path: Path, **changes) -> None:
    doc = json.loads(path.read_text())
    doc.update(changes)
    path.write_text(json.dumps(doc))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_loop")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    pb = root / "perfbench"
    _edit(pb / "configs/rec-yambda-r64.json", n_users=2000, n_items=5000,
          rank=8, microbatch_max=8, check={"answers": 16})
    _edit(pb / "traffic/serve-saturated.json", connections=8, user_pool=512,
          trace_after_s=0.1, trace_seconds=0.3)
    _edit(pb / "traffic/serve-steady.json", connections=16, rate_per_s=1500,
          user_pool=512, trace_after_s=0.1, trace_seconds=0.3)
    _edit(pb / "configs/sim-amazon14-r128.json", n_items=30000,
          microbatch_max=8, check={"answers": 16})
    _edit(pb / "traffic/serve-similar-steady.json", connections=16,
          rate_per_s=400, query_pool=64, trace_after_s=0.1,
          trace_seconds=0.3)
    return root


@pytest.fixture()
def fake_trace(monkeypatch):
    """The CPU has no device plane: the reduction is made up, the
    program's own records are real."""
    summary = tracereduce.TraceSummary(
        window_ns=1_000_000_000, busy_ns=600_000_000.0, n_devices=1,
        ops=[("%pio_block_max = custom-call:tpu_custom_call", 400_000_000, 10),
             ("%custom-call = custom-call:TopK", 100_000_000, 10),
             ("%fusion = fusion", 100_000_000, 10)],
        gaps=[(0, 400_000_000, tracereduce.NO_HOST_SPAN)],
    )
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: summary)


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_traced_rehearsal_prints_the_loop_and_part_metrics(
        tiny, fake_trace, monkeypatch, suffix):
    seen = {}
    read_per_layer = run.read_per_layer

    def keep_the_run(cell, run_, root):
        seen["run"] = run_
        return read_per_layer(cell, run_, root)

    monkeypatch.setattr(run, "read_per_layer", keep_the_run)
    seconds = 1.5
    r = run.execute(cells.resolve(CELLS[suffix][0], tiny), 2**31 + 38,
                    seconds, True, CPU, tiny)
    assert r["correct"] is True and r["failed"] == 0
    got = {k: v["value"] for k, v in r["metrics"].items()}
    for metric in NEW:
        assert metric + suffix in got, metric
    assert 0 < got["loop_busy_share" + suffix] <= 100
    assert 0 <= got["loop_offcpu_share" + suffix] <= 100
    assert 0 < got["loop_handoff_ms" + suffix] < 1e3 * seconds
    assert got["complete_observe_us" + suffix] > 0
    assert got["complete_respond_us" + suffix] > 0
    # the two parts lie inside `complete`, request for request
    complete_us = (1e3 * got["turn_complete_ms" + suffix]
                   / got["batcher_mean_batch" + suffix])
    assert (got["complete_observe_us" + suffix]
            + got["complete_respond_us" + suffix]) < complete_us
    # two threads of one interpreter cannot fill two cores
    answered = seen["run"]["answered"]
    assert 0 < got["host_cpu_us_per_request" + suffix] * answered / seconds \
        < 2e6
    # the loop's own count of answers written, between the first and the
    # last beat inside the window (a tenth of a second short of it at
    # either end), is in step with what the generator counted
    d = loop_share.between_beats(seen["run"])
    assert 0.6 * answered <= d["responses"] <= answered
    assert d["handoffs"] == pytest.approx(d["responses"], abs=16)
    assert sum(d["wall"].values()) == pytest.approx(d["elapsed"], rel=0.01)
    # every turn's parts lie inside its `complete`
    for t in timeline.batch_turns()[-20:]:
        assert sum(t["parts"].values()) <= t["wall"]["complete"]
        assert t["requests"] == t["rows"]
