"""Cell name -> files; adding a configuration, a mix and a metric as new
files plus one entry; BENCHMARK.json inside the contract's limits."""

import json
import re
import shutil
from pathlib import Path

import pytest

from perfbench import cells

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture()
def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture()
def copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_every_cell_resolves_to_its_files(manifest):
    for w in manifest["workloads"]:
        cell = cells.resolve(w["name"])
        assert (cell.config_name, cell.traffic_name) == (
            w["config"], w["traffic"])
        assert cell.driver in ("train_sweeps", "http_closed_loop",
                               "http_open_loop")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, "a cell reports a per-layer metric"
        for m in cell.per_layer:
            assert m.moves in names
            assert callable(cells.load_reader(m.reader))
        assert callable(cells.load_driver(cell.driver))
        assert set(cell.config["limits"]), "a cell compares something"


def test_name_splits_at_the_first_dot():
    assert cells.split_cell("a-b.c.d") == ("a-b", "c.d")
    with pytest.raises(cells.CellError, match="<config>.<traffic>"):
        cells.split_cell("nodot")


@pytest.mark.parametrize("gone, says", [
    ("perfbench/traffic/train.json", "traffic train: no file"),
    ("perfbench/configs/rec-netflix-r64.json", "config rec-netflix-r64: no file"),
    ("perfbench/metrics/train_mfu.json", "metric train_mfu: no file"),
])
def test_a_missing_file_is_named(copy, gone, says):
    (copy / gone).unlink()
    with pytest.raises(cells.CellError, match=says):
        cells.resolve("rec-netflix-r64.train", copy)


def test_unknown_cell_and_reader_are_errors(copy):
    with pytest.raises(cells.CellError, match="not in BENCHMARK.json"):
        cells.resolve("rec-netflix-r64.nothing", copy)
    with pytest.raises(cells.CellError, match="reader 'nothing'"):
        cells.load_reader("nothing", copy)


def test_a_later_pr_adds_a_cell_with_files_and_entries_alone(copy):
    """A configuration, a traffic mix and a per-layer metric, each as new
    files plus one entry of BENCHMARK.json; no file that was there changes."""
    before = {p: p.read_bytes() for p in (copy / "perfbench").rglob("*")
              if p.is_file()}
    cfg = json.loads((copy / "perfbench/configs/rec-yambda-r64.json").read_text())
    cfg.update(rank=128)
    (copy / "perfbench/configs/rec-other-r128.json").write_text(json.dumps(cfg))
    mix = json.loads((copy / "perfbench/traffic/serve-steady.json").read_text())
    mix.update(rate_per_s=100)
    (copy / "perfbench/traffic/serve-slow.json").write_text(json.dumps(mix))
    (copy / "perfbench/metrics/answered_per_batch.slow.json").write_text(
        json.dumps({"reader": "answered_per_batch"}))
    (copy / "perfbench/readers/answered_per_batch.py").write_text(
        "def read(run, args):\n"
        "    return run['answered'] / run['batches'] if run.get('batches') "
        "else None\n")
    m = json.loads((copy / "BENCHMARK.json").read_text())
    m["configs"].append({
        "name": "rec-other-r128", "source": "x", "reduced": [], "why": "y",
        "file": "perfbench/configs/rec-other-r128.json"})
    m["workloads"].append({
        "name": "rec-other-r128.serve-slow", "config": "rec-other-r128",
        "traffic": "serve-slow", "chips": 1, "why": "z"})
    for e in m["end_to_end"]:
        if e["name"] == "serve_p95_ms":
            e["workloads"].append("rec-other-r128.serve-slow")
    m["per_layer"].append({
        "name": "answered_per_batch.slow", "unit": "requests",
        "better": "higher", "source": "program_counter", "layer": "batcher",
        "moves": "serve_p95_ms", "workloads": ["rec-other-r128.serve-slow"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(m))

    cell = cells.resolve("rec-other-r128.serve-slow", copy)
    assert cell.config["rank"] == 128 and cell.traffic["rate_per_s"] == 100
    mine = [x for x in cell.per_layer if x.name == "answered_per_batch.slow"]
    assert len(mine) == 1
    read = cells.load_reader(mine[0].reader, copy)
    assert read({"answered": 30, "batches": 10}, {}) == 3
    assert read({}, {}) is None
    # metrics with no `workloads` key (set-up) reach the new cell too
    assert "warmup_s" in {x.name for x in cell.per_layer}
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def test_manifest_keeps_to_the_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    layers = set()
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cell_names = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        layers.add(m["layer"])
        moved = e2e[m["moves"]].get("workloads", cell_names)
        assert set(m.get("workloads", moved)) <= set(moved), m["name"]
        if m["name"].endswith("_roofline") or "roofline" in m["name"]:
            assert m["unit"] == "%"
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for c in manifest["configs"]:
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert 1 <= len(c["why"]) <= 200 and len(c["reduced"]) <= 16
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("perfbench/")
    for p in manifest["paths"]:
        for f in (ROOT / p).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                rel = f.relative_to(ROOT).as_posix()
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
