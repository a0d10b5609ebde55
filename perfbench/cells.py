"""Cell name -> the data files that define it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    <config>.<traffic>              the cell (split at the first ".")
    perfbench/configs/<config>.json    sizes, precision, limits of `correct`
    perfbench/traffic/<traffic>.json   parameters of one general driver
    perfbench/metrics/<metric>.json    layer, unit, moves, reader, reader args
    perfbench/readers/<reader>.py      `read(run, args) -> float | None`
    perfbench/drivers/<driver>.py      `run(cell, opts) -> dict`

A later PR adds a cell by adding files and one entry to
``BENCHMARK.json``; it edits nothing that is here.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class CellError(Exception):
    """A cell, or a file it names, cannot be found or read."""


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise CellError(f"{what}: no file {path}")
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except json.JSONDecodeError as e:
        raise CellError(f"{what}: {path} is not JSON ({e})") from e
    if not isinstance(doc, dict):
        raise CellError(f"{what}: {path} does not hold a JSON object")
    return doc


@dataclass
class Metric:
    name: str
    unit: str
    moves: str
    layer: str
    source: str
    reader: str
    args: dict = field(default_factory=dict)


@dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list   # [{"name", "unit"}] reported by this cell
    per_layer: list    # [Metric] reported by this cell

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def split_cell(name: str) -> tuple:
    config, sep, traffic = name.partition(".")
    if not sep or not config or not traffic:
        raise CellError(
            f"cell {name!r} is not <config>.<traffic> (split at the first '.')"
        )
    return config, traffic


def _applies(entry: dict, cell_name: str) -> bool:
    listed = entry.get("workloads")
    return listed is None or cell_name in listed


def resolve(cell_name: str, root: Path = ROOT) -> Cell:
    """Find every file of a cell; a missing one is an error that names it."""
    bench_dir = root / BENCH_DIR.name
    manifest = _load_json(root / "BENCHMARK.json", "manifest")
    entry = next(
        (w for w in manifest.get("workloads", []) if w["name"] == cell_name),
        None,
    )
    if entry is None:
        known = ", ".join(w["name"] for w in manifest.get("workloads", []))
        raise CellError(
            f"cell {cell_name!r} is not in BENCHMARK.json (cells: {known})"
        )
    config_name, traffic_name = split_cell(cell_name)
    if (entry["config"], entry["traffic"]) != (config_name, traffic_name):
        raise CellError(
            f"cell {cell_name!r} names config {entry['config']!r} and traffic "
            f"{entry['traffic']!r}; its name has to be <config>.<traffic>"
        )
    cfg_entry = next(
        (c for c in manifest.get("configs", []) if c["name"] == config_name),
        None,
    )
    if cfg_entry is None:
        raise CellError(f"config {config_name!r} is not in BENCHMARK.json")
    config = _load_json(root / cfg_entry["file"], f"config {config_name}")
    traffic = _load_json(
        bench_dir / "traffic" / f"{traffic_name}.json",
        f"traffic {traffic_name}",
    )
    if "driver" not in traffic:
        raise CellError(f"traffic {traffic_name}: the file names no driver")
    end_to_end = [
        {"name": m["name"], "unit": m["unit"]}
        for m in manifest["end_to_end"] if _applies(m, cell_name)
    ]
    reported = {m["name"] for m in end_to_end}
    per_layer = []
    for m in manifest["per_layer"]:
        if not _applies(m, cell_name) or m["moves"] not in reported:
            continue
        doc = _load_json(
            bench_dir / "metrics" / f"{m['name']}.json", f"metric {m['name']}"
        )
        per_layer.append(Metric(
            name=m["name"], unit=m["unit"], moves=m["moves"],
            layer=m["layer"], source=m["source"],
            reader=doc["reader"], args=doc.get("args", {}),
        ))
    return Cell(
        name=cell_name, config_name=config_name, traffic_name=traffic_name,
        chips=int(entry["chips"]), config=config, traffic=traffic,
        end_to_end=end_to_end, per_layer=per_layer,
    )


@functools.lru_cache(maxsize=None)
def _load_module(kind: str, name: str, root: Path):
    path = root / BENCH_DIR.name / kind / f"{name}.py"
    if not path.is_file():
        raise CellError(f"{kind[:-1]} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(name: str, root: Path = ROOT):
    """The `read(run, args)` function of perfbench/readers/<name>.py."""
    return _load_module("readers", name, root).read


def load_driver(name: str, root: Path = ROOT):
    """The `run(cell, opts)` function of perfbench/drivers/<name>.py."""
    return _load_module("drivers", name, root).run
