"""The `similarproduct` cell under `categories` rehearsed on the CPU at a
tiny size, with the look for a chip patched by the test itself: the new
driver end to end, the result line's keys, the faults that `correct` has to
catch (categories ignored, one category's bits dropped, the chosen blocks
not tested again, answers returned to the wrong requests), the fp8 control,
the plain reference against a brute-force numpy answer and its own draw of
the categories, name-to-files resolution of the new cell and of each new
metric file, the new work count and reader on hand-made runs, and the check
with which the driver refuses a program that keeps no category index."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import (cells, harness, run, serve_simcat, tracereduce, work,
                       work_simcat)
from perfbench.reference import simcat_ref

ROOT = Path(__file__).resolve().parents[2]
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
CELL = "simcat-amazon14-r128.serve-category-steady"
CONFIG = "perfbench/configs/simcat-amazon14-r128.json"
TRAFFIC = "perfbench/traffic/serve-category-steady.json"
TWINS = {
    "gen_late_p95_ms", "edge_host_ms", "batcher_wait_ms",
    "batcher_mean_batch", "turn_prepare_ms", "turn_fetch_ms",
    "turn_complete_ms", "scorer_device_ms", "filter_build_ms",
    "loop_busy_share", "loop_offcpu_share", "host_cpu_us_per_request",
    "gc_pause_ms_per_s", "device_idle_share", "serve_mfu",
    # the dispatcher and the event loop where a stalled process shows
    "turn_park_ms", "dispatcher_offcpu_share", "loop_handoff_ms",
    "complete_respond_us",
}
OWN = {"category_path_share", "allow_device_ms", "filtered_scorer_roofline"}
NEW_METRICS = {f"{name}.cats" for name in TWINS | OWN}
LIMITS = {"rank_gap", "score_err", "answers_with_repeats",
          "answers_with_excluded", "answers_outside_categories",
          "answers_short", "answers_filter_blind"}

# a batch's words re-laid to rows on the sublanes, and one row's read of
# the resident index, as the chip's trace names the ops
RELAY = ("%copy_bitcast_fusion = u32[64,292864]{1,0:T(8,128)} fusion(u32[8,8,"
         "2288,128]{3,2,1,0:T(8,128)S(1)} %bitcast.17), kind=kLoop")
ROW_READ = ("%fusion.4 = u32[1,286,8,128]{3,2,1,0:T(8,128)S(1)} fusion(u32["
            "4098,286,8,128]{3,2,1,0:T(8,128)} %get-tuple-element.12, s32[]"
            " %bitcast.3), kind=kLoop")


def _edit(path: Path, **changes) -> None:
    doc = json.loads(path.read_text())
    doc.update(changes)
    path.write_text(json.dumps(doc))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with the cell's files at sizes a test can
    hold: a catalogue long enough for the blocked path with ids, few
    enough sub-categories that every allowed set holds `num` items."""
    root = tmp_path_factory.mktemp("tiny-simcat")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _edit(root / CONFIG, n_items=30000, subcategories=96,
          subcategories_min=2, microbatch_max=8,
          check={"answers": 16, "narrowest": 3, "widest": 3})   # no "what"
    _edit(root / TRAFFIC, connections=16, rate_per_s=100, query_pool=64,
          trace_after_s=0.1, trace_seconds=0.8)
    return root


def _run(tiny, seed=2**31 + 29, seconds=1.0, trace=False):
    return run.execute(cells.resolve(CELL, tiny), seed, seconds, trace,
                       CPU, tiny)


def _numbers(r):
    return {c["name"]: c["value"] for c in r["compared"]}


@pytest.fixture()
def fake_trace(monkeypatch):
    """The CPU has no device plane: a traced rehearsal reads a made-up
    reduction, so that every reader and the result line are driven."""
    summary = tracereduce.TraceSummary(
        window_ns=1_000_000_000, busy_ns=600_000_000.0, n_devices=1,
        ops=[("%pio_block_max = custom-call:tpu_custom_call", 400_000_000, 10),
             ("%custom-call = custom-call:TopK", 50_000_000, 10),
             (RELAY, 20_000_000, 10), (ROW_READ, 30_000_000, 80),
             ("%fusion = fusion", 100_000_000, 10)],
        gaps=[(0, 400_000_000, tracereduce.NO_HOST_SPAN)],
    )
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: summary)


@pytest.fixture()
def fresh_programs():
    """A fault planted inside the jitted scorer is traced only by a
    program compiled after it: drop what this process compiled, before and
    after."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


# -- the cell and its files ---------------------------------------------------


def test_the_cell_resolves_to_its_files():
    cell = cells.resolve(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "simcat-amazon14-r128", "serve-category-steady", 1)
    assert cell.driver == "http_simcat"
    assert callable(cells.load_driver(cell.driver))
    assert {m["name"] for m in cell.end_to_end} == {"serve_p95_ms", "setup_s"}
    cfg = cell.config
    assert (cfg["n_items"], cfg["rank"]) == (9_350_000, 128)
    assert cfg["microbatch_max"] == 64 and cfg["retrieval"] == "exact"
    assert cfg["architecture"] is None
    assert len(cfg["departments"]) == 24
    assert sum(d["products"] for d in cfg["departments"]) == 9_388_151
    assert "recall" in cfg["departments_remark"].lower()
    assert cfg["subcategories"] + len(cfg["departments"]) == 4096
    check = cfg["check"]
    assert (check["answers"], check["narrowest"], check["widest"]) == (
        48, 8, 8)
    assert set(cfg["limits"]) == LIMITS
    assert (cfg["limits"]["rank_gap"], cfg["limits"]["score_err"]) == (
        0.04, 0.04)
    assert all(cfg["limits"][name] == 0 for name in LIMITS
               - {"rank_gap", "score_err"})
    for key in ("source", "engine", "guarantees", "precision", "assumed",
                "index", "category_sizes"):
        assert cfg[key], key
    t = cell.traffic
    assert t["mode"] == "open" and t["connections"] == 256 and t["num"] == 10
    assert t["rate_per_s"] % 10 == 0 and t["rate_per_s"] > 0
    assert t["query_pool"] == 32768
    assert (t["seeds_min"], t["seeds_max"]) == (1, 3)
    assert (t["blacklist_min"], t["blacklist_max"]) == (0, 16)
    assert t["department_share"] == 0.5 and t["subcategories_max"] == 2
    from predictionio_tpu.ops.topk import CATEGORY_SLOTS, EXCLUDE_LADDER

    assert t["subcategories_max"] <= CATEGORY_SLOTS
    assert t["seeds_max"] + t["blacklist_max"] <= EXCLUDE_LADDER[0], \
        "categories ride with the pairwise ids alone"
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    # by name, not by place: a later PR appends after these
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "simcat-amazon14-r128")
    assert entry["reduced"] == ["training"]
    mine = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(mine) == 1 and mine[0]["chips"] == 1
    assert len(mine[0]["why"]) <= 200
    p95 = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_p95_ms")
    assert CELL in p95["workloads"] and p95["bound"] == 0.1


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_resolves_to_a_reader(name):
    cell = cells.resolve(CELL)
    mine = [m for m in cell.per_layer if m.name == name]
    assert len(mine) == 1 and mine[0].moves == "serve_p95_ms"
    read = cells.load_reader(mine[0].reader)
    assert read({}, mine[0].args) is None, "nothing to read, nothing raised"
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    base = name.rsplit(".", 1)[0]
    if base in TWINS:       # the accepted cell's metric, letter for letter
        twin = next(m for m in manifest["per_layer"]
                    if m["name"] == f"{base}.similar")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == twin[key]
        pb = ROOT / "perfbench/metrics"
        assert json.loads((pb / f"{name}.json").read_text()) == json.loads(
            (pb / f"{base}.similar.json").read_text())


def test_the_accepted_cells_report_none_of_the_new_metrics():
    for name in ("sim-amazon14-r128.serve-similar-steady",
                 "ecomm-amazon14-r128.serve-unseen-steady",
                 "rec-yambda-r64.serve-steady", "rec-netflix-r64.train"):
        assert not NEW_METRICS & {m.name for m in
                                  cells.resolve(name).per_layer}


def test_nothing_the_benchmark_had_is_edited_but_one_list():
    """Against the parent's manifest as git has it: every older entry is
    where it was, letter for letter, but `serve_p95_ms`'s `workloads`."""
    import subprocess

    shown = subprocess.run(
        ["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
        capture_output=True, text=True)
    if shown.returncode:
        pytest.skip("no git history here")
    old = json.loads(shown.stdout)
    if any(w["name"] == CELL for w in old["workloads"]):
        pytest.skip("HEAD already holds the cell")
    new = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "per_layer"):
        assert new[key][:len(old[key])] == old[key]
    assert len(new["configs"]) == len(old["configs"]) + 1
    assert len(new["workloads"]) == len(old["workloads"]) + 1
    for was, now in zip(old["end_to_end"], new["end_to_end"]):
        if was["name"] == "serve_p95_ms":
            assert now == dict(was, workloads=was["workloads"] + [CELL])
        else:
            assert now == was


# -- the data: departments, sub-categories, the pool ---------------------------


def test_the_categories_are_the_configurations():
    """At the published sizes: 24 departments scaled to the catalogue,
    4,072 sub-category names dealt in proportion with a floor, every item
    three names, two distinct sub-categories of its own department, a
    department no range of ids; the sizes the file states."""
    cfg = cells.resolve(CELL).config
    items = simcat_ref.department_items(cfg)
    subs = simcat_ref.department_subcategories(cfg)
    assert items.sum() == cfg["n_items"] and len(items) == 24
    assert subs.sum() == cfg["subcategories"]
    assert subs.min() >= cfg["subcategories_min"]
    assert items[0] == cfg["category_sizes"]["largest_department"]
    names = simcat_ref.category_names(cfg)
    assert len(names) == len(set(names)) == 4096
    assert names[0] == "Books" and names[24] == "Books/s0"


def test_items_categories_from_the_seed_at_a_small_size():
    cfg = dict(cells.resolve(CELL).config, n_items=200_000,
               subcategories=400, subcategories_min=4)
    cats = simcat_ref.item_categories(cfg, 2**31 + 5)
    again = simcat_ref.item_categories(cfg, 2**31 + 5)
    other = simcat_ref.item_categories(cfg, 6)
    np.testing.assert_array_equal(cats, again)
    assert (cats != other).any()
    items = simcat_ref.department_items(cfg)
    subs = simcat_ref.department_subcategories(cfg)
    np.testing.assert_array_equal(np.bincount(cats[:, 0], minlength=24),
                                  items)
    assert (cats[:, 1] != cats[:, 2]).all()
    first = 24 + np.concatenate(([0], np.cumsum(subs)[:-1]))
    for column in (1, 2):        # a sub-category of the item's department
        own = cats[:, column] - first[cats[:, 0]]
        assert (own >= 0).all() and (own < subs[cats[:, 0]]).all()
    books = np.flatnonzero(cats[:, 0] == 0)
    assert books.min() < 100 and books.max() > 199_900, "no range of ids"
    sizes = np.bincount(cats[:, 1:].reshape(-1))[24:24 + subs[0]]
    assert sizes[0] > 3 * sizes[5] > 3 * sizes[-1] > 0, "Zipf over a shelf"


def test_the_pool_names_the_first_seeds_categories():
    cell = cells.resolve(CELL)
    cfg = dict(cell.config, n_items=50_000, subcategories=200,
               subcategories_min=2)
    traffic = dict(cell.traffic, query_pool=2000)
    cats = simcat_ref.item_categories(cfg, 9)
    pool = serve_simcat.make_pool(cfg, traffic, cats)
    kinds = {"department": 0, "one": 0, "two": 0}
    for query in pool:
        mine = cats[query["seeds"][0]].tolist()
        named = query["categories"]
        assert set(named) <= set(mine) and 1 <= len(named) <= 2
        if named == [mine[0]]:
            kinds["department"] += 1
        elif len(named) == 2:
            assert named == mine[1:]
            kinds["two"] += 1
        else:
            kinds["one"] += 1
    assert 900 < kinds["department"] < 1100
    assert 400 < kinds["one"] < 600 and 400 < kinds["two"] < 600
    # which categories a query names is the same for every seed
    other = serve_simcat.make_pool(cfg, traffic,
                                   simcat_ref.item_categories(cfg, 10))
    assert [len(q["categories"]) for q in pool] == \
        [len(q["categories"]) for q in other]
    assert [q["seeds"] for q in pool] == [q["seeds"] for q in other]
    names = simcat_ref.category_names(cfg)
    body = json.loads(serve_simcat.body_of(pool[0], 10, names))
    assert body["categories"] == [names[c] for c in pool[0]["categories"]]
    assert body["items"] == [f"i{ix}" for ix in pool[0]["seeds"]]
    sizes = serve_simcat.allowed_sizes(pool, cats)
    assert sizes.min() >= 10, "no query allows fewer than num"


# -- the work count and the new reader ------------------------------------------


def test_work_count_holds_one_bit_an_item_a_row():
    assert work_simcat.category_batch_flops(2, 1000, 8) == 2 * 2 * 1000 * 8
    # table 32000, bits 2*1000/8, candidates 2*(16+3)*8*4, queries 64,
    # lists 2*(3+4)*4, results 2*16*8
    assert work_simcat.category_batch_bytes(2, 1000, 8, 16, 3) == \
        32000 + 250 + 1216 + 64 + 56 + 256
    t, bound = work.least_seconds(
        work_simcat.category_batch_flops(64, 9_350_000, 128),
        work_simcat.category_batch_bytes(64, 9_350_000, 128, 16, 19),
        work.peaks_for("TPU v5 lite"))
    assert bound == "bytes"
    # the table's 4.787 GB and 74.8 MB of bits
    assert t == pytest.approx((4.7872e9 + 74.8e6) / 819e9, rel=2e-3)


def test_new_readers_on_hand_made_runs():
    summary = tracereduce.TraceSummary(
        window_ns=10**9, busy_ns=8e8, n_devices=1,
        ops=[("%pio_block_max = custom-call", 6 * 10**8, 100),
             ("%custom-call = custom-call:TopK", 1 * 10**8, 100),
             (RELAY, 2 * 10**7, 100), (ROW_READ, 3 * 10**7, 800),
             ("%fusion = fusion", 5 * 10**7, 100)])
    roof = cells.load_reader("simcat_scorer_roofline")
    run_ = {"trace": summary, "traced_batch_spans": [(0.0, 0.01, 8)] * 100,
            "peaks": work.peaks_for("TPU v5 lite"),
            "shape": {"n_items": 9_350_000, "rank": 128, "k": 16,
                      "excluded": 19}}
    # 8 ms of device time a batch against 5.86 ms for the table's bytes
    # and 8 rows' bits
    assert roof(run_, {"per_batch_op": "TopK"}) == pytest.approx(
        100 * 5.857 / 8.0, rel=1e-3)
    assert roof(run_, {"per_batch_op": "no_such_kernel"}) is None
    assert roof(dict(run_, shape={}), {"per_batch_op": "TopK"}) is None
    share = cells.load_reader("filtered_path_share")
    assert share({"filter_rows": {"cats": 30.0, "ids": 10.0}},
                 {"filter": "cats"}) == 75.0
    metric = json.loads((ROOT / "perfbench/metrics/allow_device_ms.cats.json"
                         ).read_text())
    assert metric["reader"] == "op_ms_per_batch"
    per_batch = cells.load_reader(metric["reader"])
    # the re-lay and the rows' reads, 0.2 + 0.3 ms a batch
    assert per_batch({"trace": summary}, metric["args"]) == \
        pytest.approx(0.5)


# -- the plain reference --------------------------------------------------------


def test_reference_against_brute_force_numpy():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    m, r, num = 3000, 16, 10
    table = rng.normal(size=(m, r)).astype(np.float32)
    table /= np.linalg.norm(table, axis=1, keepdims=True)
    cats = np.stack([rng.integers(0, 3, m), 3 + rng.integers(0, 20, m),
                     23 + rng.integers(0, 400, m)], axis=1).astype(np.int32)
    queries = [
        {"seeds": [5], "blacklist": [], "categories": [int(cats[5, 0])]},
        {"seeds": [7, 8], "blacklist": [9, 10],
         "categories": [int(cats[7, 1]), int(cats[7, 2])]},
        {"seeds": [11], "blacklist": [], "categories": [int(cats[11, 2])]},
        {"seeds": [12], "blacklist": [], "categories": [999]},
        {"seeds": [13], "blacklist": [14], "categories": []},
    ]
    items, vals = simcat_ref.answer(table, jnp.asarray(table), cats, queries,
                                    num)
    qvecs = simcat_ref.query_vectors(table, [q["seeds"] for q in queries])
    served_items, served_scores = [], []
    for q, query in enumerate(queries):
        s = table @ qvecs[q]
        allowed = np.isin(cats, query["categories"]).any(axis=1) \
            if query["categories"] else np.ones(m, bool)
        allowed[query["seeds"] + query["blacklist"]] = False
        order = np.argsort(-np.where(allowed, s, -np.inf), kind="stable")
        n = min(num, int(allowed.sum()))
        np.testing.assert_array_equal(items[q][:n], order[:n])
        np.testing.assert_allclose(vals[q][:n], s[order[:n]], rtol=1e-5)
        assert np.isneginf(vals[q][n:]).all()
        served_items.append(order[:n].tolist())
        served_scores.append(s[order[:n]].tolist())
    assert len(served_items[2]) < num, "a narrow set: a short answer"
    assert served_items[3] == [], "an unknown category allows nothing"
    out = simcat_ref.compare(table, jnp.asarray(table), cats, queries,
                             served_items, served_scores, num)
    assert out["rank_gap"] <= 1e-6 and out["score_err"] <= 1e-6
    for name in ("answers_with_repeats", "answers_with_excluded",
                 "answers_outside_categories", "answers_short"):
        assert out[name] == 0, name
    # the last query names nothing: its filter changes nothing
    assert out["answers_filter_blind"] == 1
    assert out["per_query"]["allowed"][2] == len(served_items[2])
    # each count, planted
    outsider = int(np.flatnonzero(cats[:, 0] != cats[5, 0])[0])
    served_items[0][3] = outsider
    served_items[1][2] = 9
    served_items[4][1] = served_items[4][0]
    served_items[2] = served_items[2][:-1]
    served_scores[2] = served_scores[2][:-1]
    out = simcat_ref.compare(table, jnp.asarray(table), cats, queries,
                             served_items, served_scores, num)
    assert out["answers_outside_categories"] == 2     # the blackListed too
    assert out["answers_with_excluded"] == 1
    assert out["answers_with_repeats"] == 1
    assert out["answers_short"] == 1


def test_reference_imports_nothing_of_the_program():
    text = (ROOT / "perfbench/reference/simcat_ref.py").read_text()
    assert "predictionio_tpu" not in text
    assert "import similar_ref" not in text and "from .similar_ref" not in text
    assert 'default_matmul_precision("highest")' in text


# -- the driver, end to end ---------------------------------------------------


def test_result_line_of_the_category_driver(tiny):
    r = _run(tiny)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"serve_p95_ms", "setup_s"}
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    numbers = _numbers(r)
    assert set(numbers) == LIMITS
    assert numbers["answers_filter_blind"] == 0
    assert numbers["answers_outside_categories"] == 0 == \
        numbers["answers_short"]
    rows = r["info"]["rows_by_filter_in_window"]
    assert rows["cats"] > 0 and sum(rows.values()) == rows["cats"], \
        "every batch's categories ride as numbers"
    assert rows.get("mask", 0) == 0
    assert r["info"]["category_ids_in_window"] >= r["attempted"]
    paths = r["info"]["calls_by_path_in_window"]
    assert sum(paths.values()) == paths["blocked_cats"] > 0
    assert r["info"]["categories"] == 24 + 96
    assert r["info"]["memberships"] == 3 * 30000
    assert r["info"]["categoryIndexBytes"] == (24 + 96 + 2) * 1024 * 4
    narrow, wide = r["info"]["narrowest_and_widest_sampled_set"]
    assert narrow < 500 and wide > 5000, \
        "the sample holds the narrowest and the widest allowed sets"
    json.dumps(r)


def test_traced_result_line_of_the_category_driver(tiny, fake_trace):
    import re

    pattern = json.loads((ROOT / "perfbench/metrics/allow_device_ms.cats.json"
                          ).read_text())["args"]["pattern"]
    assert re.search(pattern, RELAY) and re.search(pattern, ROW_READ)
    for other in ("%pio_block_max.1 = f32[64,146944]{1,0:T(8,128)} custom-"
                  "call(f32[64,128]{1,0} %q, f32[9350000,128]{1,0} %t, s32[64"
                  ",292864]{1,0} %w)",
                  "%custom-call = (f32[64,48]{1,0}, s32[64,48]{1,0}) custom-"
                  "call(f32[64,146944]{1,0} %r), custom_call_target=\"TopK\"",
                  "%fusion.1 = f32[3072,128]{1,0:T(8,128)S(1)} fusion(f32["
                  "9350000,128]{1,0:T(8,128)} %table_t_packed.1, s32[3072])",
                  "%while.2 = (s32[], u32[64,286,8,128]{3,2,1,0:T(8,128)}, "
                  "pred[64]{0}, s32[64,4]{1,0}) while(%tuple.29)",
                  "%conditional.3 = u32[1,286,8,128]{3,2,1,0:T(8,128)} "
                  "conditional(pred[] %p, (s32[4]) %a, (s32[4]) %b)"):
        assert not re.search(pattern, other), other
    # a window long enough that the profiler, slow to start on a loaded
    # machine, still opens while batches run
    r = _run(tiny, trace=True, seconds=3.0)
    cell = cells.resolve(CELL, tiny)
    wanted = {m.name for m in cell.per_layer}
    assert NEW_METRICS <= wanted
    assert set(r["metrics"]) == wanted, wanted ^ set(r["metrics"])
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert r["metrics"]["category_path_share.cats"]["value"] == 100.0
    assert r["metrics"]["filter_build_ms.cats"]["value"] > 0
    assert r["metrics"]["allow_device_ms.cats"]["value"] > 0
    for key, m in r["metrics"].items():
        if "roofline" in key or "mfu" in key:
            assert 0 < m["value"] <= 105, key
    assert r["correct"] is True
    json.dumps(r)


def test_closed_loop_mode_reports_requests_per_second(tiny):
    """The mode the saturation sweep runs the same queries in."""
    cell = cells.resolve(CELL, tiny)
    cell.traffic = dict(cell.traffic, mode="closed", connections=8)
    out = cells.load_driver(cell.driver, tiny)(cell, {
        "seed": 11, "seconds": 0.5, "trace": False, "log": lambda m: None,
        "clock": harness.SetupClock(0.0), "device": CPU})
    assert out["failed"] == 0 and out["end_to_end"]["serve_rps"] > 0
    assert harness.judge(out["numbers"], cell.config["limits"])[0]


@pytest.mark.parametrize("lack", ["no_index", "no_slots"])
def test_the_parents_program_fails_the_cell_at_once(tiny, monkeypatch, lack):
    """Laid over a program that keeps no category index, the driver exits
    2 before it builds anything."""
    from predictionio_tpu.ops import topk
    from predictionio_tpu.templates import _common

    if lack == "no_index":
        monkeypatch.delattr(_common, "CategoryIndex")
    else:
        monkeypatch.delattr(topk, "CATEGORY_SLOTS")
    monkeypatch.setattr(simcat_ref, "item_categories", lambda *a: 1 / 0)
    with pytest.raises(SystemExit) as exit_:
        _run(tiny)
    assert exit_.value.code == 2


# -- faults planted under the timed path: `correct` has to come out false ----


def test_fault_categories_ignored(tiny, monkeypatch):
    import dataclasses

    from predictionio_tpu.templates.similarproduct import (
        SimilarProductAlgorithm,
    )

    real = SimilarProductAlgorithm.batch_predict

    def blind(self, model, queries):
        return real(self, model, [
            dataclasses.replace(q, categories=None) for q in queries])

    monkeypatch.setattr(SimilarProductAlgorithm, "batch_predict", blind)
    r = _run(tiny)
    assert r["correct"] is False and r["failed"] == 0
    assert _numbers(r)["answers_outside_categories"] > 0


def test_fault_one_categorys_bits_dropped(tiny, monkeypatch):
    """Half of the largest department's bits are lost on the way to the
    device: its queries are served worse items than the reference finds,
    or too few."""
    from predictionio_tpu.templates._common import DeviceTableMixin

    real = DeviceTableMixin.device_category_rows

    def lossy(self):
        rows = real(self)
        if not getattr(self, "_lost", False):
            self._lost = True
            rows = rows.at[0, :, ::2].set(0)
            self._dev_category_rows = rows
        return rows

    monkeypatch.setattr(DeviceTableMixin, "device_category_rows", lossy)
    r = _run(tiny)
    numbers = _numbers(r)
    assert r["correct"] is False
    assert numbers["rank_gap"] > 0.04 or numbers["answers_short"] > 0
    assert numbers["answers_outside_categories"] == 0


def test_fault_the_chosen_blocks_not_tested_again(tiny, monkeypatch,
                                                  fresh_programs):
    """The scan's bits choose the right blocks; without the second test a
    chosen block's other items are served with them."""
    import jax.numpy as jnp

    from predictionio_tpu.ops import topk

    monkeypatch.setattr(
        topk, "_allowed_in_blocks", lambda words, blocks, blk: jnp.ones(
            (blocks.shape[0], blocks.shape[1] * blk), bool))
    r = _run(tiny)
    assert r["correct"] is False and r["failed"] == 0
    assert _numbers(r)["answers_outside_categories"] > 0


def test_fault_answers_returned_to_the_wrong_request(tiny, monkeypatch):
    from predictionio_tpu.templates.similarproduct import (
        SimilarProductAlgorithm,
    )

    real = SimilarProductAlgorithm.batch_predict

    def rotated(self, model, queries):
        out = real(self, model, queries)
        return out[1:] + out[:1]

    monkeypatch.setattr(SimilarProductAlgorithm, "batch_predict", rotated)
    # a batch of one rotates onto itself: keep the server busy
    _edit(tiny / TRAFFIC, rate_per_s=400)
    try:
        r = _run(tiny)
    finally:
        _edit(tiny / TRAFFIC, rate_per_s=100)
    assert r["correct"] is False


# -- the control ----------------------------------------------------------------


@pytest.mark.parametrize("precision,correct", [("highest", True),
                                               ("bf16", True),
                                               ("fp8", False)])
def test_fp8_control_reads_not_correct(tiny, precision, correct):
    """The reference at the nearest precision below the stated one, put
    in the program's place, fails by `rank_gap` or `score_err`; at
    `highest` it passes against itself, and with bf16-rounded operands
    (what the configuration states) it passes too."""
    import jax.numpy as jnp

    cell = cells.resolve(CELL, tiny)
    cfg = cell.config
    table = np.array(serve_simcat.serve_similar.make_items(cfg, 9))
    cats = simcat_ref.item_categories(cfg, 9)
    pool = serve_simcat.make_pool(cfg, cell.traffic, cats)[:12]
    items, vals = simcat_ref.answer(table, jnp.asarray(table), cats, pool, 10,
                                    precision)
    out = simcat_ref.compare(table, jnp.asarray(table), cats, pool,
                             [r.tolist() for r in items],
                             [v.tolist() for v in vals], 10)
    numbers = {name: out[name] for name in cfg["limits"]}
    assert harness.judge(numbers, cfg["limits"])[0] is correct
    if not correct:
        assert max(numbers["rank_gap"], numbers["score_err"]) > 0.04
        assert numbers["answers_outside_categories"] == 0
