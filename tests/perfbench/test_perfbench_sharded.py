"""The four-chip serving cell over a table no chip holds, rehearsed on the CPU
at a tiny size on the 8-device virtual mesh, with the look for a chip
patched by the test itself: the new driver end to end and its result line,
a traced run's per-layer metrics from a made-up reduction, the plain
reference against a brute-force numpy answer, the fp8 control failing the
limits, a fault that `correct` has to catch, name-to-files resolution of
the new cell and of the accepted metrics it reports, the manifest's caps,
and the check with which the driver refuses a program that cannot take a
table that lies sharded on the chips."""

import json
import shutil
import subprocess
from pathlib import Path

import numpy as np
import pytest

from perfbench import cells, harness, run, serve_sharded, tracereduce
from perfbench.reference import sharded_topk_ref

ROOT = Path(__file__).resolve().parents[2]
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 8}
CELL = "rec-amazon23-r128-x4.serve-sharded-steady"
CONFIG = "perfbench/configs/rec-amazon23-r128-x4.json"
TRAFFIC = "perfbench/traffic/serve-sharded-steady.json"
# accepted metrics of the one-chip Recommendation cell whose `workloads`
# take this cell too: the manifest holds its cap of per-layer metrics, so
# the cell reports no metric of its own
OLD_CELL = "rec-yambda-r64.serve-steady"
APPENDED = {"device_idle_share.steady", "batcher_mean_batch.steady",
            "turn_fetch_ms.steady", "gen_late_p95_ms.steady"}
MAX_PER_LAYER = 128
LIMITS = {"rank_gap", "score_err", "answers_with_repeats"}


def _edit(path: Path, **changes) -> None:
    doc = json.loads(path.read_text())
    doc.update(changes)
    path.write_text(json.dumps(doc))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with the cell's files at sizes a test can
    hold: eight shards of 3,072 rows (long enough for the blocked scan on
    each), a short pool, few connections."""
    root = tmp_path_factory.mktemp("tiny-sharded")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _edit(root / CONFIG, n_users=5000, n_items=8 * 3072, microbatch_max=8,
          check={"answers": 16})
    _edit(root / TRAFFIC, connections=16, rate_per_s=100, user_pool=64,
          trace_after_s=0.1, trace_seconds=0.8)
    return root


def _run(tiny, seed=2**31 + 29, seconds=1.0, trace=False):
    return run.execute(cells.resolve(CELL, tiny), seed, seconds, trace,
                       CPU, tiny)


# -- the cell and its files ---------------------------------------------------


def test_the_cell_resolves_to_its_files():
    cell = cells.resolve(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "rec-amazon23-r128-x4", "serve-sharded-steady", 4)
    assert cell.driver == "http_sharded"
    assert callable(cells.load_driver(cell.driver))
    assert {m["name"] for m in cell.end_to_end} == {"serve_p95_ms", "setup_s"}
    assert {m.name for m in cell.per_layer} == APPENDED | {
        "backend_init_s", "data_build_s", "warmup_s", "compiles_in_window"}
    cfg = cell.config
    assert (cfg["n_users"], cfg["n_items"], cfg["rank"]) == (
        54_510_000, 48_190_000, 128)
    assert cfg["microbatch_max"] == 64 and cfg["retrieval"] == "exact"
    assert cfg["distributedTopk"] is True and cfg["architecture"] is None
    assert cfg["n_items"] % cfg["deployment"]["chips"] == 0
    assert cfg["check"]["answers"] == 48
    assert set(cfg["limits"]) == LIMITS == set(cfg["limits_why"])
    assert (cfg["limits"]["rank_gap"], cfg["limits"]["score_err"]) == (
        0.04, 0.04)
    assert cfg["limits"]["answers_with_repeats"] == 0
    t = cell.traffic
    assert t["mode"] == "open" and t["connections"] == 256 and t["num"] == 10
    assert t["rate_per_s"] % 10 == 0 and t["rate_per_s"] > 0
    assert (t["user_pool"], t["user_zipf_exponent"]) == (65536, 1.1)
    assert (t["trace_after_s"], t["trace_seconds"]) == (2.0, 4.0)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "rec-amazon23-r128-x4")
    assert entry["reduced"] == ["training"]
    mine = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(mine) == 1 and mine[0]["chips"] == 4
    assert len(mine[0]["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(entry["source"]) <= 200
    fours = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert fours <= max(1, len(manifest["workloads"]) // 4)


def _manifest_before_the_cell():
    """The manifest as git has it before the commit that added this cell's
    configuration (HEAD while the cell is not committed); None without git
    history."""
    def git(*args):
        out = subprocess.run(["git", *args], cwd=ROOT, capture_output=True,
                             text=True)
        return out.stdout if out.returncode == 0 else None

    added = git("log", "--diff-filter=A", "--format=%H", "--", CONFIG)
    ref = f"{added.split()[-1]}^" if added and added.split() else "HEAD"
    shown = git("show", f"{ref}:BENCHMARK.json")
    return json.loads(shown) if shown else None


@pytest.mark.parametrize("name", sorted(APPENDED))
def test_each_appended_metric_reads_the_cell(name):
    cell = cells.resolve(CELL)
    mine = [m for m in cell.per_layer if m.name == name]
    assert len(mine) == 1 and mine[0].moves == "serve_p95_ms"
    read = cells.load_reader(mine[0].reader)
    assert read({}, mine[0].args) is None, "nothing to read, nothing raised"
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [OLD_CELL, CELL]
    assert {m.name for m in cells.resolve(OLD_CELL).per_layer} >= {name}


def test_the_manifest_keeps_to_its_caps():
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert 1 <= len(manifest["per_layer"]) <= MAX_PER_LAYER
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["workloads"]) <= 24
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in manifest[key]]
    assert len(names) == len(set(names))


def test_nothing_the_benchmark_had_is_edited_but_the_appended_lists():
    """Against the manifest before the cell: every older entry is where it
    was, letter for letter, but the `workloads` of `serve_p95_ms` and of
    the appended metrics, which gain this cell at their end."""
    old = _manifest_before_the_cell()
    if old is None:
        pytest.skip("no git history here")
    new = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads"):
        assert new[key][:len(old[key])] == old[key]
        assert len(new[key]) == len(old[key]) + 1
    assert len(new["per_layer"]) == len(old["per_layer"])
    appended = {"serve_p95_ms"} | APPENDED
    for was, now in zip(old["end_to_end"] + old["per_layer"],
                        new["end_to_end"] + new["per_layer"]):
        if was["name"] in appended:
            assert now == dict(was, workloads=was["workloads"] + [CELL])
        else:
            assert now == was


# -- the data ------------------------------------------------------------------


def test_tables_and_pool_come_from_the_seed(tiny):
    from predictionio_tpu.parallel import make_mesh

    cfg = cells.resolve(CELL, tiny).config
    a = serve_sharded.make_users(cfg, 2**31 + 5)
    np.testing.assert_array_equal(a, serve_sharded.make_users(cfg,
                                                              2**31 + 5))
    assert (a != serve_sharded.make_users(cfg, 6)).any()
    assert a.dtype == np.float32 and abs(a.std() * 128 ** 0.5 - 1) < 0.05
    items = serve_sharded.make_items(cfg, 2**31 + 5, make_mesh())
    assert items.shape == (cfg["n_items"], cfg["rank"])
    assert len(items.sharding.device_set) == 8
    on_four = serve_sharded.make_items(cfg, 2**31 + 5, make_mesh(4))
    np.testing.assert_array_equal(np.asarray(items), np.asarray(on_four))
    pool = serve_sharded.user_pool(5000, 1.1, 4096, 7, 2**31 + 5)
    again = serve_sharded.user_pool(5000, 1.1, 4096, 7, 12)
    assert sorted(pool) == sorted(again) and pool != again
    counts = np.bincount(pool, minlength=5000)
    assert counts[0] > counts[10] > counts[1000]


def test_numbered_ids_answer_as_a_string_index_would():
    ids = serve_sharded.NumberedIds("u", 100)
    assert len(ids) == 100
    assert [ids.get(s) for s in ("u0", "u99", "u100", "u007", "x1", "u",
                                 "u-1", 5)] == [0, 99, -1, -1, -1, -1, -1,
                                                -1]
    assert ids.decode(np.array([[1, 20]])).tolist() == [["u1", "u20"]]


# -- the reference -------------------------------------------------------------


def _placed(table, n_rows, mesh):
    from predictionio_tpu.ops.distributed_topk import place_rows

    return place_rows(np.concatenate(
        [table, np.zeros((n_rows - len(table), table.shape[1]),
                         np.float32)]), mesh)


def test_reference_against_brute_force_numpy():
    """Every item scored where its row lies, the chips' best merged: the
    answer and the spread are numpy's over the whole table, and rows past
    `n_items` count for nothing."""
    from predictionio_tpu.parallel import make_mesh

    rng = np.random.default_rng(3)
    n, rank = 8 * 500 - 3, 16
    table = rng.normal(size=(n, rank)).astype(np.float32)
    users = rng.normal(size=(20, rank)).astype(np.float32)
    placed = _placed(table, 8 * 500, make_mesh())
    items, scores = sharded_topk_ref.answer(users, placed, 10, "highest", n)
    s = users.astype(np.float64) @ table.astype(np.float64).T
    want = np.argsort(-s, axis=1)[:, :10]
    np.testing.assert_array_equal(items, want)
    np.testing.assert_allclose(scores, np.take_along_axis(s, want, 1),
                               rtol=1e-5, atol=1e-5)
    out = sharded_topk_ref.compare(users, placed, items, scores, n)
    assert out["rank_gap"] < 1e-5 and out["score_err"] < 1e-5
    # one served item swapped for the 11th best: rank_gap sees it
    worse = items.copy()
    worse[:, 9] = np.argsort(-s, axis=1)[:, 10]
    gap = sharded_topk_ref.compare(users, placed, worse, scores, n)
    sigma = s.std(axis=1)
    expect = ((np.take_along_axis(s, want[:, 9:10], 1)
               - np.take_along_axis(s, worse[:, 9:10], 1))[:, 0] / sigma)
    assert gap["rank_gap"] == pytest.approx(expect.max(), rel=1e-3)


def test_the_fp8_control_fails_the_limits_and_bf16_does_not(tiny):
    """The reference at the nearest precision below the stated one, served
    in the program's place, is not correct by the cell's limits; at the
    stated one (bfloat16 operands) it is."""
    from predictionio_tpu.parallel import make_mesh

    cfg = cells.resolve(CELL, tiny).config
    limits = cfg["limits"]
    items = serve_sharded.make_items(cfg, 2**31 + 3, make_mesh())
    users = serve_sharded.make_users(cfg, 2**31 + 3)[:48]
    read = {}
    for precision in ("fp8", "bf16", "highest"):
        served, scores = sharded_topk_ref.answer(users, items, 10, precision,
                                                 cfg["n_items"])
        out = sharded_topk_ref.compare(users, items, served, scores,
                                       cfg["n_items"])
        read[precision] = (out["rank_gap"], out["score_err"])
    assert read["fp8"][0] > limits["rank_gap"] or \
        read["fp8"][1] > limits["score_err"], read
    assert read["bf16"][0] < limits["rank_gap"] / 2, read
    assert read["bf16"][1] < limits["score_err"] / 2, read
    assert read["highest"][0] < 1e-5 and read["highest"][1] < 1e-5


# -- the driver end to end -------------------------------------------------------


def test_rehearsal_prints_the_result_line(tiny, capsys):
    r = _run(tiny)
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] == 100
    assert set(r["metrics"]) == {"serve_p95_ms", "setup_s"}
    assert {c["name"] for c in r["compared"]} == LIMITS
    index = r["info"]["index"]
    assert (index["shards"], index["shardRows"]) == (8, 3072)
    assert index["shardBytes"] == index["parityBytes"] == 3072 * 128 * 4
    assert index["degradedPolls"] == 0
    json.dumps(r)


@pytest.fixture()
def fake_trace(monkeypatch):
    """The CPU has no device plane: a traced rehearsal reads a made-up
    reduction, so that every reader and the result line are driven."""
    summary = tracereduce.TraceSummary(
        window_ns=1_000_000_000, busy_ns=600_000_000.0, n_devices=2,
        ops=[("%pio_block_max = custom-call:tpu_custom_call", 400_000_000, 10),
             ("%custom-call = custom-call:TopK", 50_000_000, 10),
             ("%fusion = fusion", 100_000_000, 10)],
        gaps=[(0, 400_000_000, tracereduce.NO_HOST_SPAN)],
    )
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: summary)


def test_traced_rehearsal_reads_every_appended_metric(tiny, fake_trace):
    r = _run(tiny, trace=True)
    assert r["correct"] is True
    metrics = r["metrics"]
    assert APPENDED <= set(metrics), sorted(metrics)
    assert metrics["device_idle_share.steady"]["value"] == pytest.approx(40)
    assert metrics["batcher_mean_batch.steady"]["value"] > 0


def test_answers_returned_to_the_wrong_requests_are_not_correct(
        tiny, monkeypatch):
    from predictionio_tpu.ops import distributed_topk

    scan = distributed_topk.ShardedTopK.__call__

    def rotated(self, queries, k, deadline=None):
        vals, ixs = scan(self, queries, k, deadline)
        return np.roll(np.asarray(vals), 1, 0), np.roll(np.asarray(ixs), 1, 0)

    monkeypatch.setattr(distributed_topk.ShardedTopK, "__call__", rotated)
    r = _run(tiny, seed=11)
    assert r["correct"] is False
    assert dict((c["name"], c["value"]) for c in r["compared"])[
        "rank_gap"] > 1.0


def test_a_program_that_cannot_take_the_table_exits_2(monkeypatch, capsys):
    from predictionio_tpu.ops import distributed_topk

    monkeypatch.delattr(distributed_topk, "place_rows")
    with pytest.raises(SystemExit) as e:
        cells.load_driver("http_sharded")(cells.resolve(CELL), {})
    assert e.value.code == 2
    assert "place_rows" in capsys.readouterr().err
