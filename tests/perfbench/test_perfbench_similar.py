"""The `similarproduct` cell rehearsed on the CPU at a tiny size, with the
look for a chip patched by the test itself: the new driver end to end, the
result line's keys, the faults that `correct` has to catch (exclusions
dropped, a served blackListed item, answers returned to the wrong requests),
the fp8 control, the system against the plain reference on seeded tables,
name-to-files resolution of the new cell and of each new metric file, the
new work counts and readers on hand-made runs, and the body-pool generator
against a stub server."""

import json
import shutil
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import numpy as np
import pytest

from perfbench import (cells, harness, run, serve_similar, tracereduce, work,
                       work_similar)

ROOT = Path(__file__).resolve().parents[2]
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
CELL = "sim-amazon14-r128.serve-similar-steady"
TRAFFIC = "perfbench/traffic/serve-similar-steady.json"
TWINS = {
    "gen_late_p95_ms", "edge_host_ms", "batcher_wait_ms",
    "batcher_mean_batch", "scorer_device_ms", "turn_prepare_ms",
    "turn_fetch_ms", "turn_complete_ms", "turn_park_ms",
    "dispatcher_offcpu_share", "gc_pause_ms_per_s", "device_idle_share",
    "serve_mfu",
}
NEW_METRICS = {f"{name}.similar" for name in TWINS | {
    "filter_build_ms", "filtered_path_share", "filtered_scorer_roofline"}}
LIMITS = {"rank_gap", "score_err", "answers_with_repeats",
          "answers_with_excluded"}


def _edit(path: Path, **changes) -> None:
    doc = json.loads(path.read_text())
    doc.update(changes)
    path.write_text(json.dumps(doc))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with the cell's files at sizes a test can
    hold: a catalogue just long enough for the blocked path with ids."""
    root = tmp_path_factory.mktemp("tiny-similar")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    pb = root / "perfbench"
    _edit(pb / "configs/sim-amazon14-r128.json", n_items=30000,
          microbatch_max=8, check={"answers": 16})
    _edit(root / TRAFFIC, connections=16, rate_per_s=100, query_pool=64,
          trace_after_s=0.1, trace_seconds=0.3)
    return root


def _run(tiny, seed=2**31 + 29, seconds=0.6, trace=False):
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
             ("%custom-call = custom-call:TopK", 100_000_000, 10),
             ("%fusion = fusion", 100_000_000, 10)],
        gaps=[(0, 400_000_000, tracereduce.NO_HOST_SPAN)],
    )
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: summary)


# -- the cell and its files ---------------------------------------------------


def test_the_cell_resolves_to_its_files():
    cell = cells.resolve(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "sim-amazon14-r128", "serve-similar-steady", 1)
    assert cell.driver == "http_similar"
    assert callable(cells.load_driver(cell.driver))
    assert {m["name"] for m in cell.end_to_end} == {"serve_p95_ms", "setup_s"}
    assert cell.config["n_items"] == 9_350_000 and cell.config["rank"] == 128
    assert cell.config["microbatch_max"] == 64
    assert set(cell.config["limits"]) == LIMITS
    t = cell.traffic
    assert t["mode"] == "open" and t["connections"] == 256 and t["num"] == 10
    assert t["rate_per_s"] % 10 == 0
    assert (t["seeds_min"], t["seeds_max"]) == (1, 3)
    assert (t["blacklist_min"], t["blacklist_max"]) == (0, 16)
    assert t["item_zipf_exponent"] == 1.1
    from predictionio_tpu.ops.topk import EXCLUDE_LADDER

    assert t["seeds_max"] + t["blacklist_max"] <= EXCLUDE_LADDER[-1], \
        "every query's excluded ids ride as ids"
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "sim-amazon14-r128")
    assert entry["reduced"] == ["training"]
    p95 = next(m for m in manifest["end_to_end"]
               if m["name"] == "serve_p95_ms")
    assert p95["workloads"][-1] == CELL and p95["bound"] == 0.1


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
    base = name.rsplit(".", 1)[0]
    if base in TWINS:       # the accepted cell's metric, letter for letter
        twin = next(m for m in manifest["per_layer"]
                    if m["name"] == f"{base}.steady")
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == twin[key]
        pb = ROOT / "perfbench/metrics"
        assert json.loads((pb / f"{name}.json").read_text()) == json.loads(
            (pb / f"{base}.steady.json").read_text())


def test_the_accepted_cells_report_none_of_the_new_metrics():
    for name in ("rec-yambda-r64.serve-steady", "rec-netflix-r64.train"):
        assert not NEW_METRICS & {m.name for m in
                                  cells.resolve(name).per_layer}


def test_work_counts_with_excluded_ids():
    # 2 queries x 1000 items x rank 8, k 16, 19 excluded ids a row
    assert work_similar.filtered_batch_flops(2, 1000, 8) == 2 * 2 * 1000 * 8
    # table 1000*8*4, candidates 2*(16+19)*8*4, queries 2*8*4, lists 2*19*4,
    # results 2*16*(4+4)
    assert work_similar.filtered_batch_bytes(2, 1000, 8, 16, 19) == (
        32000 + 2240 + 64 + 152 + 256)
    peaks = work.peaks_for("TPU v5 lite")
    t, bound = work.least_seconds(
        work_similar.filtered_batch_flops(64, 9_350_000, 128),
        work_similar.filtered_batch_bytes(64, 9_350_000, 128, 16, 19),
        peaks)
    assert bound == "bytes"
    assert t == pytest.approx(4.7872e9 / 819e9, rel=1e-3)


def test_new_readers_on_hand_made_runs():
    share = cells.load_reader("filtered_path_share")
    assert share({"filter_rows": {"ids": 30.0, "mask": 10.0}},
                 {"filter": "ids"}) == 75.0
    assert share({"filter_rows": {"none": 5.0}}, {"filter": "ids"}) == 0.0
    assert share({"filter_rows": {}}, {"filter": "ids"}) is None
    build = cells.load_reader("filter_build_ms")
    assert build({"filter_build": (0.5, 1000)}, {}) == pytest.approx(0.5)
    assert build({"filter_build": (0.0, 0)}, {}) is None
    roof = cells.load_reader("filtered_scorer_roofline")
    summary = tracereduce.TraceSummary(
        window_ns=10**9, busy_ns=8e8, n_devices=1,
        ops=[("%pio_block_max = custom-call", 6 * 10**8, 100),
             ("%custom-call = custom-call:TopK", 1 * 10**8, 100),
             ("%fusion = fusion", 1 * 10**8, 100)])
    run_ = {"trace": summary, "traced_batch_spans": [(0.0, 0.01, 8)] * 100,
            "peaks": work.peaks_for("TPU v5 lite"),
            "shape": {"n_items": 9_350_000, "rank": 128, "k": 16,
                      "excluded": 19}}
    # 8 ms of device time a scan event against 5.85 ms for its bytes
    assert roof(run_, {"per_batch_op": "pio_block_max"}) == pytest.approx(
        100 * 5.845 / 8.0, rel=1e-3)
    assert roof(run_, {"per_batch_op": "no_such_kernel"}) is None
    del run_["shape"]["excluded"]
    assert roof(run_, {"per_batch_op": "pio_block_max"}) is None


# -- the driver, end to end ---------------------------------------------------


def test_result_line_of_the_similar_driver(tiny):
    r = _run(tiny)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"serve_p95_ms", "setup_s"}
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    assert {c["name"] for c in r["compared"]} == LIMITS
    rows = r["info"]["rows_by_filter_in_window"]
    assert rows["ids"] > 0 and sum(rows.values()) == rows["ids"]
    paths = r["info"]["calls_by_path_in_window"]
    assert paths["blocked_ids"] > 0
    assert sum(paths.values()) == paths["blocked_ids"], \
        "a lone request takes the batch's path too"
    json.dumps(r)


def test_traced_result_line_of_the_similar_driver(tiny, fake_trace):
    r = _run(tiny, trace=True)
    cell = cells.resolve(CELL, tiny)
    wanted = {m.name for m in cell.per_layer}
    assert NEW_METRICS <= wanted
    assert set(r["metrics"]) == wanted, wanted - set(r["metrics"])
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert r["metrics"]["filtered_path_share.similar"]["value"] == 100.0
    assert r["metrics"]["filter_build_ms.similar"]["value"] > 0
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


def test_the_parents_program_fails_the_cell_at_once(tiny, monkeypatch):
    """Laid over a program without filters as data, the driver raises
    before it builds anything."""
    from predictionio_tpu.templates import _common

    monkeypatch.delattr(_common, "batch_filter")
    with pytest.raises(ImportError):
        _run(tiny)


# -- faults planted under the timed path: `correct` has to come out false ----


def _with_rows(monkeypatch, change):
    """Plant a fault where the template hands its batch's filters over."""
    from predictionio_tpu.templates import similarproduct

    real = similarproduct.batch_filter

    def planted(items, item_props, rows):
        return real(items, item_props, [
            None if row is None else change(row) for row in rows])

    monkeypatch.setattr(similarproduct, "batch_filter", planted)


def test_fault_exclusions_dropped(tiny, monkeypatch):
    _with_rows(monkeypatch,
               lambda row: row._replace(blacklist=(), exclude_ix=()))
    r = _run(tiny)
    assert r["correct"] is False and r["failed"] == 0
    assert _numbers(r)["answers_with_excluded"] > 0


def test_a_served_blacklisted_item_or_a_repeat_is_counted(tiny):
    """The blackList is drawn by popularity, so an ignored one seldom
    shows among a query's ten best; where it does, it is counted."""
    cell = cells.resolve(CELL, tiny)
    table = np.array(serve_similar.make_items(cell.config, 9))
    pool = serve_similar.make_pool(cell.config, cell.traffic)
    pool = [q for q in pool if q["blacklist"]][:4]
    sample = _reference_sample(table, pool, "highest")
    clean = serve_similar.compare_sample(table, pool, sample, 10)
    assert harness.judge(clean, cell.config["limits"])[0]
    for j, changed in ((1, "answers_with_excluded"),
                       (2, "answers_with_repeats")):
        body = json.loads(sample[j]["body"])
        body["itemScores"][3]["item"] = (
            f"i{pool[j]['blacklist'][0]}" if j == 1
            else body["itemScores"][0]["item"])
        dirty = list(sample)
        dirty[j] = {"user": j, "body": json.dumps(body)}
        numbers = serve_similar.compare_sample(table, pool, dirty, 10)
        assert numbers[changed] == 1.0
        assert not harness.judge(numbers, cell.config["limits"])[0]


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


# -- the control, and the system against the reference ------------------------


def _reference_sample(table, queries, precision):
    """A sample as the load generator keeps it, answered by the reference."""
    import jax.numpy as jnp

    from perfbench.reference import similar_ref

    items, vals = similar_ref.answer(table, jnp.asarray(table), queries, 10,
                                     precision)
    return [
        {"user": j, "body": json.dumps({"itemScores": [
            {"item": f"i{int(ix)}", "score": float(v)}
            for ix, v in zip(items[j], vals[j])]})}
        for j in range(len(queries))
    ]


@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_control_fp8_product_fails_the_similar_limits(tiny, seed):
    cell = cells.resolve(CELL, tiny)
    table = np.array(serve_similar.make_items(cell.config, seed))
    pool = serve_similar.make_pool(cell.config, cell.traffic)[:16]
    for precision, passes in (("fp8", False), ("highest", True)):
        sample = _reference_sample(table, pool, precision)
        numbers = serve_similar.compare_sample(table, pool, sample, 10)
        correct, compared = harness.judge(numbers, cell.config["limits"])
        assert correct is passes, (precision, compared)
        assert numbers["answers_with_excluded"] == 0


@pytest.mark.parametrize("seed", [7, 2**31 + 8])
def test_the_system_agrees_with_the_reference_on_seeded_tables(tiny, seed):
    """`SimilarProductAlgorithm` itself, no server: every query of the pool
    through `batch_predict` (eight at a time) and the first also through
    `predict`, held against `similar_ref` within the cell's own limits;
    no array of the catalogue's length is built on the way."""
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates import _common
    from predictionio_tpu.templates import similarproduct as smod

    cell = cells.resolve(CELL, tiny)
    table = np.array(serve_similar.make_items(cell.config, seed))
    pool = serve_similar.make_pool(cell.config, cell.traffic)[:24]
    model = smod.SimilarALSModel(
        item_factors=table,
        items=StringIndex([f"i{j}" for j in range(len(table))]),
        item_props={})
    algo = smod.SimilarProductAlgorithm()
    queries = [smod.Query.from_json(json.loads(serve_similar.body_of(q, 10)))
               for q in pool]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(_common, "filter_bias_mask", lambda *a, **k: 1 / 0)
        served = [r for lo in range(0, len(queries), 8)
                  for r in algo.batch_predict(model, queries[lo:lo + 8])]
        alone = algo.predict(model, queries[0])
    assert [s.item for s in alone.item_scores] == \
        [s.item for s in served[0].item_scores]
    np.testing.assert_allclose([s.score for s in alone.item_scores],
                               [s.score for s in served[0].item_scores],
                               atol=1e-6)
    sample = [{"user": j, "body": json.dumps(r.to_json())}
              for j, r in enumerate(served)]
    numbers = serve_similar.compare_sample(table, pool, sample, 10)
    correct, compared = harness.judge(numbers, cell.config["limits"])
    assert correct, compared
    assert numbers["rank_gap"] < 1e-4 and numbers["score_err"] < 1e-4


def test_the_pool_is_what_the_traffic_file_says(tiny):
    cell = cells.resolve(CELL, tiny)
    cfg, traffic = cell.config, cell.traffic
    table = np.asarray(serve_similar.make_items(cfg, 7))
    assert table.shape == (cfg["n_items"], cfg["rank"])
    np.testing.assert_allclose(np.linalg.norm(table, axis=1), 1.0, atol=1e-5)
    other = np.asarray(serve_similar.make_items(cfg, 2**31 + 7))
    assert not np.allclose(table[:4], other[:4])
    pool = serve_similar.make_pool(cfg, traffic)
    assert pool == serve_similar.make_pool(cfg, traffic)
    assert len(pool) == traffic["query_pool"]
    for query in pool:
        assert 1 <= len(query["seeds"]) <= 3
        assert 0 <= len(query["blacklist"]) <= 16
        ids = query["seeds"] + query["blacklist"]
        assert len(set(ids)) == len(ids) and max(ids) < cfg["n_items"]
    assert {len(q["seeds"]) for q in pool} == {1, 2, 3}
    assert min(len(q["blacklist"]) for q in pool) == 0
    assert max(len(q["blacklist"]) for q in pool) == 16
    heads = sum(q["seeds"][0] < 100 for q in pool)
    assert heads > len(pool) // 4, "a Zipf(1.1) head, not uniform draws"
    body = json.loads(serve_similar.body_of(pool[0], 10))
    assert set(body) <= {"items", "num", "blackList"}
    assert body["items"] == [f"i{ix}" for ix in pool[0]["seeds"]]
    bare = serve_similar.body_of({"seeds": [1], "blacklist": []}, 10)
    assert json.loads(bare) == {"items": ["i1"], "num": 10}


# -- the body-pool generator against a stub server ----------------------------


class _Echo(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        n = int(self.headers["Content-Length"])
        query = json.loads(self.rfile.read(n))
        body = json.dumps({"itemScores": [
            {"item": query["items"][0], "score": len(query["blackList"])}
        ] * query["num"]}).encode()
        self.wfile.write(
            b"HTTP/1.1 200 OK\r\nContent-Type: application/json\r\n"
            b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n" + body
        )

    def log_message(self, *a):
        pass


def test_the_generator_sends_the_pools_bodies():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Echo)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    bodies = [json.dumps({"items": [f"i{j}"], "num": 3,
                          "blackList": ["x"] * j}) for j in range(5)]
    spec = {"host": "127.0.0.1", "port": srv.server_address[1],
            "path": "/queries.json", "mode": "closed", "num": 3,
            "seconds": 0.5, "users": [4, 2, 0, 1, 3], "bodies": bodies,
            "connections": 2, "sample": 8, "sample_seed": 1}
    gen = serve_similar.Generator(spec)
    try:
        gen.go()
        result = gen.result()
    finally:
        gen.close()
        srv.shutdown()
        srv.server_close()
        t.join(timeout=5)
    assert result["answered"] > 0 and result["failed"] == 0
    for s in result["sample"]:
        served = json.loads(s["body"])["itemScores"]
        assert served[0]["item"] == f"i{s['user']}"
        assert served[0]["score"] == s["user"]
