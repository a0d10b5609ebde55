"""The `ecommercerecommendation` cell with `unseenOnly` rehearsed on the CPU
at a tiny size, with the look for a chip patched by the test itself: the new
driver end to end over its live event store, the result line's keys, the
faults that `correct` has to catch (seen items ignored, a history cached
from one request to the next, a failing store, no answer to a query asked
again after the window), the fp8 control, the plain
reference against a brute-force numpy answer and its own read of the store,
name-to-files resolution of the new cell and of each new metric file, the
new work count and readers on hand-made runs, and the check with which the
driver refuses a program that cannot keep the pool's longest list on the
device."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import (cells, harness, run, serve_unseen, tracereduce, work,
                       work_unseen)

ROOT = Path(__file__).resolve().parents[2]
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
CELL = "ecomm-amazon14-r128.serve-unseen-steady"
CONFIG = "perfbench/configs/ecomm-amazon14-r128.json"
TRAFFIC = "perfbench/traffic/serve-unseen-steady.json"
TWINS = {
    "filter_build_ms", "filtered_path_share", "gen_late_p95_ms",
    "batcher_wait_ms", "batcher_mean_batch", "scorer_device_ms",
    "turn_prepare_ms", "turn_fetch_ms", "turn_complete_ms",
    "device_idle_share", "serve_mfu", "loop_busy_share",
    "host_cpu_us_per_request", "edge_host_ms", "loop_offcpu_share",
    "gc_pause_ms_per_s",
}
OWN = {"seen_read_ms", "exclude_width_mean", "exclude_device_ms",
       "filtered_scorer_roofline"}
NEW_METRICS = {f"{name}.unseen" for name in TWINS | OWN}
LIMITS = {"rank_gap", "score_err", "answers_with_repeats",
          "answers_with_excluded", "answers_filter_blind", "answers_stale",
          "seen_read_failures"}


# the listed ids' own blocks gathered, as the chip's trace names the op
LISTED_GATHER = ("%fusion = f32[131072,128]{1,0:T(8,128)S(1)} fusion(f32["
                 "9350000,128]{1,0:T(8,128)} %table_t_packed.1, s32[131072]"
                 "{0:T(1024)S(1)} %broadcast_clamp_fusion), kind=kCustom")


def _edit(path: Path, **changes) -> None:
    doc = json.loads(path.read_text())
    doc.update(changes)
    path.write_text(json.dumps(doc))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with the cell's files at sizes a test can
    hold: a catalogue long enough for the blocked path at every rung, a
    table of events whose heaviest users take the wide rungs."""
    root = tmp_path_factory.mktemp("tiny-unseen")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _edit(root / CONFIG, n_users=3000, n_items=30000, n_events=60000,
          microbatch_max=8, check={"answers": 16, "longest": 4})
    _edit(root / TRAFFIC, connections=16, rate_per_s=100, query_pool=64,
          trace_after_s=0.1, trace_seconds=0.8)
    return root


def _run(tiny, seed=2**31 + 29, seconds=1.2, trace=False):
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
             (LISTED_GATHER, 50_000_000, 10),
             ("%fusion = fusion", 100_000_000, 10)],
        gaps=[(0, 400_000_000, tracereduce.NO_HOST_SPAN)],
    )
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: summary)


# -- the cell and its files ---------------------------------------------------


def test_the_cell_resolves_to_its_files():
    cell = cells.resolve(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "ecomm-amazon14-r128", "serve-unseen-steady", 1)
    assert cell.driver == "http_unseen"
    assert callable(cells.load_driver(cell.driver))
    assert {m["name"] for m in cell.end_to_end} == {"serve_p95_ms", "setup_s"}
    cfg = cell.config
    assert (cfg["n_users"], cfg["n_items"], cfg["n_events"], cfg["rank"]) == (
        20_980_000, 9_350_000, 82_830_000, 128)
    assert cfg["microbatch_max"] == 64 and cfg["retrieval"] == "exact"
    assert cfg["unseenOnly"] is True
    assert cfg["seenEvents"] == ["buy", "view"]
    assert cfg["architecture"] is None
    assert cfg["check"]["answers"] == 48 and cfg["check"]["longest"] == 8
    assert set(cfg["limits"]) == LIMITS
    assert (cfg["limits"]["rank_gap"], cfg["limits"]["score_err"]) == (
        0.04, 0.04)
    assert cfg["limits"]["answers_filter_blind"] == 0.5
    t = cell.traffic
    assert t["mode"] == "open" and t["connections"] == 256 and t["num"] == 10
    assert t["rate_per_s"] % 10 == 0 and t["rate_per_s"] > 0
    assert t["query_pool"] == 32768 and t["history_max"] == 4096
    from predictionio_tpu.ops.topk import EXCLUDE_LADDER

    assert t["history_max"] + cfg["unavailable_items"] <= EXCLUDE_LADDER[-1], \
        "every query's excluded ids ride as ids"
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    # by name, not by place: a later PR appends after these
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "ecomm-amazon14-r128")
    assert entry["reduced"] == ["training", "event_store"]
    assert sum(w["name"] == CELL for w in manifest["workloads"]) == 1
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
    for was, now in zip(old["end_to_end"], new["end_to_end"]):
        if was["name"] == "serve_p95_ms":
            assert now == dict(was, workloads=was["workloads"] + [CELL])
        else:
            assert now == was


# -- the data: degrees, pool, walk, tables -------------------------------------


def test_the_pools_histories_are_the_configurations():
    """At the published sizes the degrees are `ials-amazon14-r128-x4`'s
    and the query's user is drawn by its count of events: the lists a
    query carries are what ISSUE 40 reckoned."""
    cell = cells.resolve(CELL)
    cfg, traffic = cell.config, cell.traffic
    counts = serve_unseen.degrees(cfg["n_users"], cfg["user_exponent"],
                                  cfg["n_events"], cfg["user_max_events"])
    assert counts.sum() == cfg["n_events"] and counts.min() >= 1
    assert counts.max() == cfg["user_max_events"]
    assert (counts > traffic["history_max"]).sum() == 122
    pool = serve_unseen.make_pool(traffic, counts)
    lengths = counts[pool]
    assert len(pool) == 32768 and lengths.max() <= 4096
    assert 55 < lengths.mean() < 72 and np.median(lengths) in (3, 4, 5)
    assert 1000 < np.percentile(lengths, 99) < 2200
    assert 1_400_000 < counts[np.unique(pool)].sum() < 1_900_000


def test_histories_follow_the_walk_of_make_ratings():
    from perfbench.drivers import train_sweeps_sharded

    cfg = {"n_users": 500, "n_items": 800, "n_events": 4000,
           "n_ratings": 4000, "user_exponent": 0.7, "item_exponent": 1.0,
           "user_max_events": 400, "item_max_events": 300,
           "user_max_ratings": 400, "item_max_ratings": 300}
    u, i, counts_u = train_sweeps_sharded.make_ratings(cfg, 12345)
    users = np.array([0, 3, 77, 499])
    offsets, items = serve_unseen.histories(
        cfg, 12345, serve_unseen.degrees(500, 0.7, 4000, 400), users)
    for j, user in enumerate(users):
        np.testing.assert_array_equal(items[offsets[j]:offsets[j + 1]],
                                      i[u == user])


def test_user_rows_lie_near_the_users_recent_items():
    cfg = {"n_users": 50, "rank": 16, "recent_items": 3}
    items = np.eye(16, dtype=np.float32)[np.arange(40) % 16]
    users = np.array([2, 9])
    offsets = np.array([0, 1, 6])
    hist = np.array([5, 1, 2, 3, 4, 7], np.int32)
    table = serve_unseen.make_user_table(cfg, 7, items, users, offsets, hist)
    assert table.shape == (50, 16) and table.dtype == np.float32
    np.testing.assert_allclose(table[2], items[5])
    np.testing.assert_allclose(
        table[9], items[7] + 0.5 * items[4] + 0.25 * items[3])
    others = np.delete(table, users, axis=0)
    assert abs(others.std() - 0.25) < 0.02 and abs(others.mean()) < 0.02
    again = serve_unseen.make_user_table(cfg, 7, items, users, offsets, hist)
    np.testing.assert_array_equal(table, again)


def test_send_order_places_the_longest_among_the_first():
    pool = np.arange(1000) * 3
    longest = np.array([999, 500, 7])
    for seed in (1, 2**31 + 5):
        order = serve_unseen.send_order(pool, longest, seed, 20)
        assert sorted(order.tolist()) == list(range(1000))
        assert set(longest.tolist()) <= set(order[:20].tolist())
    assert (serve_unseen.send_order(pool, longest, 1, 20)
            != serve_unseen.send_order(pool, longest, 2, 20)).any()


def test_choose_sample_holds_the_longest_that_were_answered():
    kept = [{"user": j % 50, "body": "{}"} for j in range(200)]
    sample = serve_unseen.choose_sample(kept, {3, 7, 999}, 16, 5)
    assert len(sample) == 16
    assert {3, 7} <= {s["user"] for s in sample}
    assert serve_unseen.choose_sample(kept[:5], {3}, 16, 5) == kept[:5]
    assert serve_unseen.choose_sample([], {3}, 16, 5) == []


# -- the work count and the new readers ----------------------------------------


def test_work_count_leaves_the_exclusions_out():
    assert work_unseen.unseen_batch_flops(2, 1000, 8) == 2 * 2 * 1000 * 8
    # table 1000*8*4, queries 2*8*4, results 2*16*(4+4)
    assert work_unseen.unseen_batch_bytes(2, 1000, 8, 16) == 32000 + 64 + 256
    t, bound = work.least_seconds(
        work_unseen.unseen_batch_flops(64, 9_350_000, 128),
        work_unseen.unseen_batch_bytes(64, 9_350_000, 128, 16),
        work.peaks_for("TPU v5 lite"))
    assert bound == "bytes"
    assert t == pytest.approx(4.7872e9 / 819e9, rel=1e-3)


def test_new_readers_on_hand_made_runs():
    seen = cells.load_reader("seen_read_ms")
    assert seen({"seen_read": (0.25, 500)}, {}) == pytest.approx(0.5)
    assert seen({"seen_read": (0.0, 0)}, {}) is None
    width = cells.load_reader("exclude_width_mean")
    assert width({"exclude_width_batches": {"128": 3.0, "512": 1.0}},
                 {}) == 224.0
    assert width({"exclude_width_batches": {"128": 0.0}}, {}) is None
    summary = tracereduce.TraceSummary(
        window_ns=10**9, busy_ns=8e8, n_devices=1,
        ops=[("%pio_block_max = custom-call", 6 * 10**8, 100),
             ("%custom-call = custom-call:TopK", 1 * 10**8, 100),
             (LISTED_GATHER, 5 * 10**7, 100),
             ("%fusion = fusion", 5 * 10**7, 100)])
    per_batch = cells.load_reader("op_ms_per_batch")
    assert per_batch({"trace": summary},
                     {"per_batch_op": "TopK", "pattern": r"^%fusion = f32\[131072"}) == \
        pytest.approx(0.5)
    assert per_batch({"trace": summary},
                     {"per_batch_op": "TopK", "pattern": "no_such"}) is None
    assert per_batch({"trace": summary},
                     {"per_batch_op": "no_such", "pattern": "fusion"}) is None
    roof = cells.load_reader("unseen_scorer_roofline")
    run_ = {"trace": summary, "traced_batch_spans": [(0.0, 0.01, 8)] * 100,
            "peaks": work.peaks_for("TPU v5 lite"),
            "shape": {"n_items": 9_350_000, "rank": 128, "k": 16}}
    # 8 ms of device time a batch against 5.85 ms for the table's bytes
    assert roof(run_, {"per_batch_op": "TopK"}) == pytest.approx(
        100 * 5.845 / 8.0, rel=1e-3)
    assert roof(run_, {"per_batch_op": "no_such_kernel"}) is None
    assert roof(dict(run_, shape=None), {"per_batch_op": "TopK"}) is None
    metric = json.loads((ROOT / "perfbench/metrics/exclude_device_ms.unseen"
                         ".json").read_text())
    assert metric["reader"] == "op_ms_per_batch"


# -- the plain reference --------------------------------------------------------


def _store_with(events):
    from predictionio_tpu.storage.levents import MemoryEventStore

    store = MemoryEventStore()
    store.init_channel(serve_unseen.APP_ID)
    store.insert_batch(events, serve_unseen.APP_ID)
    return store


def test_reference_reads_the_store_itself_and_filters_by_id():
    import jax.numpy as jnp

    from perfbench.reference import ecomm_ref
    from predictionio_tpu.storage import DataMap, Event

    def buy(user, item, name="buy"):
        return Event(event=name, entity_type="user", entity_id=user,
                     target_entity_type="item", target_entity_id=item)

    def unavailable(items, second):
        import datetime as dt

        return Event(
            event="$set", entity_type="constraint",
            entity_id="unavailableItems", properties=DataMap({"items": items}),
            event_time=dt.datetime(2020, 1, 1, 0, 0, second,
                                   tzinfo=dt.timezone.utc))

    store = _store_with([
        buy("u1", "i3"), buy("u1", "i4", "view"), buy("u1", "i9", "rate"),
        buy("u2", "i5"), unavailable(["i1"], 1), unavailable(["i7", "i8"], 3),
        unavailable(["i2"], 2),
        Event(event="$set", entity_type="constraint", entity_id="other",
              properties=DataMap({"items": ["i0"]}))])
    seen, gone = ecomm_ref.read_store(store, serve_unseen.APP_ID,
                                      ["u1", "u3"], ["buy", "view"])
    assert seen == {"u1": {"i3", "i4"}, "u3": set()}
    assert gone == ["i7", "i8"], "the latest $set alone"
    rng = np.random.default_rng(0)
    table = rng.normal(size=(3000, 16)).astype(np.float32)
    rows = rng.normal(size=(3, 16)).astype(np.float32)
    scores = table @ rows.T
    excluded = [set(np.argsort(-scores[:, 0])[:5].tolist()), set(), {7}]
    items, vals, blind = ecomm_ref.answer(rows, jnp.asarray(table), excluded,
                                          10)
    for q in range(3):
        allowed = np.where(np.isin(np.arange(3000), list(excluded[q])),
                           -np.inf, scores[:, q])
        order = np.argsort(-allowed, kind="stable")[:10]
        np.testing.assert_array_equal(items[q], order)
        np.testing.assert_allclose(vals[q], allowed[order], rtol=1e-5)
        np.testing.assert_array_equal(
            blind[q], np.argsort(-scores[:, q], kind="stable")[:10])
    served = [items[q].tolist() for q in range(3)]
    out = ecomm_ref.compare(rows, jnp.asarray(table), excluded, served,
                            [vals[q].tolist() for q in range(3)], 10)
    assert out["rank_gap"] <= 1e-6 and out["score_err"] <= 1e-6
    assert out["answers_with_excluded"] == 0 == out["answers_with_repeats"]
    # the first query's filter changed its answer; the other two's did not
    assert out["answers_filter_blind"] == pytest.approx(2 / 3)
    served[0][2] = min(excluded[0])
    served[1][4] = served[1][0]
    out = ecomm_ref.compare(rows, jnp.asarray(table), excluded, served,
                            [vals[q].tolist() for q in range(3)], 10)
    assert out["answers_with_excluded"] == 1 == out["answers_with_repeats"]


def test_reference_imports_nothing_of_the_scorer_or_the_filters():
    text = (ROOT / "perfbench/reference/ecomm_ref.py").read_text()
    assert "predictionio_tpu" not in text.replace(
        "nothing of `ops/topk.py`", "")
    assert 'default_matmul_precision("highest")' in text
    assert "stable=True" in text


# -- the driver, end to end ---------------------------------------------------


def test_result_line_of_the_unseen_driver(tiny):
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
    assert numbers["answers_filter_blind"] <= 0.5
    assert numbers["answers_stale"] == 0 == numbers["seen_read_failures"]
    rows = r["info"]["rows_by_filter_in_window"]
    assert rows["ids"] > 0 and sum(rows.values()) == rows["ids"], \
        "every batch's lists ride as ids"
    widths = r["info"]["batches_by_exclude_width_in_window"]
    assert max(int(w) for w, n in widths.items() if n) > 128, \
        "the pool's long histories take the wide rungs"
    assert r["info"]["seen_events_read_in_window"] > 0
    assert r["info"]["excluded_ids_in_window"] > \
        r["info"]["seen_events_read_in_window"] * 0.9
    assert r["info"]["longest_sampled_list"] > 512, \
        "the sample holds the pool's longest histories"
    paths = r["info"]["calls_by_path_in_window"]
    assert sum(paths.values()) == paths["blocked_ids"] > 0
    json.dumps(r)


def test_traced_result_line_of_the_unseen_driver(tiny, fake_trace,
                                                 monkeypatch):
    pattern = json.loads((ROOT / "perfbench/metrics/exclude_device_ms.unseen"
                          ".json").read_text())["args"]["pattern"]
    import re

    assert re.search(pattern, LISTED_GATHER)
    for other in ("%pio_block_max.1 = f32[16,1169408]{1,0:T(8,128)} custom-"
                  "call(f32[16,128]{1,0} %q, f32[9350000,128]{1,0} %t)",
                  "%custom-call = (f32[16,16]{1,0}, s32[16,16]{1,0}) custom-"
                  "call(f32[16,9136]{1,0} %r), custom_call_target=\"TopK\"",
                  "%fusion.1 = f32[4096,128]{1,0:T(8,128)S(1)} fusion(f32["
                  "9350000,128]{1,0:T(8,128)} %table_t_packed.1, s32[4096])",
                  "%while.3 = (s32[], f32[16,256]{1,0}, s32[16,256]{1,0})"):
        assert not re.search(pattern, other), other
    # a window long enough that the profiler, slow to start on a loaded
    # machine, still opens while batches run
    r = _run(tiny, trace=True, seconds=3.0)
    cell = cells.resolve(CELL, tiny)
    wanted = {m.name for m in cell.per_layer}
    assert NEW_METRICS <= wanted
    assert set(r["metrics"]) == wanted, wanted ^ set(r["metrics"])
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert r["metrics"]["filtered_path_share.unseen"]["value"] == 100.0
    assert r["metrics"]["seen_read_ms.unseen"]["value"] > 0
    assert r["metrics"]["filter_build_ms.unseen"]["value"] > 0
    assert r["metrics"]["exclude_width_mean.unseen"]["value"] >= 128
    assert r["metrics"]["exclude_device_ms.unseen"]["value"] > 0
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


@pytest.mark.parametrize("lack", ["a_short_ladder", "a_masking_engine"])
def test_the_parents_program_fails_the_cell_at_once(tiny, monkeypatch, lack):
    """Laid over a program whose ladder ends at 32 ids, or whose engine
    still masks on the host, the driver exits 2 before it builds
    anything."""
    from predictionio_tpu.ops import topk
    from predictionio_tpu.templates import ecommerce

    if lack == "a_short_ladder":
        monkeypatch.setattr(topk, "EXCLUDE_LADDER", (32,))
    else:
        monkeypatch.delattr(ecommerce, "batch_filter")
    monkeypatch.setattr(serve_unseen, "degrees", lambda *a: 1 / 0)
    with pytest.raises(SystemExit) as exit_:
        _run(tiny)
    assert exit_.value.code == 2


# -- faults planted under the timed path: `correct` has to come out false ----


def test_fault_seen_items_ignored(tiny, monkeypatch):
    from predictionio_tpu.templates.ecommerce import ECommAlgorithm

    monkeypatch.setattr(ECommAlgorithm, "_seen_items",
                        lambda self, model, users: [[] for _ in users])
    r = _run(tiny)
    assert r["correct"] is False and r["failed"] == 0
    assert _numbers(r)["answers_with_excluded"] > 0
    assert _numbers(r)["answers_stale"] > 0


def test_fault_a_history_cached_from_one_request_to_the_next(tiny,
                                                             monkeypatch):
    """The window's answers are right, for nothing was written during it;
    the `buy` after the window is what the cache misses."""
    from predictionio_tpu.templates.ecommerce import ECommAlgorithm

    real = ECommAlgorithm._seen_items
    cache = {}

    def cached(self, model, users):
        fresh = [u for u in users if u not in cache]
        cache.update(zip(fresh, real(self, model, fresh)))
        return [cache[u] for u in users]

    monkeypatch.setattr(ECommAlgorithm, "_seen_items", cached)
    r = _run(tiny)
    numbers = _numbers(r)
    assert numbers["answers_with_excluded"] == 0
    assert numbers["answers_stale"] > 0
    assert r["correct"] is False and r["failed"] == 0


def test_fault_no_answer_to_a_query_asked_again(tiny, monkeypatch):
    """A program that answers the queries after the window with nothing
    shows nothing of the item: that is not "gone"."""
    from predictionio_tpu.templates.ecommerce import (ECommAlgorithm,
                                                      PredictedResult)

    real = serve_unseen.count_stale

    def after_the_window(*args):
        monkeypatch.setattr(
            ECommAlgorithm, "batch_predict", lambda self, model, queries: [
                PredictedResult(item_scores=()) for _ in queries])
        return real(*args)

    monkeypatch.setattr(serve_unseen, "count_stale", after_the_window)
    r = _run(tiny)
    numbers = _numbers(r)
    assert numbers["answers_with_excluded"] == 0 and r["failed"] == 0
    assert numbers["answers_stale"] > 0
    assert r["correct"] is False


def test_fault_the_store_fails_during_the_window(tiny, monkeypatch):
    from predictionio_tpu.storage.levents import MemoryEventStore

    real = MemoryEventStore.find_target_ids
    calls = []

    def flaky(self, *args, **kwargs):
        calls.append(1)
        if len(calls) % 5 == 0:
            raise TimeoutError("the store did not answer")
        return real(self, *args, **kwargs)

    monkeypatch.setattr(MemoryEventStore, "find_target_ids", flaky)
    r = _run(tiny)
    assert _numbers(r)["seen_read_failures"] > 0
    assert r["correct"] is False


def test_fault_answers_returned_to_the_wrong_request(tiny, monkeypatch):
    from predictionio_tpu.templates.ecommerce import ECommAlgorithm

    real = ECommAlgorithm.batch_predict

    def rotated(self, model, queries):
        out = real(self, model, queries)
        return out[1:] + out[:1]

    monkeypatch.setattr(ECommAlgorithm, "batch_predict", rotated)
    # a batch of one rotates onto itself: keep the server busy
    _edit(tiny / TRAFFIC, rate_per_s=400)
    try:
        r = _run(tiny)
    finally:
        _edit(tiny / TRAFFIC, rate_per_s=100)
    assert r["correct"] is False


# -- the control ----------------------------------------------------------------


@pytest.mark.parametrize("precision,correct", [("highest", True),
                                               ("fp8", False)])
def test_fp8_control_reads_not_correct(tiny, precision, correct):
    """The reference at the nearest precision below the stated one, put
    in the program's place, fails by `rank_gap` or `score_err`; at
    `highest` it passes against itself."""
    import jax.numpy as jnp

    from perfbench.reference import ecomm_ref

    cell = cells.resolve(CELL, tiny)
    rng = np.random.default_rng(3)
    table = np.array(serve_unseen.serve_similar.make_items(cell.config, 9))
    picks = rng.integers(0, len(table), (12, 3))
    rows = (table[picks] * np.array([1.0, 0.5, 0.25],
                                    np.float32)[None, :, None]).sum(axis=1)
    excluded = [set(p.tolist()) for p in picks]
    items, vals, _ = ecomm_ref.answer(rows, jnp.asarray(table), excluded, 10,
                                      precision)
    out = ecomm_ref.compare(rows, jnp.asarray(table), excluded,
                            [r.tolist() for r in items],
                            [v.tolist() for v in vals], 10)
    numbers = {name: out[name] for name in (
        "rank_gap", "score_err", "answers_with_repeats",
        "answers_with_excluded", "answers_filter_blind")}
    numbers.update(answers_stale=0.0, seen_read_failures=0.0)
    assert harness.judge(numbers, cell.config["limits"])[0] is correct
    if not correct:
        assert max(numbers["rank_gap"], numbers["score_err"]) > 0.04
        assert numbers["answers_with_excluded"] == 0
