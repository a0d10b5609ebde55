"""The sharded implicit-ALS train cell (`ials-amazon14-r128-x4.train-sharded`)
rehearsed on the CPU's virtual devices at a tiny size, with the look for a
chip patched by the test: the plain reference against float64 NumPy normal
equations, the program against the reference, the result line, the faults
that `correct` has to catch, the `high` control that it has to fail, and
the readers that divide by four chips' peaks."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import cells, harness, run, tracereduce, work, work_ials
from perfbench.reference import ials_ref

ROOT = Path(__file__).resolve().parents[2]
CELL = "ials-amazon14-r128-x4.train-sharded"
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 4}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with the cell's own files at a size a test
    can hold (12,001 users and 6,002 items, 3.3 ratings a user as the
    source's table has 3.9: four chips divide neither);
    limits, driver, readers and reference are the committed ones."""
    root = tmp_path_factory.mktemp("tiny-ials")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "perfbench/configs/ials-amazon14-r128-x4.json"
    doc = json.loads(path.read_text())
    doc.update(n_users=12001, n_items=6002, n_ratings=40000, rank=8,
               user_max_ratings=400, item_max_ratings=1000,
               check=dict(doc["check"], user_rows=64, item_rows=16))
    path.write_text(json.dumps(doc))
    return root


def _run(tiny, seed=2**31 + 99, seconds=0.3, trace=False):
    return run.execute(cells.resolve(CELL, tiny), seed, seconds, trace,
                       CPU, tiny)


def _driver(tiny):
    return cells._load_module("drivers", "train_sweeps_sharded", tiny)


# -- the cell's files -------------------------------------------------------


def test_the_cell_resolves_to_its_files():
    cell = cells.resolve(CELL)
    assert cell.chips == 4 and cell.driver == "train_sweeps_sharded"
    assert callable(cells.load_driver(cell.driver))
    assert {m["name"] for m in cell.end_to_end} == {
        "train_ratings_per_s", "setup_s"}
    assert {m.name for m in cell.per_layer} == {
        "backend_init_s", "data_build_s", "warmup_s", "compiles_in_window",
        "als_user_half_s.x4", "als_item_half_s.x4", "als_exchange_s.x4",
        "als_sweep_roofline.x4", "train_mfu.x4", "device_idle_share.x4"}
    for m in cell.per_layer:
        assert callable(cells.load_reader(m.reader))
    cfg = cell.config
    # the source's whole shape, nothing reduced, and the sizes ISSUE 34 gives
    assert (cfg["n_users"], cfg["n_items"], cfg["n_ratings"], cfg["rank"]) \
        == (20_980_000, 9_350_000, 82_830_000, 128)
    assert cfg["implicit"] is True and cfg["chips"] == 4
    assert cfg["architecture"] is None and cfg["assumed"]
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "ials-amazon14-r128-x4")
    assert entry["reduced"] == []
    assert set(cfg["limits"]) == {"u_fro", "u_worst_row", "v_fro",
                                  "v_worst_row", "window_nonfinite"}


def test_the_reference_imports_nothing_of_the_program():
    source = (ROOT / "perfbench/reference/ials_ref.py").read_text()
    assert "predictionio_tpu" not in source.split('"""', 2)[2]


def test_degrees_give_every_row_a_rating_and_are_the_same_for_every_seed(
        tiny):
    drv = _driver(tiny)
    cfg = cells.resolve(CELL, tiny).config
    u1, i1, counts1 = drv.make_ratings(cfg, 1)
    u2, i2, counts2 = drv.make_ratings(cfg, 2**31 + 7)
    assert len(u1) == cfg["n_ratings"] == counts1.sum()
    assert counts1.min() >= 1 and counts1.max() <= cfg["user_max_ratings"]
    by_item = np.bincount(i1, minlength=cfg["n_items"])
    assert by_item.min() >= 1 and by_item.max() <= cfg["item_max_ratings"]
    np.testing.assert_array_equal(counts1, counts2)
    np.testing.assert_array_equal(np.sort(by_item),
                                  np.sort(np.bincount(i2)))
    assert not np.array_equal(i1, i2)
    # the swap is undone: the explicit cell's generator is what it was
    assert drv.base.capped_power_law.__name__ == "capped_power_law"


# -- the plain reference against float64 normal equations -------------------


def _normal_equations(table, ids, vals, starts, counts, lam, alpha, weighted):
    table = table.astype(np.float64)
    yty = table.T @ table
    out = []
    for s, n in zip(starts, counts):
        y = table[ids[s:s + n]]
        c = 1.0 + alpha * vals[s:s + n].astype(np.float64)
        a = yty + (y * (c - 1.0)[:, None]).T @ y
        a += (lam * max(n, 1) if weighted else lam) * np.eye(table.shape[1])
        out.append(np.linalg.solve(a, (y * c[:, None]).sum(axis=0)))
    return np.asarray(out)


@pytest.mark.parametrize("weighted", [True, False])
def test_reference_matches_float64_normal_equations(weighted, monkeypatch):
    # small blocks, so that rows are cut into several and a wide row is
    # summed over chunks
    monkeypatch.setattr(ials_ref, "ENTRIES_PER_BLOCK", 256)
    monkeypatch.setattr(ials_ref, "ROWS_PER_BLOCK", 16)
    monkeypatch.setattr(ials_ref, "TABLE_BLOCK_ROWS", 100)
    monkeypatch.setattr(ials_ref, "GRAM_ROWS", 32)
    rng = np.random.default_rng(4)
    table = (rng.normal(size=(700, 12)) / np.sqrt(12)).astype(np.float32)
    counts = np.concatenate([rng.integers(1, 9, size=50), [300, 513, 40]])
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    ids = rng.integers(0, 700, size=counts.sum())
    vals = rng.integers(1, 4, size=counts.sum()).astype(np.float32)
    want = _normal_equations(table, ids, vals, starts, counts, 0.05, 1.5,
                             weighted)
    yty = ials_ref.gram([table[:450], table[450:]])
    np.testing.assert_allclose(
        yty, table.astype(np.float64).T @ table.astype(np.float64),
        rtol=1e-6, atol=1e-5)
    got = ials_ref.solve_rows(yty, table[ids], vals, starts, counts, 0.05,
                              1.5, weighted=weighted)
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-6
    low = ials_ref.solve_rows(ials_ref.gram([table], "high"), table[ids],
                              vals, starts, counts, 0.05, 1.5,
                              weighted=weighted, precision="high")
    err = np.abs(low - want).max() / np.abs(want).max()
    assert 2e-6 < err < 1e-3        # the control is lower, not broken


# -- the result line --------------------------------------------------------


def test_result_line_program_against_reference(tiny):
    r = _run(tiny)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"train_ratings_per_s", "setup_s"}
    assert {c["name"] for c in r["compared"]} == {
        "u_fro", "u_worst_row", "v_fro", "v_worst_row", "window_nonfinite"}
    for c in r["compared"]:
        assert c["value"] <= c["limit"]
    staged = r["info"]["staged"]
    assert staged["exchange_bytes"]["user"] > 0
    assert r["info"]["sampled_rows"] == {"user": 66, "item": 18}
    json.dumps(r)


@pytest.fixture()
def fake_trace(monkeypatch):
    """The CPU has no device plane: a traced rehearsal reads a made-up
    reduction of four chips, with the exchange's operations in it under
    the names the TPU's compiler gives them."""
    summary = tracereduce.TraceSummary(
        window_ns=1_000_000_000, busy_ns=600_000_000.0, n_devices=4,
        ops=[("%fusion = fusion", 1_600_000_000, 40),
             ("%all-gather.3 = s32[4,8]{1,0} all-gather(%x)", 160_000_000,
              40),
             ("%fusion.286 = f32[8208,8,128]{2,1,0} fusion(%m), kind=kCustom,"
              " calls=%all-reduce-scatter.clone", 320_000_000, 40)],
        gaps=[(0, 400_000_000, tracereduce.NO_HOST_SPAN)],
    )
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: summary)


def test_traced_result_line(tiny, fake_trace):
    r = _run(tiny, trace=True)
    cell = cells.resolve(CELL, tiny)
    assert set(r["metrics"]) == {m.name for m in cell.per_layer}
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    # (0.16 + 0.32) s over four chips and one traced sweep
    assert r["metrics"]["als_exchange_s.x4"]["value"] == pytest.approx(0.12)
    assert r["metrics"]["device_idle_share.x4"]["value"] == pytest.approx(40)
    halves = (r["metrics"]["als_user_half_s.x4"]["value"]
              + r["metrics"]["als_item_half_s.x4"]["value"])
    assert 0 < halves <= np.mean(r["info"]["sweep_s"]) * 1.05
    for key in ("als_sweep_roofline.x4", "train_mfu.x4"):
        assert 0 < r["metrics"][key]["value"] <= 100, key
    assert r["correct"] is True
    json.dumps(r)


# -- faults planted under the timed path: `correct` has to come out false ---


def _values(r):
    return {c["name"]: c["value"] for c in r["compared"]}


def _wrap_solve_buckets(monkeypatch, **replace):
    from predictionio_tpu.models import als

    real = als._solve_buckets

    def faulty(upd_write, opp, bucket_args, lam, alpha, **kw):
        if "gram" in replace and kw.get("gram") is not None:
            kw["gram"] = kw["gram"] * 0.0
        if "alpha" in replace:
            alpha = alpha * 0.0
        return real(upd_write, opp, bucket_args, lam, alpha, **kw)

    monkeypatch.setattr(als, "_solve_buckets", faulty)


def test_fault_the_yty_term_dropped(tiny, monkeypatch):
    _wrap_solve_buckets(monkeypatch, gram=True)
    r = _run(tiny)
    assert r["correct"] is False
    assert _values(r)["u_fro"] > 0.5


def test_fault_alpha_ignored(tiny, monkeypatch):
    _wrap_solve_buckets(monkeypatch, alpha=True)
    r = _run(tiny)
    assert r["correct"] is False
    assert _values(r)["u_fro"] > 0.1


def test_fault_one_shards_rows_left_out_of_the_exchange(tiny, monkeypatch):
    import jax
    from predictionio_tpu.parallel.collectives import ShardedRows

    real = ShardedRows.spread

    def spread(self, idx, valid):
        local, mine = real(self, idx, valid)
        return local, mine & (jax.lax.axis_index(self.axis) != 1)

    monkeypatch.setattr(ShardedRows, "spread", spread)
    r = _run(tiny)
    assert r["correct"] is False
    assert _values(r)["u_worst_row"] > 0.1


def test_fault_a_half_that_returns_its_state_unchanged(tiny, monkeypatch):
    from predictionio_tpu.models.als import ALSTrainer

    real = ALSTrainer._half

    def half(self, upd, opp, side, lam=None):
        if side is self._item_side:
            return upd
        return real(self, upd, opp, side, lam=lam)

    monkeypatch.setattr(ALSTrainer, "_half", half)
    r = _run(tiny)
    assert r["correct"] is False
    values = _values(r)
    assert values["v_fro"] > 0.5 and values["u_fro"] < 1e-5


# -- the control: the reference at the precision below, in the program's place


@pytest.mark.parametrize("seed", [3, 2**31 + 4])
def test_control_three_pass_contractions_fail_the_limits(tiny, seed):
    import jax
    from predictionio_tpu.parallel.mesh import make_mesh

    drv = _driver(tiny)
    cfg = cells.resolve(CELL, tiny).config
    u, i, counts_u = drv.make_ratings(cfg, seed)
    sample = drv.sample_entries(cfg, seed, u, i, counts_u)
    mesh = make_mesh(4)
    U0, V0 = drv.init_tables(cfg, seed, mesh)
    assert len(U0.addressable_shards) == 4
    assert U0.shape == (12004, 8) and not np.asarray(U0)[12001:].any()
    both = ("highest", "high")
    inputs = {"user": drv.reference_inputs(V0, sample["user"], both)}
    ref_u = drv.reference_rows(cfg, sample["user"], inputs["user"])
    # the control's user rows stand in the program's first-sweep table
    # for the item half's inputs too
    low_u = drv.reference_rows(cfg, sample["user"], inputs["user"], "high")
    U1 = jax.device_put(
        np.asarray(U0).copy(), U0.sharding)
    inputs["item"] = drv.reference_inputs(U1, sample["item"], both)
    captured = {"inputs": inputs,
                "got": {"user": low_u,
                        "item": drv.reference_rows(
                            cfg, sample["item"], inputs["item"], "high")}}
    numbers = drv.compare_first_sweep(cfg, sample, captured)
    numbers["window_nonfinite"] = 0.0
    correct, compared = harness.judge(numbers, cfg["limits"])
    assert correct is False, compared
    same = dict(captured, got={"user": ref_u, "item": drv.reference_rows(
        cfg, sample["item"], inputs["item"])})
    assert all(x == 0 for x in
               drv.compare_first_sweep(cfg, sample, same).values())


def test_fetch_rows_reads_each_row_where_it_lies(tiny):
    from predictionio_tpu.parallel.mesh import make_mesh

    drv = _driver(tiny)
    cfg = cells.resolve(CELL, tiny).config
    U0, _ = drv.init_tables(cfg, 5, make_mesh(4))
    ids = np.array([3000, 0, 750, 751, 2999, 12, 1502])
    np.testing.assert_array_equal(drv.fetch_rows(U0, ids),
                                  np.asarray(U0)[ids])
    assert sum(b.shape[0] for b in drv.table_blocks(U0)) == 12004


# -- the readers on a hand-made run -----------------------------------------

SHAPE = {"nnz": 82_830_000, "n_users": 20_980_000, "n_items": 9_350_000,
         "rank": 128}
PEAKS = work.peaks_for("TPU v5 lite")


def _hand_run(busy_s, n_devices=4, ops=(), **extra):
    trace = tracereduce.TraceSummary(
        window_ns=int(20e9), busy_ns=busy_s * 1e9, n_devices=n_devices,
        ops=list(ops))
    return dict({"trace": trace, "traced_sweeps": 1, "sweeps": 3,
                 "window_s": 3 * busy_s, "shape": SHAPE, "peaks": PEAKS,
                 "chips": 4}, **extra)


def test_work_of_an_implicit_sweep():
    dims = tuple(SHAPE.values())
    extra = 2.0 * (20_980_000 + 9_350_000) * 128 * 128
    assert work_ials.ials_sweep_flops(*dims) == pytest.approx(
        work.als_sweep_flops(*dims) + extra)
    assert work_ials.ials_sweep_hbm_bytes(*dims) == work.als_sweep_bytes(
        *dims)
    assert work_ials.ials_sweep_ici_bytes(20_980_000, 9_350_000, 128, 4) \
        == (20_980_000 + 9_350_000) * 512
    assert work_ials.ials_sweep_ici_bytes(20_980_000, 9_350_000, 128, 1) == 0
    four = work_ials.host_peaks(PEAKS, 4)
    assert four["flops_per_s"] == 4 * PEAKS["flops_per_s"]
    least4, _ = work_ials.least_seconds(SHAPE, PEAKS, 4)
    least1, _ = work_ials.least_seconds(SHAPE, PEAKS, 1)
    assert least4 == pytest.approx(least1 / 4)


def test_a_four_chip_roofline_cannot_read_over_100():
    """Four chips busy for exactly the least time four chips need read
    100 %; the one-chip readers would read the same run at 400 %, the
    impossible reading the new readers exist to avoid."""
    roofline = cells.load_reader("ials_sweep_roofline")
    mfu = cells.load_reader("ials_train_mfu")
    least4, _ = work_ials.least_seconds(SHAPE, PEAKS, 4)
    at_the_roof = _hand_run(least4)
    assert roofline(at_the_roof, {}) == pytest.approx(100.0)
    assert mfu(at_the_roof, {}) <= 100.0
    assert roofline(_hand_run(10.0), {}) < 1.0
    one_chip = cells.load_reader("als_sweep_roofline")(at_the_roof, {})
    assert one_chip > 300.0


def test_readers_return_none_where_there_is_nothing_to_read():
    roofline = cells.load_reader("ials_sweep_roofline")
    mfu = cells.load_reader("ials_train_mfu")
    exchange = cells.load_reader("als_exchange_s")
    bare = {"shape": SHAPE, "peaks": PEAKS}
    for reader in (roofline, mfu, exchange):
        assert reader(dict(bare), {}) is None
    no_chips = _hand_run(5.0)
    del no_chips["chips"]
    assert roofline(no_chips, {}) is None and mfu(no_chips, {}) is None
    # a program that exchanges nothing, as the parent's on one chip
    assert exchange(_hand_run(5.0, ops=[("%fusion = fusion", 10**9, 3)]),
                    {}) is None
    ops = [("%all-reduce.70 = f32[32832,8,128] all-reduce(%pad)", 4 * 10**9,
            8),
           ("%collective-permute-start.1 = collective-permute-start(%x)",
            2 * 10**9, 8),
           ("%reduce-scatter.4 = f32[8,128] reduce-scatter(%y)", 10**9, 4),
           ("%fusion.3 = fusion(%z), calls=%all-reduce-scatter.2", 10**9, 4),
           ("%fusion.9 = fusion(%w), calls=%fused_computation", 7 * 10**9, 4)]
    assert exchange(_hand_run(5.0, ops=ops), {}) == pytest.approx(2.0)


def test_a_program_without_the_bounded_exchange_exits_2_at_once(
        tiny, monkeypatch, capsys):
    """The parent of the PR that added the cell: its sharded half gathers
    the whole opposite table; the driver says so and exits 2 before it
    makes a rating."""
    from predictionio_tpu.parallel import collectives

    monkeypatch.delattr(collectives, "ShardedRows")
    with pytest.raises(SystemExit) as e:
        _run(tiny)
    assert e.value.code == 2
    assert "cannot run this cell" in capsys.readouterr().err
