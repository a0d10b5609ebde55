"""The block-sweep train cell (`ials-msd-d2048.train-subspace`) rehearsed on
the CPU at a cut size (rank 32, blocks of 8), with the look for a chip
patched by the test: the plain reference against a float64 NumPy walk of
the blocks, the program against the reference, the result line, the four
faults that `correct` has to catch, the `high` control that it has to
fail, and the readers of the new per-layer metrics."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from perfbench import cells, harness, run, tracereduce, work, work_subspace
from perfbench.reference import ials_subspace_ref as ref

ROOT = Path(__file__).resolve().parents[2]
CELL = "ials-msd-d2048.train-subspace"
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with the cell's own files at a size a test
    can hold (3,001 users x 601 songs, 20 songs a user as the source has
    59, rank 32 in blocks of 8); driver, readers, reference and the user
    side's limits are the committed ones.  The item side's are four
    times the committed: `YtY` of the cut table's first-sweep user table
    is worse conditioned than the full table's (eigenvalues 4.5 to 200
    at rank 32), the REFERENCE itself stands 8.7e-6 from a float64 walk
    of the blocks there and the program 2.7e-6, so `v_fro` reads 3e-6 to
    1.3e-5 on the CPU where the chip reads 1.7e-6 to 2.0e-6."""
    root = tmp_path_factory.mktemp("tiny-subspace")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = root / "perfbench/configs/ials-msd-d2048.json"
    doc = json.loads(path.read_text())
    doc.update(n_users=3001, n_items=601, n_ratings=60000, rank=32,
               subspace_size=8, user_min_ratings=5, item_min_ratings=20,
               user_max_ratings=300, item_max_ratings=2000,
               check=dict(doc["check"], user_rows=64, item_rows=16),
               limits=dict(doc["limits"],
                           v_fro=4 * doc["limits"]["v_fro"],
                           v_worst_row=4 * doc["limits"]["v_worst_row"]))
    path.write_text(json.dumps(doc))
    return root


@pytest.fixture()
def fresh_programs():
    """A planted fault lies inside a jitted half that an earlier test of
    this process may have compiled without it (and would leave behind
    for the next): the traces are dropped on both sides."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(tiny, seed=2**31 + 99, seconds=0.3, trace=False):
    return run.execute(cells.resolve(CELL, tiny), seed, seconds, trace,
                       CPU, tiny)


def _driver(tiny):
    return cells._load_module("drivers", "train_sweeps_subspace", tiny)


def _values(r):
    return {c["name"]: c["value"] for c in r["compared"]}


# -- the cell's files -------------------------------------------------------


def test_the_cell_resolves_to_its_files():
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.driver == "train_sweeps_subspace"
    assert callable(cells.load_driver(cell.driver))
    assert {m["name"] for m in cell.end_to_end} == {
        "train_ratings_per_s", "setup_s"}
    assert {m.name for m in cell.per_layer} == {
        "backend_init_s", "data_build_s", "warmup_s", "compiles_in_window",
        "als_user_half_s.sub", "als_item_half_s.sub",
        "als_block_solve_s.sub", "als_block_gram_s.sub",
        "als_solve_roofline.sub", "als_sweep_roofline.sub", "train_mfu.sub",
        "device_idle_share.sub"}
    for m in cell.per_layer:
        assert callable(cells.load_reader(m.reader))
    cfg = cell.config
    # the sources' whole shape, nothing reduced, the sizes ISSUE 36 gives
    assert (cfg["n_users"], cfg["n_items"], cfg["n_ratings"]) \
        == (571_355, 41_140, 33_633_450)
    assert (cfg["rank"], cfg["solver_mode"], cfg["subspace_size"]) \
        == (2048, "subspace", 128)
    assert cfg["implicit"] is True and cfg["chips"] == 1
    assert cfg["architecture"] is None and cfg["assumed"]
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "ials-msd-d2048")
    assert entry["reduced"] == []
    assert set(cfg["limits"]) == {"u_fro", "u_worst_row", "v_fro",
                                  "v_worst_row", "window_nonfinite"}


def test_the_reference_imports_nothing_of_the_program():
    source = (ROOT / "perfbench/reference/ials_subspace_ref.py").read_text()
    assert "predictionio_tpu" not in source.split('"""', 2)[2]


def test_the_trainer_is_the_templates_own_with_three_keys(tiny):
    """rank, solverMode and subspaceSize alone: every other field of the
    trainer's config is what `SimilarProductAlgorithm` gives a user who
    writes nothing else."""
    from predictionio_tpu.controller.base import instantiate
    from predictionio_tpu.templates.similarproduct import (
        SimilarALSParams, SimilarProductAlgorithm,
    )

    drv = _driver(tiny)
    cfg = cells.resolve(CELL, tiny).config
    u, i, _ = drv.make_ratings(cfg, 7)
    got = drv.build_trainer(cfg, u, i).cfg
    plain = instantiate(SimilarProductAlgorithm, SimilarALSParams())._config()
    differs = {k for k in vars(plain) if getattr(plain, k) != getattr(got, k)}
    assert differs == {"rank", "solver_mode", "subspace_size"}
    assert got.implicit and got.factor_placement == "replicated"


def test_degrees_keep_the_sources_floors_and_are_the_same_for_every_seed(
        tiny):
    drv = _driver(tiny)
    cfg = cells.resolve(CELL, tiny).config
    u1, i1, counts1 = drv.make_ratings(cfg, 1)
    u2, i2, counts2 = drv.make_ratings(cfg, 2**31 + 7)
    assert len(u1) == cfg["n_ratings"] == counts1.sum()
    assert cfg["user_min_ratings"] <= counts1.min()
    assert counts1.max() <= cfg["user_max_ratings"]
    by_item = np.bincount(i1, minlength=cfg["n_items"])
    assert cfg["item_min_ratings"] <= by_item.min()
    assert by_item.max() <= cfg["item_max_ratings"]
    np.testing.assert_array_equal(counts1, counts2)
    np.testing.assert_array_equal(np.sort(by_item),
                                  np.sort(np.bincount(i2)))
    assert not np.array_equal(i1, i2)
    # the swap is undone: the explicit cell's generator is what it was
    assert drv.base.capped_power_law.__name__ == "capped_power_law"


def test_the_full_size_degrees_are_the_ones_the_file_states():
    drv = cells._load_module("drivers", "train_sweeps_subspace", ROOT)
    cfg = cells.resolve(CELL).config
    floors = {cfg["n_users"]: 20, cfg["n_items"]: 200}
    with drv._degrees_with_floors(floors):
        users = drv.base.capped_power_law(
            cfg["n_users"], cfg["user_exponent"], cfg["n_ratings"],
            cfg["user_max_ratings"])
        songs = drv.base.capped_power_law(
            cfg["n_items"], cfg["item_exponent"], cfg["n_ratings"],
            cfg["item_max_ratings"])
    assert users.sum() == songs.sum() == 33_633_450
    assert (users.min(), users.max(), int(np.median(users))) == (32, 4400, 40)
    assert (songs.min(), songs.max(), int(np.median(songs))) \
        == (275, 110_000, 349)
    assert (songs == 110_000).sum() == 27


# -- the plain reference against a float64 walk of the blocks ---------------


def _float64_walk(table, ids, vals, starts, counts, x0, lam, alpha, block,
                  weighted):
    table = table.astype(np.float64)
    yty = table.T @ table
    out = []
    for s, n, x in zip(starts, counts, x0.astype(np.float64)):
        y = table[ids[s:s + n]]
        c = 1.0 + alpha * vals[s:s + n].astype(np.float64)
        a = yty + (y * (c - 1.0)[:, None]).T @ y
        a += (lam * max(n, 1) if weighted else lam) * np.eye(table.shape[1])
        b = (y * c[:, None]).sum(axis=0)
        x = x.copy()
        for lo in range(0, len(x), block):
            sl = slice(lo, lo + block)
            x[sl] -= np.linalg.solve(a[sl, sl], (a @ x - b)[sl])
        out.append(x)
    return np.asarray(out)


@pytest.mark.parametrize("weighted,block", [(True, 4), (False, 5)])
def test_reference_matches_a_float64_walk_of_the_blocks(weighted, block,
                                                        monkeypatch):
    # small batches, so that rows are cut into several batches and a
    # wide row is summed over chunks of its entries
    monkeypatch.setattr(ref, "ENTRIES_PER_BATCH", 256)
    monkeypatch.setattr(ref, "MIN_ENTRIES", 8)
    monkeypatch.setattr(ref, "ROWS_PER_BATCH", 16)
    monkeypatch.setattr(ref, "GRAM_ROWS", 32)
    monkeypatch.setattr(ref, "GRAMS_PER_CALL", 3)
    rng = np.random.default_rng(4)
    table = (rng.normal(size=(700, 12)) / np.sqrt(12)).astype(np.float32)
    counts = np.concatenate([rng.integers(1, 9, size=50), [300, 513, 40]])
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    ids = rng.integers(0, 700, size=counts.sum())
    vals = rng.integers(1, 4, size=counts.sum()).astype(np.float32)
    x0 = (rng.normal(size=(len(counts), 12)) / np.sqrt(12)).astype(np.float32)
    want = _float64_walk(table, ids, vals, starts, counts, x0, 0.05, 1.5,
                         block, weighted)
    yty = ref.gram([table[:450], table[450:]])
    np.testing.assert_allclose(
        yty, table.astype(np.float64).T @ table.astype(np.float64),
        rtol=1e-6, atol=1e-5)
    got = ref.sweep_rows(yty, table[ids], vals, starts, counts, x0, 0.05,
                         1.5, block, weighted=weighted)
    assert np.abs(got - want).max() / np.abs(want).max() < 2e-6
    # one sweep of the blocks is not the solution of the whole system
    solved = _float64_walk(table, ids, vals, starts, counts, x0, 0.05, 1.5,
                           12, weighted)
    assert np.abs(want - solved).max() / np.abs(solved).max() > 1e-3
    low = ref.sweep_rows(ref.gram([table], "high"), table[ids], vals, starts,
                         counts, x0, 0.05, 1.5, block, weighted=weighted,
                         precision="high")
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
    # a system a row a rank block, batch padding included
    assert staged["solve_systems"]["user"] >= 4 * 3001
    assert staged["solve_systems"]["user"] % 4 == 0
    assert staged["gather_bytes"]["item"] >= 60000 * 32 * 4
    assert r["info"]["sampled_rows"] == {"user": 66, "item": 18}
    json.dumps(r)


@pytest.fixture()
def fake_trace(monkeypatch):
    """The CPU has no device plane: a traced rehearsal reads a made-up
    reduction of one chip, with the solve kernel and the block Hessians
    in it under the names the TPU's compiler gives them."""
    summary = tracereduce.TraceSummary(
        window_ns=1_000_000_000, busy_ns=900_000_000.0, n_devices=1,
        ops=[("%fusion.7 = f32[262144,2048]{1,0} fusion(%p)", 300_000_000, 9),
             ('%_solve.3 = f32[128,8192]{1,0} custom-call(%a, %b), '
              'custom_call_target="tpu_custom_call"', 400_000_000, 160),
             ("%fusion.1172 = f32[4096,128,128]{2,1,0:T(8,128)} "
              "fusion(%x), kind=kOutput", 100_000_000, 160),
             ("%fusion.987 = f32[2,128,128]{2,1,0:T(8,128)S(1)} fusion(%w)",
              50_000_000, 160),
             ("%fusion.1171 = f32[128,128]{1,0:T(8,128)S(1)} fusion(%g)",
              20_000_000, 160),
             ("%fusion.1174 = f32[4096,1,128]{2,1,0} fusion(%y)", 50_000_000,
              160)],
        gaps=[(0, 100_000_000, tracereduce.NO_HOST_SPAN)],
    )
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: summary)


def test_traced_result_line(tiny, fake_trace):
    r = _run(tiny, trace=True)
    cell = cells.resolve(CELL, tiny)
    assert set(r["metrics"]) == {m.name for m in cell.per_layer}
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert r["metrics"]["als_block_solve_s.sub"]["value"] \
        == pytest.approx(0.4)
    assert r["metrics"]["als_block_gram_s.sub"]["value"] \
        == pytest.approx(0.15)
    assert r["metrics"]["device_idle_share.sub"]["value"] \
        == pytest.approx(10)
    halves = (r["metrics"]["als_user_half_s.sub"]["value"]
              + r["metrics"]["als_item_half_s.sub"]["value"])
    assert 0 < halves <= np.mean(r["info"]["sweep_s"]) * 1.05
    for key in ("als_solve_roofline.sub", "als_sweep_roofline.sub",
                "train_mfu.sub"):
        assert 0 < r["metrics"][key]["value"] <= 100, key
    assert r["correct"] is True
    json.dumps(r)


# -- faults planted under the timed path: `correct` has to come out false ---


def test_fault_one_block_skipped(tiny, monkeypatch, fresh_programs):
    import jax

    real = jax.lax.fori_loop

    def from_the_second_block(lower, upper, body, init):
        # the sweep's loop over the 4 whole blocks of rank 32
        if (lower, upper) == (0, 4):
            lower = 1
        return real(lower, upper, body, init)

    monkeypatch.setattr(jax.lax, "fori_loop", from_the_second_block)
    r = _run(tiny)
    assert r["correct"] is False
    assert _values(r)["u_fro"] > 0.1


def test_fault_the_q_cache_not_updated(tiny, monkeypatch, fresh_programs):
    import jax.numpy as jnp

    real = jnp.einsum

    def einsum(spec, *operands, **kw):
        out = real(spec, *operands, **kw)
        # q's advance by a block's step d [B, 8]; q itself starts from
        # the whole row [B, 32]
        if spec == "bs,sr->br" and operands[0].shape[-1] == 8:
            return out * 0.0
        return out

    monkeypatch.setattr(jnp, "einsum", einsum)
    r = _run(tiny)
    assert r["correct"] is False
    assert _values(r)["u_fro"] > 1e-3


def test_fault_the_yty_term_dropped(tiny, monkeypatch, fresh_programs):
    from predictionio_tpu.models import als

    real = als._table_gram
    monkeypatch.setattr(als, "_table_gram",
                        lambda table, prec: real(table, prec) * 0.0)
    r = _run(tiny)
    assert r["correct"] is False
    assert _values(r)["u_fro"] > 0.5


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
    assert values["v_fro"] > 0.1 and values["u_fro"] < 1e-5


# -- the control: the reference at the precision below, in the program's place


@pytest.mark.parametrize("seed", [3, 2**31 + 4])
def test_control_three_pass_contractions_fail_the_limits(tiny, seed):
    drv = _driver(tiny)
    cfg = cells.resolve(CELL, tiny).config
    u, i, counts_u = drv.make_ratings(cfg, seed)
    sample = drv.sharded.sample_entries(cfg, seed, u, i, counts_u)
    U0, V0 = harness.seeded_tables(cfg, seed, stream=2)
    both = ("highest", "high")
    v0 = np.asarray(V0)
    captured = {
        "x0": {"user": drv.fetch_rows(U0, sample["user"]["rows"]),
               "item": v0[sample["item"]["rows"]]},
        "yty": {"user": drv.table_gram(V0, both),
                # the seed's user table stands in the program's
                # first-sweep table for the item half's inputs
                "item": drv.table_gram(U0, both)},
        "v0": v0,
        "item_entry_rows": drv.fetch_rows(U0, sample["item"]["ids"]),
    }

    def rows(name, precision):
        entries = (v0[sample["user"]["ids"]] if name == "user"
                   else captured["item_entry_rows"])
        return drv.reference_rows(
            cfg, sample[name], captured["yty"][name][precision], entries,
            captured["x0"][name], precision)

    low = dict(captured, got={"user": rows("user", "high"),
                              "item": rows("item", "high")})
    numbers = drv.compare_first_sweep(cfg, sample, low)
    numbers["window_nonfinite"] = 0.0
    correct, compared = harness.judge(numbers, cfg["limits"])
    assert correct is False, compared
    same = dict(captured, got={"user": rows("user", "highest"),
                               "item": rows("item", "highest")})
    assert all(x == 0 for x in
               drv.compare_first_sweep(cfg, sample, same).values())


def test_fetch_rows_in_pieces(tiny, monkeypatch):
    drv = _driver(tiny)
    monkeypatch.setattr(drv, "FETCH_ROWS", 4)
    table = np.arange(60, dtype=np.float32).reshape(20, 3)
    ids = np.array([19, 0, 7, 7, 3, 12, 1, 18, 5])
    import jax.numpy as jnp

    np.testing.assert_array_equal(drv.fetch_rows(jnp.asarray(table), ids),
                                  table[ids])


# -- the work counts and the readers on a hand-made run ---------------------

SHAPE = {"nnz": 33_633_450, "n_users": 571_355, "n_items": 41_140,
         "rank": 2048, "block": 128}
PEAKS = work.peaks_for("TPU v5 lite")
SOLVE = '%_solve.3 = f32[128,8192]{1,0} custom-call(%a, %b)'


def _hand_run(busy_s, ops=(), **extra):
    trace = tracereduce.TraceSummary(
        window_ns=int(40e9), busy_ns=busy_s * 1e9, n_devices=1,
        ops=list(ops))
    return dict({"trace": trace, "traced_sweeps": 1, "sweeps": 2,
                 "window_s": 2 * busy_s, "shape": SHAPE, "peaks": PEAKS,
                 "chips": 1}, **extra)


def test_work_of_a_block_sweep():
    nnz, nu, ni, r, w = SHAPE.values()
    flops = work_subspace.sweep_flops(nnz, nu, ni, r, w)
    # the three terms ISSUE 36 reckons: block Hessians 3.5e13, 9.8 M
    # systems of 128 x 128 1.4e13, YtY and the q caches 1.0e13 + 0.5e13
    hessians = 2 * 2.0 * nnz * r * w
    solves = 16 * (nu + ni) * work_subspace.solve_flops(w)
    assert hessians == pytest.approx(3.53e13, rel=0.01)
    assert solves == pytest.approx(1.4e13, rel=0.03)
    assert hessians + solves < flops < 1.2 * (hessians + solves + 1.54e13)
    # blocks as wide as the rank: the Hessians are the full Gram's work
    assert work_subspace.half_flops(nnz, nu, ni, r, r) > \
        work_subspace.half_flops(nnz, nu, ni, r, w)
    assert work_subspace.sweep_bytes(nnz, nu, ni, r) \
        == work.als_sweep_bytes(nnz, nu, ni, r)
    least, binds = work_subspace.least_sweep_seconds(SHAPE, PEAKS)
    assert binds == "flops" and 0.3 < least < 0.4
    least, binds = work_subspace.least_solve_seconds(9_800_000, 128, PEAKS)
    assert binds == "bytes" and least == pytest.approx(0.796, rel=0.01)


def test_a_roofline_cannot_read_over_100():
    roofline = cells.load_reader("subspace_sweep_roofline")
    mfu = cells.load_reader("subspace_train_mfu")
    solve = cells.load_reader("als_solve_roofline")
    least, _ = work_subspace.least_sweep_seconds(SHAPE, PEAKS)
    at_the_roof = _hand_run(least)
    assert roofline(at_the_roof, {}) == pytest.approx(100.0)
    assert mfu(at_the_roof, {}) <= 100.0 + 1e-9
    assert roofline(_hand_run(17.0), {}) < 3.0
    least_solve, _ = work_subspace.least_solve_seconds(9_800_000, 128, PEAKS)
    ops = [(SOLVE, int(least_solve * 1e9), 2400)]
    args = {"pattern": r"^%_solve[.\d]* = "}
    assert solve(_hand_run(17.0, ops, solve_systems=9_800_000), args) \
        == pytest.approx(100.0, rel=1e-6)
    ops = [(SOLVE, int(7.9e9), 2400)]
    assert 5 < solve(_hand_run(17.0, ops, solve_systems=9_800_000),
                     args) < 15


def test_readers_return_none_where_there_is_nothing_to_read():
    """On the parent, and in any cell whose program lacks the block
    sweep's ops, the new readers find nothing and the line leaves the
    metric out."""
    roofline = cells.load_reader("subspace_sweep_roofline")
    mfu = cells.load_reader("subspace_train_mfu")
    solve = cells.load_reader("als_solve_roofline")
    # the solve and Hessian seconds are read by `als_exchange_s` with a
    # pattern of their own, as the collectives are
    op_s = cells.load_reader("als_exchange_s")
    args = {"pattern": r"^%_solve[.\d]* = "}
    bare = {"shape": SHAPE, "peaks": PEAKS}
    for reader in (roofline, mfu):
        assert reader(dict(bare), {}) is None
    for reader in (solve, op_s):
        assert reader(dict(bare), args) is None
    full_solve = _hand_run(5.0)
    full_solve["shape"] = {k: v for k, v in SHAPE.items() if k != "block"}
    assert roofline(full_solve, {}) is None and mfu(full_solve, {}) is None
    other_ops = [("%fusion = fusion", 10**9, 3)]
    assert op_s(_hand_run(5.0, other_ops), args) is None
    assert solve(_hand_run(5.0, other_ops, solve_systems=10), args) is None
    assert solve(_hand_run(5.0, [(SOLVE, 10**9, 3)]), args) is None
    assert op_s(_hand_run(5.0, [(SOLVE, 10**9, 3), (SOLVE + " ", 10**9, 1)]),
                args) == pytest.approx(2.0)
    gram = json.loads(
        (ROOT / "perfbench/metrics/als_block_gram_s.sub.json").read_text())
    ops = [("%fusion.1172 = f32[4096,128,128]{2,1,0:T(8,128)} fusion(%x)",
            3 * 10**9, 16),
           ("%fusion.1171 = f32[128,128]{1,0} fusion(%g)", 10**9, 16),
           ("%fusion.1174 = f32[4096,1,128]{2,1,0} fusion(%y)", 10**9, 16)]
    assert op_s(_hand_run(5.0, ops), gram["args"]) == pytest.approx(3.0)


def test_a_program_without_the_byte_bound_exits_2_at_once(
        tiny, monkeypatch, capsys):
    """The parent of the PR that added the cell: its block sweep gathers a
    chunk's rows under an entry cap that knows no rank; the driver says so
    and exits 2 before it makes a rating."""
    from predictionio_tpu.models import als

    monkeypatch.delattr(als, "gather_chunk_entries")
    with pytest.raises(SystemExit) as e:
        _run(tiny)
    assert e.value.code == 2
    assert "cannot run this cell" in capsys.readouterr().err
