"""Each driver rehearsed on the CPU at a tiny size, with the look for a chip
patched by the test itself (the command has no option that skips it): the
result line's keys, the faults that `correct` has to catch, and the
lower-precision controls that it has to fail."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from perfbench import cells, harness, run, serve, tracereduce

ROOT = Path(__file__).resolve().parents[2]
CPU = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
TRAIN = "rec-netflix-r64.train"
SAT = "rec-yambda-r64.serve-saturated"
STEADY = "rec-yambda-r64.serve-steady"


def _edit(path: Path, **changes) -> None:
    doc = json.loads(path.read_text())
    doc.update(changes)
    path.write_text(json.dumps(doc))


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A copy of the benchmark with the same files at sizes a test can hold;
    limits, drivers, readers and references are the committed ones."""
    root = tmp_path_factory.mktemp("tiny")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    pb = root / "perfbench"
    _edit(pb / "configs/rec-netflix-r64.json", n_users=3000, n_items=200,
          n_ratings=120000, rank=8, user_max_ratings=2000,
          item_max_ratings=20000,
          check={"item_rows": 16, "widest_rows": 2})
    _edit(pb / "configs/rec-yambda-r64.json", n_users=2000, n_items=5000,
          rank=8, microbatch_max=8, check={"answers": 16})
    _edit(pb / "traffic/serve-saturated.json", connections=8, user_pool=512,
          trace_after_s=0.1, trace_seconds=0.3)
    _edit(pb / "traffic/serve-steady.json", connections=16, rate_per_s=100,
          user_pool=512, trace_after_s=0.1, trace_seconds=0.3)
    return root


def _present(manifest_cells):
    names = {w["name"] for w in
             json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}
    return [c for c in manifest_cells if c in names]


def _run(tiny, name, seed=2**31 + 99, seconds=0.6, trace=False):
    return run.execute(cells.resolve(name, tiny), seed, seconds, trace,
                       CPU, tiny)


@pytest.fixture()
def fake_trace(monkeypatch):
    """The CPU has no device plane: a traced rehearsal reads a made-up
    reduction, so that every reader and the result line are driven."""
    summary = tracereduce.TraceSummary(
        window_ns=1_000_000_000, busy_ns=600_000_000.0, n_devices=1,
        ops=[("%custom-call = custom-call:TopK", 400_000_000, 10),
             ("%fusion = fusion", 200_000_000, 10)],
        gaps=[(0, 400_000_000, tracereduce.NO_HOST_SPAN)],
    )
    monkeypatch.setattr(harness.Tracer, "reduce", lambda self: summary)


@pytest.mark.parametrize("name", _present([TRAIN, SAT, STEADY]))
def test_result_line_of_each_driver(tiny, name):
    r = _run(tiny, name)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "compared"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    cell = cells.resolve(name, tiny)
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in r["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}
    for c in r["compared"]:
        assert set(c) == {"name", "value", "limit"}
    json.dumps(r)


@pytest.mark.parametrize("name", _present([TRAIN, SAT, STEADY]))
def test_traced_result_line_of_each_driver(tiny, name, fake_trace):
    r = _run(tiny, name, trace=True)
    cell = cells.resolve(name, tiny)
    wanted = {m.name for m in cell.per_layer}
    # a closed loop has no schedule to be late on, nothing else is silent
    assert wanted - set(r["metrics"]) <= {"gen_late_p95_ms.steady"} - (
        {"gen_late_p95_ms.steady"} if name == STEADY else set())
    assert set(r["metrics"]) <= wanted
    assert r["device"]["busy_s"] == pytest.approx(0.6)
    assert r["device"]["window_s"] == pytest.approx(1.0)
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert len(r["breakdown"]["device_ops"]) == 2
    for key, m in r["metrics"].items():
        if "roofline" in key or "mfu" in key:
            assert 0 < m["value"] <= 105, key
    assert r["correct"] is True
    json.dumps(r)


def test_unpatched_command_exits_nonzero_without_a_chip():
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", TRAIN, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "BENCH_RUN": "x"},
    )
    assert p.returncode != 0 and p.stdout == ""
    assert "no accelerator" in p.stderr


# -- faults planted under the timed path: `correct` has to come out false ----

needs_train = pytest.mark.skipif(not _present([TRAIN]), reason="no train cell")
needs_serve = pytest.mark.skipif(not _present([SAT]), reason="no serve cell")


@needs_train
def test_fault_a_sweep_that_returns_its_state_unchanged(tiny, monkeypatch):
    from predictionio_tpu.models.als import ALSTrainer

    monkeypatch.setattr(ALSTrainer, "run",
                        lambda self, U, V, n, lam=None: (U, V))
    r = _run(tiny, TRAIN)
    assert r["correct"] is False
    worst = {c["name"]: c["value"] for c in r["compared"]}
    assert worst["u_fro"] > 0.5 and worst["v_fro"] > 0.5


@needs_train
def test_fault_half_of_the_rows_left_out(tiny, monkeypatch):
    import jax.numpy as jnp
    from predictionio_tpu.models.als import ALSTrainer

    real = ALSTrainer._half

    def half(self, upd, opp, side, lam=None):
        old = jnp.array(upd, copy=True)
        new = real(self, upd, opp, side, lam=lam)
        keep = old.shape[0] // 2
        return new.at[:keep].set(old[:keep])

    monkeypatch.setattr(ALSTrainer, "_half", half)
    r = _run(tiny, TRAIN)
    assert r["correct"] is False
    worst = {c["name"]: c["value"] for c in r["compared"]}
    assert worst["u_worst_row"] > 0.5


@needs_serve
def test_fault_an_answer_altered_where_it_is_produced(tiny, monkeypatch):
    from predictionio_tpu.templates import recommendation

    real = recommendation.batch_topk_scores_t

    def altered(query_vecs, table_t, k, mask=None):
        vals, ixs = real(query_vecs, table_t, k, mask=mask)
        return vals, (ixs + 1) % table_t.shape[1]

    monkeypatch.setattr(recommendation, "batch_topk_scores_t", altered)
    r = _run(tiny, SAT)
    assert r["correct"] is False
    assert r["failed"] == 0, "the answers are well formed, and wrong"


@needs_serve
def test_fault_answers_returned_to_the_wrong_request(tiny, monkeypatch):
    from predictionio_tpu.templates.recommendation import ALSAlgorithm

    real = ALSAlgorithm.batch_predict

    def rotated(self, model, queries):
        out = real(self, model, queries)
        return out[1:] + out[:1]

    monkeypatch.setattr(ALSAlgorithm, "batch_predict", rotated)
    r = _run(tiny, SAT)
    assert r["correct"] is False


# -- the controls: the reference at the precision below, in the program's place


@needs_train
@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_control_three_pass_gram_fails_the_train_limits(tiny, seed):
    cell = cells.resolve(TRAIN, tiny)
    drv = cells._load_module("drivers", "train_sweeps", tiny)
    u, i, v, counts_u = drv.make_ratings(cell.config, seed)
    tables0 = drv.init_tables(cell.config, seed)
    data = (cell.config, seed, u, i, v, counts_u, tables0)
    ref = drv.reference_first_sweep(*data)
    ctl = drv.reference_first_sweep(*data, precision="high")
    numbers, _ = drv.compare_first_sweep(ref, ctl["u1"], ctl["v1_rows"])
    numbers["window_nonfinite"] = 0.0
    correct, compared = harness.judge(numbers, cell.config["limits"])
    assert correct is False, compared
    same, _ = drv.compare_first_sweep(ref, ref["u1"], ref["v1_rows"])
    assert all(x == 0 for x in same.values())


@needs_serve
@pytest.mark.parametrize("seed", [3, 2**31 + 4, 5])
def test_control_fp8_product_fails_the_serve_limits(tiny, seed):
    import jax.numpy as jnp
    from perfbench.reference import topk_ref

    cell = cells.resolve(SAT, tiny)
    users, items = serve.make_tables(cell.config, seed)
    ask = np.random.default_rng(seed).integers(0, len(users), 16)
    table = jnp.asarray(items)
    for precision, passes in (("fp8", False), ("highest", True)):
        got_items, got_scores = topk_ref.answer(users[ask], table, 16,
                                                precision)
        out = topk_ref.compare(users[ask], table, got_items, got_scores)
        numbers = {"rank_gap": out["rank_gap"], "score_err": out["score_err"],
                   "answers_with_repeats": 0.0}
        correct, compared = harness.judge(numbers, cell.config["limits"])
        assert correct is passes, (precision, compared)
