"""bench.py orchestration contract: the attempt chain, budget clamping,
history fencing, and the refusal to print a result without an
accelerator."""

import json

import pytest

import bench


@pytest.fixture(autouse=True)
def _isolated_artifacts(tmp_path, monkeypatch):
    """bench.main() writes the canonical BENCH_PR<k>.json and reads
    BENCH_HISTORY.jsonl at the repo root.  Tests that drive main() with
    stubbed runners must never touch the real artifacts: an unstubbed
    _write_pr_summary once committed a trajectory point whose "error"
    field was the literal 'fail' sentinel from the stubs below."""
    monkeypatch.setattr(bench, "HISTORY_PATH",
                        tmp_path / "BENCH_HISTORY.jsonl")
    monkeypatch.setattr(bench, "_write_pr_summary",
                        lambda rec, fenced=None: None)
    monkeypatch.setenv("PIO_TPU_PR_SUMMARY",
                       str(tmp_path / "BENCH_PR_TEST.json"))


@pytest.fixture()
def patched(monkeypatch):
    calls = {"probe": [], "inner": []}

    def probe(timeout):
        calls["probe"].append(timeout)
        return ("tpu", None)

    monkeypatch.setattr(bench, "_probe_accelerator", probe)
    monkeypatch.setattr(bench, "_record_history", lambda line: None)
    return calls


def _run(monkeypatch, argv=None):
    import sys

    monkeypatch.setattr(sys, "argv", ["bench.py"] + (argv or []))
    bench.main()


def test_optimized_config_tried_first_then_safe(patched, monkeypatch,
                                                capsys):
    def supervised(extra, hard_cap, stall_timeout=None):
        patched["inner"].append(list(extra))
        if "pallas" in extra:
            return None, "simulated lowering failure"
        return json.dumps({"metric": "m", "value": 1.0,
                           "platform": "tpu", "scale": 1.0}), None

    monkeypatch.setattr(bench, "_run_inner_supervised", supervised)
    _run(monkeypatch)
    a1, a2 = patched["inner"]
    # best first: Pallas solves + bf16x3 Gram
    assert "pallas" in a1 and "high" in a1
    # then the conservative all-XLA config
    assert "--solver" not in a2 and "--precision" not in a2
    out = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(out)["platform"] == "tpu"


def test_explicit_solver_pins_single_attempt(patched, monkeypatch, capsys):
    def supervised(extra, hard_cap, stall_timeout=None):
        patched["inner"].append(list(extra))
        return json.dumps({"metric": "m", "value": 1.0,
                           "platform": "tpu", "scale": 1.0}), None

    monkeypatch.setattr(bench, "_run_inner_supervised", supervised)
    _run(monkeypatch, ["--solver", "xla"])
    assert len(patched["inner"]) == 1
    assert "pallas" not in patched["inner"][0]


def test_timeouts_clamped_to_budget(patched, monkeypatch, capsys):
    seen = []

    def supervised(extra, hard_cap, stall_timeout=None):
        seen.append(hard_cap)
        return None, "fail"

    monkeypatch.setattr(bench, "_run_inner_supervised", supervised)
    monkeypatch.setattr(bench, "TOTAL_BUDGET", 300)
    with pytest.raises(SystemExit) as exc:
        _run(monkeypatch)
    # every attempt failed: non-zero exit and NO result line — there is
    # no CPU stage after the accelerator attempts
    assert exc.value.code == 1
    assert capsys.readouterr().out.strip() == ""
    # every stage timeout respects the shrunken budget
    assert patched["probe"][0] <= 300
    assert len(seen) == 2 and all(60 <= t <= 300 for t in seen)


def test_no_accelerator_exits_nonzero_without_a_result(monkeypatch,
                                                       capsys):
    """The default invocation measures on the accelerator or not at
    all: a backend that resolves to the CPU (or fails to init) ends the
    run non-zero before any train attempt, and nothing is printed under
    the metric's name."""
    ran = []
    monkeypatch.setattr(
        bench, "_probe_accelerator",
        lambda timeout: (None, "backend resolved to cpu (no accelerator)"),
    )
    monkeypatch.setattr(
        bench, "_run_inner_supervised",
        lambda *a, **k: ran.append(a) or (None, "must not run"),
    )
    with pytest.raises(SystemExit) as exc:
        _run(monkeypatch)
    assert exc.value.code == 1 and not ran
    cap = capsys.readouterr()
    assert cap.out.strip() == ""
    assert "no accelerator" in cap.err


def test_probe_reports_cpu_as_no_accelerator():
    """The real probe child under the suite's JAX_PLATFORMS=cpu: a CPU
    backend is not an accelerator."""
    platform, why = bench._probe_accelerator(120)
    assert platform is None and "cpu" in why


def test_record_history_marks_fenced(tmp_path, monkeypatch):
    hist = tmp_path / "hist.jsonl"
    monkeypatch.setattr(bench, "HISTORY_PATH", hist)
    bench._record_history(json.dumps(
        {"metric": "m", "value": 5.0, "platform": "tpu", "scale": 1.0}
    ))
    rec = json.loads(hist.read_text().strip())
    assert rec["fenced"] is True and "recorded_at" in rec
    # cpu and small-scale runs are never recorded
    bench._record_history(json.dumps(
        {"metric": "m", "value": 5.0, "platform": "cpu", "scale": 1.0}
    ))
    bench._record_history(json.dumps(
        {"metric": "m", "value": 5.0, "platform": "tpu", "scale": 0.02}
    ))
    assert len(hist.read_text().strip().splitlines()) == 1


def test_probe_retry_ladder(monkeypatch, capsys):
    """A transient probe failure (attempts 1-2 fail, 3 succeeds) must
    still reach the accelerator attempt chain."""
    import sys

    attempts = []

    def probe(timeout):
        attempts.append(timeout)
        if len(attempts) < 3:
            return None, "timed out (injected)"
        return "tpu", None

    monkeypatch.setattr(bench, "_probe_accelerator", probe)
    monkeypatch.setattr(bench, "_record_history", lambda line: None)
    monkeypatch.setattr(
        bench, "_run_inner_supervised",
        lambda extra, hard_cap, stall_timeout=None: (
            json.dumps({"metric": "m", "value": 1.0,
                        "platform": "tpu", "scale": 1.0}), None),
    )
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    bench.main()
    assert len(attempts) == 3
    out = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(out)["platform"] == "tpu"


def test_inner_raises_when_the_requested_kernel_does_not_compile(
        monkeypatch, capsys):
    """A requested kernel that fails to compile fails the run — no
    record is printed under another solver's name."""
    from predictionio_tpu.ops import solve as solve_mod

    def boom(*a, **k):
        raise RuntimeError("injected lowering failure")

    monkeypatch.setattr(solve_mod, "cholesky_solve_batched", boom)
    args = bench._parse_args(
        ["--inner", "--scale", "0.001", "--rank", "5", "--iters", "1",
         "--solver", "pallas"]
    )
    with pytest.raises(RuntimeError, match="injected lowering failure"):
        bench.run_inner(args)
    assert capsys.readouterr().out.strip() == ""


def test_inner_records_the_solver_it_ran(capsys):
    """The record names the solver that ran — always the requested one
    — and quality fields ride every holdout-splitting record, not only
    full-scale ones."""
    args = bench._parse_args(
        ["--inner", "--scale", "0.001", "--rank", "6", "--iters", "1",
         "--solver", "pallas"]
    )
    bench.run_inner(args)
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["solver"] == "pallas" and rec["platform"] == "cpu"
    assert "solver_requested" not in rec and "degraded" not in rec
    assert rec["train_rmse"] > 0 and rec["rmse_holdout"] > 0


def test_parity_mode_emits_zero_delta_line(capsys, tmp_path, monkeypatch):
    """`bench.py --parity` (quality half of the north star): our trainer
    must match the dense MLlib-convention oracle to ~1e-3 RMSE on both
    train and hold-out splits at the verifiable 400x250 scale — and
    write the driver-readable BENCH_PARITY.json artifact."""
    import bench

    out = tmp_path / "BENCH_PARITY.json"
    monkeypatch.setattr(bench, "PARITY_PATH", out)
    args = bench._parse_args(["--parity"])
    bench.run_parity(args)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["metric"] == "als_rmse_parity_vs_mllib_oracle"
    assert rec["holdout_delta"] < 1e-3
    assert abs(rec["rmse_train_tpu"] - rec["rmse_train_oracle"]) < 1e-3
    assert json.loads(out.read_text())["holdout_delta"] < 1e-3


def test_pipeline_mode_emits_stage_breakdown(capsys):
    """`bench.py --pipeline` drives file -> native import -> sqlite ->
    columnar scan -> encode -> train and reports every stage."""
    import bench

    args = bench._parse_args(
        ["--pipeline", "--scale", "0.002", "--iters", "2"]
    )
    bench.run_pipeline(args)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["metric"] == "ml20m_pipeline_file_to_model_seconds"
    for stage in ("import", "scan_and_encode_fused", "train"):
        assert rec["stages"][stage] >= 0
    assert rec["n_events"] > 0
    # which read path actually ran must be visible in the artifact
    assert rec["scan_path"] in ("native", "python")
    assert rec["value"] > 0 and "train_rmse" in rec


def test_attempt_budget_split_prevents_starvation(patched, monkeypatch,
                                                  capsys):
    """A first attempt that eats its whole hard cap must still leave the
    second attempt real time (the per-attempt cap splits what remains
    instead of letting attempt 1 take everything)."""
    tpu_caps = []

    def supervised(extra, hard_cap, stall_timeout=None):
        tpu_caps.append(hard_cap)
        return None, "fail"

    monkeypatch.setattr(bench, "_run_inner_supervised", supervised)
    monkeypatch.setattr(bench, "TOTAL_BUDGET", 900)
    with pytest.raises(SystemExit):
        _run(monkeypatch)
    # 2 accelerator attempts ran, and nothing after them
    assert len(tpu_caps) == 2
    # first attempt got the larger share of the window, not all of it:
    # the conservative config keeps a real slot
    assert tpu_caps[0] < 900 - 100
    # every attempt got a meaningful floor
    assert all(t >= 60 for t in tpu_caps)


def _stub_cmd(script):
    import sys as _sys

    return lambda extra: [_sys.executable, "-u", "-c", script]


def test_supervised_returns_json_and_streams_progress(monkeypatch):
    """A healthy child that prints progress markers and then its JSON
    line completes under supervision."""
    monkeypatch.setattr(bench, "_inner_cmd", _stub_cmd(
        "import sys, time\n"
        "for k in range(3):\n"
        "    print('# stage', k, file=sys.stderr, flush=True)\n"
        "    time.sleep(0.05)\n"
        "print('{\"value\": 7}')\n"
    ))
    line, err = bench._run_inner_supervised([], hard_cap=60,
                                            stall_timeout=15)
    assert err is None and json.loads(line)["value"] == 7


def test_supervised_kills_stalled_child(monkeypatch):
    """A child that stops emitting markers dies after one stall window,
    not after the whole budget (a hung backend init must not starve the
    later attempts)."""
    import time

    monkeypatch.setattr(bench, "_inner_cmd", _stub_cmd(
        "import sys, time\n"
        "print('# started', file=sys.stderr, flush=True)\n"
        "time.sleep(60)\n"
        "print('{\"value\": 7}')\n"
    ))
    t0 = time.time()
    line, err = bench._run_inner_supervised([], hard_cap=45,
                                            stall_timeout=2)
    assert line is None and "no progress" in err
    assert time.time() - t0 < 20


def test_supervised_spares_slow_but_advancing_child(monkeypatch):
    """Markers keep a slow child alive well past the stall window."""
    monkeypatch.setattr(bench, "_inner_cmd", _stub_cmd(
        "import sys, time\n"
        "for k in range(6):\n"
        "    print('# slow stage', k, file=sys.stderr, flush=True)\n"
        "    time.sleep(0.8)\n"
        "print('{\"value\": 9}')\n"
    ))
    line, err = bench._run_inner_supervised([], hard_cap=60,
                                            stall_timeout=10)
    assert err is None and json.loads(line)["value"] == 9


def test_supervised_honors_declared_phase_budget(monkeypatch):
    """A marker may declare next-phase-budget=N for a known-long silent
    phase (backend init, the timed train): the stall window
    widens for that one phase, then snaps back at the next marker."""
    monkeypatch.setattr(bench, "_inner_cmd", _stub_cmd(
        "import sys, time\n"
        "print('# start next-phase-budget=30 (long quiet phase)',\n"
        "      file=sys.stderr, flush=True)\n"
        "time.sleep(5)\n"   # > the 3s stall default, < the budget
        "print('{\"value\": 11}')\n"
    ))
    line, err = bench._run_inner_supervised([], hard_cap=60,
                                            stall_timeout=3)
    assert err is None and json.loads(line)["value"] == 11


def test_supervised_recovers_json_from_killed_child(monkeypatch):
    """A child that prints its JSON line and then hangs in teardown
    still yields the measurement: the kill path reads the buffered
    stdout."""
    monkeypatch.setattr(bench, "_inner_cmd", _stub_cmd(
        "import sys, time\n"
        "print('# started', file=sys.stderr, flush=True)\n"
        "print('{\"value\": 13}', flush=True)\n"
        "time.sleep(60)\n"   # hung teardown, no more markers
    ))
    # duration == stall_timeout by construction (the child never prints
    # again): 6 s is boot margin on a loaded box without 15 s dead wait
    line, err = bench._run_inner_supervised([], hard_cap=60,
                                            stall_timeout=6)
    assert err is None and json.loads(line)["value"] == 13


def test_supervised_enforces_hard_cap(monkeypatch):
    """Even a continuously-progressing child cannot exceed the hard cap
    (the driver watchdog is ~20 min; bench must never outlive it)."""
    import time

    monkeypatch.setattr(bench, "_inner_cmd", _stub_cmd(
        "import sys, time\n"
        "while True:\n"
        "    print('# tick', file=sys.stderr, flush=True)\n"
        "    time.sleep(0.2)\n"
    ))
    t0 = time.time()
    line, err = bench._run_inner_supervised([], hard_cap=3,
                                            stall_timeout=30)
    assert line is None and "hard cap" in err
    assert time.time() - t0 < 15


def test_inner_line_carries_mfu_roofline(monkeypatch, capsys):
    """Every --inner record must carry the roofline fields: achieved
    FLOP/s from the closed-form ALS FLOP count, mfu (null when the
    device peak is unknown — CPU runs must not invent one), and the
    device kind the peak was looked up for (VERDICT r4 #4)."""
    args = bench._parse_args(
        ["--inner", "--scale", "0.001", "--rank", "6", "--iters", "1"]
    )
    bench.run_inner(args)
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # the field is there and is a rate; a CPU rate is no measurement,
    # and under a loaded machine it rounds to 0.0
    rate = rec["achieved_tflops_per_s"]
    assert isinstance(rate, (int, float)) and not isinstance(rate, bool)
    assert rate >= 0
    assert "mfu" in rec and "device_kind" in rec
    # the test mesh is CPU: unknown peak -> null mfu, never a number
    assert rec["mfu"] is None
    # holdout explain-or-gate: the mean baseline rides next to the rmse
    assert rec["rmse_holdout_mean_baseline"] > 0
    assert "holdout_note" in rec


def test_als_flops_closed_form():
    """The FLOP model itself: hand-expanded for a tiny config."""
    # nnz=10, users=3, items=2, rank=2, 1 iter:
    # gram/half = 2*10*4 = 80; rhs/half = 2*10*2 = 40
    # solves = (3+2) * (2/3)*8 = 26.667
    expect = 2 * (80 + 40) + 5 * (2.0 / 3.0) * 8
    assert abs(bench.als_train_flops(10, 3, 2, 2) - expect) < 1e-9


def test_device_peak_lookup_reports_basis():
    class _Dev:
        device_kind = "TPU v4"
        platform = "tpu"

    class _Jax:
        @staticmethod
        def devices():
            return [_Dev()]

    peak, kind = bench.device_peak_flops(_Jax)
    assert peak == 275e12 and kind == "TPU v4"

    class _Cpu:
        device_kind = "cpu"
        platform = "cpu"

    class _JaxCpu:
        @staticmethod
        def devices():
            return [_Cpu()]

    peak, kind = bench.device_peak_flops(_JaxCpu)
    assert peak is None and kind == "cpu"
