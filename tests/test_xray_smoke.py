"""tools/xray_smoke.py drives the compiler/device observability
contract through a real trained-and-deployed engine (the pio-xray
analogue of tests/test_obs_smoke.py): a recompile the ring misses, a
dead /debug/xray payload, an exemplar that doesn't resolve to a flight
record, or a bench gate that stops gating fails here in CI — not
mid-incident when an operator is asking "why did my query recompile?".
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_xray_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "xray.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
        "PIO_TPU_TRACE_ALS": "1",
    })
    env.pop("PIO_FAULT_PLAN", None)
    env.pop("PIO_TPU_TELEMETRY_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "xray_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["metric"] == "xray_smoke"
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for stage in ("train_tiny_engine", "boot_server", "forced_recompile",
                  "debug_xray", "device_gauges", "flight_recorder",
                  "bench_gate"):
        assert rec["stages"][stage] >= 0, stage
