"""tools/pulse_smoke.py drives the pio-pulse decomposition contract
through real servers under real multi-process load (the pulse analogue
of tests/test_obs_smoke.py): a segment that stops being booked, a
timeline that leaks tail time, a dead /debug/profile, or a flight
record without its decomposition fails HERE — not during an incident
when an operator is asking where the 30 ms went."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_pulse_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "pulse.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    env.pop("PIO_FAULT_PLAN", None)
    env.pop("PIO_TPU_TELEMETRY_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "pulse_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["metric"] == "pulse_smoke"
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for stage in ("train_tiny_engine", "boot_servers",
                  "concurrent_load", "segments_complete",
                  "segments_reconcile", "saturation_metrics",
                  "profile_artifact", "flight_decomposes"):
        assert rec["stages"][stage] >= 0, stage
    # the profiler artifact landed under the isolated telemetry home
    profiles = list(
        (tmp_path / "home" / "telemetry" / "profiles").rglob("*")
    )
    assert any(p.is_file() for p in profiles), "profile artifact missing"
