"""tools/obs_smoke.py drives the observability contract through real
servers (the pio-obs analogue of tests/test_chaos_smoke.py): a broken
/metrics exposition, a dead bucket ladder, or a dropped trace id fails
here in CI — not during an incident when an operator needs them.  Runs
inside tier-1 alongside the chaos smoke; the whole drill is seconds on
CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_obs_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "obs.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    env.pop("PIO_FAULT_PLAN", None)
    env.pop("PIO_TPU_TELEMETRY_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "obs_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["metric"] == "obs_smoke"
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for stage in ("train_tiny_engine", "boot_servers", "traffic",
                  "metrics_exposition", "trace_propagation"):
        assert rec["stages"][stage] >= 0, stage
    # the journal the tutorial teaches operators to grep must exist
    journals = list((tmp_path / "telemetry").glob("spans-*.jsonl"))
    assert journals, "telemetry journal missing"
    assert any("t-123" in p.read_text() for p in journals)
