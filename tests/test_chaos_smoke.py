"""tools/chaos_smoke.py drives the failure-semantics invariants through
real servers (the chaos analogue of tests/test_fullscale_cert.py): a
regression in any degradation path fails here in CI, not during an
actual outage.  Runs inside tier-1 — the whole drill is seconds on
CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).parent.parent

pytestmark = pytest.mark.chaos


def test_chaos_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "chaos.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    env.pop("PIO_FAULT_PLAN", None)  # the driver arms its own plans
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "chaos_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["metric"] == "chaos_smoke"
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for stage in ("storage_write_retry", "train_tiny_engine",
                  "feedback_redelivery", "stale_reload"):
        assert rec["stages"][stage] >= 0, stage
