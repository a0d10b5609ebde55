"""tools/surge_smoke.py drives the pio-surge fleet contract end to end
through REAL processes (router + 2 subprocess replicas on the
event-loop edge): round-robin serving, a rolling fold-in delta push
that freshens every replica with zero /reload calls, and a SIGKILLed
replica masked from clients with zero failed requests.  A regression
in the fleet path fails here in CI, not during an incident."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_surge_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "surge.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "surge_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=500, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for s in ("train", "spawn_fleet", "fleet_serves",
              "rolling_push_freshens", "kill_masked"):
        assert s in rec["stages"]
