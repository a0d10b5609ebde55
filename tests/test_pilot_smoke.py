"""tools/pilot_smoke.py drives the pio-pilot contract end to end
through real servers: an A/B with a seeded conversion gap concludes
ITSELF — SPRT crosses its threshold, traffic ramps toward the winner in
bounded steps landing as real POST /tenants/weights calls, the loser is
floored (never zeroed) — and a fault-plan-broken variant holding the
BEST conversion rate is guardrail-vetoed back down, with evidence at
the client, /metrics, and pio-tower-manifest levels.  A regression in
the self-driving-experiment story fails here in CI, not in production
traffic."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_pilot_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "pilot.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    env.pop("PIO_FAULT_PLAN", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "pilot_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for s in ("train", "seed", "autopilot_concludes",
              "guardrail_veto", "surfaces"):
        assert s in rec["stages"]
    # the closed loop is concrete, not vacuous: real HTTP applies and
    # a replayable decision trail
    assert len(rec["detail"]["httpApplies"]) >= 3
    assert rec["detail"]["manifestDecisions"]["ramps"] >= 3
    assert rec["detail"]["manifestDecisions"]["vetoes"] >= 1
