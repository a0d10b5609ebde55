"""tools/fleet_smoke.py drives the pio-lens fleet-observability
contract end to end through REAL processes (router + 2 subprocess
replicas): the router's merged /metrics equals the sum of the
replicas' (grammar-checked by the strict parser), a SIGSTOPped
replica's tail is attributed to it by the router flight recorder while
the merged exposition stays monotone, and tools/tracecat.py stitches
one trace across the router's and a replica's span journals."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_fleet_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "fleet.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    env.pop("PIO_TPU_TELEMETRY_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "fleet_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=500, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for s in ("train", "spawn_fleet", "merged_exposition",
              "tail_attribution", "tracecat_stitches"):
        assert s in rec["stages"]
    # the smoke prints the stitched tree — spot-check the CLI render
    assert "router.request" in proc.stdout
    assert "serve.query" in proc.stdout
