"""tools/ingest_smoke.py drives the pio-levee one-shard-down chaos
contract end to end through REAL processes (ingest router + 2
subprocess shard-owner workers): a SIGKILLed owner mid-load costs zero
errors on healthy shards, its own entities answer structured
503 + Retry-After (positionally inside batches too), the federated
/stats.json stays monotone through the death, and after a restart on
the same WAL dir every acknowledged event is still readable — zero
acked loss."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_ingest_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "ingest.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    env.pop("PIO_TPU_TELEMETRY_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "ingest_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=500, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for s in ("boot_fleet", "steady_ingest", "kill_mid_load",
              "degraded_batch", "stats_through_death",
              "restart_recovery"):
        assert s in rec["stages"]
    # the acked ledger actually exercised the recovery path
    assert rec["stages"]["recovery_detail"]["acked"] > 0
    assert rec["stages"]["recovery_detail"]["missing"] == 0
    assert rec["stages"]["kill_detail"]["structured"] > 0
