"""tools/foldin_smoke.py drives the pio-live contract end to end
through real servers (event server ingest -> fold-in cycle -> in-place
serving delta apply -> fresh non-fallback predictions, zero /reload):
a regression in the freshness path fails here in CI, not in front of a
cold-start user."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_foldin_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "foldin.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "foldin_smoke.py"),
         "--out", str(out), "--home", str(tmp_path / "storage")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    # the contract's headline stages all ran
    for s in ("train", "cold_query", "ingest", "foldin_cycle",
              "serving_apply", "signature_stability"):
        assert s in rec["stages"]
