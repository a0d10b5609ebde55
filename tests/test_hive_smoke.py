"""tools/hive_smoke.py drives the pio-hive contract end to end through
real servers: multi-tenant routing with sticky weighted A/B assignment,
per-tenant breaker/quota isolation (one tenant's chaos leaves its
neighbor's error count at zero), budget-driven eviction with zero
failed in-flight requests + lazy reload, and per-variant feedback
attribution flowing through the event store into /metrics and a
pio-tower manifest.  A regression in the isolation story fails here in
CI, not in front of a co-tenant."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_hive_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "hive.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    env.pop("PIO_FAULT_PLAN", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "hive_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    # the contract's headline stages all ran
    for s in ("train", "routing", "breaker_isolation",
              "quota_isolation", "eviction", "attribution"):
        assert s in rec["stages"]
    # the isolation evidence is concrete, not vacuous
    assert rec["detail"]["evicted"]
    assert rec["detail"]["assignmentSplit"]
