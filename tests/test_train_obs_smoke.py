"""tools/train_obs_smoke.py drives the pio-tower contract end to end
through a real ``run_train``: a complete crash-tolerant run manifest
whose phase decomposition reconciles with the ``train.run`` wall time,
a typed watchdog abort on an injected NaN sweep, the cluster
counter-merge on a chief's /metrics, and the runlog CLI over the
manifests the run produced.  A regression in training observability
fails here in CI, not during a 135 s TPU incident."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_train_obs_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "tower.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    env.pop("PIO_TPU_RUNLOG_DIR", None)
    env.pop("PIO_FAULT_PLAN", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "train_obs_smoke.py"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for s in ("train_twice", "manifest_complete",
              "phase_sums_reconcile", "watchdog_nan_abort",
              "cluster_merge", "runlog_cli"):
        assert s in rec["stages"]
    # the reconciliation numbers are reported, not just judged
    assert rec["detail"]["reconciliation"]["trainRunGap"] <= 0.02
