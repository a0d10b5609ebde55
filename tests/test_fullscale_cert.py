"""tools/fullscale_cert.py drives the real end-to-end pipeline.

The full-scale run is the judge-read artifact (BENCH_FULLSCALE_CPU.json);
this executes the same driver at tiny scale so API drift in any stage
(import, fused scan, staging, checkpointed train, restore, deploy
smoke) fails in CI instead of at certification time."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_cert_driver_runs_at_tiny_scale(tmp_path):
    out = tmp_path / "cert.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "fullscale_cert.py"),
         "--scale", "0.002", "--rank", "6", "--iters", "2",
         "--checkpoint-every", "1", "--out", str(out)],
        capture_output=True, text=True, timeout=420, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["metric"] == "fullscale_cpu_certification"
    for stage in ("write_source_file", "import", "scan_and_encode_fused",
                  "bucketize_and_stage", "train_and_checkpoint",
                  "rmse_eval", "deploy_smoke_from_checkpoint"):
        assert rec["stages"][stage] >= 0, stage
    assert rec["n_events_imported"] > 0
    assert rec["checkpoint_restored_step"] == 2
    assert rec["value"] > 0 and rec["train_rmse"] > 0
