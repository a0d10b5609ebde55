"""tools/forge_smoke.py proves the pio-forge one-file-engine contract
end to end: a from-scratch engine written to a temp dir and named by
``PIO_TPU_ENGINE_PATH`` must light up `engines list/describe`,
`train --engine`, real HTTP serving, and the engine-labeled obs counter
— with zero platform code changes.  A regression in discovery, registry
dispatch, or the auto-wiring fails here in CI, not in a user's first
custom engine."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).parent.parent


def test_forge_smoke_runs_and_all_invariants_hold(tmp_path):
    out = tmp_path / "forge.json"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PIO_TPU_HOME": str(tmp_path / "home"),
    })
    env.pop("PIO_TPU_ENGINE_PATH", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "forge_smoke.py"),
         "--out", str(out), "--home", str(tmp_path / "storage")],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=tmp_path,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    rec = json.loads(out.read_text())
    assert rec["ok"] is True
    for name, held in rec["invariants"].items():
        assert held, f"invariant {name} violated"
    for s in ("discover", "cli_list", "train", "deploy_query", "obs"):
        assert s in rec["stages"]
