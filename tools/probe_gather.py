"""Row versus grouped gather timings for ROADMAP D1 (CLI over
`ops/gather_probe.py`).

Times, at the ML-20M table shapes, the XLA ``jnp.take`` row gather (what
the ALS hot loop pays) against the grouped tile-slab take behind
``gather_mode="grouped"``, float32.  Each probe prints one JSON line.
``--smoke`` runs both at small shapes (shape and row validation for
``tools/gate.sh``) and exits nonzero if either gathers wrong rows.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from predictionio_tpu.ops import gather_probe as gp  # noqa: E402


def _emit(rec) -> None:
    print(json.dumps(rec), flush=True)


def run_smoke() -> int:
    """Small-shape run of both forms: row validation."""
    _emit({"metric": "probe_env", "backend": jax.default_backend(),
           "mode": "smoke",
           "note": "shape/logic validation only"})
    recs = gp.smoke()
    bad = 0
    for rec in recs:
        _emit(rec)
        if rec.get("ok") is False:
            bad += 1
    _emit({"metric": "probe_smoke_summary", "forms": len(recs),
           "failed": bad, "ok": bad == 0})
    return 1 if bad else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small-shape validation of both gather forms "
                    "(the gate.sh step); exits nonzero on a wrong row")
    args = ap.parse_args(argv)
    if args.smoke:
        return run_smoke()

    _emit({"metric": "probe_env", "backend": jax.default_backend(),
           "device": str(jax.devices()[0])})
    r = 64
    for m in (26744, 138493):
        _emit(gp.probe_xla_take(m, 32768, r))
        _emit(gp.probe_xla_grouped_take(m, 32768, r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
