"""Gather-form timings for ROADMAP S3 (CLI over `ops/gather_probe.py`).

Which in-kernel gather forms the v5e compiler accepts was settled in
PR 21 (CHANGES.md): only the row-DMA loop.  This script times, at the
ML-20M table shapes, what is left to compare on the chip:

  * the XLA ``jnp.take`` baseline (what the unfused path pays), f32 and
    bf16;
  * the grouped tile-slab take behind ``gather_mode="grouped"``;
  * the fused kernel's rolling-window ``pltpu.make_async_copy`` row
    loop (indices scalar-prefetched to SMEM, float32 rows).

Each probe prints one JSON line.  ``--smoke`` runs every form at small
shapes (CPU interpret-mode shape and logic validation for
``tools/gate.sh``) and exits nonzero if any form's math is wrong.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from predictionio_tpu.ops import gather_probe as gp  # noqa: E402


def _emit(rec) -> None:
    print(json.dumps(rec), flush=True)


def run_smoke() -> int:
    """Small-shape run of every form: interpret-mode math validation."""
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
                    help="small-shape CPU interpret-mode validation of "
                    "every gather form (the gate.sh step); exits "
                    "nonzero on any math mismatch")
    args = ap.parse_args(argv)
    if args.smoke:
        return run_smoke()

    _emit({"metric": "probe_env", "backend": jax.default_backend(),
           "device": str(jax.devices()[0])})
    r = 64
    _emit({"metric": "section", "form": "xla_take_baseline"})
    for dtype in (jnp.float32, jnp.bfloat16):
        _emit(gp.probe_xla_take(26744, 32768, r, dtype))
        _emit(gp.probe_xla_take(138493, 32768, r, dtype))
    # r=128: are lane-padded (full-vreg) rows gathered faster per byte?
    _emit(gp.probe_xla_take(26744, 32768, 128, jnp.float32))
    _emit({"metric": "section", "form": "xla_grouped_take"})
    for dtype in (jnp.float32, jnp.bfloat16):
        # group defaults to the dtype's tile height (8 f32 / 16 bf16)
        for rec in gp.probe_xla_grouped_take(26744, 32768, r, dtype):
            _emit(rec)
        for rec in gp.probe_xla_grouped_take(138493, 32768, r, dtype):
            _emit(rec)
    _emit({"metric": "section", "form": "dma_row_gather"})
    for nout in (4096, 32768):
        _emit(gp.probe_dma(26744, nout, r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
