"""Every ALS config A/B in ONE process: one backend init, one synth.

Backend init (~15 s to reach the chip) and the 20M-rating synth dominate
short runs; the per-config ``bench.py --breakdown`` steps pay them once
per config.  This driver pays them once TOTAL: init + synth + holdout
split happen once, then each config stages, warms (compiles), and times
``--steady`` iterations, emitting one JSON line per config — the matrix
ROADMAP S1-S3/D1 decide the ALSConfig path selectors from.

Configs run in value order — the baseline first (everything is a delta
against it), then the single-knob A/Bs, then the best-combo candidates
— so a run cut short still leaves interpretable prefixes.

Usage:
    python tools/breakdown_matrix.py [--scale 1.0] [--steady 3]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


CONFIGS = [
    # (label, ALSConfig overrides, staging)
    ("baseline_xla_f32_highest", {}, "auto"),
    ("solver_pallas", {"solver": "pallas"}, "auto"),
    ("gather_bf16", {"gather_dtype": "bfloat16"}, "auto"),
    ("gather_grouped", {"gather_mode": "grouped"}, "auto"),
    ("gather_grouped_bf16",
     {"gather_mode": "grouped", "gather_dtype": "bfloat16"}, "auto"),
    ("precision_high", {"matmul_precision": "high"}, "auto"),
    # the fused gather+Gram+solve kernel (float32 tables only)
    ("solver_fused", {"solver": "fused"}, "auto"),
    ("best_pallas_bf16_high",
     {"solver": "pallas", "gather_dtype": "bfloat16",
      "matmul_precision": "high"}, "auto"),
    ("best_plus_grouped",
     {"solver": "pallas", "gather_dtype": "bfloat16",
      "matmul_precision": "high", "gather_mode": "grouped"}, "auto"),
    ("best_fused_high",
     {"solver": "fused", "matmul_precision": "high"}, "auto"),
    ("staging_host", {}, "host"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--steady", type=int, default=3,
                    help="timed steady-state iterations per config")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--only", default=None,
                    help="comma-separated config labels to run")
    args = ap.parse_args()

    from bench import synth_ml20m, als_train_flops, device_peak_flops
    from predictionio_tpu.models.als import (
        ALSConfig, ALSFactors, ALSTrainer, rmse,
    )
    from predictionio_tpu.parallel.mesh import (
        enable_compilation_cache, make_mesh,
    )
    import numpy as np

    enable_compilation_cache()
    t0 = time.time()
    u, i, v, n_users, n_items = synth_ml20m(args.scale)
    # same holdout convention as bench --inner: the quality fields ride
    # every config line so the RMSE-conditioned default flips are
    # decidable from this one artifact
    hmask = np.random.default_rng(917).random(len(v)) < 0.02
    uh, ih, vh = u[hmask], i[hmask], v[hmask]
    u, i, v = u[~hmask], i[~hmask], v[~hmask]
    import jax

    print(json.dumps({
        "metric": "matrix_env", "scale": args.scale,
        "n_ratings": len(v), "devices": str(jax.devices()),
        "setup_seconds": round(time.time() - t0, 2),
    }), flush=True)
    mesh = make_mesh()
    mesh = mesh if mesh.size > 1 else None
    peak, kind = device_peak_flops(jax)
    if peak:  # mesh-aggregate roofline, same basis as bench.py
        peak *= mesh.size if mesh is not None else 1

    labels = set(args.only.split(",")) if args.only else None
    for label, overrides, staging in CONFIGS:
        if labels is not None and label not in labels:
            continue
        t0 = time.time()
        trainer = U = V = None
        try:
            cfg = ALSConfig(rank=args.rank, num_iterations=20, lam=0.01,
                            seed=args.seed, **overrides)
            trainer = ALSTrainer((u, i, v), n_users, n_items, cfg,
                                 mesh=mesh, staging=staging)
            U, V = trainer.init_factors()
            U, V = trainer.run(U, V, 1)   # staging wait + compiles
            warm = time.time() - t0
            t1 = time.time()
            U, V = trainer.run(U, V, args.steady)  # returns completed
            span = time.time() - t1
            per_iter = span / args.steady
            factors = ALSFactors(user_factors=np.asarray(U),
                                 item_factors=np.asarray(V))
            flops = als_train_flops(len(v), n_users, n_items, args.rank)
            rec = {
                "metric": "als_config_per_iteration_seconds",
                "config": label,
                "value": round(per_iter, 4),
                "warm_seconds": round(warm, 2),
                "solver": cfg.solver,
                "staging": trainer.staging,
                "achieved_tflops_per_s": round(flops / per_iter / 1e12, 3),
                "mfu": (round(flops / per_iter / peak, 5)
                        if peak else None),
                "device_kind": kind,
                # quality after 1 + steady iterations — NOT a converged
                # 20-iter rmse, but config-comparable: a precision/dtype
                # knob that hurts shows up as a delta vs the baseline row
                "train_rmse": round(rmse(factors, u, i, v), 4),
                "rmse_holdout": (round(rmse(factors, uh, ih, vh), 4)
                                 if len(vh) else None),
            }
        except Exception as e:  # noqa: BLE001 — later configs must run
            rec = {
                "metric": "als_config_per_iteration_seconds",
                "config": label, "value": None,
                "error": repr(e)[:300],
            }
        finally:
            # drop staged device tables even on failure: a dead
            # trainer's HBM must not cascade later configs into OOM
            del trainer, U, V
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
