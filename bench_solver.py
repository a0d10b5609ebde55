"""Batched-SPD-solver benchmark: XLA (cholesky + triangular_solve) vs the
Pallas Cholesky kernel (`ops/solve.py`, the batch on the lanes) vs the
iALS++ subspace sweep's solve phase, on the default accelerator.

The crossover is MEASURED on the real chip, not promised in a
docstring.  Run with the TPU reachable:

    python bench_solver.py                 # full grid, prints a table
    python bench_solver.py --rank 64 --batch 32768   # one cell
    python bench_solver.py --solver subspace --block 16   # sweep cells

Prints one JSON line per (rank, batch) cell:
  {"metric": "spd_solve_batched_ms", "rank": R, "batch": B,
   "xla_ms": ..., "pallas_ms": ..., "speedup": ..., "max_err": ...}
plus, per --block B, a subspace line measuring the SOLVE PHASE of an
iALS++ sweep — ceil(R/B) data-dependent chained batched B×B solves,
the work `ALSConfig(solver_mode="subspace")` dispatches per
half-iteration in place of one batched R×R solve:
  {"metric": "spd_solve_subspace_ms", "rank": R, "batch": B,
   "block": Bk, "n_blocks": ..., "sweep_xla_ms": ...,
   "sweep_pallas_ms": ..., "solve_speedup_vs_full": ...}
and a final summary line naming the fastest per rank.  `[32768, 64, 64]`
on one v5e chip read 264 ms (xla) and 8.0 ms (the kernel) in PR 32
(PERF.md, Findings); `ALSConfig.solver="auto"` takes the kernel on a TPU
for that reason.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, action="append",
                    help="rank(s) to test (default: 10 64 128)")
    ap.add_argument("--batch", type=int, action="append",
                    help="batch size(s) (default: 4096 32768)")
    ap.add_argument("--solver", action="append",
                    choices=("xla", "pallas", "subspace"),
                    help="solver(s) to grid (default: all three); "
                    "'subspace' times the iALS++ sweep's solve phase "
                    "(xla full-solve always runs as the baseline)")
    ap.add_argument("--block", type=int, action="append",
                    help="subspace block width(s) B (default: 16); "
                    "only used with the subspace solver")
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.solve import cholesky_solve_batched

    def xla_solve(A, b):
        L = jax.lax.linalg.cholesky(A)
        y = jax.lax.linalg.triangular_solve(
            L, b[..., None], left_side=True, lower=True
        )
        return jax.lax.linalg.triangular_solve(
            L, y, left_side=True, lower=True, transpose_a=True
        )[..., 0]

    xla_j = jax.jit(xla_solve)
    rng = np.random.default_rng(0)
    ranks = args.rank or [10, 64, 128]
    batches = args.batch or [4096, 32768]
    solvers = tuple(args.solver or ("xla", "pallas", "subspace"))
    blocks = args.block or [16]
    # per rank: solver label -> list of per-batch ms (xla always runs —
    # it is the baseline every speedup/recommendation is measured from)
    times: dict[int, dict[str, list[float]]] = {}

    def note(R, name, ms):
        times.setdefault(R, {}).setdefault(name, []).append(ms)

    for R in ranks:
        for B in batches:
            M = rng.normal(size=(B, R, R)).astype(np.float32)
            A = jax.device_put(
                M @ M.transpose(0, 2, 1)
                + 10 * np.eye(R, dtype=np.float32)
            )
            b = jax.device_put(rng.normal(size=(B, R)).astype(np.float32))

            x1 = jax.block_until_ready(xla_j(A, b))

            # time all reps as one span with a single closing wait, so
            # the per-solve figure excludes per-call host round-trips
            def timed(fn, *operands):
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    x = fn(*operands)
                jax.block_until_ready(x)
                return (time.perf_counter() - t0) / args.reps

            xm = timed(xla_j, A, b) * 1e3
            note(R, "xla", xm)
            if "pallas" in solvers:
                x2 = jax.block_until_ready(cholesky_solve_batched(A, b))
                err = float(jnp.max(jnp.abs(x1 - x2)))
                pm = timed(cholesky_solve_batched, A, b) * 1e3
                note(R, "pallas", pm)
                print(json.dumps({
                    "metric": "spd_solve_batched_ms",
                    "platform": jax.default_backend(),
                    "rank": R, "batch": B,
                    "xla_ms": round(xm, 3), "pallas_ms": round(pm, 3),
                    "speedup": round(xm / pm, 3),
                    "max_err": float(f"{err:.3e}"),
                }), flush=True)
            if "subspace" not in solvers:
                continue
            for blk in blocks:
                if blk >= R:
                    continue
                nb = -(-R // blk)
                # the sweep's solve phase: nb chained batched blk×blk
                # solves (each block's rhs depends on the previous
                # block's solution through the residual update, so the
                # chain is data-dependent — XLA cannot overlap them,
                # matching the real sweep's dispatch structure)
                Ab = jax.device_put(np.ascontiguousarray(
                    np.asarray(A)[:, :blk, :blk]))
                bb = jax.device_put(np.asarray(b)[:, :blk])

                def sweep(solve_fn):
                    def f(Ab, bb):
                        x = bb
                        for _ in range(nb):
                            x = solve_fn(Ab, x)
                        return x
                    return jax.jit(f)

                sweep_x = sweep(xla_solve)
                jax.block_until_ready(sweep_x(Ab, bb))
                sm_x = timed(sweep_x, Ab, bb) * 1e3
                note(R, f"subspace:{blk}", sm_x)
                rec = {
                    "metric": "spd_solve_subspace_ms",
                    "platform": jax.default_backend(),
                    "rank": R, "batch": B, "block": blk, "n_blocks": nb,
                    "full_xla_ms": round(xm, 3),
                    "sweep_xla_ms": round(sm_x, 3),
                }
                if "pallas" in solvers:
                    sweep_p = sweep(cholesky_solve_batched)
                    jax.block_until_ready(sweep_p(Ab, bb))
                    sm_p = timed(sweep_p, Ab, bb) * 1e3
                    note(R, f"subspace-pallas:{blk}", sm_p)
                    rec["sweep_pallas_ms"] = round(sm_p, 3)
                best_sweep = min(
                    [sm_x] + ([sm_p] if "pallas" in solvers else [])
                )
                rec["solve_speedup_vs_full"] = round(xm / best_sweep, 3)
                print(json.dumps(rec), flush=True)

    # recommendation: the lowest mean solve-phase time per rank; names
    # are "xla" | "pallas" | "subspace:B" | "subspace-pallas:B"
    rec = {}
    for R, per in times.items():
        best = min(per, key=lambda name: float(np.mean(per[name])))
        rec[R] = best
    print(json.dumps({"metric": "solver_recommendation",
                      "per_rank": rec}))


if __name__ == "__main__":
    main()
