#!/usr/bin/env python
"""pio-scout honesty layer: recall@k + batched-serving latency A/B for
two-stage ANN retrieval vs the exact scan, at synthetic catalog tiers.

An ANN index without a recall gate is a silent-correctness bug waiting
to ship: a config change (nprobe, clusters, candidate_factor) or a
code change to the candidate kernels can tank result quality while
every latency gate stays green.  This bench closes that hole the same
way bench.py closed the train-time one — fenced records in
BENCH_HISTORY.jsonl that tools/bench_gate.py judges:

* ``ann_recall_at_10``      (direction UP, scale = catalog size): mean
  per-query fraction of the exact top-10 the two-stage path returns,
  for the headline mode (``--gate-mode``, default ivf).  The gate
  fails when it drops below baseline - epsilon (the rolling-median -
  max(10%%, 4 sigma) threshold every other metric gets).
* ``ann_serving_p50_ms`` / ``exact_serving_p50_ms`` (direction DOWN,
  scale = catalog size): batched template predict p50 through the REAL
  serving algorithm (`templates.recommendation.ALSAlgorithm.
  batch_predict` — device top-k + host decode, the micro-batcher's
  batch_fn), two-stage vs exact on the same model.  Per-mode detail
  records get a ``_int8``/``_ivf`` metric suffix so trajectories never
  mix.

Catalogs are drawn from a mixture of Gaussians
(:func:`clustered_factors`: cluster centers + per-item noise) because
that is the shape trained ALS item tables actually have (items cluster
by latent genre/popularity directions) — pure iid noise is the known
adversarial case for any coarse-clustering index and would
under-report IVF recall by construction.  The generator + seed ride
every record, so a future rerun reproduces the same catalog.

Timings are host-complete by construction (batch_predict materializes
decoded results per call), hence ``fenced: true``.

Usage: python tools/bench_ann.py [--items 100000,1000000] [--rank 64]
       [--batch 16] [--k 10] [--append-history]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench_gate  # noqa: E402


def clustered_factors(m: int, rank: int, rng,
                      n_centers: int | None = None,
                      noise: float = 0.35) -> np.ndarray:
    """Mixture-of-Gaussians item factors: ``centers[assign] + noise``.
    ``n_centers`` defaults to ~sqrt(m) (matching the IVF auto cluster
    count's order, but drawn independently of the index's k-means — the
    index never sees the generator's labels)."""
    if n_centers is None:
        n_centers = max(int(np.sqrt(m)), 4)
    centers = rng.normal(size=(n_centers, rank)).astype(np.float32)
    assign = rng.integers(0, n_centers, m)
    return (
        centers[assign]
        + noise * rng.normal(size=(m, rank)).astype(np.float32)
    ).astype(np.float32)


def _build_model(items: int, rank: int, users: int, rng):
    from predictionio_tpu.storage.bimap import StringIndex
    from predictionio_tpu.templates.recommendation import ALSModel

    return ALSModel(
        user_factors=rng.normal(size=(users, rank)).astype(np.float32),
        item_factors=clustered_factors(items, rank, rng),
        users=StringIndex([f"u{i}" for i in range(users)]),
        items=StringIndex([f"i{i}" for i in range(items)]),
        item_props={},
    )


def _algo(mode: str, args):
    from predictionio_tpu.templates.recommendation import ALSAlgorithm

    algo = ALSAlgorithm()
    if mode != "exact":
        algo.params = algo.params_class(
            retrieval=mode,
            candidate_factor=args.candidate_factor,
            nprobe=args.nprobe,
            ann_clusters=args.clusters,
        )
    return algo


def _measure_p50(algo, model, queries, reps: int) -> tuple[float, list]:
    """Median batched-predict wall time over ``reps`` calls (first
    call already warmed by the caller); returns (p50_s, last_results).
    """
    lat = np.empty(reps)
    out = None
    for j in range(reps):
        t0 = time.perf_counter()
        out = algo.batch_predict(model, queries)
        lat[j] = time.perf_counter() - t0
    return float(np.percentile(lat, 50)), out


def bench_tier(items: int, args, platform: str) -> list[dict]:
    from predictionio_tpu.templates.recommendation import Query

    rng = np.random.default_rng(args.seed)
    t_build = time.perf_counter()
    model = _build_model(items, args.rank, args.users, rng)
    queries = [
        Query(user=f"u{int(u)}", num=args.k)
        for u in rng.integers(0, args.users, args.batch)
    ]
    records: list[dict] = []
    common = {
        "unit": "ms",
        "platform": platform,
        "scale": float(items),
        "fenced": True,
        "items": items,
        "rank": args.rank,
        "batch": args.batch,
        "k": args.k,
        "catalog": "clustered",
        "seed": args.seed,
    }

    # exact reference: both the recall ground truth and the A side
    exact = _algo("exact", args)
    exact.batch_predict(model, queries)  # warm the executable
    exact_p50, exact_res = _measure_p50(exact, model, queries, args.reps)
    exact_ids = [
        [s.item for s in r.item_scores] for r in exact_res
    ]
    records.append({
        "metric": "exact_serving_p50_ms",
        "value": round(exact_p50 * 1e3, 3),
        "direction": "down",
        **common,
    })
    print(f"# items={items:,} build+warm "
          f"{time.perf_counter() - t_build:.1f}s exact p50 "
          f"{exact_p50 * 1e3:.2f}ms", file=sys.stderr)

    for mode in args.modes:
        t_idx = time.perf_counter()
        algo = _algo(mode, args)
        algo.batch_predict(model, queries)  # builds index + warms
        build_s = time.perf_counter() - t_idx
        p50, res = _measure_p50(algo, model, queries, args.reps)
        ids = [[s.item for s in r.item_scores] for r in res]
        # recall in DECODED id space (ops.ann.recall_at_k's contract,
        # applied after the full serve-path decode — ties and mask
        # semantics included)
        rec_at_k = float(np.mean([
            len(set(e) & set(a)) / max(len(e), 1)
            for e, a in zip(exact_ids, ids)
        ]))
        speedup = exact_p50 / p50 if p50 > 0 else float("inf")
        print(f"#   {mode}: p50 {p50 * 1e3:.2f}ms ({speedup:.2f}x) "
              f"recall@{args.k} {rec_at_k:.4f} "
              f"(index build {build_s:.1f}s)", file=sys.stderr)
        mode_cfg = {
            "retrieval": mode,
            "candidate_factor": args.candidate_factor,
            **({"nprobe": args.nprobe, "clusters": args.clusters}
               if mode == "ivf" else {}),
        }
        records.append({
            "metric": f"ann_serving_p50_ms_{mode}",
            "value": round(p50 * 1e3, 3),
            "direction": "down",
            "speedup_vs_exact": round(speedup, 3),
            "exact_p50_ms": round(exact_p50 * 1e3, 3),
            **mode_cfg, **common,
        })
        records.append({
            "metric": f"ann_recall_at_{args.k}_{mode}",
            "value": round(rec_at_k, 4),
            "direction": "up",
            **{**mode_cfg, **common, "unit": "recall"},
        })
        if mode == args.gate_mode:
            # the headline records the gate judges (acceptance: the
            # plain ann_recall_at_10 / ann_serving_p50_ms keys)
            records.append({
                "metric": f"ann_recall_at_{args.k}",
                "value": round(rec_at_k, 4),
                "direction": "up",
                **{**mode_cfg, **common, "unit": "recall"},
            })
            records.append({
                "metric": "ann_serving_p50_ms",
                "value": round(p50 * 1e3, 3),
                "direction": "down",
                "speedup_vs_exact": round(speedup, 3),
                "exact_p50_ms": round(exact_p50 * 1e3, 3),
                **mode_cfg, **common,
            })
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--items", default="100000,1000000",
                    help="comma-separated catalog tiers (10M wants "
                    "~8 GB host RAM for the f32 + transposed tables)")
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--users", type=int, default=10_000)
    ap.add_argument("--batch", type=int, default=16,
                    help="queries per batched predict (the serving "
                    "micro-batcher's common coalesced size)")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--reps", type=int, default=30,
                    help="timed batch_predict calls per mode")
    ap.add_argument("--modes", default="int8,ivf")
    ap.add_argument("--gate-mode", default="ivf",
                    choices=("int8", "ivf"),
                    help="which mode writes the headline "
                    "ann_recall_at_10 / ann_serving_p50_ms records")
    ap.add_argument("--candidate-factor", type=int, default=10)
    ap.add_argument("--nprobe", type=int, default=8)
    ap.add_argument("--clusters", type=int, default=0,
                    help="0 = auto ~sqrt(items)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--append-history", action="store_true",
                    help="append every record to BENCH_HISTORY.jsonl")
    args = ap.parse_args(argv)
    args.modes = [m.strip() for m in args.modes.split(",") if m.strip()]

    import jax

    platform = jax.default_backend()
    all_records = []
    for tier in (int(x) for x in args.items.split(",")):
        for rec in bench_tier(tier, args, platform):
            print(json.dumps(rec), flush=True)
            all_records.append(rec)
            if args.append_history:
                bench_gate.append_history(bench_gate.DEFAULT_HISTORY, rec)
    # nest the largest tier's headline pair into BENCH_PR<k>.json
    headline = [
        r for r in all_records
        if r["metric"] in (f"ann_recall_at_{args.k}",
                           "ann_serving_p50_ms")
    ]
    if headline:
        try:
            for r in headline[-2:]:
                bench_gate.write_pr_summary(
                    r, key=f"ann_{r['metric']}"
                )
        except Exception as e:
            print(f"# WARNING: could not write bench summary: {e}",
                  file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
