"""North-star benchmark: MovieLens-20M-scale ALS, rank=64, 20 iterations.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where value
is train wall-clock seconds on the available accelerator and vs_baseline is
baseline_seconds / value (>1 means faster than the 60 s v5e-8 target,
BASELINE.md).  The dataset is synthetic with ML-20M marginals (138,493 users,
26,744 items, 20M ratings, power-law user activity) because the container
has no network egress to fetch the real set; shapes and sparsity structure —
what determines ALS cost — match.

Flags: --scale 0.05 for a quick small run, --iters/--rank to override.

The default invocation measures on the accelerator or not at all.  The
parent process does no jax work; it (1) probes the backend in a
subprocess with a bounded timeout, (2) runs the timed train in a
subprocess (``--inner``) under progress-aware supervision, one attempt
at a time, and (3) exits NON-ZERO, printing no result line, when it
finds no accelerator or every attempt fails.  There is no CPU fallback:
a CPU number is never written under this metric's name.  A run the
operator puts on the CPU (``JAX_PLATFORMS=cpu python bench.py --inner
--scale 0.02``) says ``"platform": "cpu"`` in its line.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent))


def _bench_gate():
    """tools/bench_gate.py (tools/ is scripts, not a package): the
    shared canonical-record/history/PR-summary writer, so this file,
    bench_serving.py and the CI gate all speak one schema."""
    tools_dir = str(Path(__file__).resolve().parent / "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import bench_gate

    return bench_gate

BASELINE_SECONDS = 60.0  # north star: < 60 s on v5e-8 (BASELINE.md)

PROBE_TIMEOUT = 120   # s per attempt: backend init + tiny matmul
PROBE_ATTEMPTS = 3    # retry ladder within TOTAL_BUDGET
# hard wall-clock budget for the WHOLE orchestrated invocation: every
# stage's timeout is clamped to the time remaining
TOTAL_BUDGET = int(os.environ.get("PIO_TPU_BENCH_BUDGET_S", "1020"))

N_USERS = 138_493
N_ITEMS = 26_744
N_RATINGS = 20_000_263


def synth_ml20m(scale: float = 1.0, seed: int = 0):
    """Synthetic ratings with ML-20M-like power-law user activity."""
    rng = np.random.default_rng(seed)
    n_users = max(64, int(N_USERS * scale))
    n_items = max(32, int(N_ITEMS * scale))
    n_ratings = max(1024, int(N_RATINGS * scale))
    # user activity ~ Zipf-ish: weights 1/(rank^0.8), min 20 ratings in full set
    w_u = (1.0 / np.arange(1, n_users + 1) ** 0.8)
    w_u /= w_u.sum()
    u = rng.choice(n_users, size=n_ratings, p=w_u).astype(np.int32)
    # item popularity also power-law
    w_i = (1.0 / np.arange(1, n_items + 1) ** 1.0)
    w_i /= w_i.sum()
    i = rng.choice(n_items, size=n_ratings, p=w_i).astype(np.int32)
    # half-star ratings 0.5..5.0
    v = (rng.integers(1, 11, size=n_ratings) * 0.5).astype(np.float32)
    return u, i, v, n_users, n_items


def als_train_flops(nnz: int, n_users: int, n_items: int, rank: int,
                    iters: int = 1) -> float:
    """Closed-form FLOP count of ``iters`` ALS iterations (both halves):
    Gram accumulation 2·nnz·R² per half, rhs 2·nnz·R per half, one
    (2/3)·R³ dense SPD solve per row per iteration.  Gathers/scatters
    move bytes, not FLOPs — they show up in MFU as lost utilization,
    which is exactly what the metric is for."""
    gram = 2.0 * nnz * rank * rank
    rhs = 2.0 * nnz * rank
    solve = (2.0 / 3.0) * rank ** 3
    per_iter = 2.0 * (gram + rhs) + (n_users + n_items) * solve
    return iters * per_iter


# per-jax-device dense matmul peaks (FLOP/s) by device_kind prefix, at
# the dtype the Gram einsum actually runs on the MXU (bf16-class for
# default/"high", f32 via passes for "highest" — we report against the
# bf16 peak and carry the basis in the record so the number can't be
# silently misread).  Public figures; device_kind strings as the TPU
# runtime reports them.
_PEAK_FLOPS_BF16 = (
    ("TPU v6", 918e12),      # Trillium chip
    ("TPU v5p", 459e12),
    ("TPU v5 lite", 197e12), # v5e
    ("TPU v5e", 197e12),
    ("TPU v4", 275e12),
    ("TPU v3", 61.5e12),     # per jax device (core)
    ("TPU v2", 22.5e12),
)


def device_peak_flops(jax) -> tuple:
    """(peak FLOP/s or None, device_kind).  None for CPU/unknown kinds:
    an unknown peak yields mfu=null rather than a made-up number."""
    try:
        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", dev.platform)
    except Exception:  # noqa: BLE001 — bench must always print a line
        return None, "unknown"
    for prefix, peak in _PEAK_FLOPS_BF16:
        if str(kind).startswith(prefix):
            return peak, str(kind)
    return None, str(kind)


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument(
        "--holdout", type=float, default=0.02,
        help="fraction of ratings held out of training; at full scale "
        "the JSON line carries rmse_holdout next to train_rmse (north "
        "star: RMSE parity, not just speed).  0 disables",
    )
    ap.add_argument("--gather-mode", default=None,
                    choices=("row", "grouped"),
                    help="ALS gather form: plain row take vs tile-"
                    "aligned slab gather + in-slab select (A/B the "
                    "tile-waste hypothesis on-chip)")
    ap.add_argument("--staging", default="auto",
                    choices=("auto", "host", "device"),
                    help="COO staging path: host counting-sort vs compact "
                    "transfer + on-device sort (auto: device at this "
                    "bench's full scale)")
    ap.add_argument("--solver", default=None,
                    choices=("xla", "pallas"),
                    help="batched SPD solver override (default: "
                    "ALSConfig default)")
    ap.add_argument("--solver-mode", default=None,
                    choices=("full", "subspace"),
                    help="rank-sweep strategy: 'full' = R×R solve per "
                    "row, 'subspace' = iALS++ block sweep "
                    "(ALSConfig.solver_mode)")
    ap.add_argument("--subspace-block", type=int, default=None,
                    metavar="B",
                    help="block width of the subspace sweep "
                    "(ALSConfig.subspace_size; default 16)")
    ap.add_argument("--precision", default=None,
                    choices=("highest", "high", "default"),
                    help="Gram-einsum MXU precision override "
                    "(highest=f32, high=bf16x3, default=bf16)")
    ap.add_argument("--verbose", action="store_true")
    ap.add_argument(
        "--inner",
        action="store_true",
        help="run the timed train in THIS process (no probe/subprocess "
        "supervision); used by the default orchestrated invocation",
    )
    ap.add_argument(
        "--profile",
        metavar="DIR",
        help="with --breakdown: capture a jax profiler trace of the "
        "steady-state iterations into DIR (TensorBoard/Perfetto)",
    )
    ap.add_argument(
        "--breakdown",
        action="store_true",
        help="also time each phase (host bucketing, device staging, "
        "compile, per-side half-iterations) — the bottleneck data the "
        "perf note needs; implies --inner semantics",
    )
    ap.add_argument(
        "--parity",
        action="store_true",
        help="run the small-scale RMSE parity check against the dense "
        "NumPy oracle that encodes the MLlib ALS conventions "
        "(tests/test_als.py) and print its JSON line; the quality half "
        "of the north star, as a recordable artifact",
    )
    ap.add_argument(
        "--parity-northstar",
        action="store_true",
        help="the parity check AT the north-star config — rank 64, "
        "20 iterations, ML-20M scale (scaled by --scale), low-rank "
        "ground-truth ratings so holdout RMSE is meaningful — vs the "
        "same shared oracle, untimed, CPU-friendly; writes "
        "BENCH_PARITY_R64.json (VERDICT r4 #3)",
    )
    ap.add_argument(
        "--pipeline",
        action="store_true",
        help="run the PRODUCT data path end to end — ratings file -> "
        "native import -> sqlite -> columnar scan -> id encode -> "
        "train — and print one JSON line with per-stage seconds; "
        "proves the import/scan/train throughput claims compose at "
        "scale (the in-memory synth of the default bench skips the "
        "storage path)",
    )
    ap.add_argument(
        "--phase-probe",
        action="store_true",
        help="with --breakdown: additionally time gather-only / "
        "gather+gram / full-solve variants of the user half-iteration "
        "to localize the per-iteration cost",
    )
    ap.add_argument(
        "--straggler-ab",
        action="store_true",
        help="fenced clean-vs-straggler A/B of the coded sharded "
        "sweep (pio-armor): times one clean coded sweep and one with a "
        "deterministically delayed shard per half (parity serve), and "
        "appends the fenced als_sweep_straggler_overhead_ratio record "
        "to BENCH_HISTORY.jsonl so tools/bench_gate.py gates parity "
        "overhead like any other metric; needs a multi-device mesh "
        "and refuses without one",
    )
    args = ap.parse_args(argv)
    if args.phase_probe and not args.breakdown:
        ap.error("--phase-probe requires --breakdown")
    return args


def _prepare(args):
    """Shared --inner/--breakdown setup: backend-touching imports,
    compilation cache, synthetic data, mesh, config.  One place so both
    paths always measure an identically-configured trainer."""
    import jax

    from predictionio_tpu.models.als import ALSConfig
    from predictionio_tpu.parallel.mesh import (
        enable_compilation_cache, make_mesh,
    )

    enable_compilation_cache()
    u, i, v, n_users, n_items = synth_ml20m(args.scale)
    # always a marker, not verbose-gated: the supervised orchestrator
    # reads "# " stderr lines as proof of progress
    print(
        f"# {len(v):,} ratings, {n_users:,} users x {n_items:,} items, "
        f"devices={jax.devices()}",
        file=sys.stderr, flush=True,
    )
    mesh = make_mesh()
    mesh = mesh if mesh.size > 1 else None
    extra = {}
    if args.solver:
        extra["solver"] = args.solver
    if args.precision:
        extra["matmul_precision"] = args.precision
    if args.solver_mode:
        extra["solver_mode"] = args.solver_mode
    if args.subspace_block is not None:
        extra["subspace_size"] = args.subspace_block
    cfg = ALSConfig(
        rank=args.rank, num_iterations=args.iters, lam=0.01,
        seed=args.seed, gather_mode=args.gather_mode or "row",
        **extra,
    )
    return jax, (u, i, v, n_users, n_items), mesh, cfg


def run_breakdown(args) -> None:
    """Phase-by-phase timing of the north-star train ('what's the
    bottleneck: solves, gathers, or scatter?').  Prints one JSON line
    per phase.

    Every phase boundary is a ``jax.block_until_ready``.  Steady state
    is timed as ONE span over iters-1 iterations with a single closing
    wait, so the per-iteration figure isn't polluted by per-step host
    round-trips."""
    t0 = time.time()
    jax, (u, i, v, n_users, n_items), mesh, cfg = _prepare(args)
    from predictionio_tpu.models.als import ALSTrainer
    from predictionio_tpu.obs import TRAIN_PHASE_SECONDS

    def emit(phase, seconds, **kw):
        # every phase measurement also lands in the SAME
        # pio_train_phase_seconds histogram family the workflow spans
        # feed, so a bench run and a production train emit one metric
        # schema (ALX-style comparability) instead of private timers
        TRAIN_PHASE_SECONDS.labels(phase=f"bench.{phase}").observe(seconds)
        print(json.dumps({"metric": "als_phase_seconds", "phase": phase,
                          "value": round(seconds, 4), **kw}), flush=True)

    emit("setup_and_synth_data", time.time() - t0)

    t0 = time.time()
    trainer = ALSTrainer((u, i, v), n_users, n_items, cfg, mesh=mesh,
                         staging=args.staging)
    emit("bucketize_and_stage_dispatch", time.time() - t0,
         staging=trainer.staging,
         **(
             {"transfer_bytes": trainer.staged_transfer_bytes,
              "bytes_per_rating": round(
                  trainer.staged_transfer_bytes / max(len(v), 1), 2)}
             if getattr(trainer, "staged_transfer_bytes", None) else {}
         ))

    t0 = time.time()
    U, V = jax.block_until_ready(trainer.init_factors())
    emit("init_factors", time.time() - t0)

    # first compile + wait for staged arrays: one half-iteration per side
    t0 = time.time()
    U1 = jax.block_until_ready(trainer._half(U, V, trainer._user_side))
    emit("user_half_first_incl_compile_and_staging", time.time() - t0)
    t0 = time.time()
    V1 = jax.block_until_ready(trainer._half(V, U1, trainer._item_side))
    emit("item_half_first_incl_compile", time.time() - t0)

    import contextlib

    prof = (
        jax.profiler.trace(args.profile)
        if args.profile
        else contextlib.nullcontext()
    )
    n_steady = max(args.iters - 1, 1)
    with prof:
        t0 = time.time()
        Us, Vs = trainer.run(U1, V1, n_steady)   # run() returns completed
        span = time.time() - t0
    if args.profile:
        print(json.dumps({"metric": "profile_trace_dir",
                          "value": args.profile}), flush=True)
    per_iter = span / n_steady
    emit("steady_iteration", per_iter, n=n_steady, total=round(span, 4))
    nnz = len(v)
    flops_iter = als_train_flops(nnz, n_users, n_items, args.rank)
    achieved = flops_iter / per_iter
    peak, kind = device_peak_flops(jax)
    # aggregate mesh peak, not one device's: the trainer shards the
    # work, so per-device peak would overstate MFU by the device count
    n_dev = mesh.size if mesh is not None else 1
    if peak:
        peak *= n_dev
    print(json.dumps({
        "metric": "als_derived_tflops_per_s",
        "value": round(achieved / 1e12, 3),
        # MFU vs the mesh's bf16 matmul peak: the roofline context that
        # turns a phase split into "we are at X% of this silicon"
        # without a human decoding it (VERDICT r4 #4)
        "mfu": round(achieved / peak, 5) if peak else None,
        "peak_tflops_bf16": round(peak / 1e12, 1) if peak else None,
        "device_kind": kind,
        "n_devices": n_dev,
        "platform": str(jax.devices()[0].platform),
    }), flush=True)

    if args.phase_probe:
        _run_phase_probe(jax, trainer, Us, Vs, cfg, emit)


def _run_phase_probe(jax, trainer, U, V, cfg, emit) -> None:
    """Time truncated variants of the user half-iteration.

    ``gather_only`` stops after the [B, K, R] gather+mask expansion,
    ``gather_gram`` adds the Gram/rhs einsums and regularization,
    ``full_half`` is the real `_half` including solves AND the
    factor-table scatter.  The truncations run the REAL kernel
    (`models/als._solve_buckets` with ``stop_after``), so implicit mode,
    weighted-λ, precision, gather mode, and solver choice are all
    whatever the trainer is configured with — the deltas attribute the
    per-iteration time to gather vs MXU vs solver vs scatter, the
    decision data for docs/ARCHITECTURE.md 'Measured performance'.
    """
    import functools

    import jax.numpy as jnp

    from predictionio_tpu.models.als import _solve_buckets

    side = trainer._user_side

    @functools.partial(jax.jit, static_argnames=("ks", "stop_after"))
    def probe(upd_tab, opp, buckets, lam, alpha, *, ks, stop_after):
        # upd_tab: the current factor table — subspace mode's "gram"
        # probe warm-starts its block sweep from it, so the measured
        # Gram phase includes the residual/prediction cache builds the
        # real sweep pays
        return _solve_buckets(
            None, opp, buckets, lam, alpha,
            ks=ks, implicit=cfg.implicit,
            weighted_lambda=cfg.weighted_lambda,
            precision=cfg.matmul_precision, solver=cfg.solver,
            gather_mode=cfg.gather_mode, solver_mode=cfg.solver_mode,
            subspace_size=cfg.subspace_size,
            upd_table=upd_tab, stop_after=stop_after,
        )

    lam = jnp.asarray(cfg.lam, jnp.float32)
    alpha = jnp.asarray(cfg.alpha, jnp.float32)

    def timed(fn):
        jax.block_until_ready(fn())
        t0 = time.time()
        for _ in range(3):
            out = fn()
        jax.block_until_ready(out)
        return (time.time() - t0) / 3

    for stop in ("gather", "gram"):
        emit(
            f"user_half_probe_{stop}",
            timed(lambda: probe(
                U, V, side["buckets"], lam, alpha, ks=side["ks"],
                stop_after=stop,
            )),
            **(
                {"solver_mode": cfg.solver_mode,
                 "subspace_size": cfg.subspace_size}
                if cfg.solver_mode == "subspace" else {}
            ),
        )
    # the full half-iteration donates its first argument; feed copies
    emit(
        "user_half_probe_full_half",
        timed(lambda: trainer._half(jnp.array(U, copy=True), V,
                                    trainer._user_side)),
    )


def run_straggler_ab(args) -> None:
    """Fenced clean-vs-straggler A/B of the coded sharded sweep.

    Stages ONE dataset into a coded sharded trainer
    (``factor_placement="sharded", coded_shards=True``), then times —
    fenced, warm-first, identical staged data — (a) a clean coded sweep
    and (b) the same sweep with ONE shard deterministically flagged
    late on every half (``dist.shard_delay`` with zero injected lag, so
    the measurement is the parity-serve COMPUTE overhead: the masked
    gather, the reconstruction psum, and the frozen-write select — not
    the straggler's wait, which the whole feature exists to avoid).
    The ratio lands in BENCH_HISTORY.jsonl as the fenced
    ``als_sweep_straggler_overhead_ratio`` record (direction: down),
    so ``tools/bench_gate.py`` gates parity overhead like any other
    trajectory metric.

    Needs a multi-device mesh and REFUSES without one (exit 2): it
    measures the devices jax gives it, never a stand-in.  The simulated
    cluster tier-1 certifies is asked for in the open:
    ``JAX_PLATFORMS=cpu
    XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    jax, (u, i, v, n_users, n_items), mesh, cfg0 = _prepare(args)
    import dataclasses

    from predictionio_tpu.models.als import ALSConfig, ALSTrainer
    from predictionio_tpu.resilience import faults

    if mesh is None:
        print("# ERROR: --straggler-ab needs a multi-device mesh; jax "
              f"sees {jax.devices()}", file=sys.stderr, flush=True)
        sys.exit(2)

    base = {
        f.name: getattr(cfg0, f.name) for f in dataclasses.fields(cfg0)
    }
    base.update(factor_placement="sharded", coded_shards=True)
    # the measured sweep: short, repeated — the ratio is per-sweep and
    # the staged data is identical across arms
    sweep_iters = max(2, min(args.iters, 4))
    base.update(num_iterations=sweep_iters)
    cfg = ALSConfig(**base)
    trainer = ALSTrainer((u, i, v), n_users, n_items, cfg, mesh=mesh,
                         )
    assert trainer.coded, "coded trainer did not engage"
    U0, V0 = trainer.init_factors()
    reps = 5
    platform = str(jax.default_backend())
    # one shard late on EVERY half: zero injected lag isolates the
    # parity-serve compute overhead (reconstruction + frozen writes)
    plan = "dist.shard_delay:shard=1,delay=0"

    def sweep_s():
        t0 = time.time()
        trainer.run(U0, V0, sweep_iters)   # returns completed arrays
        return time.time() - t0

    # warm: compile the coded halves (the degraded executable is the
    # SAME program — the mask is a traced operand), then interleave the
    # arms per rep so clock drift and cache state cancel instead of
    # biasing whichever arm ran second
    trainer.run(U0, V0, 1)
    faults.arm(plan)
    trainer.run(U0, V0, 1)
    clean_t, strag_t = [], []
    for _ in range(reps):
        faults.disarm()
        clean_t.append(sweep_s())
        faults.arm(plan)
        strag_t.append(sweep_s())
    faults.disarm()
    t_clean = float(np.median(clean_t))
    t_strag = float(np.median(strag_t))

    ratio = t_strag / t_clean if t_clean > 0 else None
    rec = {
        "metric": "als_sweep_straggler_overhead_ratio",
        "value": round(ratio, 4) if ratio else None,
        "unit": "ratio",
        "platform": platform,
        "scale": args.scale,
        "fenced": True,
        "direction": "down",
        "rank": cfg.rank,
        "sweep_iters": sweep_iters,
        "mesh_devices": int(mesh.size),
        "n_ratings": int(len(v)),
        "clean_sweep_s": round(t_clean, 5),
        "straggler_sweep_s": round(t_strag, 5),
        "degraded_polls": trainer.shard_health.degraded_polls,
    }
    print(json.dumps(rec), flush=True)
    try:
        gate = _bench_gate()
        gate.append_history(HISTORY_PATH, rec)
        gate.write_pr_summary(rec, key="straggler_ab")
    except Exception as e:  # noqa: BLE001 — the print already landed
        print(f"# WARNING: could not record straggler A/B: {e}",
              file=sys.stderr, flush=True)


def run_inner(args) -> None:
    """The actual timed train: stages, warms up, trains, prints the JSON."""
    # markers may declare how long the NEXT silent stretch is allowed to
    # take (next-phase-budget=N); the supervisor widens its stall window
    # accordingly
    print("# bench inner start next-phase-budget=420 (backend init + "
          "synth)", file=sys.stderr, flush=True)
    jax, (u, i, v, n_users, n_items), mesh, cfg = _prepare(args)
    from predictionio_tpu.models.als import ALSFactors, ALSTrainer, rmse

    # hold-out split (ML convention): the timed train sees only the
    # training portion; the JSON line carries BOTH rmses at full scale
    # so a wrong-but-fast config can't post a headline number and
    # quality regressions show up as generalization, not just fit
    hold_frac = max(args.holdout, 0.0)
    if hold_frac > 0:
        hmask = np.random.default_rng(917).random(len(v)) < hold_frac
        uh, ih, vh = u[hmask], i[hmask], v[hmask]
        u, i, v = u[~hmask], i[~hmask], v[~hmask]
    else:
        uh = ih = vh = np.empty(0, np.int32)

    # warmup: compile both half-iteration executables (one per direction)
    print("# next-phase-budget=420 (staging + first compiles)",
          file=sys.stderr, flush=True)
    warm = ALSTrainer((u, i, v), n_users, n_items, cfg, mesh=mesh,
                      staging=args.staging)
    print(f"# warm trainer staged (staging={warm.staging}) "
          "next-phase-budget=420 (first compiles)",
          file=sys.stderr, flush=True)
    wU, wV = warm.init_factors()
    warm.run(wU, wV, 1)
    del warm, wU, wV
    # the sweep loop itself waits once per half (always-on sweep
    # telemetry), so dt is a sequence of device-complete sweeps.  It is
    # still one long silent stretch host-side: declare its budget
    # instead of emitting heartbeats
    print("# warm iteration done (compiles cached); timed train starts "
          "next-phase-budget=600", file=sys.stderr, flush=True)

    # timed: full train — staging + 20 iterations (compiles now cached).
    # trainer.run() returns completed arrays, so dt includes the full
    # device execution, not just dispatch
    t0 = time.time()
    trainer = ALSTrainer((u, i, v), n_users, n_items, cfg, mesh=mesh,
                         staging=args.staging)
    U, V = trainer.init_factors()
    U, V = trainer.run(U, V, cfg.num_iterations)
    dt = time.time() - t0
    factors = ALSFactors(user_factors=np.asarray(U),
                         item_factors=np.asarray(V))

    full_scale = args.scale >= 1.0
    # quality fields ride EVERY record that split a holdout, not only
    # full-scale ones
    train_rmse = rmse(factors, u, i, v)
    rmse_holdout = rmse(factors, uh, ih, vh) if len(vh) else None
    # explain-or-gate (VERDICT r4 weak #2): this bench's synthetic
    # ratings are STRUCTURELESS (uniform half-stars, synth_ml20m), so
    # holdout RMSE cannot beat the predict-the-train-mean baseline and
    # rank-64/λ=0.01 overfits noise past it — the number certifies the
    # holdout plumbing, not model quality.  Quality parity lives in
    # BENCH_PARITY.json (low-rank ground truth).  Carrying the baseline
    # in the same line makes that readable without a human decoding it.
    holdout_mean_baseline = (
        float(np.sqrt(np.mean((vh - float(np.mean(v))) ** 2)))
        if len(vh) else None
    )
    # roofline context (VERDICT r4 #4): achieved FLOP/s over the WHOLE
    # timed span (staging + init + train — the span the 60 s target
    # covers) and MFU vs the chip's bf16 peak; null mfu on CPU/unknown
    total_flops = als_train_flops(len(v), n_users, n_items, cfg.rank,
                                  cfg.num_iterations)
    achieved_flops = total_flops / dt
    peak_flops, device_kind = device_peak_flops(jax)
    # the train shards across the whole mesh, so the roofline is the
    # MESH's aggregate peak — a per-device peak would overstate MFU by
    # the device count on any multi-chip run
    n_dev = mesh.size if mesh is not None else 1
    if peak_flops:
        peak_flops *= n_dev
    if args.verbose:
        print(f"# train RMSE {train_rmse:.4f}, wall {dt:.2f}s",
              file=sys.stderr)

    print(
        json.dumps(
            {
                "metric": "ml20m_als_rank64_20iter_train_seconds",
                "value": round(dt, 3),
                "unit": "s",
                # only a full-scale run is comparable to the 60 s target
                "vs_baseline": (
                    round(BASELINE_SECONDS / dt, 3)
                    if full_scale
                    else None
                ),
                "platform": jax.default_backend(),
                "scale": args.scale,
                "staging": trainer.staging,
                "solver": cfg.solver,
                "solver_mode": cfg.solver_mode,
                **(
                    {"subspace_size": cfg.subspace_size}
                    if cfg.solver_mode == "subspace" else {}
                ),
                "precision": cfg.matmul_precision,
                "gather_mode": cfg.gather_mode,
                # the timed train covers the (1-holdout) split; recorded
                # so the workload identity is explicit in every artifact
                # (no fenced full-scale history predates this field, so
                # no prior record is silently re-scaled)
                "holdout": hold_frac,
                "n_ratings_trained": int(len(v)),
                "achieved_tflops_per_s": round(achieved_flops / 1e12, 4),
                "mfu": (
                    round(achieved_flops / peak_flops, 5)
                    if peak_flops else None
                ),
                "device_kind": device_kind,
                "n_devices": n_dev,
                **(
                    {"train_rmse": round(train_rmse, 4)}
                    if train_rmse is not None else {}
                ),
                **(
                    {
                        "rmse_holdout": round(rmse_holdout, 4),
                        "rmse_holdout_mean_baseline": round(
                            holdout_mean_baseline, 4
                        ),
                        "holdout_note": (
                            "synthetic ratings are structureless; "
                            "holdout rmse has a noise floor at the "
                            "mean baseline and small-lambda rank-64 "
                            "overfits past it — quality parity is "
                            "certified by BENCH_PARITY.json, not "
                            "this field"
                        ),
                    }
                    if rmse_holdout is not None else {}
                ),
            }
        )
    )


def run_parity(args) -> None:
    """RMSE parity vs the dense NumPy oracle at a verifiable scale.

    The oracle re-implements the exact MLlib ALS conventions the parity
    tests encode (ALS-WR weighted-λ normal equations, identical PRNG
    init; tests/test_als.py::_reference_als_explicit): at 400x250 it is
    small enough to solve densely row-by-row, which makes the recorded
    number independently checkable.  Ratings come from a noisy low-rank
    ground truth so hold-out RMSE is meaningful.  Prints one JSON line —
    the quality-parity artifact next to the wall-clock one (north star:
    "RMSE parity with Spark MLlib ALS at same rank/iters/lambda").
    """
    import jax

    from predictionio_tpu.models.als import (
        ALSConfig, ALSFactors, rmse, train_als,
    )

    rng = np.random.default_rng(7)
    n_users, n_items, rank_true = 400, 250, 5
    Ut = rng.normal(size=(n_users, rank_true))
    Vt = rng.normal(size=(n_items, rank_true))
    R = Ut @ Vt.T + 0.1 * rng.normal(size=(n_users, n_items))
    mask = rng.random((n_users, n_items)) < 0.3
    u, i = np.nonzero(mask)
    v = R[u, i].astype(np.float32)
    u, i = u.astype(np.int32), i.astype(np.int32)
    hold = rng.random(len(v)) < 0.1
    ut, it_, vt = u[~hold], i[~hold], v[~hold]
    uh, ih, vh = u[hold], i[hold], v[hold]

    cfg = ALSConfig(rank=16, num_iterations=10, lam=0.01, seed=3)
    ours = train_als((ut, it_, vt), n_users, n_items, cfg)

    # THE shared oracle (tools/mllib_oracle.py — also what
    # tests/test_als.py compares against, and itself pinned by the
    # closed-form rank-2 self-check there): identical init, identical
    # ALS-WR conventions, independent per-row dense implementation
    from tools.mllib_oracle import reference_als

    U, V = reference_als(ut, it_, vt, n_users, n_items, cfg)
    oracle = ALSFactors(user_factors=U, item_factors=V)

    ho_tpu = rmse(ours, uh, ih, vh)
    ho_orc = rmse(oracle, uh, ih, vh)
    rec = {
        "metric": "als_rmse_parity_vs_mllib_oracle",
        "rank": cfg.rank, "iters": cfg.num_iterations, "lam": cfg.lam,
        "n_train": int(len(vt)), "n_holdout": int(len(vh)),
        "rmse_train_tpu": round(rmse(ours, ut, it_, vt), 5),
        "rmse_train_oracle": round(rmse(oracle, ut, it_, vt), 5),
        "rmse_holdout_tpu": round(ho_tpu, 5),
        "rmse_holdout_oracle": round(ho_orc, 5),
        "holdout_delta": round(abs(ho_tpu - ho_orc), 5),
        "platform": jax.default_backend(),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    # driver-readable artifact next to the BENCH output (round-3
    # verdict: the parity evidence lived only in ARCHITECTURE.md prose)
    PARITY_PATH.write_text(json.dumps(rec, indent=1) + "\n")
    print(json.dumps(rec))


def run_parity_northstar(args) -> None:
    """RMSE parity vs the shared oracle AT the north-star config:
    rank 64, 20 iterations, λ=0.01, ML-20M-scale sparsity pattern
    (power-law users/items like ``synth_ml20m``), but rating VALUES
    from a noisy low-rank ground truth — unlike the wall-clock bench's
    structureless ratings, holdout RMSE here measures real
    generalization, so "holdout_delta ≈ 0 at rank 64 full scale" is
    the quality half of BASELINE.md's north star as one artifact
    (VERDICT r4 #3: the round-4 parity evidence was rank 16 / 27k
    ratings).  Untimed: the oracle is a single-core python row loop —
    correctness evidence, not a benchmark."""
    import jax

    from predictionio_tpu.models.als import ALSConfig, ALSFactors, rmse, train_als
    from tools.mllib_oracle import reference_als

    # sparsity pattern at bench scale; values from low-rank truth
    u, i, _, n_users, n_items = synth_ml20m(args.scale)
    rng = np.random.default_rng(7)
    rank_true = 16
    Ut = rng.normal(size=(n_users, rank_true)).astype(np.float32)
    Vt = rng.normal(size=(n_items, rank_true)).astype(np.float32)
    v = (
        np.einsum("nr,nr->n", Ut[u], Vt[i]) / np.sqrt(rank_true)
        + 0.1 * rng.normal(size=len(u)).astype(np.float32)
    ).astype(np.float32)

    hold = rng.random(len(v)) < 0.05
    ut, it_, vt = u[~hold], i[~hold], v[~hold]
    uh, ih, vh = u[hold], i[hold], v[hold]

    cfg = ALSConfig(rank=args.rank, num_iterations=args.iters,
                    lam=0.01, seed=3)
    t0 = time.time()
    ours = train_als((ut, it_, vt), n_users, n_items, cfg)
    t_ours = time.time() - t0
    print(f"# trainer done in {t_ours:.1f}s", file=sys.stderr, flush=True)

    t0 = time.time()
    U, V = reference_als(
        ut, it_, vt, n_users, n_items, cfg,
        progress=lambda it: print(
            f"# oracle iteration {it + 1}/{cfg.num_iterations} "
            f"({time.time() - t0:.0f}s)", file=sys.stderr, flush=True
        ),
    )
    oracle = ALSFactors(user_factors=U, item_factors=V)

    ho_tpu = rmse(ours, uh, ih, vh)
    ho_orc = rmse(oracle, uh, ih, vh)
    delta = abs(ho_tpu - ho_orc)
    rec = {
        "metric": "als_rmse_parity_vs_mllib_oracle_northstar",
        "rank": cfg.rank, "iters": cfg.num_iterations, "lam": cfg.lam,
        "scale": args.scale, "rank_true": rank_true,
        "n_train": int(len(vt)), "n_holdout": int(len(vh)),
        "n_users": int(n_users), "n_items": int(n_items),
        "rmse_train_tpu": round(rmse(ours, ut, it_, vt), 5),
        "rmse_train_oracle": round(rmse(oracle, ut, it_, vt), 5),
        "rmse_holdout_tpu": round(ho_tpu, 5),
        "rmse_holdout_oracle": round(ho_orc, 5),
        "holdout_delta": round(delta, 5),
        "parity": bool(delta < 0.02),
        "trainer_seconds_untimed_context": round(t_ours, 1),
        "platform": jax.default_backend(),
        "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    # always its own artifact: BENCH_PARITY.json stays the small
    # verifiable-config record; a smoke invocation of this mode must
    # not clobber it
    PARITY_R64_PATH.write_text(json.dumps(rec, indent=1) + "\n")
    print(json.dumps(rec))


def run_pipeline(args) -> None:
    """The full product data path at bench scale, stage by stage.

    The default bench synthesizes the COO in memory; users reach
    training through import -> store -> scan (reference:
    `tools/.../imprt/FileToEvents.scala:30-95` feeding HBase feeding
    `PEventStore.find`).  This measures that path composed: a
    MovieLens-format ratings file is imported through the native
    scanner's raw-row fast path into sqlite, scanned columnar
    (`minimal=True`), id-encoded, and trained.  One JSON line with
    per-stage seconds so no stage can hide inside another's number.
    """
    import shutil
    import tempfile

    jax, (u, i, v, n_users, n_items), mesh, cfg = _prepare(args)
    from predictionio_tpu.models.als import ALSTrainer, rmse
    from predictionio_tpu.storage.sqlite_events import SQLiteEventStore
    from predictionio_tpu.tools.import_export import import_ratings_csv

    stages: dict[str, float] = {}
    tmp = tempfile.mkdtemp(prefix="pio_pipeline_bench_")
    try:
        # stage 0 (uncounted toward the pipeline: the user already has
        # their file): write the synthetic ratings as MovieLens CSV
        t0 = time.time()
        csv = Path(tmp) / "ratings.csv"
        with open(csv, "w") as f:
            for s in range(0, len(v), 1 << 20):
                e = min(s + (1 << 20), len(v))
                np.savetxt(
                    f,
                    np.stack(
                        [u[s:e], i[s:e], v[s:e]], axis=1
                    ),
                    fmt=["%d", "%d", "%.1f"],
                    delimiter="::",
                )
        stages["write_source_file"] = round(time.time() - t0, 3)

        t0 = time.time()
        store = SQLiteEventStore(str(Path(tmp) / "events.db"))
        n_imported = import_ratings_csv(csv, store, app_id=1)
        stages["import"] = round(time.time() - t0, 3)

        t0 = time.time()
        # fused native scan+encode when the store offers it (C pass
        # over the sqlite B-tree building the id dictionaries in-scan,
        # native/sqlite_scan.cpp); recorded as one stage
        scan_path = None
        if hasattr(store, "find_ratings"):
            ratings = store.find_ratings(app_id=1, event_names=("rate",),
                                         rating_property="rating",
                                         dedup="last")
            stages["scan_and_encode_fused"] = round(time.time() - t0, 3)
            scan_path = store.last_ratings_scan_path
        else:
            frame = store.find_columnar(
                app_id=1, event_names=["rate"], float_property="rating",
                minimal=True,
            )
            stages["scan_columnar"] = round(time.time() - t0, 3)
            t0 = time.time()
            ratings = frame.to_ratings(rating_property="rating",
                                       dedup="last")
            stages["encode_ids"] = round(time.time() - t0, 3)

        t0 = time.time()
        trainer = ALSTrainer(ratings, cfg=cfg, mesh=mesh,
                             staging=args.staging)
        U, V = trainer.init_factors()
        U, V = trainer.run(U, V, cfg.num_iterations)
        stages["train"] = round(time.time() - t0, 3)

        factors = trainer._factors(U, V)
        err = rmse(factors, ratings.user_ix, ratings.item_ix,
                   ratings.rating)
        store.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    pipeline_total = sum(
        sec for name, sec in stages.items() if name != "write_source_file"
    )
    print(json.dumps({
        "metric": "ml20m_pipeline_file_to_model_seconds",
        "value": round(pipeline_total, 3),
        "unit": "s",
        "stages": stages,
        "n_events": int(n_imported),
        **({"scan_path": scan_path} if scan_path else {}),
        "import_events_per_s": (
            round(n_imported / stages["import"], 1)
            if stages["import"] else None
        ),
        "train_rmse": round(err, 4),
        "platform": jax.default_backend(),
        "scale": args.scale,
        "solver": cfg.solver,
    }))


def _probe_accelerator(timeout: int = PROBE_TIMEOUT):
    """Init the default jax backend in a subprocess; returns
    ``(platform, None)`` for an accelerator or ``(None, why)`` when init
    fails, hangs, or resolves to the CPU."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "x = jnp.ones((256, 256))\n"
        "assert float((x @ x)[0, 0]) == 256.0\n"
        "print('PLATFORM=' + jax.default_backend())\n"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        return None, "backend init timed out after %ds" % timeout
    for line in proc.stdout.splitlines():
        if line.startswith("PLATFORM="):
            platform = line.split("=", 1)[1]
            if platform != "cpu":
                return platform, None
            return None, "backend resolved to cpu (no accelerator)"
    return None, (proc.stderr.strip().splitlines() or ["backend init failed"])[-1]


def _inner_cmd(extra_args):
    """The ``bench.py --inner`` command line (tests substitute a stub)."""
    return [
        sys.executable, str(Path(__file__).resolve()), "--inner"
    ] + extra_args


def _extract_result(stdout_text, stderr_lines):
    """(json_line, err) from a finished child's captured output."""
    for line in (stdout_text or "").splitlines():
        if line.startswith("{"):
            return line, None
    tail = [ln.strip() for ln in stderr_lines if ln.strip()]
    return None, (tail or ["no output"])[-1]


# kill an accelerator attempt only when it stops PROGRESSING for this
# long: a killed attempt wastes its whole backend init and compiles
STALL_TIMEOUT = int(os.environ.get("PIO_TPU_BENCH_STALL_S", "330"))


def _run_inner_supervised(extra_args, hard_cap, stall_timeout=None):
    """Run ``bench.py --inner`` with progress-aware supervision.

    The child is killed only when (a) no ``# `` progress marker has
    appeared on its stderr for the current stall window, or (b)
    ``hard_cap`` expires.  Stage markers are printed by ``run_inner`` at
    every phase boundary (inner start → backend init/synth → warm staged
    → compiles done → timed train), so a slow-but-advancing attempt
    survives, while a hung backend init dies in one stall window
    instead of eating the whole budget.  A marker may carry
    ``next-phase-budget=N`` to widen the window for a known-long silent
    phase (backend init, the timed train) — still clamped by
    ``hard_cap``.  Returns (json_line, err)."""
    import re
    import threading

    stall = STALL_TIMEOUT if stall_timeout is None else stall_timeout
    cmd = _inner_cmd(extra_args)
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    state = {"last_progress": time.time(), "stderr": [], "allow": stall}

    def _drain():
        for ln in proc.stderr:
            state["stderr"].append(ln)
            if ln.startswith("# "):
                state["last_progress"] = time.time()
                m = re.search(r"next-phase-budget=(\d+)", ln)
                # each declared budget covers ONE phase: reset to the
                # default at the next marker unless it declares its own
                state["allow"] = int(m.group(1)) if m else stall
            sys.stderr.write(ln)
            sys.stderr.flush()

    t = threading.Thread(target=_drain, daemon=True)
    t.start()
    start = time.time()
    why = None
    while proc.poll() is None:
        now = time.time()
        if now - start > hard_cap:
            why = f"hard cap {hard_cap}s"
            break
        if now - state["last_progress"] > max(state["allow"], stall):
            why = (
                f"no progress for {state['allow']}s "
                f"(ran {int(now - start)}s total)"
            )
            break
        time.sleep(1.0)
    if why is not None:
        proc.kill()
        proc.wait()
        # the child may have PRINTED its JSON line and hung in teardown:
        # a completed measurement must survive the kill
        try:
            out = proc.stdout.read() if proc.stdout else ""
        except Exception:  # noqa: BLE001
            out = ""
        line, _ = _extract_result(out, [])
        if line is not None:
            return line, None
        return None, f"killed: {why}"
    out = proc.stdout.read() if proc.stdout else ""
    t.join(timeout=5)
    return _extract_result(out, state["stderr"])


HISTORY_PATH = Path(__file__).resolve().parent / "BENCH_HISTORY.jsonl"
PARITY_PATH = Path(__file__).resolve().parent / "BENCH_PARITY.json"
PARITY_R64_PATH = Path(__file__).resolve().parent / "BENCH_PARITY_R64.json"


def _record_history(line: str) -> None:
    """Append a successful accelerator measurement to BENCH_HISTORY.jsonl
    (full-scale runs only — the comparable ones), in the canonical
    schema tools/bench_gate.py judges."""
    try:
        rec = json.loads(line)
        if (
            rec.get("platform") not in (None, "cpu")
            and rec.get("value")
            and rec.get("scale", 0) >= 1.0
        ):
            # "fenced": the timing ended in block_until_ready — the
            # only kind of record tools/bench_gate.py judges
            _bench_gate().append_history(HISTORY_PATH, {
                **rec, "fenced": True,
                "recorded_at": time.strftime(
                    "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
                ),
            })
    except Exception:
        pass


def _write_pr_summary(rec: dict, fenced=None) -> None:
    """Canonical BENCH_PR<k>.json next to the history: the harness
    reads the PR's trajectory from this file."""
    try:
        gate = _bench_gate()
        if isinstance(rec, str):
            rec = json.loads(rec)
        path = gate.write_pr_summary(
            gate.canonical_record(rec, fenced=fenced)
        )
        print(f"# bench summary written: {path.name}", file=sys.stderr,
              flush=True)
    except Exception as e:
        print(f"# WARNING: could not write bench summary: {e}",
              file=sys.stderr, flush=True)


def main() -> None:
    args = _parse_args()
    if args.parity:
        run_parity(args)
        return
    if args.parity_northstar:
        run_parity_northstar(args)
        return
    if args.pipeline:
        run_pipeline(args)
        return
    if args.straggler_ab:
        run_straggler_ab(args)
        return
    if args.breakdown:
        run_breakdown(args)
        return
    if args.inner:
        # run directly, no supervision
        run_inner(args)
        return

    # ---- orchestrated default invocation: an accelerator number or a
    # non-zero exit, never a hang ----
    common = [
        "--scale", str(args.scale), "--rank", str(args.rank),
        "--iters", str(args.iters), "--seed", str(args.seed),
        "--staging", args.staging, "--holdout", str(args.holdout),
    ] + (["--gather-mode", args.gather_mode]
         if args.gather_mode else []) \
      + (["--solver", args.solver] if args.solver else []) \
      + (["--solver-mode", args.solver_mode] if args.solver_mode else []) \
      + (["--subspace-block", str(args.subspace_block)]
         if args.subspace_block is not None else []) \
      + (["--precision", args.precision] if args.precision else []) \
      + (["--verbose"] if args.verbose else [])

    start = time.time()

    def remaining():
        return max(60, int(TOTAL_BUDGET - (time.time() - start)))

    platform, probe_err = None, "not probed"
    for attempt in range(PROBE_ATTEMPTS):
        # raw (unfloored) remainder: `remaining()` floors at 60 for
        # stage timeouts, which would make a budget-exhaustion guard
        # unreachable — retries must actually stop when the train
        # attempts' share is gone
        raw = TOTAL_BUDGET - (time.time() - start) - 2 * 60
        if attempt > 0 and raw < 30:
            break
        platform, probe_err = _probe_accelerator(
            min(PROBE_TIMEOUT, max(60, int(raw)))
        )
        if platform is not None:
            break
    if platform is None:
        print(f"# ERROR: no accelerator ({probe_err}); nothing measured",
              file=sys.stderr, flush=True)
        sys.exit(1)
    # attempt the best configuration first — Pallas solves + bf16x3
    # Gram — then the conservative all-XLA config.  Explicit
    # --solver/--precision flags pin a single attempt.
    attempts = [common]
    if args.solver is None and args.precision is None:
        attempts.insert(
            0, common + ["--solver", "pallas", "--precision", "high"])
    errs = []
    # progress-aware supervision: a slow-but-advancing attempt keeps its
    # slot until the budget genuinely runs out, while a stalled attempt
    # dies after one STALL_TIMEOUT window.  The first (best) config gets
    # the larger share of what remains.
    weights = [3, 2][: len(attempts)] or [1]
    for k, extra in enumerate(attempts):
        share = weights[k] / sum(weights[k:])
        cap = int(remaining() * share)
        line, err = _run_inner_supervised(extra, max(cap, 60))
        if line is not None:
            _record_history(line)
            _write_pr_summary(line, fenced=True)
            print(line)
            return
        errs.append(err)
    print(f"# ERROR: every accelerator attempt failed: {errs}; nothing "
          "measured", file=sys.stderr, flush=True)
    sys.exit(1)


if __name__ == "__main__":
    main()
