"""Driver `train_sweeps`: whole ALS sweeps, back to back, through
`ALSTrainer.run` one sweep at a call, until the clock passes the window's
length; the window closes at that sweep's end (never a half sweep: the
user and item halves differ in cost).

Set-up makes the ratings and the initial tables from the seed, builds ONE
trainer, and drives it through its first sweep by the window's own call;
that sweep compiles both halves, and its tables are what `correct`
compares with the plain reference.  The same trainer, continuing from
those tables, is what the window times.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from perfbench import harness


def capped_power_law(n: int, exponent: float, total: int, cap: int):
    """Whole-number degrees of n rows that sum to `total`: row k has weight
    k**-exponent, clipped so that the widest row has `cap` entries.  The
    degrees are the same for every seed (largest remainders round them), so
    that every seed stages the same bucket shapes and compiles nothing new."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -exponent
    lo, hi = 0.0, 1.0
    for _ in range(100):
        mid = (lo + hi) / 2
        clipped = np.minimum(w, mid)
        if clipped[0] / clipped.sum() * total > cap:
            hi = mid
        else:
            lo = mid
    clipped = np.minimum(w, lo)
    exact = clipped / clipped.sum() * total
    counts = np.floor(exact).astype(np.int64)
    short = int(total - counts.sum())
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def make_ratings(cfg: dict, seed: int):
    """(u, i, v, counts_u): host arrays sorted by user.  Every seed has the
    same degrees of users and of items.  The seed pairs them: rating p (in
    user order) takes the item at place (a*p + b) mod n of the item column
    sorted by item, a walk with a stride a near n/golden ratio and coprime
    to n, so each user's items follow the items' popularity; a, b and the
    stars (uniform on 1..5) come from the seed."""
    n, nu, ni = cfg["n_ratings"], cfg["n_users"], cfg["n_items"]
    counts_u = capped_power_law(nu, cfg["user_exponent"], n,
                                cfg["user_max_ratings"])
    counts_i = capped_power_law(ni, cfg["item_exponent"], n,
                                cfg["item_max_ratings"])
    if n >= 2 ** 31:
        raise ValueError("the walk's 32-bit arithmetic needs n < 2**31")
    rng = np.random.default_rng(seed)
    stride = int(n * 0.6180339887498949) + int(rng.integers(0, max(n // 64, 1)))
    while np.gcd(stride, n) != 1:
        stride += 1
    shift = int(rng.integers(0, n))
    # place(p) = (a*p + b) mod n in 32 bits: p = q*block + r, so
    # place = (rows[q] + cols[r]) mod n with both terms under n
    block = 16384
    q = np.arange(-(-n // block), dtype=np.int64)
    rows = (((q * block % n) * stride + shift) % n).astype(np.uint32)
    cols = (np.arange(block, dtype=np.int64) * stride % n).astype(np.uint32)
    place = (rows[:, None] + cols[None, :]).reshape(-1)[:n]
    place[place >= n] -= np.uint32(n)
    i = np.repeat(np.arange(ni, dtype=np.int32), counts_i)[place]
    del place
    u = np.repeat(np.arange(nu, dtype=np.int32), counts_u)
    v = rng.integers(1, 6, size=n, dtype=np.uint8).astype(np.float32)
    return u, i, v, counts_u


def init_tables(cfg: dict, seed: int):
    """The seed's initial tables (the harness's, not `init_factors()`'s, so
    that program and reference start from one input)."""
    return harness.seeded_tables(cfg, seed, stream=2)


def build_trainer(cfg: dict, u, i, v):
    """The recommendation engine's trainer with every path selector at the
    program's default."""
    from predictionio_tpu.controller.base import instantiate
    from predictionio_tpu.models.als import ALSTrainer
    from predictionio_tpu.templates.recommendation import (
        ALSAlgorithm, ALSAlgorithmParams,
    )

    params = ALSAlgorithmParams(rank=cfg["rank"], lam=cfg["lambda"])
    als_cfg = instantiate(ALSAlgorithm, params)._config()
    return ALSTrainer((u, i, v), cfg["n_users"], cfg["n_items"], cfg=als_cfg)


def phase_seconds() -> dict:
    """{phase: (sum_s, count)} of the program's own sweep phases."""
    from predictionio_tpu.obs import TRAIN_PHASE_SECONDS

    out = {}
    for phase in ("als.user_half", "als.item_half"):
        snap = TRAIN_PHASE_SECONDS.labels(phase=phase).snapshot()
        out[phase] = (snap["sum"], snap["count"])
    return out


def sample_rows(counts: np.ndarray, n_random: int, n_widest: int,
                rng: np.random.Generator) -> np.ndarray:
    """Rows to compare: a draw from the seed, with the widest in it."""
    widest = np.argsort(-counts, kind="stable")[:n_widest]
    pool = np.setdiff1d(np.arange(len(counts)), widest)
    drawn = rng.choice(pool, size=min(n_random, len(pool)), replace=False)
    return np.sort(np.concatenate([widest, drawn]))


def row_gaps(got: np.ndarray, ref: np.ndarray) -> tuple:
    """(Frobenius gap, worst row's gap) of `got` against `ref`; a row's gap
    is measured against its own norm or the median row's, whichever is
    larger."""
    diff = np.linalg.norm(got - ref, axis=1)
    norms = np.linalg.norm(ref, axis=1)
    floor = np.maximum(norms, np.median(norms))
    fro = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    return fro, float((diff / floor).max())


def reference_first_sweep(cfg: dict, seed: int, u, i, v, counts_u, tables0,
                          precision: str = "highest", log=None) -> dict:
    """The plain reference's first sweep from the same inputs: the user half
    in full (`u1`), and of the item half the rows `rows_i`, a sample drawn
    from the seed with the widest in it (`v1_rows`).  At the control's
    precision this is what stands in the program's place."""
    import jax.numpy as jnp

    from perfbench.reference import als_ref

    log = log or (lambda *_: None)
    t0 = time.perf_counter()
    check, lam = cfg["check"], cfg["lambda"]
    i_dev, v_dev = jnp.asarray(i), jnp.asarray(v)
    counts_u = np.asarray(counts_u, np.int32)
    starts_u = np.concatenate(([0], np.cumsum(counts_u)[:-1])).astype(np.int32)
    u1 = als_ref.solve_rows(
        tables0[1], i_dev, v_dev, starts_u, counts_u,
        np.arange(cfg["n_users"]), lam, precision, timing=(t_u := {}),
    )
    log(f"reference ({precision}) user half {time.perf_counter() - t0:.1f}s "
        f"{t_u}")
    t1 = time.perf_counter()
    rng = np.random.default_rng(seed ^ 0x5EED)
    counts_i = np.bincount(i, minlength=cfg["n_items"])
    rows_i = sample_rows(counts_i, check["item_rows"], check["widest_rows"], rng)
    # the sampled items' ratings alone, sorted by item: row j of this small
    # COO is item rows_i[j]
    wanted = np.full(cfg["n_items"], -1, np.int32)
    wanted[rows_i] = np.arange(len(rows_i), dtype=np.int32)
    row_of = wanted[i]
    mine = np.flatnonzero(row_of >= 0)
    mine = mine[np.argsort(row_of[mine], kind="stable")]
    sub_counts = counts_i[rows_i].astype(np.int32)
    sub_starts = np.concatenate(([0], np.cumsum(sub_counts)[:-1])).astype(np.int32)
    # padded to a whole number of 2**21 entries: the sample's size changes
    # with the seed, and a new length would compile the reference anew
    room = -(-len(mine) // (1 << 21)) * (1 << 21)
    sub_u = np.zeros(room, np.int32)
    sub_v = np.zeros(room, np.float32)
    sub_u[: len(mine)] = u[mine]
    sub_v[: len(mine)] = v[mine]
    v1_rows = als_ref.solve_rows(
        jnp.asarray(u1), jnp.asarray(sub_u), jnp.asarray(sub_v),
        sub_starts, sub_counts, np.arange(len(rows_i)), lam, precision,
        timing=(t_i := {}),
    )
    log(f"reference ({precision}) item rows {time.perf_counter() - t1:.1f}s "
        f"{t_i}")
    # the loss after the sweep is taken over the sampled items' ratings
    return {"u1": u1, "rows_i": rows_i, "v1_rows": v1_rows,
            "loss_u": u[mine], "loss_row": row_of[mine], "loss_r": v[mine],
            "seconds": time.perf_counter() - t0}


def compare_first_sweep(ref: dict, got_u1: np.ndarray,
                        got_v1_rows: np.ndarray) -> tuple:
    """The numbers `correct` compares: tables after the first sweep against
    the reference's.  The loss is the RMSE over a sample of ratings, both
    sides by the same arithmetic."""
    from perfbench.reference import als_ref

    u_fro, u_worst = row_gaps(got_u1, ref["u1"])
    v_fro, v_worst = row_gaps(got_v1_rows, ref["v1_rows"])
    triples = (ref["loss_u"], ref["loss_row"], ref["loss_r"])
    loss_ref = als_ref.rmse(ref["u1"], ref["v1_rows"], *triples)
    loss_got = als_ref.rmse(got_u1, got_v1_rows, *triples)
    return {
        "u_fro": u_fro, "u_worst_row": u_worst,
        "v_fro": v_fro, "v_worst_row": v_worst,
        "loss_gap": abs(loss_got - loss_ref) / loss_ref,
    }, {"loss_ref": loss_ref, "loss_got": loss_got,
        "loss_ratings": int(len(ref["loss_r"])),
        "reference_s": ref["seconds"]}


def run(cell, opts) -> dict:
    cfg, clock, seed = cell.config, opts["clock"], opts["seed"]
    log = opts["log"]
    from predictionio_tpu.parallel.mesh import enable_compilation_cache

    enable_compilation_cache()
    with clock.phase("data_build_s"):
        t0 = time.perf_counter()
        u, i, v, counts_u = make_ratings(cfg, seed)
        log(f"ratings made in {time.perf_counter() - t0:.1f}s")
        tables0 = init_tables(cfg, seed)
    with clock.phase("warmup_s"):
        t0 = time.perf_counter()
        trainer = build_trainer(cfg, u, i, v)
        log(f"staged in {time.perf_counter() - t0:.1f}s")
        U, V = trainer.run(tables0[0], tables0[1], 1)
        first = (np.asarray(U), np.asarray(V))
        tables0 = (None, tables0[1])   # only V0 feeds the reference
    gc.collect()

    nnz = int(len(v))
    tracer = harness.Tracer(cell.name) if opts["trace"] else None
    compiles0 = harness.compile_count()
    phases0 = phase_seconds()
    t_open = clock.window_opens()
    sweeps = 0
    sweep_s = []
    while True:
        if tracer is not None and sweeps == 0:
            tracer.start()
        t_s = time.perf_counter()
        U, V = trainer.run(U, V, 1)
        now = time.perf_counter()
        sweep_s.append(now - t_s)
        if tracer is not None and sweeps == 0:
            tracer.stop()
        sweeps += 1
        if now - t_open >= opts["seconds"]:
            break
    window_s = now - t_open
    compiles = harness.compile_count() - compiles0
    phases1 = phase_seconds()
    peak = harness.memory_peak_bytes()
    peak_in_use = harness.memory_peak_in_use_bytes()
    last = (np.asarray(U), np.asarray(V))
    del trainer, U, V
    gc.collect()

    ref = reference_first_sweep(cfg, seed, u, i, v, counts_u, tables0,
                                log=log)
    numbers, info = compare_first_sweep(ref, first[0], first[1][ref["rows_i"]])
    numbers["window_nonfinite"] = float(
        sum(int((~np.isfinite(t)).sum()) for t in last)
    )
    phases = {
        k: (phases1[k][0] - phases0[k][0], phases1[k][1] - phases0[k][1])
        for k in phases1
    }
    return {
        "attempted": sweeps, "failed": 0,
        "end_to_end": {"train_ratings_per_s": nnz * sweeps / window_s},
        "numbers": numbers, "info": {**info, "sweep_s": sweep_s,
                 "memory_peak_in_use_bytes": peak_in_use},
        "memory_peak_bytes": peak,
        "window_s": window_s,
        "run": {
            "kind": "train", "sweeps": sweeps, "window_s": window_s,
            "compiles_in_window": compiles, "phases": phases,
            "shape": {"nnz": nnz, "n_users": cfg["n_users"],
                      "n_items": cfg["n_items"], "rank": cfg["rank"]},
            "traced_sweeps": 1,
        },
        "tracer": tracer,
    }
