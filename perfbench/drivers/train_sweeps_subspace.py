"""Driver `train_sweeps_subspace`: whole implicit-feedback ALS sweeps in
the iALS++ block sweep (arXiv 2110.14044), back to back on one chip,
through `ALSTrainer.run(U, V, 1, donate=True)` one sweep at a call, until
the clock passes the window's length; the window closes at that sweep's
end.

Set-up makes the ratings from the seed (`train_sweeps.make_ratings`'s walk
over this configuration's degrees: a floor a user and a floor an item, as
the source's preprocessing leaves them, the rest a capped power law),
draws the initial tables on the device, builds ONE trainer from the
Similar Product template's own `ALSConfig` with the three engine.json
keys a user would write (`rank`, `solverMode`, `subspaceSize`) and drives
it through its first sweep by the window's own call.  The plain reference
(`perfbench/reference/ials_subspace_ref.py`) walks the same blocks over a
sample of rows drawn from the seed, the widest among them, in both
halves, from each row's whole normal equations.  The item half's inputs
are the PROGRAM's first-sweep user table (the configuration's `check`
says so): the reference does not sweep 571,355 users to have its own.

On a program whose block sweep still gathers a chunk's whole rows under
an entry cap that knows no rank (before PR 36: 34 GB a chunk at rank
2,048) it exits 2 at once.
"""

from __future__ import annotations

import gc
import sys
import time
from contextlib import contextmanager

import numpy as np

from perfbench import harness
from perfbench.drivers import train_sweeps as base
from perfbench.drivers import train_sweeps_sharded as sharded


@contextmanager
def _degrees_with_floors(floors: dict):
    """`train_sweeps.make_ratings` with this configuration's degrees:
    every row of a side of `n` rows has `floors[n]` ratings, as the
    source's preprocessing leaves no user or song with fewer, and the
    rest follow the capped power law; the walk that pairs users with
    items is `train_sweeps`'s own, not a copy of it."""
    law = base.capped_power_law

    def degrees(n, exponent, total, cap):
        floor = floors[n]
        return floor + law(n, exponent, total - n * floor, cap - floor)

    base.capped_power_law = degrees
    try:
        yield
    finally:
        base.capped_power_law = law


def make_ratings(cfg: dict, seed: int):
    """(u, i, counts_u): host arrays sorted by user; every pair is one
    event of value 1.  The same degrees for every seed."""
    floors = {cfg["n_users"]: cfg["user_min_ratings"],
              cfg["n_items"]: cfg["item_min_ratings"]}
    with _degrees_with_floors(floors):
        u, i, _, counts_u = base.make_ratings(cfg, seed)
    return u, i, counts_u


def build_trainer(cfg: dict, u, i):
    """The Similar Product engine's trainer: the template's own config
    (implicit; `lam`, `alpha` and every path selector at its default)
    with the rank, the solver mode and the block width the configuration
    states, on one chip.  The configuration's file states the same
    lambda and alpha, or this is an error of the files."""
    from predictionio_tpu.controller.base import instantiate
    from predictionio_tpu.models.als import ALSTrainer
    from predictionio_tpu.templates.similarproduct import (
        SimilarALSParams, SimilarProductAlgorithm,
    )

    params = SimilarALSParams(rank=cfg["rank"], solver_mode="subspace",
                              subspace_size=cfg["subspace_size"])
    als_cfg = instantiate(SimilarProductAlgorithm, params)._config()
    stated = (cfg["lambda"], cfg["alpha"], cfg["implicit"],
              cfg["weighted_lambda"])
    runs = (als_cfg.lam, als_cfg.alpha, als_cfg.implicit,
            als_cfg.weighted_lambda)
    if stated != runs:
        raise ValueError(f"the configuration states {stated} (lambda, "
                         f"alpha, implicit, weighted) and the template "
                         f"runs {runs}")
    v = np.ones(len(u), np.float32)
    return ALSTrainer((u, i, v), cfg["n_users"], cfg["n_items"], cfg=als_cfg)


# -- what the reference is given ------------------------------------------


FETCH_ROWS = 1 << 15   # rows of one look-up: [32768, 2048] f32 is 256 MiB


def fetch_rows(table, ids: np.ndarray) -> np.ndarray:
    """`table[ids]` as a host array, FETCH_ROWS rows to a look-up (the
    last one padded to as many, so that seeds whose samples differ in
    size compile nothing new): a sample's 270,000 rows of 8 KB are never
    on the chip at once beside the tables."""
    import jax.numpy as jnp

    ids = np.asarray(ids, np.int32)
    out = np.zeros((len(ids), table.shape[1]), np.float32)
    for lo in range(0, len(ids), FETCH_ROWS):
        piece = np.zeros(FETCH_ROWS, np.int32)
        n = min(FETCH_ROWS, len(ids) - lo)
        piece[:n] = ids[lo:lo + n]
        out[lo:lo + n] = np.asarray(
            jnp.take(table, jnp.asarray(piece), axis=0))[:n]
    return out


def table_gram(table, precisions=("highest",)) -> dict:
    """`Y^T Y` of a table on the device by the reference's own blocked
    sum, at each precision asked for."""
    from perfbench.reference import ials_subspace_ref as ref

    return {p: ref.gram([table], p) for p in precisions}


def reference_rows(cfg: dict, side: dict, yty, entry_rows, x0,
                   precision: str = "highest") -> np.ndarray:
    """The reference's block sweep of one side's sampled rows."""
    from perfbench.reference import ials_subspace_ref as ref

    return ref.sweep_rows(
        yty, entry_rows, np.ones(len(side["ids"]), np.float32),
        side["starts"], side["counts"], x0, cfg["lambda"], cfg["alpha"],
        cfg["subspace_size"], weighted=cfg["weighted_lambda"],
        precision=precision,
    )


def first_sweep(trainer, tables0, sample: dict, precisions=("highest",)):
    """The trainer's first sweep by the window's own call, and all that
    `correct` needs of it: (U, V, captured).  The inputs are read before
    the sweep consumes them, the outputs before the next one does.  The
    seed's item table (0.34 GB) is kept whole on the host; of the user
    tables only the rows asked for leave the chip."""
    U0, V0 = tables0
    user, item = sample["user"], sample["item"]
    v0 = np.asarray(V0)
    captured = {
        "x0": {"user": fetch_rows(U0, user["rows"]),
               "item": v0[item["rows"]]},
        "yty": {"user": table_gram(V0, precisions)},
        "v0": v0,
    }
    U, V = trainer.run(U0, V0, 1, donate=True)
    captured["yty"]["item"] = table_gram(U, precisions)
    captured["item_entry_rows"] = fetch_rows(U, item["ids"])
    captured["got"] = {"user": fetch_rows(U, user["rows"]),
                       "item": fetch_rows(V, item["rows"])}
    return U, V, captured


def compare_first_sweep(cfg: dict, sample: dict, captured: dict,
                        precision: str = "highest") -> dict:
    """The numbers `correct` compares: the program's sampled rows after
    its first sweep against the reference's."""
    numbers = {}
    for name, prefix in (("user", "u"), ("item", "v")):
        entry_rows = (captured["v0"][sample["user"]["ids"]] if name == "user"
                      else captured["item_entry_rows"])
        ref = reference_rows(cfg, sample[name],
                             captured["yty"][name][precision], entry_rows,
                             captured["x0"][name], precision)
        fro, worst = base.row_gaps(captured["got"][name], ref)
        numbers[f"{prefix}_fro"] = fro
        numbers[f"{prefix}_worst_row"] = worst
    return numbers


def require_bounded_block_sweep() -> None:
    """Exit 2, at once, on a program whose block sweep gathers a chunk's
    rows under an entry cap that knows no rank (before PR 36): at rank
    2,048 a chunk is 34 GB, and the run would end in the compiler's
    out-of-memory error minutes later."""
    try:
        from predictionio_tpu.models.als import gather_chunk_entries  # noqa: F401
    except ImportError:
        print("perfbench: this program's block sweep bounds no chunk by "
              "the bytes it gathers (no models/als.gather_chunk_entries); "
              "it cannot run this cell", file=sys.stderr)
        raise SystemExit(2) from None


def run(cell, opts) -> dict:
    cfg, clock, seed = cell.config, opts["clock"], opts["seed"]
    log = opts["log"]
    require_bounded_block_sweep()
    from predictionio_tpu.parallel.mesh import enable_compilation_cache

    enable_compilation_cache()
    with clock.phase("data_build_s"):
        t0 = time.perf_counter()
        u, i, counts_u = make_ratings(cfg, seed)
        nnz = int(len(u))
        sample = sharded.sample_entries(cfg, seed, u, i, counts_u)
        log(f"ratings made and rows sampled in "
            f"{time.perf_counter() - t0:.1f}s")
        tables0 = harness.seeded_tables(cfg, seed, stream=2)
    with clock.phase("warmup_s"):
        t0 = time.perf_counter()
        trainer = build_trainer(cfg, u, i)
        del u, i
        log(f"staged in {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        U, V, captured = first_sweep(trainer, tables0, sample)
        del tables0
        log(f"first sweep and its capture in "
            f"{time.perf_counter() - t0:.1f}s")
    gc.collect()

    tracer = harness.Tracer(cell.name) if opts["trace"] else None
    compiles0 = harness.compile_count()
    phases0 = base.phase_seconds()
    t_open = clock.window_opens()
    sweeps = 0
    sweep_s = []
    while True:
        if tracer is not None and sweeps == 0:
            tracer.start()
        t_s = time.perf_counter()
        U, V = trainer.run(U, V, 1, donate=True)
        now = time.perf_counter()
        sweep_s.append(now - t_s)
        if tracer is not None and sweeps == 0:
            tracer.stop()
        sweeps += 1
        if now - t_open >= opts["seconds"]:
            break
    window_s = now - t_open
    compiles = harness.compile_count() - compiles0
    phases1 = base.phase_seconds()
    peak = harness.memory_peak_bytes()
    peak_in_use = harness.memory_peak_in_use_bytes()
    nonfinite = sharded.count_nonfinite(U, V)
    solve_systems = sum(trainer.solve_systems.values())
    staged = {
        "solve_path": trainer.solve_path,
        "solve_systems": trainer.solve_systems,
        "gather_bytes": trainer.gather_bytes,
        "gather_chunk_bytes": trainer.gather_chunk_bytes,
        "gram_chunk_bytes": trainer.gram_chunk_bytes,
        "chunks_looped": trainer.chunks_looped,
    }
    del trainer, U, V
    gc.collect()

    t0 = time.perf_counter()
    numbers = compare_first_sweep(cfg, sample, captured)
    numbers["window_nonfinite"] = float(nonfinite)
    reference_s = time.perf_counter() - t0
    phases = {
        k: (phases1[k][0] - phases0[k][0], phases1[k][1] - phases0[k][1])
        for k in phases1
    }
    return {
        "attempted": sweeps, "failed": 0,
        "end_to_end": {"train_ratings_per_s": nnz * sweeps / window_s},
        "numbers": numbers,
        "info": {"sweep_s": sweep_s, "reference_s": reference_s,
                 "memory_peak_in_use_bytes": peak_in_use,
                 "sampled_rows": {k: int(len(s["rows"]))
                                  for k, s in sample.items()},
                 "staged": staged},
        "memory_peak_bytes": peak,
        "window_s": window_s,
        "run": {
            "kind": "train", "sweeps": sweeps, "window_s": window_s,
            "compiles_in_window": compiles, "phases": phases,
            "shape": {"nnz": nnz, "n_users": cfg["n_users"],
                      "n_items": cfg["n_items"], "rank": cfg["rank"],
                      "block": cfg["subspace_size"]},
            "solve_systems": solve_systems,
            "traced_sweeps": 1, "chips": cell.chips,
        },
        "tracer": tracer,
    }
