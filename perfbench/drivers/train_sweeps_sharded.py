"""Driver `train_sweeps_sharded`: whole implicit-feedback ALS sweeps, back
to back, with both factor tables and the ratings sharded over the cell's
chips, through `ALSTrainer.run(U, V, 1, donate=True)` one sweep at a call,
until the clock passes the window's length; the window closes at that
sweep's end.

Set-up makes the ratings from the seed (`train_sweeps.make_ratings`'s walk
over this configuration's degrees), draws the initial tables shard by
shard on the mesh, builds ONE trainer from the Similar Product template's
own `ALSConfig` with `factor_placement="sharded"`, and drives it through
its first sweep by the window's own call.  No whole table ever leaves the
chips or lands on one of them: the plain reference
(`perfbench/reference/ials_ref.py`) is given the rows it needs, fetched
shard by shard, and sums `Y^T Y` over each chip's own block; it solves a
sample of rows drawn from the seed, the widest among them, in both halves.
The item half's inputs are the PROGRAM's first-sweep user table (the
configuration's `check` says so): the reference does not solve 20.98 M
users to have its own.
"""

from __future__ import annotations

import gc
import math
import sys
import time
from contextlib import contextmanager

import numpy as np

from perfbench import harness
from perfbench.drivers import train_sweeps as base


@contextmanager
def _degrees_with_a_floor_of_one():
    """`train_sweeps.make_ratings` with this configuration's degrees: every
    row has one rating, as every user and product of the source's table
    has, and the rest follow the capped power law; the walk that pairs
    users with items is `train_sweeps`'s own, not a copy of it."""
    law = base.capped_power_law

    def degrees(n, exponent, total, cap):
        return 1 + law(n, exponent, total - n, cap - 1)

    base.capped_power_law = degrees
    try:
        yield
    finally:
        base.capped_power_law = law


def make_ratings(cfg: dict, seed: int):
    """(u, i, counts_u): host arrays sorted by user; every pair is one
    event of value 1.  The same degrees for every seed."""
    with _degrees_with_a_floor_of_one():
        u, i, _, counts_u = base.make_ratings(cfg, seed)
    return u, i, counts_u


def init_tables(cfg: dict, seed: int, mesh):
    """The seed's initial tables, N(0, 1)/sqrt(rank), float32, each chip
    drawing its own block of rows (no chip ever holds a whole table); rows
    past the table's end, where the mesh does not divide it, are zero."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from predictionio_tpu.parallel.mesh import DATA_AXIS, pad_to_multiple

    rank, d = cfg["rank"], mesh.size

    def table(key, n):
        shard = pad_to_multiple(n, d) // d

        def block(key):
            me = jax.lax.axis_index(DATA_AXIS)
            x = jax.random.normal(
                jax.random.fold_in(key, me), (shard, rank), jnp.float32
            ) / math.sqrt(rank)
            row = me * shard + jnp.arange(shard)
            return jnp.where((row < n)[:, None], x, 0.0)

        return jax.jit(jax.shard_map(
            block, mesh=mesh, in_specs=P(), out_specs=P(DATA_AXIS, None),
            check_vma=False,
        ))(key)

    ku, ki = jax.random.split(harness.seed_key(seed, stream=2))
    return table(ku, cfg["n_users"]), table(ki, cfg["n_items"])


def build_trainer(cfg: dict, u, i, mesh):
    """The Similar Product engine's trainer: the template's own config
    (implicit; `lam`, `alpha` and every path selector at its default),
    sharded over `mesh`.  The configuration's file states the same
    lambda and alpha, or this is an error of the files."""
    from predictionio_tpu.controller.base import instantiate
    from predictionio_tpu.models.als import ALSTrainer
    from predictionio_tpu.templates.similarproduct import (
        SimilarALSParams, SimilarProductAlgorithm,
    )

    params = SimilarALSParams(rank=cfg["rank"], factor_placement="sharded")
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
    return ALSTrainer((u, i, v), cfg["n_users"], cfg["n_items"], cfg=als_cfg,
                      mesh=mesh)


# -- what the reference is given ------------------------------------------


def fetch_rows(table, ids: np.ndarray) -> np.ndarray:
    """`table[ids]` as a host array, each row read on the chip that holds
    it.  A shard's ids are padded to a power of two, so that seeds whose
    samples differ in size compile nothing new."""
    import jax.numpy as jnp

    ids = np.asarray(ids, np.int64)
    out = np.zeros((len(ids), table.shape[1]), np.float32)
    for shard in table.addressable_shards:
        lo, hi, _ = shard.index[0].indices(table.shape[0])
        sel = np.flatnonzero((ids >= lo) & (ids < hi))
        if not len(sel):
            continue
        local = np.zeros(1 << (len(sel) - 1).bit_length(), np.int32)
        local[: len(sel)] = ids[sel] - lo
        rows = jnp.take(shard.data, jnp.asarray(local), axis=0)
        out[sel] = np.asarray(rows)[: len(sel)]
    return out


def table_blocks(table):
    """Each chip's own block of the table, where it lies."""
    return [shard.data for shard in table.addressable_shards]


def entries_of(rows: np.ndarray, counts: np.ndarray, sorted_opp: np.ndarray):
    """(opposite ids back to back, starts, counts) of `rows` in a COO
    whose opposite ids `sorted_opp` are grouped by row."""
    starts_all = np.concatenate(([0], np.cumsum(counts)[:-1]))
    n = counts[rows].astype(np.int64)
    starts = np.concatenate(([0], np.cumsum(n)[:-1]))
    pos = np.repeat(starts_all[rows] - starts, n) + np.arange(int(n.sum()))
    return sorted_opp[pos], starts, n


def sample_entries(cfg: dict, seed: int, u, i, counts_u) -> dict:
    """The rows `correct` compares, drawn from the seed with the widest
    in them, and each one's entries: the sampled users' item ids, the
    sampled items' user ids."""
    check = cfg["check"]
    rng = np.random.default_rng(seed ^ 0x5EED)
    rows_u = base.sample_rows(counts_u, check["user_rows"],
                              check["widest_rows"], rng)
    counts_i = np.bincount(i, minlength=cfg["n_items"])
    rows_i = base.sample_rows(counts_i, check["item_rows"],
                              check["widest_rows"], rng)
    ids_u, starts_u, n_u = entries_of(rows_u, np.asarray(counts_u), i)
    # the sampled items' ratings alone, grouped by item (u is sorted by
    # user, so within an item the users ascend)
    wanted = np.full(cfg["n_items"], -1, np.int32)
    wanted[rows_i] = np.arange(len(rows_i), dtype=np.int32)
    slot = wanted[i]
    mine = np.flatnonzero(slot >= 0)
    mine = mine[np.argsort(slot[mine], kind="stable")]
    n_i = counts_i[rows_i].astype(np.int64)
    return {
        "user": {"rows": rows_u, "ids": ids_u, "starts": starts_u,
                 "counts": n_u},
        "item": {"rows": rows_i, "ids": u[mine],
                 "starts": np.concatenate(([0], np.cumsum(n_i)[:-1])),
                 "counts": n_i},
    }


def reference_inputs(opp_table, side: dict,
                     precisions=("highest",)) -> dict:
    """What the plain reference needs of the opposite table for one
    side's sample: `Y^T Y` summed over the chips' blocks (at each
    precision asked for) and the entries' rows, fetched."""
    from perfbench.reference import ials_ref

    return {
        "yty": {p: ials_ref.gram(table_blocks(opp_table), p)
                for p in precisions},
        "entry_rows": fetch_rows(opp_table, side["ids"]),
    }


def reference_rows(cfg: dict, side: dict, inputs: dict,
                   precision: str = "highest") -> np.ndarray:
    """The reference's solution of one side's sampled rows."""
    from perfbench.reference import ials_ref

    return ials_ref.solve_rows(
        inputs["yty"][precision], inputs["entry_rows"],
        np.ones(len(side["ids"]), np.float32), side["starts"],
        side["counts"], cfg["lambda"], cfg["alpha"],
        weighted=cfg["weighted_lambda"], precision=precision,
    )


def first_sweep(trainer, tables0, sample: dict, precisions=("highest",)):
    """The trainer's first sweep by the window's own call, and all that
    `correct` needs of it: (U, V, captured).  The inputs are read before
    the sweep consumes them, the outputs before the next one does."""
    U0, V0 = tables0
    inputs_u = reference_inputs(V0, sample["user"], precisions)
    U, V = trainer.run(U0, V0, 1, donate=True)
    captured = {
        "inputs": {"user": inputs_u,
                   "item": reference_inputs(U, sample["item"], precisions)},
        "got": {"user": fetch_rows(U, sample["user"]["rows"]),
                "item": fetch_rows(V, sample["item"]["rows"])},
    }
    return U, V, captured


def compare_first_sweep(cfg: dict, sample: dict, captured: dict,
                        precision: str = "highest") -> dict:
    """The numbers `correct` compares: the program's sampled rows after
    its first sweep against the reference's."""
    numbers = {}
    for name, prefix in (("user", "u"), ("item", "v")):
        ref = reference_rows(cfg, sample[name], captured["inputs"][name],
                             precision)
        fro, worst = base.row_gaps(captured["got"][name], ref)
        numbers[f"{prefix}_fro"] = fro
        numbers[f"{prefix}_worst_row"] = worst
    return numbers


def count_nonfinite(U, V) -> int:
    """Non-finite entries of both tables, counted on the chips."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def count(a, b):
        return (jnp.sum(~jnp.isfinite(a), dtype=jnp.int32)
                + jnp.sum(~jnp.isfinite(b), dtype=jnp.int32))

    return int(count(U, V))


def require_bounded_exchange() -> None:
    """Exit 2, at once, on a program whose sharded half still gathers the
    whole opposite table on every chip (before PR 34): its item half asks
    one chip for 10.74 GB beside what it holds, and the run would end in
    the compiler's out-of-memory error minutes later."""
    try:
        from predictionio_tpu.parallel.collectives import ShardedRows  # noqa: F401
    except ImportError:
        print("perfbench: this program's sharded ALS half gathers the whole "
              "opposite table (no parallel/collectives.ShardedRows); it "
              "cannot run this cell", file=sys.stderr)
        raise SystemExit(2) from None


def run(cell, opts) -> dict:
    cfg, clock, seed = cell.config, opts["clock"], opts["seed"]
    log = opts["log"]
    require_bounded_exchange()
    from predictionio_tpu.parallel.mesh import (
        enable_compilation_cache, make_mesh,
    )

    enable_compilation_cache()
    with clock.phase("data_build_s"):
        t0 = time.perf_counter()
        u, i, counts_u = make_ratings(cfg, seed)
        nnz = int(len(u))
        sample = sample_entries(cfg, seed, u, i, counts_u)
        log(f"ratings made and rows sampled in "
            f"{time.perf_counter() - t0:.1f}s")
        mesh = make_mesh(cell.chips)
        tables0 = init_tables(cfg, seed, mesh)
    with clock.phase("warmup_s"):
        t0 = time.perf_counter()
        trainer = build_trainer(cfg, u, i, mesh)
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
    nonfinite = count_nonfinite(U, V)
    staged = {
        "solve_path": trainer.solve_path,
        "solve_systems": trainer.solve_systems,
        "exchange_bytes": trainer.exchange_bytes,
        "opp_transient_bytes": trainer.opp_transient_bytes,
        "gram_chunk_bytes": trainer.gram_chunk_bytes,
        "chunks_looped": trainer.chunks_looped,
        "coo_shard_entries": trainer.coo_shard_entries,
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
                      "n_items": cfg["n_items"], "rank": cfg["rank"]},
            "traced_sweeps": 1, "chips": cell.chips,
        },
        "tracer": tracer,
    }
