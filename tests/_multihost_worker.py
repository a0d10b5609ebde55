"""Worker for the multi-host ingest/train tests.

Launched by tools/multihost_harness.spawn_workers as:
    python _multihost_worker.py <pid> <nprocs> <coord_dir> <db> <exch> <out>

``coord_dir`` is the harness's coordination directory: worker 0 binds
port 0 itself and publishes the bound address there
(`tools/multihost_harness.resolve_coordinator`), so no parent-side
free-port scan can race another concurrent run.

Each process jax.distributed-inits into the cluster, reads ITS entity-hash
shard of the shared sqlite event store, exchanges id dictionaries, gathers
the global COO, and (to prove the union trains) runs a tiny ALS locally;
results go to <out> for the parent to compare.
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def main() -> None:
    pid, nprocs = int(sys.argv[1]), int(sys.argv[2])
    coord_dir, db, exch, out = sys.argv[3:7]
    home = sys.argv[7] if len(sys.argv) > 7 else ""

    from tools.multihost_harness import resolve_coordinator

    coordinator = resolve_coordinator(coord_dir, pid, nprocs)

    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=nprocs,
        process_id=pid,
    )
    assert jax.process_count() == nprocs, jax.process_count()

    mode = sys.argv[8] if len(sys.argv) > 8 else ""
    if home:
        return _run_train_end_to_end(pid, home, out, local=(mode == "local"))
    if mode == "sharded":
        return _run_sharded_trainer(pid, db, exch, out)

    from predictionio_tpu.models.als import ALSConfig, train_als
    from predictionio_tpu.parallel.ingest import (
        find_columnar_sharded, read_ratings_distributed,
    )
    from predictionio_tpu.storage.sqlite_events import SQLiteEventStore

    es = SQLiteEventStore(db)

    # the local shard really is a strict subset (both processes see >0 rows
    # for any non-trivial dataset split by entity hash)
    local = find_columnar_sharded(
        es, n_shards=nprocs, shard_id=pid,
        app_id=1, event_names=["rate"], float_property="rating",
    )

    ratings = read_ratings_distributed(
        es, exch, rating_property="rating",
        app_id=1, event_names=["rate"],
    )

    cfg = ALSConfig(rank=4, num_iterations=3, lam=0.1, seed=3)
    factors = train_als(ratings, cfg=cfg)

    order = np.lexsort((ratings.item_ix, ratings.user_ix))
    np.savez(
        out,
        local_rows=np.int64(len(local)),
        n_total=np.int64(len(ratings)),
        user_ix=ratings.user_ix[order],
        item_ix=ratings.item_ix[order],
        rating=ratings.rating[order],
        user_ids=ratings.users.ids.astype(str),
        item_ids=ratings.items.ids.astype(str),
        user_factors=factors.user_factors,
        item_factors=factors.item_factors,
    )
    print("WORKER_OK", pid, flush=True)


def _run_sharded_trainer(pid: int, db: str, exch: str, out: str) -> None:
    """Sharded-COO multi-host path: sharded scan -> id exchange ->
    row-owner COO exchange -> ALSTrainer.distributed.  No process ever
    holds the full COO; the parent asserts per-process rating bytes are
    a strict subset and the model matches a single-process train."""
    from predictionio_tpu.models.als import ALSConfig
    from predictionio_tpu.parallel.ingest import distributed_trainer
    from predictionio_tpu.parallel.mesh import make_mesh

    cfg = ALSConfig(rank=4, num_iterations=3, lam=0.1, seed=3,
                    factor_placement="sharded", solver="xla")
    from predictionio_tpu.storage.sqlite_events import SQLiteEventStore

    es = SQLiteEventStore(db)
    mesh = make_mesh()
    tr = distributed_trainer(
        es, exch, cfg, mesh, rating_property="rating",
        app_id=1, event_names=["rate"],
    )
    assert tr.staging == "sharded-distributed", tr.staging
    # rating slots THIS process holds on its devices (the scaling
    # claim): a shard's COO length for each device that holds a shard
    # of the staged blocks, which were expanded from it
    _rows, idx, _val, _counts = tr._user_side["buckets"][0]
    local_nnz = len(idx.addressable_shards) * tr._user_side["shard_len"]
    factors = tr.train()
    np.savez(
        out,
        local_nnz=np.int64(local_nnz),
        shard_len=np.int64(tr._user_side["shard_len"]),
        n_dev=np.int64(mesh.size),
        user_factors=factors.user_factors,
        item_factors=factors.item_factors,
    )
    print("WORKER_OK", pid, flush=True)


def _run_train_end_to_end(pid: int, home: str, out: str,
                          local: bool = False) -> None:
    """Full multi-host workflow over shared storage: run_train (sharded
    ingest + SPMD train + chief-only metadata/model writes) then deploy +
    predict on BOTH processes from the persisted instance.

    ``local=True`` drives the no-full-COO configuration end to end:
    datasource ``coo: "local"`` + algorithm ``factorPlacement:
    "sharded"`` — the rating set is never resident on one process at any
    point of the workflow."""
    os.environ["PIO_TPU_HOME"] = home
    import jax

    from predictionio_tpu.storage.registry import get_storage
    from predictionio_tpu.templates.recommendation import (
        Query, recommendation_engine,
    )
    from predictionio_tpu.workflow.train import (
        prepare_deploy_components, run_train,
    )

    engine = recommendation_engine()
    ds_params = {"app_name": "mhapp"}
    algo_params = {"rank": 4, "numIterations": 3, "lambda": 0.1}
    if local:
        ds_params["coo"] = "local"
        algo_params["factorPlacement"] = "sharded"
    params = engine.params_from_variant({
        "datasource": {"params": ds_params},
        "algorithms": [{"name": "als", "params": algo_params}],
    })
    local_rows = -1
    if local:
        # prove the read really is local (a strict per-process subset,
        # globally encoded) before the workflow consumes it — a
        # regression to the gathered read would double-count ratings
        from predictionio_tpu.controller.base import WorkflowContext

        td = engine._data_source(params).read_training(
            WorkflowContext(mode="Training")
        )
        assert td.coo_local, "coo='local' read lost its marker"
        local_rows = len(td.ratings)
    iid = run_train(engine, params)

    md = get_storage().get_metadata()
    inst = md.engine_instance_get(iid)
    assert inst is not None and inst.status == "COMPLETED", inst
    # exactly one instance row + one model row (chief-only writes)
    n_rows = sum(
        1 for i in md.engine_instance_get_completed("default", "1",
                                                    "engine.json")
        if i.id == iid
    )
    assert n_rows == 1, f"duplicate instance rows: {n_rows}"

    algos, models, _ = prepare_deploy_components(engine, params, iid)
    r = algos[0].predict(models[0], Query(user="u1", num=3))
    assert len(r.item_scores) == 3, r

    np.savez(
        out,
        iid=np.array([iid], dtype=str),
        local_rows=np.int64(local_rows),
        user_factors=np.asarray(models[0].user_factors),
        predict_items=np.array([s.item for s in r.item_scores], dtype=str),
        predict_scores=np.array(
            [s.score for s in r.item_scores], dtype=np.float64
        ),
    )
    print("WORKER_OK", pid, flush=True)


if __name__ == "__main__":
    main()
