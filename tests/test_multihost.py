"""Multi-host sharded ingest: real jax.distributed CPU processes (2 and 4).

The TPU-build analogue of the reference's region-parallel HBase scans
(`data/.../storage/hbase/HBPEvents.scala:99-105`): each process reads only
its entity-hash shard of the event store, id dictionaries are exchanged
through the shared storage dir, and the numeric COO either all-gathers
(replicated path) or is exchanged to each row's owning process so no
process holds the full rating set (sharded-COO path,
`ALSTrainer.distributed`).  The suite launches actual processes (the way
`local[4]` stood in for a Spark cluster in the reference's tests, a small
CPU cluster stands in for TPU hosts) and checks every path against a
single-process read.
"""

import datetime as dt
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from tools.multihost_harness import spawn_workers

from predictionio_tpu.storage.event import DataMap, Event
from predictionio_tpu.storage.sqlite_events import SQLiteEventStore

UTC = dt.timezone.utc


def _make_events(n_users=12, n_items=8, seed=0):
    rng = np.random.default_rng(seed)
    events = []
    for u in range(n_users):
        for i in range(n_items):
            if rng.random() < 0.5:
                events.append(
                    Event(
                        event="rate",
                        entity_type="user",
                        entity_id=f"u{u}",
                        target_entity_type="item",
                        target_entity_id=f"i{i}",
                        properties=DataMap(
                            {"rating": float(rng.integers(1, 6))}
                        ),
                        event_time=dt.datetime(2020, 1, 1, tzinfo=UTC),
                    )
                )
    return events


def test_shard_masks_partition_events(tmp_path):
    """Entity-hash shards are a disjoint cover and keep each entity whole."""
    from predictionio_tpu.parallel.ingest import find_columnar_sharded

    db = tmp_path / "events.db"
    es = SQLiteEventStore(db)
    es.init_channel(1)
    for e in _make_events():
        es.insert(e, app_id=1)

    full = es.find_columnar(app_id=1, event_names=["rate"])
    shards = [
        find_columnar_sharded(
            es, n_shards=3, shard_id=s, app_id=1, event_names=["rate"]
        )
        for s in range(3)
    ]
    assert sum(len(s) for s in shards) == len(full)
    owners = {}
    for six, s in enumerate(shards):
        for eid in s.entity_id:
            assert owners.setdefault(eid, six) == six
    es.close()


def _spawn_workers(nprocs, args_of, timeout=300, device_count=0):
    """Harness launch + the test-suite failure policy (pytest.fail on
    timeout, hard assert on rc/marker)."""
    results = spawn_workers(
        nprocs, args_of, device_count=device_count, timeout=timeout,
    )
    for r in results:
        if r.timed_out:
            pytest.fail(f"worker {r.pid} timed out")
        assert r.returncode == 0, (
            f"worker {r.pid} rc={r.returncode}\n{r.stdout}\n{r.stderr}"
        )
        assert f"WORKER_OK {r.pid}" in r.stdout
    return [r.stdout for r in results]


@pytest.mark.parametrize("nprocs", [2, 4])
def test_multi_process_ingest_and_train(tmp_path, nprocs):
    """jax.distributed CPU processes each read their shard; the gathered
    COO and the model trained on it match a single-process run.  4
    processes cover ids_exchange fan-in and uneven shard sizes beyond
    the pairwise case."""
    db = tmp_path / "events.db"
    es = SQLiteEventStore(db)
    es.init_channel(1)
    for e in _make_events():
        es.insert(e, app_id=1)

    # single-process expectation
    frame = es.find_columnar(
        app_id=1, event_names=["rate"], float_property="rating"
    )
    expected = frame.to_ratings(rating_property="rating")
    es.close()

    from predictionio_tpu.models.als import ALSConfig, train_als

    exp_factors = train_als(
        expected, cfg=ALSConfig(rank=4, num_iterations=3, lam=0.1, seed=3)
    )

    coordinator = tmp_path / "coord"
    exch = tmp_path / "exchange"
    outs = [tmp_path / f"out{p}.npz" for p in range(nprocs)]
    _spawn_workers(
        nprocs,
        lambda p: [p, nprocs, coordinator, db, exch, outs[p]],
    )
    results = [np.load(o, allow_pickle=False) for o in outs]

    # each worker saw a strict subset, together the whole set
    locals_ = [int(r["local_rows"]) for r in results]
    assert all(0 < n < len(expected) for n in locals_), locals_
    assert sum(locals_) == len(expected)

    order = np.lexsort((expected.item_ix, expected.user_ix))
    for r in results:
        # same global dictionaries and full COO on every process
        assert r["user_ids"].tolist() == expected.users.ids.tolist()
        assert r["item_ids"].tolist() == expected.items.ids.tolist()
        assert int(r["n_total"]) == len(expected)
        np.testing.assert_array_equal(r["user_ix"], expected.user_ix[order])
        np.testing.assert_array_equal(r["item_ix"], expected.item_ix[order])
        np.testing.assert_allclose(r["rating"], expected.rating[order])
        # the union trains to the same model as the single-process read
        np.testing.assert_allclose(
            r["user_factors"], exp_factors.user_factors, rtol=1e-4, atol=1e-4
        )


def test_two_process_run_train_end_to_end(tmp_path):
    """The FULL workflow across 2 processes sharing one storage home:
    run_train (sharded ingest, SPMD train, chief-only metadata/model
    writes, collective-safe save) then deploy + predict on both.
    Regressions covered: duplicate metadata rows, np.asarray on
    process-spanning arrays at save time, divergent instance ids."""
    import os

    from predictionio_tpu.storage.registry import Storage

    home = tmp_path / "home"
    st = Storage({"PIO_TPU_HOME": str(home)})
    app = st.get_metadata().app_insert("mhapp")
    es = st.get_event_store()
    for e in _make_events():
        es.insert(e, app_id=app.id)
    st.close()

    coordinator = tmp_path / "coord"
    outs = [tmp_path / f"train_out{p}.npz" for p in range(2)]
    _spawn_workers(
        2,
        lambda p: [p, 2, coordinator, "-", "-", outs[p], home],
    )
    results = [np.load(o, allow_pickle=False) for o in outs]

    # same instance, same model, same predictions on both processes
    assert results[0]["iid"][0] == results[1]["iid"][0]
    np.testing.assert_allclose(
        results[0]["user_factors"], results[1]["user_factors"],
        rtol=1e-5, atol=1e-5,
    )
    assert (
        results[0]["predict_items"].tolist()
        == results[1]["predict_items"].tolist()
    )


@pytest.mark.parametrize(
    "nprocs,device_count",
    [(2, 2), (4, 0)],
    ids=["2proc_x_2dev", "4proc_x_1dev"],
)
def test_sharded_coo_distributed_trainer(tmp_path, nprocs, device_count):
    """ALSTrainer.distributed over real processes: NO process holds the
    full COO (per-process rating arrays are a strict subset), the mesh
    spans processes (2x2 covers devices != processes), and the trained
    model matches a single-process replicated train.  A pre-planted
    stale exchange file from a 'crashed run' must be swept, never merged."""
    import os
    import time as _time

    db = tmp_path / "events.db"
    es = SQLiteEventStore(db)
    es.init_channel(1)
    for e in _make_events(n_users=24, n_items=16, seed=1):
        es.insert(e, app_id=1)
    frame = es.find_columnar(
        app_id=1, event_names=["rate"], float_property="rating"
    )
    expected = frame.to_ratings(rating_property="rating")
    es.close()

    from predictionio_tpu.models.als import ALSConfig, train_als

    exp_factors = train_als(
        expected, cfg=ALSConfig(rank=4, num_iterations=3, lam=0.1, seed=3)
    )

    exch = tmp_path / "exchange"
    exch.mkdir()
    # crashed-run residue: an aged file with a colliding-looking name and
    # a fresh one; the aged one must be swept, the fresh one left alone,
    # and (nonce in the filename) neither can be merged into this run
    stale = exch / "ratings-users-deadbeefdeadbeef-0.npz"
    np.savez_compressed(stale, ids=np.asarray(["GHOST"], dtype=str))
    os.utime(stale, (_time.time() - 7200, _time.time() - 7200))
    fresh = exch / "unrelated-fresh.npz"
    np.savez_compressed(fresh, ids=np.asarray(["KEEP"], dtype=str))

    coordinator = tmp_path / "coord"
    outs = [tmp_path / f"sh{p}.npz" for p in range(nprocs)]
    _spawn_workers(
        nprocs,
        lambda p: [p, nprocs, coordinator, db, exch, outs[p], "",
                   "sharded"],
        device_count=device_count,
    )
    results = [np.load(o, allow_pickle=False) for o in outs]

    assert not stale.exists(), "stale exchange file survived the sweep"
    assert fresh.exists(), "fresh file was wrongly swept"

    n_dev = int(results[0]["n_dev"])
    assert n_dev == nprocs * max(device_count, 1)
    nnz = len(expected)
    for r in results:
        # strict subset of the ratings on every process, padded total
        # stays near nnz (sharded, not replicated)
        assert 0 < int(r["local_nnz"]) < nnz
        assert int(r["shard_len"]) * n_dev < 2 * nnz + n_dev * 64
        # GHOST ids from the stale file never entered the dictionaries
        assert r["user_factors"].shape == exp_factors.user_factors.shape
        np.testing.assert_allclose(
            r["user_factors"], exp_factors.user_factors,
            rtol=1e-4, atol=1e-4,
        )
        np.testing.assert_allclose(
            r["item_factors"], exp_factors.item_factors,
            rtol=1e-4, atol=1e-4,
        )


def test_run_train_no_full_coo_end_to_end(tmp_path):
    """The FULL workflow with datasource coo='local' + sharded placement:
    run_train never gathers the rating set to any process, yet trains,
    persists (chief-gated), deploys, and predicts identically on both
    processes."""
    import os

    from predictionio_tpu.storage.registry import Storage

    home = tmp_path / "home"
    st = Storage({"PIO_TPU_HOME": str(home)})
    app = st.get_metadata().app_insert("mhapp")
    es = st.get_event_store()
    for e in _make_events():
        es.insert(e, app_id=app.id)
    st.close()

    # single-process expectation: same events, same conventions — the
    # sorted-unique id union matches a single-process read's encoding
    st2 = Storage({"PIO_TPU_HOME": str(tmp_path / "ref_home")})
    app2 = st2.get_metadata().app_insert("mhapp")
    es2 = st2.get_event_store()
    for e in _make_events():
        es2.insert(e, app_id=app2.id)
    frame = es2.find_columnar(
        app_id=app2.id, event_names=["rate"], float_property="rating"
    )
    expected = frame.to_ratings(rating_property="rating", dedup="last")
    st2.close()

    from predictionio_tpu.models.als import ALSConfig, train_als

    exp_factors = train_als(
        expected, cfg=ALSConfig(rank=4, num_iterations=3, lam=0.1, seed=3)
    )

    coordinator = tmp_path / "coord"
    outs = [tmp_path / f"local_out{p}.npz" for p in range(2)]
    _spawn_workers(
        2,
        lambda p: [p, 2, coordinator, "-", "-", outs[p], home, "local"],
    )
    results = [np.load(o, allow_pickle=False) for o in outs]
    # the reads really were local: strict subsets covering the whole set
    locals_ = [int(r["local_rows"]) for r in results]
    assert all(0 < n < len(expected) for n in locals_), locals_
    assert sum(locals_) == len(expected)
    assert results[0]["iid"][0] == results[1]["iid"][0]
    for r in results:
        # and the distributed train equals the single-process model —
        # a gathered-read regression would double-count every rating
        np.testing.assert_allclose(
            r["user_factors"], exp_factors.user_factors,
            rtol=1e-4, atol=1e-4,
        )
    assert (
        results[0]["predict_items"].tolist()
        == results[1]["predict_items"].tolist()
    )
