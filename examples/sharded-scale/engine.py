"""Scaling an ALS train past one chip's HBM: the sharded-COO layout.

The reference scales by adding Spark executors — MLlib block-partitions
both the factor matrices AND the rating blocks across the cluster
(SURVEY §2.7(2)).  The TPU-native equivalent is one config knob:

    ALSConfig(factor_placement="sharded")

* both factor tables live ``P('data', None)`` over the mesh (model
  capacity scales with total HBM — ALX-style, arXiv 2112.02194),
* the rating COO is co-partitioned with the bucket rows each device
  solves (`models/als._plan_shard_layout`) so DATA capacity scales with
  total HBM too, and the int32-offset ceiling applies per shard.

Multi-host, the same layout extends across processes (datasource
``coo: "local"`` + `ALSTrainer.distributed`): rating triples travel
point-to-point to their row's owner and the full COO never exists
anywhere — see ``tests/test_multihost.py`` for the 2- and 4-process
drive of that path (it needs real `jax.distributed` processes, so this
in-process example shows the single-host multi-device half).

Run: ``python engine.py`` (uses the visible devices; under
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``
it demonstrates on a virtual 8-device mesh).
"""

from __future__ import annotations

import numpy as np

from predictionio_tpu.models.als import ALSConfig, ALSTrainer, rmse
from predictionio_tpu.parallel import make_mesh


def synth(n_users=600, n_items=240, nnz=40_000, seed=0):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_users, nnz).astype(np.int32)
    i = rng.integers(0, n_items, nnz).astype(np.int32)
    v = (rng.integers(1, 11, nnz) * 0.5).astype(np.float32)
    return u, i, v, n_users, n_items


def main() -> None:
    u, i, v, n_users, n_items = synth()
    mesh = make_mesh()
    print(f"mesh: {mesh.size} device(s) over axis {mesh.axis_names}")
    if mesh.size < 2:
        print(
            "only one device visible — sharded placement degenerates to "
            "replicated, so there is nothing to demonstrate.  Re-run "
            "with a multi-device mesh, e.g.:\n  JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "python engine.py"
        )
        return

    replicated = ALSTrainer(
        (u, i, v), n_users, n_items,
        ALSConfig(rank=8, num_iterations=4), mesh=mesh,
    )
    sharded = ALSTrainer(
        (u, i, v), n_users, n_items,
        ALSConfig(rank=8, num_iterations=4, factor_placement="sharded"),
        mesh=mesh,
    )
    L = sharded.coo_shard_entries
    print(
        f"rating COO: {len(v):,} ratings total; each device stores "
        f"{L:,} (~1/{mesh.size} + padding) in sharded placement vs "
        f"{len(v):,} replicated"
    )
    print(f"solver: {sharded.cfg.solver!r}")

    f_rep = replicated.train()
    f_sh = sharded.train()
    err_rep = rmse(f_rep, u, i, v)
    err_sh = rmse(f_sh, u, i, v)
    print(f"train RMSE: replicated {err_rep:.4f} vs sharded {err_sh:.4f}")
    assert abs(err_rep - err_sh) < 1e-3, "placements must agree"
    drift = float(np.abs(f_sh.user_factors - f_rep.user_factors).max())
    print(f"max |factor drift| between placements: {drift:.2e}")
    print("sharded-scale OK")


if __name__ == "__main__":
    main()
