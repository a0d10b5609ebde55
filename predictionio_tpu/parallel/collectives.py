"""Mesh collectives — the distributed communication backend.

The reference's communication substrate is Spark's shuffle/broadcast
fabric (SURVEY §2.7); the TPU-native equivalent is XLA collectives over
ICI (within a slice) and DCN (across slices), expressed with ``shard_map``
over a `Mesh`.  These wrappers give the framework's runtime and engine
code named, tested entry points for the four primitives the training and
scoring paths use — all-reduce (gradient/stat sums), all-gather (factor
blocks), reduce-scatter (sharded updates), and ring permute (block
rotation) — instead of scattering raw ``jax.lax`` calls around.

Everything here is jit-compatible and works identically on a virtual CPU
mesh (tests) and a TPU pod slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DATA_AXIS

# collective outputs (psum/all_gather) are replicated in ways the static
# checker can't always infer
shard_map = functools.partial(jax.shard_map, check_vma=False)


__all__ = [
    "ShardedRows",
    "all_reduce_sum",
    "all_gather_blocks",
    "all_to_all_blocks",
    "reduce_scatter_sum",
    "ring_shift",
]


# names every collective of the sharded ALS half in the HLO metadata
EXCHANGE_SCOPE = "als.exchange"


class ShardedRows:
    """Rows of a block-sharded ``[M, R]`` table by GLOBAL row id, from
    inside a ``shard_map`` body, without any device ever holding more of
    the table than its own ``[M/d, R]`` shard (ALX's sharded gather,
    arXiv 2112.02194).

    Every device asks for the rows of its own ``[B, K]`` ids.  The ids
    of all devices are all-gathered (4 bytes an entry); each device
    looks up, in its shard alone, the ids that fall in its block of
    rows and leaves zeros for the rest (:meth:`spread`, then the
    caller's own gather of ``[d*B, K, R]``); a reduce-scatter sums the
    d partial answers and hands each device the ``[B, K, R]`` rows it
    asked for (:meth:`collect`).  Exactly one of the d terms of each
    sum is non-zero, so the rows are the table's own bits.
    """

    def __init__(self, axis: str, shard_rows: int):
        self.axis = axis
        self.shard_rows = shard_rows

    def spread(self, idx: jax.Array, valid: jax.Array):
        """``[B, K]`` global ids and their validity -> ``[d*B, K]`` ids
        local to this device's shard and the mask of those it owns."""
        with jax.named_scope(EXCHANGE_SCOPE):
            asked = jax.lax.all_gather(
                jnp.where(valid, idx, -1), self.axis, axis=0, tiled=True
            )
        local = asked - jax.lax.axis_index(self.axis) * self.shard_rows
        # an invalid slot asks for -1, which no device owns
        mine = (local >= 0) & (local < self.shard_rows)
        return jnp.where(mine, local, 0), mine

    def collect(self, rows: jax.Array) -> jax.Array:
        """``[d*B, K, R]`` partial answers -> this device's ``[B, K, R]``."""
        with jax.named_scope(EXCHANGE_SCOPE):
            return jax.lax.psum_scatter(
                rows, self.axis, scatter_dimension=0, tiled=True
            )


def all_reduce_sum(x: jax.Array, mesh: Mesh, axis: str = DATA_AXIS):
    """Sum a data-sharded array's shards: [N, ...] sharded -> same value
    replicated on every device (the ``psum`` of a per-shard partial)."""

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(),
    )
    def _sum(shard):
        return jax.lax.psum(jnp.sum(shard, axis=0, keepdims=True), axis)

    return _sum(x)[0]


def all_gather_blocks(x: jax.Array, mesh: Mesh, axis: str = DATA_AXIS):
    """Gather a sharded leading dim onto every device (factor-block
    exchange): [N/d per device] -> [N] replicated."""

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(),
    )
    def _gather(shard):
        return jax.lax.all_gather(shard, axis, tiled=True)

    return _gather(x)


def all_to_all_blocks(x: jax.Array, mesh: Mesh, axis: str = DATA_AXIS):
    """Shard-transpose: device i's j-th block becomes device j's i-th
    block (the shuffle primitive — Spark's repartition-by-key fabric
    collapsed to one XLA collective over ICI).

    ``x`` is sharded on its leading dim, and each device's shard is
    itself organized as ``d`` destination blocks: ``[d*B, ...]`` sharded
    -> ``[d*B, ...]`` sharded, where the returned device-j shard is
    ``concat(block j of every device i, over i)``.  This is the device-
    side form of the owner-exchange the multi-host ingest does over the
    wire (`parallel/ingest.exchange_ratings_by_owner`): rows grouped by
    owning shard on the way in, landing grouped by origin on the way
    out.  Block sizes must be equal (pad the trailing block — the same
    contract as the host exchange)."""
    d = mesh.shape[axis]
    if x.shape[0] % (d * d) != 0:
        raise ValueError(
            f"all_to_all_blocks needs leading dim divisible by "
            f"mesh_size^2 = {d * d} (d equal blocks per device shard); "
            f"got shape {x.shape}"
        )

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
    )
    def _a2a(shard):  # [d*B, ...] per device
        blocks = shard.reshape((d, shard.shape[0] // d) + shard.shape[1:])
        out = jax.lax.all_to_all(
            blocks, axis, split_axis=0, concat_axis=0, tiled=False
        )
        return out.reshape(shard.shape)

    return _a2a(x)


def reduce_scatter_sum(x: jax.Array, mesh: Mesh, axis: str = DATA_AXIS):
    """Per-device partials [d, M, ...] (sharded on dim 0) -> the summed
    [M, ...] sharded over the mesh: each device keeps only the slice of
    the sum it owns (the memory-efficient half of an all-reduce)."""
    d = mesh.shape[axis]
    if x.shape[0] != d:
        raise ValueError(
            f"reduce_scatter_sum expects leading dim == mesh axis size "
            f"{d} (one partial per device); got shape {x.shape}"
        )

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
    )
    def _scatter(partial):  # [1, M, ...] per device
        return jax.lax.psum_scatter(
            partial[0], axis, scatter_dimension=0, tiled=True
        )

    return _scatter(x)


def ring_shift(x: jax.Array, mesh: Mesh, axis: str = DATA_AXIS, shift: int = 1):
    """Rotate shards around the mesh ring (block-cyclic ALS-style
    exchange): shard i -> device (i + shift) mod d."""
    n_dev = mesh.shape[axis]
    perm = [(i, (i + shift) % n_dev) for i in range(n_dev)]

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
    )
    def _shift(shard):
        return jax.lax.ppermute(shard, axis, perm)

    return _shift(x)
