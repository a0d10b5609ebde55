"""Coded (parity) factor shards — straggler tolerance for sharded ALS.

The coded-ALS idea (arXiv 2105.03631) applied to this repo's ALX-style
sharded placement (arXiv 2112.02194): alongside the ``d`` row blocks of
a ``P('data', None)`` factor table, maintain one **parity block** — the
elementwise SUM of the ``d`` blocks (the real-arithmetic analogue of an
XOR parity stripe).  Any single block is then recoverable from the
other ``d-1`` plus parity::

    block_i = parity - sum_{j != i} block_j

so a half-iteration (or a sharded top-k batch) whose ``i``-th shard is
late or dead completes from the survivors instead of stalling the whole
mesh behind one slow host — the one failure mode a pod slice actually
has.  Parity ownership ROTATES per half (RAID-5 style) so the extra
write bandwidth of keeping parity fresh is spread across the mesh
rather than hammering one chip.

Two layers live here:

* **Device math** — :func:`build_parity_fn` (parity of a sharded
  table) and :func:`build_coded_gather` (all-gather with dead blocks
  reconstructed in the same program).  Both are ``shard_map`` programs:
  identical on a virtual CPU mesh (tier-1) and a TPU slice.
* **Host orchestration** — :class:`ShardHealth`: consults the
  ``dist.*`` fault-injection points (`resilience/faults.py`) and an
  optional per-hop time budget, decides which shard (if any) must be
  served from parity this half, books
  ``pio_shard_degraded_total{shard}`` / ``pio_shard_lag_seconds`` and a
  ``dist.parity_serve`` span, and remembers kills (a dead worker stays
  dead).  With no fault plan armed and no budget set, a poll is a few
  module-global loads — the happy path costs nothing.

A single parity block tolerates ONE missing shard.  Two simultaneous
holes are unrecoverable by construction; :class:`ShardHealth` raises
:class:`ParityExhausted` loudly instead of silently serving garbage.

Simulated-cluster honesty note: on the in-process fallback mesh the
parity block is materialized REPLICATED (every virtual device holds the
[M/d, R] parity) because single-host placement is moot; on a real pod
the block belongs on the rotating owner.  The degradation *semantics*
— what is reconstructed, when, and what is booked — are identical, and
that is what the tier-1 chaos suite certifies.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..obs import SHARD_DEGRADED_TOTAL, SHARD_LAG_SECONDS, get_tracer, tower
from ..resilience import faults
from .collectives import shard_map
from .mesh import DATA_AXIS

logger = logging.getLogger(__name__)

__all__ = [
    "ParityExhausted",
    "ShardHealth",
    "build_parity_fn",
    "build_coded_gather",
    "row_chunks",
]


class ParityExhausted(RuntimeError):
    """More shards are missing than the parity code can reconstruct."""


# bytes of a shard one step of a parity build or rebuild sums across the
# mesh: the step's psum in and out are its only temporaries, so a shard of
# 6 GB and its parity fit a 16 GB chip beside them
PARITY_CHUNK_BYTES = 256 << 20


def row_chunks(shard) -> tuple:
    """``(step, whole, rest)``: `shard`'s rows as `whole` chunks of `step`
    rows within `PARITY_CHUNK_BYTES`, then `rest` rows (static numbers)."""
    rows = shard.shape[0]
    row_bytes = max(shard.size // max(rows, 1), 1) * shard.dtype.itemsize
    step = max(1, min(rows, PARITY_CHUNK_BYTES // row_bytes))
    return step, rows // step, rows % step


def build_parity_fn(mesh: Mesh, axis: str = DATA_AXIS):
    """Jitted ``[d*S, R] sharded -> [S, R] replicated`` parity (block sum).

    Summed a row chunk at a time (:func:`row_chunks`), each chunk one
    ``psum`` written into place: beside the shard and the parity nothing
    of the shard's size is made.  Called once at trainer/index build; the
    coded half refreshes the parity of the table it just updated with the
    same sum inside its own program.
    """

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(axis, None), out_specs=P(),
    )
    def _par(shard):
        step, whole, rest = row_chunks(shard)

        def put(par, start, size):
            part = jax.lax.dynamic_slice_in_dim(shard, start, size)
            return jax.lax.dynamic_update_slice_in_dim(
                par, jax.lax.psum(part, axis), start, 0)

        par = jax.lax.fori_loop(
            0, whole, lambda c, par: put(par, c * step, step),
            jnp.zeros_like(shard))
        if rest:   # from shapes alone  # piolint: disable=PIO104
            par = put(par, whole * step, rest)
        return par

    return jax.jit(_par)


def build_coded_gather(mesh: Mesh, axis: str = DATA_AXIS):
    """Jitted coded all-gather: assemble the FULL table with any masked
    (late/dead) block reconstructed from parity.

    ``fn(table, parity, ok_mask) -> [M, R] replicated`` where ``table``
    is ``P(axis, None)`` sharded, ``parity`` is the replicated ``[M/d,
    R]`` block sum, and ``ok_mask`` is a replicated ``[d]`` 0/1 vector
    (0 = serve this block from parity).  With all-ones the result is
    bitwise the plain all-gather (the reconstruction branch multiplies
    by zero); with one zero the missing block is ``parity - sum(alive)``
    — exact as long as parity is current with the table.
    """
    d = mesh.shape[axis]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis, None), P(), P()),
        out_specs=P(),
    )
    def _gather(shard, par, ok):
        me = jax.lax.axis_index(axis)
        masked = shard * ok[me].astype(shard.dtype)
        gathered = jax.lax.all_gather(masked, axis, axis=0, tiled=True)
        alive_sum = jax.lax.psum(masked, axis)
        recon = (par - alive_sum).astype(shard.dtype)
        blocks = gathered.reshape((d,) + shard.shape)
        okb = ok.reshape((d,) + (1,) * shard.ndim).astype(shard.dtype)
        out = blocks * okb + recon[None] * (1.0 - okb)
        return out.reshape(gathered.shape)

    return jax.jit(_gather)


def _fire(point: str, max_wait: float = 0.0):
    """One ask-and-degrade fault consultation; the host waits at most
    ``max_wait`` of the injected lag (see ``faults.fired_shard``)."""
    return faults.fired_shard(point, max_wait=max_wait)


class ShardHealth:
    """Host-side shard liveness for one coded run (a train, or a
    serving index's lifetime).

    Per half-iteration / top-k call the orchestrating host calls
    :meth:`poll`, which consults the three ``dist.*`` shard points and
    answers the ``[d]`` ok-mask the coded device program consumes:

    * ``dist.worker_kill`` — the target shard is dead from now on
      (sticky across polls; a killed worker does not come back).
    * ``dist.shard_drop``  — the target shard is out for THIS poll only
      (transient loss: a torn exchange, a dropped heartbeat).
    * ``dist.shard_delay`` — the target shard is SLOW: the rule's delay
      is the simulated wait, observed by this host.  With a hop budget
      set (``hop_budget_s``, or a request :class:`Deadline`'s remaining
      budget split per hop), a wait within budget is tolerated — the
      shard answered late but in time; a wait past budget degrades the
      shard to parity.  With no budget, any fired delay degrades (the
      deterministic default the chaos suite pins).

    Every degradation books ``pio_shard_degraded_total{shard}``,
    observes the lag in ``pio_shard_lag_seconds{op}``, and records a
    ``dist.parity_serve`` span so a degraded sweep is visible in the
    same place every other anomaly is.  Parity ownership rotates per
    poll (:attr:`parity_owner`).
    """

    def __init__(self, n_shards: int, hop_budget_s: Optional[float] = None,
                 op: str = "als.half"):
        if n_shards < 2:
            raise ValueError("coded shards need a mesh of >= 2 devices")
        self.n_shards = n_shards
        self.hop_budget_s = hop_budget_s
        self.op = op
        self.killed: set[int] = set()
        self.degraded_polls = 0
        self.parity_owner = 0
        self._polls = 0

    def poll(self, deadline=None) -> np.ndarray:
        """Consult the fault points; return the ``[d]`` f32 ok-mask for
        the next coded device call (1 = shard on time, 0 = serve from
        parity).  Raises :class:`ParityExhausted` when more than one
        shard is down — a single parity block cannot cover two holes.
        """
        self._polls += 1
        self.parity_owner = (self._polls - 1) % self.n_shards
        degraded: dict[int, float] = {}

        hit = _fire("dist.worker_kill")
        if hit is not None:
            k, lag = hit
            k %= self.n_shards
            if k not in self.killed:
                logger.warning(
                    "shard %d killed (fault plan); serving from parity "
                    "for the rest of the run", k,
                )
            self.killed.add(k)
            degraded[k] = lag
        for k in self.killed:
            degraded.setdefault(k, 0.0)

        hit = _fire("dist.shard_drop")
        if hit is not None:
            s, lag = hit
            degraded[s % self.n_shards] = lag

        budget = self.hop_budget_s
        if deadline is not None:
            # a request deadline splits into per-hop budgets: every
            # shard must answer within its share of what remains
            rem = max(deadline.remaining(), 0.0)
            per_hop = rem / max(self.n_shards, 1)
            budget = per_hop if budget is None else min(budget, per_hop)
        # the host waits out a straggler only up to its hop budget:
        # lag <= budget means the shard answered late but in time;
        # past it (or with no budget at all) it is served from parity
        # WITHOUT waiting the rest of the injected delay — degrading
        # is what keeps the call inside its deadline
        hit = _fire("dist.shard_delay", max_wait=budget or 0.0)
        if hit is not None:
            s, lag = hit
            s %= self.n_shards
            if budget is None or lag > budget:
                degraded[s] = lag
            else:
                # late but within its hop budget: tolerated, but the
                # lag is still evidence worth keeping
                SHARD_LAG_SECONDS.labels(op=self.op).observe(lag)

        if len(degraded) > 1:
            raise ParityExhausted(
                f"shards {sorted(degraded)} are all missing; a single "
                "parity block reconstructs at most one — rebuild the "
                "table or widen the code before continuing"
            )

        ok = np.ones(self.n_shards, np.float32)
        for shard, shard_lag in degraded.items():
            ok[shard] = 0.0
            self.degraded_polls += 1
            SHARD_DEGRADED_TOTAL.labels(shard=str(shard)).inc()
            SHARD_LAG_SECONDS.labels(op=self.op).observe(shard_lag)
            get_tracer().record(
                "dist.parity_serve", shard_lag,
                attrs={"shard": shard, "op": self.op,
                       "sticky": shard in self.killed},
            )
            # pio-tower sink: a degradation during a tracked training
            # run lands in the run manifest (event record + next sweep
            # record), not just in process-local metrics
            tower.note_shard_event({
                "shard": shard, "lagSeconds": round(shard_lag, 6),
                "op": self.op, "sticky": shard in self.killed,
            })
        return ok

    def summary(self) -> dict:
        """Status-JSON view (the serving status block and the harness
        report both render this)."""
        return {
            "shards": self.n_shards,
            "killed": sorted(self.killed),
            "degradedPolls": self.degraded_polls,
            "parityOwner": self.parity_owner,
        }
