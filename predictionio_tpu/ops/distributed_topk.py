"""Ring top-k scoring over a mesh-sharded item table.

Serving's hot op is ``scores = U @ V.T`` + top-k (`ops/topk.py`).  When the
item-factor table outgrows one chip's HBM, it lives sharded over the mesh
(`P("data")` on rows) — and gathering it per query would waste ICI
bandwidth and HBM.  This op keeps every shard where it is and instead
rotates them around the ring (the classic ring-matmul schedule): at each
of the d steps every device scores its resident query block against the
item shard currently passing through, folds the result into a running
top-k, and forwards the shard to its neighbor.  Communication is d-1
shard-sized ppermutes riding neighbor ICI links; nothing is ever
materialized at [B, M].

The same schedule is the building block the long-sequence world calls
ring attention — score-block against rotating KV shards with a running
reduction — applied here to the framework's actual workload (CF scoring).

**Straggler tolerance (pio-armor).**  A serving ring is only as fast as
its slowest shard, so the op composes with the coded-shard machinery
(`parallel/coded.py`): pass the table's ``parity`` block and each call
consults the ``dist.*`` fault points plus a per-shard deadline — the
request :class:`~predictionio_tpu.resilience.Deadline` already in scope
on the serving thread, split into per-hop budgets.  A shard that misses
its hop budget is *served from parity* (its block reconstructed from
the other ``d-1`` plus parity inside the same program), the call
returns within budget, and ``pio_shard_degraded_total{shard}`` books
the degradation.  Reconstruction is exact while parity is current with
the table (always, for a static serving index); a stale parity serves
the shard's last published rows — degraded-but-bounded recall instead
of a stalled ring.

:class:`ShardedTopK` packages the serving-side lifecycle: shard + pad
the item table, build parity once, keep the rotating
:class:`~predictionio_tpu.parallel.coded.ShardHealth`, and read the
request deadline from the resilience scope on every call.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.collectives import shard_map
from ..parallel.mesh import DATA_AXIS
from ..resilience import current_deadline

__all__ = ["ring_topk_scores", "ShardedTopK"]


def ring_topk_scores(
    queries: jax.Array,       # [B, R] replicated query block
    item_shards: jax.Array,   # [M, R] sharded over `axis` (M % d == 0)
    k: int,
    mesh: Mesh,
    axis: str = DATA_AXIS,
    *,
    parity: Optional[jax.Array] = None,   # [M/d, R] replicated block sum
    row_bias: Optional[jax.Array] = None,  # [M] sharded additive bias
    health=None,
    deadline=None,
    hop_budget_s: Optional[float] = None,
):
    """Top-k (values, global indices) of ``queries @ item_table.T``.

    Returns ``([B, k] scores, [B, k] int32 indices)`` replicated.  Index
    space is the global row index of ``item_shards``.

    ``row_bias`` is an additive per-row score bias (sharded like the
    table) — ``-inf`` rows can never win, which is how
    :class:`ShardedTopK` masks its mesh-padding rows.

    With ``parity`` set, the call is straggler-tolerant: before
    dispatch the host polls the ``dist.shard_delay`` /
    ``dist.shard_drop`` / ``dist.worker_kill`` fault points (and the
    per-shard budget derived from ``deadline`` — defaulting to the
    :func:`~predictionio_tpu.resilience.current_deadline` in scope, the
    request deadline serving propagates — or ``hop_budget_s``).  A
    shard flagged late/dead is scored from its parity reconstruction
    instead of waiting on its owner.  ``health`` carries sticky state
    (killed workers) across calls; omitted, an ephemeral tracker is
    built per call.
    """
    d = mesh.shape[axis]
    M = item_shards.shape[0]
    if M % d:
        raise ValueError(f"item count {M} must be divisible by mesh size {d}")
    shard_rows = M // d
    if k > M:
        raise ValueError(f"k={k} > item count {M}")

    ok_arr = None
    if parity is not None and d >= 2:
        from ..parallel.coded import ShardHealth

        if health is None:
            health = ShardHealth(d, hop_budget_s=hop_budget_s,
                                 op="topk.ring")
        if deadline is None:
            deadline = current_deadline()
        ok = health.poll(deadline=deadline)
        if ok.min() < 1.0:
            ok_arr = jnp.asarray(ok, jnp.float32)

    if row_bias is None:
        row_bias = jnp.zeros((M,), queries.dtype)

    fn = _ring_callable(mesh, axis, k, ok_arr is not None)
    if ok_arr is not None:
        return fn(queries, item_shards, row_bias, parity, ok_arr)
    return fn(queries, item_shards, row_bias)


@functools.lru_cache(maxsize=128)
def _ring_callable(mesh: Mesh, axis: str, k: int, coded: bool,
                   candidate_k: int = 0):
    """The jitted ring program per (mesh, axis, k, variant).

    Cached so the serving hot path never re-traces: a per-call closure
    would re-lower the shard_map on EVERY query (hundreds of ms on CPU
    — enough to blow the very deadline the coded variant exists to
    honor).  The ok-mask is a traced operand, so one coded executable
    serves every degradation pattern; batch-size/table-shape variants
    compile once inside the jit cache.

    ``candidate_k > 0`` is the pio-scout variant: each hop scores the
    passing shard's int8-quantized rows first, shortlists the top
    ``candidate_k`` LOCAL candidates, and reranks only those rows from
    the f32 shard before folding — per-hop f32 work drops from
    O(B·M/d·R) to O(B·candidate_k·R) while the int8 scan reads a
    table a quarter the size.  The quantized variant does not compose
    with the coded one (parity reconstructs f32 rows, which have no
    quantized counterpart): :class:`ShardedTopK` routes degraded calls
    to the coded EXACT program instead — correctness over candidate
    savings while a shard is being served from parity.
    """
    if coded and candidate_k:
        raise ValueError(
            "coded and quantized ring variants do not compose; "
            "degraded calls ride the coded exact program"
        )
    d = mesh.shape[axis]
    extra_specs = (P(), P()) if coded else ()
    if candidate_k:
        # int8 shard + its per-row scales rotate with the f32 shard
        extra_specs = (P(axis, None), P(axis))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis)) + extra_specs,
        out_specs=(P(), P()),
    )
    def _ring(q, v_shard, b_shard, *extra):
        # q: [B, R]; v_shard: [M/d, R]; b_shard: [M/d]
        my = jax.lax.axis_index(axis)
        shard_rows = v_shard.shape[0]
        fwd = [(i, (i + 1) % d) for i in range(d)]
        qv0 = qs0 = None
        if coded:
            par, ok_m = extra
            # the late shard's rows, reconstructed from the survivors:
            # exact while parity is current with the table
            masked = v_shard * ok_m[my].astype(v_shard.dtype)
            alive_sum = jax.lax.psum(
                masked.astype(jnp.float32), axis
            )
            recon = (par - alive_sum).astype(v_shard.dtype)
            v0 = masked
        else:
            ok_m = recon = None
            v0 = v_shard
            if candidate_k:
                qv0, qs0 = extra   # [M/d, R] int8, [M/d] f32

        def step(carry, _):
            # the carry only holds the quantized shard when the
            # variant uses it (a scan carry cannot hold None leaves)
            if candidate_k:
                v, b, qv, qs, owner, best_val, best_ix = carry
            else:
                v, b, owner, best_val, best_ix = carry
                qv = qs = None
            if recon is not None:
                v_use = jnp.where(ok_m[owner] > 0, v, recon)
            else:
                v_use = v
            base = owner * shard_rows
            if candidate_k:
                # per-shard candidate stage: int8 scan (+bias so -inf
                # padding rows can't shortlist), then exact rerank of
                # the survivors from the f32 shard
                cscores = (
                    q @ qv.T.astype(jnp.float32)
                ) * qs[None, :] + b[None, :]
                _, cix = jax.lax.top_k(cscores, candidate_k)  # [B, kc]
                rows = v_use[cix]                    # [B, kc, R]
                scores = jnp.einsum("bkr,br->bk", rows, q) + b[cix]
                ix = base + cix.astype(jnp.int32)
            else:
                scores = q @ v_use.T + b[None, :]   # [B, M/d] on the MXU
                ix = base + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, 1
                )
            # fold into the running top-k: concat + re-topk
            cat_val = jnp.concatenate([best_val, scores], axis=1)
            cat_ix = jnp.concatenate([best_ix, ix], axis=1)
            new_val, pos = jax.lax.top_k(cat_val, k)
            new_ix = jnp.take_along_axis(cat_ix, pos, axis=1)
            # pass the shard to the next device; track whose shard we hold
            v = jax.lax.ppermute(v, axis, fwd)
            b = jax.lax.ppermute(b, axis, fwd)
            if candidate_k:
                qv = jax.lax.ppermute(qv, axis, fwd)
                qs = jax.lax.ppermute(qs, axis, fwd)
            owner = jax.lax.ppermute(owner, axis, fwd)
            out = (v, b) + ((qv, qs) if candidate_k else ()) + (
                owner, new_val, new_ix,
            )
            return out, None

        init_val = jnp.full((q.shape[0], k), -jnp.inf, q.dtype)
        init_ix = jnp.zeros((q.shape[0], k), jnp.int32)
        init = (v0, b_shard) + (
            (qv0, qs0) if candidate_k else ()
        ) + (my, init_val, init_ix)
        final, _ = jax.lax.scan(step, init, None, length=d)
        best_val, best_ix = final[-2], final[-1]
        # after d steps every device has folded every shard, so the
        # result is replicated by construction
        return best_val, best_ix

    return jax.jit(_ring)


class ShardedTopK:
    """Serve-time distributed top-k index: sharded item table + parity.

    Built once at model (re)load from the host item-factor table; every
    call answers ``(values, global indices)`` for a replicated query
    block.  The table rows are padded to a mesh multiple with
    ``-inf``-biased rows (never returned), parity is computed once, and
    a single rotating :class:`~predictionio_tpu.parallel.coded.
    ShardHealth` carries straggler state across requests — a worker
    killed under chaos stays killed for this index's lifetime, exactly
    like a real dead host until the next reload.

    The per-request deadline needs NO plumbing: serving's
    ``predict_json`` already runs the device dispatch inside
    ``deadline_scope(request_deadline)``, and :func:`ring_topk_scores`
    reads that scope — the request budget becomes the per-shard hop
    budget.
    """

    def __init__(self, item_factors, mesh: Mesh, axis: str = DATA_AXIS,
                 hop_budget_s: Optional[float] = None,
                 retrieval: str = "exact", candidate_factor: int = 10):
        from ..parallel.coded import ShardHealth, build_parity_fn
        from ..parallel.mesh import pad_to_multiple

        self.mesh = mesh
        self.axis = axis
        d = mesh.shape[axis]
        table = np.asarray(item_factors, np.float32)
        self.n_items = table.shape[0]
        mp = pad_to_multiple(max(self.n_items, d), d)
        padded = np.zeros((mp, table.shape[1]), np.float32)
        padded[: self.n_items] = table
        bias = np.full(mp, -np.inf, np.float32)
        bias[: self.n_items] = 0.0
        sh = NamedSharding(mesh, P(axis, None))
        self.table = jax.device_put(padded, sh)
        self.row_bias = jax.device_put(bias, NamedSharding(mesh, P(axis)))
        self.parity = build_parity_fn(mesh, axis)(self.table)
        self.health = (
            ShardHealth(d, hop_budget_s=hop_budget_s, op="topk.ring")
            if d >= 2 else None
        )
        # pio-scout per-shard candidate stage: int8 shards + per-row
        # scales, sharded like the table, rotated with it.  "ivf" maps
        # to "int8" here — coarse clusters are a whole-catalog
        # structure and don't shard; the flat int8 scan per hop is the
        # ring's candidate stage.
        self.candidate_factor = candidate_factor
        if retrieval not in ("exact", "int8", "ivf"):
            raise ValueError(
                f"retrieval must be 'exact', 'int8' or 'ivf', "
                f"got {retrieval!r}"
            )
        self.retrieval = "int8" if retrieval == "ivf" else retrieval
        if self.retrieval == "int8":
            from .ann import quantize_rows

            q8, scale = quantize_rows(padded)
            self.q_table = jax.device_put(q8, sh)
            self.q_scale = jax.device_put(
                scale, NamedSharding(mesh, P(axis))
            )
        else:
            self.q_table = self.q_scale = None

    def _candidate_k(self, k: int) -> int:
        """Per-hop shortlist width: candidate_factor*k, at least k
        (d hops each contribute this many exact-reranked rows), capped
        at the shard height (a shortlist covering the whole shard IS
        the exact scan)."""
        shard_rows = self.table.shape[0] // self.mesh.shape[self.axis]
        return min(max(self.candidate_factor * k, k), shard_rows)

    def __call__(self, queries, k: int, deadline=None):
        if isinstance(queries, jax.Array):
            q = jnp.atleast_2d(queries.astype(jnp.float32))
        else:
            # serving hands host arrays: shape them on the host —
            # `jnp.atleast_2d` is a jit of its own per batch size,
            # compiled in the middle of the first request of each size
            q = jnp.asarray(
                np.atleast_2d(np.asarray(queries, np.float32)))
        k = min(k, self.n_items)
        if self.q_table is not None:
            ok = None
            if self.health is not None:
                ok = self.health.poll(
                    deadline=deadline or current_deadline()
                )
            if ok is None or ok.min() >= 1.0:
                fn = _ring_callable(self.mesh, self.axis, k, False,
                                    self._candidate_k(k))
                return fn(q, self.table, self.row_bias,
                          self.q_table, self.q_scale)
            # degraded: parity reconstruction has no quantized
            # counterpart, so the hop rides the coded EXACT program —
            # correctness over candidate savings while a shard is down
            fn = _ring_callable(self.mesh, self.axis, k, True)
            return fn(q, self.table, self.row_bias, self.parity,
                      jnp.asarray(ok, jnp.float32))
        return ring_topk_scores(
            q, self.table, k, self.mesh, self.axis,
            parity=self.parity if self.health is not None else None,
            row_bias=self.row_bias,
            health=self.health,
            deadline=deadline,
        )

    def warm(self, k: int, batch: int = 1) -> None:
        """Pre-compile EVERY ring variant this index can dispatch
        (clean + coded + the quantized candidate one under
        retrieval != exact) for this (batch, k) shape, bypassing the
        health poll — a first degradation must not pay a mid-request
        XLA compile on top of the straggler it is already absorbing
        (the compile would blow the very deadline the coded path
        exists to honor)."""
        k = min(k, self.n_items)
        q = jnp.zeros((batch, self.table.shape[1]), jnp.float32)
        clean = _ring_callable(self.mesh, self.axis, k, False)
        clean(q, self.table, self.row_bias)
        if self.q_table is not None:
            quant = _ring_callable(self.mesh, self.axis, k, False,
                                   self._candidate_k(k))
            quant(q, self.table, self.row_bias, self.q_table,
                  self.q_scale)
        if self.health is not None:
            coded = _ring_callable(self.mesh, self.axis, k, True)
            d = self.mesh.shape[self.axis]
            coded(q, self.table, self.row_bias, self.parity,
                  jnp.ones((d,), jnp.float32))

    def summary(self) -> dict:
        """Status-JSON block (`distributedTopk` in serving status)."""
        out = {
            "items": self.n_items,
            "shards": int(self.mesh.shape[self.axis]),
            "retrieval": self.retrieval,
        }
        if self.retrieval == "int8":
            out["candidateFactor"] = self.candidate_factor
        if self.health is not None:
            out.update(self.health.summary())
        return out
