"""Exact top-k over a mesh-sharded item table, the table staying where it is.

Serving's hot op is ``scores = U @ V.T`` + top-k (`ops/topk.py`).  When the
item-factor table outgrows one chip's HBM it lives sharded over the mesh
(`P("data")` on rows), and nothing of it moves to answer a batch: the
batch's query block is ``[B, R]`` (32 KB at 64 x 128) and replicated, so
every chip scans ITS OWN shard with the one-chip scorer (`ops/topk.py`'s
blocked path: the `pio_block_max` scan, the block select, the rescoring of
the chosen blocks and the k-pass select; the product and ``lax.top_k``
where a shard is too short for blocks), offsets its candidates' ids by its
first row, and ONE all-gather of the ``d x [B, k]`` (value, id) candidates
and a final select by (score descending, id ascending) finish the batch.
The clean program's only collective is that all-gather: no shard leaves its
chip and no ``[B, M/d]`` score matrix is written.  The two stages carry the
named scopes ``topk.shard_scan`` and ``topk.shard_merge``.

Rows past the table (the zero rows that pad it to a multiple of the mesh,
fewer than d) are dropped by id: each chip keeps ``k`` plus that many
candidates, so that a padding row it kept cannot push a real one out, and a
candidate whose id lies past the table scores ``-inf``.  A shard shorter
than ``k`` pads its candidates with ``-inf``.

**Straggler tolerance (pio-armor).**  The batch waits for its slowest
shard, so the op composes with the coded-shard machinery
(`parallel/coded.py`): with the table's ``parity`` block (the sum of the
shards, replicated) each call consults the ``dist.*`` fault points plus a
per-shard deadline, the request
:class:`~predictionio_tpu.resilience.Deadline` already in scope on the
serving thread split into per-shard budgets.  A shard that misses its
budget is *served from parity*: its candidates are dropped, and its rows
are rebuilt as ``parity - sum(others)`` one row chunk at a time
(`parallel/coded.row_chunks`: a chunk's ``psum``, then the chunk scanned
like a shard), so that nothing of a shard's size is made beside the shard
and the parity.  The call returns within budget and
``pio_shard_degraded_total{shard}`` books the degradation.  Reconstruction
is exact while parity is current with the table (always, for a static
serving index); a stale parity serves the shard's last published rows.

:class:`ShardedTopK` packages the serving-side lifecycle: place the table's
rows chip by chip (:func:`place_rows`), build parity once, keep the
:class:`~predictionio_tpu.parallel.coded.ShardHealth`, and read the request
deadline from the resilience scope on every call.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.collectives import shard_map
from ..parallel.mesh import DATA_AXIS, pad_to_multiple
from ..resilience import current_deadline
from .topk import (TOPK_PATH, ItemTables, _blocked_topk, _select_k,
                   block_items, rows_per_line)

__all__ = ["sharded_topk_scores", "place_rows", "ShardedTopK"]


def place_rows(rows, mesh: Mesh, axis: str = DATA_AXIS,
               dtype=np.float32) -> jax.Array:
    """The ``[M, ...]`` table as `dtype` rows sharded ``P(axis)`` over
    `mesh`, padded with zero rows to a multiple of the mesh.  A table that
    already lies so (drawn on the mesh) is taken as it is; a host table is
    placed chip by chip from its own rows, each chip handed its slice (the
    last one's padding added to that slice alone), never a padded whole
    copy."""
    d = mesh.shape[axis]
    n = rows.shape[0]
    padded = pad_to_multiple(max(n, d), d)
    shape = (padded,) + tuple(rows.shape[1:])
    sharding = NamedSharding(mesh, P(axis, *[None] * (len(shape) - 1)))
    if (isinstance(rows, jax.Array) and padded == n
            and rows.dtype == dtype
            and rows.sharding.is_equivalent_to(sharding, len(shape))):
        return rows
    host = np.asarray(rows)

    def piece(index):
        lo, hi, _ = index[0].indices(padded)
        part = np.asarray(host[min(lo, n):min(hi, n)], dtype)
        if len(part) < hi - lo:
            part = np.concatenate(
                [part, np.zeros((hi - lo - len(part),) + shape[1:], dtype)])
        return part

    return jax.make_array_from_callback(shape, sharding, piece)


def _local_topk(q, rows, k: int, bias=None):
    """The `k` best of ``q @ rows.T`` on one chip: ``([B, k] values, [B,
    k] int32 row numbers)``, best first, ties to the lower row, ``-inf``
    past the rows' count.  The one-chip scorer's own forms: the blocked
    path where its blocks pay (rows of whole lines, a catalogue long enough,
    no additive bias: `ops/topk.block_items`), else the product and
    ``lax.top_k``."""
    n, rank = rows.shape
    width = min(k, n)
    blk = 0
    if bias is None and rows_per_line(rank) == 1:
        blk = block_items(q.shape[0], n, rank, width, rows.dtype.itemsize)
    if blk:
        vals, ids = _blocked_topk(q, ItemTables(None, rows), width, blk)
    else:
        scores = q @ rows.T
        if bias is not None:
            scores = scores + bias[None, :]
        vals, ids = jax.lax.top_k(scores, width)
    if width < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - width)),
                       constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, k - width)), constant_values=n)
    return vals, ids.astype(jnp.int32)


def _fold(best, found, k: int):
    """The k best of two candidate lists by (score, id)."""
    return _select_k(jnp.concatenate([best[0], found[0]], axis=1),
                     jnp.concatenate([best[1], found[1]], axis=1), k)


def _quantized_topk(q, rows, q_rows, q_scale, k: int, candidate_k: int):
    """pio-scout per shard: the int8 rows scanned, the `candidate_k` best
    kept, their float32 rows gathered and scored again, the `k` best of
    those."""
    width = min(candidate_k, rows.shape[0])
    approx = (q @ q_rows.T.astype(jnp.float32)) * q_scale[None, :]
    _, cix = jax.lax.top_k(approx, width)                    # [B, kc]
    exact = jnp.einsum("bkr,br->bk", rows[cix], q)
    vals, pos = jax.lax.top_k(exact, min(k, width))
    ids = jnp.take_along_axis(cix, pos, axis=1)
    if width < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - width)),
                       constant_values=-jnp.inf)
        ids = jnp.pad(ids, ((0, 0), (0, k - width)),
                      constant_values=rows.shape[0])
    return vals, ids.astype(jnp.int32)


def _rebuilt_topk(q, shard, parity, ok, k: int, axis: str, bias=None):
    """The `k` best of the late shard's rows (the one whose `ok` entry is
    0), rebuilt as ``parity - sum(other shards)`` a row chunk at a time and
    each chunk scanned like a shard: the same answer on every chip, ids
    the late shard's own row numbers."""
    from ..parallel.coded import row_chunks

    me = jax.lax.axis_index(axis)
    late = jnp.argmin(ok)
    mine = ok[me].astype(shard.dtype)
    step, whole, rest = row_chunks(shard)

    def chunk(start, size):
        with jax.named_scope("topk.shard_rebuild"):
            own = jax.lax.dynamic_slice_in_dim(shard, start, size) * mine
            rebuilt = (jax.lax.dynamic_slice_in_dim(parity, start, size)
                       - jax.lax.psum(own, axis)).astype(shard.dtype)
            b = None
            if bias is not None:
                b = jax.lax.psum(jnp.where(
                    me == late, jax.lax.dynamic_slice_in_dim(bias, start, size),
                    0.0), axis)
        vals, ids = _local_topk(q, rebuilt, k, b)
        return vals, ids + start

    best = (jnp.full((q.shape[0], k), -jnp.inf, jnp.float32),
            jnp.zeros((q.shape[0], k), jnp.int32))
    best = jax.lax.fori_loop(
        0, whole, lambda c, best: _fold(best, chunk(c * step, step), k),
        best)
    if rest:
        best = _fold(best, chunk(whole * step, rest), k)
    return best, late


def _merge(vals, ids, k: int, axis: str, extra=None):
    """ONE all-gather of every chip's ``[B, kc]`` candidates (values
    bitcast to int32 beside the ids), and the k best of all of them (and
    of `extra`, the same on every chip) by (score descending, id
    ascending), as the one-chip select takes them."""
    with jax.named_scope("topk.shard_merge"):
        both = jnp.stack([jax.lax.bitcast_convert_type(vals, jnp.int32),
                          ids])
        every = jax.lax.all_gather(both, axis, axis=2, tiled=True)
        vals = jax.lax.bitcast_convert_type(every[0], jnp.float32)
        ids = every[1]
        if extra is not None:
            vals = jnp.concatenate([vals, extra[0]], axis=1)
            ids = jnp.concatenate([ids, extra[1]], axis=1)
        return _select_k(vals, ids, k)


@functools.lru_cache(maxsize=128)
def _sharded_callable(mesh: Mesh, axis: str, k: int, coded: bool,
                      candidate_k: int = 0, biased: bool = False):
    """The jitted sharded program per (mesh, axis, k, variant); the
    table's real row count ``n_valid`` is a static argument of the call.

    Cached so the serving hot path never re-traces: a per-call closure
    would re-lower the shard_map on EVERY query (hundreds of ms on CPU,
    enough to blow the very deadline the coded variant exists to honor).
    The ok-mask is a traced operand, so one coded executable serves every
    degradation pattern; batch sizes compile once inside the jit cache.

    Operands after ``(queries, table)``: ``(parity, ok)`` when `coded`;
    ``(int8 rows, row scales)`` when ``candidate_k > 0`` (pio-scout: each
    shard shortlists its `candidate_k` best by the int8 rows and scores
    those again from its float32 rows); ``(bias,)`` sharded like the table
    when `biased` (an additive score a row; the dense form).  The quantized
    variant does not compose with the coded one (parity rebuilds float32
    rows, which have no quantized counterpart): :class:`ShardedTopK` routes
    degraded calls to the coded EXACT program instead."""
    if coded and candidate_k:
        raise ValueError(
            "coded and quantized variants do not compose; degraded calls "
            "ride the coded exact program"
        )
    d = mesh.shape[axis]
    rows_spec = P(axis, None)
    extra_specs = ()
    if coded:
        extra_specs = (P(), P())
    elif candidate_k:
        extra_specs = (rows_spec, P(axis))
    if biased:
        extra_specs += (P(axis),)

    def body(q, shard, *extra, n_valid):
        rows = shard.shape[0]
        # a padding row a chip keeps can push no real one out
        kc = k + d * rows - n_valid
        bias = extra[-1] if biased else None
        me = jax.lax.axis_index(axis)
        with jax.named_scope("topk.shard_scan"):
            if candidate_k:
                vals, ids = _quantized_topk(q, shard, extra[0], extra[1],
                                            kc, candidate_k)
            else:
                vals, ids = _local_topk(q, shard, kc, bias)
            ids = ids + me * rows
            vals = jnp.where(ids < n_valid, vals, -jnp.inf)
        rebuilt = None
        if coded:
            parity, ok = extra[0], extra[1]
            # a late shard's own answer is not waited for
            vals = jnp.where(ok[me] > 0, vals, -jnp.inf)
            (r_vals, r_ids), late = _rebuilt_topk(q, shard, parity, ok, kc,
                                                  axis, bias)
            r_ids = r_ids + late * rows
            r_vals = jnp.where((r_ids < n_valid) & (ok.min() < 1), r_vals,
                               -jnp.inf)
            rebuilt = (r_vals, r_ids)
        return _merge(vals, ids, k, axis, rebuilt)

    def sharded_topk(q, table, *extra, n_valid: int):
        return shard_map(
            functools.partial(body, n_valid=n_valid), mesh=mesh,
            in_specs=(P(), rows_spec) + extra_specs,
            out_specs=(P(), P()),
        )(q, table, *extra)

    # the program's name in a profile (`jit_<name>`): a trace reduction
    # finds the clean program's runs by it
    sharded_topk.__name__ = sharded_topk.__qualname__ = "sharded_topk" + (
        "_coded" if coded else "_int8" if candidate_k else "")
    return jax.jit(sharded_topk, static_argnames=("n_valid",))


def sharded_topk_scores(
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
    table): ``-inf`` rows can never win.  A shard then takes the dense form.

    With ``parity`` set, the call is straggler-tolerant: before dispatch
    the host polls the ``dist.shard_delay`` / ``dist.shard_drop`` /
    ``dist.worker_kill`` fault points (and the per-shard budget derived
    from ``deadline``, defaulting to the
    :func:`~predictionio_tpu.resilience.current_deadline` in scope, the
    request deadline serving propagates, or ``hop_budget_s``).  A shard
    flagged late or dead is scored from its parity reconstruction instead
    of waiting on its owner.  ``health`` carries sticky state (killed
    workers) across calls; omitted, an ephemeral tracker is built per call.
    """
    d = mesh.shape[axis]
    M = item_shards.shape[0]
    if M % d:
        raise ValueError(f"item count {M} must be divisible by mesh size {d}")
    if k > M:
        raise ValueError(f"k={k} > item count {M}")

    ok = None
    if parity is not None and d >= 2:
        from ..parallel.coded import ShardHealth

        if health is None:
            health = ShardHealth(d, hop_budget_s=hop_budget_s,
                                 op="topk.sharded")
        if deadline is None:
            deadline = current_deadline()
        ok = health.poll(deadline=deadline)
        if ok.min() >= 1.0:
            ok = None

    TOPK_PATH.labels(path="sharded").inc()
    extra = () if ok is None else (parity, jnp.asarray(ok, jnp.float32))
    if row_bias is not None:
        extra += (row_bias,)
    fn = _sharded_callable(mesh, axis, k, ok is not None,
                           biased=row_bias is not None)
    return fn(queries, item_shards, *extra, n_valid=M)


class ShardedTopK:
    """Serve-time distributed top-k index: sharded item table + parity.

    Built once at model (re)load from the item-factor table, a host array
    or one already sharded on the mesh (:func:`place_rows`); every call
    answers ``(values, global indices)`` for a replicated query block.
    Parity is computed once (by row chunks), and a single
    :class:`~predictionio_tpu.parallel.coded.ShardHealth` carries straggler
    state across requests: a worker killed under chaos stays killed for
    this index's lifetime, exactly like a real dead host until the next
    reload.

    The per-request deadline needs NO plumbing: serving's ``predict_json``
    already runs the device dispatch inside
    ``deadline_scope(request_deadline)``, and every call reads that scope:
    the request budget becomes the per-shard budget.
    """

    def __init__(self, item_factors, mesh: Mesh, axis: str = DATA_AXIS,
                 hop_budget_s: Optional[float] = None,
                 retrieval: str = "exact", candidate_factor: int = 10):
        from ..parallel.coded import ShardHealth, build_parity_fn

        if retrieval not in ("exact", "int8", "ivf"):
            raise ValueError(
                f"retrieval must be 'exact', 'int8' or 'ivf', "
                f"got {retrieval!r}"
            )
        self.mesh = mesh
        self.axis = axis
        d = mesh.shape[axis]
        self.n_items = int(item_factors.shape[0])
        self.table = place_rows(item_factors, mesh, axis)
        multi = d >= 2
        self.parity = build_parity_fn(mesh, axis)(self.table) if multi \
            else None
        self.health = (
            ShardHealth(d, hop_budget_s=hop_budget_s, op="topk.sharded")
            if multi else None
        )
        # pio-scout per-shard candidate stage: int8 shards + per-row
        # scales, sharded like the table.  "ivf" maps to "int8" here:
        # coarse clusters are a whole-catalog structure and don't shard;
        # the flat int8 scan of each shard is the candidate stage.
        self.candidate_factor = candidate_factor
        self.retrieval = "int8" if retrieval == "ivf" else retrieval
        self.q_table = self.q_scale = None
        if self.retrieval == "int8":
            from .ann import quantize_rows

            q8, scale = quantize_rows(item_factors)
            self.q_table = place_rows(q8, mesh, axis, np.int8)
            self.q_scale = place_rows(scale, mesh, axis, np.float32)

    @property
    def shard_rows(self) -> int:
        return self.table.shape[0] // self.mesh.shape[self.axis]

    def _candidate_k(self, k: int) -> int:
        """Per-shard shortlist width: candidate_factor*k, at least k,
        capped at the shard height (a shortlist covering the whole shard
        IS the exact scan)."""
        return min(max(self.candidate_factor * k, k), self.shard_rows)

    def _programs(self, k: int) -> list:
        """``(callable, extra operands)`` of every program this index can
        dispatch at `k`: clean (or quantized), and coded with all shards
        on time, which rebuilds nothing it keeps."""
        if self.q_table is not None:
            clean = (_sharded_callable(self.mesh, self.axis, k, False,
                                       self._candidate_k(k)),
                     (self.q_table, self.q_scale))
        else:
            clean = (_sharded_callable(self.mesh, self.axis, k, False), ())
        if self.health is None:
            return [clean]
        d = self.mesh.shape[self.axis]
        return [clean, (_sharded_callable(self.mesh, self.axis, k, True),
                        (self.parity, jnp.ones((d,), jnp.float32)))]

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
        ok = None
        if self.health is not None:
            ok = self.health.poll(deadline=deadline or current_deadline())
        TOPK_PATH.labels(path="sharded").inc()
        if ok is None or ok.min() >= 1.0:
            fn, extra = self._programs(k)[0]
            return fn(q, self.table, *extra, n_valid=self.n_items)
        # degraded: parity rebuilds float32 rows, which have no quantized
        # counterpart, so the call rides the coded EXACT program —
        # correctness over candidate savings while a shard is down
        fn = _sharded_callable(self.mesh, self.axis, k, True)
        return fn(q, self.table, self.parity, jnp.asarray(ok, jnp.float32),
                  n_valid=self.n_items)

    def warm(self, k: int, batch: int = 1) -> None:
        """Pre-compile EVERY program this index can dispatch (clean or
        quantized, and coded) for this (batch, k) shape, bypassing the
        health poll: a first degradation must not pay a mid-request XLA
        compile on top of the straggler it is already absorbing (the
        compile would blow the very deadline the coded path exists to
        honor)."""
        k = min(k, self.n_items)
        q = jnp.zeros((batch, self.table.shape[1]), jnp.float32)
        for fn, extra in self._programs(k):
            jax.block_until_ready(
                fn(q, self.table, *extra, n_valid=self.n_items))

    def summary(self) -> dict:
        """Status-JSON block (`distributedTopk` in serving status): what a
        chip holds, and the health of the shards."""
        rank = self.table.shape[1]
        out = {
            "items": self.n_items,
            "shards": int(self.mesh.shape[self.axis]),
            "shardRows": self.shard_rows,
            "shardBytes": self.shard_rows * rank * self.table.dtype.itemsize,
            "parityBytes": 0 if self.parity is None else int(
                self.parity.nbytes),
            "retrieval": self.retrieval,
        }
        if self.retrieval == "int8":
            out["candidateFactor"] = self.candidate_factor
        if self.health is not None:
            out.update(self.health.summary())
        return out
