"""Shared template helpers."""

from __future__ import annotations

import time
from typing import NamedTuple, Optional, Sequence

import numpy as np

from ..obs import get_registry, log_buckets
from ..obs.timeline import annotate

__all__ = ["BatchFilter", "DeviceTableMixin", "RowFilter", "batch_filter",
           "filter_bias_mask", "normalize_rows", "pow2_ladder",
           "warm_batched_topk", "warm_shapes"]

_registry = get_registry()
FILTER_ROWS = _registry.counter(
    "pio_filter_rows_total",
    "Rows of the batches the templates' batch_predict dispatched, by the "
    "form their batch's filters took: none, ids (excluded item ids "
    "applied on the device) or mask (a [B, M] additive mask built on "
    "the host: categories, a whiteList, or a list past the ids' width)",
    labels=("filter",),
)
FILTER_EXCLUDE_WIDTH = _registry.counter(
    "pio_filter_exclude_width_total",
    "Batches dispatched with excluded ids, by the width of their ids "
    "array: the rung of ops.topk.EXCLUDE_LADDER that the batch's longest "
    "list took",
    labels=("width",),
)
FILTER_EXCLUDED_IDS = _registry.counter(
    "pio_filter_excluded_ids_total",
    "Excluded item ids dispatched to the device (the ids arrays' real "
    "entries, their -1 padding left out)",
).child()
FILTER_BUILD_SECONDS = _registry.histogram(
    "pio_filter_build_seconds",
    "Host time of one batch's `pio.filter.build` span: its queries' "
    "filters resolved into the ids array or the mask",
    buckets=log_buckets(1e-6, 10.0, per_decade=4),
).child()


def normalize_rows(table: np.ndarray) -> np.ndarray:
    """Row-normalize a factor table in f32 — the shared train-time
    step of the normalized-table cosine path (itemsimilarity and,
    since pio-lens, similarproduct): inner product over the stored
    table IS cosine, so the exact scorer and the two-stage int8/IVF
    retriever serve cosine with no per-query normalization."""
    t = np.asarray(table, np.float32)
    return t / (np.linalg.norm(t, axis=-1, keepdims=True) + 1e-9)


class DeviceTableMixin:
    """Lazy one-time host->device transfer of model factor tables, cached on
    the model instance (serving hot-path: every scoring call reuses the
    device-resident arrays).

    ``dtype`` lets serving trade precision for HBM bandwidth: a
    ``bfloat16`` table halves the bytes each scoring matmul reads, which
    is the scoring bottleneck for large item tables, at a ranking-only
    precision cost (RMSE-parity training is unaffected — this is
    serve-time only).  Each dtype is cached separately.
    """

    def _cached_device(self, cache_name: str, source,
                       dtype: Optional[str] = None):
        import jax.numpy as jnp

        key = f"{cache_name}_{dtype or 'native'}"
        dev = getattr(self, key, None)
        if dev is None:
            dev = jnp.asarray(source)
            if dtype:
                dev = dev.astype(jnp.dtype(dtype))
            setattr(self, key, dev)
        return dev

    def device_item_factors(self, dtype: Optional[str] = None):
        return self._cached_device(
            "_dev_item_factors", self.item_factors, dtype
        )

    def patch_device_item_rows(
        self, ixs, rows, appended: Optional[np.ndarray] = None
    ) -> None:
        """pio-live delta apply: patch every CACHED device item table in
        place (row writes + appends) instead of dropping the caches and
        re-uploading the whole table on the next query.

        The device tables are the serve-time top-k index — every query's
        score matmul reads them — so this is what makes a fold-in visible
        to predictions without a stop-the-world reload.  Normalized
        caches get their patched rows re-normalized (in f32, matching
        ``device_item_factors_normalized``).  Caches that don't exist
        yet are left absent: they'll be built lazily from the already-
        patched host table.  Each updated array is swapped in with one
        attribute rebind, so a concurrent reader sees the old table or
        the new one, never a torn row.
        """
        import jax.numpy as jnp

        if len(ixs) == 0 and (appended is None or len(appended) == 0):
            return
        ixs_d = jnp.asarray(np.asarray(ixs, np.int32))
        rows_np = np.asarray(rows, np.float32)
        app_np = (
            np.asarray(appended, np.float32)
            if appended is not None and len(appended) else None
        )

        def norm(a: np.ndarray) -> np.ndarray:
            return a / (
                np.linalg.norm(a, axis=-1, keepdims=True) + 1e-9
            )

        from ..ops.topk import patch_packed_rows

        # the host table is the patched one already (see above)
        n_before = len(self.item_factors) - (
            0 if app_np is None else len(app_np)
        )
        for attr in list(vars(self)):
            if attr.startswith("_dev_item_packed_"):
                # the packed rows of `device_item_tables`: a scatter of
                # the delta, like the row writes below
                setattr(self, attr, patch_packed_rows(
                    getattr(self, attr), n_before, ixs_d, rows_np, app_np
                ))
            if not attr.startswith("_dev_item_factors_"):
                continue
            normed = attr.startswith("_dev_item_factors_norm_")
            transposed = attr.startswith("_dev_item_factors_t_")
            dev = getattr(self, attr)
            src_rows = norm(rows_np) if normed else rows_np
            src_app = (
                None if app_np is None
                else (norm(app_np) if normed else app_np)
            )
            if transposed:
                # the [R, M] serving layout: appended rows become
                # appended COLUMNS, patched rows become column writes
                if src_app is not None:
                    dev = jnp.concatenate(
                        [dev, jnp.asarray(src_app.T).astype(dev.dtype)],
                        axis=1,
                    )
                if len(rows_np):
                    dev = dev.at[:, ixs_d].set(
                        jnp.asarray(src_rows.T).astype(dev.dtype)
                    )
            else:
                if src_app is not None:
                    dev = jnp.concatenate(
                        [dev, jnp.asarray(src_app).astype(dev.dtype)],
                        axis=0,
                    )
                if len(rows_np):
                    dev = dev.at[ixs_d].set(
                        jnp.asarray(src_rows).astype(dev.dtype)
                    )
            setattr(self, attr, dev)

    def device_item_factors_t(self, dtype: Optional[str] = None):
        """The item table PRE-TRANSPOSED to ``[R, M]`` (contiguous) —
        the layout ``ops.topk.batch_topk_scores_t`` scores against: the
        items lie on the lanes, so the blocked scan streams ``[R, TM]``
        tiles of it as the matmul's right operand.  Cached per dtype;
        pio-live delta applies patch it column-wise in place."""
        import jax.numpy as jnp

        key = f"_dev_item_factors_t_{dtype or 'native'}"
        dev = getattr(self, key, None)
        if dev is None:
            dev = jnp.asarray(np.ascontiguousarray(
                np.asarray(self.item_factors).T
            ))
            if dtype:
                dev = dev.astype(jnp.dtype(dtype))
            setattr(self, key, dev)
        return dev

    def device_item_tables(self, dtype: Optional[str] = None):
        """What the batched scorer is handed
        (``ops.topk.batch_topk_scores_t``): the transposed table for the
        scan and the packed rows (``ops.topk.pack_rows``, cached per
        dtype beside the tables) for rescoring the chosen blocks.  At a
        rank whose rows pack into no line the scorer has no blocked
        path, and gets the transposed table alone: no third copy.  At a
        rank of whole lines (128) it gets the row-major table alone."""
        import jax

        from ..ops.topk import ItemTables, pack_rows, rows_per_line

        p = rows_per_line(np.shape(self.item_factors)[1])
        if p == 1:
            # a row is whole lines: the row-major table is its own packed
            # form and the scan reads it too (ItemTables): ONE copy
            return ItemTables(None, self.device_item_factors(dtype))
        if not p:
            return self.device_item_factors_t(dtype)
        # the [M, R] table up before the transposed one's upload is
        # issued: with both in flight at once the device held neither
        # until seconds after the warm-up had returned, and a
        # warm-started server's first batch waited that long in `fetch`
        # (PERF.md, PR 31)
        table = jax.block_until_ready(self.device_item_factors(dtype))
        table_t = self.device_item_factors_t(dtype)
        key = f"_dev_item_packed_{dtype or 'native'}"
        packed = getattr(self, key, None)
        if packed is None:
            packed = pack_rows(table)
            setattr(self, key, packed)
        return ItemTables(table_t, packed)

    def device_ann_index(self, cfg):
        """Lazy per-config two-stage ANN retriever (pio-scout), cached
        on the model like the device tables: int8 table + scale (+
        IVF centroids/members) are serve-time artifacts built once per
        model (re)load and delta-PATCHED in place thereafter
        (:meth:`patch_ann_indexes`).  ``cfg`` is a
        ``retrieval.RetrievalConfig``; each distinct config caches its
        own index (mirrors the per-dtype device-table caches)."""
        from ..retrieval import TwoStageRetriever

        key = f"_ann_index_{cfg.cache_key()}"
        idx = getattr(self, key, None)
        if idx is None:
            idx = TwoStageRetriever.build(self.item_factors, cfg)
            setattr(self, key, idx)
        return idx

    def patch_ann_indexes(self, ixs, rows, appended=None) -> int:
        """pio-live delta apply: fold the touched/appended item rows
        into every CACHED quantized index in place (re-quantize only
        those rows, append new items to their nearest coarse cluster)
        — the quantized artifacts are part of the serve-time index
        exactly like the device tables, so a fold-in must patch them
        or ANN-served predictions would go stale while exact-served
        ones advance.  No rebuild: patch cost scales with the delta,
        not the catalog.  Returns the number of indexes patched."""
        n = 0
        for attr in list(vars(self)):
            if attr.startswith("_ann_index_"):
                getattr(self, attr).patch(ixs, rows, appended)
                n += 1
        return n

    def device_item_factors_normalized(self, dtype: Optional[str] = None):
        """Row-normalized table for cosine scoring — normalized once (in
        f32, then cast), not per request."""
        import jax.numpy as jnp

        key = f"_dev_item_factors_norm_{dtype or 'native'}"
        dev = getattr(self, key, None)
        if dev is None:
            table = self.device_item_factors()
            dev = table / (
                jnp.linalg.norm(table, axis=-1, keepdims=True) + 1e-9
            )
            if dtype:
                dev = dev.astype(jnp.dtype(dtype))
            setattr(self, key, dev)
        return dev


def filter_bias_mask(
    items,
    item_props: Optional[dict] = None,
    *,
    categories=None,
    whitelist=None,
    blacklist=(),
    exclude_ix=(),
    none_if_empty: bool = False,
):
    """Additive -inf bias over the item table for query-side filtering —
    the shared core of the filter-by-category / whitelist / blacklist
    template variants (plus query-item exclusion for similar-item
    queries).  ``none_if_empty=True`` returns None when no filter is
    active so callers can dispatch the cheaper unbiased scorer.
    """
    import numpy as np

    ex = tuple(exclude_ix)  # materialize ONCE: one-shot iterables
    has_filter = bool(categories or whitelist or blacklist or ex)
    if none_if_empty and not has_filter:
        return None
    n = len(items)
    allowed = np.ones(n, dtype=bool)
    if ex:
        allowed[list(ex)] = False
    if whitelist:
        allowed &= np.isin(items.ids.astype(str),
                           np.array(sorted(whitelist), dtype=str))
    if categories:
        cats = set(categories)
        has = np.zeros(n, dtype=bool)
        for item_id, props in (item_props or {}).items():
            ix = items.get(item_id)
            if ix >= 0 and cats & set(props.get("categories", [])):
                has[ix] = True
        allowed &= has
    if blacklist:
        allowed &= ~np.isin(items.ids.astype(str),
                            np.array(sorted(blacklist), dtype=str))
    return np.where(allowed, 0.0, -np.inf).astype(np.float32)


class RowFilter(NamedTuple):
    """One query's filters, as the templates read them off the query:
    the wire's lists of item ids, and `exclude_ix`, item indices the
    engine itself takes out (a similar-items query's own seeds; a
    shopper's seen items, as one int32 array of distinct indices)."""

    categories: Sequence[str] = ()
    whitelist: Sequence[str] = ()
    blacklist: Sequence[str] = ()
    exclude_ix: Sequence[int] = ()


class BatchFilter(NamedTuple):
    """A batch's filters in the form the scorer takes
    (``ops.topk.batch_topk_scores_t``): `kind` ``"none"``, ``"ids"``
    (`exclude`: ``[B, E]`` int32 item indices, -1 for none) or ``"mask"``
    (`mask`: ``[B, M]`` float32, additive)."""

    kind: str
    exclude: Optional[np.ndarray] = None
    mask: Optional[np.ndarray] = None

    @property
    def width(self) -> int:
        """The ids array's width (its rung of the ladder); 0 without."""
        return 0 if self.exclude is None else self.exclude.shape[1]

    def scorer_kwargs(self) -> dict:
        """The scorer's keyword arguments: `mask` as its callers have
        always passed it, `exclude` only where there are ids (a stand-in
        for the scorer written before it took ids keeps working)."""
        if self.exclude is None:
            return {"mask": self.mask}
        return {"mask": self.mask, "exclude": self.exclude}


def batch_filter(items, item_props: Optional[dict],
                 rows: Sequence[Optional[RowFilter]]) -> BatchFilter:
    """Filters as data.  Each row's excluded items (its `exclude_ix` and
    the `blacklist` ids the model knows, a hash lookup an id) go into one
    ``[B, E]`` array for the device, E the rung of
    ``ops.topk.EXCLUDE_LADDER`` that holds the batch's longest list; no
    array of the catalogue's length is built.  Only a batch that holds
    a row with `categories` or a `whitelist`, or more excluded ids than
    the ladder's last rung, takes the ``[B, M]`` mask
    (:func:`filter_bias_mask` a row).  A row that is None (a query that
    will not be answered) filters nothing."""
    from ..ops.topk import exclude_layout, exclude_width

    t0 = time.perf_counter()
    with annotate("pio.filter.build"):
        lists, by_ids = [], True
        for row in rows:
            if row is None:
                lists.append(())
                continue
            if row.categories or row.whitelist:
                by_ids = False
                break
            found = [ix for ix in map(items.get, row.blacklist or ())
                     if ix >= 0]
            if isinstance(row.exclude_ix, np.ndarray):
                # distinct already; an id the blackList repeats only
                # lengthens the list
                lists.append(np.concatenate([row.exclude_ix, found])
                             if found else row.exclude_ix)
            else:
                lists.append(tuple(dict.fromkeys(
                    [*row.exclude_ix, *found])))
        longest = max(map(len, lists), default=0)
        width = exclude_width(longest) if by_ids else 0
        if by_ids and not longest:
            out = BatchFilter("none")
        elif width:
            exclude = np.full((len(rows), width), -1, np.int32)
            for bi, ex in enumerate(lists):
                if len(ex):     # in the order this width's form reads
                    ex = exclude_layout(ex, width)
                    exclude[bi, :len(ex)] = ex
            out = BatchFilter("ids", exclude=exclude)
        else:
            mask = np.zeros((len(rows), len(items)), np.float32)
            for bi, row in enumerate(rows):
                if row is not None:
                    bias = filter_bias_mask(
                        items, item_props, categories=row.categories,
                        whitelist=row.whitelist,
                        blacklist=row.blacklist or (),
                        exclude_ix=row.exclude_ix, none_if_empty=True)
                    if bias is not None:
                        mask[bi] = bias
            out = BatchFilter("mask", mask=mask)
    FILTER_BUILD_SECONDS.observe(time.perf_counter() - t0)
    FILTER_ROWS.labels(filter=out.kind).inc(len(rows))
    if out.kind == "ids":
        FILTER_EXCLUDE_WIDTH.labels(width=str(width)).inc()
        FILTER_EXCLUDED_IDS.inc(int((out.exclude >= 0).sum()))
    return out


def pow2_ladder(max_batch: int) -> list[int]:
    """Every batch size the micro-batcher's pow2 padding can dispatch
    for a given ``max_batch`` — including the pow2 CEILING of a
    non-pow2 max_batch (a 33..48-item batch under max_batch=48 pads to
    64, so 64 is dispatchable).  Delegates to the batcher's own
    ``dispatchable_sizes`` so the warmup ladder is derived from the
    padding scheme, not a parallel re-implementation of it."""
    from ..server.microbatch import dispatchable_sizes

    return dispatchable_sizes(max_batch)


def warm_shapes(max_batch: int, n: int, lone_nums=()) -> list:
    """The ``(B, k)`` shapes a warm-up compiles: EVERY B in
    ``pow2_ladder(max_batch)`` at the pow2-rounded default num — every
    rung, not a subset: a size the padding can produce but the warmup
    skipped compiles on first exposure mid-traffic, which is exactly
    the p99 spike the padding exists to avoid (ADVICE r4).  A lone
    request is the one-row rung; ``max_batch <= 0`` (no batcher) leaves
    that rung alone: what an engine whose ``predict`` is a one-row
    ``batch_predict`` still dispatches.  `lone_nums` adds the one-row
    rung at each of these nums' pow2 k (a lone "three similar items"
    under a product page).  On the chip every rung is an executable to
    load, 0.18-0.31 s of each server's start over a 9.39 M-item table
    (PERF.md, PR 31), so the caller names what its traffic asks for; a
    k no rung holds compiles once and lands in the persistent
    compilation cache."""
    from ..ops.topk import pow2_ceil

    k_default = min(pow2_ceil(10), n)
    lone_ks = {min(pow2_ceil(num), n) for num in lone_nums} - {k_default}
    return ([(b, k_default) for b in pow2_ladder(max_batch) or [1]]
            + [(1, k) for k in sorted(lone_ks)])


def warm_batched_topk(table, rank: int, n: int,
                      unmasked_too: bool = False,
                      max_batch: int = 64,
                      table_t=None, lone_nums=(),
                      exclude_widths=None) -> None:
    """Pre-compile the batched top-k scorer at the shapes serving
    dispatches (:func:`warm_shapes`, which reads `max_batch` and
    `lone_nums`: server/microbatch.py pads batches to powers of two;
    templates round k to pow2).

    With `table_t` (what the caller's batch path hands
    ``ops.topk.batch_topk_scores_t``: its ``device_item_tables``) the
    filtered rungs carry excluded ids, at each of `exclude_widths`: the
    rungs of ``ops.topk.EXCLUDE_LADDER`` that the engine's queries can
    take, the first alone unless it names more (a blackList; an engine
    that excludes a user's whole history names them all); each rung
    compiles the path, blocked or dense, that its shapes will take under
    traffic.  The ``[B, M]``
    masked form of that scorer (`categories`, a `whiteList`) is not
    warmed: its rungs each shipped a ``[B, M]`` array of zeros, 2.4 GB
    at 64 rows over 9.4 M items, and set the server's peak memory.  Without
    `table_t` it is the classic ``[M, R]`` scorer under a ``[B, M]``
    mask, for the template whose every batch is still masked and whose
    ``predict`` is a scorer of its own (itemsimilarity): with
    no batcher nothing dispatches it, and nothing is compiled."""
    from ..ops.topk import (
        EXCLUDE_LADDER, batch_topk_scores, batch_topk_scores_t,
    )

    if table_t is None and max_batch <= 0:
        return
    if exclude_widths is None:
        exclude_widths = EXCLUDE_LADDER[:1]
    for b, k in warm_shapes(max_batch, n, lone_nums):
        vecs = np.zeros((b, rank), np.float32)
        if table_t is None:
            batch_topk_scores(vecs, table, k,
                              mask=np.zeros((b, n), np.float32))
            continue
        # the keyword arguments as `batch_predict` passes them: they are
        # part of the compiled call's key
        filters = [BatchFilter("none")] if unmasked_too else []
        filters += [BatchFilter("ids", np.full((b, width), -1, np.int32))
                    for width in exclude_widths]
        for flt in filters:
            batch_topk_scores_t(vecs, table_t, k, **flt.scorer_kwargs())
