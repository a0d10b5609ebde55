"""Shared template helpers."""

from __future__ import annotations

from typing import Optional

import numpy as np

__all__ = ["DeviceTableMixin", "filter_bias_mask", "normalize_rows",
           "pow2_ladder", "warm_batched_topk"]


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
        path, and gets the transposed table alone: no third copy."""
        from ..ops.topk import ItemTables, pack_rows, rows_per_line

        table_t = self.device_item_factors_t(dtype)
        if not rows_per_line(table_t.shape[0]):
            return table_t
        key = f"_dev_item_packed_{dtype or 'native'}"
        packed = getattr(self, key, None)
        if packed is None:
            packed = pack_rows(self.device_item_factors(dtype))
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


def pow2_ladder(max_batch: int) -> list[int]:
    """Every batch size the micro-batcher's pow2 padding can dispatch
    for a given ``max_batch`` — including the pow2 CEILING of a
    non-pow2 max_batch (a 33..48-item batch under max_batch=48 pads to
    64, so 64 is dispatchable).  Delegates to the batcher's own
    ``dispatchable_sizes`` so the warmup ladder is derived from the
    padding scheme, not a parallel re-implementation of it."""
    from ..server.microbatch import dispatchable_sizes

    return dispatchable_sizes(max_batch)


def warm_batched_topk(table, rank: int, n: int,
                      unmasked_too: bool = False,
                      max_batch: int = 64,
                      table_t=None) -> None:
    """Pre-compile the pow2 batched top-k shapes the serving
    micro-batcher dispatches (server/microbatch.py pads batches to
    powers of two; templates round k to pow2): EVERY B in
    ``pow2_ladder(max_batch)`` at the pow2-rounded default num, plus
    the small-k shapes at B=1.  Every pow2 rung, not a subset — a size
    the padding can produce but the warmup skipped compiles on first
    exposure mid-traffic, which is exactly the p99 spike the padding
    exists to avoid (ADVICE r4).  ``max_batch <= 0`` (no batcher: the
    per-query predict path serves everything) skips the batched warms
    entirely — they would compile executables nothing dispatches."""
    from ..ops.topk import batch_topk_scores, batch_topk_scores_t, pow2_ceil

    ladder = pow2_ladder(max_batch)
    if not ladder:
        return

    def warm(vecs, k, mask=None):
        # warm the scorer the caller's batch path actually dispatches:
        # the transposed [R, M] one when a transposed table is given
        # (recommendation: its `device_item_tables`, so every rung
        # compiles the path, blocked or dense, that its shapes will
        # take under traffic), the classic [M, R] one otherwise
        if table_t is not None:
            batch_topk_scores_t(vecs, table_t, k, mask=mask)
        else:
            batch_topk_scores(vecs, table, k, mask=mask)

    k_default = min(pow2_ceil(10), n)
    for b in ladder:
        vecs = np.zeros((b, rank), np.float32)
        warm(vecs, k_default, mask=np.zeros((b, n), np.float32))
        if unmasked_too:
            warm(vecs, k_default)
    for k in {min(pow2_ceil(k), n) for k in (1, 4)}:
        vecs = np.zeros((1, rank), np.float32)
        warm(vecs, k, mask=np.zeros((1, n), np.float32))
        if unmasked_too:
            warm(vecs, k)
