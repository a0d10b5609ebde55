"""Shared template helpers."""

from __future__ import annotations

import logging
import time
from typing import NamedTuple, Optional, Sequence

import numpy as np

from ..obs import get_registry, log_buckets, tower
from ..obs.timeline import annotate

__all__ = ["BatchFilter", "CategoryIndex", "DeviceTableMixin", "RowFilter",
           "batch_filter", "filter_bias_mask", "normalize_rows",
           "pow2_ladder", "props_without_categories", "warm_batched_topk",
           "warm_shapes"]

logger = logging.getLogger(__name__)

_registry = get_registry()
FILTER_ROWS = _registry.counter(
    "pio_filter_rows_total",
    "Rows of the batches the templates' batch_predict dispatched, by the "
    "form their batch's filters took: none, ids (excluded item ids "
    "applied on the device), cats (category numbers tested on the device "
    "against the model's resident category index, with up to 32 excluded "
    "ids) or mask (a [B, M] additive mask built on the host: a "
    "whiteList, categories of a model whose index is not on the device "
    "or beside a list of more than 32 ids or more than 4 names, "
    "or a list past the ids' width)",
    labels=("filter",),
)
FILTER_CATEGORY_IDS = _registry.counter(
    "pio_filter_category_ids_total",
    "Category numbers dispatched to the device (the numbers arrays' real "
    "entries, a name the model does not know among them, their -1 "
    "padding left out)",
).child()
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


class CategoryIndex:
    """The items' ``categories`` property as arrays, category-major: a
    vocabulary of names (a category's number is its place in `names`) and
    each category's distinct item indices, ascending
    (``members[offsets[c]:offsets[c + 1]]``).  The train's snapshot of the
    items' properties, 4 bytes a membership on the host; the ONE owner of
    what serving reads of the items' ``categories``, empty where no item
    has any.  It holds no length of the catalogue: an item a live fold-in
    appends is in no category, and the callers say how many items there
    are now.  On the device it is one bit row a category
    (:meth:`DeviceTableMixin.device_category_rows`)."""

    def __init__(self, names=(), offsets=(0,), members=()):
        self.names = np.asarray(names, dtype=str)
        self.offsets = np.asarray(offsets, np.int64)
        self.members = np.asarray(members, np.int32)
        # cleared by `DeviceTableMixin.device_category_rows` where the
        # device has no room for the bit rows: `batch_filter` then sends
        # `categories` to the mask, which reads the lists here
        self.resident = True
        self._number = {name: j for j, name in enumerate(self.names.tolist())}

    @classmethod
    def from_memberships(cls, names, category, item) -> "CategoryIndex":
        """From parallel arrays: membership j says item `item[j]` carries
        category number `category[j]` (a pair given twice counts once)."""
        item = np.asarray(item, np.int64)
        span = int(item.max()) + 1 if len(item) else 1
        key = np.unique(np.asarray(category, np.int64) * span + item)
        offsets = np.searchsorted(key // span, np.arange(len(names) + 1))
        return cls(names, offsets, key % span)

    @classmethod
    def from_props(cls, items, item_props) -> "CategoryIndex":
        """From the items' property dicts, one pass; empty where no item
        the model knows carries a category."""
        number: dict = {}
        category, item = [], []
        for item_id, props in (item_props or {}).items():
            ix = items.get(item_id)
            if ix < 0:
                continue
            for name in props.get("categories") or ():
                category.append(number.setdefault(str(name), len(number)))
                item.append(ix)
        return cls.from_memberships(list(number), category, item)

    def __len__(self) -> int:
        return len(self.names)

    @property
    def memberships(self) -> int:
        return len(self.members)

    @property
    def nbytes(self) -> int:
        return self.names.nbytes + self.offsets.nbytes + self.members.nbytes

    def number(self, name: str) -> int:
        """A category's number; ``len(self)``, the number of no item, for
        a name the model does not know."""
        return self._number.get(name, len(self))

    def allowed(self, names, n_items: int) -> np.ndarray:
        """``[n_items]`` bool: the items that carry one of `names`, of a
        catalogue that holds `n_items` now."""
        out = np.zeros(n_items, bool)
        for c in {self.number(name) for name in names} - {len(self)}:
            out[self.members[self.offsets[c]:self.offsets[c + 1]]] = True
        return out

    def arrays(self) -> dict:
        """What persists the index beside a model's tables (``np.savez``)."""
        return {"category_names": self.names,
                "category_offsets": self.offsets,
                "category_members": self.members}

    @classmethod
    def from_arrays(cls, data) -> Optional["CategoryIndex"]:
        """The index `arrays` wrote; None for a file from before it."""
        if "category_names" not in data:
            return None
        return cls(data["category_names"], data["category_offsets"],
                   data["category_members"])


def props_without_categories(item_props) -> dict:
    """The items' property dicts with ``categories`` taken out, and no
    entry for an item that has nothing else: what a model keeps beside
    its :class:`CategoryIndex`, which owns the categories."""
    return {item_id: rest for item_id, fields in (item_props or {}).items()
            if (rest := {k: v for k, v in fields.items()
                         if k != "categories"})}


_CATEGORY_ROWS_PIECE = 256 << 20   # bytes of bit rows built and sent at once
# The share of the device's memory that the bit rows leave free beside
# what is resident when they are built (the item table): 2.1 GB of a
# v5e's 16.9, for one piece in flight (0.27 GB) and the temporaries of the
# widest program a server warms (0.3 GB a 64-row category batch over
# 9.35 M items, 1.1 GB the 4,224-id rung; PERF.md, PR 40 and 42).
_CATEGORY_ROWS_SPARE = 1 / 8
_NO_CATEGORIES = CategoryIndex()


def _device_free_bytes() -> Optional[tuple]:
    """``(free, limit)`` bytes of the first local device's memory; None
    where the backend keeps no such count (the CPU)."""
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return (stats["bytes_limit"] - stats.get("bytes_in_use", 0),
            stats["bytes_limit"])


def _category_rows_on_device(index: CategoryIndex, n_items: int):
    """`DeviceTableMixin.device_category_rows`' array: `index`'s bit rows
    over `n_items` items, built from its lists a piece at a time on the
    host and written into place on the device."""
    import jax
    import jax.numpy as jnp

    from ..ops.topk import allow_words, category_bit_rows

    n, width = len(index), allow_words(n_items)
    step = min(n + 2, max(2, _CATEGORY_ROWS_PIECE // (4 * width)))
    put = jax.jit(
        lambda rows, piece, lo: jax.lax.dynamic_update_slice_in_dim(
            rows, piece, lo, 0), donate_argnums=0)
    tiled = (width // 1024, 8, 128)      # a row by (8, 128) tiles
    dev = jnp.zeros((n + 2, *tiled), jnp.uint32)
    for lo in range(0, n + 2, step):
        lo = min(lo, n + 2 - step)     # pieces of one shape
        piece = np.zeros((step, width), np.uint32)
        named = min(lo + step, n)
        if named > lo:
            piece[:named - lo] = category_bit_rows(
                index.offsets, index.members, n_items, lo, named)
        if lo + step == n + 2:
            # every item, and the words' padding past the catalogue: the
            # scan drops what lies past the table whatever its bit
            piece[-1] = ~np.uint32(0)
        # a piece at a time ON the device too: uploads queued ahead of
        # their writes held up to 2 GB more than the rows (v5e)
        dev = jax.block_until_ready(
            put(dev, piece.reshape(step, *tiled), lo))
    return dev


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

        from ..ops.topk import grow_category_rows, patch_packed_rows

        if app_np is not None and \
                vars(self).get("_dev_category_rows") is not None:
            # appended items are in no category: the resident rows follow
            # the table's length (zero bits; the row of every item grows
            # by set ones), BEFORE the tables grow: a concurrent scorer
            # may meet rows wider than its table, never narrower
            self._dev_category_rows = grow_category_rows(
                self._dev_category_rows, len(self.item_factors))
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

    # the train's snapshot of the items' `categories`; a model class
    # declares it as a field, and `categories` fills it where it is None
    category_index = None

    def categories(self) -> CategoryIndex:
        """The model's :class:`CategoryIndex`.  A model that came without
        one (built by hand; a file from before the index) gets it here,
        once, from its items' property dicts: empty where they name no
        category."""
        if self.category_index is None:
            self.category_index = CategoryIndex.from_props(
                self.items, getattr(self, "item_props", None))
        return self.category_index

    def serving_categories(self) -> CategoryIndex:
        """:meth:`categories` as :func:`batch_filter` takes it in a turn:
        its bit rows on the device, or found not to fit and the index
        marked so (`resident`)."""
        self.device_category_rows()
        return self.categories()

    def device_category_rows(self):
        """The model's category index resident on the device beside the
        item table: ``[n + 2, allow_words(M) / 1024, 8, 128]`` uint32, one
        bit an item a category in the layout the blocked scan tests
        (``ops.topk.category_bit_rows``), then a row of no item and a row
        of every item (``ops.topk.Allowed``); M / 8 bytes a category, M
        the item table's length (:meth:`patch_device_item_rows` keeps it
        so).  Built once a model (re)load.  None where the index is
        empty, or where the rows would not leave `_CATEGORY_ROWS_SPARE`
        of the device's memory free beside what it holds: the index then
        stays on the host, marked not `resident`, and `categories` take
        :func:`batch_filter`'s counted mask, which reads its lists."""
        from ..ops.topk import allow_words

        if "_dev_category_rows" in vars(self):
            return self._dev_category_rows
        index = self.categories()
        t0 = time.perf_counter()
        n, n_items = len(index), len(self.item_factors)
        facts = {"categories": n, "memberships": index.memberships,
                 "categoryIndexBytes": 4 * (n + 2) * allow_words(n_items)}
        dev, room = None, _device_free_bytes() if n else None
        if room is not None and facts["categoryIndexBytes"] \
                > room[0] - _CATEGORY_ROWS_SPARE * room[1]:
            logger.warning(
                "category index kept on the host: its bit rows (%d bytes) "
                "do not fit the device (%d bytes free of %d); `categories` "
                "take the [B, M] mask", facts["categoryIndexBytes"], *room)
            facts["categoryIndexBytes"] = 0
        elif n:
            dev = _category_rows_on_device(index, n_items)
        self._dev_category_rows, index.resident = dev, dev is not None
        if n:
            tower.note_event("category_index", **facts)
            logger.info("category index %s in %.1fs: %s",
                        "on the host" if dev is None else "resident",
                        time.perf_counter() - t0, facts)
        return dev

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
    index=None,
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
    queries).  `index` is the model's :class:`CategoryIndex`, whose lists
    name a category's items; without one no item carries a category.
    ``none_if_empty=True`` returns None
    when no filter is active so callers can dispatch the cheaper
    unbiased scorer.
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
        allowed &= (index or _NO_CATEGORIES).allowed(categories, n)
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
    (`exclude`: ``[B, E]`` int32 item indices, -1 for none), ``"cats"``
    (`categories`: ``[B, CATEGORY_SLOTS]`` int32 category numbers, a row's
    named slots first, -1 for none, beside an `exclude` of the first rung) or
    ``"mask"`` (`mask`: ``[B, M]`` float32, additive)."""

    kind: str
    exclude: Optional[np.ndarray] = None
    mask: Optional[np.ndarray] = None
    categories: Optional[np.ndarray] = None

    @property
    def width(self) -> int:
        """The ids array's width (its rung of the ladder); 0 without."""
        return 0 if self.exclude is None else self.exclude.shape[1]

    @property
    def category_rows(self) -> int:
        """Rows of the batch that name a category; 0 without."""
        return 0 if self.categories is None \
            else int((self.categories[:, 0] >= 0).sum())

    def scorer_kwargs(self, model=None) -> dict:
        """The scorer's keyword arguments: `mask` as its callers have
        always passed it, `exclude` only where there are ids (a stand-in
        for the scorer written before it took ids keeps working),
        `allow` only where there are categories: their numbers beside
        `model`'s resident bit rows."""
        from ..ops.topk import Allowed

        if self.exclude is None:
            return {"mask": self.mask}
        if self.categories is None:
            return {"mask": self.mask, "exclude": self.exclude}
        return {"mask": self.mask, "exclude": self.exclude,
                "allow": Allowed(self.categories,
                                 model.device_category_rows())}


def batch_filter(items, index,
                 rows: Sequence[Optional[RowFilter]]) -> BatchFilter:
    """Filters as data.  Each row's excluded items (its `exclude_ix` and
    the `blacklist` ids the model knows, a hash lookup an id) go into one
    ``[B, E]`` array for the device, E the rung of
    ``ops.topk.EXCLUDE_LADDER`` that holds the batch's longest list; no
    array of the catalogue's length is built.  `index` is the model's
    :class:`CategoryIndex` (none, or an empty one: no item carries a
    category).  Where its bit rows are `resident` on the device
    (:meth:`DeviceTableMixin.serving_categories`), a row's `categories`
    go as category NUMBERS (a hash lookup a name) into one
    ``[B, CATEGORY_SLOTS]`` array beside the ids, for the device to test
    against them.  The ``[B, M]`` mask (:func:`filter_bias_mask` a row,
    which reads the index's lists) is left for a batch that holds a row
    with a `whitelist`; `categories` without resident rows, beside more
    excluded ids than the ladder's first rung, or more of them than
    ``ops.topk.CATEGORY_SLOTS``; or more excluded ids than the ladder's
    last rung.  A row that is None (a query that will not be answered)
    filters nothing."""
    from ..ops.topk import CATEGORY_SLOTS, exclude_layout, exclude_width

    index = index or _NO_CATEGORIES
    resident = index.resident and len(index) > 0
    t0 = time.perf_counter()
    with annotate("pio.filter.build"):
        lists, by_ids, named = [], True, 0
        for row in rows:
            if row is None:
                lists.append(())
                continue
            if row.whitelist or (row.categories and not resident):
                by_ids = False
                break
            named = max(named, len(row.categories or ()))
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
        if not by_ids:
            width = 0
        elif named:
            # categories ride with the pairwise ids alone: the first rung
            width = exclude_width(1)
            width = width if named <= CATEGORY_SLOTS and longest <= width \
                else 0
        else:
            width = exclude_width(longest)
        if by_ids and not longest and not named:
            out = BatchFilter("none")
        elif width:
            exclude = np.full((len(rows), width), -1, np.int32)
            for bi, ex in enumerate(lists):
                if len(ex):     # in the order this width's form reads
                    ex = exclude_layout(ex, width)
                    exclude[bi, :len(ex)] = ex
            numbers = None
            if named:
                numbers = np.full((len(rows), CATEGORY_SLOTS), -1, np.int32)
                for bi, row in enumerate(rows):
                    if row is not None and row.categories:
                        mine = list(dict.fromkeys(
                            map(index.number, row.categories)))
                        numbers[bi, :len(mine)] = mine
            out = BatchFilter("cats" if named else "ids", exclude=exclude,
                              categories=numbers)
        else:
            mask = np.zeros((len(rows), len(items)), np.float32)
            for bi, row in enumerate(rows):
                if row is not None:
                    bias = filter_bias_mask(
                        items, index, categories=row.categories,
                        whitelist=row.whitelist,
                        blacklist=row.blacklist or (),
                        exclude_ix=row.exclude_ix, none_if_empty=True)
                    if bias is not None:
                        mask[bi] = bias
            out = BatchFilter("mask", mask=mask)
    FILTER_BUILD_SECONDS.observe(time.perf_counter() - t0)
    FILTER_ROWS.labels(filter=out.kind).inc(len(rows))
    if out.exclude is not None:
        FILTER_EXCLUDE_WIDTH.labels(width=str(width)).inc()
        FILTER_EXCLUDED_IDS.inc(int((out.exclude >= 0).sum()))
    if out.categories is not None:
        FILTER_CATEGORY_IDS.inc(int((out.categories >= 0).sum()))
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
                      exclude_widths=None, category_model=None) -> None:
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
    traffic.  Where `category_model` (the engine's model) keeps a
    category index resident on the device, each shape is also warmed with
    category numbers against its rows, so a server's first `categories`
    query compiles nothing.  The ``[B, M]`` masked form of that scorer (a
    `whiteList`) is not warmed: its rungs each shipped a ``[B, M]`` array of zeros, 2.4 GB
    at 64 rows over 9.4 M items, and set the server's peak memory.  Without
    `table_t` it is the classic ``[M, R]`` scorer under a ``[B, M]``
    mask, for the template whose every batch is still masked and whose
    ``predict`` is a scorer of its own (itemsimilarity): with
    no batcher nothing dispatches it, and nothing is compiled."""
    from ..ops.topk import (
        CATEGORY_SLOTS, EXCLUDE_LADDER, batch_topk_scores,
        batch_topk_scores_t,
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
        if category_model is not None and \
                category_model.device_category_rows() is not None:
            filters.append(BatchFilter(
                "cats", np.full((b, EXCLUDE_LADDER[0]), -1, np.int32),
                categories=np.full((b, CATEGORY_SLOTS), -1, np.int32)))
        for flt in filters:
            batch_topk_scores_t(vecs, table_t, k,
                                **flt.scorer_kwargs(category_model))
