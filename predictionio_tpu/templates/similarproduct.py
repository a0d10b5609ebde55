"""Similar-product engine template.

Capability parity with `/root/reference/examples/scala-parallel-
similarproduct/` (incl. the ``multi`` variant's persistent ``ALSModel``):
implicit-feedback ALS over view events, then item-item cosine ranking —
query items' factor vectors averaged, scored against the item-factor table
with one fused cosine matmul + top-k.

The custom model persistence demonstrates the `PersistentModel` contract
(reference `multi/src/main/scala/ALSAlgorithm.scala:25-66` saves factor
RDDs with ``saveAsObjectFile``; here: one ``.npz``).

The documented query, ``{"items": [...], "num": 4, "categories": ["c4",
"c3"], "whiteList": [...], "blackList": [...]}``, at catalogue scale:
``train`` builds a category index from the items' ``categories`` property
(``_common.CategoryIndex``: names -> numbers and each category's items,
saved with the model as arrays), ``deploy`` keeps it on the device as one
bit row a category, and a query's ``categories`` travel in the turn as
numbers and are tested inside the blocked top-k, beside its seeds and
blackList as item ids: exact over the allowed items, no array of the
catalogue's length a query (9.35 M items x 4,096 categories: 4.8 GB
resident, 75 MB of bits for 64 rows; ``PERF.md``,
``simcat-amazon14-r128``).  A ``whiteList`` still takes the ``[B, M]``
mask.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import numpy as np

from ..controller import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
    ModelPlacement,
    Params,
    WorkflowContext,
)
from ..models.als import ALSConfig, train_als
from ..obs.timeline import annotate
from ..ops.topk import batch_topk_scores_t, pow2_ceil, topk_path

from ._common import CategoryIndex, DeviceTableMixin, RowFilter, \
    batch_filter, normalize_rows, props_without_categories, \
    warm_batched_topk
from .recommendation import (
    PredictedResult,
    _resolve_app_id,
    decode_batch_item_scores,
)


@dataclass(frozen=True)
class Query:
    items: tuple[str, ...]
    num: int = 10
    categories: Optional[tuple[str, ...]] = None
    whitelist: Optional[tuple[str, ...]] = None
    blacklist: Optional[tuple[str, ...]] = None

    @staticmethod
    def from_json(d: dict) -> "Query":
        return Query(
            items=tuple(d["items"]),
            num=int(d.get("num", 10)),
            categories=tuple(d["categories"]) if d.get("categories") else None,
            whitelist=tuple(d.get("whiteList") or d.get("whitelist") or ())
            or None,
            blacklist=tuple(d.get("blackList") or d.get("blacklist") or ())
            or None,
        )


@dataclass(frozen=True)
class SimilarDataSourceParams(Params):
    app_name: str = ""
    app_id: int = -1
    view_events: tuple[str, ...] = ("view",)
    # ranking eval (pio-lens satellite; ROADMAP 4(b)): hold out a
    # seeded evalHoldout fraction of each user's co-viewed items, query
    # with one kept item, score MAP@evalNum against the held-out set
    eval_holdout: float = 0.0
    eval_num: int = 10
    eval_seed: int = 7

    def __post_init__(self) -> None:
        if not 0.0 <= self.eval_holdout < 1.0:
            raise ValueError(
                f"evalHoldout must be in [0, 1), got {self.eval_holdout}"
            )


@dataclass
class SimilarTrainingData:
    ratings: Any  # implicit view-count Ratings
    items: dict[str, dict]

    def sanity_check(self) -> None:
        if len(self.ratings) == 0:
            raise ValueError("no view events found")


class SimilarProductDataSource(DataSource):
    params_class = SimilarDataSourceParams

    def read_training(self, ctx: WorkflowContext) -> SimilarTrainingData:
        p = self.params
        app_id = _resolve_app_id(ctx, p)
        es = ctx.storage.get_event_store()
        if hasattr(es, "find_ratings"):
            # fused native implicit read: one C pass counting view
            # events per (user, item) pair (native/sqlite_scan.cpp)
            ratings = es.find_ratings(
                app_id=app_id, event_names=p.view_events,
                rating_property=None, dedup="sum", entity_type="user",
            )
        else:
            frame = es.find_columnar(
                app_id=app_id, entity_type="user",
                event_names=list(p.view_events),
                minimal=True,   # only to_ratings fields are consumed
            )
            ratings = frame.to_ratings(dedup="sum")  # implicit counts
        items = {
            k: dict(v.fields)
            for k, v in es.aggregate_properties_of(
                app_id=app_id, entity_type="item"
            ).items()
        }
        return SimilarTrainingData(ratings=ratings, items=items)

    def read_eval(self, ctx: WorkflowContext):
        """Leave-some-out co-view split: per user with >= 2 distinct
        items, a seeded ``evalHoldout`` fraction of their (user, item)
        pairs is held out of training; the query anchors on one KEPT
        item and the held-out items are the relevant set MAP@k scores
        against.  Shared by the similarproduct and itemsimilarity
        engines (same DataSource)."""
        p: SimilarDataSourceParams = self.params
        if p.eval_holdout <= 0:
            return []
        from ..controller.metrics import ActualItems
        from ..storage.columnar import Ratings

        data = self.read_training(ctx)
        ratings = data.ratings
        rng = np.random.default_rng(p.eval_seed)
        hold_mask = np.zeros(len(ratings), bool)
        by_user: dict[int, list[int]] = {}
        for pos, u in enumerate(ratings.user_ix):
            by_user.setdefault(int(u), []).append(pos)
        qa = []
        for _u, positions in sorted(by_user.items()):
            if len(positions) < 2:
                continue
            k_hold = min(
                max(int(round(len(positions) * p.eval_holdout)), 1),
                len(positions) - 1,
            )
            perm = rng.permutation(len(positions))
            held = [positions[i] for i in perm[:k_hold]]
            kept = [positions[i] for i in perm[k_hold:]]
            hold_mask[held] = True
            anchor = str(ratings.items.id_of(
                int(ratings.item_ix[kept[0]])
            ))
            actual = tuple(sorted(
                str(ratings.items.id_of(int(ratings.item_ix[h])))
                for h in held
            ))
            qa.append((
                Query(items=(anchor,), num=p.eval_num),
                ActualItems(items=actual),
            ))
        if not qa:
            return []
        keep = ~hold_mask
        train = Ratings(
            user_ix=ratings.user_ix[keep],
            item_ix=ratings.item_ix[keep],
            rating=ratings.rating[keep],
            users=ratings.users,
            items=ratings.items,
        )
        td = SimilarTrainingData(ratings=train, items=data.items)
        return [(td, {"holdout": p.eval_holdout, "users": len(qa)}, qa)]


@dataclass(frozen=True)
class SimilarALSParams(Params):
    __param_aliases__ = {"lambda": "lam"}
    # records written before gather_dtype went hold its float32 default
    __retired_params__ = {"gather_dtype": "float32"}
    # a model trained with the fused kernel, which went, retrains and
    # folds in on the default route
    __retired_values__ = {"solver": {"fused": "auto"}}

    rank: int = 10
    num_iterations: int = 20
    lam: float = 0.01
    alpha: float = 1.0
    seed: int = 3
    # scaling knobs (models/als.py): solver "auto" takes the
    # ops/solve.py kernel on a TPU and lax.linalg elsewhere; kernels
    # fail the train if they do not compile;
    # "sharded" placement shards factor tables AND the rating COO
    # over the mesh
    solver: str = "auto"
    solver_mode: str = "full"    # "subspace" = iALS++ block sweep
    subspace_size: int = 16
    factor_placement: str = "replicated"
    gather_mode: str = "row"


@dataclass
class SimilarALSModel(DeviceTableMixin):
    """``item_factors`` is row-NORMALIZED at train time (the
    normalized-table path itemsimilarity proved out, migrated here per
    ROADMAP 2(d)): inner product over the stored table IS cosine, so
    scoring needs no per-query table normalization and the table is
    directly servable by the two-stage int8/IVF retriever.  Legacy
    ``.npz`` models saved by the pre-migration template (raw factors)
    are normalized once at load.

    ``category_index`` is the train's snapshot of the items'
    ``categories`` property (`_common.CategoryIndex`): what a query's
    `categories` are looked up in and, as bit rows resident on the device
    beside the table, tested against inside the blocked top-k.  The
    categories live THERE ONLY: ``item_props`` holds the items' other
    properties (a custom Serving may read them; the algorithm does not)
    and no entry for an item that has none, on a trained model and on a
    deployed one alike.  A model built by hand without an index gets one
    from its ``item_props`` at first use (``categories()``)."""

    item_factors: np.ndarray
    items: Any  # StringIndex
    item_props: dict[str, dict]
    category_index: Optional[CategoryIndex] = None


class SimilarProductAlgorithm(Algorithm):
    """Implicit ALS -> item-item cosine
    (reference `similarproduct/multi/.../ALSAlgorithm.scala:70-200`)."""

    params_class = SimilarALSParams
    placement = ModelPlacement.DEVICE_SHARDED

    def _config(self) -> ALSConfig:
        p: SimilarALSParams = self.params
        return ALSConfig(
            rank=p.rank, num_iterations=p.num_iterations, lam=p.lam,
            implicit=True, alpha=p.alpha, seed=p.seed,
            solver=p.solver, factor_placement=p.factor_placement,
            solver_mode=p.solver_mode,
            subspace_size=p.subspace_size,
            gather_mode=p.gather_mode,
        )

    def train(self, ctx: WorkflowContext, data: SimilarTrainingData):
        factors = train_als(data.ratings, cfg=self._config(), mesh=ctx.mesh)
        return SimilarALSModel(
            item_factors=normalize_rows(factors.item_factors),
            items=data.ratings.items,
            item_props=props_without_categories(data.items),
            category_index=CategoryIndex.from_props(
                data.ratings.items, data.items),
        )

    # -- custom persistence (PersistentModel demo) -------------------------
    def save_model(self, ctx, model_id, model: SimilarALSModel, base_dir):
        base_dir.mkdir(parents=True, exist_ok=True)
        path = base_dir / f"{model_id}-similar.npz"
        np.savez_compressed(
            path,
            item_factors=model.item_factors,
            item_ids=model.items.ids.astype(str),
            # normalized-table marker: load_model normalizes legacy
            # files (saved raw by the pre-migration template) exactly
            # once, and leaves stamped files alone
            normalized=np.array(True),
            # the categories as arrays (4 bytes a membership), not as one
            # dict an item
            **model.categories().arrays(),
        )
        import json as _json

        # what the index holds is not written twice: the JSON keeps the
        # items' OTHER properties, and no entry for an item without any
        props_path = base_dir / f"{model_id}-props.json"
        props_path.write_text(_json.dumps(
            props_without_categories(model.item_props)))
        return {"npz": path.name, "props": props_path.name}

    def load_model(self, ctx, model_id, manifest, base_dir):
        import json as _json

        from ..storage.bimap import StringIndex

        data = np.load(base_dir / manifest["npz"], allow_pickle=False)
        props = _json.loads((base_dir / manifest["props"]).read_text())
        factors = data["item_factors"]
        if "normalized" not in data.files or not bool(data["normalized"]):
            factors = normalize_rows(factors)
        items = StringIndex(list(data["item_ids"]))
        index = CategoryIndex.from_arrays(data)
        if index is None:
            # a file from before the index: the categories are in the JSON
            index = CategoryIndex.from_props(items, props)
        return SimilarALSModel(
            item_factors=factors, items=items,
            item_props=props_without_categories(props),
            category_index=index,
        )

    # -- serving -----------------------------------------------------------
    def warmup(self, model: SimilarALSModel, max_batch: int = 64) -> None:
        """Pre-compile the cosine top-k scorer for the pow2 batched
        shapes the serving micro-batcher dispatches and the small-k
        one-row shapes of a lone request, each with excluded ids (every
        query excludes its own seeds) and, where the model holds a
        category index, with category numbers beside them.  The table is
        train-time normalized, so the plain device tables serve cosine
        directly."""
        n = len(model.items)
        if n == 0:
            return
        warm_batched_topk(
            None, model.item_factors.shape[1], n, max_batch=max_batch,
            table_t=model.device_item_tables(), lone_nums=(1, 4),
            category_model=model,
        )

    @staticmethod
    def _query_vecs(model: SimilarALSModel, known: list) -> np.ndarray:
        """``[B, R]``: per query the mean of its known seed items' rows
        (already unit-norm — the mean of normalized rows is
        itemsimilarity's query semantics, which this template shares)
        re-normalized; a zero row for a query with no known seed.  ONE
        gather from the host table for the whole batch."""
        flat = np.fromiter((ix for ixs in known for ix in ixs), np.int64)
        rows = np.asarray(model.item_factors[flat], np.float32)
        qvecs = np.zeros((len(known), rows.shape[1]), np.float32)
        lo = 0
        for bi, ixs in enumerate(known):
            if ixs:
                qvec = rows[lo:lo + len(ixs)].mean(axis=0)
                qvecs[bi] = qvec / (np.linalg.norm(qvec) + 1e-9)
                lo += len(ixs)
        return qvecs

    def predict(self, model: SimilarALSModel, query: Query) -> PredictedResult:
        """A lone request is a one-row batch: the same device program,
        the same filters as data."""
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: SimilarALSModel, queries):
        """Eval + micro-batched serving path: one batched cosine scoring
        for the whole query set.  Same shape-stability contract as the
        recommendation template: the device batch stays len(queries)
        (unanswerable queries score a zero vector, discarded on host)
        and k rounds up to pow2, bounding the XLA executable key space.

        A query's own seed items and its `blackList` travel to the device
        as item ids, its `categories` as category numbers looked up in
        the model's index (``_common.batch_filter``): no array of the
        catalogue's length is built for them, on the host or on the
        device, at any catalogue size (a 9.35 M-item catalogue with 4,096
        categories: 75 MB of allowed bits for 64 rows, formed on the
        device from the resident index).  Only a `whiteList` still makes
        the batch's ``[B, M]`` mask."""
        out = [PredictedResult(item_scores=()) for _ in queries]
        n = len(model.items)
        if n == 0 or not queries:
            return out
        with annotate("pio.turn.prepare"):
            known = [
                [ix for ix in map(model.items.get, q.items) if ix >= 0]
                if q.num > 0 else [] for q in queries
            ]
            valid = np.array([bool(ixs) for ixs in known])
            if not valid.any():
                return out
            # cosine: both sides normalized — the table at train time,
            # the query vector per request
            qvecs = self._query_vecs(model, known)
            k = min(
                pow2_ceil(max(q.num for q, v in zip(queries, valid) if v)),
                n,
            )
            # exclude the query items themselves plus any filters
            flt = batch_filter(model.items, model.serving_categories(), [
                RowFilter(q.categories, q.whitelist, q.blacklist, ixs)
                if ixs else None for q, ixs in zip(queries, known)
            ])
            tables = model.device_item_tables()
        with annotate("pio.turn.dispatch", filter=flt.kind,
                      path=topk_path(qvecs, tables, k, flt.mask,
                                     flt.exclude, flt.categories),
                      exclude_width=flt.width,
                      categories=flt.category_rows):
            vals, ixs = batch_topk_scores_t(
                qvecs, tables, k, **flt.scorer_kwargs(model))
        with annotate("pio.turn.fetch"):
            vals, ixs = jax.device_get((vals, ixs))
        with annotate("pio.turn.decode"):
            decoded = decode_batch_item_scores(
                model.items, vals, ixs, [q.num for q in queries], valid, k
            )
            return [
                PredictedResult(item_scores=scores) for scores in decoded
            ]


def similarproduct_engine() -> Engine:
    return Engine(
        SimilarProductDataSource,
        IdentityPreparator,
        {"als": SimilarProductAlgorithm, "": SimilarProductAlgorithm},
        FirstServing,
    )


# -- pio-forge registration -------------------------------------------------


def _conformance_events():
    from ..storage import DataMap, Event

    events = []
    # two co-view clusters (even / odd items)
    for u in range(12):
        cluster = u % 2
        for j in range(5):
            i = (2 * j + cluster) % 10
            events.append(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
            ))
    for j in range(10):
        events.append(Event(
            event="$set", entity_type="item", entity_id=f"i{j}",
            properties=DataMap(
                {"categories": ["even" if j % 2 == 0 else "odd"]}),
        ))
    return events


from ..engines import ConformanceFixture, engine_spec  # noqa: E402

similarproduct_engine = engine_spec(
    "similarproduct",
    description=(
        "Similar-product ranking from item factors "
        "(scala-parallel-similarproduct analogue)"
    ),
    default_params={
        "datasource": {"params": {"appName": "MyApp"}},
        "algorithms": [
            {
                "name": "als",
                "params": {"rank": 10, "numIterations": 20,
                           "lambda": 0.01, "seed": 3},
            }
        ],
    },
    query_example={"items": ["1"], "num": 4},
    conformance=ConformanceFixture(
        app_name="forge-conf",
        seed_events=_conformance_events,
        queries=({"items": ["i0"], "num": 3},),
        check=lambda r: len(r.get("itemScores", [])) >= 1
        and all(s["item"] != "i0" for s in r["itemScores"]),
        variant={
            "datasource": {"params": {"appName": "forge-conf"}},
            "algorithms": [
                {"name": "als",
                 "params": {"rank": 4, "numIterations": 3,
                            "lambda": 0.1, "alpha": 10.0, "seed": 1}}
            ],
        },
    ),
)(similarproduct_engine)
