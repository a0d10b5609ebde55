"""E-commerce recommendation engine template.

Capability parity with `/root/reference/examples/scala-parallel-
ecommercerecommendation/` (``ECommAlgorithm``): implicit ALS over view
(+ optional buy/rate) events, with **predict-time event-store reads** —
the serving path consults the live event store for

* the user's already-seen items (``unseen_only`` + ``seen_events`` params,
  reference `ALSAlgorithm.scala:160-192`), and
* the latest ``$set`` on the ``constraint``/``unavailableItems`` entity
  (reference `:194-215`),

then merges both with the query blacklist before the top-k matmul.  This is
the template that demonstrates low-latency `LEventStore` access from
``predict`` (SURVEY §2.6).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Any, Optional

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
from ..ops.topk import batch_topk_scores, pow2_ceil, topk_scores

from ._common import DeviceTableMixin, filter_bias_mask, warm_batched_topk
from .recommendation import (
    PredictedResult,
    Query,
    _resolve_app_id,
    decode_batch_item_scores,
    decode_item_scores,
)

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class ECommDataSourceParams(Params):
    app_name: str = ""
    app_id: int = -1
    view_events: tuple[str, ...] = ("view",)
    rating_property: Optional[str] = None  # train-with-rate-event variant


@dataclass
class ECommTrainingData:
    ratings: Any
    items: dict[str, dict]
    app_id: int = -1

    def sanity_check(self) -> None:
        if len(self.ratings) == 0:
            raise ValueError("no view events found")


class ECommDataSource(DataSource):
    params_class = ECommDataSourceParams

    def read_training(self, ctx: WorkflowContext) -> ECommTrainingData:
        p = self.params
        app_id = _resolve_app_id(ctx, p)
        es = ctx.storage.get_event_store()
        if hasattr(es, "find_ratings"):
            # fused native read (explicit or implicit-count mode,
            # native/sqlite_scan.cpp)
            ratings = es.find_ratings(
                app_id=app_id, event_names=p.view_events,
                rating_property=p.rating_property,
                dedup="last" if p.rating_property else "sum",
                entity_type="user",
            )
        else:
            frame = es.find_columnar(
                app_id=app_id, entity_type="user",
                event_names=list(p.view_events),
                float_property=p.rating_property,
                minimal=True,   # only to_ratings fields are consumed
            )
            ratings = frame.to_ratings(
                rating_property=p.rating_property,
                dedup="last" if p.rating_property else "sum",
            )
        items = {
            k: dict(v.fields)
            for k, v in es.aggregate_properties_of(
                app_id=app_id, entity_type="item"
            ).items()
        }
        return ECommTrainingData(ratings=ratings, items=items, app_id=app_id)


@dataclass(frozen=True)
class ECommAlgorithmParams(Params):
    __param_aliases__ = {"lambda": "lam"}

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
    gather_dtype: str = "float32"
    gather_mode: str = "row"
    unseen_only: bool = False
    seen_events: tuple[str, ...] = ("view", "buy")


@dataclass
class ECommModel(DeviceTableMixin):
    user_factors: np.ndarray
    item_factors: np.ndarray
    users: Any
    items: Any
    item_props: dict[str, dict]
    app_id: int


class ECommAlgorithm(Algorithm):
    params_class = ECommAlgorithmParams
    placement = ModelPlacement.DEVICE_SHARDED

    def train(self, ctx: WorkflowContext, data: ECommTrainingData) -> ECommModel:
        p = self.params
        implicit = True
        factors = train_als(
            data.ratings,
            cfg=ALSConfig(
                rank=p.rank, num_iterations=p.num_iterations, lam=p.lam,
                implicit=implicit, alpha=p.alpha, seed=p.seed,
                solver=p.solver, factor_placement=p.factor_placement,
                solver_mode=p.solver_mode,
                subspace_size=p.subspace_size,
                gather_dtype=p.gather_dtype,
                gather_mode=p.gather_mode,
            ),
            mesh=ctx.mesh,
        )
        self._ctx = ctx  # predict-time event-store access
        return ECommModel(
            user_factors=factors.user_factors,
            item_factors=factors.item_factors,
            users=data.ratings.users,
            items=data.ratings.items,
            item_props=data.items,
            app_id=data.app_id,
        )

    # -- predict-time event store reads ------------------------------------
    def _event_store(self):
        ctx = getattr(self, "_ctx", None)
        if ctx is None:
            from ..storage.registry import get_storage

            return get_storage().get_event_store()
        return ctx.storage.get_event_store()

    def _seen_items(self, model: ECommModel, user: str) -> set[str]:
        """The user's already-seen items (reference `:160-192`)."""
        p = self.params
        try:
            events = self._event_store().find(
                app_id=model.app_id,
                entity_type="user",
                entity_id=user,
                event_names=list(p.seen_events),
            )
            return {
                e.target_entity_id for e in events if e.target_entity_id
            }
        except Exception as e:
            logger.error("error reading seen events: %s", e)
            return set()

    def _unavailable_items(self, model: ECommModel) -> set[str]:
        """Latest constraint/unavailableItems $set (reference `:194-215`)."""
        try:
            pm = self._event_store().aggregate_properties_single_entity(
                app_id=model.app_id,
                entity_type="constraint",
                entity_id="unavailableItems",
            )
            if pm is None:
                return set()
            return set(pm.get_string_list("items"))
        except Exception as e:
            logger.error("error reading unavailableItems: %s", e)
            return set()

    def warmup(self, model: ECommModel, max_batch: int = 64) -> None:
        """Pre-compile the biased top-k scorer for the common ``num``
        values (every e-comm query carries a filter mask), single-query
        AND the pow2 batched shapes the serving micro-batcher
        dispatches."""
        n = len(model.items)
        if n == 0:
            return
        table = model.device_item_factors()
        rank = model.item_factors.shape[1]
        vec = np.zeros(rank, np.float32)
        bias = np.zeros(n, np.float32)
        for k in {min(k, n) for k in (1, 4, 10, 20)}:
            topk_scores(vec, table, k, bias=bias)
        warm_batched_topk(table, rank, n, max_batch=max_batch)

    def _query_mask(self, model: ECommModel, query: Query,
                    unavailable: Optional[set] = None):
        """Serve-time filter for one query: blacklist + (optionally)
        the user's SEEN events read from the live event store + the
        unavailable-items constraint — the reference's predict-time
        LEventStore reads (`ECommAlgorithm.scala` predict).

        ``unavailable`` lets batch_predict read the batch-invariant
        constraint entity ONCE instead of once per coalesced query."""
        black = set(query.blacklist or ())
        if self.params.unseen_only:
            black |= self._seen_items(model, query.user)
        black |= (
            self._unavailable_items(model)
            if unavailable is None else unavailable
        )
        return filter_bias_mask(
            model.items, model.item_props,
            categories=query.categories, whitelist=query.whitelist,
            blacklist=black,
        )

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        uix = model.users.get(query.user)
        if uix < 0 or query.num <= 0:
            return PredictedResult(item_scores=())
        mask = self._query_mask(model, query)
        k = min(query.num, len(model.items))
        vals, ixs = topk_scores(
            np.asarray(model.user_factors[uix], np.float32),
            model.device_item_factors(), k, bias=mask,
        )
        return PredictedResult(
            item_scores=decode_item_scores(model.items, vals, ixs)
        )

    def batch_predict(self, model: ECommModel, queries):
        """Micro-batched serving + eval path: the per-query event-store
        reads (seen/unavailable) stay host work, the scoring collapses
        to one batched masked matmul under the same shape-stability
        contract as the other templates (device batch = len(queries),
        k rounded to pow2)."""
        out = [PredictedResult(item_scores=()) for _ in queries]
        n = len(model.items)
        if n == 0 or not queries:
            return out
        uix = np.array(
            [model.users.get(q.user) for q in queries], dtype=np.int64
        )
        nums = np.array([q.num for q in queries], dtype=np.int64)
        valid = (uix >= 0) & (nums > 0)
        if not valid.any():
            return out
        masks = np.zeros((len(queries), n), np.float32)
        unavailable = self._unavailable_items(model)  # batch-invariant
        for bi, q in enumerate(queries):
            if valid[bi]:
                masks[bi] = self._query_mask(model, q, unavailable)
        k = min(pow2_ceil(int(nums[valid].max())), n)
        uvecs = np.asarray(
            model.user_factors[np.where(valid, uix, 0)], np.float32
        )
        vals, ixs = batch_topk_scores(
            uvecs, model.device_item_factors(), k, mask=masks
        )
        decoded = decode_batch_item_scores(
            model.items, vals, ixs, [q.num for q in queries], valid, k
        )
        return [
            PredictedResult(item_scores=scores) for scores in decoded
        ]


def ecommerce_engine() -> Engine:
    return Engine(
        ECommDataSource,
        IdentityPreparator,
        {"ecomm": ECommAlgorithm, "": ECommAlgorithm},
        FirstServing,
    )


# -- pio-forge registration -------------------------------------------------


def _conformance_events():
    from ..storage import Event

    events = []
    for u in range(10):
        for j in range(4):
            i = (u * 3 + j) % 8
            events.append(Event(
                event="view", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
            ))
    return events


from ..engines import ConformanceFixture, engine_spec  # noqa: E402

ecommerce_engine = engine_spec(
    "ecommercerecommendation",
    description=(
        "E-commerce recommendation with serving-time event filtering "
        "(scala-parallel-ecommercerecommendation analogue)"
    ),
    default_params={
        "datasource": {"params": {"appName": "MyApp"}},
        "algorithms": [
            {
                "name": "ecomm",
                "params": {
                    "appName": "MyApp",
                    "unseenOnly": True,
                    "seenEvents": ["buy", "view"],
                    "rank": 10,
                    "numIterations": 20,
                    "lambda": 0.01,
                    "seed": 3,
                },
            }
        ],
    },
    query_example={"user": "u1", "num": 4},
    conformance=ConformanceFixture(
        app_name="forge-conf",
        seed_events=_conformance_events,
        queries=({"user": "u1", "num": 3},),
        check=lambda r: len(r.get("itemScores", [])) >= 1,
        variant={
            "datasource": {"params": {"appName": "forge-conf"}},
            "algorithms": [
                {"name": "ecomm",
                 "params": {"rank": 4, "numIterations": 3,
                            "lambda": 0.1, "alpha": 10.0, "seed": 1}}
            ],
        },
    ),
)(ecommerce_engine)
