"""E-commerce recommendation engine template.

Capability parity with `/root/reference/examples/scala-parallel-
ecommercerecommendation/` (``ECommAlgorithm``): implicit ALS over view
(+ optional buy/rate) events, with **predict-time event-store reads** —
the serving path consults the live event store, inside the turn, for

* the batch's users' already-seen items (``unseen_only`` +
  ``seen_events`` params, reference `ALSAlgorithm.scala:160-192`): ONE
  read a batch through ``EventStore.find_target_ids``, nothing cached
  from one request to the next, so a ``buy`` acknowledged before a query
  is received is out of that query's answer, and
* the latest ``$set`` on the ``constraint``/``unavailableItems`` entity
  (reference `:194-215`), once a batch.

Both, with the query's ``blackList``, travel to the device as item ids
(``_common.batch_filter`` -> ``ops.topk.batch_topk_scores_t(exclude=)``):
one ``[B, E]`` int32 array a batch, E the rung of
``ops.topk.EXCLUDE_LADDER`` that holds the batch's longest list (32 to
4,224 ids), taken out inside the exact blocked top-k.  A query's
``categories`` travel as numbers of the model's category index and are
tested on the device where the row's list holds at most 32 ids (a
shopper's whole history is usually longer: such a batch takes the mask,
counted).  No array of the catalogue's length is built on the host for
the ids; a ``whiteList``, and ``categories`` beside a longer list, still
make the ``[B, M]`` mask.

What ``unseenOnly`` costs a batch, by the longest history in it (one
v5e chip, 9.35 M items at rank 128, 16 rows; builder's chip runs,
PR 40; PERF.md, section 5): the read of the store 0.06 ms and the
filter's build 0.4 ms on the host; on the device 6.7 ms with no ids,
7.3 ms up to 128 ids, 8.0 up to 512, 10.3 up to 2,048, 14.0 up to
4,224 (6.3-6.5 of each is the one read of the table), and a longer
list sends the batch to the host's mask.  This is the template that
demonstrates low-latency `LEventStore` access from ``predict``
(SURVEY §2.6).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass
from typing import Any, Optional, Sequence

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
from ..obs import get_registry, log_buckets
from ..obs.timeline import annotate
from ..ops.topk import (
    EXCLUDE_LADDER, batch_topk_scores_t, pow2_ceil, topk_path,
)

from ._common import (
    CategoryIndex, DeviceTableMixin, RowFilter, batch_filter,
    warm_batched_topk,
)
from .recommendation import (
    PredictedResult,
    Query,
    _resolve_app_id,
    decode_batch_item_scores,
)

logger = logging.getLogger(__name__)

_registry = get_registry()
SEEN_READ_SECONDS = _registry.histogram(
    "pio_seen_read_seconds",
    "Host time of one batch's `pio.seen.read` span: its users' seen "
    "items read from the event store inside the turn",
    buckets=log_buckets(1e-6, 10.0, per_decade=4),
).child()
SEEN_EVENTS = _registry.counter(
    "pio_seen_events_total",
    "Target ids of seen events that the e-commerce engine read from the "
    "event store at query time",
).child()
SEEN_READ_FAILURES = _registry.counter(
    "pio_seen_read_failures_total",
    "Reads of a batch's seen events that failed or timed out: logged and "
    "answered as if the users had seen nothing, as upstream does",
).child()


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
    # the train's snapshot of the items' `categories` as arrays; a model
    # made without one gets it from `item_props` at first use
    # (`categories()`)
    category_index: Optional[CategoryIndex] = None


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
            category_index=CategoryIndex.from_props(
                data.ratings.items, data.items),
        )

    # -- predict-time event store reads ------------------------------------
    def _event_store(self):
        ctx = getattr(self, "_ctx", None)
        if ctx is None:
            from ..storage.registry import get_storage

            return get_storage().get_event_store()
        return ctx.storage.get_event_store()

    def _seen_items(self, model: ECommModel,
                    users: Sequence[str]) -> list:
        """Each user's already-seen items (reference `:160-192`), one
        list of item ids a user: ONE read of the store for the batch,
        inside the turn.  A failed read is logged, counted, and answers
        as if nothing had been seen."""
        t0 = time.perf_counter()
        with annotate("pio.seen.read"):
            try:
                seen = self._event_store().find_target_ids(
                    app_id=model.app_id,
                    entity_type="user",
                    entity_ids=users,
                    event_names=list(self.params.seen_events),
                )
            except Exception as e:
                logger.error("error reading seen events: %s", e)
                SEEN_READ_FAILURES.inc()
                seen = [[] for _ in users]
        SEEN_READ_SECONDS.observe(time.perf_counter() - t0)
        SEEN_EVENTS.inc(sum(map(len, seen)))
        return seen

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
        """Pre-compile the batched top-k scorer at the pow2 shapes the
        serving micro-batcher dispatches (a lone request is the one-row
        rung), with excluded ids at the widths this engine's queries can
        take: with ``unseen_only`` a user's whole history, every rung of
        the ladder; without, a blackList and the unavailable items, the
        first.  No small-k rungs: they would be one more program a
        width, and a shelf asks for ten."""
        n = len(model.items)
        if n == 0:
            return
        warm_batched_topk(
            None, model.item_factors.shape[1], n, unmasked_too=True,
            max_batch=max_batch, table_t=model.device_item_tables(),
            exclude_widths=EXCLUDE_LADDER if self.params.unseen_only
            else None,
            category_model=model,
        )

    def _excluded_items(self, model: ECommModel, users: Sequence[str]):
        """For each user, the distinct item indices (int32) the engine
        itself takes out: the user's seen items (``unseen_only``) and
        the unavailable ones, both read from the live event store, the
        constraint entity ONCE for the batch."""
        gone = model.items.encode(sorted(self._unavailable_items(model)))
        if not self.params.unseen_only:
            return [np.unique(gone[gone >= 0])] * len(users)
        out = []
        for seen in self._seen_items(model, users):
            ixs = np.concatenate([model.items.encode(seen), gone])
            out.append(np.unique(ixs[ixs >= 0]))
        return out

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        """A lone request is a one-row batch: the same device program,
        the same filters as data."""
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: ECommModel, queries):
        """THE scoring path (serving, batched or lone, and eval): one
        batched scorer call under the same shape-stability contract as
        the other templates (device batch = len(queries), k rounded to
        pow2).  Each row's blackList, seen items and the unavailable
        items travel to the device as item ids
        (``_common.batch_filter``), `categories` as category numbers
        where the batch's longest list holds at most 32 ids; a
        `whiteList`, and `categories` beside a longer list, still make
        the batch's ``[B, M]`` mask."""
        out = [PredictedResult(item_scores=()) for _ in queries]
        n = len(model.items)
        if n == 0 or not queries:
            return out
        with annotate("pio.turn.prepare"):
            uix = np.array(
                [model.users.get(q.user) for q in queries], dtype=np.int64
            )
            nums = np.array([q.num for q in queries], dtype=np.int64)
            valid = (uix >= 0) & (nums > 0)
            if not valid.any():
                return out
            k = min(pow2_ceil(int(nums[valid].max())), n)
            uvecs = np.asarray(
                model.user_factors[np.where(valid, uix, 0)], np.float32
            )
            asked = [q for q, v in zip(queries, valid) if v]
            gone = iter(self._excluded_items(
                model, [q.user for q in asked]))
            flt = batch_filter(model.items, model.serving_categories(), [
                RowFilter(q.categories, q.whitelist, q.blacklist, next(gone))
                if v else None for q, v in zip(queries, valid)
            ])
            tables = model.device_item_tables()
        with annotate("pio.turn.dispatch", filter=flt.kind,
                      path=topk_path(uvecs, tables, k, flt.mask,
                                     flt.exclude, flt.categories),
                      exclude_width=flt.width,
                      categories=flt.category_rows):
            vals, ixs = batch_topk_scores_t(
                uvecs, tables, k, **flt.scorer_kwargs(model))
        with annotate("pio.turn.fetch"):
            vals, ixs = jax.device_get((vals, ixs))
        with annotate("pio.turn.decode"):
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
