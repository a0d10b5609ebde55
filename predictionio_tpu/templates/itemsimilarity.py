"""Item-to-item similarity at catalog scale — cosine on the two-stage
ANN index.

The similarproduct template scores cosine with a brute-force scan over
the normalized item table; at catalog scale (1M+ items) that exact scan
is exactly what pio-scout's two-stage retriever was built to replace —
but the retriever only rode the recommendation template's inner-product
path (ROADMAP 2(d): "cosine/similarproduct scoring rides the exact
path").  This engine closes that gap with one move: the model stores
the item table ALREADY row-normalized, so inner product over it IS
cosine, and the unchanged int8/IVF candidate stage + exact f32 rerank
(`retrieval.TwoStageRetriever`) does cosine retrieval with no new
kernel.  Query items are excluded host-side from an over-fetched
shortlist (``pow2_ceil(num + |query items|)`` keeps the executable key
space bounded); filtered queries (categories/white/blacklist) keep the
exact masked scorer, the same contract as the recommendation template.

Wire format parity with similarproduct: query ``{"items": [...],
"num": 4, ...filters}``; result ``{"itemScores": [...]}``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from ..controller import (
    Algorithm,
    Engine,
    FirstServing,
    IdentityPreparator,
    ModelPlacement,
    Params,
    WorkflowContext,
)
from ..models.als import ALSConfig, train_als
from ..ops.topk import batch_topk_scores, pow2_ceil, topk_scores
from ._common import CategoryIndex, DeviceTableMixin, filter_bias_mask, \
    normalize_rows, pow2_ladder, warm_batched_topk
from .recommendation import (
    ItemScore,
    PredictedResult,
    decode_batch_item_scores,
    decode_item_scores,
)
from .similarproduct import Query, SimilarProductDataSource


@dataclass(frozen=True)
class ItemSimilarityParams(Params):
    __param_aliases__ = {"lambda": "lam"}
    # a model trained with the fused kernel, which went, retrains and
    # folds in on the default route
    __retired_values__ = {"solver": {"fused": "auto"}}

    rank: int = 10
    num_iterations: int = 20
    lam: float = 0.01
    alpha: float = 1.0
    seed: int = 3
    solver: str = "auto"
    factor_placement: str = "replicated"
    # pio-scout two-stage cosine (the point of this engine): "ivf" is
    # the catalog-scale default; "exact" restores the brute-force scan
    # (the A/B baseline `tools/bench_engines.py` records)
    retrieval: str = "ivf"
    candidate_factor: int = 10
    nprobe: int = 8
    ann_clusters: int = 0

    def __post_init__(self) -> None:
        if self.retrieval not in ("exact", "int8", "ivf"):
            raise ValueError(
                f"retrieval must be 'exact', 'int8' or 'ivf', "
                f"got {self.retrieval!r}"
            )
        if self.candidate_factor < 1:
            raise ValueError(
                f"candidateFactor must be >= 1, got {self.candidate_factor}"
            )
        if self.nprobe < 1:
            raise ValueError(f"nprobe must be >= 1, got {self.nprobe}")
        if self.ann_clusters < 0:
            raise ValueError(
                f"annClusters must be >= 0, got {self.ann_clusters}"
            )


@dataclass
class ItemSimilarityModel(DeviceTableMixin):
    """``item_factors`` is row-NORMALIZED at train time: every scorer
    (exact, int8, IVF) computes cosine as a plain inner product, and
    the ANN index quantizes unit-norm rows (per-row scales stay well
    conditioned)."""

    item_factors: np.ndarray
    items: Any  # StringIndex
    item_props: dict[str, dict]
    # the train's snapshot of the items' `categories`, which the mask of
    # a filtered query reads (`_common.filter_bias_mask`)
    category_index: Optional[CategoryIndex] = None

    def sanity_check(self) -> None:
        if not np.isfinite(self.item_factors).all():
            raise ValueError("item factors contain non-finite values")


class ItemSimilarityAlgorithm(Algorithm):
    """Implicit ALS -> normalized item table -> two-stage cosine."""

    params_class = ItemSimilarityParams
    placement = ModelPlacement.DEVICE_SHARDED

    def train(self, ctx: WorkflowContext, data) -> ItemSimilarityModel:
        p: ItemSimilarityParams = self.params
        factors = train_als(
            data.ratings,
            cfg=ALSConfig(
                rank=p.rank, num_iterations=p.num_iterations, lam=p.lam,
                implicit=True, alpha=p.alpha, seed=p.seed,
                solver=p.solver, factor_placement=p.factor_placement,
            ),
            mesh=ctx.mesh,
        )
        return ItemSimilarityModel(
            item_factors=normalize_rows(factors.item_factors),
            items=data.ratings.items,
            item_props=data.items,
            category_index=CategoryIndex.from_props(
                data.ratings.items, data.items),
        )

    def _retrieval_config(self):
        p = self.params
        if p.retrieval == "exact":
            return None
        from ..retrieval import RetrievalConfig

        return RetrievalConfig(
            mode=p.retrieval,
            candidate_factor=p.candidate_factor,
            nprobe=p.nprobe,
            clusters=p.ann_clusters,
        )

    # -- serving -----------------------------------------------------------
    def warmup(self, model: ItemSimilarityModel,
               max_batch: int = 64) -> None:
        n = len(model.items)
        if n == 0:
            return
        table = model.device_item_factors()  # already normalized
        rank = model.item_factors.shape[1]
        vec = np.zeros(rank, np.float32)
        bias = np.zeros(n, np.float32)
        for k in {min(k, n) for k in (1, 4, 10, 20)}:
            topk_scores(vec, table, k, bias=bias)
        warm_batched_topk(table, rank, n, max_batch=max_batch)
        rcfg = self._retrieval_config()
        if rcfg is not None:
            # the two-stage cosine path joins the warmup ladder: every
            # pow2 batch at the over-fetch widths single-item and
            # few-item queries dispatch (k + |query items| rounds up)
            idx = model.device_ann_index(rcfg)
            ladder = (pow2_ladder(max_batch) or []) + [1]
            for k in {min(pow2_ceil(kk), n) for kk in (11, 16)}:
                idx.warm(k, ladder, table)

    def _known_and_qvec(self, model: ItemSimilarityModel, query: Query):
        known = [model.items.get(i) for i in query.items]
        known = [i for i in known if i >= 0]
        if not known or query.num <= 0:
            return None, None
        qvec = model.item_factors[known].mean(axis=0)
        qn = qvec / (np.linalg.norm(qvec) + 1e-9)
        return known, np.asarray(qn, np.float32)

    def _has_filters(self, query: Query) -> bool:
        return bool(query.categories or query.whitelist or query.blacklist)

    def _exact_mask(self, model, query, known):
        return filter_bias_mask(
            model.items, model.categories(),
            categories=query.categories, whitelist=query.whitelist,
            blacklist=query.blacklist or (), exclude_ix=known,
        )

    @staticmethod
    def _decode_excluding(model, vals, ixs, num, exclude) -> tuple:
        """Host-side decode of ONE over-fetched shortlist row: drop the
        query items + non-finite rows, truncate to ``num``."""
        import jax

        vals, ixs = jax.device_get((vals, ixs))
        ex = set(int(i) for i in exclude)
        out = []
        for v, ix in zip(vals, ixs):
            if not np.isfinite(v) or int(ix) in ex:
                continue
            out.append(
                ItemScore(item=str(model.items.id_of(int(ix))),
                          score=float(v))
            )
            if len(out) >= num:
                break
        return tuple(out)

    def predict(self, model: ItemSimilarityModel,
                query: Query) -> PredictedResult:
        known, qn = self._known_and_qvec(model, query)
        if known is None:
            return PredictedResult(item_scores=())
        n = len(model.items)
        k = min(query.num, n)
        rcfg = self._retrieval_config()
        if rcfg is not None and not self._has_filters(query):
            # two-stage cosine: over-fetch to survive the host-side
            # exclusion of the query items themselves
            kq = min(pow2_ceil(k + len(known)), n)
            vals, ixs = model.device_ann_index(rcfg).search(
                qn[None, :], kq, model.device_item_factors()
            )
            return PredictedResult(item_scores=self._decode_excluding(
                model, np.asarray(vals)[0], np.asarray(ixs)[0],
                query.num, known,
            ))
        mask = self._exact_mask(model, query, known)
        vals, ixs = topk_scores(qn, model.device_item_factors(), k,
                                bias=mask)
        return PredictedResult(
            item_scores=decode_item_scores(model.items, vals, ixs)
        )

    def batch_predict(self, model: ItemSimilarityModel, queries):
        """Micro-batched serving + eval path: one batched two-stage
        search (or one batched masked exact matmul) for the whole
        coalesced batch — the same shape-stability contract as the
        other templates (batch stays ``len(queries)``, k pow2)."""
        out = [PredictedResult(item_scores=()) for _ in queries]
        n = len(model.items)
        if n == 0 or not queries:
            return out
        rank = model.item_factors.shape[1]
        qvecs = np.zeros((len(queries), rank), np.float32)
        knowns: list[list[int]] = [[] for _ in queries]
        valid = np.zeros(len(queries), bool)
        any_filters = False
        for bi, q in enumerate(queries):
            known, qn = self._known_and_qvec(model, q)
            if known is None:
                continue
            valid[bi] = True
            qvecs[bi] = qn
            knowns[bi] = known
            any_filters = any_filters or self._has_filters(q)
        if not valid.any():
            return out
        max_num = max(q.num for q, v in zip(queries, valid) if v)
        rcfg = self._retrieval_config()
        if rcfg is not None and not any_filters:
            max_known = max(len(kn) for kn in knowns)
            kq = min(pow2_ceil(max_num + max_known), n)
            vals, ixs = model.device_ann_index(rcfg).search(
                qvecs, kq, model.device_item_factors()
            )
            vals, ixs = np.asarray(vals), np.asarray(ixs)
            for bi, q in enumerate(queries):
                if valid[bi]:
                    out[bi] = PredictedResult(
                        item_scores=self._decode_excluding(
                            model, vals[bi], ixs[bi], q.num, knowns[bi]
                        ))
            return out
        k = min(pow2_ceil(max_num), n)
        masks = np.zeros((len(queries), n), np.float32)
        for bi, q in enumerate(queries):
            if valid[bi]:
                masks[bi] = self._exact_mask(model, q, knowns[bi])
        vals, ixs = batch_topk_scores(
            qvecs, model.device_item_factors(), k, mask=masks
        )
        decoded = decode_batch_item_scores(
            model.items, vals, ixs, [q.num for q in queries], valid, k
        )
        return [PredictedResult(item_scores=s) for s in decoded]


def itemsimilarity_engine() -> Engine:
    return Engine(
        SimilarProductDataSource,
        IdentityPreparator,
        {"cosine": ItemSimilarityAlgorithm, "": ItemSimilarityAlgorithm},
        FirstServing,
    )


def itemsimilarity_evaluation(app_name: str = "MyApp", k: int = 10,
                              holdout: float = 0.3):
    """MAP@k evaluation binding (ROADMAP 4(b)): `pio-tpu eval --engine
    itemsimilarity` sweeps the exact scorer against the two-stage IVF
    retriever on a leave-some-out co-view split — the eval leg's
    answer to "does the ANN path cost ranking quality here"."""
    from ..controller import Evaluation
    from ..controller.metrics import MAPatK

    engine = itemsimilarity_engine()
    eps = []
    for retrieval in ("exact", "ivf"):
        eps.append(engine.params_from_variant({
            "datasource": {"params": {
                "appName": app_name,
                "evalHoldout": holdout, "evalNum": k,
            }},
            "algorithms": [{"name": "cosine", "params": {
                "rank": 8, "numIterations": 5, "lambda": 0.05,
                "alpha": 2.0, "seed": 3, "retrieval": retrieval,
                "candidateFactor": 10, "nprobe": 8,
            }}],
        }))
    return Evaluation(engine, MAPatK(k), engine_params_list=eps)


# -- pio-forge registration -------------------------------------------------


def _conformance_events():
    from .similarproduct import _conformance_events as sim_events

    return sim_events()


from ..engines import ConformanceFixture, engine_spec  # noqa: E402

itemsimilarity_engine = engine_spec(
    "itemsimilarity",
    description=(
        "Item-to-item cosine similarity at catalog scale: normalized "
        "item table riding the two-stage int8/IVF retriever"
    ),
    default_params={
        "datasource": {"params": {"appName": "MyApp"}},
        "algorithms": [
            {
                "name": "cosine",
                "params": {"rank": 10, "numIterations": 20,
                           "lambda": 0.01, "seed": 3,
                           "retrieval": "ivf", "candidateFactor": 10,
                           "nprobe": 8},
            }
        ],
    },
    query_example={"items": ["1"], "num": 4},
    evaluation=itemsimilarity_evaluation,
    conformance=ConformanceFixture(
        app_name="forge-conf",
        seed_events=_conformance_events,
        queries=({"items": ["i0"], "num": 3},),
        check=lambda r: len(r.get("itemScores", [])) >= 1
        and all(s["item"] != "i0" for s in r["itemScores"]),
        variant={
            "datasource": {"params": {"appName": "forge-conf"}},
            "algorithms": [
                {"name": "cosine",
                 "params": {"rank": 4, "numIterations": 3,
                            "lambda": 0.1, "alpha": 10.0, "seed": 1,
                            "retrieval": "int8",
                            "candidateFactor": 16}}
            ],
        },
    ),
)(itemsimilarity_engine)
