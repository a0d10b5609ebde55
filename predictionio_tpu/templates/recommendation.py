"""Recommendation engine template — the flagship end-to-end slice.

Capability parity with
`/root/reference/examples/scala-parallel-recommendation/` (all four variants:
custom-prepartor, custom-query, custom-serving, filter-by-category), rebuilt
TPU-first: the MLlib ``ALS.train``/``trainImplicit`` call becomes
:func:`predictionio_tpu.models.als.train_als` (bucketed block solves on the
mesh) and the predict-time cosine scan becomes one fused matmul + top-k
(`predictionio_tpu.ops.topk`).

Wire format parity (reference `DataSource.scala` / `Serving.scala` of the
template): query ``{"user": "u1", "num": 4, "categories": [...],
"whitelist": [...], "blacklist": [...]}``; result
``{"itemScores": [{"item": ..., "score": ...}]}``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
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
from ..ops.topk import (
    batch_topk_scores,  # noqa: F401 — public template API surface
    batch_topk_scores_t,
    pow2_ceil,
    topk_path,
)
from ..storage.columnar import Ratings
from ._common import (
    CategoryIndex,
    DeviceTableMixin,
    RowFilter,
    batch_filter,
    warm_batched_topk,
    warm_shapes,
)
from ..storage.levents import EventStore


# --------------------------------------------------------------------------
# Queries / results (wire format parity)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Query:
    user: str
    num: int = 10
    categories: Optional[tuple[str, ...]] = None
    whitelist: Optional[tuple[str, ...]] = None
    blacklist: Optional[tuple[str, ...]] = None

    @staticmethod
    def from_json(d: dict) -> "Query":
        # reference wire format uses camelCase whiteList/blackList
        wl = d.get("whiteList") or d.get("whitelist")
        bl = d.get("blackList") or d.get("blacklist")
        return Query(
            user=str(d["user"]),
            num=int(d.get("num", 10)),
            categories=tuple(d["categories"]) if d.get("categories") else None,
            whitelist=tuple(wl) if wl else None,
            blacklist=tuple(bl) if bl else None,
        )


@dataclass(frozen=True)
class ItemScore:
    item: str
    score: float


@dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple[ItemScore, ...]

    def to_json(self) -> dict:
        return {
            "itemScores": [
                {"item": s.item, "score": s.score} for s in self.item_scores
            ]
        }


# --------------------------------------------------------------------------
# DataSource
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    app_id: int = -1
    event_names: tuple[str, ...] = ("rate",)
    rating_property: Optional[str] = "rating"
    entity_type: str = "user"
    target_entity_type: str = "item"
    item_entity_type: str = "item"
    eval_k: int = 0          # >0 enables k-fold read_eval
    eval_seed: int = 3
    # multi-host COO handling: "gathered" (every process receives the
    # full rating set — the replicated-placement path) or "local" (each
    # process keeps only its scan shard, globally id-encoded; the
    # algorithm then exchanges triples straight to each row's owning
    # device via ALSTrainer.distributed — NO process ever holds the
    # full COO, so rating capacity scales with the cluster.  Requires
    # the algorithm side to set factorPlacement="sharded")
    coo: str = "gathered"

    def __post_init__(self) -> None:
        if self.coo not in ("gathered", "local"):
            raise ValueError(
                f"coo must be 'gathered' or 'local', got {self.coo!r}"
            )


@dataclass
class TrainingData:
    ratings: Ratings
    items: dict[str, dict] = field(default_factory=dict)  # item -> properties
    # True when `ratings` is this PROCESS's shard of a multi-host read
    # (globally id-encoded); algorithms must route through
    # ALSTrainer.distributed instead of assuming a full COO
    coo_local: bool = False

    def sanity_check(self) -> None:
        n = len(self.ratings)
        if self.coo_local:
            # a local shard can legitimately be empty on skewed data;
            # only GLOBAL emptiness is a real problem — sum the counts
            # (sanity_check runs symmetrically on every process, so the
            # collective pairs up)
            if jax.process_count() > 1:
                from jax.experimental import multihost_utils

                n = int(np.sum(np.asarray(
                    multihost_utils.process_allgather(np.int64(n))
                )))
        if n == 0:
            raise ValueError("no rating events found — is the app empty?")


def decode_item_scores(items, vals, ixs) -> tuple:
    """ONE host sync for both top-k outputs (each separate readback costs
    a full RTT on a remote-attached accelerator), then decode to
    :class:`ItemScore` rows, dropping -inf-masked entries."""
    vals, ixs = jax.device_get((vals, ixs))
    ok = np.isfinite(vals)
    ids = items.decode(ixs[ok])
    return tuple(
        ItemScore(item=str(i), score=float(s))
        for i, s in zip(ids, vals[ok])
    )


def decode_batch_item_scores(items, vals, ixs, nums, valid, k):
    """Host-side decode for a shape-stable batched top-k: ONE device
    fetch for the whole batch, then per-query slicing to ``min(num, k)``
    with -inf-masked entries dropped.  Shared by every template
    ``batch_predict`` so the filtering/decode contract cannot diverge."""
    vals, ixs = jax.device_get((vals, ixs))
    out = [()] * len(nums)
    for bi, (num, ok_q) in enumerate(zip(nums, valid)):
        if not ok_q:
            continue
        m = min(num, k)
        ok = np.isfinite(vals[bi, :m])
        ids = items.decode(ixs[bi, :m][ok])
        out[bi] = tuple(
            ItemScore(item=str(it), score=float(s))
            for it, s in zip(ids, vals[bi, :m][ok])
        )
    return out


def _resolve_app_id(ctx: WorkflowContext, p: DataSourceParams) -> int:
    if p.app_id >= 0:
        return p.app_id
    app = ctx.storage.get_metadata().app_get_by_name(p.app_name)
    if app is None:
        raise ValueError(f"app {p.app_name!r} not found")
    return app.id


class RecommendationDataSource(DataSource):
    """Reads rate events + item properties
    (reference template `DataSource.scala:29-66`)."""

    params_class = DataSourceParams

    def _read_items(self, es: EventStore, app_id: int) -> dict[str, dict]:
        p: DataSourceParams = self.params
        return {
            k: dict(v.fields)
            for k, v in es.aggregate_properties_of(
                app_id=app_id, entity_type=p.item_entity_type
            ).items()
        }

    def _read_frame(self, ctx: WorkflowContext, es=None, app_id=None):
        p: DataSourceParams = self.params
        if es is None:
            app_id = _resolve_app_id(ctx, p)
            es = ctx.storage.get_event_store()
        frame = es.find_columnar(
            app_id=app_id,
            entity_type=p.entity_type,
            event_names=list(p.event_names),
            float_property=p.rating_property,
            minimal=True,   # only to_ratings fields are consumed
        )
        return frame, self._read_items(es, app_id)

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        p: DataSourceParams = self.params
        # one resolution for every branch below (metadata lookup +
        # store handle; the branches used to each re-resolve)
        app_id = _resolve_app_id(ctx, p)
        es: EventStore = ctx.storage.get_event_store()
        if jax.process_count() > 1:
            # multi-host run: each process scans only its entity-hash shard
            # (the region-parallel HBase analogue, `HBPEvents.scala:99-105`),
            # then id dictionaries + COO are exchanged/gathered
            from ..parallel.ingest import read_ratings_distributed

            ratings = read_ratings_distributed(
                es,
                exchange_dir=ctx.storage.model_data_dir() / "_ingest",
                tag=f"app{app_id}",
                rating_property=p.rating_property,
                dedup="last" if p.rating_property else "sum",
                gather=(p.coo == "gathered"),
                app_id=app_id,
                entity_type=p.entity_type,
                event_names=list(p.event_names),
            )
            return TrainingData(
                ratings=ratings,
                items=self._read_items(es, app_id),
                coo_local=(p.coo == "local"),
            )
        if hasattr(es, "find_ratings"):
            # fused native scan+encode (one C pass over the events
            # table, `native/sqlite_scan.cpp`); rating_property=None is
            # the implicit-count mode, so every configuration routes
            # through it — stores without the method take the general
            # columnar path below
            ratings = es.find_ratings(
                app_id=app_id,
                event_names=p.event_names,
                rating_property=p.rating_property,
                dedup="last" if p.rating_property else "sum",
                entity_type=p.entity_type,
            )
            return TrainingData(
                ratings=ratings, items=self._read_items(es, app_id)
            )
        frame, items = self._read_frame(ctx, es=es, app_id=app_id)
        ratings = frame.to_ratings(
            rating_property=p.rating_property,
            dedup="last" if p.rating_property else "sum",
        )
        return TrainingData(ratings=ratings, items=items)

    def read_eval(self, ctx: WorkflowContext):
        """k-fold split (e2 `CrossValidation.scala:33-63` semantics: fold i
        holds out every k-th rating after a seeded shuffle, so folds are
        deterministic and size-balanced)."""
        p: DataSourceParams = self.params
        if p.eval_k <= 0:
            return []
        frame, items = self._read_frame(ctx)
        ratings = frame.to_ratings(
            rating_property=p.rating_property,
            dedup="last" if p.rating_property else "sum",
        )
        rng = np.random.default_rng(p.eval_seed)
        perm = rng.permutation(len(ratings))
        fold = np.empty(len(ratings), dtype=np.int64)
        fold[perm] = np.arange(len(ratings)) % p.eval_k
        out = []
        for f in range(p.eval_k):
            tr = fold != f
            te = ~tr
            train = Ratings(
                user_ix=ratings.user_ix[tr],
                item_ix=ratings.item_ix[tr],
                rating=ratings.rating[tr],
                users=ratings.users,
                items=ratings.items,
            )
            qa = [
                (
                    Query(user=ratings.users.id_of(int(u)), num=0),
                    ActualRating(
                        item=ratings.items.id_of(int(i)), rating=float(r)
                    ),
                )
                for u, i, r in zip(
                    ratings.user_ix[te], ratings.item_ix[te], ratings.rating[te]
                )
            ]
            out.append((TrainingData(ratings=train, items=items), {"fold": f}, qa))
        return out


@dataclass(frozen=True)
class ActualRating:
    item: str
    rating: float


# --------------------------------------------------------------------------
# ALS algorithm
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    """engine.json parity: {"rank": 10, "numIterations": 20, "lambda": 0.01,
    "seed": 3} (reference `custom-query/engine.json:11-20`)."""

    __param_aliases__ = {"lambda": "lam"}
    # records written before gather_dtype went hold its float32 default
    __retired_params__ = {"gather_dtype": "float32"}
    # a model trained with the fused kernel, which went, retrains and
    # folds in on the default route
    __retired_values__ = {"solver": {"fused": "auto"}}

    rank: int = 10
    num_iterations: int = 20
    lam: float = 0.01
    seed: int = 3
    implicit: bool = False
    alpha: float = 1.0
    weighted_lambda: bool = True
    # serve-time scoring dtype: "float32" (default) or "bfloat16" (halves
    # HBM reads per query; ranking-only precision cost, training unaffected)
    serving_dtype: str = "float32"
    # gather access pattern: "row" | "grouped" (tile-aligned slab
    # gather — models/als.py ALSConfig.gather_mode)
    gather_mode: str = "row"
    # batched SPD solver: "auto" (the ops/solve.py kernel on a TPU,
    # lax.linalg elsewhere) | "xla" | "pallas" (a kernel that does not
    # compile on this backend fails the train)
    solver: str = "auto"
    # rank-sweep strategy: "full" (R×R solve per row) | "subspace"
    # (iALS++ block sweep — engine.json keys solverMode/subspaceSize;
    # models/als.py ALSConfig.solver_mode)
    solver_mode: str = "full"
    # block width B of the subspace sweep; B >= rank is exactly "full"
    subspace_size: int = 16
    # "replicated" (both factor tables + COO on every device) or
    # "sharded" (tables AND rating COO block-sharded over the mesh —
    # model and data capacity scale with total HBM)
    factor_placement: str = "replicated"
    # coded-ALS parity shards for sharded placement (engine.json key
    # codedShards): a late/dead shard's half-iteration contribution is
    # reconstructed from the other d-1 plus parity instead of stalling
    # the ring (models/als.py ALSConfig.coded_shards)
    coded_shards: bool = False
    # serve queries through the sharded top-k over a mesh-sharded item
    # table (engine.json key distributedTopk; ops/distributed_topk: each
    # chip scans its own shard, one all-gather of the candidates) with
    # parity-coded straggler tolerance: a shard missing its per-request
    # budget (the serving Deadline, split per shard) is served from
    # parity.  Unfiltered queries only — category/white/blacklist
    # queries keep the local scorer, which needs the whole table on one
    # chip and is not warmed under this knob
    distributed_topk: bool = False
    # pio-scout two-stage retrieval (engine.json key retrieval):
    # "exact" (default — brute-force scan, the pre-scout behavior),
    # "int8" (flat quantized candidate stage + exact f32 rerank), or
    # "ivf" (int8 candidates restricted to the nprobe nearest coarse
    # clusters — the catalog-scale mode).  Unfiltered queries only;
    # category/white/blacklist queries keep the exact scorer (a
    # per-query mask over a shortlist can starve it below num).  With
    # distributedTopk, each chip runs the int8 candidate stage over its
    # own shard ("ivf" maps to "int8" there — coarse clusters don't
    # shard).
    retrieval: str = "exact"
    # shortlist width in units of k: candidateFactor*k quantized
    # candidates survive to the exact rerank (recall@k rises with it;
    # candidateFactor covering the catalog is exact by construction)
    candidate_factor: int = 10
    # "ivf" only: clusters scanned per query (recall/latency dial)
    nprobe: int = 8
    # "ivf" only: coarse cluster count (engine.json annClusters;
    # 0 = auto ~sqrt(catalog), pow2-rounded)
    ann_clusters: int = 0

    def __post_init__(self) -> None:
        # serve-time knobs validated at CONFIG time (the ALSConfig
        # convention): a typo'd engine.json value must fail at
        # params_from_variant, not as a 500 on the first query
        if self.retrieval not in ("exact", "int8", "ivf"):
            raise ValueError(
                f"retrieval must be 'exact', 'int8' or 'ivf', "
                f"got {self.retrieval!r}"
            )
        if self.candidate_factor < 1:
            raise ValueError(
                f"candidateFactor must be >= 1, "
                f"got {self.candidate_factor}"
            )
        if self.nprobe < 1:
            raise ValueError(f"nprobe must be >= 1, got {self.nprobe}")
        if self.ann_clusters < 0:
            raise ValueError(
                f"annClusters must be >= 0, got {self.ann_clusters}"
            )


@jax.jit
def _device_all_finite(table):
    return jnp.isfinite(table).all()


def _all_finite(table, rows_at_a_time: int = 1 << 20) -> bool:
    """Whether every entry is finite: a table on the device is tested
    where it lies (a sharded one on its chips), one on the host a block of
    rows at a time, with no whole-table temporary."""
    if isinstance(table, jax.Array):
        return bool(_device_all_finite(table))
    return all(np.isfinite(table[lo:lo + rows_at_a_time]).all()
               for lo in range(0, len(table), rows_at_a_time))


@dataclass
class ALSModel(DeviceTableMixin):
    """Factor tables + id dictionaries + item metadata for filtering."""

    user_factors: np.ndarray
    item_factors: np.ndarray
    users: Any   # StringIndex
    items: Any   # StringIndex
    item_props: dict[str, dict]
    # the train's snapshot of the items' `categories` as arrays; a model
    # made without one gets it from `item_props` at first use
    # (`categories()`)
    category_index: Optional[CategoryIndex] = None

    def sanity_check(self) -> None:
        if not _all_finite(self.user_factors):
            raise ValueError("user factors contain non-finite values")
        if not _all_finite(self.item_factors):
            raise ValueError("item factors contain non-finite values")

    def sharded_topk_index(self, retrieval: str = "exact",
                           candidate_factor: int = 10):
        """Lazy distributed top-k index (ops/distributed_topk.ShardedTopK):
        item table sharded over the mesh + parity block + sticky shard
        health, built once per model (re)load like the device caches.
        The per-request deadline needs no plumbing — the index reads the
        serving thread's deadline scope on every call.  ``retrieval``
        != "exact" builds per-shard int8 candidate artifacts so each
        ring hop shortlists before the exact fold (pio-scout); the
        first caller's config wins for this model's lifetime (params
        are fixed per deployed algorithm)."""
        idx = getattr(self, "_sharded_topk", None)
        if idx is None:
            from ..ops.distributed_topk import ShardedTopK
            from ..parallel import make_mesh

            idx = ShardedTopK(self.item_factors, make_mesh(),
                              retrieval=retrieval,
                              candidate_factor=candidate_factor)
            self._sharded_topk = idx
        return idx



class ALSAlgorithm(Algorithm):
    """MLlib-ALS-equivalent on TPU
    (reference template `ALSAlgorithm.scala` train ~:24-77, predict :79-105)."""

    params_class = ALSAlgorithmParams
    placement = ModelPlacement.DEVICE_SHARDED

    def _config(self) -> ALSConfig:
        p: ALSAlgorithmParams = self.params
        return ALSConfig(
            rank=p.rank,
            num_iterations=p.num_iterations,
            lam=p.lam,
            seed=p.seed,
            implicit=p.implicit,
            alpha=p.alpha,
            weighted_lambda=p.weighted_lambda,
            gather_mode=p.gather_mode,
            solver=p.solver,
            solver_mode=p.solver_mode,
            subspace_size=p.subspace_size,
            factor_placement=p.factor_placement,
            coded_shards=p.coded_shards,
            retrieval=p.retrieval,
            candidate_factor=p.candidate_factor,
            nprobe=p.nprobe,
        )

    def _serve_dtype(self):
        dt = getattr(self.params, "serving_dtype", "float32")
        return None if dt in ("float32", "", None) else dt

    def _retrieval_config(self):
        """The pio-scout two-stage config, or None when this algorithm
        serves exact (the default) — call sites dispatch on None so
        the exact hot path pays nothing for the feature existing."""
        p = self.params
        mode = getattr(p, "retrieval", "exact")
        if mode in ("exact", "", None):
            return None
        from ..retrieval import RetrievalConfig

        return RetrievalConfig(
            mode=mode,
            candidate_factor=getattr(p, "candidate_factor", 10),
            nprobe=getattr(p, "nprobe", 8),
            clusters=getattr(p, "ann_clusters", 0),
        )

    def _sharded_index(self, model: "ALSModel"):
        p = self.params
        return model.sharded_topk_index(
            retrieval=getattr(p, "retrieval", "exact"),
            candidate_factor=getattr(p, "candidate_factor", 10),
        )

    def train(self, ctx: WorkflowContext, data: TrainingData) -> ALSModel:
        cfg = self._config()
        if getattr(data, "coo_local", False):
            # the DataSource kept each process's shard local (coo:
            # "local"): exchange triples straight to each row's owning
            # device — the full COO never exists anywhere
            if cfg.factor_placement != "sharded":
                raise ValueError(
                    "datasource coo='local' requires the algorithm side "
                    "to set factorPlacement='sharded' (the sharded-COO "
                    "layout); 'replicated' needs the gathered read"
                )
            from ..models.als import ALSTrainer

            trainer = ALSTrainer.distributed(
                data.ratings, cfg=cfg, mesh=ctx.mesh,
                exchange_dir=ctx.storage.model_data_dir() / "_ingest",
                tag="als-coo",
            )
            factors = trainer.train()
        else:
            factors = train_als(data.ratings, cfg=cfg, mesh=ctx.mesh)
        return ALSModel(
            user_factors=factors.user_factors,
            item_factors=factors.item_factors,
            users=data.ratings.users,
            items=data.ratings.items,
            item_props=data.items,
            category_index=CategoryIndex.from_props(
                data.ratings.items, data.items),
        )

    # -- serving ----------------------------------------------------------
    def warmup(self, model: ALSModel, max_batch: int = 64) -> None:
        """Compile the batched top-k scorers before the first real
        query.  Every request routes through :meth:`batch_predict` — a
        lone one as a batch of one, with or without a batcher — whose
        executable key space is bounded to (pow2 B) x (pow2 k) x
        (filter) by the shape-stability contract there.  This warms
        every pow2 B the batcher's padding can dispatch up to
        ``max_batch`` at the pow2-rounded default num (k=16); remaining
        shapes compile once under load and land in the persistent
        compilation cache."""
        n = len(model.items)
        if n == 0:
            return
        shapes = warm_shapes(max_batch, n)
        if getattr(self.params, "distributed_topk", False):
            # the sharded index alone: the one-chip table is never made
            # (under this knob the item table may be one no chip holds).
            # It compiles every program it can dispatch (clean or
            # quantized, and parity-coded) per (batch, k), so neither a
            # first degradation nor a first burst pays a mid-request
            # compile; a filtered query, which keeps the local scorer,
            # compiles at its first batch
            idx = self._sharded_index(model)
            for b, k in shapes:
                idx.warm(k, batch=b)
            return
        table = model.device_item_factors(self._serve_dtype())
        rank = model.item_factors.shape[1]
        warm_batched_topk(
            table, rank, n, unmasked_too=True, max_batch=max_batch,
            table_t=model.device_item_tables(self._serve_dtype()),
            category_model=model,
        )
        rcfg = self._retrieval_config()
        if rcfg is not None:
            # pio-scout: the candidate + rerank executables, at the
            # same shapes
            idx = model.device_ann_index(rcfg)
            for b, k in shapes:
                idx.warm(k, [b], table)

    def predict(self, model: ALSModel, query: Query) -> PredictedResult:
        """A lone request is a one-row batch: the same device program,
        the same filters as data."""
        return self.batch_predict(model, [query])[0]

    def batch_predict(self, model: ALSModel, queries: Sequence[Query]):
        """THE scoring path (serving, batched or lone, and eval): ONE
        batched scorer call for all queries, each under its own filters.

        Shape stability contract: the device call's batch size is
        ``len(queries)`` regardless of how many queries are valid —
        invalid ones (unknown user, num<=0) score a harmless row-0
        duplicate that is discarded on the host.  Dropping them would
        make the device batch size data-dependent, defeating the
        serving micro-batcher's pow2 padding (every valid-count would
        compile its own XLA executable mid-traffic).  ``k`` is likewise
        rounded up to the next power of two, so the executable key
        space is (pow2 B) x (pow2 k) x (masked?)."""
        out: list[PredictedResult] = [
            PredictedResult(item_scores=()) for _ in queries
        ]
        # the pio.turn.* scopes are the batch dispatcher's turn segments
        # (obs/timeline.Turn); outside a turn they only name the step in
        # a profiler trace
        with annotate("pio.turn.prepare"):
            uix = np.array(
                [model.users.get(q.user) for q in queries], dtype=np.int64
            )
            nums = np.array([q.num for q in queries], dtype=np.int64)
            valid = (uix >= 0) & (nums > 0)
            if not valid.any():
                return out
            n_items = len(model.items)
            k = min(pow2_ceil(int(nums[valid].max())), n_items)
            uvecs = model.user_factors[np.where(valid, uix, 0)]
            # filters as data: a blackList rides as item ids, `categories`
            # as numbers of the model's category index, both applied on
            # the device; only a `whiteList` makes the batch's [B, M]
            # mask (_common.batch_filter)
            flt = batch_filter(model.items, model.serving_categories(), [
                RowFilter(q.categories, q.whitelist, q.blacklist)
                if v and (q.categories or q.whitelist or q.blacklist)
                else None for q, v in zip(queries, valid)
            ])
            unfiltered = flt.kind == "none"
            rcfg = self._retrieval_config()
        if unfiltered and getattr(self.params, "distributed_topk",
                                  False):
            # every chip scans its own shard for the [B, R] query block;
            # per-query filters keep the local scorer below
            with annotate("pio.turn.dispatch", filter=flt.kind,
                          path="sharded"):
                vals, ixs = self._sharded_index(model)(uvecs, k)
        elif unfiltered and rcfg is not None:
            # pio-scout two-stage: the batched serving path is exactly
            # where the candidate stage pays — per-batch device work
            # drops from O(M*R) f32 to a quantized shortlist scan +
            # O(candidate_factor*k*R) exact rerank
            vals, ixs = model.device_ann_index(rcfg).search(
                uvecs, k, model.device_item_factors(self._serve_dtype())
            )
        else:
            # the pre-transposed [R, M] table with the packed rows: a
            # batch over a long catalogue, with or without excluded ids,
            # is scored in blocks of the item axis (one read of the
            # table, no [B, M] matrix; exact), anything else as one
            # matmul + top-k (ops/topk.py)
            tables = model.device_item_tables(self._serve_dtype())
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

    def predict_rating(self, model: ALSModel, user: str, item: str) -> float:
        """Point prediction for RMSE-style evaluation."""
        u = model.users.get(user)
        i = model.items.get(item)
        if u < 0 or i < 0:
            return float("nan")
        return float(model.user_factors[u] @ model.item_factors[i])


# --------------------------------------------------------------------------
# Engine factory
# --------------------------------------------------------------------------


class RecommendationServing(FirstServing):
    pass


def _validate_rec_params(ep) -> None:
    """Cross-component coupling: datasource ``coo: "local"`` hands each
    ALS algorithm a process-local shard, which only the sharded-COO
    layout can train — catch the mismatch at config time, not after a
    multi-host ingest."""
    ds = ep.data_source[1]
    if getattr(ds, "coo", "gathered") != "local":
        return
    bad = [
        name or "als"
        for name, p in ep.algorithms
        if getattr(p, "factor_placement", None) != "sharded"
    ]
    if bad:
        raise ValueError(
            "datasource coo='local' requires factorPlacement='sharded' "
            f"on every algorithm; offending: {bad} — 'replicated' "
            "placement needs the gathered read (coo='gathered')"
        )


def recommendation_engine() -> Engine:
    """`EngineFactory` analogue for the recommendation template."""
    return Engine(
        RecommendationDataSource,
        IdentityPreparator,
        {"als": ALSAlgorithm, "": ALSAlgorithm},
        RecommendationServing,
        params_validator=_validate_rec_params,
    )


# --------------------------------------------------------------------------
# Evaluation (the BASELINE.json "e2 evaluation workflow" config:
# k-fold MetricEvaluator over the recommendation engine)
# --------------------------------------------------------------------------


class RatingAlgorithm(ALSAlgorithm):
    """ALS variant whose predictions are point rating estimates — used by the
    RMSE evaluation where queries carry ``num=0`` and the actual is an
    :class:`ActualRating`."""

    def batch_predict(self, model: ALSModel, queries: Sequence[Query]):
        # during eval the actuals carry the item; the prediction for (user,
        # item) is the factor dot product.  We return the full user vector
        # index per query; the metric resolves the item side.
        return [RatingPrediction(model=model, user=q.user) for q in queries]

    def predict(self, model: ALSModel, query: Query):
        return RatingPrediction(model=model, user=query.user)


@dataclass
class RatingPrediction:
    model: ALSModel
    user: str


class RMSEMetric:
    """Root-mean-squared error over held-out ratings (lower is better).

    Works with :class:`RatingAlgorithm` predictions + :class:`ActualRating`
    actuals from ``read_eval``."""

    header = "RMSE"

    def calculate(self, ctx, data) -> float:
        sq, n = 0.0, 0
        for _, qpa in data:
            if not qpa:
                continue
            # one model per eval set: vectorize the gathers + dot products
            model = qpa[0][1].model
            u = model.users.encode([p.user for _, p, _ in qpa])
            i = model.items.encode([a.item for _, _, a in qpa])
            r = np.asarray([a.rating for _, _, a in qpa], dtype=np.float64)
            ok = (u >= 0) & (i >= 0)
            if not ok.any():
                continue
            pred = np.einsum(
                "nr,nr->n",
                model.user_factors[u[ok]],
                model.item_factors[i[ok]],
            )
            sq += float(((pred - r[ok]) ** 2).sum())
            n += int(ok.sum())
        return float(np.sqrt(sq / n)) if n else float("nan")

    def compare(self, a: float, b: float) -> int:
        if a == b:
            return 0
        return 1 if a < b else -1  # lower RMSE wins


def recommendation_evaluation():
    """Evaluation binding for sweeps over ALS hyperparameters.  Fold count
    comes from each candidate's ``DataSourceParams.eval_k``."""
    from ..controller import Evaluation

    engine = Engine(
        RecommendationDataSource,
        IdentityPreparator,
        {"als": RatingAlgorithm, "": RatingAlgorithm},
        RecommendationServing,
    )
    return Evaluation(engine, RMSEMetric())


# --------------------------------------------------------------------------
# pio-forge registration: ONE declaration lights up `pio-tpu engines
# list/describe`, `--engine recommendation` dispatch, the template
# gallery entry, obs/tower engine labels, tenancy manifests, and the
# registry conformance suite (tests/test_engine_conformance.py)
# --------------------------------------------------------------------------


def _conformance_events():
    from ..storage import DataMap, Event

    events = []
    for u in range(8):
        for j in range(4):
            i = (u + j * 3) % 10
            events.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap({"rating": float((u + i) % 5 + 1)}),
            ))
    for j in range(10):
        events.append(Event(
            event="$set", entity_type="item", entity_id=f"i{j}",
            properties=DataMap(
                {"categories": ["even" if j % 2 == 0 else "odd"]}),
        ))
    return events


from ..engines import ConformanceFixture, engine_spec  # noqa: E402

recommendation_engine = engine_spec(
    "recommendation",
    description=(
        "Personalized recommendation via block-ALS on TPU "
        "(scala-parallel-recommendation analogue)"
    ),
    default_params={
        "datasource": {
            "params": {"appName": "MyApp", "eventNames": ["rate", "buy"]}
        },
        "algorithms": [
            {
                "name": "als",
                "params": {"rank": 10, "numIterations": 20,
                           "lambda": 0.01, "seed": 3},
            }
        ],
    },
    query_example={"user": "1", "num": 4},
    evaluation=recommendation_evaluation,
    conformance=ConformanceFixture(
        app_name="forge-conf",
        seed_events=_conformance_events,
        queries=({"user": "u1", "num": 3},),
        check=lambda r: len(r.get("itemScores", [])) >= 1,
        variant={
            # evalK 2: the conformance suite's eval step runs a REAL
            # 2-fold read_eval for this engine (the others exercise
            # eval dispatch with an empty set)
            "datasource": {"params": {"appName": "forge-conf",
                                      "eventNames": ["rate"],
                                      "evalK": 2}},
            "algorithms": [
                {"name": "als",
                 "params": {"rank": 4, "numIterations": 3,
                            "lambda": 0.1, "seed": 1}}
            ],
        },
    ),
)(recommendation_engine)
