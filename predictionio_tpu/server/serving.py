"""Engine deployment server: answers ``/queries.json`` with predictions.

Re-expression of reference `workflow/CreateServer.scala` (`ServerActor`
routes `:433-612`, `MasterActor` lifecycle `:255-377`) on the selector
event loop of `server/eventloop.py` — no spray/akka.  Routes:

* ``GET  /``             — status JSON: engine info, request count, latency
  (``avgServingSec``/``lastServingSec`` parity, `CreateServer.scala:552-559`)
* ``POST /queries.json`` — score a query (the hot path)
* ``GET  /reload``       — hot-swap to the latest COMPLETED engine instance
  without restarting the process (`:315-336,592-599`)
* ``POST /stop``         — graceful shutdown (`:600-607`)

Query/result JSON mapping: the engine's first algorithm may declare
``query_class`` (with ``from_json``) and results may expose ``to_json`` —
the serving-layer analogue of the reference's json4s ``Extraction.extract``
(`:470-471`).  Scoring runs a precompiled batched XLA call per request;
feedback-loop event injection (prId) is wired when an event server URL is
configured.
"""

from __future__ import annotations

import json
import logging
import threading
import time
import urllib.parse
import uuid
from dataclasses import is_dataclass, asdict
from typing import Any, Callable, Optional

from ..controller.base import WorkflowContext
from .http_base import HTTPServerBase, observability_response
from .eventloop import callback_scope
from .microbatch import AdmissionRejected
from ..controller.engine import Engine, EngineParams
from ..obs import (
    ENGINE_QUERIES_TOTAL,
    FOLDIN_APPLIES_TOTAL,
    FOLDIN_PHASE_SECONDS,
    FOLDIN_WATERMARK_LAG,
    MODEL_FRESHNESS_SECONDS,
    QUERIES_TOTAL,
    QUERY_LATENCY,
    RELOADS_TOTAL,
    TRACE_HEADER,
    Histogram,
    current_trace_id,
    gcpause,
    get_flight_recorder,
    get_tracer,
    new_trace_id,
    scope,
    timeline,
    trace_scope,
    xray,
)
from ..obs.timeline import SERVE_INFLIGHT, annotate
from ..resilience import faults
from ..resilience.delivery import DeliveryQueue
from ..resilience.policy import (
    CircuitBreaker,
    Deadline,
    DeadlineExceeded,
    RetryPolicy,
    deadline_scope,
)
from ..tenancy.errors import QuotaExceeded, TenantUnavailable
from ..workflow.train import prepare_deploy_components

logger = logging.getLogger(__name__)

__all__ = ["EngineServer", "ServerConfig"]

# pulse: the serving edge's saturation gauge, child cached at import
# (labels()/child() lookups are too hot for the per-request path)
_m_inflight = SERVE_INFLIGHT.child()


class ServerConfig:
    def __init__(self, host: str = "127.0.0.1", port: int = 8000,
                 feedback: bool = False, event_server_url: Optional[str] = None,
                 access_key: Optional[str] = None,
                 log_url: Optional[str] = None, log_prefix: str = "",
                 microbatch: str = "auto", microbatch_max: int = 64,
                 query_timeout_s: Optional[float] = None,
                 feedback_capacity: int = 1024,
                 delivery_attempts: int = 50,
                 delivery_base_s: float = 0.1,
                 delivery_cap_s: float = 5.0,
                 delivery_timeout_s: float = 2.0,
                 breaker_failures: int = 5,
                 breaker_reset_s: float = 10.0,
                 retry_seed: Optional[int] = None,
                 foldin_poll_s: Optional[float] = None,
                 max_connections: int = 512,
                 slo_ms: Optional[float] = None):
        self.host = host
        self.port = port
        # concurrent-connection cap: connection attempts past it are
        # answered a structured 503 and closed, so a slow-loris client
        # can't pin unbounded sockets
        self.max_connections = max_connections
        self.feedback = feedback
        self.event_server_url = event_server_url
        self.access_key = access_key
        # remote error-log shipping (CreateServer.scala:413-424): serving
        # failures POST `log_prefix + json` to log_url, fire-and-forget
        self.log_url = log_url
        self.log_prefix = log_prefix
        # concurrent-query coalescing (server/microbatch.py): "auto"
        # batches when every algorithm provides a real batch_predict,
        # "on" forces it, "off" keeps per-request device dispatch
        self.microbatch = microbatch
        self.microbatch_max = microbatch_max
        # per-request time budget (None = unbounded, the pre-resilience
        # behavior); expiry answers a structured 503 instead of queueing
        # device work for a client that already gave up
        self.query_timeout_s = query_timeout_s
        # feedback/remote-log delivery queue + breaker knobs
        self.feedback_capacity = feedback_capacity
        self.delivery_attempts = delivery_attempts
        self.delivery_base_s = delivery_base_s
        self.delivery_cap_s = delivery_cap_s
        self.delivery_timeout_s = delivery_timeout_s
        self.breaker_failures = breaker_failures
        self.breaker_reset_s = breaker_reset_s
        self.retry_seed = retry_seed
        # pio-live: poll the model dir for fold-in delta links every N
        # seconds and patch them into the serving model in place (no
        # stop-the-world reload).  None = off; deltas already on disk
        # at (re)load time are still caught up once.
        self.foldin_poll_s = foldin_poll_s
        # pio-lens: latency SLO in milliseconds — arms the
        # pio_slo_burn_rate{window} gauges on this server's end-to-end
        # latency histogram (None = no SLO, gauges stay absent)
        self.slo_ms = slo_ms


class _QueryCtx:
    """Per-query snapshot shared by the blocking and event-loop paths:
    decoded query, deadline, the components captured under the state
    lock, the pio-live attribution fields, and (pio-hive) the tenant
    lease the query holds."""

    __slots__ = ("query", "deadline", "algorithms", "models", "serving",
                 "batcher", "freshness", "foldin_seq", "lease")

    def __init__(self, query, deadline, algorithms, models, serving,
                 batcher, freshness, foldin_seq, lease=None):
        self.query = query
        self.deadline = deadline
        self.algorithms = algorithms
        self.models = models
        self.serving = serving
        self.batcher = batcher
        self.freshness = freshness
        self.foldin_seq = foldin_seq
        self.lease = lease


def _lease_status(e: BaseException) -> str:
    """Map a query-path exception to the per-tenant outcome label (the
    same taxonomy the HTTP error mapping uses)."""
    if isinstance(e, QuotaExceeded):
        return "quota"
    if isinstance(e, TenantUnavailable):
        return "shed"
    if isinstance(e, AdmissionRejected):
        return "rejected"
    if isinstance(e, DeadlineExceeded):
        return "timeout"
    if isinstance(e, (KeyError, ValueError, TypeError)):
        return "bad_request"
    return "error"


def _takes_max_batch(fn: Callable) -> bool:
    """Whether a warmup hook accepts the ``max_batch`` keyword (older
    third-party algorithms may still have the one-arg signature).
    Hooks taking ``**kwargs`` (or whose visible signature is erased by
    a plain decorator) count as accepting it."""
    import inspect

    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    return "max_batch" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


def _warm_signature(algo, model, warm_max: int) -> Optional[tuple]:
    """Shape signature of one (algorithm, model) warmup obligation —
    two tenants with equal signatures compile the SAME pow2 executable
    ladder (jit caches key on function identity + abstract shapes), so
    the second tenant's full-ladder warmup would be pure cache hits.
    None (unrecognizable model) means "never share"."""
    try:
        fields = vars(model)
    except TypeError:
        return None
    shapes = []
    for name in sorted(fields):
        v = fields[name]
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is not None and dtype is not None:
            shapes.append((name, tuple(shape), str(dtype)))
    if not shapes:
        return None
    try:
        params_repr = repr(getattr(algo, "params", None))
    except Exception:
        params_repr = "?"
    return (type(algo).__module__, type(algo).__qualname__,
            params_repr, warm_max, tuple(shapes))


def _warm_components(algorithms, models, warm_max: int,
                     seen: Optional[set] = None) -> None:
    """Run each algorithm's warmup ladder (shared by the engine
    server's own ``_load`` and the pio-hive tenant loader — a lazily
    loaded tenant gets the exact same compile obligations a deployed
    single model does).  A warmup failure only costs the first query a
    compile; it never fails the load.

    ``seen`` (pio-confluence) shares the ladder across co-shaped
    tenants: the FIRST (algo, model) with a given shape signature
    warms the full pow2 ladder; later co-shaped ones warm only
    ``max_batch=1`` — enough to materialize their own per-model device
    arrays, while every batched executable comes out of the jit cache
    the first tenant already filled.  A concurrent double-warm is a
    benign race (both warm fully), so ``seen`` needs no lock."""
    for algo, model in zip(algorithms, models):
        algo_max = warm_max
        sig = _warm_signature(algo, model, warm_max) if seen is not None \
            else None
        if sig is not None and sig in seen:
            algo_max = 1
        t0 = time.perf_counter()
        try:
            # pass the batcher's real maximum so the warmup ladder
            # covers every pow2 size the padding can dispatch; algos
            # with the pre-max_batch one-arg signature still work
            if _takes_max_batch(algo.warmup):
                try:
                    algo.warmup(model, max_batch=algo_max)
                except TypeError:
                    # a decorator-erased signature (*args/**kwargs
                    # wrapper around an old one-arg hook) can lie
                    # about accepting max_batch; retry plain once
                    # rather than regress a hook that warmed fine
                    # before max_batch existed
                    algo.warmup(model)
            else:
                algo.warmup(model)
        except Exception:
            logger.exception(
                "warmup failed for %s (first query will compile)",
                type(algo).__name__,
            )
        else:
            if sig is not None:
                seen.add(sig)
            dt = time.perf_counter() - t0
            if dt > 0.05:
                logger.info("%s warmed up in %.2fs",
                            type(algo).__name__, dt)


def _default_query_decoder(engine: Engine, engine_params: EngineParams):
    name, _ = engine_params.algorithms[0]
    cls = engine._lookup(engine.algorithm_class_map, name, "algorithm")
    qcls = getattr(cls, "query_class", None)
    if qcls is None:
        # try the template convention: module-level Query with from_json
        import sys

        mod = sys.modules.get(cls.__module__)
        qcls = getattr(mod, "Query", None) if mod else None
    if qcls is not None and hasattr(qcls, "from_json"):
        return qcls.from_json
    if qcls is not None and is_dataclass(qcls):
        # plain dataclass Query without from_json: construct it from the
        # matching JSON fields (the generic analogue of the reference's
        # json4s ``Extraction.extract`` into case classes,
        # `CreateServer.scala:470-471`); unknown keys are ignored
        import dataclasses

        names = {f.name for f in dataclasses.fields(qcls)}

        def decode(d):
            return qcls(**{k: v for k, v in d.items() if k in names})

        return decode
    return lambda d: d


def _result_to_json(r: Any) -> Any:
    if hasattr(r, "to_json"):
        return r.to_json()
    if is_dataclass(r) and not isinstance(r, type):
        return asdict(r)
    if isinstance(r, (list, tuple)):
        return [_result_to_json(v) for v in r]
    if isinstance(r, dict):
        return {k: _result_to_json(v) for k, v in r.items()}
    return r


def _experiments_response(tenants) -> tuple:
    """``GET /debug/experiments``: the autopilot's live document, a
    disabled stub when tenancy runs without an autopilot, 404 when
    there is no tenancy at all.  Returns ``(code, payload)``."""
    if tenants is None:
        from ..tenancy.autopilot import autopilot_payload

        doc = autopilot_payload()
        if doc is not None:
            return 200, doc
        return 404, {"message": "tenancy is not enabled (deploy --multi)"}
    pilot = getattr(tenants, "autopilot", None)
    if pilot is not None:
        return 200, pilot.payload()
    return 200, {
        "enabled": False,
        "weights": {
            app: tenants.experiment(app).weights()
            for app in tenants.apps()
        },
        "onlineEval": tenants.online.snapshot(),
    }


class EngineServer(HTTPServerBase):
    """One deployed engine instance behind an HTTP server."""

    def __init__(
        self,
        engine: Engine,
        engine_params: EngineParams,
        instance_id: str,
        ctx: Optional[WorkflowContext] = None,
        config: Optional[ServerConfig] = None,
        query_decoder: Optional[Callable[[dict], Any]] = None,
        engine_id: str = "default",
        engine_version: str = "1",
        engine_variant: str = "engine.json",
        tenants=None,
    ):
        self.engine = engine
        self.engine_params = engine_params
        self.ctx = ctx or WorkflowContext(mode="Serving")
        self.config = config or ServerConfig()
        self.instance_id = instance_id
        self.engine_id = engine_id
        self.engine_version = engine_version
        self.engine_variant = engine_variant
        # pio-hive: an optional TenantRegistry turns this server into a
        # multi-tenant host — queries carrying app/appId/accessKey (+
        # optional variant) route to the registry's resident models,
        # everything else rides the anchor components loaded below.
        # The registry gets this server's component loader unless the
        # caller injected its own (benches/tests pass prebuilt models).
        self.tenants = tenants
        if tenants is not None and tenants.loader is None:
            tenants.loader = self._tenant_loader
        self.query_decoder = query_decoder or _default_query_decoder(
            engine, engine_params
        )
        self._lock = threading.RLock()
        self.last_reload_error: Optional[str] = None
        # bounded background delivery (resilience/delivery.py) replaces
        # the old thread-per-request fire-and-forget POSTs; built even
        # when feedback/log_url are off (the drain thread only starts on
        # first submit) so post-init config changes keep working
        def _queue(name, point):
            return DeliveryQueue(
                name,
                capacity=self.config.feedback_capacity,
                retry=RetryPolicy(
                    max_attempts=self.config.delivery_attempts,
                    base_s=self.config.delivery_base_s,
                    cap_s=self.config.delivery_cap_s,
                    seed=self.config.retry_seed,
                ),
                breaker=CircuitBreaker(
                    failure_threshold=self.config.breaker_failures,
                    reset_timeout_s=self.config.breaker_reset_s,
                ),
                timeout_s=self.config.delivery_timeout_s,
                fault_point=point,
            )

        self._feedback_queue = _queue("feedback", "http.feedback")
        self._log_queue = _queue("remote-log", "http.remote_log")
        # pio-live delta-poll machinery, built before the first _load
        # (which catches up on any chain already on disk): repeated
        # apply failures open the breaker — polling pauses, the stale
        # model keeps serving, exactly the failed-/reload semantics
        self._foldin_breaker = CircuitBreaker(
            failure_threshold=self.config.breaker_failures,
            reset_timeout_s=self.config.breaker_reset_s,
        )
        # pio-surge admission breaker: consecutive deadline-admission
        # rejects open it, and while open every deadlined request is
        # shed immediately (no estimator math) — the cheap-shed mode an
        # overloaded edge needs; any completed query closes it again
        self._admission_breaker = CircuitBreaker(
            failure_threshold=max(self.config.breaker_failures * 4, 8),
            reset_timeout_s=min(self.config.breaker_reset_s, 1.0),
        )
        # aux pool for the blocking routes (status, reload,
        # /debug/profile, fold-in apply, unbatched predicts); built
        # lazily at first bind
        self._aux_pool = None
        # the server's one shared batcher core (built lazily by the
        # first _make_batcher call that batches at all) plus
        # the warmup-ladder signature set — co-shaped tenant models
        # share one compile per pow2 batch shape instead of re-warming
        # the full ladder per tenant
        self._shared_core = None
        self._shared_lock = threading.Lock()
        self._warm_signatures: set = set()
        self._foldin_stop = threading.Event()
        self._load(instance_id)
        if self.config.foldin_poll_s:
            threading.Thread(
                target=self._foldin_poll_loop,
                daemon=True,
                name="foldin-poll",
            ).start()
        # pio-hive: the online-eval poller folds variant-attributed
        # conversion events back out of the event store on a cadence
        self._eval_stop = threading.Event()
        if self.tenants is not None:
            threading.Thread(
                target=self._online_eval_loop,
                daemon=True,
                name="hive-eval",
            ).start()
        # serving stats (CreateServer.scala:396-398).  Latency is
        # histogram-backed (pio-obs): this instance's private histogram
        # drives the /status percentiles + average, and the same deltas
        # feed the process-wide pio_query_latency_seconds family that
        # /metrics exposes — one measurement, two views.
        self.request_count = 0
        self.last_serving_sec = 0.0
        self.start_time = time.time()  # wall clock: a TIMESTAMP, not a span
        self._latency = Histogram()
        self._m_latency = QUERY_LATENCY.child()
        # per-outcome query counters resolved once (.labels() is too
        # hot for per-request use)
        self._m_queries = {
            s: QUERIES_TOTAL.labels(status=s)
            for s in ("ok", "bad_request", "timeout", "error", "rejected")
        }
        # pio-forge: the engine-labeled mirror — every query books
        # {engine=<registered spec name>} so multi-engine fleets (and
        # the conformance suite) read per-engine traffic off /metrics
        from ..engines import engine_label_of

        self.engine_name = engine_label_of(engine, fallback=engine_id)
        self._m_engine_queries = {
            s: ENGINE_QUERIES_TOTAL.labels(engine=self.engine_name,
                                           status=s)
            for s in ("ok", "bad_request", "timeout", "error",
                      "rejected", "quota", "shed")
        }
        self._httpd = None  # EventLoopHTTPServer once bound
        # pio-lens: --slo-ms arms the error-budget burn-rate gauges on
        # the process-wide latency histogram (the replica-side half of
        # the fleet's alert-ready signal; the router arms its own on
        # the forward round-trip histogram)
        self._burn = None
        if self.config.slo_ms:
            from ..obs import fleet

            self._burn = fleet.install_burn_rate(
                self._m_latency, self.config.slo_ms / 1e3
            )
        # pio-xray: compile/cache events during warmup+serving book into
        # /metrics, and the daemon device sampler keeps the per-device
        # memory gauges fresh (registered like the breaker gauges above)
        xray.install()
        xray.start_sampler()
        gcpause.install()
        # pio-scope: the always-on CPU sampler rides every serving
        # process (no-op when --no-profiler / PIO_TPU_SCOPE=0 opted out)
        scope.ensure_started()

    # -- lifecycle --------------------------------------------------------
    def _load(self, instance_id: str) -> None:
        # a failed (re)load must leave the previous components serving —
        # nothing below mutates server state until the atomic swap at
        # the end, and the injection point lets chaos tests prove it
        faults.check("reload.load_model")
        # serve with the params the instance was trained with; the current
        # engine.json may have drifted (engineInstanceToEngineParams parity)
        with self._lock:
            variant_params = self.engine_params
        engine_params = variant_params
        rec = self.ctx.storage.get_metadata().engine_instance_get(instance_id)
        if rec is not None and rec.algorithms_params:
            try:
                engine_params = self.engine.params_from_instance(rec)
            except Exception:
                logger.exception(
                    "could not reconstruct params from instance %s; "
                    "using variant params", instance_id,
                )
                engine_params = variant_params
        algorithms, models, serving = prepare_deploy_components(
            self.engine, engine_params, instance_id, ctx=self.ctx
        )
        # the batcher decides which batch sizes serving can dispatch, so
        # build it BEFORE warmup: with batching off (or auto-gated off)
        # every request runs B=1 and compiling the batched ladder at
        # deploy/reload time would be pure wasted XLA work
        batcher = self._make_batcher(algorithms, models)
        # 0 = "no batched path at all" (empty warmup ladder); a real
        # batcher with microbatch_max=1 still needs its B=1 shapes
        warm_max = self.config.microbatch_max if batcher is not None else 0
        _warm_components(algorithms, models, warm_max,
                         seen=self._warm_signatures)
        with self._lock:
            old_batcher = getattr(self, "batcher", None)
            self.engine_params = engine_params
            self.models = models
            self.algorithms = algorithms
            self.serving = serving
            self.instance_id = instance_id
            self.batcher = batcher
            # pio-live bookkeeping restarts with every full (re)load:
            # the delta chain is per instance, and a fresh full model
            # IS the freshness anchor
            self.foldin_applied_seq = {}
            self.foldin_watermark = None
            self.foldin_deltas_applied = 0
            self.last_foldin_error = None
            self.model_advanced_mono = time.monotonic()
        # the old batcher's dispatcher thread (continuous path) drains
        # and exits; in-flight queries still holding it complete fine
        if old_batcher is not None and old_batcher is not batcher:
            old_batcher.close()
        # catch up on delta links already published for this instance
        # (a redeploy/reload must not serve staler than the chain)
        self._apply_available_deltas()
        # pio-hive: re-adopt the freshly loaded components as the
        # anchor tenant's runtime (ONE model copy serves both the
        # tenant-less default path and explicit anchor queries; a
        # /reload therefore advances the anchor tenant too)
        if getattr(self, "tenants", None) is not None:
            self._adopt_anchor_runtime()

    # -- pio-hive: tenant component loading --------------------------------
    def _tenant_breaker(self) -> CircuitBreaker:
        return CircuitBreaker(
            failure_threshold=self.config.breaker_failures,
            reset_timeout_s=self.config.breaker_reset_s,
        )

    def _tenant_quota(self, spec):
        from ..tenancy.quota import TokenBucket

        if spec.quota_qps is None:
            return None
        return TokenBucket(spec.quota_qps, spec.quota_burst)

    def _adopt_anchor_runtime(self) -> None:
        from ..tenancy.registry import TenantRuntime

        spec = self.tenants.spec(self.tenants.anchor_key)
        with self._lock:
            rt = TenantRuntime(
                spec, self.engine, self.engine_params, self.instance_id,
                self.algorithms, self.models, self.serving, self.batcher,
                self.query_decoder, self.ctx,
                breaker=self._tenant_breaker(),
                quota=self._tenant_quota(spec),
            )
        self.tenants.adopt_anchor(rt)

    def _resolve_tenant_components(self, spec):
        """(engine, engine_params, instance_id, ctx) for a spec —
        prebuilt objects win, then a registered engine name (pio-forge
        registry dispatch), else the engine.json is loaded; either way
        the latest COMPLETED instance resolves exactly like
        ``deploy``."""
        ctx = spec.ctx or self.ctx
        if spec.engine is not None:
            if spec.instance_id is None:
                raise ValueError(
                    f"tenant {spec.key_str}: a prebuilt engine needs an "
                    "instance_id"
                )
            return spec.engine, spec.engine_params, spec.instance_id, ctx
        if spec.engine_name:
            from .. import engines

            engine, ep, variant = engines.resolve(spec.engine_name)
            variant_key = f"engine:{spec.engine_name}"
        else:
            from ..cli.main import load_engine_from_variant

            engine, ep, variant = load_engine_from_variant(
                spec.engine_json
            )
            variant_key = str(spec.engine_json)
        iid = spec.instance_id
        if iid is None:
            md = ctx.storage.get_metadata()
            latest = md.engine_instance_get_latest_completed(
                variant.get("id", "default"), "1", variant_key
            )
            if latest is None:
                raise LookupError(
                    f"tenant {spec.key_str}: no completed engine "
                    f"instance for {variant_key}; train it first"
                )
            iid = latest.id
        return engine, ep, iid, ctx

    def _tenant_loader(self, spec):
        """Build one tenant's full serving runtime — the same component
        pipeline ``_load`` runs for the anchor: prepare + batcher +
        warmup ladder + decoder, plus the per-tenant breaker/quota."""
        from ..tenancy.registry import TenantRuntime

        engine, ep, iid, ctx = self._resolve_tenant_components(spec)
        algorithms, models, serving = prepare_deploy_components(
            engine, ep, iid, ctx=ctx
        )
        batcher = self._make_batcher(algorithms, models, tenant=spec.key)
        warm_max = self.config.microbatch_max if batcher is not None else 0
        _warm_components(algorithms, models, warm_max,
                         seen=self._warm_signatures)
        return TenantRuntime(
            spec, engine, ep, iid, algorithms, models, serving, batcher,
            _default_query_decoder(engine, ep), ctx,
            breaker=self._tenant_breaker(),
            quota=self._tenant_quota(spec),
        )

    def _online_eval_loop(self) -> None:
        scope.register_thread_role("hive_eval")
        interval = max(float(self.tenants.eval_interval_s), 0.5)
        while not self._eval_stop.wait(interval):
            try:
                self.tenants.refresh_online_eval(
                    self.ctx.storage.get_event_store()
                )
            except Exception:
                logger.exception("online-eval refresh failed")
            # pio-pilot: the autopilot rides the same cadence — fresh
            # conversion counts in, at most one bounded ramp step out
            # (tick() never raises; a no-autopilot registry no-ops)
            try:
                self.tenants.autopilot_tick()
            except Exception:
                logger.exception("autopilot tick failed")

    def _make_batcher(self, algorithms, models, tenant=None):
        """This (algorithms, models) snapshot's view on the server's
        one shared batcher — or None when batching can't help: the one
        batched path and the one unbatched.

        Concurrent requests each dispatching their own device call
        serialize on the single TPU execution queue (measured:
        per-request latency grows ~linearly with thread count at flat
        QPS).  When every algorithm overrides ``batch_predict`` with a
        real batched implementation, coalescing the in-flight queries
        into one [B]-wide device call makes concurrency wider instead
        of deeper — see server/microbatch.py.  The base-class
        ``batch_predict`` just maps ``predict``, which would serialize
        *inside* the dispatcher's turn for no gain, so "auto" only
        batches genuinely batched algorithms.  Whatever the dispatcher
        claims goes to ``batch_predict``, a lone request as a batch of
        one (on the chip the one-row batch is the faster program:
        3.3 ms against 11-12 for a ``[M]`` matvec and a full sort).
        """
        from ..controller.base import Algorithm
        from .microbatch import SharedBatcher, SharedBatcherView

        mode = self.config.microbatch
        if mode == "off":
            return None
        if mode == "auto" and not all(
            type(a).batch_predict is not Algorithm.batch_predict
            for a in algorithms
        ):
            return None

        def batch_fn(queries):
            per_algo = [
                algo.batch_predict(model, queries)
                for algo, model in zip(algorithms, models)
            ]
            return [
                [pa[i] for pa in per_algo] for i in range(len(queries))
            ]

        # every tenant (and the anchor) gets a VIEW on the server's one
        # SharedBatcher — single pending queue, single dispatcher,
        # claim-time weighted deficit round-robin across tenants (plain
        # FIFO while one tenant is pending).  The view carries this
        # snapshot's batch_fn, so entries group by model identity
        # inside a claim and in-flight queries survive a reload on the
        # model they snapshotted.  pad_batches: predicts are pure
        # per-item maps, and padding bounds the per-batch-size XLA
        # executables to log2(max)+1 instead of compiling mid-traffic
        # for every new size
        with self._shared_lock:
            if self._shared_core is None:
                self._shared_core = SharedBatcher(
                    max_batch=self.config.microbatch_max,
                    pad_batches=True,
                )
            core = self._shared_core
        if tenant is None:
            tenant = self.tenants.anchor_key if self.tenants is not None \
                else "__anchor__"
        weight_fn = None
        if self.tenants is not None:
            registry, key = self.tenants, tenant

            def weight_fn():
                # pulled at claim time: a hot POST /tenants/weights
                # reshapes the very next dispatcher claim
                return registry.deficit_weight(key)

        return SharedBatcherView(core, tenant, batch_fn,
                                 weight_fn=weight_fn)

    def reload(self) -> str:
        """Swap in the latest COMPLETED instance (GET /reload).

        A failed load is recorded (``lastReloadError`` in the status
        JSON) and re-raised; the previously-loaded components keep
        serving untouched — stale answers beat no answers."""
        md = self.ctx.storage.get_metadata()
        latest = md.engine_instance_get_latest_completed(
            self.engine_id, self.engine_version, self.engine_variant
        )
        if latest is None:
            raise LookupError("no completed engine instance found")
        with get_tracer().span("serve.reload",
                               attrs={"instance": latest.id}):
            try:
                self._load(latest.id)
            except Exception as e:
                with self._lock:
                    self.last_reload_error = f"{type(e).__name__}: {e}"
                RELOADS_TOTAL.labels(result="error").inc()
                raise
        with self._lock:
            self.last_reload_error = None
        RELOADS_TOTAL.labels(result="ok").inc()
        return latest.id

    # -- pio-live delta apply ---------------------------------------------
    def _apply_available_deltas(self) -> int:
        """Apply any fold-in delta links (pio-live) newer than what this
        server already holds, IN PLACE under the state lock — factor
        rows and the device-resident top-k index are patched row-wise;
        queries in flight keep scoring on the tables they snapshotted,
        the next query sees the folded-in rows.  No ``reload()``, no
        warmup, no batcher rebuild: the model OBJECTS stay the same,
        only their row contents advance.

        A torn or gapped chain truncates cleanly (``load_model_delta_
        chain``): the good prefix applies, the rest waits — stale rows
        beat corrupted rows.  Returns the number of links applied."""
        from ..live.apply import apply_model_delta, model_supports_deltas
        from ..workflow.model_io import load_model_delta_chain, model_key

        with self._lock:
            iid = self.instance_id
            models = self.models
            ep = self.engine_params
            applied_seq = dict(self.foldin_applied_seq)
        base_dir = self.ctx.storage.model_data_dir() / iid
        names = [n for n, _ in ep.algorithms]
        n_applied = 0
        for ax, (name, model) in enumerate(zip(names, models)):
            if not model_supports_deltas(model):
                continue
            key = model_key(iid, ax, name)
            chain, err = load_model_delta_chain(
                base_dir, key, after_seq=applied_seq.get(key, 0)
            )
            if err:
                with self._lock:
                    self.last_foldin_error = err
                logger.warning("fold-in chain for %s: %s", key, err)
            for d in chain:
                t0 = time.perf_counter()
                with self._lock:
                    if self.instance_id != iid:
                        # a reload swapped instances mid-walk; the new
                        # instance's own catch-up already ran
                        return n_applied
                    apply_model_delta(model, d)
                    self.foldin_applied_seq[key] = d.seq
                    self.foldin_watermark = d.watermark
                    self.foldin_deltas_applied += 1
                    self.model_advanced_mono = time.monotonic()
                    self.last_foldin_error = None
                dt = time.perf_counter() - t0
                FOLDIN_APPLIES_TOTAL.labels(result="ok").inc()
                FOLDIN_PHASE_SECONDS.labels(phase="live.apply").observe(dt)
                get_tracer().record(
                    "live.apply", dt,
                    attrs={"instance": iid, "seq": d.seq},
                )
                n_applied += 1
        return n_applied

    def _foldin_poll_loop(self) -> None:
        """Delta-poll daemon thread (``--foldin-poll``): breaker-guarded
        and deadline-scoped so a sick storage volume degrades to a
        paused poll + stale model, never a wedged serving thread."""
        scope.register_thread_role("foldin_runner")
        interval = float(self.config.foldin_poll_s)
        while not self._foldin_stop.wait(interval):
            if not self._foldin_breaker.allow():
                continue
            try:
                with deadline_scope(Deadline.after(max(interval, 1.0))):
                    self._apply_available_deltas()
                    if self.tenants is not None:
                        # per-tenant chains; one tenant's error is
                        # booked on that tenant inside the registry and
                        # never pauses the others (the fold-in half of
                        # the isolation contract)
                        self.tenants.apply_available_deltas()
            except Exception as e:
                logger.exception(
                    "fold-in delta apply failed; serving keeps the "
                    "stale model"
                )
                with self._lock:
                    self.last_foldin_error = f"{type(e).__name__}: {e}"
                FOLDIN_APPLIES_TOTAL.labels(result="error").inc()
                self._foldin_breaker.record_failure()
            else:
                self._foldin_breaker.record_success()
            self._refresh_foldin_gauges()

    def _foldin_status(self) -> dict:
        """The pio-live status fields, or {} while the subsystem is off
        (no poll configured and no delta ever applied) — status JSON
        stays byte-compatible for deployments that never fold in."""
        with self._lock:
            active = (
                self.config.foldin_poll_s is not None
                or self.foldin_deltas_applied > 0
                # a torn/gapped chain with zero applies must still
                # surface: the operator is one lastFoldinError away
                # from knowing why the model is stale
                or self.last_foldin_error is not None
            )
            if not active:
                return {}
            advanced_mono = self.model_advanced_mono
            wm = self.foldin_watermark
            err = self.last_foldin_error
            applied = self.foldin_deltas_applied
        freshness = max(time.monotonic() - advanced_mono, 0.0)
        lag = 0
        if wm:
            try:
                es = self.ctx.storage.get_event_store()
                if hasattr(es, "cursor_lag"):
                    # handles both cursor kinds (int rowid / sharded
                    # per-shard vector string) in the store itself
                    lag = max(es.cursor_lag(
                        int(wm.get("appId", -1)),
                        int(wm.get("channelId", 0)),
                        wm.get("rowid", 0),
                    ), 0)
                elif hasattr(es, "max_rowid"):
                    lag = max(
                        es.max_rowid(
                            int(wm.get("appId", -1)),
                            int(wm.get("channelId", 0)),
                        ) - int(wm.get("rowid", 0)),
                        0,
                    )
            except Exception:
                lag = 0
        out = {
            "modelFreshnessSec": freshness,
            "foldinWatermarkLag": lag,
            "foldinDeltasApplied": applied,
            "foldinBreakerState": self._foldin_breaker.state,
        }
        if err:
            out["lastFoldinError"] = err
        MODEL_FRESHNESS_SECONDS.child().set(freshness)
        FOLDIN_WATERMARK_LAG.child().set(float(lag))
        return out

    def _refresh_foldin_gauges(self) -> None:
        self._foldin_status()  # computing the fields also sets the gauges

    # -- query path -------------------------------------------------------
    def _query_setup(self, query_json: dict, timeout_s: Optional[float],
                     tl) -> "_QueryCtx":
        """Shared front half of a query on ANY edge: budget, decode,
        state snapshot, fault point, deadline-aware admission.  Marks
        the ``parse``/``auth`` timeline boundaries.  Runs on the
        calling thread (the event loop's, or ``predict_json``'s caller)
        and never blocks."""
        # the request's time budget: per-request override, else the
        # configured default, else unbounded (None costs nothing)
        budget = timeout_s if timeout_s is not None \
            else self.config.query_timeout_s
        deadline = Deadline.after(budget) if budget is not None else None
        # pio-hive: route to the tenant FIRST — quota and the
        # per-tenant breaker shed inside resolve(), before any decode
        # or device work spends on a query its tenant cannot serve
        lease = None
        if self.tenants is not None:
            lease = self.tenants.resolve(query_json)
        try:
            decoder = (lease.runtime.query_decoder if lease is not None
                       else self.query_decoder)
            query = decoder(query_json)
            tl.mark("parse")
            if lease is not None:
                rt = lease.runtime
                ctx = _QueryCtx(
                    query=query,
                    deadline=deadline,
                    algorithms=rt.algorithms,
                    models=rt.models,
                    serving=rt.serving,
                    batcher=rt.batcher,
                    freshness=time.monotonic() - rt.model_advanced_mono,
                    foldin_seq=max(
                        rt.foldin_applied_seq.values(), default=0
                    ),
                    lease=lease,
                )
            else:
                with self._lock:
                    ctx = _QueryCtx(
                        query=query,
                        deadline=deadline,
                        algorithms=self.algorithms,
                        models=self.models,
                        serving=self.serving,
                        batcher=self.batcher,
                        # pio-live attribution, captured with the
                        # snapshot: a slow query concurrent with a
                        # fold-in apply is explicable from its flight
                        # record alone
                        freshness=time.monotonic()
                        - self.model_advanced_mono,
                        foldin_seq=max(
                            self.foldin_applied_seq.values(), default=0
                        ),
                    )
            faults.check("device.dispatch")
            if lease is not None:
                faults.check_tenant("tenant.dispatch", lease.key_str)
            tl.mark("auth")
            if deadline is not None:
                # deadline-aware admission (pio-surge): a request that
                # cannot make its SLO is answered a structured 503 NOW
                # instead of queued to die.  The breaker is the
                # cheap-shed mode: after repeated rejects it opens and
                # deadlined requests shed without estimator math until
                # a success.  With a lease, the TENANT's breaker
                # already gated inside resolve() (re-calling allow()
                # here would strand its half-open probe); rejects feed
                # it through lease.complete below.
                if lease is None and not self._admission_breaker.allow():
                    raise AdmissionRejected(
                        "admission breaker open: the edge is shedding "
                        "deadlined requests (overload)"
                    )
                try:
                    if ctx.batcher is not None:
                        ctx.batcher.check_admission(deadline)
                    else:
                        deadline.check("query admission")
                except AdmissionRejected:
                    if lease is None:
                        self._admission_breaker.record_failure()
                    raise
            return ctx
        except BaseException as e:
            if lease is not None:
                lease.complete(_lease_status(e))
            raise

    def _query_finish(self, ctx: "_QueryCtx", predictions, tl, t0: float,
                      query_json: dict) -> Any:
        """Shared back half: serving.serve, JSON encode, stats/
        histogram/trace/flight bookkeeping, feedback injection.  Runs
        on whatever thread completed the device work."""
        if ctx.deadline is not None:
            ctx.deadline.check("query serving")
        result = ctx.serving.serve(ctx.query, predictions)
        out = _result_to_json(result)
        lease = ctx.lease
        if lease is not None and isinstance(out, dict):
            # the assigned variant rides the reply so clients can echo
            # it (with prId) on their conversion events — the
            # attribution loop online eval closes
            out = {**out, "variant": lease.variant}
        tl.mark("serialize")
        timeline.mark_part("serve")
        self._admission_breaker.record_success()
        dt = time.perf_counter() - t0
        with self._lock:
            self.request_count += 1
            self.last_serving_sec = dt
            instance_id = self.instance_id
        # the request's trace id rides the histograms as a bucket
        # exemplar AND keys the flight record — /metrics names a trace,
        # the flight recorder holds its span tree, one grep joins them.
        # The segment decomposition + pio-live freshness ride BOTH the
        # span attrs and the flight record, so a worst-N entry already
        # says which segment ate the time (write lands only in the
        # histogram family: the record is captured before the socket
        # write).
        tid = current_trace_id()
        self._latency.observe(dt, exemplar=tid)
        self._m_latency.observe(dt, exemplar=tid)
        self._m_engine_queries["ok"].inc()
        attrs = {
            "instance": instance_id,
            "engine": self.engine_name,
            "modelFreshnessSec": round(max(ctx.freshness, 0.0), 3),
            "segmentsMs": tl.snapshot_ms(),
        }
        if ctx.foldin_seq:
            attrs["foldinSeq"] = ctx.foldin_seq
        if tl.turn is not None:
            # the dispatcher's turn that scored it (timeline.batch_turns)
            attrs["batchTurn"] = tl.turn
        if lease is not None:
            # pio-hive: per-tenant latency histogram + online-eval
            # impression + trace/flight attribution (a slow query's
            # flight record names its tenant AND variant)
            attrs["tenant"] = lease.key_str
            attrs["variant"] = lease.variant
            lease.observe_latency(dt, exemplar=tid)
            self.tenants.online.impression(
                lease.runtime.spec.app, lease.variant
            )
        # start is back-dated to the request's beginning (pio-lens):
        # tracecat nests spans by interval containment across
        # processes, so serve.query must COVER its measured window,
        # not sit at its end
        get_tracer().record("serve.query", dt, attrs=attrs,
                            start=time.time() - dt)
        get_flight_recorder().offer(
            tid, dt, name="serve.query", attrs=attrs
        )
        if self.config.feedback and self.config.event_server_url:
            out = self._send_feedback(query_json, out, lease=lease)
        if lease is not None:
            lease.complete("ok")
        return out

    def predict_json(self, query_json: dict,
                     timeout_s: Optional[float] = None) -> Any:
        """The in-process entry (tests, library callers): the same
        setup/finish halves as ``_el_query``, round a blocking
        ``submit`` that parks until the dispatcher's turn has answered.
        It owns the request's timeline; the batcher finds it through
        the thread-local scope and credits queue/batch/device waits."""
        tl = timeline.Timeline("serve")
        _m_inflight.inc()
        ctx = None
        try:
            with timeline.timeline_scope(tl), annotate("pio.serve.query"):
                ctx = self._query_setup(query_json, timeout_s, tl)
                with deadline_scope(ctx.deadline):
                    if ctx.deadline is not None:
                        # checked at the device boundary: dispatching a
                        # batched XLA call for a request whose client
                        # gave up wastes the one resource concurrency
                        # shares — the device queue
                        ctx.deadline.check("query device dispatch")
                    if ctx.batcher is not None:
                        # concurrent requests coalesce into one batched
                        # device call (serve() stays per-request on the
                        # caller's thread); the batcher books the
                        # queue_wait/batch_wait/device segments
                        predictions = ctx.batcher.submit(
                            ctx.query, deadline=ctx.deadline
                        )
                    else:
                        predictions = [
                            algo.predict(model, ctx.query)
                            for algo, model in zip(ctx.algorithms,
                                                   ctx.models)
                        ]
                        tl.mark("device")
                    out = self._query_finish(
                        ctx, predictions, tl, tl.t0, query_json
                    )
        except BaseException as e:
            # _query_setup completes its own lease on setup failures;
            # this covers post-setup failures (device, serve, deadline)
            if ctx is not None and ctx.lease is not None:
                ctx.lease.complete(_lease_status(e))
            raise
        finally:
            _m_inflight.dec()
        tl.finish()
        return out

    # -- event-loop edge (pio-surge) ---------------------------------------
    def _build_httpd(self):
        from .eventloop import EventLoopHTTPServer

        if self._aux_pool is None:
            import concurrent.futures

            # blocking routes only (status/reload/profile/fold-in and
            # unbatched predicts) — the query hot path never lands here
            self._aux_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="serve-aux",
                initializer=scope.register_thread_role,
                initargs=("serve_aux",),
            )
        return EventLoopHTTPServer(
            (self.host, self.port), self._el_handle,
            max_connections=self.config.max_connections,
            name="serving",
        )

    def _aux_submit(self, respond, fn) -> None:
        """Hand a blocking route to the aux pool; if the pool is gone
        (server stopping) answer 503 instead of crashing the loop."""
        try:
            self._aux_pool.submit(fn)
        except RuntimeError:
            try:
                respond(503, {"message": "server is stopping"})
            except RuntimeError:
                pass

    def _aux(self, respond, fn, *args) -> None:
        """Run ``fn(*args) -> (code, payload, ctype, extra_headers)``
        on the aux pool and answer from there."""
        def run():
            try:
                code, payload, ctype, extra = fn(*args)
                respond(code, payload, ctype=ctype, extra_headers=extra)
            except Exception as e:
                logger.exception("aux route failed")
                try:
                    respond(500, {"message": str(e)})
                except RuntimeError:
                    pass  # route answered before raising

        self._aux_submit(respond, run)

    @callback_scope
    def _el_handle(self, req, respond) -> None:
        """Event-loop request router: runs ON the loop thread — every
        branch either answers inline from in-memory state or hands off
        (batcher dispatcher / aux pool) without blocking."""
        u = urllib.parse.urlparse(req.path)
        path = u.path
        if req.method == "POST":
            if path == "/queries.json":
                self._el_query(req, u.query, respond)
            elif path == "/stop":
                respond(200, {"message": "stopping"})
                threading.Thread(target=self.stop, daemon=True).start()
            elif path == "/foldin/apply":
                self._aux(respond, self._blocking_foldin_apply)
            elif path == "/tenants/weights":
                self._aux(respond, self._blocking_set_weights, req.body)
            elif path == "/admin/tenants":
                self._aux(respond, self._blocking_admin_tenants,
                          req.body)
            else:
                respond(404, {"message": "not found"})
            return
        if req.method == "GET":
            accept = req.header("accept", "") or ""
            self._aux(respond, self._blocking_get, path, u.query, accept)
            return
        respond(405, {"message": f"method {req.method} not allowed"})

    def _blocking_get(self, path: str, query: str, accept: str):
        ans = observability_response(path, query)
        if ans is not None:
            code, payload, ctype = ans
            return code, payload, ctype or "application/json", ()
        if path == "/debug/tenants":
            if self.tenants is None:
                return (404, {"message": "tenancy is not enabled "
                              "(deploy --multi)"},
                        "application/json", ())
            return (200, self.tenants.debug_payload(),
                    "application/json", ())
        if path == "/debug/experiments":
            return (*_experiments_response(self.tenants),
                    "application/json", ())
        if path == "/":
            if "text/html" in accept:
                return (200, self.status_html().encode(),
                        "text/html; charset=utf-8", ())
            return 200, self.status_json(), "application/json", ()
        if path == "/reload":
            try:
                iid = self.reload()
                return 200, {"reloaded": iid}, "application/json", ()
            except LookupError as e:
                return 404, {"message": str(e)}, "application/json", ()
            except Exception as e:
                logger.exception("reload failed")
                return (500, {"message": f"reload failed: {e}"},
                        "application/json", ())
        return 404, {"message": "not found"}, "application/json", ()

    def _blocking_foldin_apply(self):
        """POST /foldin/apply: apply any pending fold-in delta links
        NOW (the router's rolling delta push calls this per replica —
        push semantics on top of the poll machinery).  With tenancy
        on, every resident tenant's chain is walked too."""
        n = self._apply_available_deltas()
        if self.tenants is not None:
            n += self.tenants.apply_available_deltas()
        out = {"applied": n}
        out.update(self._foldin_status())
        return 200, out, "application/json", ()

    def _blocking_set_weights(self, raw: bytes):
        """POST /tenants/weights: hot-update an app's A/B variant
        weights — ``{"app": ..., "weights": {"variant": w, ...}}``.
        The router broadcasts this to every replica so the whole fleet
        assigns identically."""
        if self.tenants is None:
            return (404, {"message": "tenancy is not enabled"},
                    "application/json", ())
        try:
            doc = json.loads(raw.decode() or "{}")
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            return (400, {"message": f"invalid JSON: {e}"},
                    "application/json", ())
        app = doc.get("app")
        weights = doc.get("weights")
        if not app or not isinstance(weights, dict) or not weights:
            return (400, {"message": "body needs app + weights{}"},
                    "application/json", ())
        try:
            snap = self.tenants.set_weights(str(app), weights)
        except KeyError as e:
            return 404, {"message": str(e)}, "application/json", ()
        except (TypeError, ValueError) as e:
            return 400, {"message": str(e)}, "application/json", ()
        return 200, {"updated": snap}, "application/json", ()

    def _blocking_admin_tenants(self, raw: bytes):
        """POST /admin/tenants: live tenant lifecycle (ROADMAP 5d) —
        ``{"action": "add", "tenant": {...manifest-entry fields...}}``
        registers a tenant without redeploy (model loads lazily on its
        first query, budget rules apply); ``{"action": "remove",
        "app": ..., "variant": ...}`` stops new queries immediately,
        drains in-flight leases, and unloads.  Guarded: 404 without
        tenancy, the anchor tenant is never removable, malformed specs
        answer 400.  The router broadcasts this route fleet-wide."""
        if self.tenants is None:
            return (404, {"message": "tenancy is not enabled"},
                    "application/json", ())
        try:
            doc = json.loads(raw.decode() or "{}")
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            return (400, {"message": f"invalid JSON: {e}"},
                    "application/json", ())
        action = doc.get("action")
        if action == "add":
            t = doc.get("tenant")
            if not isinstance(t, dict):
                return (400, {"message": "body needs a tenant{} object"},
                        "application/json", ())
            from ..tenancy import TenantSpec

            try:
                spec = TenantSpec(
                    app=t.get("app", ""),
                    variant=t.get("variant", "default"),
                    engine_json=t.get("engineJson"),
                    engine_name=t.get("engine"),
                    instance_id=t.get("engineInstanceId"),
                    access_key=t.get("accessKey"),
                    weight=float(t.get("weight", 1.0)),
                    pinned=bool(t.get("pinned", False)),
                    quota_qps=t.get("quotaQps"),
                    quota_burst=t.get("quotaBurst"),
                )
            except (TypeError, ValueError) as e:
                return (400, {"message": str(e)},
                        "application/json", ())
            # resolve app id + default access key from metadata (the
            # same enrichment `deploy --multi` does at boot)
            try:
                md = self.ctx.storage.get_metadata()
                app_rec = md.app_get_by_name(spec.app)
                if app_rec is not None:
                    spec.app_id = app_rec.id
                    if spec.access_key is None:
                        keys = md.access_key_get_by_app(app_rec.id)
                        if keys:
                            spec.access_key = keys[0].key
            except Exception:
                logger.exception(
                    "tenant add: metadata enrichment failed; "
                    "accessKey routing is off for %s", spec.key_str,
                )
            try:
                out = self.tenants.add_tenant(spec)
            except ValueError as e:
                return (400, {"message": str(e)},
                        "application/json", ())
            return 200, out, "application/json", ()
        if action == "remove":
            app = doc.get("app")
            if not app:
                return (400, {"message": "remove needs an app"},
                        "application/json", ())
            try:
                out = self.tenants.remove_tenant(
                    (str(app), str(doc.get("variant", "default"))),
                    drain_timeout_s=float(
                        doc.get("drainTimeoutSec", 10.0)
                    ),
                )
            except KeyError as e:  # UnknownTenant ⊂ KeyError
                return 404, {"message": str(e)}, "application/json", ()
            except ValueError as e:
                return 400, {"message": str(e)}, "application/json", ()
            return 200, out, "application/json", ()
        return (400, {"message": "action must be 'add' or 'remove'"},
                "application/json", ())

    @callback_scope
    def _el_query(self, req, query_str: str, respond) -> None:
        """The continuous hot path: parse + admission on the loop
        thread, device work on the batcher dispatcher, completion
        (serve/encode/bookkeeping) on the dispatcher's callback, socket
        write back on the loop.  One request never parks a thread."""
        tid = (req.header(TRACE_HEADER) or "").strip() or new_trace_id()
        hdrs = [(TRACE_HEADER, tid)]
        tl = timeline.Timeline("serve")
        try:
            query_json = json.loads(req.body.decode() or "{}")
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            self._m_queries["bad_request"].inc()
            respond(400, {"message": f"invalid JSON: {e}"},
                    extra_headers=hdrs)
            return
        timeout_s = None
        tv = urllib.parse.parse_qs(query_str).get("timeout")
        if tv:
            try:
                timeout_s = float(tv[0])
            except ValueError:
                self._m_queries["bad_request"].inc()
                respond(400, {"message": f"bad timeout: {tv[0]!r}"},
                        extra_headers=hdrs)
                return
        _m_inflight.inc()
        try:
            with trace_scope(tid), timeline.timeline_scope(tl):
                ctx = self._query_setup(query_json, timeout_s, tl)
        except Exception as e:
            _m_inflight.dec()
            self._el_reply_error(e, respond, hdrs)
            return

        if ctx.batcher is None:
            # no batched path for this engine: the per-query predict is
            # blocking device work — aux pool, not the loop
            def run_direct():
                try:
                    with trace_scope(tid), timeline.timeline_scope(tl), \
                            deadline_scope(ctx.deadline), \
                            annotate("pio.serve.query"):
                        if ctx.deadline is not None:
                            ctx.deadline.check("query device dispatch")
                        predictions = [
                            algo.predict(model, ctx.query)
                            for algo, model in zip(ctx.algorithms,
                                                   ctx.models)
                        ]
                        tl.mark("device")
                        out = self._query_finish(
                            ctx, predictions, tl, tl.t0, query_json
                        )
                except Exception as e:
                    _m_inflight.dec()
                    self._el_reply_error(e, respond, hdrs,
                                         lease=ctx.lease)
                    return
                _m_inflight.dec()
                self._m_queries["ok"].inc()
                respond(200, out, extra_headers=hdrs, tl=tl)

            self._aux_submit(respond, run_direct)
            return

        def done(entry):
            # batcher dispatcher thread: the entry's timeline is booked
            # (queue_wait/batch_wait/device) before this fires
            err = entry.error
            out = None
            if err is None:
                try:
                    with trace_scope(tid), deadline_scope(ctx.deadline):
                        out = self._query_finish(
                            ctx, entry.value, tl, tl.t0, query_json
                        )
                except Exception as e:
                    err = e
            _m_inflight.dec()
            if err is not None:
                self._el_reply_error(err, respond, hdrs, lease=ctx.lease)
                return
            self._m_queries["ok"].inc()
            timeline.mark_part("observe")
            respond(200, out, extra_headers=hdrs, tl=tl)

        try:
            ctx.batcher.submit_nowait(
                ctx.query, done, deadline=ctx.deadline, timeline=tl
            )
        except RuntimeError:
            # the snapshot raced a reload that closed this batcher:
            # retry once on the current one (single-tenant path only —
            # a tenant's batcher is replaced only by its own reload)
            with self._lock:
                batcher = self.batcher
            if (ctx.lease is None and batcher is not None
                    and batcher is not ctx.batcher):
                ctx.batcher = batcher
                batcher.submit_nowait(
                    ctx.query, done, deadline=ctx.deadline, timeline=tl
                )
            else:
                _m_inflight.dec()
                self._el_reply_error(
                    RuntimeError("batcher unavailable during reload"),
                    respond, hdrs, lease=ctx.lease,
                )

    def _el_reply_error(self, e: BaseException, respond, hdrs,
                        lease=None) -> None:
        """THE table of query-path exception -> structured reply and
        counter.  A lease passed here books the tenant outcome
        (idempotent — setup failures were already completed inside
        ``_query_setup``)."""
        if lease is not None:
            lease.complete(_lease_status(e))
        self._book_engine_query(_lease_status(e))
        try:
            if isinstance(e, QuotaExceeded):
                # per-tenant token bucket: the client is over ITS
                # rate, not the server over capacity — 429, not 503
                self._m_queries["rejected"].inc()
                respond(429, {"message": str(e),
                              "error": "QuotaExceeded"},
                        extra_headers=hdrs + [("Retry-After", "1")])
            elif isinstance(e, TenantUnavailable):
                self._m_queries["rejected"].inc()
                respond(503, {"message": str(e),
                              "error": "TenantUnavailable"},
                        extra_headers=hdrs + [("Retry-After", "1")])
            elif isinstance(e, AdmissionRejected):
                self._m_queries["rejected"].inc()
                respond(503, {"message": str(e),
                              "error": "AdmissionRejected"},
                        extra_headers=hdrs + [("Retry-After", "1")])
            elif isinstance(e, DeadlineExceeded):
                self._m_queries["timeout"].inc()
                respond(503, {"message": str(e),
                              "error": "DeadlineExceeded"},
                        extra_headers=hdrs + [("Retry-After", "1")])
            elif isinstance(e, (KeyError, ValueError, TypeError)):
                self._m_queries["bad_request"].inc()
                respond(400, {"message": f"bad query: {e}"},
                        extra_headers=hdrs)
                self.remote_log(f"Query is invalid: {e}")
            else:
                self._m_queries["error"].inc()
                logger.error("query failed: %s", e)
                respond(500, {"message": str(e)}, extra_headers=hdrs)
                self.remote_log(f"Query failed: {e}")
        except RuntimeError:
            pass  # request already answered

    def _book_engine_query(self, status: str) -> None:
        """Book one engine-labeled outcome (unknown statuses fold into
        'error' so the label space stays bounded)."""
        child = self._m_engine_queries.get(status)
        (child if child is not None
         else self._m_engine_queries["error"]).inc()

    def _send_feedback(self, query_json: dict, result_json: Any,
                       lease=None) -> Any:
        """Enqueue a pio_pr feedback event with prId injection, off the
        hot path (reference `CreateServer.scala:480-550` does this async
        too).  The bounded delivery queue retries with backoff behind a
        circuit breaker, so a down event server neither stalls serving
        nor loses events below queue capacity — they deliver when it
        returns."""
        pr_id = (
            result_json.get("prId") if isinstance(result_json, dict) else None
        ) or uuid.uuid4().hex
        props = {"query": query_json, "prediction": result_json}
        access_key = self.config.access_key
        if lease is not None:
            # pio-hive: the A/B attribution tag — every feedback event
            # flowing back through the event store names its (app,
            # variant), which is what makes interleaved serving an
            # ONLINE evaluation (online_eval.py scans these back out)
            props["variant"] = lease.variant
            props["app"] = lease.runtime.spec.app
            if lease.runtime.spec.access_key:
                access_key = lease.runtime.spec.access_key
        event = {
            "event": "predict",
            "entityType": "pio_pr",
            "entityId": pr_id,
            "properties": props,
        }
        url = (
            f"{self.config.event_server_url}/events.json"
            f"?accessKey={access_key or ''}"
        )
        from ..obs import current_trace_id

        tid = current_trace_id()
        self._feedback_queue.submit(
            url, event, headers={TRACE_HEADER: tid} if tid else None
        )
        if isinstance(result_json, dict):
            result_json = {**result_json, "prId": pr_id}
        return result_json

    def remote_log(self, message: str) -> None:
        """Ship a serving error to the configured remote log endpoint
        (reference `CreateServer.scala:413-424` ``remoteLog``): POST
        ``log_prefix + json({engineInstance, message})`` off the hot
        path via the delivery queue; delivery failures are retried then
        counted, never raised."""
        if not self.config.log_url:
            return
        with self._lock:
            instance_id = self.instance_id
        payload = self.config.log_prefix + json.dumps({
            "engineInstance": {
                "id": instance_id,
                "engineId": self.engine_id,
                "engineVersion": self.engine_version,
                "engineVariant": self.engine_variant,
            },
            "message": message,
        })
        self._log_queue.submit(self.config.log_url, payload.encode())

    def latency_stats(self) -> dict:
        """Histogram-backed latency view for /status: the same buckets
        /metrics exposes, so an operator's curl and their Grafana panel
        cannot disagree.  ``avg`` keeps the old ``avgServingSec``
        contract (now sum/count, no incremental-mean drift)."""
        snap = self._latency.snapshot()
        if snap["count"] == 0:
            return {"count": 0, "avg": 0.0, "p50": 0.0, "p95": 0.0,
                    "p99": 0.0}
        return {
            "count": snap["count"],
            "avg": snap["sum"] / snap["count"],
            "p50": self._latency.percentile(50, snap),
            "p95": self._latency.percentile(95, snap),
            "p99": self._latency.percentile(99, snap),
        }

    def status_json(self) -> dict:
        # snapshot the hot-swapped / request-updated state under the
        # lock; the reload thread and in-flight queries mutate it
        with self._lock:
            instance_id = self.instance_id
            request_count = self.request_count
            last_serving_sec = self.last_serving_sec
            batcher = self.batcher
            last_reload_error = self.last_reload_error
        lat = self.latency_stats()
        out = {
            "status": "alive",
            "engineInstanceId": instance_id,
            "engineId": self.engine_id,
            "engineVersion": self.engine_version,
            "engineVariant": self.engine_variant,
            "requestCount": request_count,
            "avgServingSec": lat["avg"],
            "lastServingSec": last_serving_sec,
            "p50ServingSec": lat["p50"],
            "p95ServingSec": lat["p95"],
            "p99ServingSec": lat["p99"],
            "startTime": self.start_time,
        }
        if batcher is not None:
            # locked snapshot — the counters are mutated under the
            # batcher's condition by whichever thread leads a batch
            out["microbatch"] = batcher.stats()
        # pio-live: model freshness + watermark lag (absent when off)
        out.update(self._foldin_status())
        # failure observability: queue depths/drops, breaker states, and
        # the last reload error an operator should know about
        out["resilience"] = {
            "lastReloadError": last_reload_error,
            "queryTimeoutSec": self.config.query_timeout_s,
            "feedback": self._feedback_queue.stats(),
            "remoteLog": self._log_queue.stats(),
        }
        # pio-hive: registry residency/budget counters (full per-tenant
        # detail lives on /debug/tenants)
        if self.tenants is not None:
            out["tenancy"] = self.tenants.summary()
        # pio-xray: the worst-N flight records (ids + durations; full
        # span trees live on /debug/xray) and the histogram's bucket
        # exemplars, so /status alone links a slow bucket to a trace id
        out["xray"] = {
            "flight": get_flight_recorder().summary(),
            "latencyExemplars": [
                {"le": le, "traceId": ex, "value": v}
                for le, ex, v, _ts in self._latency.exemplar_items()
            ],
        }
        return out

    def status_html(self) -> str:
        """Browser view of the deployed engine (reference's Twirl status
        page, `core/src/main/twirl/io/prediction/workflow/index.scala.html`):
        engine + server info and per-component params.  Same data as
        :meth:`status_json`; content-negotiated on ``/``."""
        import html as _html

        from ..controller.params import params_to_json

        def esc(v) -> str:
            return _html.escape(str(v))

        def row(k, v) -> str:
            return f"<tr><th>{esc(k)}</th><td>{esc(v)}</td></tr>"

        def table(rows) -> str:
            return "<table border='1' cellpadding='4'>" + "".join(rows) + "</table>"

        with self._lock:
            instance_id = self.instance_id
            request_count = self.request_count
            last_serving_sec = self.last_serving_sec
            ep = self.engine_params
        lat = self.latency_stats()
        rec = self.ctx.storage.get_metadata().engine_instance_get(
            instance_id
        )
        engine_rows = [
            row("Instance ID", instance_id),
            row("Engine ID", self.engine_id),
            row("Engine Version", self.engine_version),
            row("Variant", self.engine_variant),
        ]
        if rec is not None:
            engine_rows += [
                row("Training Start Time", rec.start_time),
                row("Training End Time", rec.end_time),
            ]
        started = time.strftime(
            "%Y-%m-%d %H:%M:%S UTC", time.gmtime(self.start_time)
        )
        server_rows = [
            row("Start Time", started),
            row("Request Count", request_count),
            row("Average Serving Time", f"{lat['avg']:.4f} s"),
            row("Last Serving Time", f"{last_serving_sec:.4f} s"),
            row("Serving Time p50 / p95 / p99",
                f"{lat['p50']:.4f} / {lat['p95']:.4f} / "
                f"{lat['p99']:.4f} s"),
        ]
        live = self._foldin_status()
        if live:
            server_rows.append(row(
                "Model Freshness (pio-live)",
                f"{live['modelFreshnessSec']:.1f} s since last advance; "
                f"watermark lag {live['foldinWatermarkLag']} rows; "
                f"{live['foldinDeltasApplied']} deltas applied",
            ))
        worst = get_flight_recorder().summary()["worst"]
        if worst:
            server_rows.append(row(
                "Slowest Requests (flight recorder)",
                "; ".join(
                    f"{w['traceId']} {w['durationSec'] * 1e3:.1f} ms"
                    for w in worst[:5]
                ) + " — span trees at /debug/xray",
            ))
        comp_rows = [
            row(f"Data Source [{ep.data_source[0] or 'default'}]",
                json.dumps(params_to_json(ep.data_source[1]))),
            row(f"Preparator [{ep.preparator[0] or 'default'}]",
                json.dumps(params_to_json(ep.preparator[1]))),
        ]
        for name, p in ep.algorithms:
            comp_rows.append(
                row(f"Algorithm [{name or 'default'}]",
                    json.dumps(params_to_json(p)))
            )
        comp_rows.append(
            row(f"Serving [{ep.serving[0] or 'default'}]",
                json.dumps(params_to_json(ep.serving[1])))
        )
        title = (
            f"Engine Server at {self.config.host}:{self.config.port}"
        )
        return (
            "<!DOCTYPE html><html><head>"
            f"<title>{esc(title)}</title>"
            "<style>body{font-family:sans-serif;margin:2em}"
            "td{font-family:monospace}</style></head><body>"
            f"<h1>{esc(title)}</h1>"
            "<h2>Engine Information</h2>" + table(engine_rows) +
            "<h2>Server Information</h2>" + table(server_rows) +
            "<h2>Components</h2>" + table(comp_rows) +
            "<p>POST queries to <code>/queries.json</code>.</p>"
            "</body></html>"
        )

    def stop(self) -> None:
        super().stop()
        # release the delta-poll, batcher-dispatcher, aux and delivery
        # drain threads (pending entries are abandoned — the process is
        # going away)
        self._foldin_stop.set()
        self._eval_stop.set()
        if self.tenants is not None:
            self.tenants.close()
        with self._lock:
            batcher = getattr(self, "batcher", None)
        if batcher is not None:
            batcher.close()
        # pio-confluence: a view's close only retires its tenant; the
        # shared core (and its dispatcher thread) is the server's to
        # stop
        with self._shared_lock:
            core, self._shared_core = self._shared_core, None
        if core is not None:
            core.close()
        if self._aux_pool is not None:
            self._aux_pool.shutdown(wait=False)
            self._aux_pool = None
        self._feedback_queue.close()
        self._log_queue.close()

    # -- http --------------------------------------------------------------
    @property
    def host(self) -> str:
        return self.config.host

    @property
    def port(self) -> int:
        return self.config.port

    @port.setter
    def port(self, v: int) -> None:
        self.config.port = v

    @property
    def max_connections(self) -> int:
        return self.config.max_connections
