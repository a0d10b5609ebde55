"""pio-surge: serving replica fleet router.

One serving process is one core's worth of QPS; "millions of users"
means going horizontal.  ``pio-tpu deploy --replicas N`` boots N
single-replica EngineServer *processes* (each its own interpreter —
no shared GIL, its own device queue, its own ``/metrics``) and ONE
router process in front:

* **Routing**: ``POST /queries.json`` round-robins over healthy
  replicas on pooled keep-alive connections.  A transport failure
  (replica killed, connection refused, read timeout) marks the replica
  down, books a ``failover``, and retries the SAME request on the next
  replica — predicts are idempotent, so the client sees one 200 and no
  evidence a replica died.  Only when every replica is unreachable
  does the router answer a structured 503.
* **Health**: a daemon thread polls each replica's ``GET /`` status
  every ``health_interval_s``, maintaining per-replica health, breaker
  state, and the fleet gauges ``pio_replica_up{replica}`` /
  ``pio_replica_model_freshness_seconds{replica}`` (the labeled
  fleet-wide view of each replica's own
  ``pio_model_freshness_seconds``).
* **Rolling delta push (pio-live x fleet)**: ``POST
  /admin/push-foldin`` walks the replicas ONE AT A TIME, POSTing
  ``/foldin/apply`` so each patches any pending fold-in delta links in
  place (no reload, no warmup).  Strictly sequential by construction:
  fleet availability never drops below N-1 replicas during a push, and
  a replica that fails to apply keeps serving its stale model while
  the rest of the fleet advances.  ``--push-foldin SEC`` runs the same
  rolling push on a timer.

The router itself rides the event-loop edge (`server/eventloop.py`):
the loop parses and routes, a bounded worker pool does the blocking
upstream HTTP, so router threads are O(pool), not O(connections).
"""

from __future__ import annotations

import http.client
import json
import logging
import socket
import subprocess
import sys
import threading
import time
import urllib.parse
from pathlib import Path
from typing import Optional

from ..obs import (
    REPLICA_MODEL_FRESHNESS,
    REPLICA_REQUESTS_TOTAL,
    REPLICA_RESPAWNS_TOTAL,
    REPLICA_UP,
    ROUTER_ADMISSION_TOTAL,
    TRACE_HEADER,
    FlightRecorder,
    fleet,
    get_registry,
    get_tracer,
    metrics_enabled,
    new_trace_id,
    scope,
    timeline,
)
from ..resilience.policy import CircuitBreaker
from .eventloop import EventLoopHTTPServer, callback_scope
from .http_base import (
    HTTPServerBase,
    PROMETHEUS_CTYPE,
    observability_response,
)
from .microbatch import EwmaEstimator

__all__ = [
    "Replica",
    "ReplicaSupervisor",
    "RouterConfig",
    "RouterServer",
    "spawn_replica",
    "wait_for_port_file",
]

logger = logging.getLogger(__name__)


class RouterConfig:
    def __init__(self, host: str = "127.0.0.1", port: int = 8000,
                 health_interval_s: float = 1.0,
                 health_timeout_s: float = 2.0,
                 forward_timeout_s: float = 30.0,
                 breaker_failures: int = 3,
                 breaker_reset_s: float = 2.0,
                 max_connections: int = 1024,
                 workers: int = 16,
                 push_foldin_s: Optional[float] = None,
                 scrape_metrics: bool = True,
                 slo_ms: Optional[float] = None):
        self.host = host
        self.port = port
        self.health_interval_s = health_interval_s
        self.health_timeout_s = health_timeout_s
        self.forward_timeout_s = forward_timeout_s
        self.breaker_failures = breaker_failures
        self.breaker_reset_s = breaker_reset_s
        self.max_connections = max_connections
        # blocking upstream forwards run on this many pool threads;
        # the loop thread itself never blocks on a replica
        self.workers = workers
        # optional timer driving the rolling fold-in push (the same
        # walk POST /admin/push-foldin triggers on demand)
        self.push_foldin_s = push_foldin_s
        # pio-lens: the health loop also pulls each replica's /metrics
        # and merges the parsed states into the router's own GET
        # /metrics (Prometheus-federation style — ONE scrape answers
        # for the fleet); slo_ms additionally arms the router-side
        # pio_slo_burn_rate{window} gauges on the forward round-trip
        # histogram
        self.scrape_metrics = scrape_metrics
        self.slo_ms = slo_ms


class Replica:
    """Router-side state for one replica: address, pooled keep-alive
    connections, breaker, health + last-seen status fields."""

    def __init__(self, name: str, host: str, port: int,
                 breaker_failures: int = 3, breaker_reset_s: float = 2.0,
                 timeout_s: float = 30.0):
        self.name = name
        self.host = host
        self.port = port
        self.timeout_s = timeout_s
        self.breaker = CircuitBreaker(
            failure_threshold=breaker_failures,
            reset_timeout_s=breaker_reset_s,
        )
        self._lock = threading.Lock()
        self._pool: list[http.client.HTTPConnection] = []
        # healthy starts True: a fresh fleet serves immediately and the
        # first failed forward/health-check flips it (optimistic start
        # beats rejecting the first second of traffic)
        self.healthy = True
        self.last_status: dict = {}
        self.last_error: Optional[str] = None
        self.forwarded = 0
        self.errors = 0
        self.failovers = 0
        # pio-lens: the replica's last successfully scraped + parsed
        # /metrics state (a dump_state()-shaped dict).  Rebound whole
        # on every good scrape, never mutated — readers (the merged
        # exposition, the fleet tail table) see the old snapshot or
        # the new one, and a replica that dies mid-scrape keeps its
        # last good snapshot standing (cumulative values, so the
        # merged counters stay monotone).
        self.metrics_state: Optional[dict] = None
        self.scrape_errors = 0
        self.last_scrape_at: Optional[float] = None
        self.last_scrape_error: Optional[str] = None
        self._m_scrape_err = fleet.REPLICA_SCRAPE_ERRORS.labels(
            replica=name)
        self._m_up = REPLICA_UP.labels(replica=name)
        self._m_fresh = REPLICA_MODEL_FRESHNESS.labels(replica=name)
        self._m_ok = REPLICA_REQUESTS_TOTAL.labels(
            replica=name, outcome="ok")
        self._m_err = REPLICA_REQUESTS_TOTAL.labels(
            replica=name, outcome="error")
        self._m_fail = REPLICA_REQUESTS_TOTAL.labels(
            replica=name, outcome="failover")
        self._m_up.set(1.0)

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def _connect(self, timeout_s: Optional[float] = None
                 ) -> http.client.HTTPConnection:
        # fresh connections honor the CALLER's timeout (pio-lens fix):
        # a SIGSTOPped replica accepts the TCP handshake from its
        # kernel backlog and then never answers — with the default 30s
        # here, one stalled replica used to wedge every health sweep
        # (and the metrics scrape behind it) for 30s per tick
        c = http.client.HTTPConnection(
            self.host, self.port,
            timeout=timeout_s if timeout_s is not None
            else self.timeout_s,
        )
        c.connect()
        c.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return c

    def request(self, method: str, path: str, body: Optional[bytes],
                headers: Optional[dict] = None,
                timeout_s: Optional[float] = None,
                tl=None) -> tuple[int, bytes, str]:
        """One upstream round trip on a pooled keep-alive connection.
        Transport trouble raises OSError/http.client exceptions — the
        router's failover signal; HTTP error statuses return normally
        (an application 4xx/5xx is the replica's answer, not a death).

        ``tl`` (a pulse Timeline, pio-lens) books the round trip's
        interior split: ``forward`` = pool/connect + request send,
        ``replica`` = waiting on the replica's response head (its
        serve time), ``read`` = draining the body."""
        with self._lock:
            conn = self._pool.pop() if self._pool else None
        if conn is None:
            conn = self._connect(timeout_s)
        elif timeout_s is not None and conn.sock is not None:
            conn.sock.settimeout(timeout_s)
        try:
            hdrs = {"Content-Type": "application/json"}
            if headers:
                hdrs.update(headers)
            conn.request(method, path, body, headers=hdrs)
            if tl is not None:
                tl.mark("forward")
            r = conn.getresponse()
            if tl is not None:
                tl.mark("replica")
            data = r.read()
            if tl is not None:
                tl.mark("read")
            ctype = r.getheader("Content-Type",
                                "application/json") or "application/json"
            status = r.status
        except BaseException:
            try:
                conn.close()
            except OSError:
                pass
            raise
        with self._lock:
            if len(self._pool) < 32:
                self._pool.append(conn)
            else:
                try:
                    conn.close()
                except OSError:
                    pass
        return status, data, ctype

    def mark_down(self, err: str) -> None:
        self.healthy = False
        self.last_error = err
        self.breaker.record_failure()
        self._m_up.set(0.0)
        # drop pooled connections: they point at a corpse
        with self._lock:
            pool, self._pool = self._pool, []
        for c in pool:
            try:
                c.close()
            except OSError:
                pass

    def mark_up(self, status: dict) -> None:
        self.healthy = True
        self.last_error = None
        self.last_status = status
        self.breaker.record_success()
        self._m_up.set(1.0)
        fresh = status.get("modelFreshnessSec")
        if fresh is not None:
            self._m_fresh.set(float(fresh))

    def scrape(self, timeout_s: float) -> bool:
        """Pull + parse this replica's ``/metrics`` into
        :attr:`metrics_state` (pio-lens).  Any failure — transport,
        HTTP status, exposition grammar — books a scrape error and
        leaves the previous snapshot standing; health marking is the
        health check's job, not the scrape's."""
        try:
            status, data, _ = self.request(
                "GET", "/metrics", None, timeout_s=timeout_s,
            )
            if status != 200:
                raise RuntimeError(f"/metrics answered {status}")
            state = fleet.parse_prometheus(data.decode())
        except Exception as e:
            self.scrape_errors += 1
            self.last_scrape_error = f"{type(e).__name__}: {e}"
            self._m_scrape_err.inc()
            return False
        self.metrics_state = state
        self.last_scrape_at = time.time()
        self.last_scrape_error = None
        return True

    def snapshot(self) -> dict:
        out = {
            "name": self.name,
            "url": self.url,
            "healthy": self.healthy,
            "breaker": self.breaker.state,
            "forwarded": self.forwarded,
            "errors": self.errors,
            "failovers": self.failovers,
        }
        if self.scrape_errors:
            out["scrapeErrors"] = self.scrape_errors
        if self.last_error:
            out["lastError"] = self.last_error
        st = self.last_status
        for src_key, dst_key in (
            ("engineInstanceId", "engineInstanceId"),
            ("requestCount", "requestCount"),
            ("modelFreshnessSec", "modelFreshnessSec"),
            ("foldinDeltasApplied", "foldinDeltasApplied"),
        ):
            if src_key in st:
                out[dst_key] = st[src_key]
        return out


class ReplicaSupervisor:
    """Respawn-on-death for the replica fleet (pio-scout satellite;
    ROADMAP item 1b): before this, a SIGKILLed replica stayed dead —
    masked by failover, but the fleet ran at N-1 until an operator
    acted.  The router's health loop ticks the supervisor every sweep;
    a replica whose *process* has exited is respawned through the same
    spawner ``deploy --replicas`` used, with capped exponential backoff
    between attempts so a crash-looping engine (bad model, OOM) cannot
    melt the box, and ``pio_replica_respawns_total{replica}`` books
    every successful respawn.

    The respawn itself (subprocess boot + port-file wait — seconds to
    minutes) runs on a per-replica background thread so one slow boot
    never stalls health sweeps for the rest of the fleet.
    """

    def __init__(self, spawner, waiter=None, backoff_base_s: float = 0.5,
                 backoff_cap_s: float = 30.0,
                 spawn_timeout_s: float = 180.0):
        # spawner(index) -> spawned dict (router.spawn_replica shape);
        # waiter(spawned) -> bound port (defaults to wait_for_port_file)
        self.spawner = spawner
        self.waiter = waiter or wait_for_port_file
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        self.spawn_timeout_s = spawn_timeout_s
        self._lock = threading.Lock()
        # replica name -> {"spawned", "index", "attempts", "next_try",
        #                  "busy"}
        self._procs: dict[str, dict] = {}
        self.respawns = 0

    def attach(self, replica: Replica, spawned: dict) -> None:
        with self._lock:
            self._procs[replica.name] = {
                "spawned": spawned,
                "index": spawned["index"],
                "attempts": 0,
                "next_try": 0.0,
                "busy": False,
            }

    def live_procs(self) -> list:
        """Every currently-tracked subprocess (fleet teardown reaps
        these, not the boot-time list — respawns replace entries)."""
        with self._lock:
            return [st["spawned"]["proc"] for st in self._procs.values()]

    def tick(self, replicas: list[Replica]) -> None:
        """One health-loop sweep: respawn any replica whose process
        has exited (past its backoff), reset backoff for replicas that
        are alive AND healthy again."""
        now = time.monotonic()
        for replica in replicas:
            with self._lock:
                st = self._procs.get(replica.name)
                if st is None or st["busy"]:
                    continue
                proc = st["spawned"]["proc"]
                if proc.poll() is None:
                    if replica.healthy:
                        st["attempts"] = 0
                    continue
                if now < st["next_try"]:
                    continue
                st["busy"] = True
            threading.Thread(
                target=self._respawn, args=(replica,),
                daemon=True, name=f"respawn-{replica.name}",
            ).start()

    def _respawn(self, replica: Replica) -> None:
        name = replica.name
        with self._lock:
            st = self._procs[name]
            index = st["index"]
            attempt = st["attempts"]
        try:
            spawned = self.spawner(index)
            port = self.waiter(spawned, timeout_s=self.spawn_timeout_s)
        except Exception as e:
            logger.warning("respawn of %s failed: %s", name, e)
            with self._lock:
                st["attempts"] += 1
                st["next_try"] = time.monotonic() + min(
                    self.backoff_cap_s,
                    self.backoff_base_s * (2.0 ** st["attempts"]),
                )
                st["busy"] = False
            return
        # point the router at the new process: update the port, drop
        # pooled connections to the corpse (mark_down does), and let
        # the next health tick flip it healthy
        replica.port = port
        replica.mark_down(f"respawned on port {port}; awaiting health")
        REPLICA_RESPAWNS_TOTAL.labels(replica=name).inc()
        with self._lock:
            st["spawned"] = spawned
            self.respawns += 1
            # successful respawns back off too: a crash-looping engine
            # respawns at the capped cadence, not as fast as it dies
            st["attempts"] += 1
            st["next_try"] = time.monotonic() + min(
                self.backoff_cap_s,
                self.backoff_base_s * (2.0 ** st["attempts"]),
            )
            st["busy"] = False
        logger.info("respawned %s on port %d", name, port)

    def summary(self) -> dict:
        with self._lock:
            return {
                "respawns": self.respawns,
                "tracked": len(self._procs),
                "backoffCapSec": self.backoff_cap_s,
            }


class RouterServer(HTTPServerBase):
    """The fleet front door; see module docstring."""

    server_name = "router"

    def __init__(self, replicas: list[Replica],
                 config: Optional[RouterConfig] = None,
                 supervisor: Optional[ReplicaSupervisor] = None):
        if not replicas:
            raise ValueError("router needs at least one replica")
        self.replicas = replicas
        self.config = config or RouterConfig()
        self.supervisor = supervisor
        self._pool = None
        self._rr_lock = threading.Lock()
        self._rr = 0
        self._push_lock = threading.Lock()
        self._stop_event = threading.Event()
        self.start_time = time.time()  # wall clock: a TIMESTAMP
        self.request_count = 0
        self.unroutable = 0
        # router-level deadline admission (pio-scout satellite; ROADMAP
        # item 1b): the same EWMA estimator shape the micro-batcher
        # uses for device batches, fed with replica round-trip times —
        # a request whose ?timeout= budget the fleet demonstrably
        # cannot meet is answered a structured 503 HERE, without
        # burning a replica round trip on a doomed forward (today only
        # replicas shed).  Seeded 0: a cold router never sheds.
        self._ewma_forward = EwmaEstimator()
        self._ewma_lock = threading.Lock()
        self.admission_rejected = 0
        self._m_adm_ok = ROUTER_ADMISSION_TOTAL.labels(outcome="admitted")
        self._m_adm_rej = ROUTER_ADMISSION_TOTAL.labels(
            outcome="rejected")
        # pio-lens: the router's own flight recorder — worst-N proxied
        # requests with per-replica attribution (which replica served,
        # its round trip vs its self-reported segment split, the EWMA
        # estimate at admission time).  A separate instance from the
        # process-global recorder so an in-process replica's serve.query
        # offers never crowd out the fleet view.
        self.flight = FlightRecorder()
        self._m_forward = fleet.ROUTER_FORWARD_SECONDS.child()
        self._burn = None
        if self.config.slo_ms:
            self._burn = fleet.install_burn_rate(
                self._m_forward, self.config.slo_ms / 1e3
            )
        fleet.set_fleet_provider(self.fleet_payload)
        self._health_thread: Optional[threading.Thread] = None
        self._push_thread: Optional[threading.Thread] = None

    # -- lifecycle ---------------------------------------------------------
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

    def _build_httpd(self):
        import concurrent.futures

        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.config.workers,
                thread_name_prefix="router-fwd",
                initializer=scope.register_thread_role,
                initargs=("router_fwd",),
            )
        self._start_daemons()
        # pio-scope: the router is THE single-event-loop suspect at
        # fleet saturation (ROADMAP item 1) — always profile it
        scope.ensure_started()
        return EventLoopHTTPServer(
            (self.host, self.port), self._el_handle,
            max_connections=self.config.max_connections,
            name="router",
        )

    def _start_daemons(self) -> None:
        if self._health_thread is None:
            self._health_thread = threading.Thread(
                target=self._health_loop, daemon=True, name="router-health"
            )
            self._health_thread.start()
        if self.config.push_foldin_s and self._push_thread is None:
            self._push_thread = threading.Thread(
                target=self._push_loop, daemon=True, name="router-push"
            )
            self._push_thread.start()

    def stop(self) -> None:
        super().stop()
        self._stop_event.set()
        # clear the provider only if WE are still the installed one (a
        # second router in the same process may have replaced it)
        if getattr(fleet, "_fleet_provider", None) == self.fleet_payload:
            fleet.set_fleet_provider(None)
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    # -- health ------------------------------------------------------------
    def probe_replica(self, replica: Replica) -> bool:
        try:
            status, data, _ = replica.request(
                "GET", "/", None,
                timeout_s=self.config.health_timeout_s,
            )
            if status != 200:
                replica.mark_down(f"status {status}")
                return False
            replica.mark_up(json.loads(data.decode()))
            return True
        except Exception as e:
            replica.mark_down(f"{type(e).__name__}: {e}")
            return False

    def check_all(self) -> None:
        for r in self.replicas:
            self.probe_replica(r)

    def scrape_all(self) -> None:
        """pio-lens: pull every replica's /metrics on the pooled
        keep-alive connections.  A dead replica's scrape fails fast
        (connection refused — one attempt per sweep, same cost as its
        health probe), books ``pio_replica_scrape_errors_total`` and
        leaves its last good snapshot standing in the merged
        exposition — cumulative values, so the fleet counters stay
        monotone through the death."""
        for r in self.replicas:
            r.scrape(self.config.health_timeout_s)

    def _health_loop(self) -> None:
        scope.register_thread_role("health_loop")
        while not self._stop_event.wait(self.config.health_interval_s):
            try:
                self.check_all()
            except Exception:
                logger.exception("router health sweep failed")
            if self.config.scrape_metrics:
                try:
                    self.scrape_all()
                except Exception:
                    logger.exception("router metrics scrape failed")
            if self.supervisor is not None:
                try:
                    self.supervisor.tick(self.replicas)
                except Exception:
                    logger.exception("replica supervisor tick failed")

    # -- rolling fold-in push ---------------------------------------------
    def push_foldin(self) -> dict:
        """Walk the fleet ONE replica at a time, telling each to apply
        any pending fold-in delta links now (``POST /foldin/apply``).
        Sequential by construction — mid-push, at most the one replica
        currently applying is busy (and the apply is in-place anyway),
        so availability never drops below N-1."""
        results = []
        with self._push_lock:  # one rolling push at a time
            for r in self.replicas:
                if not r.healthy:
                    results.append({
                        "replica": r.name, "skipped": "unhealthy",
                    })
                    continue
                try:
                    status, data, _ = r.request(
                        "POST", "/foldin/apply", b"{}",
                        timeout_s=self.config.forward_timeout_s,
                    )
                    body = json.loads(data.decode())
                    entry = {"replica": r.name, "status": status}
                    entry.update({
                        k: body[k] for k in
                        ("applied", "modelFreshnessSec",
                         "foldinDeltasApplied")
                        if k in body
                    })
                    results.append(entry)
                    fresh = body.get("modelFreshnessSec")
                    if fresh is not None:
                        r._m_fresh.set(float(fresh))
                except Exception as e:
                    r.mark_down(f"{type(e).__name__}: {e}")
                    results.append({
                        "replica": r.name,
                        "error": f"{type(e).__name__}: {e}",
                    })
        return {"pushed": results}

    def _push_loop(self) -> None:
        scope.register_thread_role("push_loop")
        while not self._stop_event.wait(self.config.push_foldin_s):
            try:
                self.push_foldin()
            except Exception:
                logger.exception("rolling fold-in push failed")

    # -- forwarding --------------------------------------------------------
    def _candidates(self) -> list[Replica]:
        with self._rr_lock:
            self._rr += 1
            start = self._rr
        n = len(self.replicas)
        order = [self.replicas[(start + i) % n] for i in range(n)]
        healthy = [r for r in order if r.healthy]
        # last resort: unhealthy replicas whose breaker grants a probe
        # (a recovered replica starts taking traffic before the next
        # health tick)
        probes = [r for r in order
                  if not r.healthy and r.breaker.allow()]
        return healthy + probes

    def _broadcast_post(self, target: str, body: bytes, respond) -> None:
        """POST ``body`` to ``target`` on every healthy replica from
        the forward pool and answer the merged per-replica results —
        the admin fan-out shared by the weights and tenant-lifecycle
        routes."""
        pool = self._pool
        if pool is None:
            respond(503, {"message": "router is stopping"})
            return

        def broadcast():
            results = []
            for r in self.replicas:
                if not r.healthy:
                    results.append({
                        "replica": r.name, "skipped": "unhealthy",
                    })
                    continue
                try:
                    status, data, _ = r.request(
                        "POST", target, body,
                        timeout_s=self.config.forward_timeout_s,
                    )
                    entry = {"replica": r.name, "status": status}
                    try:
                        entry.update(json.loads(data.decode()))
                    except (json.JSONDecodeError, UnicodeDecodeError):
                        pass
                    results.append(entry)
                except Exception as e:
                    r.mark_down(f"{type(e).__name__}: {e}")
                    results.append({
                        "replica": r.name,
                        "error": f"{type(e).__name__}: {e}",
                    })
            try:
                respond(200, {"pushed": results})
            except RuntimeError:
                pass

        try:
            pool.submit(broadcast)
        except RuntimeError:
            respond(503, {"message": "router is stopping"})

    def _forward_query(self, path_qs: str, body: bytes,
                       trace_id: Optional[str], respond,
                       tl=None, est_at_admission: float = 0.0) -> None:
        """Worker-pool half of the hot path: try candidates in order
        until one answers; transport failures fail over with the
        replica marked down.

        pio-lens: the request's Timeline accumulates the
        ``forward/replica/read`` split (inside ``Replica.request``),
        the successful round trip feeds the forward histogram (with
        the trace id as its bucket exemplar) and a ``router.forward``
        span, and the finished request is offered to the router's
        flight recorder with the serving replica's name + the EWMA
        estimate admission saw — the per-replica tail attribution
        ROADMAP 1(c) asks for."""
        headers = {TRACE_HEADER: trace_id} if trace_id else None
        hdrs_out = [(TRACE_HEADER, trace_id)] if trace_id else []
        candidates = self._candidates()
        last_err = "no replicas configured"
        failed: list[str] = []
        for i, replica in enumerate(candidates):
            t0 = time.perf_counter()
            wall0 = time.time()
            try:
                status, data, ctype = replica.request(
                    "POST", path_qs, body, headers=headers,
                    timeout_s=self.config.forward_timeout_s, tl=tl,
                )
            except Exception as e:
                last_err = f"{replica.name}: {type(e).__name__}: {e}"
                replica.errors += 1
                replica._m_fail.inc()
                replica.failovers += 1
                replica.mark_down(last_err)
                failed.append(replica.name)
                continue
            if not replica.healthy:
                replica.mark_up(replica.last_status)
            replica.forwarded += 1
            rt = time.perf_counter() - t0
            # feed the admission estimator with the fleet's actual
            # round-trip time (success paths only: a failover's
            # timeout would teach the estimator to shed everything)
            with self._ewma_lock:
                self._ewma_forward.observe(rt)
            self._m_forward.observe(rt, exemplar=trace_id)
            (replica._m_ok if status < 500 else replica._m_err).inc()
            tracer = get_tracer()
            tracer.record(
                "router.forward", rt, trace_id=trace_id,
                attrs={"replica": replica.name, "status": status},
                start=wall0,
            )
            if tl is not None:
                total = tl.elapsed()
                attrs = {
                    "replica": replica.name,
                    "status": status,
                    "ewmaAtAdmissionSec": round(est_at_admission, 6),
                    "roundTripSec": round(rt, 6),
                    "segmentsMs": tl.snapshot_ms(),
                }
                if failed:
                    # the tail-attribution fix for failover: a request
                    # that waited out a stalled replica's timeout and
                    # then succeeded elsewhere names the replica that
                    # ATE the time, not just the one that answered
                    attrs["failedReplicas"] = failed
                if i:
                    attrs["failovers"] = i
                tracer.record(
                    "router.request", total, trace_id=trace_id,
                    attrs=attrs, start=time.time() - total,
                )
                # offer AFTER the spans land so an admitted record's
                # captured tree holds them
                self.flight.offer(
                    trace_id, total, name="router.request", attrs=attrs,
                )
            try:
                respond(status, data, ctype=ctype,
                        extra_headers=hdrs_out, tl=tl)
            except RuntimeError:
                pass
            return
        self.unroutable += 1
        try:
            respond(503, {
                "message": f"no replica available ({last_err})",
                "error": "NoReplicaAvailable",
            }, extra_headers=hdrs_out + [("Retry-After", "1")])
        except RuntimeError:
            pass

    # -- pio-lens: merged exposition + fleet tail view ---------------------
    def render_fleet_metrics(self) -> bytes:
        """The router's ``GET /metrics`` body: local registry state
        merged with every replica's last scraped snapshot via
        ``registry.merge_states`` (counters/histograms sum exactly,
        gauges gain ``{replica}`` labels) and rendered through the ONE
        shared renderer — so ``pio_queries_total`` on the router equals
        the sum of the replicas' and percentile re-derivation over the
        merged buckets is exact.  A schema drift between replicas
        degrades to the local exposition LOUDLY rather than 500ing the
        scrape."""
        tagged = [("router", get_registry().dump_state())]
        for r in self.replicas:
            state = r.metrics_state
            if state is not None:
                tagged.append((r.name, state))
        try:
            return fleet.render_fleet(tagged).encode()
        except ValueError as e:
            logger.warning(
                "fleet metrics merge failed (%s); serving the "
                "router-local exposition", e,
            )
            return get_registry().render_prometheus().encode()

    def _replica_tail_entry(self, r: Replica) -> dict:
        entry = r.snapshot()
        entry["respawns"] = REPLICA_RESPAWNS_TOTAL.labels(
            replica=r.name).value()
        state = r.metrics_state
        if state is not None:
            hist = fleet.state_histogram(
                state, "pio_query_latency_seconds")
            if hist and hist["count"]:
                entry["p50Ms"] = round(
                    fleet.hist_quantile(hist, 50) * 1e3, 3)
                entry["p99Ms"] = round(
                    fleet.hist_quantile(hist, 99) * 1e3, 3)
                entry["latencyCount"] = hist["count"]
            entry["queriesTotal"] = fleet.state_counter_total(
                state, "pio_queries_total")
            if r.last_scrape_at is not None:
                entry["scrapeAgeSec"] = round(
                    max(time.time() - r.last_scrape_at, 0.0), 3)
        if r.last_scrape_error:
            entry["lastScrapeError"] = r.last_scrape_error
        return entry

    def _enrich_worst(self, worst: list) -> list:
        """Lazily join each worst-N record with the serving replica's
        OWN view of that trace: ``GET /debug/flight?trace=<id>`` on
        the replica answers its flight record, whose ``segmentsMs``
        decomposition sits next to the router's round trip — the
        queue-vs-device split of a fleet tail entry without shipping
        every span through the router.  Fetched once per record and
        cached back into the router's flight attrs."""
        by_name = {r.name: r for r in self.replicas}
        for w in worst[:8]:
            attrs = w.get("attrs") or {}
            if "replicaSegmentsMs" in attrs or "replica" not in attrs:
                continue
            replica = by_name.get(attrs["replica"])
            if replica is None or not replica.healthy:
                continue
            try:
                status, data, _ = replica.request(
                    "GET",
                    f"/debug/flight?trace="
                    f"{urllib.parse.quote(w['traceId'])}",
                    None, timeout_s=self.config.health_timeout_s,
                )
                if status != 200:
                    continue
                rec = json.loads(data.decode()).get("record")
            except Exception:
                continue
            if not rec:
                continue
            extra = {
                "replicaDurationSec": rec.get("durationSec"),
                "replicaSegmentsMs": (rec.get("attrs") or {}).get(
                    "segmentsMs"),
            }
            self.flight.annotate(w["traceId"], extra)
            attrs.update(extra)
            w["attrs"] = attrs
        return worst

    def fleet_payload(self) -> dict:
        """``GET /debug/fleet``: how is the fleet doing and who is
        slow — per-replica tail table (scrape-derived p50/p99, breaker
        + respawn state) plus the router flight recorder's worst-N
        with per-replica attribution and lazily fetched replica
        segment splits."""
        summary = self.flight.summary()
        out = {
            "role": "router",
            "replicas": [
                self._replica_tail_entry(r) for r in self.replicas
            ],
            "healthyReplicas": sum(r.healthy for r in self.replicas),
            "requestCount": self.request_count,
            "unroutable": self.unroutable,
            "admissionRejected": self.admission_rejected,
            "ewmaForwardSec": self._ewma_forward.value,
            "scrapeErrors": sum(r.scrape_errors for r in self.replicas),
            "flight": {
                "capacity": summary["capacity"],
                "offers": summary["offers"],
                "admissions": summary["admissions"],
            },
            "worst": self._enrich_worst(summary["worst"]),
        }
        if self.config.slo_ms:
            out["sloMs"] = self.config.slo_ms
            if self._burn is not None:
                out["burnRate"] = {
                    name: round(self._burn.rate(secs), 4)
                    for name, secs in fleet.BURN_WINDOWS
                }
        if self.supervisor is not None:
            out["supervisor"] = self.supervisor.summary()
        return out

    # -- http --------------------------------------------------------------
    def status_json(self) -> dict:
        out = {
            "status": "alive",
            "role": "router",
            "replicas": [r.snapshot() for r in self.replicas],
            "healthyReplicas": sum(r.healthy for r in self.replicas),
            "requestCount": self.request_count,
            "unroutable": self.unroutable,
            "admissionRejected": self.admission_rejected,
            "ewmaForwardSec": self._ewma_forward.value,
            "startTime": self.start_time,
            "maxConnections": self.config.max_connections,
        }
        if self.supervisor is not None:
            out["supervisor"] = self.supervisor.summary()
        return out

    @callback_scope
    def _el_handle(self, req, respond) -> None:
        u = urllib.parse.urlparse(req.path)
        path = u.path
        if req.method == "POST" and path == "/queries.json":
            self.request_count += 1  # loop-thread only: no lock needed
            # pio-lens: the router MINTS a trace id when the client
            # didn't bring one — every proxied request is stitchable
            # across router + replica journals (tools/tracecat.py)
            tid = (req.header(TRACE_HEADER) or "").strip() \
                or new_trace_id()
            body = req.body
            tl = timeline.Timeline("router")
            # router-level deadline admission: a ?timeout= request the
            # EWMA forward estimate already exceeds is a doomed
            # round-trip — answer the structured 503 the replica edge
            # would have, one hop earlier and without spending a
            # replica on it.  No timeout (or a cold estimator) admits.
            est = self._ewma_forward.value
            tv = urllib.parse.parse_qs(u.query).get("timeout")
            if tv:
                try:
                    budget = float(tv[0])
                except ValueError:
                    budget = None
                if budget is not None and est > 0.0 and (
                    budget <= 0.0 or est > budget
                ):
                    self.admission_rejected += 1  # loop-thread only
                    self._m_adm_rej.inc()
                    respond(503, {
                        "message": (
                            f"estimated fleet round-trip "
                            f"{est * 1e3:.1f}ms exceeds the "
                            f"{budget * 1e3:.1f}ms request budget"
                        ),
                        "error": "AdmissionRejected",
                    }, extra_headers=[("Retry-After", "1"),
                                      (TRACE_HEADER, tid)])
                    return
                self._m_adm_ok.inc()
            tl.mark("admission")
            pool = self._pool
            if pool is None:
                respond(503, {"message": "router is stopping"})
                return
            try:
                pool.submit(
                    self._forward_query, req.path, body, tid, respond,
                    tl, est,
                )
            except RuntimeError:
                respond(503, {"message": "router is stopping"})
            return
        if req.method == "POST" and path == "/admin/push-foldin":
            pool = self._pool
            if pool is None:
                respond(503, {"message": "router is stopping"})
                return

            def push():
                try:
                    respond(200, self.push_foldin())
                except RuntimeError:
                    pass
                except Exception as e:
                    logger.exception("push-foldin failed")
                    try:
                        respond(500, {"message": str(e)})
                    except RuntimeError:
                        pass

            try:
                pool.submit(push)
            except RuntimeError:
                respond(503, {"message": "router is stopping"})
            return
        if req.method == "POST" and path in ("/admin/tenants/weights",
                                             "/admin/tenants"):
            # pio-hive admin broadcast: a variant-weight update or a
            # tenant add/remove fans out to EVERY replica so the whole
            # fleet stays identical (sticky assignment is pure hash +
            # weights — same registry state everywhere == same variant
            # for every user everywhere)
            target = ("/tenants/weights"
                      if path == "/admin/tenants/weights"
                      else "/admin/tenants")
            self._broadcast_post(target, req.body, respond)
            return
        if req.method == "GET" and path == "/debug/tenants":
            # fleet view: each replica's registry document keyed by
            # replica name (one curl answers "which replica holds which
            # tenants resident, and what are the A/B rates")
            pool = self._pool
            if pool is None:
                respond(503, {"message": "router is stopping"})
                return

            def gather():
                out = {}
                for r in self.replicas:
                    try:
                        status, data, _ = r.request(
                            "GET", "/debug/tenants", None,
                            timeout_s=self.config.health_timeout_s,
                        )
                        out[r.name] = (
                            json.loads(data.decode()) if status == 200
                            else {"status": status}
                        )
                    except Exception as e:
                        out[r.name] = {
                            "error": f"{type(e).__name__}: {e}",
                        }
                try:
                    respond(200, {"replicas": out})
                except RuntimeError:
                    pass

            try:
                pool.submit(gather)
            except RuntimeError:
                respond(503, {"message": "router is stopping"})
            return
        if req.method == "POST" and path == "/stop":
            respond(200, {"message": "stopping"})
            threading.Thread(target=self.stop, daemon=True).start()
            return
        if req.method == "GET" and path == "/metrics":
            # pio-lens: the router's exposition is the FLEET's — local
            # registry state merged with every replica's last scraped
            # snapshot (counters/histograms sum, gauges labeled
            # {replica}); render on the pool, not the loop
            if not metrics_enabled():
                respond(404, {"message":
                              "metrics disabled (--no-metrics)"})
                return
            pool = self._pool
            if pool is None:
                respond(503, {"message": "router is stopping"})
                return

            def metrics():
                try:
                    respond(200, self.render_fleet_metrics(),
                            ctype=PROMETHEUS_CTYPE)
                except RuntimeError:
                    pass

            try:
                pool.submit(metrics)
            except RuntimeError:
                respond(503, {"message": "router is stopping"})
            return
        if req.method == "GET" and path == "/debug/fleet":
            # the fleet tail view: per-replica p50/p99 + worst-N with
            # replica attribution; lazy replica /debug/flight fetches
            # block, so pool it
            pool = self._pool
            if pool is None:
                respond(503, {"message": "router is stopping"})
                return

            def dbg():
                try:
                    respond(200, self.fleet_payload())
                except RuntimeError:
                    pass
                except Exception as e:
                    logger.exception("/debug/fleet failed")
                    try:
                        respond(500, {"message": str(e)})
                    except RuntimeError:
                        pass

            try:
                pool.submit(dbg)
            except RuntimeError:
                respond(503, {"message": "router is stopping"})
            return
        if req.method == "GET":
            ans = observability_response(path, u.query)
            if ans is not None:
                # /debug/profile can block for seconds — pool, not loop
                pool = self._pool

                def obs():
                    code, payload, ctype = observability_response(
                        path, u.query
                    )
                    try:
                        respond(code, payload,
                                ctype=ctype or "application/json")
                    except RuntimeError:
                        pass

                if path == "/debug/profile" and pool is not None:
                    pool.submit(obs)
                else:
                    code, payload, ctype = ans
                    respond(code, payload,
                            ctype=ctype or "application/json")
                return
            if path == "/":
                respond(200, self.status_json())
                return
        respond(404, {"message": "not found"})


# -- replica process spawning ----------------------------------------------


def chip_pin_env(chip: int) -> dict:
    """Environment that gives a process exactly ONE TPU chip of this
    host.  libtpu reads these when it loads, so they must be in the
    child's environment before it imports jax: the visible chip, and
    process bounds of one chip so that several processes may load
    libtpu side by side, each on its own chip."""
    return {
        "TPU_VISIBLE_CHIPS": str(chip),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def spawn_replica(engine_json, index: int, coord_dir,
                  extra_args=(), env=None,
                  python: str = sys.executable,
                  engine_name=None, chip=None) -> dict:
    """Launch one replica as a real subprocess (`pio-tpu deploy` on an
    ephemeral port, announcing it through a port file in
    ``coord_dir``).  ``engine_name`` dispatches a pio-forge registry
    engine (``deploy --engine NAME``) instead of an engine.json path.
    ``chip`` pins the replica to that TPU chip (:func:`chip_pin_env`).
    Returns ``{"proc", "port_file", "log_path", "index"}``; pair with
    :func:`wait_for_port_file`."""
    coord_dir = Path(coord_dir)
    coord_dir.mkdir(parents=True, exist_ok=True)
    port_file = coord_dir / f"replica-{index}.port"
    log_path = coord_dir / f"replica-{index}.log"
    # the child must resolve predictionio_tpu regardless of caller cwd
    import os as _os

    pkg_root = str(Path(__file__).resolve().parent.parent.parent)
    env = dict(env if env is not None else _os.environ)
    if chip is not None:
        env.update(chip_pin_env(chip))
    pp = env.get("PYTHONPATH", "")
    if pkg_root not in pp.split(_os.pathsep):
        env["PYTHONPATH"] = (
            pkg_root + (_os.pathsep + pp if pp else "")
        )
    engine_arg = (
        ["--engine", str(engine_name)] if engine_name
        else ["--engine-json", str(engine_json)]
    )
    cmd = [
        python, "-m", "predictionio_tpu.cli.main", "deploy",
        *engine_arg,
        "--ip", "127.0.0.1", "--port", "0",
        "--port-file", str(port_file),
        *extra_args,
    ]
    log_f = open(log_path, "w")
    proc = subprocess.Popen(
        cmd, stdout=log_f, stderr=subprocess.STDOUT, env=env,
    )
    log_f.close()
    return {"proc": proc, "port_file": port_file,
            "log_path": log_path, "index": index}


def wait_for_port_file(spawned: dict, timeout_s: float = 180.0) -> int:
    """Block until the replica announces its bound port (or dies)."""
    port_file = spawned["port_file"]
    proc = spawned["proc"]
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if port_file.exists():
            text = port_file.read_text().strip()
            if text:
                return int(text)
        if proc.poll() is not None:
            tail = ""
            try:
                tail = Path(spawned["log_path"]).read_text()[-2000:]
            except OSError:
                pass
            raise RuntimeError(
                f"replica {spawned['index']} exited rc={proc.returncode} "
                f"before announcing a port; log tail:\n{tail}"
            )
        time.sleep(0.05)
    raise TimeoutError(
        f"replica {spawned['index']} did not announce a port within "
        f"{timeout_s}s"
    )
