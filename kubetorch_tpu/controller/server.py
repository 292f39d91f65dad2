"""Controller service: pool registry, pod WebSocket hub, runs, TTL reaper.

Reference: ``services/kubetorch_controller/`` — ``routes/pool.py:39``
(register_pool), ``routes/ws_pods.py`` (PodConnectionManager, metadata push
with acks, pods-connect-before-pool-exists), ``routes/runs.py``,
``ttl_controller.py`` (inactivity reaper). This is the most stateful protocol
in the system (SURVEY.md §7 hard-part 1); the semantics kept exactly:

- pods open a persistent WS and register (service name, pod name, url);
- a pod whose pool doesn't exist yet parks as "waiting" and is matched when
  the pool registers (``try_match_pod_to_pool:386``);
- ``POST /pool`` upserts the pool row and broadcasts the module metadata to
  every connected pod of that service, then waits for per-pod acks;
- pods report activity (requests served) which feeds the TTL reaper;
- the reaper tears down services idle past their ``inactivity-ttl``.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import re
import time
import uuid
from typing import Any, Dict, List, Optional

import aiohttp
from aiohttp import ClientSession, WSMsgType, web

from kubetorch_tpu.config import env_bool, env_float, env_int, env_str
from kubetorch_tpu.controller.db import Database
from kubetorch_tpu.version import __version__, compatible

logger = logging.getLogger(__name__)


def parse_ttl(ttl: Optional[str]) -> Optional[float]:
    """'30m' / '2h' / '45s' / '1d' → seconds."""
    if not ttl:
        return None
    m = re.fullmatch(r"(\d+(?:\.\d+)?)([smhd]?)", str(ttl).strip())
    if not m:
        return None
    value = float(m.group(1))
    return value * {"": 1, "s": 1, "m": 60, "h": 3600, "d": 86400}[m.group(2)]


def _tree_names(assembled: Dict[str, Any]) -> List[dict]:
    """Compact nested view of an assembled trace (names + ms, not the
    full span dicts — those ride next to it in the same response)."""

    def node(n):
        s = n["span"]
        return {"name": s.get("name"), "span_id": s.get("span_id"),
                "proc": "/".join(p for p in (s.get("pod"),
                                             s.get("proc")) if p),
                "ms": round(s.get("dur", 0.0) * 1e3, 3),
                "children": [node(c) for c in n["children"]]}

    return [node(r) for r in assembled.get("roots", [])]


class PodConnection:
    def __init__(self, ws: web.WebSocketResponse, info: Dict[str, Any]):
        self.ws = ws
        self.pod_name = info.get("pod_name", "")
        self.service_name = info.get("service_name", "")
        self.url = info.get("url", "")
        self.connected_at = time.time()
        self.acks: Dict[str, asyncio.Future] = {}
        # setup status pushed by the pod ("status" messages): lets launch
        # waiters fail fast on terminal setup errors even on backends that
        # can't reach pod IPs directly (k8s readinessProbe only sees a
        # failing probe, not the reason).
        self.ready = bool(info.get("ready", False))
        self.setup_error = info.get("setup_error")
        # the deploy generation this pod belongs to (KT_LAUNCH_ID): lets
        # launch waiters ignore a terminating pod from a previous deploy of
        # the same service name whose stale setup_error would otherwise
        # abort a healthy relaunch.
        self.launch_id = info.get("launch_id", "")


class PodHub:
    """Connection manager (reference: ws_pods.py:47 PodConnectionManager)."""

    def __init__(self):
        # service -> {pod_name: PodConnection}; "" service = waiting pods
        self.by_service: Dict[str, Dict[str, PodConnection]] = {}
        self.waiting: Dict[str, PodConnection] = {}

    def register(self, conn: PodConnection, pool_exists: bool):
        if conn.service_name and pool_exists:
            self.by_service.setdefault(conn.service_name, {})[
                conn.pod_name] = conn
        else:
            self.waiting[conn.pod_name] = conn

    def match_waiting(self, service_name: str) -> List[PodConnection]:
        """Adopt parked pods when their pool appears (try_match_pod_to_pool)."""
        matched = []
        for pod_name, conn in list(self.waiting.items()):
            if conn.service_name == service_name:
                self.by_service.setdefault(service_name, {})[pod_name] = conn
                del self.waiting[pod_name]
                matched.append(conn)
        return matched

    def remove(self, conn: PodConnection):
        """Remove THIS connection only: re-registration is idempotent
        (a reconnecting pod replaces its entry by name), so a stale
        half-dead socket's teardown must not evict the replacement that
        already took the name — exactly the ws-flap shape."""
        if self.waiting.get(conn.pod_name) is conn:
            del self.waiting[conn.pod_name]
        pods = self.by_service.get(conn.service_name) or {}
        if pods.get(conn.pod_name) is conn:
            del pods[conn.pod_name]

    def pods_of(self, service_name: str) -> List[PodConnection]:
        return list((self.by_service.get(service_name) or {}).values())

    async def broadcast_metadata(
        self, service_name: str, metadata: Dict[str, Any],
        timeout: float = 120.0,
    ) -> Dict[str, bool]:
        """Push metadata/reload to every pod; resolve acks
        (reference: ws_pods.py:176 broadcast_to_service)."""
        pods = self.pods_of(service_name)
        results: Dict[str, bool] = {}
        loop = asyncio.get_running_loop()
        futures = []
        for conn in pods:
            reload_id = uuid.uuid4().hex[:8]
            fut = loop.create_future()
            conn.acks[reload_id] = fut
            try:
                await conn.ws.send_json({
                    "type": "metadata", "reload_id": reload_id,
                    "metadata": metadata})
                futures.append((conn, reload_id, fut))
            except (ConnectionError, RuntimeError):
                results[conn.pod_name] = False
        for conn, reload_id, fut in futures:
            try:
                ok = await asyncio.wait_for(fut, timeout)
                results[conn.pod_name] = bool(ok)
            except asyncio.TimeoutError:
                results[conn.pod_name] = False
            finally:
                conn.acks.pop(reload_id, None)
        return results


class ControllerServer:
    def __init__(self, db_path: str = ":memory:",
                 enable_reaper: bool = True,
                 reaper_interval: float = 15.0,
                 enable_resilience: bool = True,
                 rejoin_grace_s: Optional[float] = None):
        self.db = Database(db_path)
        self.hub = PodHub()
        self.enable_reaper = enable_reaper
        self.reaper_interval = reaper_interval
        self._reaper_task: Optional[asyncio.Task] = None
        # Resilience: heartbeat-fed liveness + gang-atomic auto-restart
        # (resilience/ subsystem; knobs KT_HEARTBEAT_S /
        # KT_DEAD_AFTER_MISSES / KT_MAX_RESTARTS / KT_AUTO_RESTART).
        from kubetorch_tpu.resilience.liveness import LivenessTracker
        from kubetorch_tpu.resilience.restart import (
            GangRestarter,
            RestartPolicy,
        )

        self.enable_resilience = enable_resilience
        self.liveness = LivenessTracker(
            on_transition=self._on_liveness_transition)
        self.restart_policy = RestartPolicy(
            persist=self.db.save_restart_state)
        self.restarter = GangRestarter(
            self.restart_policy, on_event=self._resilience_event)
        self.auto_restart = env_bool("KT_AUTO_RESTART")
        # Rejoin quarantine (ISSUE 15): a controller that restored
        # durable state is looking at a fleet it hasn't heard from yet —
        # for KT_REJOIN_GRACE_S (default 2.5 heartbeat intervals) the
        # resilience sweep observes but never declares dead and never
        # gang-restarts, so reconnecting pods get time to beat before
        # anything irreversible happens.
        grace = (rejoin_grace_s if rejoin_grace_s is not None
                 else env_float("KT_REJOIN_GRACE_S"))
        if grace is None:
            grace = 2.5 * self.liveness.heartbeat_s
        self.rejoin_grace_s = max(0.0, float(grace))
        self._started_mono = time.monotonic()
        self._resilience_task: Optional[asyncio.Task] = None
        self._restarting: set = set()
        # strong refs to in-flight restart tasks: the loop only holds
        # weak ones, and a GC'd restart would leave its service wedged
        # in _restarting forever (the finally never runs)
        self._restart_tasks: set = set()
        self._loop_errors: set = set()  # sweep errors already reported
        # last dead-detection per service: survives the gang restart
        # (which forgets the per-pod liveness state) so /health can
        # always answer "when did we last notice, and how fast"
        self._last_detect: Dict[str, dict] = {}
        self.auth_token = env_str("KT_CONTROLLER_TOKEN")
        # External token validation (reference: auth/middleware.py — bearer
        # validated against an endpoint, with namespace access checks).
        self.auth_validate_url = env_str("KT_AUTH_VALIDATE_URL")
        self._auth_cache: Dict[str, Any] = {}   # token -> (exp_ts, info|None)
        self._auth_session = None
        self.auth_cache_ttl = env_float("KT_AUTH_CACHE_TTL")
        self.cluster_config: Dict[str, Any] = {}
        # Controller-hosted observability sinks (SURVEY.md §5.5; reference
        # deploys Loki + Prometheus as separate components, both durable —
        # values.yaml logStreaming/metrics). Durability here: JSONL log
        # segments + metrics snapshot under KT_OBS_DIR (defaults to
        # <db>.obs/ next to a file-backed SQLite; in-memory DB ⇒ in-memory
        # sinks, e.g. tests).
        from kubetorch_tpu.observability.log_sink import LogSink, MetricsStore

        obs_dir = env_str("KT_OBS_DIR") or (
            f"{db_path}.obs" if db_path != ":memory:" else None)
        persist = snapshot = None
        if obs_dir:
            from pathlib import Path

            from kubetorch_tpu.observability.persist import (
                LogPersistence,
                MetricsSnapshot,
            )

            retain_mb = env_float("KT_LOG_RETAIN_MB")
            retain_h = env_float("KT_LOG_RETAIN_HOURS")
            persist = LogPersistence(
                Path(obs_dir) / "logs",
                retain_bytes=int(retain_mb * 1024 * 1024),
                retain_secs=retain_h * 3600.0,
                max_pending_batches=env_int("KT_LOG_MAX_PENDING"))
            snapshot = MetricsSnapshot(Path(obs_dir) / "metrics.json")
        self.log_sink = LogSink(persist=persist)
        self.metrics_store = MetricsStore(snapshot=snapshot)
        # Fleet telemetry plane: pods piggyback metric delta frames on
        # the heartbeat (WS message or POST /telemetry fallback); the
        # store retains per-(service, pod, metric) rings with counter-
        # reset splicing and serves cross-replica rollups — the sensor
        # layer the autoscaler/fleet router (ROADMAP item 5) reads.
        from kubetorch_tpu.observability.fleetstore import FleetStore
        from kubetorch_tpu.observability.slo import SLOEngine

        self.fleet = FleetStore()
        self.slo = SLOEngine(self.fleet, on_event=self._slo_event)
        # Fleet autoscaler (ISSUE 20): the loop that closes ROADMAP
        # item 5 — reads the fleet rollups + SLO burn above, decides
        # per-service (per-tier) replica counts, actuates through the
        # provisioning backend, and persists every decision/cooldown in
        # the controller DB so a restart resumes instead of flapping.
        # The scaler OBJECT always exists (ktpu scale's manual override
        # routes through it); only the automatic tick is gated on
        # KT_SCALE_ENABLE.
        from kubetorch_tpu.controller.router import RouterStats
        from kubetorch_tpu.provisioning.scaler import FleetScaler

        self.scale_enable = env_bool("KT_SCALE_ENABLE")
        self.scaler = FleetScaler(
            self.db, self.fleet, slo=self.slo,
            restart_policy=self.restart_policy,
            grace_remaining=self.rejoin_grace_remaining,
            on_event=self._resilience_event,
            actuate_in_thread=True)
        self.router_stats = RouterStats()
        # blind-polling fix: /metrics/query/{service} responses carry
        # per-pod staleness + counter-reset annotations from the fleet
        # store ("reset 12 s ago", not a silent rate glitch)
        self.metrics_store.annotate = self.fleet.pod_annotations
        # Cross-pod trace assembly: pods push span batches (slow-call
        # auto-capture, or ktpu trace pulls + re-posts) and a
        # multi-worker fan-out call renders as ONE tree even though no
        # single pod ever held all of its spans.
        from kubetorch_tpu.observability.tracing import TraceStore

        self.trace_store = TraceStore()
        # cluster events → log sink (reference: event_watcher.py → Loki
        # under job="kubetorch-events"); only when k8s creds exist.
        from kubetorch_tpu.controller.event_watcher import EventWatcher

        k8s = None
        try:
            from kubetorch_tpu.provisioning.k8s_client import K8sClient

            if K8sClient.has_credentials():
                k8s = K8sClient.from_env()
        except Exception:
            k8s = None
        self.event_watcher = EventWatcher(
            self.log_sink, k8s_client=k8s,
            list_services=self.db.list_pools)
        # Crash safety (ISSUE 15): resume from the durable tables — a
        # controller restart must be a non-event for the fleet. Liveness
        # entries re-seed the tracker (ages restart from NOW; the rejoin
        # grace covers the gap), restart budgets + backoff deadlines
        # carry over (a crash-looping controller hands out zero free
        # restarts), runtime-registered SLOs re-register, and the last
        # dead-detection records keep /health answering history.
        self._rejoined = self._restore_persisted_state()
        self._rejoins_total = int(
            self.db.get_meta("controller_rejoins_total", "0") or 0)
        if self._rejoined:
            self._rejoins_total = self.db.bump_meta_counter(
                "controller_rejoins_total")

    def _restore_persisted_state(self) -> bool:
        """Reload liveness/restart/SLO state from the database; returns
        True when any prior state existed (this start is a REJOIN, so
        the quarantine window applies)."""
        from kubetorch_tpu.observability.slo import Objective

        restored = 0
        for row in self.db.load_liveness():
            try:
                if self.liveness.restore(row["service"], row["pod"],
                                         row["state"]):
                    restored += 1
            except Exception as exc:  # noqa: BLE001 — one bad row must not
                logger.debug("liveness restore of %r failed: %r",
                             dict(row), exc)   # block the rest
        states = self.db.load_restart_states()
        restored += self.restart_policy.restore(states)
        for service, state in states.items():
            detect = state.get("last_detect")
            if isinstance(detect, dict):
                self._last_detect[service] = detect
        for spec in self.db.load_slos():
            try:
                self.slo.register(Objective.from_dict(spec),
                                  source="runtime")
                restored += 1
            except Exception as exc:  # noqa: BLE001
                logger.debug("SLO restore of %r failed: %r", spec, exc)
        try:
            # restored scaler state is a rejoin too: remembered desired
            # replica counts must sit out the quarantine before the
            # scale loop acts on a fleet this incarnation never measured
            restored += len(self.db.load_scaler_states())
        except Exception as exc:  # noqa: BLE001
            logger.debug("scaler state count failed: %r", exc)
        return restored > 0

    def rejoin_grace_remaining(self) -> float:
        """Seconds left in the rejoin quarantine (0 on a fresh-state
        controller: with nothing restored there is nothing stale to
        mis-judge — a dead verdict still needs KT_DEAD_AFTER_MISSES
        freshly-missed beats)."""
        if not self._rejoined:
            return 0.0
        return max(0.0, self.rejoin_grace_s
                   - (time.monotonic() - self._started_mono))

    # ------------------------------------------------------------- app
    def build_app(self) -> web.Application:
        middlewares = []
        if self.auth_token or self.auth_validate_url:
            middlewares.append(self._mw_auth)
        app = web.Application(middlewares=middlewares,
                              client_max_size=256 * 1024**2)
        r = app.router
        r.add_get("/health", self.h_health)
        r.add_get("/config", self.h_config)
        r.add_post("/pool", self.h_register_pool)
        r.add_get("/pool/{service}", self.h_get_pool)
        r.add_get("/pools", self.h_list_pools)
        r.add_delete("/pool/{service}", self.h_teardown_pool)
        r.add_post("/pool/{service}/activity", self.h_activity)
        r.add_post("/heartbeat", self.h_heartbeat)
        r.add_post("/telemetry", self.h_telemetry)
        r.add_get("/metrics/fleet/{service}", self.h_fleet)
        r.add_get("/metrics/fleet/{service}/range", self.h_fleet_range)
        r.add_post("/route/generate", self.h_route_generate)
        r.add_get("/scale", self.h_scale_status)
        r.add_get("/scale/{service}", self.h_scale_status)
        r.add_post("/scale/{service}", self.h_scale)
        r.add_delete("/scale/{service}", self.h_scale_auto)
        r.add_get("/slo", self.h_slo)
        r.add_get("/slo/{service}", self.h_slo)
        r.add_post("/slo", self.h_slo_register)
        r.add_get("/health/{service}", self.h_gang_health)
        r.add_get("/ws/pods", self.h_ws_pods)
        r.add_post("/traces", self.h_traces_push)
        r.add_get("/traces", self.h_traces_list)
        r.add_get("/traces/{trace_id}", self.h_trace_get)
        r.add_post("/runs", self.h_create_run)
        r.add_get("/runs", self.h_list_runs)
        r.add_get("/runs/{run_id}", self.h_get_run)
        r.add_patch("/runs/{run_id}", self.h_update_run)
        r.add_post("/runs/{run_id}/notes", self.h_add_note)
        r.add_post("/runs/{run_id}/artifacts", self.h_add_artifact)
        r.add_delete("/runs/{run_id}", self.h_delete_run)
        r.add_post("/apply", self.h_apply)
        r.add_post("/teardown/{service}", self.h_teardown_pool)
        # proxied K8s CRUD for clients without cluster credentials
        # (reference: routes/{pods,services,deployments,...}.py — here one
        # generic passthrough over the dynamic client)
        r.add_get("/k8s/{kind}", self.h_k8s_list)
        r.add_get("/k8s/{kind}/{name}", self.h_k8s_get)
        r.add_delete("/k8s/{kind}/{name}", self.h_k8s_delete)
        from kubetorch_tpu.observability import log_sink as _ls

        _ls.mount(app, self.log_sink, self.metrics_store)
        # controller-level gauges joining the /metrics scrape (pool count,
        # pod hub occupancy, log-buffer shedding — the /health numbers,
        # now PromQL-queryable)
        from kubetorch_tpu.observability import prometheus as _prom

        app._kt_prom_extra = lambda: [
            ("controller_pools", {}, len(self.db.list_pools())),
            ("controller_connected_pods", {},
             sum(len(p) for p in self.hub.by_service.values())),
            ("controller_waiting_pods", {}, len(self.hub.waiting)),
            # durable rejoin count (controller_meta table — a process-
            # local counter would reset with exactly the restart it
            # counts) + the live quarantine window
            ("controller_rejoins_total", {}, self._rejoins_total),
            ("controller_rejoin_grace_remaining_s", {},
             round(self.rejoin_grace_remaining(), 3)),
            ("controller_log_batches_dropped_total", {},
             getattr(self.log_sink.persist, "dropped_batches", 0)),
            # resilience_* counters (heartbeats, suspect/dead transitions,
            # preemptions, gang restarts) join the controller scrape
            *[(name, {}, value)
              for name, value in _prom.resilience_metrics().items()],
            # fleet rollups (per-service rates/sums/p99s) + slo_* gauges
            # join the same exposition — one scrape covers the plane
            *self.fleet.prom_samples(),
            *self.slo.prom_samples(),
            # scaler_* decision/flap/cold-start counters and router_*
            # dispatch counters — the autoscaling loop's own telemetry
            *self.scaler.prom_samples(),
            *self.router_stats.prom_samples(),
        ]
        app.on_startup.append(self._on_startup)
        app.on_shutdown.append(self._on_shutdown)
        return app

    async def _on_startup(self, app):
        # event-watcher pushes arrive from a plain thread; the sink marshals
        # them onto this loop for subscriber fan-out.
        self.log_sink.bind_loop()
        if self.enable_reaper:
            self._reaper_task = asyncio.create_task(self._reaper_loop())
        if self.enable_resilience:
            self._resilience_task = asyncio.create_task(
                self._resilience_loop())
        self.event_watcher.start()

    async def _on_shutdown(self, app):
        if self._reaper_task:
            self._reaper_task.cancel()
        if self._resilience_task:
            self._resilience_task.cancel()
        self.event_watcher.stop()
        if self.log_sink.persist is not None:
            self.log_sink.persist.close()
        self.metrics_store.flush()
        if self._auth_session is not None and not self._auth_session.closed:
            await self._auth_session.close()

    @web.middleware
    async def _mw_auth(self, request: web.Request, handler):
        if request.path == "/health":
            return await handler(request)
        header = request.headers.get("Authorization", "")
        if not header.startswith("Bearer "):
            return web.json_response({"error": "unauthorized"}, status=401)
        token = header[len("Bearer "):]
        import hmac

        if self.auth_token and hmac.compare_digest(
                token.encode(), self.auth_token.encode()):
            request["auth"] = {"username": "static", "namespaces": None}
            return await handler(request)
        if self.auth_validate_url:
            info = await self._validate_token(token)
            if info is not None:
                request["auth"] = info
                return await handler(request)
        return web.json_response({"error": "unauthorized"}, status=401)

    @staticmethod
    def _ns_denied(request, namespace) -> Optional[web.Response]:
        """403 when the authenticated token is namespace-scoped and the
        request targets a namespace outside its set. Handlers that consume
        a namespace call this with the value they actually act on — the
        enforcement point is the action, not a client-supplied query
        string. A scoped token MUST name an allowed namespace: a missing
        namespace would otherwise fall through to the cluster default,
        silently escaping the scope."""
        allowed = (request.get("auth") or {}).get("namespaces")
        if allowed is not None and namespace not in allowed:
            return web.json_response(
                {"error": f"namespace {namespace!r} not allowed"},
                status=403)
        return None

    _AUTH_CACHE_MAX = 4096   # junk-token flood must not grow memory unbounded

    async def _validate_token(self, token: str) -> Optional[Dict[str, Any]]:
        """Validate a bearer against the external endpoint, with caching.

        The endpoint receives the token as its own bearer and returns 200
        with optional ``{"username", "namespaces"}`` JSON on success.
        Failures (non-200 or unreachable) deny access; denials are cached
        too so a bad token cannot hammer the validator.
        """
        now = time.time()
        cached = self._auth_cache.get(token)
        if cached and cached[0] > now:
            return cached[1]
        info: Optional[Dict[str, Any]] = None
        try:
            if self._auth_session is None or self._auth_session.closed:
                self._auth_session = ClientSession(
                    timeout=aiohttp.ClientTimeout(total=5.0))
            async with self._auth_session.get(
                    self.auth_validate_url,
                    headers={"Authorization": f"Bearer {token}"}) as resp:
                if resp.status == 200:
                    try:
                        body = await resp.json()
                    except Exception:
                        body = {}
                    info = {"username": (body or {}).get("username", ""),
                            "namespaces": (body or {}).get("namespaces")}
        except Exception:
            info = None
        if len(self._auth_cache) >= self._AUTH_CACHE_MAX:
            # evict expired first; if still full, drop the oldest-expiring
            self._auth_cache = {
                k: v for k, v in self._auth_cache.items() if v[0] > now}
            while len(self._auth_cache) >= self._AUTH_CACHE_MAX:
                self._auth_cache.pop(next(iter(self._auth_cache)))
        self._auth_cache[token] = (now + self.auth_cache_ttl, info)
        return info

    # -------------------------------------------------------- handlers
    async def h_health(self, request):
        client_version = request.query.get("client_version")
        ok = (compatible(client_version, __version__)
              if client_version else True)
        return web.json_response({
            "status": "ok", "version": __version__,
            "compatible": ok,
            "pools": len(self.db.list_pools()),
            "connected_pods": sum(
                len(p) for p in self.hub.by_service.values()),
            "waiting_pods": len(self.hub.waiting),
            # log batches shed by the bounded persist buffer under flood
            # (0 in healthy operation) — watch this before raising caps
            "log_batches_dropped": getattr(
                self.log_sink.persist, "dropped_batches", 0),
        })

    async def h_config(self, request):
        """Cluster-level config layer (ConfigMap analog)."""
        return web.json_response(self.cluster_config)

    async def h_register_pool(self, request):
        """The core deploy RPC (reference: routes/pool.py:39 register_pool)."""
        body = await request.json()
        service = body["service_name"]
        denied = self._ns_denied(request, body.get("namespace", "default"))
        if denied is not None:
            return denied
        pool = self.db.upsert_pool(
            service,
            namespace=body.get("namespace", "default"),
            username=body.get("username"),
            module_meta=body.get("module_meta") or {},
            compute=body.get("compute") or {},
            backend=body.get("backend", "local"),
            launch_id=body.get("launch_id"),
            inactivity_ttl=(body.get("compute") or {}).get("inactivity_ttl"),
        )
        self.hub.match_waiting(service)
        acks = {}
        if body.get("broadcast", True):
            acks = await self.hub.broadcast_metadata(
                service, body.get("module_meta") or {},
                timeout=float(body.get("ack_timeout", 120.0)))
        return web.json_response({"pool": pool, "acks": acks})

    async def h_get_pool(self, request):
        pool = self.db.get_pool(request.match_info["service"])
        if pool is None:
            raise web.HTTPNotFound(text="no such pool")
        pool["pods"] = [
            {"pod_name": c.pod_name, "url": c.url,
             "connected_at": c.connected_at, "ready": c.ready,
             "setup_error": c.setup_error, "launch_id": c.launch_id}
            for c in self.hub.pods_of(pool["service_name"])]
        return web.json_response(pool)

    async def h_list_pools(self, request):
        return web.json_response({"pools": self.db.list_pools()})

    async def h_teardown_pool(self, request):
        service = request.match_info["service"]
        pool = self.db.get_pool(service)
        denied = self._ns_denied(
            request, (pool or {}).get("namespace") or "default")
        if denied is not None:
            return denied
        deleted = self.db.delete_pool(service)
        self.log_sink.drop_stream(service)
        self.metrics_store.drop(service)
        self.fleet.drop(service)
        self.slo.drop_service(service)
        # a torn-down gang is not a dead gang: no liveness ghosts, no
        # restart budget carried over to a future service of this name —
        # in memory and in the durable crash-safety tables
        self.liveness.forget_service(service)
        self.restart_policy.reset(service)
        self.scaler.drop(service)
        self._last_detect.pop(service, None)
        self._drop_durable_state(service)
        # Cascading delete: backend resources (reference:
        # helpers/delete_helpers.py).
        try:
            from kubetorch_tpu.provisioning.backend import get_backend

            get_backend().teardown(service, quiet=True)
        except Exception as exc:
            logger.debug("backend teardown during delete of %s failed: %r",
                         service, exc)
        for conn in self.hub.pods_of(service):
            try:
                await conn.ws.send_json({"type": "teardown"})
            except (ConnectionError, RuntimeError):
                pass
        return web.json_response({"deleted": deleted})

    async def h_activity(self, request):
        self.db.touch_pool(request.match_info["service"])
        return web.json_response({"ok": True})

    def _drop_durable_state(self, service: str) -> None:
        """Remove a service's crash-safety rows (teardown/reaper): a
        future service of this name starts with a clean slate."""
        try:
            self.db.delete_liveness(service)
            self.db.clear_restart_state(service)
            self.db.delete_slos(service)
            self.db.clear_scaler_state(service)
        except Exception as exc:  # noqa: BLE001 — teardown must complete
            logger.debug("durable-state drop for %s failed: %r",
                         service, exc)

    # ------------------------------------------------------- resilience
    async def h_heartbeat(self, request):
        """Pod liveness beat (HTTP form; WS-connected pods piggyback a
        ``{"type": "heartbeat"}`` message instead). Body:
        ``{"service", "pod", ["state"], ["info"]}``; ``state:
        "preempted"`` is a draining pod's explicit terminal report. A
        beat without identity is *corrupt* — rejected AND counted, so a
        chaos run (or a real serialization bug) shows on /metrics."""
        from kubetorch_tpu.observability import prometheus as prom

        try:
            body = await request.json()
        except Exception:  # noqa: BLE001
            body = None
        service = (body or {}).get("service")
        pod = (body or {}).get("pod")
        if not service or not pod:
            prom.record_resilience("corrupt_heartbeat")
            return web.json_response(
                {"error": "heartbeat needs service and pod"}, status=400)
        from kubetorch_tpu.resilience.liveness import PREEMPTED

        if (body or {}).get("state") == "preempted":
            self.liveness.mark(service, pod, PREEMPTED)
            return web.json_response({"ok": True, "state": PREEMPTED})
        prom.record_resilience("heartbeat")
        state = self.liveness.beat(service, pod, info=(body or {}).get("info"))
        # HTTP beats may carry a telemetry frame inline (same piggyback
        # contract as the WS message; the batched path is /telemetry)
        # same resync hint as the WS registration ack: a fleet store
        # that has never heard of this pod (fresh start OR controller
        # restart — the store is process memory) needs a FULL snapshot,
        # not deltas against nothing; the POST-fallback flush reads
        # this to decide between replaying its backlog and
        # snapshotting. Computed BEFORE the inline ingest below — that
        # frame would mark the pod known and mask the gap it rode in on
        resync = not self.fleet.knows(service, pod)
        telemetry = (body or {}).get("telemetry")
        if isinstance(telemetry, dict):
            self.fleet.ingest(service, pod, telemetry)
        return web.json_response({"ok": True, "state": state,
                                  "resync": resync})

    # ------------------------------------------------- fleet telemetry
    async def h_telemetry(self, request):
        """Batched telemetry ingest (the POST fallback for pods whose
        controller WS is down): ``{"service", "pod", "frames": [...]}``
        or a single ``"frame"``. Frames ingest in order; a garbled
        frame ingests what it can (see FleetStore.ingest)."""
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001
            return web.json_response({"error": "bad json"}, status=400)
        service = (body or {}).get("service")
        pod = (body or {}).get("pod")
        if not service or not pod:
            return web.json_response(
                {"error": "telemetry needs service and pod"}, status=400)
        frames = (body or {}).get("frames")
        if not isinstance(frames, list):
            frame = (body or {}).get("frame")
            frames = [frame] if isinstance(frame, dict) else []
        n = 0
        for frame in frames:
            if isinstance(frame, dict):
                n += self.fleet.ingest(service, pod, frame)
        return web.json_response({"ingested": n, "frames": len(frames)})

    async def h_fleet(self, request):
        """Cross-pod rollups over a trailing window
        (``?window=<seconds>``): counter rates/increases, gauge sums
        over non-stale pods, bucket-merged histogram quantiles, and
        per-pod staleness/reset annotations."""
        service = request.match_info["service"]
        try:
            window = float(request.query.get("window", 60) or 60)
        except ValueError:
            return web.json_response({"error": "bad window"}, status=400)
        if service not in self.fleet.services() \
                and self.db.get_pool(service) is None:
            raise web.HTTPNotFound(text="no such service")
        return web.json_response(self.fleet.fleet(service,
                                                  window_s=window))

    async def h_route_generate(self, request):
        """Phase-aware routing for disaggregated prefill/decode
        (ISSUE 17). Body: ``{"service", "prefix_hit": bool,
        "exclude": [pods], "handoff_id": optional}``. The controller
        only BROKERS the pairing — the prefill pod pushes the exported
        row directly at the decode pod's store endpoint; no row bytes
        transit here.

        Routing rules, off the fleet rollup's ``engine_phase`` /
        ``engine_row_eta_seconds`` / ``engine_queue_depth`` by-pod
        gauges (stale and excluded pods never routable):

        - ``prefix_hit`` + a decode tier → ``decode-only``: a
          full-prefix hit's KV already lives tier-local on the decode
          pod — skipping the prefill tier beats shipping a row whose
          blocks are already there. Target: earliest expected row-free
          time (PR 14's speculation-aware pricing, gauged by the
          engine).
        - a prefill AND a decode tier → ``disagg``: prefill target by
          shallowest queue (prefill is compute-bound: queue depth IS
          its backlog), decode target by earliest row-free ETA.
        - otherwise → ``monolithic`` to the min-ETA mixed pod (or any
          live pod) — also the re-route fallback when chaos/drop took
          the decode tier out (``exclude``): the exported blob is still
          in the store, and a mixed pod can import it.

        ISSUE 20 lifts the selection policy into
        ``controller.router.select_route`` (pure, testable alone) and
        adds two fleet behaviors here: per-pod admission sheds become
        router-visible backpressure (a shedding pod is deprioritized
        within its tier), and a routable-pod MISS on an autoscaled
        service parks the program — 202 + ``Retry-After`` — behind a
        scale-from-zero ask instead of erroring. Non-autoscaled
        services keep the 503.
        """
        from kubetorch_tpu.controller.router import select_route

        try:
            body = await request.json()
        except Exception:  # noqa: BLE001
            return web.json_response({"error": "bad json"}, status=400)
        service = (body or {}).get("service")
        if not service:
            return web.json_response(
                {"error": "route needs service"}, status=400)
        prefix_hit = bool((body or {}).get("prefix_hit"))
        exclude = set((body or {}).get("exclude") or [])
        # the handoff id is minted HERE (idempotent echo on re-routes):
        # prefill and decode pod must agree on the store key before
        # either has seen the program
        hid = ((body or {}).get("handoff_id")
               or "h-" + uuid.uuid4().hex[:16])
        route = select_route(self.fleet.fleet(service),
                             prefix_hit=prefix_hit, exclude=exclude,
                             stats=self.router_stats)
        if route is not None:
            route["handoff_id"] = hid
            return web.json_response(route)
        if self.scale_enable and self.db.get_pool(service) is not None:
            ask = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self.scaler.request_capacity(service))
            if ask.get("ok"):
                self.router_stats.parked_total += 1
                retry = float(ask.get("retry_after_s")
                              or self.scaler.cold_start_budget_s)
                return web.json_response(
                    {"mode": "parked", "handoff_id": hid,
                     "desired": ask.get("desired"),
                     "retry_after_s": retry},
                    status=202,
                    headers={"Retry-After": str(max(1, int(retry)))})
        return web.json_response(
            {"error": f"no routable pods for {service}"},
            status=503)

    # ---------------------------------------------------------- scaling
    async def h_scale(self, request):
        """Operator scale pin (``ktpu scale <svc> <n>`` when the
        controller is reachable): body ``{"replicas": n}`` writes a
        durable manual-override row and actuates immediately through
        the service's provisioning backend. The pin outlives controller
        restarts and wins over the automatic loop until ``ktpu scale
        <svc> --auto`` (DELETE) clears it."""
        service = request.match_info["service"]
        pool = self.db.get_pool(service)
        if pool is None:
            raise web.HTTPNotFound(text="no such pool")
        denied = self._ns_denied(request,
                                 pool.get("namespace") or "default")
        if denied is not None:
            return denied
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001
            return web.json_response({"error": "bad json"}, status=400)
        replicas = (body or {}).get("replicas")
        if not isinstance(replicas, int) or replicas < 0:
            return web.json_response(
                {"error": "replicas must be a non-negative integer"},
                status=400)
        result = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.scaler.set_override(service, replicas,
                                                   pool))
        return web.json_response(result)

    async def h_scale_auto(self, request):
        """``ktpu scale <svc> --auto``: clear the manual override and
        hand the service back to the automatic loop."""
        service = request.match_info["service"]
        pool = self.db.get_pool(service)
        denied = self._ns_denied(
            request, (pool or {}).get("namespace") or "default")
        if denied is not None:
            return denied
        cleared = await asyncio.get_running_loop().run_in_executor(
            None, lambda: self.scaler.clear_override(service))
        return web.json_response({"cleared": cleared,
                                  "auto": self.scale_enable})

    async def h_scale_status(self, request):
        """Scaler view (all services or one): desired/actual replicas,
        override pins, cooldown/settle windows, recent decisions —
        what ``ktpu top`` joins into its replica columns."""
        service = request.match_info.get("service")
        return web.json_response({
            "enabled": self.scale_enable,
            "services": self.scaler.status(service),
            "decisions": self.db.load_scale_decisions(service,
                                                      limit=20),
        })

    async def h_fleet_range(self, request):
        """Aligned fleet series for ramps: ``?metrics=a,b&start=&end=
        &step=`` (epoch seconds; start defaults to 5 minutes back,
        step to 10 s, both clamped to the store's retention)."""
        service = request.match_info["service"]
        metrics = [m for m in
                   (request.query.get("metrics") or "").split(",") if m]
        if not metrics:
            return web.json_response(
                {"error": "metrics= is required (comma-separated)",
                 "available": self.fleet.metric_names(service)},
                status=400)
        try:
            start = request.query.get("start")
            end = request.query.get("end")
            result = self.fleet.range(
                service, metrics,
                start=float(start) if start else None,
                end=float(end) if end else None,
                step=float(request.query.get("step", 10) or 10))
        except ValueError:
            return web.json_response({"error": "bad range params"},
                                     status=400)
        return web.json_response(result)

    async def h_slo(self, request):
        """SLO status (all services, or one with ``/slo/{service}``):
        last-evaluated burn rates, budget remaining, breach state."""
        service = request.match_info.get("service")
        return web.json_response({
            "objectives": self.slo.status(service),
            "eval_ms": self.slo.last_eval_ms,
            "windows": {"fast_s": self.slo.fast_s,
                        "slow_s": self.slo.slow_s},
        })

    async def h_slo_register(self, request):
        """Per-service runtime registration (the KT_SLO env list covers
        static config): body is one objective dict."""
        from kubetorch_tpu.observability.slo import Objective

        try:
            body = await request.json()
        except Exception:  # noqa: BLE001
            return web.json_response({"error": "bad json"}, status=400)
        try:
            obj = Objective.from_dict(body or {})
        except (TypeError, ValueError) as exc:
            return web.json_response({"error": str(exc)}, status=400)
        denied = self._ns_denied(
            request, (self.db.get_pool(obj.service)
                      or {}).get("namespace") or "default")
        if denied is not None:
            return denied
        self.slo.register(obj)
        # runtime objectives are durable (ISSUE 15): a controller
        # restart re-registers them from the table — before this, every
        # POST /slo silently evaporated with the process
        try:
            self.db.save_slo(obj.service, obj.name, body or {})
        except Exception as exc:  # noqa: BLE001 — registration stands
            logger.debug("SLO persist for %s/%s failed: %r",
                         obj.service, obj.name, exc)
        return web.json_response({"registered": f"{obj.service}/{obj.name}"})

    def _slo_event(self, service: str, name: str, breached: bool,
                   status: dict):
        """Breach/recovery transitions land in the log sink next to
        the resilience events — `ktpu logs -f` shows them live."""
        if breached:
            msg = (f"SLO {name} breached: burn {status['burn_rate']}x "
                   f"(fast {status['window_fast_s']:g}s) / "
                   f"{status['burn_rate_slow']}x (slow), budget "
                   f"remaining {status['error_budget_remaining']}")
        else:
            msg = (f"SLO {name} recovered: burn {status['burn_rate']}x "
                   f"below {status['burn_threshold']}x")
        self._resilience_event(service,
                               "SloBreach" if breached else "SloRecovered",
                               msg)

    async def h_gang_health(self, request):
        """Gang health for one service: per-pod liveness states + the
        gang-atomic verdict + restart bookkeeping."""
        service = request.match_info["service"]
        health = self.liveness.gang_health(service)
        pool = self.db.get_pool(service)
        if pool is None and not health["pods"]:
            raise web.HTTPNotFound(text="no such service")
        health["restarts"] = (pool or {}).get("restarts", 0)
        if service in self._last_detect:
            health["last_detect"] = self._last_detect[service]
        health["restart_attempts"] = self.restart_policy.attempts(service)
        health["max_restarts"] = self.restart_policy.max_restarts
        health["auto_restart"] = self.auto_restart
        grace = self.rejoin_grace_remaining()
        if grace > 0:
            # rejoin quarantine: verdicts are restored state, not fresh
            # observation — operators (and the e2e) can tell the window
            health["rejoin_grace_remaining_s"] = round(grace, 3)
        return web.json_response(health)

    def _on_liveness_transition(self, service, pod, old, new):
        """Every liveness state change: counters + sink events + the
        durable liveness row (transitions only — a steady-state beat
        never writes; registration, revival, suspect, dead, preempted
        all do, so a restarted controller resumes knowing the fleet)."""
        from kubetorch_tpu.observability import prometheus as prom
        from kubetorch_tpu.resilience import liveness as lv

        try:
            self.db.save_liveness(service, pod, new)
        except Exception as exc:  # noqa: BLE001 — durability is best-effort,
            logger.debug("liveness persist for %s/%s failed: %r",
                         service, pod, exc)   # tracking must go on
        if new == lv.SUSPECT:
            prom.record_resilience("suspect")
        elif new == lv.DEAD:
            prom.record_resilience("dead")
            state = (self.liveness.gang_health(service)["pods"]
                     .get(pod) or {})
            detect = state.get("detect_s")
            if detect:
                prom.record_resilience("last_detect_seconds", detect)
                self._last_detect[service] = {"pod": pod,
                                              "detect_s": detect,
                                              "at": time.time()}
                try:
                    self.db.save_last_detect(
                        service, self._last_detect[service])
                except Exception as exc:  # noqa: BLE001
                    logger.debug("last-detect persist for %s failed: %r",
                                 service, exc)
            self._resilience_event(
                service, "PodDead",
                f"missed {self.liveness.dead_after} heartbeats"
                + (f" (detected after {detect}s)" if detect else ""),
                pod=pod)
        elif new == lv.PREEMPTED:
            prom.record_resilience("preempted")
            self._resilience_event(service, "PodPreempted",
                                   "pod reported SIGTERM drain", pod=pod)

    def _resilience_event(self, service: str, reason: str, message: str,
                          pod: str = ""):
        """Recovery transitions land in the log sink next to the K8s
        events (same job label) — `ktpu logs -f` shows them live."""
        from kubetorch_tpu.controller.event_watcher import resilience_event

        try:
            self.log_sink.push([resilience_event(service, reason, message,
                                                 pod=pod)])
        except Exception as exc:  # noqa: BLE001 — events never block recovery
            logger.debug("resilience event push for %s failed: %r",
                         service, exc)

    async def _resilience_loop(self):
        """Age liveness states and auto-restart dead gangs (gang-atomic:
        the whole worker set reprovisions). Sweeps at half the heartbeat
        interval so detection lag is bounded by beats missed, not by the
        sweeper."""
        interval = max(0.05, self.liveness.heartbeat_s / 2.0)
        while True:
            await asyncio.sleep(interval)
            try:
                await self._resilience_tick()
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # noqa: BLE001 — sweep must go on
                # a persistently-failing sweep silently disables
                # auto-restart; surface each distinct error ONCE as a
                # sink event so the operator sees why
                key = f"{type(exc).__name__}: {exc}"
                if key not in self._loop_errors:
                    self._loop_errors.add(key)
                    self._resilience_event(
                        "controller", "ResilienceSweepError", key)
                continue

    async def _resilience_tick(self):
        """One sweep: liveness aging, SLO evaluation, budget decay,
        auto-restarts. During the rejoin quarantine (a restarted
        controller inside KT_REJOIN_GRACE_S of its restored state) the
        tick OBSERVES — beats still revive, telemetry still ingests,
        SLOs still evaluate — but never ages a pod toward dead and
        never launches a gang restart: the restored last-seen stamps
        are this incarnation's start, not real silence, and acting on
        them is exactly the restart storm the quarantine prevents."""
        in_grace = self.rejoin_grace_remaining() > 0.0
        if not in_grace:
            self.liveness.sweep()
        # SLO burn-rate evaluation rides the same cadence: the
        # fast window reacts within ~2 sweeps of a regression
        # landing in the fleet store (e2e-asserted)
        self.slo.evaluate()
        # budget decay: a restarted gang that stays healthy for
        # KT_RESTART_RESET_S earns its restart budget back
        for service in self.liveness.services():
            health = self.liveness.gang_health(service)
            if self.restart_policy.note_health(
                    service, health["status"] == "healthy"):
                self._resilience_event(
                    service, "RestartBudgetRestored",
                    f"healthy {self.restart_policy.reset_after_s:g}s"
                    f" after restart; budget reset")
        # fleet scaler rides the same cadence (KT_SCALE_ENABLE), but
        # never inside the rejoin quarantine: restored last-seen stamps
        # make every pod look silent, and scaling on that is the same
        # storm the quarantine exists to prevent. The tick itself runs
        # in an executor (SQLite + rollup reads); slow backend
        # actuation detaches into its own thread inside the scaler.
        if self.scale_enable and not in_grace:
            await asyncio.get_running_loop().run_in_executor(
                None, self.scaler.tick)
        if not self.auto_restart or in_grace:
            return
        for service in self.liveness.dead_services():
            if service in self._restarting:
                continue
            pool = self.db.get_pool(service)
            if pool is None:
                # no pool to restart (torn down / never
                # registered): drop the stale liveness state so
                # the sweep stops reporting it
                self.liveness.forget_service(service)
                self.db.delete_liveness(service)
                continue
            delay = self.restart_policy.next_delay(service)
            if delay is None:
                if self.restart_policy.exhausted_once(service):
                    self._resilience_event(
                        service, "RestartBudgetExhausted",
                        f"gang stays down after "
                        f"{self.restart_policy.max_restarts} "
                        f"restarts")
                continue
            self._restarting.add(service)
            task = asyncio.create_task(
                self._restart_gang(service, pool, delay))
            self._restart_tasks.add(task)
            task.add_done_callback(self._restart_tasks.discard)

    async def _restart_gang(self, service, pool, delay: float):
        try:
            if delay:
                await asyncio.sleep(delay)
                if service not in self.liveness.dead_services():
                    # the gang revived during the backoff (a transient
                    # partition healed, beats resumed): restarting now
                    # would delete a healthy, serving gang
                    self.restart_policy.refund(service)
                    self._resilience_event(
                        service, "RestartSkipped",
                        f"gang revived during {delay:.1f}s backoff")
                    return
            result = await asyncio.get_running_loop().run_in_executor(
                None, lambda: self.restarter.restart(service, pool))
            if result.get("ok"):
                self.db.record_restart(service)
                # fresh generation: liveness restarts from a clean slate
                # (pods re-register and beat again) — in memory AND in
                # the durable table, or a controller crash right after
                # this restart would resurrect the dead old generation
                self.liveness.forget_service(service)
                self.db.delete_liveness(service)
        finally:
            self._restarting.discard(service)

    # ------------------------------------------------------------- WS
    async def h_ws_pods(self, request):
        ws = web.WebSocketResponse(heartbeat=30.0)
        await ws.prepare(request)
        conn: Optional[PodConnection] = None
        try:
            async for msg in ws:
                if msg.type != WSMsgType.TEXT:
                    continue
                data = json.loads(msg.data)
                mtype = data.get("type")
                if mtype == "register":
                    conn = PodConnection(ws, data)
                    pool = self.db.get_pool(conn.service_name)
                    self.hub.register(conn, pool is not None)
                    # resync flag (ISSUE 15): a controller whose fleet
                    # store has never heard of this pod (fresh start OR
                    # restart — the store is memory) would ingest delta
                    # frames against nothing and silently show gaps
                    # until the next KT_TELEMETRY_FULL_EVERY snapshot;
                    # the ack tells the pod to ship a FULL snapshot now
                    resync = bool(
                        conn.service_name
                        and not self.fleet.knows(conn.service_name,
                                                 conn.pod_name))
                    await ws.send_json({
                        "type": "registered",
                        "waiting": pool is None,
                        "metadata": (pool or {}).get("module_meta"),
                        "resync": resync,
                    })
                elif mtype == "ack" and conn is not None:
                    fut = conn.acks.get(data.get("reload_id", ""))
                    if fut is not None and not fut.done():
                        fut.set_result(data.get("ok", True))
                elif mtype == "status" and conn is not None:
                    conn.ready = bool(data.get("ready", False))
                    conn.setup_error = data.get("setup_error")
                    if data.get("launch_id"):
                        conn.launch_id = data["launch_id"]
                elif mtype == "activity" and conn is not None:
                    self.db.touch_pool(conn.service_name)
                elif mtype == "heartbeat" and conn is not None:
                    # liveness beat piggybacked on the pod WS (identity
                    # comes from the registration, so it can't be forged
                    # by a garbled payload — the HTTP path validates)
                    from kubetorch_tpu.observability import (
                        prometheus as prom,
                    )

                    prom.record_resilience("heartbeat")
                    self.liveness.beat(conn.service_name, conn.pod_name)
                    # telemetry piggyback: the beat's delta frame feeds
                    # the fleet store (identity from the registration,
                    # same unforgeability argument as the beat itself)
                    telemetry = data.get("telemetry")
                    if isinstance(telemetry, dict):
                        self.fleet.ingest(conn.service_name,
                                          conn.pod_name, telemetry)
                elif mtype == "preempted" and conn is not None:
                    from kubetorch_tpu.resilience.liveness import PREEMPTED

                    self.liveness.mark(conn.service_name, conn.pod_name,
                                       PREEMPTED)
        finally:
            if conn is not None:
                self.hub.remove(conn)
        return ws

    # ---------------------------------------------------------- traces
    async def h_traces_push(self, request):
        """Span ingestion (``{"spans": [...]}``): pods auto-push slow
        call trees here (KT_TRACE_SLOW_MS) and ``ktpu trace`` re-posts
        what it pulled so later queries see the assembled view."""
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001
            return web.json_response({"error": "bad json"}, status=400)
        n = self.trace_store.ingest((body or {}).get("spans") or [])
        return web.json_response({"ingested": n})

    async def h_traces_list(self, request):
        return web.json_response({"traces": self.trace_store.list()})

    async def h_trace_get(self, request):
        """One assembled trace across every pod that pushed spans for
        it. ``?format=perfetto`` returns Chrome trace_event JSON ready
        for ui.perfetto.dev; default is raw spans + the parent/child
        tree."""
        from kubetorch_tpu.observability import tracing as _tracing

        trace_id = request.match_info["trace_id"]
        spans = self.trace_store.get(trace_id)
        if not spans:
            raise web.HTTPNotFound(text="no such trace")
        if request.query.get("format") == "perfetto":
            return web.json_response(_tracing.to_trace_events(spans))
        return web.json_response({
            "trace_id": trace_id, "spans": spans,
            "tree": _tree_names(_tracing.assemble(spans)),
        })

    # ------------------------------------------------------------ runs
    async def h_create_run(self, request):
        body = await request.json()
        run = self.db.create_run(
            body["run_id"], command=body.get("command"),
            workdir_key=body.get("workdir_key"), env=body.get("env"),
            user=body.get("user"), status=body.get("status", "created"))
        return web.json_response({"run": run})

    async def h_list_runs(self, request):
        return web.json_response({"runs": self.db.list_runs()})

    async def h_get_run(self, request):
        run = self.db.get_run(request.match_info["run_id"])
        if run is None:
            raise web.HTTPNotFound(text="no such run")
        return web.json_response(run)

    async def h_update_run(self, request):
        body = await request.json()
        run = self.db.update_run(request.match_info["run_id"], **body)
        if run is None:
            raise web.HTTPNotFound(text="no such run")
        return web.json_response(run)

    async def h_add_note(self, request):
        body = await request.json()
        run = self.db.append_run_item(
            request.match_info["run_id"], "notes",
            {"ts": time.time(), **body})
        if run is None:
            raise web.HTTPNotFound(text="no such run")
        return web.json_response(run)

    async def h_add_artifact(self, request):
        body = await request.json()
        run = self.db.append_run_item(
            request.match_info["run_id"], "artifacts",
            {"ts": time.time(), **body})
        if run is None:
            raise web.HTTPNotFound(text="no such run")
        return web.json_response(run)

    async def h_delete_run(self, request):
        return web.json_response(
            {"deleted": self.db.delete_run(request.match_info["run_id"])})

    async def h_apply(self, request):
        """Manifest apply passthrough (k8s backend only). With
        ``patch="merge"`` performs a JSON merge-patch (partial update, e.g.
        replica scaling) instead of server-side apply."""
        body = await request.json()
        try:
            from kubetorch_tpu.provisioning.k8s_client import K8sClient

            client = K8sClient.from_env()
            manifest = body.get("manifest") or {}
            denied = self._ns_denied(
                request,
                (manifest.get("metadata") or {}).get("namespace")
                or env_str("KT_NAMESPACE"))
            if denied is not None:
                return denied
            if body.get("patch") == "merge":
                op = lambda: client.patch(manifest)  # noqa: E731
            else:
                op = lambda: client.apply(manifest)  # noqa: E731
            result = await asyncio.get_running_loop().run_in_executor(
                None, op)
            return web.json_response({"applied": result})
        except Exception as exc:
            return web.json_response(
                {"error": f"{type(exc).__name__}: {exc}"}, status=501)

    async def _k8s_op(self, request, op):
        """Run a dynamic-client operation in a worker thread. 501 when the
        controller has no cluster credentials (local/dev mode); real K8s
        errors surface as 502 so clients can tell them apart."""
        try:
            client = self._k8s_client()
        except Exception as exc:
            return web.json_response(
                {"error": f"no cluster credentials: {exc}"}, status=501)
        try:
            result = await asyncio.get_running_loop().run_in_executor(
                None, lambda: op(client))
            return web.json_response({"result": result})
        except Exception as exc:
            return web.json_response(
                {"error": f"{type(exc).__name__}: {exc}"}, status=502)

    def _k8s_client(self):
        """One cached dynamic client per controller (kubeconfig parsing and
        its CA temp file happen once, not per proxy request)."""
        if getattr(self, "_k8s", None) is None:
            from kubetorch_tpu.provisioning.k8s_client import K8sClient

            self._k8s = K8sClient.from_env()
        return self._k8s

    @staticmethod
    def _k8s_kind(request) -> dict:
        """Kind reference (with API group) from Kind/lowercase/plural."""
        from kubetorch_tpu.provisioning.k8s_client import kind_ref

        return kind_ref(request.match_info["kind"])

    def _k8s_ns(self, request):
        """Effective namespace for proxy ops (query param or the
        controller's default), for both the op and the scope check."""
        return request.query.get("namespace") or env_str("KT_NAMESPACE")

    async def h_k8s_list(self, request):
        kind = self._k8s_kind(request)
        ns = self._k8s_ns(request)
        denied = self._ns_denied(request, ns)
        if denied is not None:
            return denied
        selector = request.query.get("selector")
        return await self._k8s_op(
            request, lambda c: c.list(kind, namespace=ns,
                                      label_selector=selector or ""))

    async def h_k8s_get(self, request):
        kind = self._k8s_kind(request)
        name = request.match_info["name"]
        ns = self._k8s_ns(request)
        denied = self._ns_denied(request, ns)
        if denied is not None:
            return denied
        return await self._k8s_op(
            request, lambda c: c.get(kind, name, namespace=ns))

    async def h_k8s_delete(self, request):
        kind = self._k8s_kind(request)
        name = request.match_info["name"]
        ns = self._k8s_ns(request)
        denied = self._ns_denied(request, ns)
        if denied is not None:
            return denied
        return await self._k8s_op(
            request, lambda c: c.delete(kind, name, namespace=ns))

    # ------------------------------------------------------------- TTL
    async def _reaper_loop(self):
        """Tear down services idle past their TTL (reference:
        ttl_controller.py:49)."""
        while True:
            await asyncio.sleep(self.reaper_interval)
            try:
                now = time.time()
                for pool in self.db.list_pools():
                    ttl = parse_ttl(pool.get("inactivity_ttl"))
                    if ttl is None:
                        continue
                    last = pool.get("last_active") or pool["created_at"]
                    pushed = self.metrics_store.last_activity(
                        pool["service_name"])
                    if pushed:
                        last = max(last, pushed)
                    if now - last > ttl:
                        service = pool["service_name"]
                        self.db.delete_pool(service)
                        self.log_sink.drop_stream(service)
                        self.metrics_store.drop(service)
                        self.fleet.drop(service)
                        self.slo.drop_service(service)
                        self.liveness.forget_service(service)
                        self.restart_policy.reset(service)
                        self.scaler.drop(service)
                        self._last_detect.pop(service, None)
                        self._drop_durable_state(service)
                        try:
                            from kubetorch_tpu.provisioning.backend import (
                                get_backend,
                            )

                            get_backend().teardown(service, quiet=True)
                        except Exception as exc:
                            logger.debug(
                                "reaper teardown of %s failed: %r",
                                service, exc)
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                logger.debug("reaper sweep error: %r", exc)
                continue


def main():
    import argparse

    parser = argparse.ArgumentParser(description="kubetorch_tpu controller")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int,
                        default=env_int("KT_CONTROLLER_PORT"))
    parser.add_argument("--db", default=str(
        os.path.expanduser(env_str("KT_CONTROLLER_DB"))))
    parser.add_argument("--reaper-interval", type=float,
                        default=env_float("KT_REAPER_INTERVAL"))
    args = parser.parse_args()
    server = ControllerServer(args.db, reaper_interval=args.reaper_interval)
    web.run_app(server.build_app(), host=args.host, port=args.port,
                print=None, access_log=None)


if __name__ == "__main__":
    main()
