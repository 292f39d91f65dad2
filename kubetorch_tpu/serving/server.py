"""In-pod HTTP server: the workload-side runtime.

aiohttp analogue of the reference's FastAPI pod server
(``serving/http_server.py``): loads the user callable behind a supervisor,
serves ``POST /{name}[/{method}]``, health/readiness, metrics, reload, and an
``/http`` reverse proxy for App workloads. Middleware spine: request-ID
propagation (``:1237``), request metrics (``:1425``), termination check
(``:1184`` — SIGTERM'd pods answer with a typed PodTerminatedError).

Metadata arrives via env (KT_*) at start and via ``POST /_reload`` afterwards
(the controller's push-reload; reference does this over a pod WebSocket,
``serving/http_server.py:352 _handle_reload`` — we keep an HTTP route so pods
stay stateless; the controller WS client lives in ``controller_ws.py``).

This module must not import jax/torch: accelerator state belongs to the
worker subprocesses (see process_worker.py).
"""

from __future__ import annotations

import asyncio
import contextvars
import json
import os
import signal
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from aiohttp import ClientSession, WSMsgType, web

from kubetorch_tpu import serialization
from kubetorch_tpu.config import (env_bool, env_float, env_int, env_json,
                                  env_path, env_set, env_str)
from kubetorch_tpu.exceptions import (
    DeadlineExceeded,
    PodTerminatedError,
    ServerOverloaded,
    package_exception,
)
from kubetorch_tpu.observability import tracing
from kubetorch_tpu.serving.replay import SessionRegistry, retry_after_estimate
from kubetorch_tpu.serving.supervisor import supervisor_factory
from kubetorch_tpu.version import __version__

request_id_var: contextvars.ContextVar = contextvars.ContextVar(
    "kt_request_id", default="-")

_RESERVED = {"health", "ready", "metrics", "app", "http", "_reload",
             "_teardown", "_gpu", "_debug", "_profile", "_actors",
             "_channel", "_trace"}


def metadata_from_env() -> Dict[str, Any]:
    """Module metadata contract (mirrors reference env application at
    ``http_server.py:254 _apply_metadata``)."""
    meta: Dict[str, Any] = {
        "service_name": env_str("KT_SERVICE_NAME") or "unknown",
        "callable_name": env_str("KT_CLS_OR_FN_NAME"),
        "callable_type": env_str("KT_CALLABLE_TYPE"),
        "root_path": env_str("KT_ROOT_PATH"),
        "import_path": env_str("KT_IMPORT_PATH"),
        "name": env_str("KT_CALLABLE_NAME"),
        "num_procs": env_int("KT_NUM_PROCS"),
        "framework": env_str("KT_FRAMEWORK"),
        "replica_index": env_int("KT_REPLICA_INDEX"),
    }
    if env_set("KT_INIT_ARGS"):
        meta["init_args"] = env_json("KT_INIT_ARGS")
    if env_set("KT_DISTRIBUTED"):
        meta["distributed"] = env_json("KT_DISTRIBUTED")
    allowed = env_str("KT_ALLOWED_SERIALIZATION")
    if allowed:
        meta["allowed_serialization"] = tuple(allowed.split(","))
    app_cmd = env_str("KT_APP_CMD")
    if app_cmd:
        meta["app_cmd"] = app_cmd
        meta["app_port"] = env_int("KT_APP_PORT")
        meta["app_health_path"] = env_str("KT_APP_HEALTH_PATH")
    code_key = env_str("KT_CODE_KEY")
    if code_key:
        meta["code_key"] = code_key
        meta["code_store_url"] = env_str("KT_STORE_URL")
    return meta


class PodServer:
    def __init__(self, metadata: Optional[Dict[str, Any]] = None):
        self.metadata = metadata or metadata_from_env()
        self.supervisor = None
        self.app_proc: Optional[asyncio.subprocess.Process] = None
        self.terminating = False
        self.launch_id = env_str("KT_LAUNCH_ID")
        self.started_at = time.time()
        self.metrics: Dict[str, Any] = {
            "http_requests_total": 0,
            "http_request_errors_total": 0,
            "http_request_duration_seconds_sum": 0.0,
            "last_activity_timestamp": time.time(),
        }
        # per-process metric snapshots (group → worker pid → counter
        # dict; "server" = this process): *_total sums across processes
        # stay monotonic where a flat merge would flip between workers.
        # Groups: "data_store_restore" (weight-sync restore counters,
        # merged under a data_store_ prefix) and "serving" (call-path
        # counters, already serving_*-named).
        self._stats_by_proc: Dict[str, Dict[Any, Dict[str, float]]] = {}
        # named-histogram snapshots per process (worker piggyback next
        # to the flat groups): buckets/sum/count SUM across processes,
        # exemplars freshest-wins — the merged view renders on /metrics
        # and ships to the controller in telemetry frames
        self._hists_by_proc: Dict[Any, Dict[str, Any]] = {}
        # engine flight-recorder rings per worker process (piggybacked
        # increments, deduped by seq, bounded per proc): the pod is the
        # export surface (/_flight, the "flight" control op) and the
        # dump site (flight-<pid>.json on preemption) — workers die
        # with the pod's os._exit and cannot dump their own rings
        self._flight_by_proc: Dict[Any, List[dict]] = {}
        # fleet telemetry plane: the delta baseline (values last
        # shipped), the POST-fallback backlog (bounded — an unreachable
        # controller must not grow memory), and the frame counter that
        # schedules periodic full snapshots
        self._tele_sent: Dict[str, Any] = {}
        self._tele_backlog: list = []
        self._tele_frames = 0
        self.ready = False
        self.setup_error: Optional[str] = None
        self.controller_ws = None
        self._activity_task = None
        self._heartbeat_task = None
        # in-flight POST calls (the channel's in-flight depth lives in the
        # prometheus gauge): the preemption drain waits on both
        self._inflight_posts = 0
        # durable channel sessions (epoch → session): the FIFO queue,
        # in-flight executions, and result-retention ring survive a
        # dropped WebSocket so a reconnecting client can replay
        # (serving/replay.py). Event-loop-confined — no lock.
        self._channel_sessions = SessionRegistry(
            self._channel_execute,
            extra_depth=lambda: self._inflight_posts)
        # recent per-POST in-server seconds (EMA) — feeds the computed
        # Retry-After when admission control sheds a POST
        self._ema_server_s = 0.05
        self._actor_host = None
        self._actor_host_lock = threading.Lock()

    @property
    def actor_host(self):
        """Lazy: most pods never host actors (single-controller mode only,
        serving/actor_supervisor.py). Locked — concurrent first spawns from
        executor threads must not each build a host and orphan the loser's
        actor processes."""
        if self._actor_host is None:
            from kubetorch_tpu.serving.actor_host import ActorHost

            with self._actor_host_lock:
                if self._actor_host is None:
                    self._actor_host = ActorHost()
        return self._actor_host

    # ------------------------------------------------------------- app
    def build_app(self) -> web.Application:
        app = web.Application(
            middlewares=[self._mw_request_id, self._mw_termination,
                         self._mw_metrics],
            client_max_size=1024**3)
        app.router.add_get("/health", self.h_health)
        app.router.add_get("/ready", self.h_ready)
        app.router.add_get("/metrics", self.h_metrics)
        app.router.add_get("/_trace", self.h_trace)
        app.router.add_get("/_flight", self.h_flight)
        app.router.add_get("/app/status", self.h_app_status)
        app.router.add_get("/_channel", self.h_channel)
        app.router.add_post("/_reload", self.h_reload)
        app.router.add_post("/_teardown", self.h_teardown)
        app.router.add_get("/_debug/ws", self.h_debug_ws)
        app.router.add_get("/_debug/ui", self.h_debug_ui)
        app.router.add_post("/_profile/{action}", self.h_profile)
        app.router.add_route("*", "/http/{tail:.*}", self.h_proxy)
        app.router.add_post("/_actors/spawn", self.h_actor_spawn)
        app.router.add_get("/_actors", self.h_actor_list)
        app.router.add_delete("/_actors/{actor}", self.h_actor_stop)
        app.router.add_post("/_actors/{actor}/{method}", self.h_actor_call)
        app.router.add_post("/{callable}", self.h_call)
        app.router.add_post("/{callable}/{method}", self.h_call)
        app.on_startup.append(self._on_startup)
        app.on_shutdown.append(self._on_shutdown)
        return app

    async def _on_startup(self, app):
        from kubetorch_tpu.observability.log_capture import install_from_env

        tracing.set_process_label("pod-server")
        self.log_capture = install_from_env("pod")
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM,):
            try:
                loop.add_signal_handler(sig, self._mark_terminating)
            except NotImplementedError:
                pass
        controller_url = env_str("KT_CONTROLLER_URL")
        if controller_url:
            from kubetorch_tpu.serving.controller_ws import ControllerWebSocket

            self.controller_ws = ControllerWebSocket(self, controller_url)
            self.controller_ws.start()
            self._activity_task = asyncio.create_task(
                self._activity_loop(controller_url))
            self._heartbeat_task = asyncio.create_task(
                self._heartbeat_loop(controller_url))
        if self.metadata.get("callable_type") == "app":
            await self._start_app_cmd()
            if (self.metadata.get("app_health_path")
                    and self.metadata.get("app_port")):
                # Readiness gates on the app's own health endpoint
                # (reference: resources/compute/app.py:20 health_path +
                # app status handling in serving/http_server.py:1700) —
                # an App pod must not be "ready" the instant the
                # subprocess spawns.
                self._app_ready_task = asyncio.create_task(
                    self._app_readiness_loop())
            else:
                self.ready = True
            return
        if self.metadata.get("import_path"):
            # Setup in a thread: subprocess spawn + user imports are slow.
            await loop.run_in_executor(None, self._setup_supervisor)
        else:
            self.ready = True  # bare pod waiting for controller metadata push

    def _pull_code(self):
        """Fetch synced user code from the data store and point root_path
        at the local copy (reference: deploy rsync → pod-side pull). Runs
        before every supervisor (re)setup so push-reloads pick up deltas
        — the store's tree diff makes unchanged re-pulls near-free."""
        key = self.metadata.get("code_key")
        if not key:
            return
        from kubetorch_tpu.data_store.commands import workdir_sync

        # Per-pod dir: local-backend pods (and k8s pods on a shared
        # volume) would otherwise extract into one directory concurrently
        # and import half-written modules.
        pod = env_str("KT_POD_NAME") or str(env_int("KT_REPLICA_INDEX"))
        dest = (env_path("KT_CODE_DEST")
                / f"{self.metadata.get('service_name', 'svc')}-{pod}")
        # Prefer the store the CLIENT synced to (rides in the metadata and
        # push-reloads); env KT_STORE_URL is the fallback for pods whose
        # metadata predates the field.
        workdir_sync(key, dest,
                     store_url=self.metadata.get("code_store_url")
                     or env_str("KT_STORE_URL"))
        self.metadata["root_path"] = str(dest)

    def _setup_supervisor(self):
        try:
            self._pull_code()
            self.supervisor = supervisor_factory(self.metadata)
            self.supervisor.setup()
            self.ready = True
            self.setup_error = None
        except Exception as exc:  # surfaced via /ready
            self.setup_error = f"{type(exc).__name__}: {exc}"
            self.ready = False
        self._notify_status()

    def _notify_status(self):
        """Tell the controller about a ready/setup_error transition so
        launch waiters on probe-only backends (k8s) fail fast too."""
        ws = getattr(self, "controller_ws", None)
        if ws is not None:
            ws.notify_status()

    async def _on_shutdown(self, app):
        self._channel_sessions.expire_all()
        if getattr(self, "controller_ws", None) is not None:
            await self.controller_ws.stop()
        if getattr(self, "_activity_task", None) is not None:
            self._activity_task.cancel()
        if getattr(self, "_heartbeat_task", None) is not None:
            self._heartbeat_task.cancel()
        if getattr(self, "_app_ready_task", None) is not None:
            self._app_ready_task.cancel()
        if self.supervisor is not None:
            self.supervisor.cleanup()
        if self._actor_host is not None:
            self._actor_host.cleanup()
        if self.app_proc and self.app_proc.returncode is None:
            self.app_proc.terminate()

    async def _activity_loop(self, controller_url: str):
        """Push metrics + last-activity to the controller (metrics-push
        analog, reference: serving/metrics_push.py:20 — the snapshot lands in
        the controller MetricsStore and feeds the TTL reaper)."""
        import socket as _socket

        import aiohttp as _aiohttp

        service = self.metadata.get("service_name", "")
        pod = env_str("KT_POD_NAME") or _socket.gethostname()
        token = env_str("KT_CONTROLLER_TOKEN")
        headers = {"Authorization": f"Bearer {token}"} if token else {}
        last_reported = 0.0
        interval = env_float("KT_METRICS_INTERVAL")
        while True:
            await asyncio.sleep(interval)
            ts = self.metrics["last_activity_timestamp"]
            try:
                async with ClientSession(
                        timeout=_aiohttp.ClientTimeout(total=5.0),
                        headers=headers) as session:
                    await session.post(
                        f"{controller_url.rstrip('/')}/metrics/push",
                        json={"service": service, "pod": pod,
                              "metrics": dict(self.metrics)})
                    if ts > last_reported:
                        await session.post(
                            f"{controller_url.rstrip('/')}/pool/{service}"
                            f"/activity")
                        last_reported = ts
            except Exception:
                # unreachable controller: the next interval retries, but
                # the gap must be countable from the pod side
                self.metrics["controller_push_errors_total"] = (
                    self.metrics.get("controller_push_errors_total", 0) + 1)

    async def _heartbeat_loop(self, controller_url: str):
        """Liveness heartbeats to the controller every ``KT_HEARTBEAT_S``
        seconds — piggybacked on the controller WS when connected (one
        tiny text frame), else ``POST /heartbeat``. Stops once the pod is
        terminating: a draining pod must not look alive (the preemption
        handler reports ``preempted`` explicitly instead)."""
        import aiohttp as _aiohttp

        from kubetorch_tpu.resilience import chaos as chaos_mod
        from kubetorch_tpu.resilience.liveness import (
            heartbeat_interval,
            pod_identity,
        )

        service = self.metadata.get("service_name", "")
        pod = pod_identity()
        token = env_str("KT_CONTROLLER_TOKEN")
        headers = {"Authorization": f"Bearer {token}"} if token else {}
        url = f"{controller_url.rstrip('/')}/heartbeat"
        # ONE session for the life of the loop: a beat is a one-line POST
        # every few seconds for the pod's whole life — per-beat session +
        # TCP churn across a fleet is sustained load on the controller.
        # The POST fallback is bounded by KT_PUSH_TIMEOUT: a hung
        # controller holding a beat open must not outlive the SIGTERM
        # drain window (found via the slow-pod chaos kind).
        session = _aiohttp.ClientSession(
            timeout=_aiohttp.ClientTimeout(
                total=env_float("KT_PUSH_TIMEOUT")), headers=headers)
        tele_url = f"{controller_url.rstrip('/')}/telemetry"
        tele_every = env_int("KT_TELEMETRY_EVERY")
        beats = 0
        try:
            while not self.terminating:
                await asyncio.sleep(heartbeat_interval())
                if self.terminating:
                    return
                beats += 1
                corrupt = chaos_mod.maybe(chaos_mod.CORRUPT_HEARTBEAT, pod)
                # fleet telemetry piggyback: a compact delta frame of
                # the pod's changed counters/gauges + histogram buckets
                # rides every KT_TELEMETRY_EVERY-th beat. What a frame
                # costs a beat is not measured.
                telemetry = None
                if tele_every and beats % tele_every == 0:
                    try:
                        telemetry = self._telemetry_frame()
                    # ktlint: disable=KT004 -- liveness must beat even if telemetry breaks
                    except Exception:  # noqa: BLE001
                        telemetry = None
                ws = self.controller_ws
                if (not corrupt and ws is not None
                        and getattr(ws, "connected", False)):
                    # one WS text frame carries liveness AND metrics;
                    # the periodic full snapshot (KT_TELEMETRY_FULL_
                    # EVERY) heals any frame a dying socket swallowed.
                    # Any POST backlog from an outage is SUPERSEDED the
                    # moment the WS path resumes — replaying those old
                    # cumulative values later would read as counter
                    # steps-DOWN at the controller (false resets)
                    self._tele_backlog.clear()
                    ws.notify_heartbeat(telemetry=telemetry)
                    continue
                if telemetry is not None:
                    # every POST-path frame enters the bounded backlog
                    # BEFORE anything can fail (the frame build already
                    # advanced the delta baseline — a frame lost here is
                    # data the controller never sees until the next full
                    # snapshot): it leaves only on confirmed delivery or
                    # superseded by a resync snapshot; cap-shed frames
                    # count as dropped
                    self._tele_backlog.append(telemetry)
                    overflow = len(self._tele_backlog) - 30
                    if overflow > 0:
                        del self._tele_backlog[:overflow]
                        self.metrics[
                            "telemetry_backlog_dropped_total"] = (
                            self.metrics.get(
                                "telemetry_backlog_dropped_total", 0)
                            + overflow)
                # a corrupted beat (chaos) ships a payload with no
                # identity — the controller must reject it AND count it
                payload = ({"garbage": True} if corrupt
                           else {"service": service, "pod": pod})
                try:
                    # release the response or the pooled connection never
                    # returns to the session (per-beat TCP churn is what
                    # the single session exists to avoid)
                    resync = True
                    async with session.post(url, json=payload) as resp:
                        raw = await resp.read()
                        if resp.status < 400:
                            # the beat response carries the controller's
                            # resync hint (see h_heartbeat); anything
                            # unparseable reads as "resync" — a full
                            # snapshot is always SAFE, deltas are not
                            try:
                                resync = bool(
                                    json.loads(raw).get("resync", True))
                            except (ValueError, TypeError,
                                    AttributeError):
                                resync = True
                    flush = (self._tele_flush_frames(resync)
                             if not corrupt and self._tele_backlog
                             else [])
                    if flush:
                        async with session.post(tele_url, json={
                                "service": service, "pod": pod,
                                "frames": flush,
                        }) as resp:
                            if resp.status < 400:
                                # delta replay confirmed delivered (a
                                # resync flush already cleared — the
                                # hint re-fires until a full LANDS)
                                if not resync:
                                    self._tele_backlog.clear()
                            else:
                                self.metrics[
                                    "telemetry_send_errors_total"] = (
                                    self.metrics.get(
                                        "telemetry_send_errors_total", 0)
                                    + 1)
                except Exception:  # noqa: BLE001 — next beat retries; the
                    # backlog already holds this beat's frame
                    self.metrics["heartbeat_send_errors_total"] = (
                        self.metrics.get("heartbeat_send_errors_total", 0)
                        + 1)
        finally:
            await session.close()

    def _mark_terminating(self):
        """SIGTERM: stop admitting new calls, then run the preemption
        sequence (drain in-flight calls → emergency checkpoint → report
        ``preempted`` to the controller) inside the grace window. The
        hard ``os._exit`` at grace end stays as the backstop — K8s will
        SIGKILL then regardless (reference: TerminationCheckMiddleware
        http_server.py:1184; sequence: resilience/preemption.py)."""
        if self.terminating:
            return
        self.terminating = True
        # dump the sanitizer graph and the flight rings NOW, not after
        # the drain: the grace backstop may os._exit mid-drain and both
        # are already complete at SIGTERM time (the writes are
        # milliseconds). The flight dump is the black box this record
        # exists for — the ticks leading INTO the preemption.
        self._dump_san_report()
        self._dump_flight_report()
        loop = asyncio.get_event_loop()
        from kubetorch_tpu.resilience.preemption import PreemptionHandler

        handler = PreemptionHandler(self)

        async def _preempt_then_exit():
            try:
                await handler.run()
            # ktlint: disable=KT004 -- dying pod: the backstop exit fires regardless
            except Exception:  # noqa: BLE001 — never block the exit
                pass
            loop.call_later(0.1, os._exit, 0)  # let the report flush

        loop.create_task(_preempt_then_exit())
        loop.call_later(handler.grace_s, os._exit, 0)

    @staticmethod
    def _dump_san_report():
        """KT_SAN=1 pods exit through ``os._exit`` (atexit never runs):
        flush the sanitizer's lock-order graph explicitly on every
        deliberate exit path so the session merge sees pod-side edges."""
        try:
            from kubetorch_tpu.analysis import san
            from kubetorch_tpu.config import env_str

            out = env_str("KT_SAN_DIR")
            if out and san.active():
                san.dump_report(out)
        # ktlint: disable=KT004 -- exit path: the dump is best-effort
        except Exception:  # noqa: BLE001
            pass

    def _dump_flight_report(self):
        """Write ``flight-<pid>.json`` (this process's ring + the
        workers' piggybacked rings) into ``KT_FLIGHT_DIR`` on every
        deliberate exit path — the per-tick black box an operator reads
        after a preemption or stall. No-op when the knob is unset."""
        try:
            from kubetorch_tpu.observability import flight

            flight.maybe_dump(by_proc=self._flight_by_proc)
        # ktlint: disable=KT004 -- exit path: the dump is best-effort
        except Exception:  # noqa: BLE001
            pass

    def _merged_flight(self, limit: Optional[int] = None
                       ) -> Dict[str, List[dict]]:
        """Per-proc flight records: the workers' piggybacked rings plus
        this process's own recorder (in-process engines, e.g. tests)."""
        from kubetorch_tpu.observability import flight

        groups = [(pid, rows) for pid, rows in
                  self._flight_by_proc.items()]
        rec = flight.get_recorder()
        if rec is not None and rec.seq:
            groups.append((os.getpid(), rec.snapshot()))
        merged = flight.merge_procs(groups)
        if limit is not None:
            merged = {k: v[-int(limit):] for k, v in merged.items()}
        return merged

    async def _start_app_cmd(self):
        cmd = self.metadata.get("app_cmd")
        if not cmd:
            return
        self.app_proc = await asyncio.create_subprocess_shell(
            cmd, cwd=self.metadata.get("root_path") or None)

    async def _app_readiness_loop(self):
        """Poll the app's health path until it answers 200, then flip
        ready. A dead subprocess fails fast (setup_error carries the exit
        code) instead of polling a corpse until the client times out."""
        import aiohttp as _aiohttp

        port = self.metadata["app_port"]
        path = "/" + self.metadata["app_health_path"].lstrip("/")
        url = f"http://127.0.0.1:{port}{path}"
        interval = env_float("KT_APP_HEALTH_INTERVAL")
        async with ClientSession(
                timeout=_aiohttp.ClientTimeout(total=5.0)) as s:
            while True:
                if self.app_proc is not None and \
                        self.app_proc.returncode is not None:
                    # any pre-health exit — 0 included — means the server
                    # the health path belongs to will never answer
                    self.setup_error = (
                        f"app exited with code {self.app_proc.returncode} "
                        f"before passing health check {path}")
                    self._notify_status()
                    return
                try:
                    async with s.get(url) as resp:
                        if resp.status == 200:
                            self.ready = True
                            self._notify_status()
                            return
                # ktlint: disable=KT004 -- refused is expected while the app boots
                except Exception:
                    pass
                await asyncio.sleep(interval)

    # ----------------------------------------------------- middleware
    @web.middleware
    async def _mw_request_id(self, request: web.Request, handler):
        rid = request.headers.get("X-Request-ID") or uuid.uuid4().hex[:12]
        token = request_id_var.set(rid)
        try:
            resp = await handler(request)
            resp.headers["X-Request-ID"] = rid
            return resp
        finally:
            request_id_var.reset(token)

    @web.middleware
    async def _mw_termination(self, request: web.Request, handler):
        if self.terminating and request.path not in ("/health", "/metrics"):
            exc = PodTerminatedError("pod received SIGTERM")
            return web.json_response(package_exception(exc), status=503)
        return await handler(request)

    @web.middleware
    async def _mw_metrics(self, request: web.Request, handler):
        start = time.perf_counter()
        self.metrics["http_requests_total"] += 1
        self.metrics["last_activity_timestamp"] = time.time()
        # user-callable POSTs only (reserved routes include long-lived
        # WS/debug connections that would pin the preemption drain open)
        is_call = (request.method == "POST"
                   and request.path.lstrip("/").split("/")[0]
                   not in _RESERVED)
        if is_call:
            self._inflight_posts += 1
        try:
            resp = await handler(request)
            if resp.status >= 500:
                self.metrics["http_request_errors_total"] += 1
            return resp
        except Exception:
            self.metrics["http_request_errors_total"] += 1
            raise
        finally:
            if is_call:
                self._inflight_posts -= 1
            self.metrics["http_request_duration_seconds_sum"] += (
                time.perf_counter() - start)

    # ------------------------------------------------------- handlers
    async def h_health(self, request):
        return web.json_response({
            "status": "ok", "version": __version__,
            "service": self.metadata.get("service_name"),
            "uptime_s": round(time.time() - self.started_at, 1),
        })

    async def h_ready(self, request):
        launch_id = request.query.get("launch_id")
        if launch_id and self.launch_id and launch_id != self.launch_id:
            return web.json_response(
                {"ready": False, "reason": "stale launch_id"}, status=409)
        if self.setup_error:
            return web.json_response(
                {"ready": False, "reason": self.setup_error}, status=500)
        # A crashed App is never ready, even after it once was: autoscalers
        # and clients must see the failure, not a stale ready=True. Exit 0
        # is NOT a crash — kt.app also runs short-lived CLI commands that
        # complete normally (h_app_status models that as a regular state).
        if self.app_proc is not None and \
                self.app_proc.returncode not in (None, 0):
            return web.json_response(
                {"ready": False,
                 "reason": ("app exited with code "
                            f"{self.app_proc.returncode}")}, status=500)
        if not self.ready:
            return web.json_response(
                {"ready": False, "reason": "setting up"}, status=503)
        return web.json_response({"ready": True})

    # group name in a worker's stats dict → metric-name prefix
    _PROC_GROUPS = {"data_store_restore": "data_store_",
                    "data_store": "data_store_", "serving": "",
                    "trace": "", "reliability": "", "engine": "",
                    # "resilience" was merged by h_metrics but never
                    # registered: a pod recording its first preemption/
                    # emergency-checkpoint tick turned every /metrics
                    # scrape into a 500 (KeyError) for the rest of the
                    # drain window — exactly when operators look
                    "resilience": "", "san": "",
                    # per-adapter LoRA tenant counters (dynamic
                    # engine_adapter__<name>_* families) — flat _total
                    # keys, summed across workers like any group
                    "adapter": "",
                    # quantized dcn allreduce + delta broadcast (train
                    # plane runs in workers; counters piggyback)
                    "coll": ""}

    def _merge_worker_stats(self, stats: Dict[str, Any]):
        """Fold a worker's per-call stats dict into pod metrics. Plain
        gauges (device memory) merge flat — freshest wins; pid-tagged
        snapshots (restore + serving counters) go through per-process
        aggregation. Worker-side trace spans piggyback here too (the
        worker's ring is invisible to HTTP; the pod's /_trace is the
        export surface, so spans must hop to THIS process's ring)."""
        spans = stats.pop("trace_spans", None)
        if spans:
            tracing.recorder.ingest(spans)
        hists = stats.pop("hists", None)
        if hists:
            # named-histogram snapshot (engine TTFT etc.): keep the
            # whole per-process snapshot; merged lazily at scrape /
            # telemetry-frame time
            pid = hists.get("pid", 0) if isinstance(hists, dict) else 0
            snap = hists.get("h") if isinstance(hists, dict) else None
            if isinstance(snap, dict):
                self._hists_by_proc[pid] = snap
        flight_inc = stats.pop("flight", None)
        if flight_inc:
            # flight-ring increments (worker piggyback): extend the
            # per-proc merged ring, deduped by seq, bounded to the ring
            # capacity's order so a chatty worker can't grow pod memory
            try:
                pid = flight_inc.get("pid", 0)
                rows = flight_inc.get("records") or []
                have = self._flight_by_proc.get(pid) or []
                by_seq = {int(r["seq"]): r for r in have
                          if isinstance(r, dict) and "seq" in r}
                for r in rows:
                    if isinstance(r, dict) and "seq" in r:
                        by_seq[int(r["seq"])] = r
                self._flight_by_proc[pid] = [
                    by_seq[s] for s in sorted(by_seq)][-4096:]
            # ktlint: disable=KT004 -- observability piggyback must never break a call
            except Exception:  # noqa: BLE001
                pass
        san_graph = stats.pop("san_graph", None)
        if san_graph:
            # KT_SAN=1: fold the worker's lock-order graph into THIS
            # process's runtime graph — the pod's exit dump then covers
            # worker-side edges (workers die with the pod's os._exit)
            try:
                from kubetorch_tpu.analysis import san

                san.ingest_graph(san_graph)
            # ktlint: disable=KT004 -- sanitizer piggyback must never break a call
            except Exception:  # noqa: BLE001
                pass
        for group in self._PROC_GROUPS:
            entry = stats.pop(group, None)
            if entry is not None:
                entry = dict(entry)
                self._merge_proc_snapshot(group, entry.pop("pid", 0), entry)
        if stats:
            self.metrics.update(stats)

    def _merge_proc_snapshot(self, group: str, proc_id,
                             snap: Dict[str, float]):
        """Re-aggregate flat per-process metric snapshots: ``*_total``
        counters SUM across processes (each worker's own counter is
        monotonic, so the sum is too — last-writer-wins would flip
        between workers' totals, which Prometheus reads as counter
        resets); everything else (``last_*``/histogram-sum gauges) comes
        from ``snap``, the process that reported most recently."""
        prefix = self._PROC_GROUPS[group]
        by_proc = self._stats_by_proc.setdefault(group, {})
        by_proc[proc_id] = snap
        for key in snap:
            if key.endswith("_total"):
                self.metrics[f"{prefix}{key}"] = sum(
                    s.get(key, 0) for s in by_proc.values())
            else:
                self.metrics[f"{prefix}{key}"] = snap[key]

    def _merged_hists(self) -> Dict[str, Any]:
        """This process's named histograms merged with the workers'
        piggybacked snapshots (buckets/sum/count summed — each
        process's own counts are monotonic; exemplars freshest-wins)."""
        from kubetorch_tpu.observability import prometheus as prom

        return prom.merge_hist_snapshots(
            [prom.hist_metrics(), *self._hists_by_proc.values()])

    def _telemetry_frame(self, full: bool = False) -> Optional[dict]:
        """One metric delta frame for the heartbeat piggyback: the
        pid-merged flat metrics (engine_*/kv_*/serving_*/replay_*/
        resilience_*/... — FRAME_PREFIXES) plus merged histogram
        buckets, restricted to keys that CHANGED since the last
        successful send. Every ``KT_TELEMETRY_FULL_EVERY``-th frame is
        a full snapshot so a restarted controller converges. When
        nothing changed the frame is a bare ``{"ts": ...}`` — it still
        ships, because the fleet store's per-pod freshness clock is the
        frame arrival: suppressing idle frames would read every idle
        (but perfectly healthy) replica as stale between full
        snapshots."""
        from kubetorch_tpu.observability.fleetstore import build_frame

        # server-process groups (channel lifecycle, replay/admission,
        # pod-side resilience ticks) normally merge at scrape time —
        # the frame must not depend on anyone ever scraping this pod
        self._refresh_server_groups()
        self._tele_frames += 1
        every = env_int("KT_TELEMETRY_FULL_EVERY")
        full = full or self._tele_frames == 1 or (
            every and self._tele_frames % every == 0)
        frame = build_frame(self.metrics, self._merged_hists(),
                            last_sent=self._tele_sent, full=full)
        n_keys = len(frame.get("m") or {}) + len(frame.get("h") or {})
        self.metrics["telemetry_frames_sent_total"] = (
            self.metrics.get("telemetry_frames_sent_total", 0) + 1)
        if full:
            self.metrics["telemetry_full_frames_total"] = (
                self.metrics.get("telemetry_full_frames_total", 0) + 1)
        self.metrics["telemetry_frame_keys_last"] = n_keys
        # sync the bookkeeping counters into the delta baseline: they
        # just changed AFTER the frame was built, and without this
        # every subsequent "idle" frame would carry exactly them —
        # they ship on full snapshots instead
        for key in ("telemetry_frames_sent_total",
                    "telemetry_full_frames_total",
                    "telemetry_frame_keys_last"):
            if key in self.metrics:
                self._tele_sent[key] = self.metrics[key]
        return frame

    def request_full_telemetry(self) -> Optional[dict]:
        """A full telemetry snapshot NOW (the controller's registration
        ack asked for one — its FleetStore has never heard of this pod,
        so deltas would land against nothing). Also drops any POST
        backlog: its cumulative content is subsumed by this snapshot,
        and replaying the stale deltas AFTER it would read as counter
        steps-down (false resets) at the controller."""
        if not env_int("KT_TELEMETRY_EVERY"):
            return None   # telemetry emission disabled
        self._tele_drop_backlog()
        return self._telemetry_frame(full=True)

    def _tele_drop_backlog(self) -> int:
        """Supersede the POST backlog with a full snapshot: clear it
        and count the discarded deltas (both resync paths — WS ack and
        POST hint — must tick the same counter or drops undercount)."""
        dropped = len(self._tele_backlog)
        if dropped:
            self._tele_backlog.clear()
            self.metrics["telemetry_backlog_dropped_total"] = (
                self.metrics.get("telemetry_backlog_dropped_total", 0)
                + dropped)
        return dropped

    def _tele_flush_frames(self, resync: bool) -> list:
        """The POST-fallback flush body. When the answering controller
        already KNOWS this pod (``resync`` False from the beat
        response), the backlog replays in order — deltas carry
        cumulative values, so an in-order replay against a store that
        has their history converges exactly; the caller clears the
        backlog only on CONFIRMED delivery. When it does NOT (fresh or
        freshly RESTARTED controller — its FleetStore is process
        memory), replaying the stale deltas would mis-splice reset
        offsets (any frame the store has newer values than reads as a
        counter reset, inflating every rate by the pre-restart total):
        the flush is ONE current full snapshot that subsumes them all,
        and the superseded deltas are counted in
        ``telemetry_backlog_dropped_total`` — superseding clears the
        backlog immediately, because even a LOST snapshot is healed by
        the hint re-firing on the next beat."""
        if not resync:
            return list(self._tele_backlog)
        self._tele_drop_backlog()
        frame = self._telemetry_frame(full=True)
        return [frame] if frame else []

    def _refresh_server_groups(self):
        """Fold THIS process's metric-group snapshots into
        ``self.metrics`` (workers piggyback theirs on call responses).
        Shared by the scrape path and the telemetry frame builder — a
        pod nobody ever scrapes must still ship its server-side
        replay/admission/channel/resilience counters on heartbeats."""
        from kubetorch_tpu.observability import prometheus as prom

        # Weight-sync restore decomposition. Worker processes report their
        # counters on the call-response channel (process_worker attaches a
        # pid-tagged snapshot next to device_stats; _merge_worker_stats
        # folds it in); restores run IN-SERVER (app mode) come from this
        # process's own counters. Same names either way, one render source.
        restore = prom.restore_metrics()
        if restore["restore_count_total"]:
            self._merge_proc_snapshot("data_store_restore", "server",
                                      restore)
        # Wire codec / delta-publish counters (all *_total → summed
        # across processes exactly like the restore counters).
        wire = prom.wire_metrics()
        if any(wire.values()):
            self._merge_proc_snapshot("data_store", "server", wire)
        # Quantized-collective + delta-broadcast counters: the training
        # plane usually runs in worker processes (piggybacked pid-tagged
        # like the wire counters), but app-mode trainers record in this
        # process directly.
        coll = prom.coll_metrics()
        if any(coll.values()):
            self._merge_proc_snapshot("coll", "server", coll)
        # Serving call-path counters: the server process records channel
        # lifecycle + server-side stage totals; worker processes piggyback
        # their own serving_worker_* counters on call responses (merged
        # pid-tagged above, summed like the restore counters).
        serving = prom.serving_metrics()
        if any(serving.values()):
            self._merge_proc_snapshot("serving", "server", serving)
        # Call-reliability counters (idempotent replay + admission
        # control) — recorded in this process by the channel sessions
        # and the POST admission gate.
        reli = prom.reliability_metrics()
        if any(reli.values()):
            self._merge_proc_snapshot("reliability", "server", reli)
        # Tracing counters (spans recorded / dropped / slow pushes —
        # worker processes piggyback theirs next to the device stats).
        trace = tracing.trace_metrics()
        if any(trace.values()):
            self._merge_proc_snapshot("trace", "server", trace)
        # Pod-side resilience ticks (preemption drain started, emergency
        # checkpoints run in this process) — best-effort: a preempted pod
        # only surfaces these to a scrape landing inside its grace window.
        resil = prom.resilience_metrics()
        if any(resil.values()):
            self._merge_proc_snapshot("resilience", "server", resil)
        # Concurrency-sanitizer counters (KT_SAN=1 sessions only): lock
        # classes tracked, order edges observed, event-loop stalls.
        san = prom.san_metrics()
        if any(san.values()):
            self._merge_proc_snapshot("san", "server", san)

    async def h_metrics(self, request):
        healthy = (self.supervisor.healthy()
                   if self.supervisor is not None else True)
        from kubetorch_tpu.observability import prometheus as prom

        # lazy session GC rides the scrape cadence too — a pod whose
        # clients vanished without a bye (and that never sees another
        # connect) must still release detached sessions' retention
        self._channel_sessions.sweep()
        self._refresh_server_groups()
        data = {**self.metrics, "workers_healthy": healthy}
        if prom.wants_prometheus(request):
            # Prometheus/OpenMetrics scrapers (Accept: text/plain...) get
            # the exposition format; the framework's JSON clients keep the
            # dict shape. Pod identity rides as labels so a cluster-level
            # scrape aggregates cleanly.
            labels = {
                "service": self.metadata.get("service_name", ""),
                "pod": env_str("KT_POD_NAME") or "",
            }
            # exemplars only on a negotiated OpenMetrics scrape: the
            # classic text format rejects the whole scrape over one
            om = prom.wants_openmetrics(request)
            return web.Response(
                text=prom.render([
                    *prom.flatten_metrics(data, labels),
                    # le-labeled call-stage histograms (the flat dict
                    # above carries only their sums/counts)
                    *prom.serving_histogram_samples(labels),
                    # named histograms (engine TTFT etc.), merged
                    # across worker processes, exemplars included
                    *prom.hist_samples(self._merged_hists(), labels),
                ], openmetrics=om),
                content_type=("application/openmetrics-text" if om
                              else "text/plain"),
                charset="utf-8")
        return web.json_response(data)

    async def h_app_status(self, request):
        if self.app_proc is None:
            return web.json_response({"running": False, "reason": "no app"})
        rc = self.app_proc.returncode
        return web.json_response({"running": rc is None, "returncode": rc})

    async def h_trace(self, request):
        """Export this pod's span ring. Default: Chrome/Perfetto
        ``trace_event`` JSON (open the body directly in
        ``ui.perfetto.dev``) — pid/tid mapped to pod/process, flow
        events stitching cross-process parent edges. ``?format=spans``
        returns the raw span dicts (what ``ktpu trace`` and the
        controller assembly consume); ``?trace_id=`` filters one trace,
        ``?last=N`` the N most recently started ones. Worker-process
        spans are here too — they piggyback on call responses into this
        ring (see ``_merge_worker_stats``)."""
        trace_id = request.query.get("trace_id")
        last = request.query.get("last")
        if trace_id:
            spans = tracing.recorder.snapshot(trace_id=trace_id)
        elif last:
            try:
                n = max(1, int(last))
            except ValueError:
                n = 1
            spans = tracing.recorder.last_traces(n)
        else:
            spans = tracing.recorder.snapshot()
        if request.query.get("format") == "spans":
            return web.json_response({"spans": spans})
        return web.json_response(tracing.to_trace_events(spans))

    async def h_flight(self, request):
        """Export the engine flight rings (per-tick black box): the
        worker processes' piggybacked records merged with this
        process's own recorder. Default: ``{"procs": {pid:
        [records...]}}`` — what ``ktpu flight`` merges fleet-wide.
        ``?format=perfetto`` returns a ui.perfetto.dev-loadable
        trace_event file (counter tracks + per-tick instants carrying
        the live trace ids); ``?last=N`` caps each proc's records to
        the newest N."""
        last = request.query.get("last")
        limit: Optional[int] = None
        if last:
            try:
                limit = max(1, int(last))
            except ValueError:
                limit = None
        merged = self._merged_flight(limit=limit)
        if request.query.get("format") == "perfetto":
            from kubetorch_tpu.observability import flight

            return web.json_response(flight.to_perfetto(merged))
        return web.json_response({"pod": env_str("KT_POD_NAME") or "",
                                  "procs": merged})

    async def h_reload(self, request):
        """Controller push-reload: new metadata (+ freshly synced code)."""
        try:
            new_meta = await request.json()
        except Exception:
            new_meta = {}
        loop = asyncio.get_running_loop()

        def do_reload():
            self.metadata.update(new_meta or {})
            if self.supervisor is None:
                self._setup_supervisor()
            else:
                self._pull_code()
                self.supervisor.reload(self.metadata)
                self.ready = True

        try:
            await loop.run_in_executor(None, do_reload)
        except Exception as exc:
            self.setup_error = f"{type(exc).__name__}: {exc}"
            return web.json_response(package_exception(exc), status=500)
        return web.json_response({"reloaded": True, "ready": self.ready})

    async def h_teardown(self, request):
        self._dump_san_report()
        self._dump_flight_report()
        asyncio.get_event_loop().call_later(0.2, os._exit, 0)
        return web.json_response({"terminating": True})

    async def h_debug_ws(self, request):
        """WS↔TCP bridge to an in-worker pdb opened by deep_breakpoint()
        (reference: serving/pdb_websocket.py WebSocket-PTY server)."""
        from kubetorch_tpu.serving.debugger import ws_tcp_bridge

        return await ws_tcp_bridge(request)

    async def h_debug_ui(self, request):
        """Browser debugger page over the same bridge (reference
        pdb-ui mode)."""
        from kubetorch_tpu.serving.debugger import debug_ui

        return await debug_ui(request)

    async def h_profile(self, request):
        """jax.profiler trace control: POST /_profile/start |
        /_profile/stop?rank=N. ``stop`` streams back a zip of the
        TensorBoard trace directory (additive vs the reference — it ships
        no tracer, SURVEY §5.1)."""
        if self.supervisor is None:
            return web.json_response(
                {"error": {"type": "StartupError",
                           "message": "no supervisor loaded"}}, status=409)
        action = request.match_info["action"]
        loop = asyncio.get_running_loop()
        try:
            rank = int(request.query.get("rank", "0"))
            if rank < 0:
                raise ValueError(f"rank must be >= 0, got {rank}")
            result = await loop.run_in_executor(
                None, lambda: self.supervisor.profile(action,
                                                      local_rank=rank))
        except ValueError as exc:
            return web.json_response(package_exception(exc), status=400)
        except Exception as exc:
            return web.json_response(package_exception(exc), status=500)
        # Embed the active span trace_id so the jax.profiler zip can be
        # joined back to the spans that triggered the capture: the
        # caller's propagated context wins, else the most recent trace
        # in this pod's ring.
        ctx = tracing.parse_ctx(request.headers.get(tracing.HEADER))
        trace_id = (ctx[0] if ctx
                    else tracing.recorder.last_trace_id()) or ""
        if action == "stop" and result.get("zip_path"):
            # worker zipped to the shared filesystem; stream it from there
            return web.FileResponse(
                result["zip_path"],
                headers={"Content-Type": "application/zip",
                         "X-Trace-Dir": result.get("dir", ""),
                         "X-KT-Trace-Id": trace_id})
        return web.json_response(
            {**{k: v for k, v in result.items()
                if not isinstance(v, (bytes, bytearray))},
             "trace_id": trace_id},
            headers={"X-KT-Trace-Id": trace_id})

    async def h_proxy(self, request: web.Request):
        """Reverse proxy to an App's own HTTP port (reference:
        http_server.py:117 /http proxy)."""
        port = self.metadata.get("app_port")
        if not port:
            return web.json_response(
                {"error": {"type": "KubetorchError",
                           "message": "no app_port configured"}}, status=404)
        tail = request.match_info.get("tail", "")
        url = f"http://127.0.0.1:{port}/{tail}"
        if request.query_string:
            url += f"?{request.query_string}"
        body = await request.read()
        import aiohttp as _aiohttp

        # bound the dial to the local app; the request itself may be long
        async with ClientSession(timeout=_aiohttp.ClientTimeout(
                total=None, sock_connect=10.0)) as session:
            async with session.request(
                request.method, url, data=body,
                headers={k: v for k, v in request.headers.items()
                         if k.lower() not in ("host", "content-length")},
            ) as upstream:
                payload = await upstream.read()
                return web.Response(
                    body=payload, status=upstream.status,
                    content_type=upstream.content_type)

    # ----------------------------------------------------------- actors
    # Single-controller mode (reference: Monarch's per-node allocator,
    # serving/monarch_supervisor.py): this pod hosts named persistent
    # actor processes spawned/driven by the mesh's controller program.
    async def h_actor_spawn(self, request: web.Request):
        ser = request.headers.get(serialization.HEADER, serialization.DEFAULT)
        body = await request.read()
        try:
            allowed = (self.supervisor.allowed if self.supervisor
                       else serialization.METHODS)
            ser = serialization.check_allowed(ser, allowed)
            spec = serialization.loads(body, ser)
        except Exception as exc:  # noqa: BLE001
            return web.json_response(package_exception(exc), status=400)
        loop = asyncio.get_running_loop()
        try:
            info = await loop.run_in_executor(None, lambda: (
                self.actor_host.spawn(
                    spec["actor"],
                    root_path=(spec.get("root_path")
                               or self.metadata.get("root_path", "")),
                    import_path=spec["import_path"],
                    class_name=spec["class_name"],
                    init_args=spec.get("init_args"),
                    env=spec.get("env"),
                    num_procs=int(spec.get("num_procs") or 1))))
        except Exception as exc:  # noqa: BLE001
            return web.json_response(package_exception(exc), status=500)
        return web.json_response(info)

    async def h_actor_list(self, request: web.Request):
        host = self._actor_host
        return web.json_response(
            {"actors": host.list() if host is not None else []})

    async def h_actor_stop(self, request: web.Request):
        name = request.match_info["actor"]
        host = self._actor_host
        stopped = False
        if host is not None:
            stopped = await asyncio.get_running_loop().run_in_executor(
                None, host.stop, name)
        return web.json_response({"stopped": stopped})

    async def h_actor_call(self, request: web.Request):
        name = request.match_info["actor"]
        method = request.match_info["method"]
        host = self._actor_host
        if host is None:
            return web.json_response(package_exception(
                KeyError(f"no actors hosted here (wanted {name!r})")),
                status=404)
        ser = request.headers.get(serialization.HEADER, serialization.DEFAULT)
        try:
            allowed = (self.supervisor.allowed if self.supervisor
                       else serialization.METHODS)
            ser = serialization.check_allowed(ser, allowed)
        except Exception as exc:  # noqa: BLE001
            return web.json_response(package_exception(exc), status=400)
        body = await request.read()
        loop = asyncio.get_running_loop()
        try:
            resp = await loop.run_in_executor(
                None, lambda: host.call(
                    name, body, ser, method=method, allowed=allowed))
        except KeyError as exc:
            return web.json_response(package_exception(exc), status=404)
        except Exception as exc:  # noqa: BLE001
            return web.json_response(package_exception(exc), status=500)
        if not resp.get("ok"):
            return web.json_response({"error": resp["error"]}, status=500)
        if "stream" in resp:
            # actor generator results: drain to one list (same contract as
            # plain h_call callers)
            resp, err = await self._drain_stream(resp, ser, allowed)
            if err is not None:
                return web.json_response(err, status=500)
        used = resp.get("serialization", ser)
        return web.Response(
            body=resp["payload"],
            content_type=("application/json" if used == "json"
                          else "application/octet-stream"),
            headers={serialization.HEADER: used})

    async def h_call(self, request: web.Request):
        name = request.match_info["callable"]
        method = request.match_info.get("method")
        if name in _RESERVED:
            raise web.HTTPNotFound()
        ser, err = self._validate_call(
            name, request.headers.get(serialization.HEADER,
                                      serialization.DEFAULT))
        if err is not None:
            exc, status = err
            return web.json_response(package_exception(exc), status=status)
        # Admission control (the POST-path twin of the channel session's
        # gate): past KT_MAX_QUEUE_DEPTH queued+executing calls on this
        # POD — channels and POSTs combined — shed with a fast 429 + a
        # computed Retry-After instead of letting the call queue into a
        # timeout. The middleware already counted THIS request into
        # _inflight_posts, hence the strict >.
        max_depth = env_int("KT_MAX_QUEUE_DEPTH")
        pod_depth = self._channel_sessions.total_depth()
        if max_depth and pod_depth > max_depth:
            retry_after = retry_after_estimate(
                pod_depth, max_depth, self._ema_server_s)
            from kubetorch_tpu.observability import prometheus as prom

            prom.record_reliability("shed")
            prom.record_reliability("last_retry_after", retry_after)
            tracing.record_span(
                "server.shed", 0.0,
                attrs={"transport": "post",
                       "queue_depth": pod_depth,
                       "retry_after_s": retry_after})
            return web.json_response(
                package_exception(ServerOverloaded(
                    f"{pod_depth} calls in flight at/over "
                    f"KT_MAX_QUEUE_DEPTH={max_depth}",
                    retry_after=retry_after)),
                status=429, headers={"Retry-After": str(retry_after)})
        # Propagated client deadline budget (X-KT-Timeout, RELATIVE
        # seconds — converted to an absolute deadline on THIS clock, so
        # client↔pod skew cannot expire or un-expire calls): a
        # non-positive budget is rejected before the body is even
        # dispatched; the worker re-checks at its queue head and between
        # streamed chunks.
        deadline = None
        raw_budget = request.headers.get("X-KT-Timeout")
        if raw_budget:
            try:
                budget = float(raw_budget)
            except ValueError:
                budget = None
            if budget is not None:
                if budget <= 0:
                    from kubetorch_tpu.observability import (
                        prometheus as prom,
                    )

                    prom.record_reliability("deadline_rejected")
                    return web.json_response(
                        package_exception(DeadlineExceeded(
                            "non-positive deadline budget",
                            deadline=time.time())), status=408)
                deadline = time.time() + budget
        body = await request.read()
        # t_recv AFTER the body upload: a slow client link's upload time
        # is wire, not server queue — stamping at handler entry would
        # misattribute it in the latency decomposition (the channel path
        # stamps at message receipt, where the payload is already here).
        t_recv = time.perf_counter()
        distributed_subcall = (
            request.query.get("distributed_subcall") == "true")
        restart_procs = request.query.get("restart_procs") == "true"
        workers = request.query.get("workers", "all")

        query = dict(request.query)
        if request.headers.get("X-KT-Stream") == "request":
            # thread the stream ask through supervisor-level proxies
            # (actor/ray coordinator election): the proxy re-issues the
            # header so the coordinator frames its response, and the frame
            # shape survives the hop (see _proxy_to_coordinator)
            query["_stream_req"] = "1"

        loop = asyncio.get_running_loop()
        # server-side span, parented to the caller's X-KT-Trace context.
        # copy_context AFTER starting it: the executor thread (and the
        # pool _submit that runs there) inherits the span, which is how
        # the trace context reaches the worker next to request_id.
        wire_ctx = tracing.parse_ctx(request.headers.get(tracing.HEADER))
        sspan = tracing.start_span(
            "server.call", parent=wire_ctx, remote=wire_ctx is not None,
            started_perf=t_recv,
            attrs={"callable": name, "method": method or "",
                   "transport": "post"})
        call_ctx = contextvars.copy_context()
        t_exec = time.perf_counter()
        try:
            resp = await loop.run_in_executor(
                None,
                lambda: call_ctx.run(
                    self.supervisor.call,
                    body, ser, method=method,
                    distributed_subcall=distributed_subcall,
                    restart_procs=restart_procs, workers=workers,
                    query=query,
                    request_id=request_id_var.get(),
                    deadline=deadline))
        except Exception as exc:
            sspan.end(error=f"{type(exc).__name__}: {exc}")
            return web.json_response(package_exception(exc), status=500)
        if resp is None:
            sspan.end(error="worker returned no response")
            return web.json_response(package_exception(
                RuntimeError("worker returned no response")), status=500)
        if not resp.get("ok"):
            # failed calls still export their worker spans (piggybacked
            # on the error response) and still qualify for slow-capture
            stats = resp.pop("device_stats", None)
            if stats:
                self._merge_worker_stats(stats)
            sspan.end(error=str(resp["error"].get("type", "error")))
            tracing.maybe_push_slow(
                sspan.span["trace_id"] if sspan.span else None,
                time.perf_counter() - t_recv)
            return web.json_response({"error": resp["error"]}, status=500)
        if "stream" in resp:
            if request.headers.get("X-KT-Stream") == "request":
                sspan.detach()
                try:
                    return await self._respond_stream(
                        request, resp["stream"], ser)
                finally:
                    sspan.end()
            # plain caller: drain the generator into one list result (one
            # executor handoff for the whole drain — no progressive
            # delivery is needed here)
            resp, err = await self._drain_stream(
                resp, ser, self.supervisor.allowed)
            if err is not None:
                sspan.end(error="stream error")
                return web.json_response(err, status=500)
        stats = resp.pop("device_stats", None)
        if stats:
            # workers attach accelerator memory stats to responses; the
            # freshest snapshot rides the next metrics push (DCGM analogue)
            self._merge_worker_stats(stats)
        # Latency decomposition (same stages the channel reports): the
        # POST path records it too, so the per-call dispatch tax is a
        # measured histogram on either path, and the client can read the
        # X-KT-Timing header to split wall into wire vs server time.
        t = self._call_timings(resp, t_recv, t_exec)
        sspan.end({"queue_ms": round(t.get("queue_s", 0.0) * 1e3, 3)})
        tracing.maybe_push_slow(sspan.span["trace_id"]
                                if sspan.span else None,
                                time.perf_counter() - t_recv)
        used = resp.get("serialization", ser)
        return web.Response(
            body=resp["payload"],
            content_type=("application/json" if used == "json"
                          else "application/octet-stream"),
            headers={serialization.HEADER: used,
                     "X-KT-Timing": json.dumps(t),
                     **({"X-KT-Trace-Id": sspan.span["trace_id"]}
                        if sspan.span else {}),
                     **resp.get("extra_headers", {})})

    def _validate_call(self, name: str, ser: str):
        """The one call gate both transports share (POST h_call and the
        channel) — readiness, served-name, and serialization-allowlist
        checks must never diverge between the two paths. Returns
        ``(checked_ser, None)`` or ``(None, (exception, http_status))``;
        the transport wraps the error (JSON status / error frame)."""
        if self.supervisor is None or not self.ready:
            exc_cls = (PodTerminatedError if self.terminating
                       else RuntimeError)
            return None, (exc_cls(self.setup_error
                                  or "callable not loaded"), 503)
        expected = (self.metadata.get("name")
                    or self.metadata.get("callable_name"))
        if name in _RESERVED or (
                expected and name not in (
                    expected, self.metadata.get("service_name"))):
            return None, (KeyError(
                f"callable {name!r} not served here "
                f"(serving {expected!r})"), 404)
        try:
            return serialization.check_allowed(
                ser, self.supervisor.allowed), None
        except Exception as exc:  # noqa: BLE001
            return None, (exc, 400)

    def _call_timings(self, resp: Dict[str, Any], t_recv: float,
                      t_exec: float) -> Dict[str, float]:
        """Pop worker-side timings off a response, fold the server-side
        stages into the Prometheus histograms, and return the wire-ready
        decomposition dict ({server_s, queue_s, dispatch_s, exec_s})."""
        from kubetorch_tpu.observability import prometheus as prom

        now = time.perf_counter()
        worker_t = resp.pop("timings", None) or {}
        t = {"server_s": now - t_recv, "queue_s": t_exec - t_recv}
        # feed the admission gate's Retry-After estimate
        self._ema_server_s = 0.8 * self._ema_server_s + 0.2 * t["server_s"]
        for key in ("dispatch_s", "exec_s"):
            if isinstance(worker_t.get(key), (int, float)):
                t[key] = float(worker_t[key])
        prom.record_call_stages({
            "server_queue": t["queue_s"],
            "worker_dispatch": t.get("dispatch_s"),
            "device": t.get("exec_s"),
        })
        return {k: round(v, 6) for k, v in t.items()}

    async def _drain_stream(self, resp, ser, allowed):
        """Drain a generator-result stream into one list-valued payload.
        Returns (resp_dict, None), or (None, packaged_error_dict) when
        the stream stalls or ends in a packaged error — the caller wraps
        the error for its transport (HTTP 500 / channel 'error' frame)."""
        try:
            chunks = await asyncio.get_running_loop().run_in_executor(
                None, list, iter(resp["stream"]))
        except TimeoutError as exc:
            return None, package_exception(exc)
        items, used = [], ser
        for chunk in chunks:
            items.append(serialization.loads(
                chunk["payload"], chunk["serialization"])["result"])
            used = chunk["serialization"]
        terminal = resp["stream"].terminal or {}
        if not terminal.get("ok"):
            return None, {"error": terminal["error"]}
        payload, used = serialization.choose(
            {"result": items}, used, allowed)
        return {**terminal, "payload": payload, "serialization": used}, None

    async def _respond_stream(self, request, stream, default_ser):
        """Chunked frame response for generator results: each frame is
        1-byte type ('D' data / 'E' error / 'Z' end) + 8-byte LE length +
        body; a 'D' body leads with one serialization-method byte (the
        worker may pick json or pickle per item). One frame per yielded
        item, written as produced — the remote analogue of iterating the
        generator locally. A client disconnect cancels the worker-side
        generator so it doesn't hold an executor thread forever."""
        from kubetorch_tpu.serving import frames

        loop = asyncio.get_running_loop()
        it = iter(stream)
        response = web.StreamResponse(headers={
            "X-KT-Stream": "1",
            serialization.HEADER: default_ser,
            "Content-Type": "application/octet-stream",
        })
        await response.prepare(request)
        try:
            while True:
                chunk = await loop.run_in_executor(None, next, it, None)
                if chunk is None:
                    break
                await response.write(frames.encode_frame(
                    frames.KIND_DATA,
                    frames.encode_item(chunk["payload"],
                                       chunk["serialization"])))
        except (ConnectionResetError, asyncio.CancelledError):
            cancel = getattr(stream, "cancel", None)
            if cancel is not None:
                cancel()
            raise
        except TimeoutError as exc:
            # Stream stalled past the call timeout (StreamResult already
            # cancelled the worker generator): tell the client with an 'E'
            # frame instead of silently truncating the stream.
            await response.write(frames.encode_frame(
                frames.KIND_ERROR,
                json.dumps({"error": package_exception(exc)["error"]}
                           ).encode()))
            await response.write_eof()
            return response
        terminal = stream.terminal or {}
        if not terminal.get("ok"):
            await response.write(frames.encode_frame(
                frames.KIND_ERROR,
                json.dumps({"error": terminal["error"]}).encode()))
        else:
            stats = terminal.get("device_stats")
            if stats:
                self._merge_worker_stats(stats)
            await response.write(frames.encode_frame(frames.KIND_END))
        await response.write_eof()
        return response

    # ---------------------------------------------------------- channel
    async def h_channel(self, request: web.Request):
        """Persistent multiplexed call channel (client:
        ``serving/channel.py``). One WebSocket carries many calls; each
        binary message is a ``frames.pack_envelope`` — a tiny JSON
        control header plus an *opaque* payload. The payload is never
        parsed here: it passes straight through supervisor → ProcessPool
        → ProcessWorker, so the pod hop costs zero re-serialization.

        The durable object is the :class:`ChannelSession`
        (``serving/replay.py``), keyed by the client's channel epoch
        (``X-KT-Channel-Epoch``): the FIFO queue, in-flight executions,
        and result-retention ring all live on the session, so a dropped
        socket loses nothing — a reconnecting client re-attaches and
        replays unacknowledged calls by ``(epoch, cid)`` idempotency
        key instead of re-executing them.

        Calls execute FIFO in arrival order per *session* — a stateful
        engine (``RollingDecoder``) driven pipelined must never see
        chunk N+1 start before chunk N finishes, reconnects included; a
        call whose header sets ``concurrent`` opts out and runs
        out-of-band. Responses carry the server-side latency
        decomposition (queue/dispatch/device) in the reply header."""
        from kubetorch_tpu.observability import prometheus as prom
        from kubetorch_tpu.serving import frames

        ws = web.WebSocketResponse(max_msg_size=1024 ** 3)
        await ws.prepare(request)
        try:
            # Nagle off: reply frames are small and the next chunk's
            # request is usually already in flight the other way —
            # without this the kernel holds replies for the delayed ACK
            # (aiohttp 3.11 does not set TCP_NODELAY itself; see
            # channel._set_nodelay for the measured stall).
            from aiohttp.tcp_helpers import tcp_nodelay

            if request.transport is not None:
                tcp_nodelay(request.transport, True)
        # ktlint: disable=KT004 -- an exotic transport without TCP still works
        except Exception:  # noqa: BLE001
            pass
        prom.record_channel_event("connect")
        if request.headers.get("X-KT-Channel-Reconnect") == "1":
            # the client re-dialed after a drop: count it HERE too —
            # operators alert on the pod's counters, not the client's
            prom.record_channel_event("reconnect")
        session, _resumed = self._channel_sessions.attach(
            request.headers.get("X-KT-Channel-Epoch"), ws,
            reconnect=request.headers.get("X-KT-Channel-Reconnect") == "1")
        try:
            async for msg in ws:
                if msg.type != WSMsgType.BINARY:
                    continue
                t_recv = time.perf_counter()
                try:
                    header, payload = frames.unpack_envelope(msg.data)
                except Exception:  # noqa: BLE001
                    # garbled envelope: no cid to answer to — count it so
                    # a misbehaving client shows up in /metrics
                    prom.record_channel_event("error")
                    continue
                kind = header.get("kind")
                if kind == "bye":
                    # clean client close: drop the session now instead of
                    # holding retention for a client that said goodbye
                    self._channel_sessions.drop(session)
                    break
                if kind == "ctl":
                    # control frame: answered OUT-OF-BAND right here,
                    # from pod/session state plus the last engine
                    # snapshot the workers piggybacked — it never joins
                    # the session FIFO (no wait behind pipelined decode
                    # chunks) and never pays a worker or device hop.
                    # Reads are idempotent, so no retention either: a
                    # replayed ctl just re-answers.
                    await self._answer_ctl(session, ws, header)
                    continue
                if kind != "call":
                    continue
                self.metrics["http_requests_total"] += 1
                self.metrics["last_activity_timestamp"] = time.time()
                if self.terminating \
                        and header.get("cid") not in session.calls:
                    # preemption: stop ADMITTING — queued/running calls
                    # keep executing (they are in-flight from the
                    # client's view and the drain waits for them), and a
                    # REPLAY of an already-seen cid is still answered
                    # from retention, but a fresh frame after SIGTERM
                    # gets the same typed refusal the POST path gives
                    error = package_exception(PodTerminatedError(
                        "pod received SIGTERM"))["error"]
                    async with session.send_lock:
                        await ws.send_bytes(frames.pack_envelope(
                            {"kind": "error", "cid": header.get("cid")},
                            json.dumps({"error": error}).encode()))
                    continue
                # admission, replay dedup, FIFO/concurrent routing — and
                # the in-flight gauge, counted from RECEIPT — all live on
                # the session (serving/replay.py)
                await session.submit(header, payload, t_recv)
                self.metrics["serving_channel_inflight"] = \
                    prom.channel_inflight(0)
        finally:
            # transport gone ≠ work gone: detach the socket, keep the
            # session (dispatcher, executions, retention) alive for
            # KT_RESULT_RETAIN_S so a reconnect can resume. Ephemeral
            # (no-epoch) sessions die with their socket.
            self._channel_sessions.detach(session, ws)
        return ws

    async def _answer_ctl(self, session, ws, header):
        """Answer a channel control frame (``kind: ctl``) from server
        state: pod-wide queue depth (channels + POSTs), this session's
        depth/EMA, and the last ``engine_*`` snapshot merged from the
        workers' call-response piggybacks. The whole point is cost —
        clients (and, later, the autoscaler's probes) poll queue depth
        at heartbeat cadence, and a full call round-trip would queue
        behind the very decode chunks being polled."""
        from kubetorch_tpu.serving import frames

        info = {
            "op": header.get("op") or "stats",
            "pod_queue_depth": self._channel_sessions.total_depth(),
            "inflight_posts": self._inflight_posts,
            "terminating": self.terminating,
            "ready": self.ready,
            **session.describe(),
        }
        engine = {k: v for k, v in self.metrics.items()
                  if k.startswith(("engine_", "kv_", "prefix_",
                                   "hbm_"))}
        if engine:
            info["engine"] = engine
        if info["op"] == "flight":
            # flight control op: the per-tick rings out-of-band — the
            # same records /_flight serves, reachable over an already-
            # open channel (no second HTTP connection needed)
            try:
                limit = int(header.get("last") or 512)
            except (TypeError, ValueError):
                limit = 512
            info["flight"] = self._merged_flight(limit=max(1, limit))
        async with session.send_lock:
            await ws.send_bytes(frames.pack_envelope(
                {"kind": "result", "ser": "json",
                 "cid": header.get("cid"), "ctl": True},
                json.dumps({"result": info}).encode()))

    async def _channel_execute(self, session, entry, header, payload,
                               t_recv):
        """Run one channel call and write its response frame(s) — every
        frame is recorded into the session's retention ring *before* it
        is delivered, so a mid-stream partition loses the socket but
        never the frames (replay re-delivers from the client's cursor)."""
        from kubetorch_tpu.observability import prometheus as prom

        cid = entry.cid
        rid = header.get("rid") or uuid.uuid4().hex[:12]

        async def reply(hdr: dict, body: bytes = b""):
            await session.send(entry, hdr, body)

        span_error: List[str] = []  # stamped on server.execute at end

        async def reply_error(exc_or_error, t=None):
            prom.record_channel_event("error")
            self.metrics["http_request_errors_total"] += 1
            error = (package_exception(exc_or_error)["error"]
                     if isinstance(exc_or_error, BaseException)
                     else exc_or_error)
            span_error.append(str(error.get("type", "error"))
                              if isinstance(error, dict)
                              else str(error)[:120])
            hdr: Dict[str, Any] = {"kind": "error"}
            if t:
                hdr["t"] = t
            await reply(hdr, json.dumps({"error": error}).encode())

        # "server.execute" backdated to receipt so the FIFO wait shows
        # inside it as the explicit "server.queue" child; the caller's
        # channel.call span (header["trace"]) is the remote parent, and
        # copy_context hands this span to the executor thread → pool
        # _submit → worker, so worker spans parent under it.
        wire_ctx = tracing.parse_ctx(header.get("trace"))
        sspan = tracing.start_span(
            "server.execute", parent=wire_ctx,
            remote=wire_ctx is not None, started_perf=t_recv,
            attrs={"cid": cid, "callable": header.get("callable") or "",
                   "method": header.get("method") or "",
                   "transport": "channel"})
        try:
            name = header.get("callable") or ""
            method = header.get("method")
            ser, err = self._validate_call(
                name, header.get("ser", serialization.DEFAULT))
            if err is not None:
                return await reply_error(err[0])
            deadline = header.get("deadline")
            deadline = (float(deadline)
                        if isinstance(deadline, (int, float)) else None)
            loop = asyncio.get_running_loop()
            call_ctx = contextvars.copy_context()
            t_exec = time.perf_counter()
            tracing.record_span(
                "server.queue", max(0.0, t_exec - t_recv),
                parent=getattr(sspan, "context", None))
            try:
                resp = await loop.run_in_executor(
                    None, lambda: call_ctx.run(
                        self.supervisor.call,
                        payload, ser, method=method, request_id=rid,
                        deadline=deadline))
            except Exception as exc:  # noqa: BLE001
                return await reply_error(exc)
            if resp is None:
                return await reply_error(
                    RuntimeError("worker returned no response"))
            if not resp.get("ok"):
                # error responses piggyback worker spans too — ingest
                # them so the failed call (the one being debugged) shows
                # its full tree in /_trace
                stats = resp.pop("device_stats", None)
                if stats:
                    self._merge_worker_stats(stats)
                return await reply_error(
                    resp["error"],
                    t=self._call_timings(resp, t_recv, t_exec))
            if "stream" in resp:
                if header.get("stream"):
                    return await self._channel_stream(
                        session, entry, reply, reply_error,
                        resp["stream"], t_recv, t_exec)
                resp, err = await self._drain_stream(
                    resp, ser, self.supervisor.allowed)
                if err is not None:
                    return await reply_error(err["error"])
            stats = resp.pop("device_stats", None)
            if stats:
                self._merge_worker_stats(stats)
            t = self._call_timings(resp, t_recv, t_exec)
            session.note_exec(t.get("server_s", 0.0))
            used = resp.get("serialization", ser)
            t0_reply = time.perf_counter()
            await reply({"kind": "result", "ser": used, "t": t},
                        resp["payload"])
            tracing.record_span(
                "server.reply", time.perf_counter() - t0_reply,
                parent=getattr(sspan, "context", None),
                attrs={"bytes": len(resp["payload"] or b"")})
        except asyncio.CancelledError:
            # session expiry cancelled this execution mid-flight
            raise
        except Exception as exc:  # noqa: BLE001 — a reply must always go
            try:
                await reply_error(exc)
            # ktlint: disable=KT004 -- retention full / teardown races only
            except Exception:  # noqa: BLE001
                pass
        finally:
            # failed channel calls must read as failed in /_trace, same
            # as the POST path's server.call span. The in-flight gauge is
            # owned by the session (released at the terminal frame —
            # including terminals written while no socket is attached);
            # here we only mirror it into the JSON metrics dict.
            sspan.end(error=(span_error[0] if span_error else None))
            tracing.maybe_push_slow(
                sspan.span["trace_id"] if sspan.span else None,
                time.perf_counter() - t_recv)
            self.metrics["serving_channel_inflight"] = \
                prom.channel_inflight(0)

    async def _channel_stream(self, session, entry, reply, reply_error,
                              stream, t_recv, t_exec):
        """Forward a generator result over the channel: one 'item' frame
        per yielded chunk (opaque payload + per-item serialization +
        monotonic ``seq`` in the header), then 'end' with the timing
        decomposition — the channel twin of :meth:`_respond_stream`.
        Frames are retained on the session entry, so a partition
        mid-stream costs nothing: the client replays with a resume
        cursor and delivery restarts at cursor+1, not token zero."""
        from kubetorch_tpu.exceptions import ReplayExpired
        from kubetorch_tpu.serving.replay import DETACHED_FRAME_CAP

        loop = asyncio.get_running_loop()
        it = iter(stream)
        try:
            while True:
                chunk = await loop.run_in_executor(None, next, it, None)
                if chunk is None:
                    break
                if session.ws is None and (
                        len(entry.frames) > DETACHED_FRAME_CAP
                        or entry.lost_detached):
                    # nobody is connected and either thousands of frames
                    # piled up or the byte cap already trimmed frames the
                    # absent client never received (large chunks keep the
                    # frame COUNT low while making the stream unresumable
                    # for any cursor the client could hold): stop burning
                    # the worker and turn the entry into a typed refusal
                    cancel = getattr(stream, "cancel", None)
                    if cancel is not None:
                        cancel()
                    return await reply_error(ReplayExpired(
                        f"stream abandoned: {len(entry.frames)} frames "
                        f"({entry.frames_bytes} B, low_seq "
                        f"{entry.low_seq}) retained with no client "
                        f"attached"))
                await reply({"kind": "item",
                             "ser": chunk["serialization"]},
                            chunk["payload"])
        except TimeoutError as exc:
            return await reply_error(exc)
        except asyncio.CancelledError:
            cancel = getattr(stream, "cancel", None)
            if cancel is not None:
                cancel()
            raise
        terminal = stream.terminal or {}
        if not terminal.get("ok"):
            return await reply_error(terminal["error"])
        stats = terminal.get("device_stats")
        if stats:
            self._merge_worker_stats(stats)
        t = self._call_timings(dict(terminal), t_recv, t_exec)
        session.note_exec(t.get("server_s", 0.0))
        await reply({"kind": "end", "t": t})


def main():
    import argparse

    # first thing, before the app builds its locks: a KT_SAN=1 session
    # wants every lock in this pod instrumented and a report dumped to
    # the inherited KT_SAN_DIR at exit. Knob-gated BEFORE the import:
    # the analysis package costs ~86 ms, which an uninstrumented pod
    # (including KT_SAN=0) must not pay at boot
    if env_bool("KT_SAN"):
        from kubetorch_tpu.analysis import san

        san.install_from_env()

    parser = argparse.ArgumentParser(description="kubetorch_tpu pod server")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int,
                        default=env_int("KT_SERVER_PORT"))
    args = parser.parse_args()
    server = PodServer()
    web.run_app(server.build_app(), host=args.host, port=args.port,
                print=None, access_log=None)


if __name__ == "__main__":
    main()
