"""Subprocess isolation for user callables.

Reference design: ``serving/process_worker.py:16,109,218`` — a
multiprocessing.Process per local rank with its own request/response queues;
async callables are awaited on a persistent event loop, sync callables are
offloaded to a thread executor; distributed env vars
(RANK/WORLD_SIZE/LOCAL_RANK/NODE_RANK/POD_IPS, ``:75``) are set *before* user
imports run so jax/torch bootstrap sees them.

TPU-critical detail: workers use the ``spawn`` start method — a forked child
inheriting an initialized libtpu/XLA client is undefined behavior, and the
pod server itself must never import jax (the chips belong to the workers).
"""

from __future__ import annotations

import asyncio
import importlib
import inspect
import multiprocessing as mp
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional

from kubetorch_tpu import serialization
from kubetorch_tpu.config import env_int
from kubetorch_tpu.exceptions import DeadlineExceeded, package_exception
from kubetorch_tpu.observability import tracing

_CTX = mp.get_context("spawn")

# Sentinel request kinds
SETUP = "__setup__"
SHUTDOWN = "__shutdown__"
PROFILE = "__profile__"
CANCEL = "__cancel__"
EMERGENCY = "__emergency__"


def get_distributed_env_vars(
    rank: int, world_size: int, local_rank: int, node_rank: int,
    pod_ips: Optional[list] = None,
) -> Dict[str, str]:
    """Base env contract every worker gets (reference: process_worker.py:75)."""
    env = {
        "RANK": str(rank),
        "WORLD_SIZE": str(world_size),
        "LOCAL_RANK": str(local_rank),
        "NODE_RANK": str(node_rank),
    }
    if pod_ips:
        env["POD_IPS"] = ",".join(pod_ips)
    return env


def _deadline_check(deadline) -> None:
    """Raise :class:`DeadlineExceeded` when the propagated deadline
    (unix seconds, or None) has passed — the shared guard for the
    dispatch queue head, the executor queue head, and between streamed
    chunks."""
    if isinstance(deadline, (int, float)) and time.time() > float(deadline):
        raise DeadlineExceeded(
            f"deadline passed {time.time() - float(deadline):.2f}s before "
            f"execution", deadline=float(deadline))


def _maybe_device_stats() -> Optional[Dict[str, int]]:
    """Accelerator memory stats from THIS process (the one owning the TPU).

    DCGM-analogue for the metrics pipeline (SURVEY §5.5 "replace DCGM with
    TPU metrics"): summed over local devices, attached to call responses so
    the pod server can report them without ever touching the devices
    itself. Device stats only report when user code already *initialized*
    a backend — a bare ``import jax`` (e.g. for tree utils, or before a
    deliberate ``jax.distributed.initialize``) must not trigger device
    acquisition from the metrics hook. Host-side counters (restore +
    serving) ride along regardless — a jax-free callable still serves.
    """
    import sys

    agg: Dict[str, int] = {}
    jax = sys.modules.get("jax")
    try:
        if jax is not None:
            xla_bridge = sys.modules.get("jax._src.xla_bridge")
            if xla_bridge is not None and getattr(xla_bridge, "_backends",
                                                 None):
                devices = jax.local_devices()
                for dev in devices:
                    stats = dev.memory_stats() or {}
                    for key in ("bytes_in_use", "bytes_limit",
                                "peak_bytes_in_use"):
                        value = stats.get(key)
                        if value is not None:
                            agg[f"device_{key}"] = (
                                agg.get(f"device_{key}", 0) + value)
                agg["device_count"] = len(devices)
    except Exception:
        agg = {}
    _attach_worker_metrics(agg)
    return agg or None


def _attach_worker_metrics(agg: Dict[str, int]) -> None:
    """Piggyback this worker's process-local counters (weight-sync
    restores + serving call accounting) on the same response channel as
    the device stats: the counted work runs HERE, not in the pod server
    that answers /metrics — without the hop the pod would always report
    zeros.

    Reported as pid-tagged sub-dicts (NOT flat keys): the pod server
    keeps a per-worker snapshot and SUMS the ``*_total`` counters across
    workers — a flat last-writer-wins merge would make the pod's counters
    flip between workers' totals, which Prometheus reads as resets. The
    serving snapshot carries ONLY ``serving_worker_*`` keys — the
    server-process gauges/histogram sums are not this worker's to report
    (a zero here would clobber them in the non-``_total`` merge)."""
    try:
        from kubetorch_tpu.observability.prometheus import (
            engine_metrics,
            restore_metrics,
            serving_metrics,
            wire_metrics,
        )

        restore = restore_metrics()
        if restore.get("restore_count_total"):
            agg["data_store_restore"] = {"pid": os.getpid(), **restore}
        wire = wire_metrics()
        if any(wire.values()):
            agg["data_store"] = {"pid": os.getpid(), **wire}
        # quantized dcn allreduce + delta-broadcast counters: trainers
        # run in worker processes, so without the piggyback the pod's
        # coll_* family would stay zero forever
        from kubetorch_tpu.observability.prometheus import coll_metrics

        coll = coll_metrics()
        if any(coll.values()):
            agg["coll"] = {"pid": os.getpid(), **coll}
        serving = {k: v for k, v in serving_metrics().items()
                   if k.startswith("serving_worker_") and v}
        if serving:
            agg["serving"] = {"pid": os.getpid(), **serving}
        # serving-engine counters/gauges: the engine loop runs in THIS
        # process (it owns the device); the snapshot rides to the pod so
        # control frames and /metrics answer queue depth without a
        # worker (let alone device) hop
        engine = engine_metrics()
        if engine.get("engine_generations_total") or \
                engine.get("engine_steps_total"):
            agg["engine"] = {"pid": os.getpid(), **engine}
        # per-adapter tenant counters (dynamic families — one set per
        # named LoRA adapter): all keys end _total so the pod server's
        # cross-worker sum treats them like any other counter group
        from kubetorch_tpu.observability.prometheus import adapter_metrics

        adapters = adapter_metrics()
        if adapters:
            agg["adapter"] = {"pid": os.getpid(), **adapters}
        # named-histogram snapshot (engine TTFT buckets + exemplars):
        # rides whole, not flattened — the pod server merges bucket
        # vectors across workers and ships them to the controller in
        # telemetry frames so fleet-level p99s are computable
        from kubetorch_tpu.observability.prometheus import hist_metrics

        hists = hist_metrics()
        if hists:
            agg["hists"] = {"pid": os.getpid(), "h": hists}
        trace = tracing.trace_metrics()
        if trace.get("trace_spans_total"):
            agg["trace"] = {"pid": os.getpid(), **trace}
        # KT_SAN=1: ship this worker's lock-order graph whenever it grew
        # — the worker dies with the pod's os._exit and cannot reliably
        # dump its own report, so the pod server merges worker graphs
        # into its OWN runtime graph and its dump covers both.
        # sys.modules lookup, not an import: an uninstrumented worker
        # must not pay the analysis-package import on its first call
        san = sys.modules.get("kubetorch_tpu.analysis.san")
        if san is not None and san.active():
            graph = san.snapshot_graph_if_changed()
            if graph is not None:
                agg["san_graph"] = graph
        # engine flight recorder: ship the per-tick records appended
        # since the last call response (ring increments, each record at
        # most once). Same rationale as the san graph — the worker dies
        # with the pod's os._exit, so the pod keeps the merged rings
        # and serves /_flight + dumps flight-<pid>.json from them.
        fl = sys.modules.get("kubetorch_tpu.observability.flight")
        if fl is not None:
            records = fl.incremental()
            if records:
                agg["flight"] = {"pid": os.getpid(), "records": records}
    # ktlint: disable=KT004 -- metrics piggyback must never break a call
    except Exception:
        pass


def _load_target(root_path: str, import_path: str, name: str,
                 callable_type: str, init_args: Optional[dict]):
    """Import the user symbol from synced source inside the worker process."""
    if root_path and root_path not in sys.path:
        sys.path.insert(0, root_path)
    if root_path:
        # Re-synced code must reload the WHOLE project tree: reloading
        # only the entry module would keep every already-imported
        # submodule (e.g. an edited helper inside a package) at its old
        # bytes. Drop them from sys.modules so the import below
        # re-executes everything under root_path fresh.
        rp = os.path.realpath(root_path) + os.sep
        for mod_name, mod in list(sys.modules.items()):
            f = getattr(mod, "__file__", None)
            if f and os.path.realpath(f).startswith(rp):
                del sys.modules[mod_name]
        # A re-sync may have ADDED files; finder directory caches keyed on
        # coarse mtimes can miss same-second creations without this.
        importlib.invalidate_caches()
    module = importlib.import_module(import_path)
    obj = module
    for part in name.split("."):
        obj = getattr(obj, part)
    if callable_type == "cls":
        init_args = init_args or {}
        return obj(*init_args.get("args", []), **init_args.get("kwargs", {}))
    return obj


class _WorkerLoop:
    """Runs inside the spawned process."""

    def __init__(self, request_q, response_q):
        self.request_q = request_q
        self.response_q = response_q
        self.target = None
        self.callable_type = "fn"
        self.executor = ThreadPoolExecutor(
            max_workers=env_int("KT_WORKER_THREADS"))
        # req_ids whose streams the client abandoned (see _stream_result)
        self._cancelled: set = set()
        self._inflight: set = set()

    def _resolve_method(self, method_name: Optional[str]):
        if self.callable_type == "cls" and method_name:
            return getattr(self.target, method_name)
        if callable(self.target):
            return self.target
        raise AttributeError(
            f"no callable method {method_name!r} on target")

    def _profile(self, req: dict) -> dict:
        """start/stop a jax.profiler trace; stop returns the zipped
        TensorBoard trace directory."""
        import jax

        action = req.get("action")
        trace_dir = os.path.join(
            req.get("dir") or "/tmp/kt-profile",
            f"rank{os.environ.get('LOCAL_RANK', '0')}")
        if action == "start":
            # Fresh dir per capture: stale traces from a previous session
            # would otherwise ride along in the next stop's zip.
            if os.path.isdir(trace_dir):
                import shutil

                shutil.rmtree(trace_dir, ignore_errors=True)
            stale_zip = trace_dir.rstrip("/") + ".zip"
            if os.path.exists(stale_zip):
                os.unlink(stale_zip)
            os.makedirs(trace_dir, exist_ok=True)
            jax.profiler.start_trace(trace_dir)
            self._profile_dir = trace_dir
            return {"started": True, "dir": trace_dir}
        if action == "stop":
            jax.profiler.stop_trace()
            trace_dir = getattr(self, "_profile_dir", trace_dir)
            import zipfile

            # zip to a file, not bytes: the server process shares this
            # filesystem, so multi-GB traces never transit the mp queue.
            zip_path = trace_dir.rstrip("/") + ".zip"
            with zipfile.ZipFile(zip_path, "w", zipfile.ZIP_DEFLATED) as zf:
                for root, _, files in os.walk(trace_dir):
                    for name in files:
                        full = os.path.join(root, name)
                        zf.write(full, os.path.relpath(full, trace_dir))
            return {"stopped": True, "dir": trace_dir,
                    "zip_path": zip_path}
        raise ValueError(f"unknown profile action {action!r}")

    @staticmethod
    def _attach_trace(stats: Optional[Dict], seq0: int,
                      trace_id: Optional[str]) -> Optional[Dict]:
        """Piggyback this call's spans on the response next to the
        device stats: the worker's ring has no HTTP surface, so spans
        must hop to the pod server's ring to be exportable via
        ``GET /_trace`` (dedup by span_id there makes re-sends safe)."""
        spans = (tracing.recorder.since(seq0, trace_id=trace_id)
                 if trace_id else None)
        if spans:
            stats = dict(stats or {})
            stats["trace_spans"] = spans
        return stats

    async def _execute(self, req: dict) -> dict:
        req_id = req["req_id"]
        wspan = None
        try:
            if req["kind"] == SETUP:
                for key, value in (req.get("env") or {}).items():
                    os.environ[key] = str(value)
                if ("JAX_NUM_PROCESSES" in os.environ
                        and "JAX_COORDINATOR_ADDRESS" in os.environ):
                    # jax-framework workload: register the ClusterEnv so a
                    # bare jax.distributed.initialize() in user code picks
                    # up the injected contract (current JAX doesn't read
                    # process count/id from env by itself).
                    from kubetorch_tpu.distributed import cluster_env

                    cluster_env.register()
                self.callable_type = req.get("callable_type", "fn")
                self.target = _load_target(
                    req.get("root_path", ""), req["import_path"],
                    req["name"], self.callable_type, req.get("init_args"))
                return {"req_id": req_id, "ok": True, "payload": None}

            if req["kind"] == EMERGENCY:
                # Preemption: the pod server is inside its SIGTERM grace
                # window and THIS process owns the device state — run the
                # registered emergency-checkpoint callbacks (a trainer's
                # save(wait=True) + delta store push) in the executor so
                # an in-flight call keeps dispatching while we save.
                from kubetorch_tpu.resilience.preemption import (
                    run_emergency_checkpoints,
                )

                payload = await asyncio.get_running_loop().run_in_executor(
                    self.executor, run_emergency_checkpoints)
                return {"req_id": req_id, "ok": True, "payload": payload}

            if req["kind"] == PROFILE:
                # jax.profiler runs HERE, in the process that owns the TPU
                # (the server process never touches devices) — a real
                # improvement over the reference, which has no tracer
                # (SURVEY §5.1). Zipping a big trace happens in the thread
                # executor so in-flight calls keep dispatching.
                payload = await asyncio.get_running_loop().run_in_executor(
                    self.executor, self._profile, req)
                return {"req_id": req_id, "ok": True, "payload": payload}

            # Dispatch stage of the latency decomposition: how long the
            # request sat in the mp queue + event loop before user code
            # ran (time.time on both sides — perf_counter isn't
            # comparable across the process boundary).
            t_start = time.time()
            dispatch_s = max(0.0, t_start - float(
                req.get("_t_submit") or t_start))
            # Queue-head deadline check: the client's propagated deadline
            # (req["deadline"], unix seconds) passed while this request
            # transited the pool — executing it now is pure waste, and on
            # a loaded pod it would also delay every call queued behind
            deadline = req.get("deadline")
            _deadline_check(deadline)
            # Per-call env (distributed rank assignment happens at call time,
            # after quorum — reference: process_pool.call_all per-rank env).
            # KT_REQUEST_ID goes into a contextvar instead: env is
            # process-global and concurrent calls would mislabel each
            # other's log lines.
            call_env = dict(req.get("env") or {})
            rid = call_env.pop("KT_REQUEST_ID", "")
            for key, value in call_env.items():
                os.environ[key] = str(value)
            from kubetorch_tpu.observability.log_capture import (
                request_id_var,
            )

            rid_token = request_id_var.set(rid)
            # Trace context arrives in the request dict next to
            # request_id (the server's span, propagated by pool._submit):
            # activate it so every span from here down — including
            # dataplane spans from a user weight-sync restore — parents
            # correctly across the process boundary.
            trace_ctx = tracing.parse_ctx(req.get("trace"))
            trace_token = tracing.activate(trace_ctx) \
                if trace_ctx is not None else None
            seq0 = tracing.recorder.seq
            tracing.record_span(
                "worker.dispatch", dispatch_s,
                start=float(req.get("_t_submit") or t_start),
                parent=trace_ctx, remote=trace_ctx is not None)
            wspan = tracing.start_span(
                "worker.execute",
                attrs={"method": req.get("method") or "",
                       "rank": os.environ.get("LOCAL_RANK", "0")},
                remote=trace_ctx is not None)
            try:
                body = serialization.loads(req["body"], req["serialization"])
                args = body.get("args", [])
                kwargs = body.get("kwargs", {})
                fn = self._resolve_method(req.get("method"))
                # exec_s brackets ONLY the user callable (+ generator
                # drain): body deserialization above and result
                # serialization below are worker overhead, and folding
                # them into the 'device' stage would overstate device
                # time exactly where it matters (multi-MB pickled args)
                t_exec0 = time.perf_counter()
                if inspect.iscoroutinefunction(fn):
                    _deadline_check(deadline)
                    result = await fn(*args, **kwargs)
                else:
                    # copy_context propagates the request-id contextvar into
                    # the executor thread running the sync callable.
                    import contextvars as _cv

                    ctx = _cv.copy_context()

                    def _run_sync():
                        # re-check at the REAL queue head: sync callables
                        # queue in this worker's thread executor
                        # (KT_WORKER_THREADS), and that wait — not the mp
                        # transit — is where a loaded pod's deadline dies
                        _deadline_check(deadline)
                        return ctx.run(fn, *args, **kwargs)

                    result = await asyncio.get_running_loop().run_in_executor(
                        self.executor, _run_sync)
                if inspect.isgenerator(result) or inspect.isasyncgen(result):
                    # Stream: push one response per yielded item (the pool
                    # routes them to the caller as they land), then a
                    # terminal marker. The generator body runs here, still
                    # under this request's id/env.
                    if await self._stream_result(req, result):
                        # deadline passed between chunks: the items
                        # already shipped are the checkpoint; the
                        # terminal is a typed refusal, not a silent
                        # truncation
                        raise DeadlineExceeded(
                            "deadline passed between streamed chunks",
                            deadline=float(req["deadline"]))
                    wspan.end({"stream": True})
                    return {"req_id": req_id, "ok": True,
                            "stream_end": True,
                            "timings": self._call_timings(
                                time.perf_counter() - t_exec0, dispatch_s),
                            "device_stats": self._attach_trace(
                                _maybe_device_stats(), seq0,
                                wspan.span["trace_id"]
                                if wspan.span else None)}
                exec_s = time.perf_counter() - t_exec0
                wspan.end({"exec_ms": round(exec_s * 1e3, 3)})
            finally:
                request_id_var.reset(rid_token)
                if trace_token is not None:
                    tracing.deactivate(trace_token)
            payload, used = serialization.choose(
                {"result": result}, req["serialization"],
                req.get("allowed", serialization.METHODS))
            return {"req_id": req_id, "ok": True, "payload": payload,
                    "serialization": used,
                    "timings": self._call_timings(exec_s, dispatch_s),
                    "device_stats": self._attach_trace(
                        _maybe_device_stats(), seq0,
                        wspan.span["trace_id"] if wspan.span else None)}
        except BaseException as exc:  # noqa: BLE001 — must package everything
            if isinstance(exc, (KeyboardInterrupt, SystemExit)):
                raise
            resp = {"req_id": req_id, "ok": False,
                    "error": package_exception(exc)["error"]}
            if wspan is not None:
                wspan.end(error=f"{type(exc).__name__}: {exc}")
                # failed calls are the PRIMARY tracing use case: their
                # worker spans must still reach the pod's exportable
                # ring, so piggyback them on the error response too
                stats = self._attach_trace(
                    None, seq0,
                    wspan.span["trace_id"] if wspan.span else None)
                if stats:
                    resp["device_stats"] = stats
            return resp

    def _call_timings(self, exec_s: float, dispatch_s: float) -> dict:
        """Worker-side stages of the per-call decomposition: ``exec_s``
        is the user callable's wall time in THIS process — for an engine
        chunk that IS the device time (the one host sync included) —
        ``dispatch_s`` the queue transit from the pod server. Also folds
        both into the worker's serving counters (summed across worker
        processes by the pod server's pid-tagged merge)."""
        try:
            from kubetorch_tpu.observability.prometheus import (
                record_worker_call,
            )

            record_worker_call(exec_s, dispatch_s)
        # ktlint: disable=KT004 -- metrics recording must never break a call
        except Exception:  # noqa: BLE001
            pass
        return {"exec_s": round(exec_s, 6), "dispatch_s": round(
            dispatch_s, 6)}

    async def _stream_result(self, req: dict, gen) -> bool:
        """Drain a (sync or async) generator result, pushing each item as
        its own response message (``stream: True``, ordered ``seq``). A
        ``cancel`` control message (client disconnected) closes the
        generator between items so it doesn't hold an executor thread.
        The propagated deadline is re-checked between chunks — each
        yielded item is a natural checkpoint; past the deadline the
        generator is closed and ``True`` is returned so the caller ends
        the stream with a typed ``DeadlineExceeded`` terminal."""
        req_id = req["req_id"]
        ser = req["serialization"]
        allowed = req.get("allowed", serialization.METHODS)
        deadline = req.get("deadline")
        deadline = (float(deadline)
                    if isinstance(deadline, (int, float)) else None)

        def _chunk(item, seq):
            payload, used = serialization.choose(
                {"result": item}, ser, allowed)
            return {"req_id": req_id, "ok": True, "stream": True,
                    "seq": seq, "payload": payload, "serialization": used}

        deadline_hit = False
        if inspect.isasyncgen(gen):
            seq = 0
            async for item in gen:
                if req_id in self._cancelled:
                    await gen.aclose()
                    break
                if deadline is not None and time.time() > deadline:
                    deadline_hit = True
                    await gen.aclose()
                    break
                self.response_q.put(_chunk(item, seq))
                seq += 1
        else:
            def _pump():
                nonlocal deadline_hit
                try:
                    for seq, item in enumerate(gen):
                        if req_id in self._cancelled:
                            break
                        if deadline is not None and time.time() > deadline:
                            deadline_hit = True
                            break
                        self.response_q.put(_chunk(item, seq))
                finally:
                    gen.close()

            # copy_context: the generator body logs under this request's id
            import contextvars as _cv

            ctx = _cv.copy_context()
            await asyncio.get_running_loop().run_in_executor(
                self.executor, lambda: ctx.run(_pump))
        self._cancelled.discard(req_id)
        return deadline_hit

    async def run(self):
        loop = asyncio.get_running_loop()
        while True:
            req = await loop.run_in_executor(None, self.request_q.get)
            if req is None or req.get("kind") == SHUTDOWN:
                break
            if req.get("kind") == CANCEL:
                # Only mark live requests: a CANCEL racing a completed (or
                # plain, already-answered) call must not grow the set
                # forever on a long-lived pod.
                if req.get("target") in self._inflight:
                    self._cancelled.add(req.get("target"))
                continue
            # Execute concurrently so async user code overlaps.
            rid = req.get("req_id")
            self._inflight.add(rid)
            task = asyncio.ensure_future(self._execute(req))

            def _finish(t, rid=rid):
                self._inflight.discard(rid)
                self._cancelled.discard(rid)
                self.response_q.put(
                    t.result() if not t.cancelled() else None)

            task.add_done_callback(_finish)


def worker_main(request_q, response_q, env: Dict[str, str]):
    """Entrypoint of the spawned process."""
    for key, value in env.items():
        os.environ[key] = str(value)
    # before user code can import jax: this is the process that compiles
    from kubetorch_tpu.config import compile_cache_dir

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    # before any lock is created: a KT_SAN=1 session instruments the
    # worker too (engine scheduler locks live HERE) — its graph
    # piggybacks to the pod on call responses (_attach_worker_metrics)
    # and also dumps via atexit on the graceful-shutdown path. Knob-
    # gated BEFORE the import: the analysis package costs ~86 ms, which
    # every uninstrumented worker spawn (incl. restart paths) must not
    # pay
    from kubetorch_tpu.config import env_bool

    if env_bool("KT_SAN"):
        from kubetorch_tpu.analysis import san

        san.install_from_env()
    tracing.set_process_label(
        f"worker-r{os.environ.get('LOCAL_RANK', '0')}")
    # Stream this worker's stdout/stderr/logging to the log sink, labeled
    # with rank + request id (reference forwards subprocess logs over a
    # queue, serving/log_capture.py; direct push is simpler and per-rank).
    try:
        from kubetorch_tpu.observability.log_capture import install_from_env

        install_from_env("worker")
    # ktlint: disable=KT004 -- log streaming is optional; stdout still works
    except Exception:
        pass
    try:
        asyncio.run(_WorkerLoop(request_q, response_q).run())
    except KeyboardInterrupt:
        pass


class ProcessWorker:
    """Parent-side handle for one worker subprocess (one local rank)."""

    def __init__(self, local_rank: int, env: Optional[Dict[str, str]] = None):
        self.local_rank = local_rank
        self.request_q = _CTX.Queue()
        self.response_q = _CTX.Queue()
        self.env = dict(env or {})
        self.process = _CTX.Process(
            target=worker_main,
            args=(self.request_q, self.response_q, self.env),
            daemon=True,
            name=f"kt-worker-{local_rank}",
        )

    def start(self):
        self.process.start()

    def send(self, req: dict):
        self.request_q.put(req)

    def stop(self, timeout: float = 5.0):
        try:
            self.request_q.put({"kind": SHUTDOWN, "req_id": SHUTDOWN})
            self.process.join(timeout)
        finally:
            if self.process.is_alive():
                self.process.terminate()
                self.process.join(2.0)
            if self.process.is_alive():
                self.process.kill()

    @property
    def alive(self) -> bool:
        return self.process.is_alive()
