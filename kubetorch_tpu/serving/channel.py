"""Persistent, multiplexed call channel to a pod server.

The per-call POST path pays one connection + header negotiation + two
full serialize/deserialize hops per call, a fixed cost a client-driven
decode loop pays on every chunk. This channel removes the per-call share
of that cost:

- **one long-lived WebSocket** (``GET /_channel`` on the pod server)
  carries every call — connection and header cost amortize to zero;
- **pipeline depth**: up to ``depth`` calls may be in flight at once, so
  the client serializes + ships decode chunk N+1 while chunk N is still
  on device. ``depth=1`` degenerates to strict request/response (the old
  numbers); depth 2 is enough to hide a dispatch tax smaller than the
  per-chunk device time;
- **opaque payloads**: the pod server parses only the tiny JSON control
  header; the call body and the result payload pass through
  PodServer → ProcessPool → ProcessWorker as bytes (zero
  re-serialization at the pod hop);
- **in-order execution**: calls on one channel execute FIFO on the
  server (unless submitted with ``concurrent=True``), so a stateful
  engine like :class:`~kubetorch_tpu.models.rolling.RollingDecoder` can
  be driven pipelined without interleaving chunks. An exception on chunk
  N rehydrates on N's handle; N+1 (already in flight) still runs and
  resolves independently.

**Delivery semantics (exactly-once per idempotency key).** Every call
carries ``(channel epoch, cid)`` — the epoch is a per-channel id that
survives reconnects (it rides the ``X-KT-Channel-Epoch`` connect
header), and cids are monotonic. On a dropped socket the channel
*recovers* instead of failing: calls queued but never written are
re-queued verbatim (they cannot have executed — no idempotency needed),
while written-but-unacknowledged calls are re-submitted with
``replay=true`` and a ``resume_from`` cursor (last received stream seq
+ 1). The server's session (``serving/replay.py``) then replays retained
frames, re-attaches to a still-running execution, or runs the call fresh
— never twice. :class:`ChannelInterrupted` is an internal recovery event
now; it surfaces only when the server's retention window expired or
``KT_REPLAY_ATTEMPTS`` reconnects failed (or with ``replay=False``,
restoring the old fail-fast contract).

All socket writes flow through ONE writer coroutine draining a
cid-ordered outbox — the invariant that makes both FIFO-across-
reconnects and the written/unwritten distinction exact.

Every call handle carries a latency decomposition (client serialize,
wire, server queue, worker dispatch, device) — the same stages the
Prometheus histograms in ``observability/prometheus.py`` record — so the
gap between the call path's wall and device time stays a measured number.

The channel owns a private event-loop thread; ``submit``/``result`` are
called from ordinary (sync) code. Wire format: one WebSocket binary
message per call/response, ``frames.pack_envelope`` layout.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import queue
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, Iterable, Optional

from kubetorch_tpu import serialization
from kubetorch_tpu.config import env_int
from kubetorch_tpu.exceptions import ReplayExpired, rehydrate_exception
from kubetorch_tpu.observability import tracing
from kubetorch_tpu.serving import frames
from kubetorch_tpu.serving.circuit import breaker_for

DEFAULT_DEPTH_ENV = "KT_CHANNEL_DEPTH"


def _set_nodelay(conn) -> None:
    """Disable Nagle on the channel socket. aiohttp (3.11) never sets
    TCP_NODELAY itself, and the pipelined pattern is exactly the one
    Nagle punishes: the client writes chunk N+1 while N's bytes are
    still unacknowledged, so the second small write sits in the kernel
    until the peer's (possibly delayed) ACK — measured as 25-50 ms
    stalls per chunk, bigger than the dispatch tax the pipeline exists
    to hide. Depth-1 (strict request/response) never trips it, which is
    why the bug only shows with pipelining on."""
    try:
        transport = getattr(conn, "transport", None)
        if transport is not None:
            from aiohttp.tcp_helpers import tcp_nodelay

            tcp_nodelay(transport, True)
    # ktlint: disable=KT004 -- an exotic transport without TCP still works
    except Exception:  # noqa: BLE001
        pass


def default_depth() -> int:
    return max(1, env_int(DEFAULT_DEPTH_ENV))


def _chaos_policy():
    """Active fault-injection policy, or None (the overwhelmingly common
    case — one env-string compare per send)."""
    try:
        from kubetorch_tpu.resilience import chaos

        return chaos.active()
    # ktlint: disable=KT004 -- chaos injection must never break serving
    except Exception:  # noqa: BLE001
        return None


def _record_reliability(event: str, value: float = 1.0) -> None:
    from kubetorch_tpu.serving.replay import record_reliability_event

    record_reliability_event(event, value)


class ChannelClosedError(ConnectionError):
    """The channel dropped with this call unresolved. The call may or may
    not have executed — resubmitting a non-idempotent call is on the
    caller (same contract as the POST path's read-failure case)."""


class ChannelInterrupted(ChannelClosedError):
    """Recovery for these calls is exhausted: the connection dropped and
    either the server's retention window expired (``ReplayExpired``) or
    ``KT_REPLAY_ATTEMPTS`` reconnects failed — so the channel can no
    longer prove whether they executed. The handle carries the
    ``call_ids`` so a caller replaying idempotent work knows exactly
    WHICH submissions are in doubt. With transparent replay on (the
    default), a plain drop never surfaces this."""

    def __init__(self, message: str, call_ids=()):
        super().__init__(message)
        self.call_ids = tuple(call_ids)

    def __str__(self) -> str:
        base = super().__str__()
        if self.call_ids:
            return f"{base} (unacknowledged call ids: {list(self.call_ids)})"
        return base


class ChannelCall:
    """Handle for one in-flight channel call."""

    def __init__(self, cid: int, client_ser_s: float, stream: bool,
                 timeout: Optional[float], on_terminal):
        self.cid = cid
        self.stream = stream
        self._timeout = timeout
        self._on_terminal = on_terminal
        self._event = threading.Event()
        self._payload: Optional[bytes] = None
        self._ser = serialization.DEFAULT
        self._exc: Optional[BaseException] = None
        self._items: "queue.SimpleQueue" = queue.SimpleQueue()
        self._t_send = time.perf_counter()
        # decomposition (seconds); wire fills in at terminal
        self._t: Dict[str, float] = {"client_ser": client_ser_s}
        # client-side "channel.call" span: opened by submit(), ended at
        # the terminal frame (the ISSUE's "inflight" span — send to
        # resolution, the client wall the decomposition splits)
        self._span = None
        # --- recovery state (owned by the channel's loop thread) ---
        self._header: Dict[str, Any] = {}
        self._body: bytes = b""
        self._written = False    # reached ws.send_bytes (in doubt on drop)
        self._next_seq = 0       # next stream-item seq expected (the ack
        #                          cursor: everything below it arrived)
        self._ooo: Dict[int, Any] = {}  # ahead-of-order frames, held
        #                          until the gap fills (replay overlap)
        self._attempts = 0       # recovery rounds survived

    # ------------------------------------------------------ loop side
    def _resolve(self, header: dict, payload: bytes):
        kind = header.get("kind")
        server_t = header.get("t") or {}
        # any frame is progress: a recovery round that WORKED must not
        # count against the replay-attempt budget, or a long stream
        # over a flaky link dies after N successful recoveries
        self._attempts = 0
        if kind == "item":
            seq = header.get("seq")
            item = (header.get("ser", serialization.DEFAULT), payload)
            if isinstance(seq, int):
                # strict in-order delivery by seq: duplicates (below the
                # cursor) drop, ahead-of-order frames (a live frame
                # racing a replay pass) wait in _ooo until the gap fills
                # — never a silent gap, never a reorder
                if seq < self._next_seq:
                    return False
                if seq > self._next_seq:
                    self._ooo[seq] = item
                    return False
                self._items.put(item)
                self._next_seq += 1
                while self._next_seq in self._ooo:
                    self._items.put(self._ooo.pop(self._next_seq))
                    self._next_seq += 1
            else:
                self._items.put(item)
            return False
        if kind == "error":
            try:
                self._exc = rehydrate_exception(json.loads(payload))
            except Exception:  # noqa: BLE001 — malformed error frame
                self._exc = RuntimeError(
                    f"channel call {self.cid} failed: {payload[:200]!r}")
            if isinstance(self._exc, ReplayExpired):
                # the ONE case recovery cannot hide: the server saw this
                # call once but its retained result is gone — surface
                # the typed interruption the docstring promises
                self._exc = ChannelInterrupted(
                    str(self._exc), call_ids=(self.cid,))
        elif kind == "result":
            self._payload = payload
            self._ser = header.get("ser", serialization.DEFAULT)
            if self.stream:
                # a stream=True call whose method returned a plain value:
                # surface it as a one-item stream, matching the POST
                # path's non-generator fallback — never drop a result
                self._items.put((self._ser, payload))
        # kind == "end": stream finished cleanly (no payload)
        self._finish(server_t)
        return True

    def _fail(self, exc: BaseException):
        self._exc = exc
        # record=False: a transport failure's wall time (which can be
        # the whole pending duration) is not a round trip — it would
        # poison the wire histogram the latency decomposition is built on
        self._finish({}, record=False)

    def _finish(self, server_t: Dict[str, float], record: bool = True):
        wall = time.perf_counter() - self._t_send
        self._t["wall"] = wall
        for stage, key in (("server", "server_s"),
                           ("server_queue", "queue_s"),
                           ("worker_dispatch", "dispatch_s"),
                           ("device", "exec_s")):
            if isinstance(server_t.get(key), (int, float)):
                self._t[stage] = float(server_t[key])
        self._t["wire"] = max(0.0, wall - self._t.get("server", 0.0))
        if self._span is not None:
            # end() is idempotent; the handle stays on the call so
            # callers (and tests) can read the trace id afterwards
            self._span.end({k: round(v * 1e3, 3)
                            for k, v in self._t.items()},
                           error=(type(self._exc).__name__
                                  if self._exc is not None else None))
        if record:
            try:
                from kubetorch_tpu.observability import prometheus as prom

                prom.record_call_stages(
                    {"client_ser": self._t["client_ser"],
                     "wire": self._t["wire"]})
            # ktlint: disable=KT004 -- metrics must never break a call
            except Exception:  # noqa: BLE001
                pass
        self._items.put(None)  # unblock a stream iterator
        cb, self._on_terminal = self._on_terminal, None
        if cb is not None:
            cb()
        self._event.set()

    # ---------------------------------------------------- caller side
    @property
    def done(self) -> bool:
        return self._event.is_set()

    @property
    def timings(self) -> Dict[str, float]:
        """Latency decomposition in milliseconds (after completion):
        ``client_ser / wire / server_queue / worker_dispatch / device``
        plus ``server`` (total in-server) and ``wall``."""
        return {k: v * 1e3 for k, v in self._t.items()}

    def result(self, timeout: Optional[float] = None) -> Any:
        """Block for the terminal response; returns the deserialized
        result or raises the rehydrated remote exception. Streamed calls
        return ``self`` (iterate for items)."""
        timeout = timeout if timeout is not None else self._timeout
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"channel call {self.cid} timed out after {timeout}s")
        if self._exc is not None:
            raise self._exc
        if self.stream:
            return self
        data = serialization.loads(self._payload, self._ser)
        if isinstance(data, dict) and "result" in data:
            return data["result"]
        return data

    def __iter__(self):
        """Stream items as they arrive (``submit(..., stream=True)``)."""
        while True:
            try:
                item = self._items.get(timeout=self._timeout)
            except queue.Empty:
                raise TimeoutError(
                    f"channel stream {self.cid} stalled: no item within "
                    f"{self._timeout}s") from None
            if item is None:
                if self._exc is not None:
                    raise self._exc
                return
            ser, payload = item
            yield serialization.loads(payload, ser)["result"]


class CallChannel:
    """Client of ``PodServer.h_channel``. Thread-safe: submit from any
    thread; a private event-loop thread owns the socket.

    >>> chan = CallChannel(url, "decoder", depth=2)
    >>> calls = [chan.submit("step") for _ in range(8)]   # pipelined
    >>> events = [c.result() for c in calls]              # in order
    """

    def __init__(self, base_url: str, callable_name: str,
                 method: Optional[str] = None, depth: Optional[int] = None,
                 ser: str = serialization.DEFAULT,
                 allowed: Iterable[str] = serialization.METHODS,
                 connect_timeout: float = 10.0,
                 call_timeout: Optional[float] = None,
                 replay: bool = True):
        self.base_url = base_url.rstrip("/")
        self.callable_name = callable_name
        self.default_method = method
        self.depth = depth if depth is not None else default_depth()
        self.ser = ser
        self.allowed = tuple(allowed)
        self.connect_timeout = connect_timeout
        self.call_timeout = call_timeout
        # exactly-once identity: (epoch, cid) is the idempotency key the
        # server's retention ring is keyed on. A fresh epoch per channel
        # — never per connection — is what lets a reconnect replay.
        self.epoch = uuid.uuid4().hex[:12]
        self.replay = replay
        self.replays = 0    # written-unacked calls re-submitted as replays
        self.requeues = 0   # queued-unwritten calls re-sent verbatim
        self._breaker = breaker_for(self.base_url)
        self._sem = (threading.BoundedSemaphore(self.depth)
                     if self.depth and self.depth > 0 else None)
        # serializes cid allocation → registration → enqueue: concurrent
        # submit threads must hit the outbox in cid order, or the
        # server's monotonic-cid watermark (the ReplayExpired refusal)
        # misreads an out-of-order lost write as an evicted result
        self._submit_lock = threading.Lock()
        self._cids = itertools.count(1)
        self._calls: Dict[int, ChannelCall] = {}
        # Lock order (ktsan-audited): _submit_lock is always taken
        # OUTSIDE _calls_lock (submit/control register under both);
        # _calls_lock blocks are snapshot-only — never an await, never
        # a callback — so the loop thread and submitter threads can
        # both take it without ordering against the asyncio side.
        self._calls_lock = threading.Lock()
        self._loop = None
        self._thread: Optional[threading.Thread] = None
        self._loop_lock = threading.Lock()
        self._loop_ready = threading.Event()
        # guards _ensure_ws: a burst of first submits must not each dial
        # a socket (calls split across connections would break the FIFO
        # ordering contract). asyncio primitives bind to the loop on
        # first await (py3.10+), so creating them here off-loop is safe.
        import asyncio as _asyncio

        self._connect_lock = _asyncio.Lock()
        # ALL socket writes drain from this cid-ordered outbox through
        # ONE writer coroutine — the single-writer invariant is what
        # keeps FIFO order exact across reconnects and makes
        # written-vs-queued a crisp distinction at disconnect time
        self._outbox: deque = deque()
        self._outbox_event = _asyncio.Event()
        self._writer = None
        self._conn_gen = 0          # bumped by every disconnect recovery
        self._connect_failures = 0  # consecutive, for replay attempts
        self._ws = None
        self._session = None
        self._reader = None
        self._ever_connected = False
        self._closed = False
        self.connects = 0  # lifetime connections (1 + reconnects)

    # --------------------------------------------------------- public
    def submit(self, *args, method: Optional[str] = None,
               kwargs: Optional[dict] = None, ser: Optional[str] = None,
               stream: bool = False, concurrent: bool = False,
               timeout: Optional[float] = None,
               deadline_s: Optional[float] = None) -> ChannelCall:
        """Serialize + enqueue one call; returns immediately with a
        handle unless ``depth`` calls are already in flight (then blocks
        until a slot frees — that backpressure IS the pipeline depth).

        For a unary call, ``timeout`` (explicit or the channel's
        ``call_timeout``) also becomes the propagated **deadline**
        (``now + timeout``): it rides the control header to the pod,
        which rejects the call at the queue head — typed
        ``DeadlineExceeded`` — instead of executing work the client
        stopped waiting for. For ``stream=True`` calls ``timeout`` stays
        what it always was — a per-item stall bound — because a healthy
        long stream must not be killed by an absolute clock; pass
        ``deadline_s`` to give any call (streams included) an explicit
        whole-call budget, enforced between chunks server-side.

        ``concurrent=True`` opts this call out of the channel's FIFO
        execution order (independent requests that may run on any free
        worker); the default keeps per-channel ordering for stateful
        engines."""
        if self._closed:
            raise ChannelClosedError("channel is closed")
        self._breaker.check()  # fail fast on an endpoint known dead
        from kubetorch_tpu.resources.callables.pointers import (
            build_call_body,
        )

        t0 = time.perf_counter()
        ser_wall0 = time.time()
        body, used = serialization.choose(
            build_call_body(args, kwargs or {}), ser or self.ser,
            self.allowed)
        ser_s = time.perf_counter() - t0
        if self._sem is not None:
            self._sem.acquire()
        # one atomic section from cid allocation to enqueue: the
        # outbox must see cids in allocation order (see _submit_lock)
        with self._submit_lock:
            cid = next(self._cids)
            effective_timeout = (timeout if timeout is not None
                                 else self.call_timeout)
            call = ChannelCall(
                cid, ser_s, stream, effective_timeout,
                (self._sem.release if self._sem is not None else None))
            # NOT registered in _calls yet: a disconnect recovery on the loop
            # thread enumerates _calls, and a half-initialized call (header/
            # body unset) would be requeued as an empty envelope and then
            # skipped forever — registration happens after the header below
            # one span per call, opened at submit, closed at the terminal
            # frame; its context rides the control header so the server (and
            # transitively the worker) parent under it. Backdated to t0:
            # serialization AND the pipeline-slot wait (the backpressure
            # blocking above) are part of the user-perceived call, and the
            # channel.send child must not precede its parent. detach() right
            # away: pipelined submits must be siblings, not nested.
            hspan = tracing.start_span(
                "channel.call", started_perf=t0, attrs={
                    "cid": cid, "callable": self.callable_name,
                    "method": method or self.default_method or "",
                    "transport": "channel"})
            trace = tracing.format_ctx(getattr(hspan, "context", None))
            hspan.detach()
            call._span = hspan if trace is not None else None
            tracing.record_span("channel.send", ser_s, start=ser_wall0,
                                parent=getattr(hspan, "context", None),
                                attrs={"bytes": len(body)})
            header = {
                "cid": cid, "kind": "call",
                "callable": self.callable_name,
                "method": method or self.default_method,
                "ser": used, "stream": bool(stream),
                "concurrent": bool(concurrent),
                "rid": uuid.uuid4().hex[:12],
            }
            # relative budget on the wire (the server stamps the absolute
            # deadline on ITS clock at receipt — skew-proof)
            if deadline_s is not None:
                header["timeout_s"] = float(deadline_s)
            elif effective_timeout is not None and not stream:
                header["timeout_s"] = float(effective_timeout)
            if trace:
                header["trace"] = trace
            call._header = header
            call._body = body
            call._t_send = time.perf_counter()
            with self._calls_lock:
                self._calls[cid] = call
            self._enqueue(cid)
        return call

    def call(self, *args, **kwargs) -> Any:
        """Submit + wait: drop-in for ``http_client.call_method`` on the
        channel (pipelining needs :meth:`submit`)."""
        return self.submit(*args, **kwargs).result()

    def control(self, op: str = "stats",
                timeout: Optional[float] = 10.0) -> Dict[str, Any]:
        """Out-of-band control round-trip (``kind: ctl`` frame): the pod
        server answers DIRECTLY from pod/session state plus the last
        worker-piggybacked ``engine_*`` snapshot — the frame never joins
        the session FIFO (it cannot queue behind pipelined decode
        chunks) and never costs a worker or device hop. The cheap way to
        poll queue depth / engine occupancy while a stream is live.

        Control frames don't consume a pipeline-depth slot (they are not
        calls) and are idempotent: a reconnect simply re-asks."""
        if self._closed:
            raise ChannelClosedError("channel is closed")
        self._breaker.check()
        with self._submit_lock:
            cid = next(self._cids)
            call = ChannelCall(cid, 0.0, False, timeout, None)
            call._header = {"cid": cid, "kind": "ctl", "op": op}
            call._body = b""
            call._t_send = time.perf_counter()
            with self._calls_lock:
                self._calls[cid] = call
            self._enqueue(cid)
        return call.result(timeout)

    @property
    def inflight(self) -> int:
        with self._calls_lock:
            return len(self._calls)

    def close(self):
        """Close the socket and fail any in-flight calls."""
        if self._closed:
            return
        self._closed = True
        if self._loop is not None:
            import asyncio

            try:
                asyncio.run_coroutine_threadsafe(
                    self._shutdown(), self._loop).result(5.0)
            # ktlint: disable=KT004 -- best-effort teardown on close
            except Exception:  # noqa: BLE001
                pass
            self._loop.call_soon_threadsafe(self._loop.stop)
            if self._thread is not None:
                self._thread.join(timeout=5.0)
        self._fail_pending(ChannelClosedError("channel closed"))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------ loop side
    def _ensure_loop(self):
        # locked: two threads racing the first submit must not each
        # spawn a loop thread — calls split across two loops would leak
        # one forever and break the single-socket FIFO contract
        with self._loop_lock:
            if self._thread is None:
                import asyncio

                def _run():
                    loop = asyncio.new_event_loop()
                    asyncio.set_event_loop(loop)
                    self._loop = loop
                    self._loop_ready.set()
                    loop.run_forever()
                    # drain pending tasks on stop, then close
                    try:
                        loop.run_until_complete(asyncio.sleep(0))
                    finally:
                        loop.close()

                self._thread = threading.Thread(
                    target=contextvars.copy_context().run, args=(_run,),
                    name="kt-channel", daemon=True)
                self._thread.start()
        self._loop_ready.wait(10.0)
        return self._loop

    def _enqueue(self, cid: int):
        loop = self._ensure_loop()

        def _put():
            import asyncio

            self._outbox.append(cid)
            self._outbox_event.set()
            if self._writer is None or self._writer.done():
                self._writer = asyncio.ensure_future(self._writer_loop())

        loop.call_soon_threadsafe(_put)

    def _get_call(self, cid: int) -> Optional[ChannelCall]:
        with self._calls_lock:
            return self._calls.get(cid)

    async def _writer_loop(self):
        """The only socket writer: drains the outbox in order, dialing
        (and re-dialing) as needed. On a connect failure it backs off
        with full jitter and retries, failing the pending calls only
        after the replay-attempt budget; on a generation bump (a
        disconnect recovery rebuilt the outbox) it discards its in-hand
        cid — the rebuild re-listed it in correct order."""
        import asyncio

        from kubetorch_tpu.retry import backoff_sleep_s

        delay = 0.05
        while not self._closed:
            while not self._outbox:
                self._outbox_event.clear()
                await self._outbox_event.wait()
            gen = self._conn_gen
            cid = self._outbox.popleft()
            call = self._get_call(cid)
            if call is None or call.done:
                continue
            if call._written and not call._header.get("replay"):
                # raced duplicate enqueue of an already-shipped call
                continue
            try:
                ws = await self._ensure_ws()
            except Exception as exc:  # noqa: BLE001 — connect failed
                self._breaker.record_failure()
                self._connect_failures += 1
                attempts = max(1, env_int("KT_REPLAY_ATTEMPTS"))
                if self._connect_failures >= attempts or not self.replay:
                    self._outbox.clear()
                    self._fail_pending(reason=(
                        f"call channel connect failed after "
                        f"{self._connect_failures} attempts: {exc}"))
                    self._connect_failures = 0
                    continue
                self._outbox.appendleft(cid)
                await asyncio.sleep(backoff_sleep_s(exc, delay, 2.0))
                delay = min(delay * 2, 2.0)
                continue
            self._connect_failures = 0
            delay = 0.05
            if gen != self._conn_gen:
                # a disconnect recovery ran while we dialed: it rebuilt
                # the outbox (this cid included) in cid order — writing
                # our stale in-hand copy now would break FIFO
                continue
            policy = _chaos_policy()
            if policy is not None:
                from kubetorch_tpu.resilience import chaos as chaos_mod

                if policy.decide(chaos_mod.DROP_CONNECTION, f"cid-{cid}"):
                    # the call was NOT written: the reader's recovery
                    # must requeue it, not replay it
                    await ws.close()
                    continue
                if policy.decide(chaos_mod.INJECT_LATENCY, f"cid-{cid}"):
                    await asyncio.sleep(policy.latency())
            if not self._call_alive(cid) or gen != self._conn_gen:
                continue
            # written BEFORE the await: a partial write is in doubt, and
            # in-doubt must replay (replay is dedup-safe server-side;
            # an optimistic "unwritten" would re-execute)
            call._written = True
            try:
                await ws.send_bytes(
                    frames.pack_envelope(call._header, call._body))
            # ktlint: disable=KT004 -- not a swallow: the call stays written/in-doubt and the reader's recovery replays it
            except Exception:  # noqa: BLE001 — socket died mid-write
                continue

    async def _ensure_ws(self):
        if self._ws is not None and not self._ws.closed:
            return self._ws
        async with self._connect_lock:
            if self._ws is not None and not self._ws.closed:
                return self._ws
            return await self._connect()

    async def _connect(self):
        import aiohttp

        if self._session is None:
            # long-lived WS session: no total bound (streams run for
            # minutes), but the dial itself is explicitly bounded
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(
                    total=None, sock_connect=self.connect_timeout))
        headers = {"X-KT-Channel-Epoch": self.epoch}
        if self._ever_connected:
            # tell the pod this is a re-dial: the server can't infer it
            # (it has no client identity), and reconnect churn must be
            # visible on the POD's /metrics, where operators alert
            headers["X-KT-Channel-Reconnect"] = "1"
        self._ws = await self._session.ws_connect(
            f"{self.base_url}/_channel", max_msg_size=1024 ** 3,
            timeout=aiohttp.ClientWSTimeout(ws_close=self.connect_timeout),
            heartbeat=30.0, headers=headers)
        _set_nodelay(getattr(self._ws, "_conn", None))
        self.connects += 1
        self._breaker.record_success()
        try:
            from kubetorch_tpu.observability import prometheus as prom

            prom.record_channel_event(
                "reconnect" if self._ever_connected else "connect")
        # ktlint: disable=KT004 -- metrics must never break a (re)connect
        except Exception:  # noqa: BLE001
            pass
        self._ever_connected = True
        import asyncio

        self._reader = asyncio.ensure_future(self._read(self._ws))
        return self._ws

    def _call_alive(self, cid: int) -> bool:
        with self._calls_lock:
            return cid in self._calls

    async def _read(self, ws):
        import aiohttp

        try:
            async for msg in ws:
                if msg.type == aiohttp.WSMsgType.BINARY:
                    self._dispatch(msg.data)
                elif msg.type in (aiohttp.WSMsgType.ERROR,
                                  aiohttp.WSMsgType.CLOSE):
                    break
        finally:
            # a dropped socket is a RECOVERY event, not a failure event:
            # unresolved calls are re-queued (never written — cannot
            # have executed) or replayed by idempotency key (written —
            # in doubt, and the server's retention dedups). Failure
            # surfaces only when recovery itself is exhausted.
            self._on_disconnect()

    def _on_disconnect(self):
        """Runs on the loop thread when the socket dies. Rebuilds the
        outbox from every pending call, in cid order, so the writer's
        next drain restores the exact submission order on the fresh
        socket."""
        if self._closed:
            self._fail_pending(ChannelClosedError("channel closed"))
            return
        self._conn_gen += 1
        with self._calls_lock:
            pending = sorted(
                (c for c in self._calls.values() if not c.done),
                key=lambda c: c.cid)
        if not pending:
            return
        if not self.replay:
            # fail-fast contract (replay=False): written calls are in
            # doubt → typed ChannelInterrupted naming exactly them.
            # Queued-but-unwritten calls never left this process — they
            # are safe to requeue even without any idempotency.
            written = [c for c in pending if c._written]
            unwritten = [c for c in pending if not c._written]
            if written:
                exc = ChannelInterrupted(
                    "call channel connection lost",
                    call_ids=[c.cid for c in written])
                with self._calls_lock:
                    for c in written:
                        self._calls.pop(c.cid, None)
                for c in written:
                    c._fail(exc)
            self._requeue(unwritten)
            return
        survivors = []
        doomed = []
        attempts = max(1, env_int("KT_REPLAY_ATTEMPTS"))
        for c in pending:
            c._attempts += 1
            if c._attempts > attempts:
                doomed.append(c)
                continue
            if c._written:
                c._header["replay"] = True
                c._header["resume_from"] = c._next_seq
                self.replays += 1
            else:
                self.requeues += 1
                _record_reliability("requeue")
            survivors.append(c)
        if doomed:
            exc = ChannelInterrupted(
                f"call channel recovery exhausted after {attempts} "
                f"attempts", call_ids=[c.cid for c in doomed])
            with self._calls_lock:
                for c in doomed:
                    self._calls.pop(c.cid, None)
            for c in doomed:
                c._fail(exc)
        self._requeue(survivors)

    def _requeue(self, calls):
        self._outbox.clear()
        self._outbox.extend(c.cid for c in calls)
        if calls:
            self._outbox_event.set()
            import asyncio

            if self._writer is None or self._writer.done():
                self._writer = asyncio.ensure_future(self._writer_loop())

    async def _shutdown(self):
        if self._ws is not None and not self._ws.closed:
            try:
                # clean goodbye: the server drops the session (and its
                # retention) immediately instead of holding it for the
                # full KT_RESULT_RETAIN_S window
                await self._ws.send_bytes(
                    frames.pack_envelope({"kind": "bye"}))
            # ktlint: disable=KT004 -- goodbye is best-effort by design
            except Exception:  # noqa: BLE001
                pass
        if self._writer is not None:
            self._writer.cancel()
        if self._reader is not None:
            self._reader.cancel()
        if self._ws is not None and not self._ws.closed:
            await self._ws.close()
        if self._session is not None:
            await self._session.close()

    def _dispatch(self, data: bytes):
        try:
            header, payload = frames.unpack_envelope(data)
        except Exception:  # noqa: BLE001 — a garbled frame kills nothing
            from kubetorch_tpu.observability import prometheus as prom

            prom.record_channel_event("error")
            return
        # every well-formed frame proves the endpoint alive: this also
        # RESOLVES a half-open breaker probe that a submit() consumed on
        # an already-connected socket (where _connect's record_success
        # never runs) — without it the shared breaker could wedge
        # half-open against a pod that is serving channel traffic fine
        self._breaker.record_success()
        cid = header.get("cid")
        with self._calls_lock:
            call = self._calls.get(cid)
        if call is None:
            return
        policy = _chaos_policy()
        if policy is not None:
            from kubetorch_tpu.resilience import chaos as chaos_mod

            seq = header.get("seq", header.get("kind"))
            if policy.decide(chaos_mod.PARTITION, f"cid-{cid}-{seq}"):
                # partition mid-stream: this frame is lost WITH the
                # connection (it was never delivered to the call), so
                # recovery must resume from the ack cursor — the exact
                # replay-from-cursor path the chaos kind exists to drive
                import asyncio

                ws = self._ws
                if ws is not None:
                    asyncio.ensure_future(ws.close())
                return
        if call._resolve(header, payload):
            self._drop_call(cid)

    def _drop_call(self, cid: int):
        with self._calls_lock:
            self._calls.pop(cid, None)

    def _fail_pending(self, exc: Optional[BaseException] = None,
                      reason: str = "call channel interrupted"):
        with self._calls_lock:
            pending, self._calls = list(self._calls.values()), {}
        if not pending:
            return
        if exc is None:
            exc = ChannelInterrupted(
                reason, call_ids=[call.cid for call in pending])
        for call in pending:
            call._fail(exc)
