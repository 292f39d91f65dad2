"""Server-side channel sessions: idempotent replay, result retention,
and admission control for ``PodServer.h_channel``.

Before this module, a channel's server-side state (FIFO queue, dispatcher
task, in-flight executions) lived on the WebSocket connection — a dropped
socket took all of it down, which is why ``ChannelInterrupted`` used to be
the *client's* problem for every in-flight call. Now the connection is
just a transport: the durable object is the :class:`ChannelSession`,
keyed by the client channel's ``epoch`` (a per-``CallChannel`` id that
survives reconnects and rides the ``X-KT-Channel-Epoch`` connect header).

One session owns:

- the **FIFO dispatcher** — execution order is per *logical channel*, not
  per connection, so a stateful engine driven pipelined keeps its
  ordering guarantee across partitions;
- the **result-retention ring** (``KT_RESULT_RETAIN`` entries): every
  reply frame of every call is recorded against its ``cid`` before it is
  written to whatever socket is currently attached. A reconnecting
  client re-submits unacknowledged calls with ``replay=true`` and a
  ``resume_from`` cursor (last acked stream seq + 1); the server then
  either **replays** the retained frames (already finished), **attaches**
  the new socket to a still-running execution, or — when the original
  submission never arrived — runs it **fresh**. Exactly-once per
  idempotency key ``(epoch, cid)``, enforced by `max_seen_cid`: cids are
  issued monotonically and written in order, so a replayed cid at or
  below the high-water mark whose entry is gone was *seen and evicted* —
  the server refuses with :class:`~kubetorch_tpu.exceptions.ReplayExpired`
  rather than risk double-executing;
- **admission control**: past ``KT_MAX_QUEUE_DEPTH`` queued+executing
  calls (or an estimated queue delay past ``KT_MAX_QUEUE_DELAY_S``) new
  calls are shed with a typed
  :class:`~kubetorch_tpu.exceptions.ServerOverloaded` carrying a
  computed ``retry_after`` — a fast retryable rejection instead of a
  timeout that wasted a queue slot. The estimate is
  :func:`retry_after_estimate`;
- **deadline enforcement at the queue head**: a call whose propagated
  ``deadline`` passed while it waited is rejected with
  :class:`~kubetorch_tpu.exceptions.DeadlineExceeded` without
  dispatching (the worker re-checks before and during execution).

Everything here runs on the pod server's event loop — no locks beyond
the per-socket send lock.
"""

from __future__ import annotations

import asyncio
import json
import time
import uuid
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from kubetorch_tpu.config import env_float, env_int
from kubetorch_tpu.exceptions import (
    DeadlineExceeded,
    ReplayExpired,
    ServerOverloaded,
    package_exception,
)
from kubetorch_tpu.observability import tracing
from kubetorch_tpu.serving import frames

# A detached session buffers frames of still-running streams so a
# reconnecting client can resume; past this many retained frames on one
# call with nobody connected, the client is presumed gone for good and
# the stream is cancelled (the entry turns into a ReplayExpired).
DETACHED_FRAME_CAP = 4096

_TERMINAL_KINDS = ("result", "error", "end")


def record_reliability_event(event: str, value: float = 1.0) -> None:
    """``prometheus.record_reliability`` behind the call path's
    must-never-raise guard — shared with the client channel."""
    try:
        from kubetorch_tpu.observability import prometheus as prom

        prom.record_reliability(event, value)
    # ktlint: disable=KT004 -- metrics must never break the call path
    except Exception:  # noqa: BLE001
        pass


_record = record_reliability_event


def retry_after_estimate(queue_depth: int, max_depth: int,
                         ema_exec_s: float,
                         cap_s: Optional[float] = None) -> float:
    """Seconds an overloaded pod tells a shed caller to stay away: the
    excess queue length times the recent per-call execution EMA — i.e.
    roughly when a slot will actually be free — floored at 50 ms (a
    zero tells the client to hammer) and capped at
    ``KT_MAX_QUEUE_DELAY_S`` (a server asking for minutes is not load
    shedding, it is down). The pod server and the engine's KV-block
    admission both price a shed with it."""
    if cap_s is None:
        cap_s = env_float("KT_MAX_QUEUE_DELAY_S")
    excess = max(1, queue_depth - max_depth + 1)
    return round(min(max(0.05, excess * max(0.01, ema_exec_s)), cap_s), 3)


class RetainedCall:
    """One call's retained server-side state (the retention-ring entry)."""

    __slots__ = ("cid", "frames", "done", "failed", "counted", "admitted",
                 "replaying", "next_seq", "low_seq", "frames_bytes",
                 "lost_detached", "created")

    def __init__(self, cid: int):
        self.cid = cid
        self.frames: deque = deque()  # deque: the byte-cap trim pops
        #                               from the front on the hot path
        self.done = False
        self.failed = False
        self.counted = False   # included in the inflight gauge (now)
        self.admitted = False  # was ever admitted for execution
        self.replaying = False  # a replay pass owns delivery right now
        self.next_seq = 0      # per-call stream-frame cursor
        self.low_seq = 0       # first item seq still retained (older
        #                        frames were trimmed under the byte cap)
        self.frames_bytes = 0  # retained bytes (incremental, O(1)/frame)
        self.lost_detached = False  # frames trimmed with NO client
        #                        attached: the stream is unresumable for
        #                        any cursor the absent client could hold
        self.created = time.time()

    @property
    def nbytes(self) -> int:
        return self.frames_bytes


class ChannelSession:
    """Durable server half of one logical client channel (one epoch)."""

    def __init__(self, epoch: str, execute: Callable, *,
                 ephemeral: bool = False, depth_fn: Optional[Callable] = None):
        self.epoch = epoch
        self.ephemeral = ephemeral  # no-epoch legacy client: dies with ws
        self._execute = execute  # async (session, entry, header, payload, t)
        # pod-global queued+executing count for admission (the knob is a
        # per-POD bound; falling back to this session's own depth keeps
        # direct/unit construction working)
        self._depth_fn = depth_fn
        self.ws = None
        # The session's ONLY lock (ktsan-audited): everything else in
        # this module runs on the single server loop, so mutual
        # exclusion is the event loop itself; send_lock serializes
        # whole-frame writes between a live delivery and a replay pass
        # (asyncio.Lock — holding it across the send await is the
        # point, and never wraps a sync lock).
        self.send_lock = asyncio.Lock()
        self.fifo: asyncio.Queue = asyncio.Queue()
        self.dispatcher: Optional[asyncio.Task] = None
        self.side_tasks: set = set()
        self.calls: Dict[int, RetainedCall] = {}
        self._done_order: deque = deque()
        self._done_bytes = 0
        # refusals (sheds / expired replays) are retained so their OWN
        # replay re-delivers the typed error — but in a separate ring:
        # a burst of tiny 429 terminals must not evict real results
        self._refusal_order: deque = deque()
        self.max_seen_cid = 0
        # the client re-dialed (X-KT-Channel-Reconnect) but this session
        # is brand new: its predecessor expired, so NO replay can be
        # trusted not to double-execute — all must be refused typed
        self.lost_history = False
        self.detached_at: Optional[float] = None
        self.expired = False
        self.last_activity = time.time()
        # recent per-call in-server seconds, EMA — feeds Retry-After
        self.ema_exec_s = 0.05

    # ------------------------------------------------------------ attach
    def attach(self, ws) -> None:
        self.ws = ws
        self.detached_at = None
        self.last_activity = time.time()
        if self.dispatcher is None or self.dispatcher.done():
            self.dispatcher = asyncio.ensure_future(self._dispatch_loop())

    def detach(self, ws) -> None:
        """The socket went away; executions keep running and frames keep
        accumulating in retention until the client re-attaches or the
        session expires (``KT_RESULT_RETAIN_S``)."""
        if self.ws is ws:
            self.ws = None
            self.detached_at = time.time()

    def expire(self) -> None:
        """Tear the session down: cancel the dispatcher (which cancels
        any in-flight FIFO execution at its next await) and side tasks,
        and release the inflight gauge for everything still counted."""
        if self.expired:
            return
        self.expired = True
        if self.dispatcher is not None:
            self.dispatcher.cancel()
        for task in list(self.side_tasks):
            task.cancel()
        while not self.fifo.empty():
            self.fifo.get_nowait()
        for entry in self.calls.values():
            self._release(entry)

    def _release(self, entry: RetainedCall) -> None:
        if entry.counted:
            entry.counted = False
            try:
                from kubetorch_tpu.observability import prometheus as prom

                prom.channel_inflight(-1)
            # ktlint: disable=KT004 -- gauge upkeep must not break teardown
            except Exception:  # noqa: BLE001
                pass

    @property
    def queue_depth(self) -> int:
        """Calls admitted but not yet terminal (queued + executing)."""
        return sum(1 for e in self.calls.values() if e.counted)

    def describe(self) -> Dict[str, Any]:
        """Session-level half of a control-frame answer (the pod server
        adds pod-wide depth and the engine snapshot). Cheap by
        construction: counters only, no retention walk."""
        return {"session_queue_depth": self.queue_depth,
                "session_ema_exec_s": round(self.ema_exec_s, 4),
                "session_retained": len(self.calls),
                "session_max_seen_cid": self.max_seen_cid}

    # ------------------------------------------------------------- send
    async def send(self, entry: RetainedCall, hdr: dict,
                   body: bytes = b"") -> bool:
        """Record one reply frame against the entry, then deliver it to
        the currently-attached socket (if any). Returns whether the frame
        reached a socket — callers must NOT treat False as failure: the
        frame is retained and will be replayed on re-attach."""
        hdr = dict(hdr)
        hdr["cid"] = entry.cid
        if hdr.get("kind") == "item":
            hdr["seq"] = entry.next_seq
            entry.next_seq += 1
        entry.frames.append((hdr, body))
        entry.frames_bytes += len(body) + 64
        if hdr.get("kind") in _TERMINAL_KINDS:
            self._finish(entry, failed=hdr.get("kind") == "error")
        elif not entry.replaying:
            # byte-bound the RUNNING entry too: a long attached stream
            # must not accumulate its whole output in pod memory. The
            # oldest item frames fall off the front; a later replay
            # asking to resume below low_seq gets a typed ReplayExpired
            # (bounded memory beats unbounded exactness — the window IS
            # the knob). Never trim while a replay pass is iterating by
            # index, and never trim the frame just appended.
            cap = max(1 << 20, env_int("KT_RESULT_RETAIN_BYTES"))
            while (entry.frames_bytes > cap and len(entry.frames) > 1
                    and entry.frames[0][0].get("kind") == "item"):
                old_hdr, old_body = entry.frames.popleft()
                entry.frames_bytes -= len(old_body) + 64
                entry.low_seq = old_hdr.get("seq", entry.low_seq) + 1
                if self.ws is None:
                    # trimmed frames the absent client never received:
                    # no reconnect cursor can resume this stream now
                    entry.lost_detached = True
        if entry.replaying:
            # a replay pass owns delivery for this entry: interleaving a
            # live frame with the catch-up would deliver out of order
            # (the client would then drop the replayed earlier frames as
            # duplicates — a permanent gap). The frame is retained; the
            # replay loop re-reads the list and delivers it in order.
            return False
        return await self._deliver(hdr, body)

    async def _deliver(self, hdr: dict, body: bytes) -> bool:
        ws = self.ws
        if ws is None or ws.closed:
            return False
        try:
            async with self.send_lock:
                await ws.send_bytes(frames.pack_envelope(hdr, body))
            return True
        except (ConnectionResetError, RuntimeError, OSError):
            # socket died under us: detach; frames stay retained
            self.detach(ws)
            return False

    def _finish(self, entry: RetainedCall, failed: bool) -> None:
        entry.done = True
        entry.failed = failed
        self._release(entry)
        retain = max(1, env_int("KT_RESULT_RETAIN"))
        if not entry.admitted:
            # a refusal terminal (shed / expired replay): its own ring,
            # so overload bursts cannot flush real results
            self._refusal_order.append(entry.cid)
            while len(self._refusal_order) > retain:
                self.calls.pop(self._refusal_order.popleft(), None)
            return
        self._done_order.append(entry.cid)
        self._done_bytes += entry.nbytes
        cap_bytes = max(1 << 20, env_int("KT_RESULT_RETAIN_BYTES"))
        # count-bounded ring with a byte backstop: retaining 256 tiny
        # terminals is free, retaining 256 multi-MB pickled results is a
        # pod OOM — evict oldest until both bounds hold (always keep the
        # just-finished entry so its own replay works)
        while len(self._done_order) > 1 and (
                len(self._done_order) > retain
                or self._done_bytes > cap_bytes):
            old = self.calls.pop(self._done_order.popleft(), None)
            if old is not None:
                self._done_bytes -= old.nbytes

    async def send_error(self, entry: RetainedCall, exc: BaseException,
                         t: Optional[dict] = None,
                         extra_hdr: Optional[dict] = None) -> None:
        hdr: Dict[str, Any] = {"kind": "error", **(extra_hdr or {})}
        if t:
            hdr["t"] = t
        await self.send(entry, hdr, json.dumps(
            {"error": package_exception(exc)["error"]}).encode())

    # ----------------------------------------------------------- submit
    async def submit(self, header: dict, payload: bytes,
                     t_recv: float) -> None:
        """Admit, dedup, or replay one incoming call frame."""
        self.last_activity = time.time()
        cid = header.get("cid")
        if not isinstance(cid, int):
            return
        # the deadline crosses the wire as a RELATIVE budget
        # (timeout_s) and becomes absolute here, on the SERVER's clock:
        # an absolute client timestamp would silently break under any
        # client↔pod clock skew larger than the timeout
        ts = header.get("timeout_s")
        if isinstance(ts, (int, float)) and "deadline" not in header:
            header["deadline"] = time.time() + float(ts)
        entry = self.calls.get(cid)
        if entry is not None:
            # seen before: never execute again. Replay what retention has
            # (done) or just let the re-attached socket receive the rest
            # (running) — either way, resend from the client's cursor.
            await self.replay(entry, int(header.get("resume_from") or 0))
            return
        if header.get("replay") and (cid <= self.max_seen_cid
                                     or self.lost_history):
            # the client replays a call this session (or its expired
            # predecessor) may have admitted before, but its entry is
            # gone: retention expired. Re-executing could double-run
            # non-idempotent work — refuse, typed.
            _record("expired")
            entry = self._admit_entry(cid, counted=False)
            await self.send_error(entry, ReplayExpired(
                f"call {cid} may have executed but its retained result "
                f"expired (KT_RESULT_RETAIN / KT_RESULT_RETAIN_S)"))
            return
        if header.get("replay"):
            # replayed, but the original submission never reached us (the
            # write was lost with the connection): fresh execution is the
            # correct — and exactly-once — outcome.
            _record("fresh")
        # ---------------------------------------------------- admission
        # the knob is a per-POD bound: count every session's queued+
        # executing calls (plus in-flight POSTs, via the server's
        # depth_fn), not just this session's
        max_depth = env_int("KT_MAX_QUEUE_DEPTH")
        depth = (self._depth_fn() if self._depth_fn is not None
                 else self.queue_depth)
        _record("queue_depth", depth)
        max_delay = env_float("KT_MAX_QUEUE_DELAY_S")
        est_delay = depth * max(0.01, self.ema_exec_s)
        # FIFO calls shed only at a pipeline BOUNDARY: rejecting chunk N
        # out of the middle while already-queued N+1 executes would break
        # the per-channel ordering a stateful engine depends on (and the
        # channel client deliberately does not auto-retry sheds). With
        # this session idle, a shed is clean — the engine restarts its
        # pipeline when the server says so. Concurrent calls are
        # independent by declaration and shed individually.
        mid_pipeline = (not header.get("concurrent")
                        and self.queue_depth > 0)
        if max_depth and not mid_pipeline and (
                depth >= max_depth or est_delay > max_delay):
            retry_after = retry_after_estimate(
                depth, max_depth, self.ema_exec_s, cap_s=max_delay)
            _record("shed")
            _record("last_retry_after", retry_after)
            tracing.record_span(
                "server.shed", 0.0,
                attrs={"cid": cid, "queue_depth": depth,
                       "retry_after_s": retry_after})
            entry = self._admit_entry(cid, counted=False)
            await self.send_error(
                entry,
                ServerOverloaded(
                    f"queue depth {depth} at/over KT_MAX_QUEUE_DEPTH="
                    f"{max_depth} (est. delay {est_delay:.2f}s)",
                    retry_after=retry_after),
                extra_hdr={"retry_after": retry_after})
            return
        entry = self._admit_entry(cid, counted=True)
        if header.get("concurrent"):
            task = asyncio.ensure_future(
                self._execute(self, entry, header, payload, t_recv))
            self.side_tasks.add(task)
            task.add_done_callback(self.side_tasks.discard)
        else:
            self.fifo.put_nowait((entry, header, payload, t_recv))

    def _admit_entry(self, cid: int, counted: bool) -> RetainedCall:
        entry = RetainedCall(cid)
        self.calls[cid] = entry
        self.max_seen_cid = max(self.max_seen_cid, cid)
        if counted:
            entry.counted = True
            entry.admitted = True
            # the client's writer has re-synced past the expired
            # predecessor: later lost writes have cids above THIS
            # session's watermark and may safely run fresh again
            self.lost_history = False
            try:
                from kubetorch_tpu.observability import prometheus as prom

                prom.record_channel_event("call")
                prom.channel_inflight(+1)
            # ktlint: disable=KT004 -- metrics must never break admission
            except Exception:  # noqa: BLE001
                pass
        return entry

    # ----------------------------------------------------------- replay
    async def replay(self, entry: RetainedCall, resume_from: int) -> None:
        """Re-deliver an entry's retained frames from the client's ack
        cursor. Items below ``resume_from`` were acked — skip them; the
        terminal frame always resends (the client drops duplicates by
        seq and resolved-cid, so over-delivery is safe, under-delivery
        is not).

        While this pass runs, it OWNS delivery for the entry
        (``entry.replaying``): a still-running execution keeps appending
        frames, but they are retained-only and picked up here — the loop
        re-reads ``entry.frames`` each step, and there is no await
        between the final length check and clearing the flag, so live
        delivery resumes with nothing skipped and nothing reordered."""
        t0 = time.perf_counter()
        if resume_from < entry.low_seq:
            # the requested prefix was trimmed under KT_RESULT_RETAIN_BYTES
            # while the client was away: the gap cannot be reconstructed,
            # and a partial resume would be a silent hole in the stream.
            # Delivered directly — NOT via send(): the entry may already
            # be terminal, and re-finishing it would corrupt the ring.
            _record("expired")
            await self._deliver(
                {"kind": "error", "cid": entry.cid},
                json.dumps({"error": package_exception(ReplayExpired(
                    f"cannot resume call {entry.cid} from seq "
                    f"{resume_from}: frames below {entry.low_seq} were "
                    f"trimmed (KT_RESULT_RETAIN_BYTES)"))["error"]}
                    ).encode())
            return
        _record("hit" if entry.done else "attach")
        resent = 0
        entry.replaying = True
        try:
            # snapshot rounds (the trim is disabled while replaying, so
            # the deque only APPENDS — `delivered` counts stay aligned):
            # after the last await, the while re-checks the live length
            # with no await before the flag clears, so nothing is missed
            delivered = 0
            while delivered < len(entry.frames):
                batch = list(entry.frames)[delivered:]
                for hdr, body in batch:
                    delivered += 1
                    if (hdr.get("kind") == "item"
                            and hdr.get("seq", 0) < resume_from):
                        continue
                    await self._deliver(hdr, body)
                    resent += 1
        finally:
            entry.replaying = False
        if resent:
            _record("frames_resent", resent)
        tracing.record_span(
            "server.replay", time.perf_counter() - t0,
            attrs={"cid": entry.cid, "frames": resent,
                   "resume_from": resume_from,
                   "state": "done" if entry.done else "running"})

    # ------------------------------------------------------- dispatcher
    async def _dispatch_loop(self) -> None:
        while True:
            entry, header, payload, t_recv = await self.fifo.get()
            if entry.done:  # shed/expired while queued (shouldn't happen)
                continue
            deadline = header.get("deadline")
            if isinstance(deadline, (int, float)) \
                    and time.time() > deadline:
                # queue-head rejection: the deadline passed while this
                # call waited behind earlier work — executing it now
                # helps nobody and delays everyone behind it
                _record("deadline_rejected")
                await self.send_error(entry, DeadlineExceeded(
                    f"deadline passed while queued "
                    f"(waited {time.perf_counter() - t_recv:.2f}s)",
                    deadline=float(deadline)))
                continue
            try:
                from kubetorch_tpu.resilience import chaos as chaos_mod

                policy = chaos_mod.active()
                if policy is not None and policy.decide(
                        chaos_mod.SLOW_POD, f"cid-{entry.cid}"):
                    await asyncio.sleep(policy.latency())
            # ktlint: disable=KT004 -- chaos injection never breaks serving
            except Exception:  # noqa: BLE001
                pass
            try:
                await self._execute(self, entry, header, payload, t_recv)
            except asyncio.CancelledError:
                raise
            except Exception as exc:  # noqa: BLE001
                # the execute path answers its own errors; anything that
                # still escapes must not kill the dispatcher — every call
                # queued behind would hang forever
                try:
                    await self.send_error(entry, exc)
                # ktlint: disable=KT004 -- teardown race: entry already terminal
                except Exception:  # noqa: BLE001
                    pass

    def note_exec(self, server_s: float) -> None:
        """Feed one call's in-server seconds into the Retry-After EMA."""
        if isinstance(server_s, (int, float)) and server_s >= 0:
            self.ema_exec_s = 0.8 * self.ema_exec_s + 0.2 * float(server_s)


class SessionRegistry:
    """The pod server's epoch → session map, with lazy expiry."""

    def __init__(self, execute: Callable,
                 extra_depth: Optional[Callable] = None):
        self._execute = execute
        # pod-global load outside the channels (the server's in-flight
        # POST count) — admission bounds the POD, not one session
        self._extra_depth = extra_depth
        self.sessions: Dict[str, ChannelSession] = {}

    def total_depth(self) -> int:
        """Queued+executing calls across every session on this pod,
        plus whatever the server reports out-of-band (POSTs)."""
        depth = sum(s.queue_depth for s in self.sessions.values())
        if self._extra_depth is not None:
            depth += int(self._extra_depth())
        return depth

    def attach(self, epoch: Optional[str], ws,
               reconnect: bool = False) -> Tuple[ChannelSession, bool]:
        """Get-or-create the session for ``epoch`` and attach the socket.
        Returns ``(session, resumed)`` — ``resumed`` when the epoch
        already had server-side state. ``reconnect`` is the client's own
        claim (the ``X-KT-Channel-Reconnect`` header): a re-dial landing
        on a FRESH session means the predecessor expired, and the new
        session must refuse replays rather than re-execute them."""
        self.sweep()
        ephemeral = epoch is None
        if ephemeral:
            epoch = f"anon-{uuid.uuid4().hex[:12]}"
        session = self.sessions.get(epoch)
        resumed = session is not None
        if session is None:
            session = ChannelSession(epoch, self._execute,
                                     ephemeral=ephemeral,
                                     depth_fn=self.total_depth)
            session.lost_history = bool(reconnect)
            self.sessions[epoch] = session
        session.attach(ws)
        return session, resumed

    def detach(self, session: ChannelSession, ws) -> None:
        session.detach(ws)
        if session.ephemeral:
            self.drop(session)

    def drop(self, session: ChannelSession) -> None:
        session.expire()
        self.sessions.pop(session.epoch, None)

    def sweep(self, now: Optional[float] = None) -> int:
        """Expire sessions detached longer than ``KT_RESULT_RETAIN_S``."""
        now = time.time() if now is None else now
        retain_s = env_float("KT_RESULT_RETAIN_S")
        dead = [s for s in self.sessions.values()
                if s.ws is None and s.detached_at is not None
                and now - s.detached_at > retain_s]
        for session in dead:
            self.drop(session)
        return len(dead)

    def expire_all(self) -> None:
        for session in list(self.sessions.values()):
            self.drop(session)
