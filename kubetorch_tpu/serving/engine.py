"""Server-resident continuous-batching decode engine.

A client that *drives* every decode chunk over the channel pays a call
round trip per chunk, and admission that swaps whole rolling batches
stalls every live row. Both taxes have the same root cause — the
generation loop living on the wrong side of the wire. This module moves
it server-side:

- the client submits ONE **generation program** — prompt(s), stopping
  criteria, sampling params, an optional deadline — as a single
  streamed channel call (``submit(program, method="generate",
  stream=True, concurrent=True)``);
- :class:`DecodeEngine`'s driver thread (inside the pod WORKER, the
  process that owns the TPU) runs rolling-engine steps back-to-back,
  device-resident, and routes each chunk's tokens into the program's
  stream as a frame — the per-chunk client round trip disappears from
  the steady state entirely. A prompt's FIRST frame holds its first
  token alone: the admission draws it, and the driver routes it when the
  prefill has ended, while the device works off the decode chunk behind
  it (``_first_frames_locked``);
- frames ride the PR-2 channel with per-frame ``seq``s recorded in the
  PR-8 result-retention ring, so replay/deadline semantics apply **per
  generation**: a mid-stream partition resumes the token stream
  byte-identical from the client's ack cursor, with the program having
  executed exactly once.

On top of the loop sits a **per-row admission scheduler**:

- new requests are admitted into free rows of the LIVE batch
  (``RollingGenerator.admit`` → the existing ``_admit_group`` /
  ``_finish_admit`` splice path) — never by swapping whole batches;
- long prompts prefill in ``KT_ENGINE_PREFILL_CHUNK``-token chunks
  *interleaved between decode chunks* (``prefill_step``), so a long
  prompt never stalls token emission for the rows around it;
- rows are EVICTED on stop-match (the rolling engine's own finish
  path), on deadline (the program's ``deadline_s``, enforced
  row-granular here on top of PR 8's between-chunk checks), and on
  client abandonment;
- when no row is expected to free within ``KT_MAX_QUEUE_DELAY_S``, new
  programs are shed with a typed
  :class:`~kubetorch_tpu.exceptions.ServerOverloaded` carrying a
  computed ``retry_after`` — the same PR-8 admission contract the POST
  path has, so ``retry.py`` retries sheds safely.

Under the scheduler sits the **paged-KV manager** (ISSUE 11,
``serving/kvpool.py``) — HBM treated as the multi-tenant resource:

- prompts split by ``KT_KV_PREFIX_SPLIT`` are content-hashed per
  adapter against a refcounted prefix cache — N programs with one
  system prompt prefill it ONCE (hit → reuse the registered device
  block, miss → register for everyone after); cold prefixes LRU-evict
  under ``KT_KV_HBM_BUDGET``;
- admission is priced in KV BLOCKS (``KT_KV_BLOCK_TOKENS``), one budget
  over row planes + prefix blocks; a prefix-hit program costs only its
  suffix, and budget exhaustion sheds typed instead of OOMing the grid;
- ``session_id`` programs can PARK (explicit :meth:`DecodeEngine.park`
  or deadline eviction): the row's KV + sampler state offloads through
  the PR-1/3 store path (int8 grids ship (q, scale) raw, re-parks ride
  the delta manifest) and a later same-session program restores into a
  free row and resumes mid-generation without re-prefill.

Speculative decoding is a **scheduler citizen** (ISSUE 14): on a
``spec_k > 1`` engine each row runs at its own adaptive lookahead
(acceptance-EMA state machine, ``kubetorch_tpu/lookahead.py``) — the
scheduler's contributions here are the per-tick occupancy throttle
(``KT_SPEC_OCCUPANCY_THROTTLE``: compute-bound batch → every row caps
to plain decode; latency regime → high-accept rows regrow), verify
cost priced into the shed check at each row's current ``k``, prefix
hits seeding the draft haystack (the old spec gate is gone), chunked
prefill composing with speculation, and park/resume carrying the
draft context + acceptance EMA through the store.

The engine publishes ``engine_*`` Prometheus counters/gauges (queue
depth, active/free rows, steps, sheds — the signal the autoscaler will
consume) plus the KV manager's ``kv_*``/``prefix_*`` set, and
``engine.step`` / ``engine.admit`` / ``engine.prefill`` /
``engine.prefix_fill`` / ``kv.offload`` / ``kv.restore`` spans into the
worker's trace ring. The driver times its own ticks and requests
(:class:`_TickTimer`, :class:`_ReqTimes`): tick phases and request
lifecycle stamps from one set of ``perf_counter`` stamps, read as flat
counters in ``stats()``, as those spans, and as ``kt.tick.<phase>``
host events in any ``jax.profiler`` trace of the process, on the device
events' clock. The generator tells the timer what it dispatched, where it
dispatches, so every tick is also filed under one class by what it held
(``tick_class_<c>_*``) and the host seconds in which the device had
nothing to do are summed by phase (``tick_starved_<phase>_s``): counters
only. Clients poll the snapshot without touching the
device via a channel **control frame**
(``CallChannel.control("stats")`` — answered by the pod server
out-of-band, no worker hop).

This module must stay importable without jax: the real engine
(:class:`~kubetorch_tpu.models.rolling.RollingGenerator`) is
constructed by user code and passed in; :class:`SimRollingEngine` is
the host-only twin the tests drive the scheduler with.
"""

from __future__ import annotations

import contextlib
import contextvars
import hashlib
import queue as _queue
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from kubetorch_tpu.config import env_float, env_int, env_str
from kubetorch_tpu.exceptions import DeadlineExceeded, ServerOverloaded
from kubetorch_tpu.lookahead import LookaheadState, spec_stats_dict
from kubetorch_tpu.observability import devstats, flight, tracing
from kubetorch_tpu.serving import kvpool
from kubetorch_tpu.serving.replay import retry_after_estimate


def _record_engine(event: str, value: float = 1.0) -> None:
    """``prometheus.record_engine`` behind the call path's
    must-never-raise guard (one shared implementation —
    ``kvpool._record``)."""
    kvpool._record(event, value)


def _record_adapter(adapter: str, event: str, value: float = 1.0) -> None:
    """Per-adapter (per-tenant) series — tokens/generations/sheds keyed
    by adapter NAME in the dynamic adapter store, behind the same
    must-never-raise guard."""
    try:
        from kubetorch_tpu.observability import prometheus as prom

        prom.record_adapter(adapter, event, value)
    # ktlint: disable=KT004 -- metrics must never break the serving path
    except Exception:  # noqa: BLE001
        pass


def _encode_adapter_name(name: str):
    """Adapter-name binding as a store-safe array leaf: a parked
    session's state blob must carry WHICH named adapter its KV was
    computed under (slot ints do not survive pool evict/reload — the
    name is the stable identity)."""
    import numpy as np

    return np.frombuffer(name.encode("utf-8"), dtype=np.uint8).copy()


def _decode_adapter_name(leaf) -> str:
    import numpy as np

    return np.asarray(leaf, dtype=np.uint8).tobytes().decode("utf-8")


# per-row lookahead histogram bounds: k is small and integral, so the
# buckets are the interesting k values themselves
_SPEC_K_BUCKETS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0)

# engine_phase gauge encoding (fleet-mergeable: the controller routes
# on the by-pod values, so the mapping is part of the wire contract)
_PHASE_CODE = {"prefill": 0, "decode": 1, "mixed": 2}

# The driver thread's timeline, cut into named phases. A tick runs
# evict .. publish in this order; ``idle`` is the wait for work and
# ``handover`` the rest of the time between two ticks (lock release, the
# yield, taking the lock and the GIL back). A ``*_sync`` phase is a wait
# for a device value: host work and waiting never share a counter.
# ``first_sync`` is the read of the first tokens that the tick's admissions
# drew, behind the decode chunk's dispatch; the ``route`` right after it
# sends them on as frames of their own while the chunk runs.
_TICK_PHASES = ("evict", "evict_sync", "admit", "prefill", "handoff",
                "handoff_sync", "decode_dispatch", "first_sync",
                "decode_sync", "route", "publish", "idle", "handover")
_IDLE = _TICK_PHASES.index("idle")
# a blocking read of a device value. Seconds of these (the wait itself) and
# of ``idle`` (no work to give it) never count as the device starving.
_SYNC = tuple(i for i, name in enumerate(_TICK_PHASES)
              if name.endswith("_sync"))
_NOT_STARVED = frozenset(_SYNC + (_IDLE,))
# when one of THESE returns the device has worked off everything that was
# queued, and until the generator's next dispatch it has nothing to do.
# ``first_sync`` is the one read that returns with work still queued (the
# decode chunk behind the admission it reads): the device stays busy
# through it and through the routing of the first frames after it.
_DRAINS = frozenset(i for i in _SYNC if _TICK_PHASES[i] != "first_sync")
# A tick is filed under what it held, by the executables the generator says
# it dispatched (``_TickTimer.dispatched``): the largest bucket of a
# bucketed admission (``b<p_pad>``), else ``admit`` (an admission of a
# generator that names no bucket: the sim), else ``chunk`` (a chunked
# prefill dispatch), else ``plain`` (a decode chunk and nothing else), else
# ``empty``. A class's rank is its place in that order; a bucket's is its
# ``p_pad`` (16 and up).
_EMPTY, _PLAIN, _CHUNK, _ADMIT = range(4)
_CLASS_RANK = {"prefill_ext": _CHUNK, "admit": _ADMIT}
_CLASS_FIELDS = ("n", "wall_s", "sync_s", "starved_s", "tokens")
# a tick is slow when its wall (with the handover before it) passes this
# many running medians; the median is over the last _WALL_RING ticks and
# nothing is judged before _WALL_MIN of them
_SLOW_FACTOR = 4.0
_WALL_RING = 33
_WALL_MIN = 8

_NO_ANNOTATION = contextlib.nullcontext()


def _no_annotation(name: str, **_kwargs):
    return _NO_ANNOTATION


class _Phase:
    """One phase of :class:`_TickTimer`: a reusable (not re-entrant)
    context manager. One pair of ``perf_counter`` stamps feeds the
    phase's counters and the profiler annotation of the same stretch."""

    __slots__ = ("timer", "index", "label", "t0", "last_s", "_child_s",
                 "_parent", "_ann")

    def __init__(self, timer: "_TickTimer", index: int):
        self.timer = timer
        self.index = index
        self.label = "kt.tick." + _TICK_PHASES[index]
        self.t0 = self.last_s = self._child_s = 0.0
        self._parent: Optional["_Phase"] = None
        self._ann = _NO_ANNOTATION

    def __enter__(self) -> "_Phase":
        timer = self.timer
        parent = self._parent = timer.open
        timer.open = self
        self._child_s = 0.0
        self._ann = timer.annotate(self.label)
        self._ann.__enter__()
        now = self.t0 = time.perf_counter()
        if timer.dry_t is not None:
            timer.starve(now, parent)
        return self

    def __exit__(self, *exc) -> None:
        now = time.perf_counter()
        self.last_s = dt = now - self.t0
        self._ann.__exit__(*exc)
        timer = self.timer
        if self.index in _DRAINS:
            timer.dry_t = now
        elif timer.dry_t is not None:
            timer.starve(now, self)
        # exclusive time: what a nested phase took is the nested one's
        timer.seconds[self.index] += dt - self._child_s
        timer.calls[self.index] += 1
        parent = timer.open = self._parent
        if parent is not None:
            parent._child_s += dt


class _TickClass:
    """What the ticks of one class add up to."""

    __slots__ = ("name",) + _CLASS_FIELDS

    def __init__(self, name: str):
        self.name = name
        self.n = self.tokens = 0
        self.wall_s = self.sync_s = self.starved_s = 0.0


class _TickTimer:
    """Times the engine's driver: ``timer(name)`` is the context manager
    of one phase, ``with timer:`` one whole tick.

    The same stamps are read three ways: as counters (``seconds`` /
    ``calls`` by phase, flat ``tick_<phase>_s`` / ``_n`` in
    ``DecodeEngine.stats()``), as spans in the ``tracing`` recorder
    (:meth:`span`, and one ``engine.slow_tick`` per slow tick with that
    tick's phase split), and as ``kt.tick`` / ``kt.tick.<phase>`` host
    events inside any ``jax.profiler`` trace of the process, on the
    clock the device events carry. Always on; with no jax in the
    process the annotations are no-ops.

    The tick also accounts for itself. The generator calls
    :meth:`dispatched` wherever it has queued an executable, so every
    tick is filed under ONE class by what it held (ticks, wall, wait
    for the device, starved seconds, tokens routed; flat
    ``tick_class_<c>_<field>`` in ``stats()``, every key there from the
    start), and the host seconds in which the device had nothing to do
    (from the return of a blocking read to the next dispatch) are summed
    by the phase they lay in (``starved``; ``tick_starved_<phase>_s``,
    their sum ``tick_starved_s``). ``max_len`` bounds the admission
    buckets a generator can name (``rolling._bucket``'s powers of two)."""

    def __init__(self, max_len: int = 0):
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        self.annotate = (profiler.TraceAnnotation if profiler is not None
                         else _no_annotation)
        self._annotate_step = (profiler.StepTraceAnnotation
                               if profiler is not None else _no_annotation)
        self.seconds = [0.0] * len(_TICK_PHASES)
        self.calls = [0] * len(_TICK_PHASES)
        self.open: Optional[_Phase] = None
        self._phases = {name: _Phase(self, i)
                        for i, name in enumerate(_TICK_PHASES)}
        self.started = 0             # ticks begun: the step number
        self.slow_ticks = 0
        self.t0 = 0.0                # start of the current tick
        self._t_end = time.perf_counter()   # end of the last tick
        # ``seconds`` as they stood when the last tick ended: the split
        # of the current tick with the handover (and idle) before it
        self._base = list(self.seconds)
        self._walls: List[float] = []
        self._step = _NO_ANNOTATION
        # when the last blocking read returned with nothing dispatched
        # since (the device's queue is dry), moved on as the stretch is
        # booked phase by phase; None while the device has work
        self.dry_t: Optional[float] = self._t_end
        self.starved = [0.0] * len(_TICK_PHASES)
        self.starved_s = 0.0
        self._base_starved = 0.0
        # the current tick: the highest rank dispatched, tokens routed
        self._held = _EMPTY
        self.tokens = 0
        names = {_EMPTY: "empty", _PLAIN: "plain", _CHUNK: "chunk",
                 _ADMIT: "admit"}
        bucket = 16
        while bucket < 2 * max_len:
            names[bucket] = f"b{bucket}"
            bucket *= 2
        self._by_rank = {rank: _TickClass(name)
                         for rank, name in names.items()}

    def __call__(self, name: str) -> _Phase:
        return self._phases[name]

    def starve(self, now: float, phase: Optional[_Phase]) -> None:
        """The dry stretch up to ``now`` lay in ``phase`` (the innermost
        one open, None between phases)."""
        if phase is not None and phase.index not in _NOT_STARVED:
            dt = now - self.dry_t
            self.starved[phase.index] += dt
            self.starved_s += dt
        self.dry_t = now

    def dispatched(self, kind: str, key: Any) -> None:
        """The generator has queued an executable (its ``fn`` returned):
        the device has work again, and the tick holds what ``kind`` and
        ``key`` say (``RollingGenerator._dispatch`` has the kinds)."""
        if self.dry_t is not None:
            self.starve(time.perf_counter(), self.open)
            self.dry_t = None
        rank = (key[1] if kind in ("prefill", "prefill_px")
                else _CLASS_RANK.get(kind, _PLAIN))
        if rank > self._held:
            self._held = rank

    def __enter__(self) -> "_TickTimer":
        self._step = self._annotate_step("kt.tick", step_num=self.started)
        self._step.__enter__()
        self.started += 1
        self._held = _EMPTY
        self.tokens = 0
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._step.__exit__(*exc)
        now = time.perf_counter()
        seconds, base = self.seconds, self._base
        wall = now - self._t_end - (seconds[_IDLE] - base[_IDLE])
        self._t_end = now
        held = self._by_rank.get(self._held)
        if held is None:        # a bucket past max_len's: filed, late
            held = self._by_rank[self._held] = _TickClass(f"b{self._held}")
        held.n += 1
        held.wall_s += wall
        held.sync_s += sum(seconds[i] - base[i] for i in _SYNC)
        held.starved_s += self.starved_s - self._base_starved
        held.tokens += self.tokens
        self._base_starved = self.starved_s
        walls = self._walls
        if (len(walls) >= _WALL_MIN
                and wall > _SLOW_FACTOR * sorted(walls)[len(walls) // 2]):
            self.slow_ticks += 1
            if tracing.enabled():
                attrs = {name: round(seconds[i] - base[i], 6)
                         for i, name in enumerate(_TICK_PHASES)
                         if seconds[i] > base[i]}
                attrs["tick"] = self.started - 1
                attrs["class"] = held.name
                tracing.record_span("engine.slow_tick", wall, attrs=attrs)
        if len(walls) < _WALL_RING:
            walls.append(wall)
        else:
            walls[self.started % _WALL_RING] = wall
        base[:] = seconds

    def span(self, name: str, dur_s: float, keys: Tuple[str, ...],
             *values) -> None:
        """A span in the ``tracing`` recorder from a phase's own
        stamps; its attrs dict exists only if the recorder is on."""
        if tracing.enabled():
            tracing.record_span(name, dur_s, attrs=dict(zip(keys, values)))

    def mark(self, name: str, rid: int) -> None:
        """An instant event of one request in the profiler's trace."""
        with self.annotate(name, rid=rid):
            pass

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for i, name in enumerate(_TICK_PHASES):
            out[f"tick_{name}_s"] = self.seconds[i]
            out[f"tick_{name}_n"] = self.calls[i]
            if i not in _NOT_STARVED:
                out[f"tick_starved_{name}_s"] = self.starved[i]
        out["slow_ticks"] = self.slow_ticks
        out["tick_starved_s"] = self.starved_s
        for held in list(self._by_rank.values()):   # read lock-free
            for field in _CLASS_FIELDS:
                out[f"tick_class_{held.name}_{field}"] = getattr(held, field)
        return out


class _ReqTimes:
    """A request's ``perf_counter`` stamps inside the engine, each set
    where the thing happens, and the submitting call's trace id (the
    first frame and every later tick run in the driver thread, which has
    no ambient span)."""

    __slots__ = ("t_submit", "t_queued", "t_admit", "t_first", "trace")

    def __init__(self, t_submit: float, t_queued: float,
                 trace: Optional[str]):
        self.t_submit = t_submit
        self.t_queued = t_queued
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.trace = trace


# the request-lifecycle histograms (``record_hist`` families), each also
# a flat ``<name>_sum`` / ``<name>_count`` pair in ``stats()``
_LIFE_HISTS = ("engine_lock_wait_seconds", "engine_queue_wait_seconds",
               "engine_admit_to_first_seconds", "engine_ttft_seconds")



class GenerationProgram:
    """Validated form of the JSON generation program a client submits.

    Wire shape (all JSON-able)::

        {"prompt": [1, 2, 3],          # or "prompts": [[...], [...]]
         "max_new_tokens": 128,
         "temperature": 0.0,
         "stop": [[13, 10]],           # optional stop token sequences
         "repetition_penalty": 1.0,
         "adapter_id": -1,
         "adapter": "tenant-a",        # optional pool-managed NAME
         "prefix_id": None,
         "deadline_s": 30.0,           # optional whole-program budget
         "tag": "req-abc"}             # optional idempotency/debug tag

    ``deadline_s`` is RELATIVE (seconds from receipt) for the same
    reason the channel's ``timeout_s`` is: an absolute client timestamp
    would break under clock skew. The engine stamps the absolute
    deadline on its own clock at submit.

    ``adapter`` vs ``adapter_id``: ``adapter`` is a stable NAME the
    engine's :class:`~kubetorch_tpu.serving.adapterpool.AdapterPool`
    resolves to a device slot at admission (and loads in the
    background on a miss); ``adapter_id`` is the raw slot int for
    directly-driven engines with a ctor-frozen stacked tree. A program
    sets at most one — slots recycle under the pool, so clients must
    never address pool-managed adapters by slot.
    """

    def __init__(self, prompts: List[List[int]], max_new_tokens: int,
                 temperature: float, stop, repetition_penalty: float,
                 adapter_id: int, prefix_id: Optional[int],
                 deadline_s: Optional[float], tag: Optional[str],
                 session_id: Optional[str] = None,
                 adapter: Optional[str] = None,
                 handoff: Optional[Dict[str, Any]] = None,
                 handoff_id: Optional[str] = None):
        self.prompts = prompts
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.stop = stop
        self.repetition_penalty = repetition_penalty
        self.adapter_id = adapter_id
        self.adapter = adapter
        self.prefix_id = prefix_id
        self.deadline_s = deadline_s
        self.tag = tag
        self.session_id = session_id
        # disaggregated prefill/decode (ISSUE 17): ``handoff`` (prefill
        # side) = {"id": ..., "store_url": optional} — prefill the row,
        # export it under the handoff id (direct-push at store_url when
        # given) and END the stream with a handoff frame, zero tokens
        # emitted locally. ``handoff_id`` (decode side) = import the
        # exported row and stream its tokens; the prompt travels too so
        # a lost handoff can fall back to monolithic same-pod decode.
        self.handoff = handoff
        self.handoff_id = handoff_id

    @classmethod
    def from_wire(cls, obj: Any) -> "GenerationProgram":
        if not isinstance(obj, dict):
            raise ValueError(
                f"generation program must be a dict, got {type(obj).__name__}")
        if "prompts" in obj:
            prompts = obj["prompts"]
        elif "prompt" in obj:
            prompts = [obj["prompt"]]
        else:
            raise ValueError("generation program needs 'prompt' or 'prompts'")
        if (not isinstance(prompts, list) or not prompts
                or not all(isinstance(p, list) and p for p in prompts)):
            raise ValueError("prompts must be a non-empty list of "
                             "non-empty token lists")
        prompts = [[int(t) for t in p] for p in prompts]
        deadline_s = obj.get("deadline_s")
        if deadline_s is not None:
            deadline_s = float(deadline_s)
            if deadline_s <= 0:
                raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        session_id = obj.get("session_id")
        if session_id is not None:
            kvpool.check_session_id(session_id)
            if len(prompts) != 1:
                # a session parks/restores ONE row's KV; a multi-prompt
                # program has no well-defined park state
                raise ValueError("session_id programs must carry exactly "
                                 "one prompt")
        adapter = obj.get("adapter")
        if adapter is not None:
            if not isinstance(adapter, str) or not adapter:
                raise ValueError(
                    f"adapter must be a non-empty string name, "
                    f"got {adapter!r}")
            if int(obj.get("adapter_id", -1)) != -1:
                raise ValueError(
                    "pass adapter= (pool-managed name) or adapter_id= "
                    "(raw slot), not both")
        handoff = obj.get("handoff")
        handoff_id = obj.get("handoff_id")
        if handoff is not None and handoff_id is not None:
            raise ValueError(
                "pass handoff= (prefill side: export the row) or "
                "handoff_id= (decode side: import it), not both")
        if (handoff is not None or handoff_id is not None):
            if session_id is not None:
                # a handoff row's lifecycle is one-shot relay, not a
                # parkable conversation — the two id namespaces must
                # not alias
                raise ValueError(
                    "handoff programs cannot also carry session_id")
            if len(prompts) != 1:
                raise ValueError(
                    "handoff programs must carry exactly one prompt "
                    "(one exported row per handoff id)")
        if handoff is not None:
            if not isinstance(handoff, dict) or "id" not in handoff:
                raise ValueError(
                    "handoff must be a dict with at least {'id': ...}")
            kvpool.check_handoff_id(handoff["id"])
            url = handoff.get("store_url")
            if url is not None and (not isinstance(url, str) or not url):
                raise ValueError(
                    "handoff['store_url'] must be a non-empty string "
                    "(the decode pod's store endpoint)")
        if handoff_id is not None:
            kvpool.check_handoff_id(handoff_id)
        return cls(
            prompts=prompts,
            max_new_tokens=int(obj.get("max_new_tokens", 128)),
            temperature=float(obj.get("temperature", 0.0)),
            stop=obj.get("stop"),
            repetition_penalty=float(obj.get("repetition_penalty", 1.0)),
            adapter_id=int(obj.get("adapter_id", -1)),
            prefix_id=obj.get("prefix_id"),
            deadline_s=deadline_s,
            tag=obj.get("tag"),
            session_id=session_id,
            adapter=adapter,
            handoff=handoff,
            handoff_id=handoff_id)

    def submit_kwargs(self) -> Dict[str, Any]:
        return {"max_new_tokens": self.max_new_tokens,
                "temperature": self.temperature, "stop": self.stop,
                "repetition_penalty": self.repetition_penalty,
                "adapter_id": self.adapter_id, "prefix_id": self.prefix_id}


def program(prompt: Optional[List[int]] = None, *,
            prompts: Optional[List[List[int]]] = None,
            max_new_tokens: int = 128, temperature: float = 0.0,
            stop: Optional[List[List[int]]] = None,
            repetition_penalty: float = 1.0, adapter_id: int = -1,
            adapter: Optional[str] = None,
            prefix_id: Optional[int] = None,
            session_id: Optional[str] = None,
            deadline_s: Optional[float] = None,
            tag: Optional[str] = None,
            handoff: Optional[Dict[str, Any]] = None,
            handoff_id: Optional[str] = None) -> Dict[str, Any]:
    """Client-side builder for the ``generate`` wire dict — the API that
    actually SETS ``prefix_id`` / ``session_id`` (the wire fields
    existed; nothing on the client wrote them)::

        chan.submit(program(toks, session_id="user-42", max_new_tokens=256),
                    method="generate", stream=True, concurrent=True)

    Validates eagerly (the same :class:`GenerationProgram` parse the
    server runs) so a bad program fails at the call site, not as a
    rehydrated server error."""
    obj: Dict[str, Any] = {"max_new_tokens": int(max_new_tokens),
                           "temperature": float(temperature),
                           "repetition_penalty": float(repetition_penalty),
                           "adapter_id": int(adapter_id)}
    if (prompt is None) == (prompts is None):
        raise ValueError("pass exactly one of prompt= or prompts=")
    if prompt is not None:
        obj["prompt"] = [int(t) for t in prompt]
    else:
        obj["prompts"] = [[int(t) for t in p] for p in prompts]
    if stop is not None:
        obj["stop"] = [[int(t) for t in s] for s in stop]
    if adapter is not None:
        obj["adapter"] = str(adapter)
    if prefix_id is not None:
        obj["prefix_id"] = int(prefix_id)
    if session_id is not None:
        obj["session_id"] = session_id
    if deadline_s is not None:
        obj["deadline_s"] = float(deadline_s)
    if tag is not None:
        obj["tag"] = str(tag)
    if handoff is not None:
        obj["handoff"] = dict(handoff)
    if handoff_id is not None:
        obj["handoff_id"] = str(handoff_id)
    GenerationProgram.from_wire(obj)
    return obj


class DecodeEngine:
    """Hosts a rolling engine inside the pod worker and runs the
    generation loop server-side.

    Deploy as a ``kt.cls`` whose ``__init__`` builds the rolling engine
    (the worker process owns the TPU), then drive it over the channel::

        chan = remote.channel(depth=2)
        frames = chan.submit({"prompt": toks, "max_new_tokens": 256},
                             method="generate", stream=True,
                             concurrent=True)
        for frame in frames.result():
            ...  # {"i": 0, "seq": k, "tokens": [...], "done": False}

    ``concurrent=True`` matters: ``generate`` streams for the life of
    the program, and the channel's FIFO lane would serialize everything
    behind it. Generations are independent by construction — the FIFO
    ordering contract protects hand-driven ``step()`` engines, not this
    one (the scheduler owns interleaving now).

    The wrapped ``engine`` needs the :class:`RollingGenerator` driving
    surface: ``submit/admit/prefill_step/decode_step/evict`` plus the
    ``queued/free_rows/active_rows/prefilling_rows/pending`` counts;
    ``admit`` and ``prefill_step`` return the rids they admitted /
    activated, and ``decode_step`` enters the ``tick_phase`` the engine
    installs on it around its dispatch, its blocking read and its
    bookkeeping (``decode_dispatch`` / ``decode_sync`` / ``route``); it
    calls the ``dispatched(kind, key)`` hook installed beside it wherever
    it has queued an executable. A generator whose admissions draw their
    rows' first tokens (:class:`RollingGenerator`) hands them to the
    ``first_frames(events)`` hook installed beside those, behind the
    chunk's dispatch and ahead of its read (``first_sync``, then a
    ``route`` of its own): a request's first frame holds its first token
    alone and leaves while the device works off the chunk.
    Prefix sharing additionally uses ``register_prefix/drop_prefix`` and
    the ``prefill_tokens`` counter; session park/restore uses
    ``export_row/import_row``; speculative engines (``engine.spec``)
    additionally expose ``spec_stats``/``spec_row_ks``/``set_spec_cap``
    — the driver tick throttles aggregate lookahead by occupancy and
    the shed check prices verify waste (all optional — an engine
    without them simply serves unshared, unparked, unspeculated).
    """

    def __init__(self, engine, poll_s: Optional[float] = None,
                 admit_rows: Optional[int] = None,
                 max_waiting: Optional[int] = None,
                 stall_s: Optional[float] = None,
                 kv_block_tokens: Optional[int] = None,
                 kv_budget_blocks: Optional[int] = None,
                 prefix_split: Optional[str] = None,
                 spec_throttle: Optional[float] = None,
                 adapter_pool=None,
                 phase: Optional[str] = None):
        self.engine = engine
        # disaggregated serving tier (ISSUE 17): "prefill" pods run
        # admit/prefill only and EXPORT every row (programs must carry
        # handoff=); "decode" pods import exported rows and stream —
        # but still run suffix prefills, so prefix-cache hits stay
        # tier-local; "mixed" (default) is the monolithic engine.
        phase = (phase if phase is not None
                 else (env_str("KT_DISAGG_PHASE") or "mixed"))
        if phase not in _PHASE_CODE:
            raise ValueError(
                f"phase must be one of {sorted(_PHASE_CODE)}, "
                f"got {phase!r} (KT_DISAGG_PHASE)")
        self._phase = phase
        self._handoffs = 0          # rows exported to the decode tier
        self._handoff_imports = 0   # rows imported from the prefill tier
        # Named-adapter residency (serving/adapterpool.py): programs
        # carry a stable adapter NAME, resolved to a device slot at
        # admission; cold adapters fetch in the background and install
        # at the driver-tick boundary (admit_ready). None → raw
        # adapter_id slots only. The evict hook drops the departing
        # adapter's name-keyed prefix entries — their device KV is HBM
        # rent for a tenant no longer resident, and a reload may land
        # in a different slot anyway.
        self._adapter_pool = adapter_pool
        if adapter_pool is not None:
            adapter_pool.on_evict = self._adapter_evicted_locked
        self._poll_s = (poll_s if poll_s is not None
                        else env_float("KT_ENGINE_POLL_S"))
        self._admit_rows = (admit_rows if admit_rows is not None
                            else env_int("KT_ENGINE_ADMIT_ROWS"))
        self._max_waiting = (max_waiting if max_waiting is not None
                             else env_int("KT_ENGINE_MAX_WAITING"))
        self._stall_s = (stall_s if stall_s is not None
                         else env_float("KT_ENGINE_STALL_S"))
        # speculation as a scheduler citizen: above this occupancy the
        # batch is compute-bound and verify width stops being free —
        # the driver tick caps every row's lookahead at 1 (plain
        # decode); below it the cap lifts and high-accept rows regrow
        self._spec_throttle = (
            spec_throttle if spec_throttle is not None
            else env_float("KT_SPEC_OCCUPANCY_THROTTLE"))
        self._spec_capped = False
        self._spec_prev: Dict[str, float] = {}
        # recent tokens-per-pass (per-tick deltas, EMA): the shed
        # check's verify-pricing input. The engine's cumulative
        # tokens_per_pass is lifetime-averaged — after a regime shift
        # (adversarial hour → extractive traffic) it lags for hours
        # and would misprice admission exactly when rows are fastest
        self._spec_tpp_ema: Optional[float] = None
        # Paged-KV manager (serving/kvpool.py): block ledger + prefix
        # cache + session offload. Budget default: 2x the decode grid in
        # blocks — the grid itself plus as much again for shared prefix
        # blocks before cold ones LRU-evict.
        bt = (kv_block_tokens if kv_block_tokens is not None
              else env_int("KT_KV_BLOCK_TOKENS"))
        budget = (kv_budget_blocks if kv_budget_blocks is not None
                  else env_int("KT_KV_HBM_BUDGET"))
        # a row's plane is physically bounded by the grid depth — price
        # admission at min(context + budget, max_len) blocks, exactly
        # what the row can occupy
        self._row_cap_tokens = int(getattr(engine, "max_len", 2048))
        # bytes a row holds whatever its depth (a decoder's row-state
        # leaves: a recurrent state), priced as the positions they would
        # be; the generator's two gauges say both (a sim engine has none)
        gauges = engine.stats() if hasattr(engine, "stats") else {}
        pos_bytes = int(gauges.get("kv_position_bytes", 0))
        row_bytes = int(gauges.get("state_row_bytes", 0))
        row_state_tokens = -(-row_bytes // pos_bytes) if pos_bytes else 0
        # a row priced by kind: layers that keep a ring hold min(depth,
        # window) positions, each worth their bytes over the full layers'
        window_tokens = int(gauges.get("window_positions", 0))
        window_weight = (gauges.get("window_position_bytes", 0) / pos_bytes
                         if pos_bytes else 0.0)
        if not budget:
            grid_blocks = (int(getattr(engine, "max_slots", 8))
                           * kvpool.blocks_for(kvpool.priced_tokens(
                               self._row_cap_tokens, row_state_tokens,
                               window_tokens, window_weight), bt))
            budget = 2 * grid_blocks
        self._kv = kvpool.PagedKVPool(budget, bt, prefix_split,
                                      row_state_tokens=row_state_tokens,
                                      window_tokens=window_tokens,
                                      window_weight=window_weight)
        # a decoder that does not carry what this engine was configured
        # for (models/decoder.py: check_serving) says so now, by name
        self._model = getattr(engine, "model", None)
        if self._model is not None:
            self._model.check_serving(
                engine.cfg, handoff=self._phase != "mixed",
                prefix=self._kv.split is not None)
        # rid -> {"blocks", "session", "prefix_pid"} — the release-side
        # bookkeeping of the ledger reservations made at submit
        self._rid_meta: Dict[int, Dict[str, Any]] = {}
        # single-flight per session: a session_id owns at most ONE live
        # row — a client retry racing its own in-flight program must not
        # restore (or decode) the same session twice
        self._live_sessions: set = set()
        # per-session activity sequence: bumped every time a program
        # claims the session (fresh submit or restore). Background
        # offloads capture it at export and refuse to publish a blob a
        # NEWER program has since superseded (a late-landing deadline
        # park must not shadow the session's next generation). Values
        # come from one GLOBAL monotonic counter: an entry evicted from
        # the bounded dict and later recreated can then never land on a
        # value an in-flight offload captured.
        self._session_seq: Dict[str, int] = {}
        self._seq_counter = 0
        # sessions that may have a blob in the store (parked or
        # restored): the completion-drop only pays its store round-trips
        # for these. LRU-bounded dict; ABSENCE must mean "no blob", so
        # evicting a tracking entry also drops its blob (the evicted
        # session loses its resume — a bounded-resource policy, like
        # prefix LRU — rather than silently keeping a stale blob its
        # completion would never clean).
        self._parked_sessions: Dict[str, bool] = {}
        # serializes park PUBLISHES (explicit + background): a stale
        # deadline-offload's check+publish must be atomic w.r.t. a
        # newer explicit park's, or the stale publish can land OVER the
        # newer blob after its durability sentinel was delivered.
        # Ordering: _offload_lock is always taken OUTSIDE _wake.
        self._offload_lock = threading.Lock()
        # seconds-per-KV-block-freed EMA: the block-admission estimate's
        # clock (rows free whole reservations at once; per-block keeps
        # the estimate size-aware)
        self._ema_block_s = 0.01
        # prefix-sharing savings accounting — BOTH sides counted HERE
        # (engine.prefill_tokens also moves on warmup()/direct submits
        # that never pass through generate(), which would skew the
        # ratio negative after a standard warm-then-serve startup)
        self._prefill_naive = 0       # sum(len(full prompt)) submitted
        self._prefill_executed = 0    # suffixes + once-per-prefix fills
        self._wake = threading.Condition()
        self._sinks: Dict[int, "_queue.SimpleQueue"] = {}
        self._deadlines: Dict[int, float] = {}
        # rid -> lifecycle stamps + the submitting call's trace id, for
        # the row's whole residency (dropped in _forget_locked): feeds
        # the lifecycle histograms at the first frame, the TTFT EMA, and
        # the flight recorder's trace ids live in the batch
        self._req: Dict[int, _ReqTimes] = {}
        # histogram name -> [sum, count] of what this engine observed
        self._life = {name: [0.0, 0] for name in _LIFE_HISTS}
        # the driver's phase timer; the generator reports the halves of
        # its decode chunk through it, and each executable it dispatches
        self._timer = _TickTimer(self._row_cap_tokens)
        engine.tick_phase = self._timer
        engine.dispatched = self._timer.dispatched
        # a fresh row's first token, read behind the decode chunk's
        # dispatch, is routed while the chunk runs (a sim draws none)
        engine.first_frames = self._first_frames_locked
        self._exec_counts: Dict[str, int] = {}
        # seconds-per-row-freed EMA — the admission estimate's clock
        # (same role the session's ema_exec_s plays for call shedding)
        self._ema_row_s = 0.05
        self._ema_ttft_s = 0.0
        self._last_free_t: Optional[float] = None
        self._steps = 0
        self._tokens = 0
        self._device_s = 0.0
        self._prefill_s = 0.0
        self._prefill_chunks = 0
        self._admitted = 0
        self._parks = 0
        self._restores = 0
        self._evictions = 0
        self._sheds = 0
        # --- device-truth utilization + flight recorder ---------------
        # MFU/MBU window state: (flops_total, bytes_total, measured
        # dispatch wall) at the last gauge publish; gauges are the
        # window's delta ratios against the chip peaks. None until the
        # generator exposes a devstats surface AND peaks are known.
        self._util_prev = (0.0, 0.0, 0.0)
        self._mfu: Optional[float] = None
        self._mbu: Optional[float] = None
        self._hbm_t = 0.0            # last memory_stats poll (monotonic)
        # per-tick black box: one record per driver tick, None when
        # KT_FLIGHT_DISABLE is set
        self._flight = flight.get_recorder()
        self._stop = False
        # the phase gauge must be visible BEFORE any traffic: the
        # controller's phase routing reads it to classify an idle tier
        self._publish_gauges()
        # copy_context: driver-thread spans/log lines keep the ids of
        # whatever context built the engine
        self._driver = threading.Thread(
            target=contextvars.copy_context().run, args=(self._drive,),
            name="kt-engine-driver", daemon=True)
        self._driver.start()

    # ------------------------------------------------------------ public
    def generate(self, program):
        """Run one generation program; a GENERATOR of token frames —
        the channel streams each as an 'item' frame with a retained
        ``seq``, so a reconnect resumes mid-stream (PR 8 replay) and
        the program executes exactly once.

        Frames: ``{"i": prompt-index, "rid": engine-rid, "seq": n,
        "tokens": [...], "done": bool}``; the stream ends when every
        prompt in the program is done. A parked program (see
        :meth:`park`) ends with one ``{"parked": True, "done": False}``
        frame instead.

        **Prefix sharing**: with ``KT_KV_PREFIX_SPLIT`` active, each
        prompt is split into (prefix, suffix); the prefix half is
        content-hashed per adapter against the pool — a hit reuses the
        already-registered device KV block and only the suffix
        prefills; a miss registers the prefix ONCE for every later
        same-hash program. **Sessions**: a program with ``session_id``
        whose id has parked KV in the store restores it through the
        streaming path into a free row and resumes mid-generation —
        its ``prompt`` is ignored (the parked state is the program)."""
        t_submit = time.perf_counter()
        prog = GenerationProgram.from_wire(program)
        if self._phase == "prefill" and prog.handoff is None:
            raise ValueError(
                "this engine is a prefill-tier pod "
                "(KT_DISAGG_PHASE=prefill): programs must carry "
                "handoff= — decode runs on the decode tier")
        if self._model is not None and (prog.handoff is not None
                                        or prog.handoff_id is not None):
            self._model.check_serving(self.engine.cfg, handoff=True)
        sink: "_queue.SimpleQueue" = _queue.SimpleQueue()
        # exemplar context for the TTFT histogram: the submit runs
        # under the call's ambient span; first token lands in the
        # driver thread where no ambient context exists
        submit_trace = tracing.current_trace_id()
        restored = None
        handoff_state = None
        if (prog.handoff_id is not None
                and hasattr(self.engine, "import_row")):
            # store fetch OUTSIDE the scheduler lock (same reasoning as
            # the session restore): poll until the prefill pod's export
            # lands or KT_HANDOFF_TIMEOUT_S passes — a timeout falls
            # back to monolithic same-pod decode (the program still
            # carries its prompt, so nothing is lost but the recompute)
            handoff_state = self._await_handoff(prog.handoff_id)
            if handoff_state is None:
                tracing.record_span(
                    "kv.handoff_fallback", 0.0,
                    attrs={"handoff": prog.handoff_id})
        if prog.session_id is not None:
            with self._wake:
                self._check_session_free_locked(prog.session_id)
            # store fetch OUTSIDE the scheduler lock: a slow restore
            # must not stall the decode loop (re-checked under the lock
            # before the import — two racing fetches, one winner). The
            # session seq is bumped only when this program actually
            # TAKES a row (submit/import): a program that sheds or fails
            # validation must not supersede an in-flight park publish —
            # that publish may hold the only copy of the state.
            if hasattr(self.engine, "import_row"):
                restored = kvpool.restore_session(prog.session_id)
        with self._wake:
            deadline = (time.time() + prog.deadline_s
                        if prog.deadline_s is not None else None)
            rids: List[int] = []
            if restored is not None:
                rid = self._restore_locked(prog, restored)
                rids.append(rid)
                self._sinks[rid] = sink
                self._imported_locked(rid, t_submit, submit_trace)
                if deadline is not None:
                    self._deadlines[rid] = deadline
                self._restores += 1
                # the blob is still in the store: completion must drop it
                self._note_parked_locked(prog.session_id)
            elif handoff_state is not None:
                rid = self._restore_locked(prog, handoff_state,
                                           handoff=True)
                rids.append(rid)
                self._sinks[rid] = sink
                self._imported_locked(rid, t_submit, submit_trace)
                if deadline is not None:
                    self._deadlines[rid] = deadline
                self._handoff_imports += 1
                # the blob is a one-shot relay buffer — spliced in, it
                # is garbage (and would shadow a reused id)
                self._drop_handoff_async(prog.handoff_id)
            else:
                if prog.session_id is not None:
                    # re-check under THIS lock hold: a racing retry may
                    # have registered the session since the pre-fetch
                    # check released the lock
                    self._check_session_free_locked(prog.session_id)
                # named adapter → device slot BEFORE pricing: a
                # residency miss sheds typed here (background fetch
                # kicked, Retry-After from the pool's load-time EMA)
                # without touching the prefix cache or the ledger
                adapter_slot = self._resolve_adapter_locked(prog)
                plan = self._plan_locked(prog)
                self._shed_check_locked(prog, plan)
                # protect the WHOLE plan's prefixes from make-room
                # eviction for the span of this submit loop: item 1's
                # row make-room must not evict item 2's (still
                # refcount-0) hit entry, or item 2's submit would hit a
                # dangling prefix_id
                protect = {item["entry"].pid for item in plan
                           if item["entry"] is not None}
                try:
                    device_adapter = (adapter_slot
                                      if adapter_slot is not None
                                      else prog.adapter_id)
                    for item in plan:
                        pid = prog.prefix_id
                        if item["prefix"]:
                            pid, registered = self._ensure_prefix_locked(
                                item["prefix"], device_adapter,
                                item["key"], frozenset(protect),
                                adapter=prog.adapter)
                            if registered:
                                # this program's miss ran the prefix
                                # fill — count it against ITS naive
                                # tokens (an explicit register_prefix
                                # is deliberately uncounted: it has no
                                # naive side and would skew the
                                # savings ratio negative)
                                self._prefill_executed += len(
                                    item["prefix"])
                        if pid is not None:
                            protect.add(pid)
                        suffix = (item["suffix"] if pid is not None
                                  or not item["prefix"]
                                  else item["prefix"] + item["suffix"])
                        kwargs = dict(prog.submit_kwargs())
                        kwargs["prefix_id"] = pid
                        if adapter_slot is not None:
                            kwargs["adapter_id"] = adapter_slot
                        row_tokens = min(
                            len(suffix) + prog.max_new_tokens,
                            self._row_cap_tokens)
                        # the shed check priced the program, but an
                        # unshared fallback (pid None on a planned
                        # prefix) costs more than priced — enforce the
                        # budget here rather than silently oversubscribe
                        # (raising rolls back this program's earlier
                        # rows below)
                        if not self._make_room_locked(
                                self._kv.row_cost(row_tokens),
                                protect=frozenset(protect)):
                            max_delay = env_float("KT_MAX_QUEUE_DELAY_S")
                            raise ServerOverloaded(
                                f"KV budget exhausted mid-admission "
                                f"({self._kv.row_cost(row_tokens)} "
                                f"blocks needed, "
                                f"{self._kv.free_blocks} free)",
                                retry_after=retry_after_estimate(
                                    self._kv.row_cost(row_tokens), 1,
                                    self._ema_block_s, cap_s=max_delay))
                        rid = self.engine.submit(suffix, **kwargs)
                        rids.append(rid)
                        self._sinks[rid] = sink
                        self._req[rid] = _ReqTimes(
                            t_submit, time.perf_counter(), submit_trace)
                        if deadline is not None:
                            self._deadlines[rid] = deadline
                        # prefix_pid=pid covers explicit prefix_ids too:
                        # if the pool knows the pid it refcounts it (an
                        # unknown/engine-only pid is a no-op)
                        blocks = self._kv.reserve_row(
                            rid, row_tokens, prefix_pid=pid)
                        self._rid_meta[rid] = {
                            "blocks": blocks,
                            "session": prog.session_id,
                            "adapter": prog.adapter,
                            "handoff": (dict(prog.handoff)
                                        if prog.handoff is not None
                                        else None)}
                        if prog.adapter is not None:
                            # one pool ref per live row: a pinned
                            # adapter is never LRU-evicted out from
                            # under a decoding row (released in
                            # _release_locked — the single free path)
                            self._adapter_pool.acquire(prog.adapter)
                        if prog.session_id is not None:
                            self._live_sessions.add(prog.session_id)
                            self._bump_session_seq_locked(
                                prog.session_id)
                        self._prefill_naive += (len(item["prefix"])
                                                + len(item["suffix"]))
                        self._prefill_executed += len(suffix)
                except BaseException:
                    # a later prompt failed validation (too long, bad
                    # adapter/prefix): the earlier prompts are already
                    # queued — release them NOW or they burn rows
                    # streaming into a sink nobody will ever read (and a
                    # client retry of the whole program would re-run
                    # their work)
                    for rid in rids:
                        self.engine.evict(rid)
                        self._release_locked(rid)
                    raise
            if prog.tag:
                # bounded: one entry per tag would be a slow leak on a
                # long-lived pod tagging every request
                if (prog.tag not in self._exec_counts
                        and len(self._exec_counts) >= 4096):
                    self._exec_counts.pop(next(iter(self._exec_counts)))
                self._exec_counts[prog.tag] = (
                    self._exec_counts.get(prog.tag, 0) + 1)
            index_of = {rid: i for i, rid in enumerate(rids)}
            _record_engine("generation")
            self._wake.notify_all()
        live = set(rids)
        seq = 0
        try:
            while live:
                try:
                    item = sink.get(timeout=self._stall_s)
                except _queue.Empty:
                    raise TimeoutError(
                        f"engine produced no frame in {self._stall_s}s "
                        f"(KT_ENGINE_STALL_S) — driver stalled?") from None
                rid, payload = item
                if isinstance(payload, BaseException):
                    live.discard(rid)
                    raise payload
                if payload is None:
                    # the row was PARKED (explicit park): its KV is on
                    # its way to the store; the stream ends cleanly and
                    # a later same-session_id program resumes it
                    live.discard(rid)
                    frame = {"i": index_of[rid], "rid": rid, "seq": seq,
                             "tokens": [], "done": False, "parked": True,
                             "session_id": prog.session_id}
                    seq += 1
                    yield frame
                    continue
                if isinstance(payload, dict):
                    # the row was HANDED OFF to the decode tier: its
                    # exported state is durable at the paired pod (the
                    # sentinel arrives only after the publish landed —
                    # the park discipline); the prefill-side stream ends
                    # with a handoff frame, zero tokens emitted locally
                    live.discard(rid)
                    frame = {"i": index_of[rid], "rid": rid, "seq": seq,
                             "tokens": [], "done": False,
                             "handoff": True,
                             "handoff_id": payload["handoff"]}
                    seq += 1
                    yield frame
                    continue
                toks, done = payload
                if done:
                    live.discard(rid)
                frame = {"i": index_of[rid], "rid": rid, "seq": seq,
                         "tokens": toks, "done": bool(done)}
                seq += 1
                yield frame
        finally:
            # ANY early exit — stall, deadline raise, or the worker
            # closing the generator because the client abandoned the
            # stream / the wire deadline passed (gen.close() →
            # GeneratorExit at the yield) — must release the rows, or
            # an abandoned program keeps burning device chunks to its
            # token budget while new programs queue behind it
            if live:
                with self._wake:
                    for rid in live:
                        self.engine.evict(rid)
                        self._release_locked(rid)
                        self._evictions += 1
                        _record_engine("evict")

    def register_prefix(self, tokens, adapter_id: int = -1,
                        adapter: Optional[str] = None) -> int:
        """Explicit client-facing prefix registration, BUDGET-ACCOUNTED:
        the block ledger charges it, cold prefixes make way for it, and
        it is LRU-evictable like an auto-split registration — an
        explicit surface that bypassed the pool would grow device prefix
        planes the shed check can't see and reintroduce the HBM OOM the
        budget exists to prevent. Content-deduplicated: re-registering
        the same tokens+adapter returns the cached pid.

        ``adapter`` (a pool-managed NAME) keys the cache entry by name
        and fills the device KV under the adapter's CURRENT slot —
        shedding typed-retryable when the adapter is not yet resident
        (the fetch runs in the background, like a named submit)."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            raise ValueError("prefix needs >= 1 token")
        if not hasattr(self.engine, "register_prefix"):
            raise ValueError(
                f"{type(self.engine).__name__} does not support "
                f"prefix registration")
        with self._wake:
            device_id = int(adapter_id)
            if adapter is not None:
                device_id = self._resolve_adapter_name_locked(adapter)
            ident = adapter if adapter is not None else int(adapter_id)
            key = kvpool.prefix_key(tokens, ident)
            need = self._kv.row_cost(len(tokens))
            if self._kv.ledger.budget and need > self._kv.ledger.budget:
                raise ValueError(
                    f"a {len(tokens)}-token prefix needs {need} KV "
                    f"blocks — more than the whole "
                    f"{self._kv.ledger.budget}-block budget "
                    f"(KT_KV_HBM_BUDGET); not retryable")
            pid, _registered = self._ensure_prefix_locked(
                tokens, device_id, key, adapter=adapter)
            if pid is None:
                max_delay = env_float("KT_MAX_QUEUE_DELAY_S")
                raise ServerOverloaded(
                    f"no KV-block headroom to register a "
                    f"{len(tokens)}-token prefix "
                    f"(KT_KV_HBM_BUDGET={self._kv.ledger.budget})",
                    retry_after=retry_after_estimate(
                        need, 1, self._ema_block_s, cap_s=max_delay))
            return pid

    def drop_prefix(self, prefix_id: int) -> bool:
        """Explicitly release a registered prefix (ledger + device)."""
        with self._wake:
            self._kv.prefixes.remove(int(prefix_id))
            return bool(getattr(self.engine, "drop_prefix",
                                lambda _pid: False)(int(prefix_id)))

    def pending(self) -> int:
        """Engine-wide pending count — host bookkeeping, no device
        sync. Channel clients should poll via ``chan.control('stats')``
        (out-of-band, no worker hop) instead of calling this."""
        return int(self.engine.pending)

    def stats(self) -> Dict[str, Any]:
        """Scheduler snapshot (host-only). Also the source of the
        ``engine_*`` gauges the pod server's control frames answer
        from."""
        eng = self.engine
        executed = self._prefill_executed
        out = {
            "queued": int(eng.queued),
            "free_rows": int(eng.free_rows),
            "active_rows": int(eng.active_rows),
            "prefilling_rows": int(eng.prefilling_rows),
            "pending": int(eng.pending),
            "steps": self._steps,
            "tokens": self._tokens,
            # the HOST's wall from a decode chunk's dispatch to the end of
            # its blocking read (tick phases decode_dispatch .. decode_sync),
            # not device time
            "device_s": round(self._device_s, 6),
            "prefill_chunks": self._prefill_chunks,
            "admitted_rows": self._admitted,
            "ema_row_free_s": round(self._ema_row_s, 4),
            "ema_ttft_s": round(self._ema_ttft_s, 4),
            # paged-KV manager: block occupancy, prefix-cache state, and
            # the prefix-sharing savings ratio (prompt tokens that never
            # ran a prefill forward because their prefix was cached)
            "ema_block_free_s": round(self._ema_block_s, 5),
            "prefill_tokens_executed": executed,
            "prefill_tokens_naive": self._prefill_naive,
            "prefill_tokens_saved_ratio": round(
                1.0 - executed / self._prefill_naive, 4)
            if self._prefill_naive else 0.0,
            "parks": self._parks,
            "restores": self._restores,
            # disaggregated tier identity + handoff traffic + the
            # controller's routing currency
            "phase": self._phase,
            "handoff_exports": self._handoffs,
            "handoff_imports": self._handoff_imports,
            "row_eta_s": round(self._row_eta_locked(), 4),
            **self._kv.stats(),
            # one source of truth for the offload/restore counts (the
            # pool carries no counters of its own)
            "kv_offloads": self._parks,
            "kv_restores": self._restores,
            # the driver's timeline by phase (seconds, calls), and ticks
            # that dispatched a decode chunk
            **self._timer.stats(),
            "ticks": self._steps,
        }
        for name, (total, count) in self._life.items():
            out[f"{name}_sum"] = total
            out[f"{name}_count"] = count
        # device-truth utilization + flight-recorder state (both
        # conditional — absent means "plane not active here")
        if self._mfu is not None:
            out["mfu"] = round(self._mfu, 4)
            out["mbu"] = round(self._mbu, 4)
        if self._flight is not None:
            out["flight_seq"] = self._flight.seq
        # the generator's own host counters (decode_kv_positions_*: what
        # decode attention read of the grid); a sim engine has none
        gen_stats = getattr(eng, "stats", None)
        if gen_stats is not None:
            out.update(gen_stats())
        snap_fn = getattr(eng, "devstats_snapshot", None)
        if snap_fn is not None:
            try:
                snap = snap_fn()
                out["devstats_flops_total"] = snap["flops_total"]
                out["devstats_bytes_total"] = snap["bytes_total"]
                out["devstats_dispatches"] = int(snap["dispatches_total"])
            # ktlint: disable=KT004 -- stats are advisory; never fail a control frame
            except Exception:  # noqa: BLE001
                pass
        if self._adapter_pool is not None:
            ps = self._adapter_pool.stats()
            out.update({
                "adapter_slots": ps["slots"],
                "adapter_resident": ps["resident"],
                "adapter_pinned": ps["pinned"],
                "adapter_loading": ps["loading"],
                "adapter_loads": ps["loads"],
                "adapter_evictions": ps["evictions"],
                "adapter_misses": ps["misses"],
                "adapter_load_ema_s": round(ps["load_ema_s"], 4),
            })
        if getattr(eng, "spec", False):
            ss = eng.spec_stats
            out.update({
                "spec_rounds": int(ss.get("rounds", 0)),
                "spec_emitted": int(ss.get("emitted", 0)),
                "spec_tokens_per_pass": round(
                    float(ss.get("tokens_per_pass", 0.0)), 4),
                "spec_accept_rate": round(
                    float(ss.get("accept_rate", 0.0)), 4),
                "spec_verify_waste": int(ss.get("verify_waste", 0)),
                "spec_k_mean": round(float(ss.get("k_mean", 0.0)), 3),
                "spec_k_cap": int(ss.get("k_cap", 0)),
            })
        return out

    def exec_count(self, tag: str) -> int:
        """How many times a tagged program was EXECUTED (not replayed)
        — the e2e exactly-once assertion reads this back."""
        return self._exec_counts.get(tag, 0)

    def warmup(self, *args, **kwargs):
        warm = getattr(self.engine, "warmup", None)
        if warm is None:
            return False
        with self._wake:
            warm(*args, **kwargs)
        return True

    def close(self) -> None:
        with self._wake:
            self._stop = True
            # fail live streams NOW: a sink left dangling would block
            # its generate() thread for the full KT_ENGINE_STALL_S
            for rid, sink in list(self._sinks.items()):
                sink.put((rid, RuntimeError(
                    "engine closed with the generation live")))
                self._release_locked(rid)
            self._wake.notify_all()
        self._driver.join(timeout=5.0)

    def park(self, session_id: str) -> int:
        """Explicitly park a live session: export its row's KV + sampler
        state, publish it to the store (synchronously — when this
        returns, the state is durable and survives a pod kill), evict
        the row, and end the program's stream with a ``parked`` frame.
        A later ``generate`` with the same ``session_id`` resumes
        mid-generation without re-prefill. Returns rows parked (0 when
        the session has no exportable row — unknown id, or still
        mid-prefill)."""
        kvpool.check_session_id(session_id)
        if not hasattr(self.engine, "export_row"):
            return 0                  # engine serves unparked (docstring)
        quantized = bool(getattr(self.engine, "kv_quantized", False))
        exported: List[tuple] = []              # (rid, sink, state)
        with self._wake:
            seq0 = self._session_seq.get(session_id, 0)
            rids = [rid for rid, meta in list(self._rid_meta.items())
                    if meta.get("session") == session_id]
            for rid in rids:
                try:
                    state = self.engine.export_row(
                        rid, block_tokens=self._kv.block_tokens)
                except (KeyError, ValueError):
                    continue          # queued / mid-prefill / exported
                aname = (self._rid_meta.get(rid) or {}).get("adapter")
                if aname is not None:
                    # the blob carries the NAME (slots recycle; the
                    # restore re-resolves and rewrites the slot int)
                    state = dict(state)
                    state["adapter_name"] = _encode_adapter_name(aname)
                self.engine.evict(rid)
                sink = self._sinks.get(rid)
                self._release_locked(rid)
                exported.append((rid, sink, state))
        parked = 0
        for rid, sink, state in exported:       # store I/O off the lock
            # _offload_lock makes check+publish atomic w.r.t. any other
            # session publish (a stale background deadline-offload must
            # not interleave with — and land over — this durable park)
            with self._offload_lock:
                with self._wake:
                    # absent = evicted-from-tracking, NOT superseded
                    # (see _offload_async) — a durable explicit park
                    # must not be falsely failed
                    superseded = self._session_seq.get(
                        session_id, seq0) != seq0
                if superseded:
                    # a new program claimed the session between the
                    # export and this publish (the single-flight slot
                    # freed with the row): landing our blob now would
                    # shadow it — fail the parked stream typed instead
                    if sink is not None:
                        sink.put((rid, RuntimeError(
                            f"park of session {session_id} superseded "
                            f"by a newer program before its state was "
                            f"published")))
                    continue
                try:
                    kvpool.offload_session(session_id, state, quantized)
                except BaseException as exc:
                    # the row is gone but the state never landed: the
                    # client must NOT be told it can resume — fail the
                    # stream typed instead of the parked sentinel
                    if sink is not None:
                        sink.put((rid, RuntimeError(
                            f"park of session {session_id} failed to "
                            f"publish: {exc}")))
                    raise
                with self._wake:
                    landed_superseded = self._session_seq.get(
                        session_id, seq0) != seq0
                    if not landed_superseded:
                        self._parks += 1
                        self._note_parked_locked(session_id)
                if landed_superseded:
                    # claimed while we published (see _offload_async):
                    # the blob is stale the moment it landed — remove
                    # it and fail the parked stream typed
                    kvpool.drop_session(session_id)
                    if sink is not None:
                        sink.put((rid, RuntimeError(
                            f"park of session {session_id} superseded "
                            f"by a newer program while publishing")))
                    continue
            parked += 1
            if sink is not None:
                # sentinel only AFTER the blob is durable: when the
                # client sees {'parked': True}, resume cannot lose state
                sink.put((rid, None))
        return parked

    def _imported_locked(self, rid: int, t_submit: float,
                         trace: Optional[str]) -> None:
        """Lifecycle record of a row spliced in by a session restore or
        a handoff import: it never sat in the generator's queue, so the
        import is its admission."""
        now = time.perf_counter()
        req = self._req[rid] = _ReqTimes(t_submit, now, trace)
        req.t_admit = now
        self._timer.mark("kt.req.admit", rid)

    def _first_frame_locked(self, rid: int, req: _ReqTimes, now: float,
                            adapter: Optional[str] = None) -> None:
        """The rid's first tokens go to its sink at ``now``: split its
        time to first token at the record's stamps and observe each part
        in its named-histogram family (fleet-mergeable buckets; the
        submitting call's trace id is the exemplar, so a slow bucket is
        one click from ``ktpu trace``), behind the same must-never-raise
        guard as the counters. Named-adapter rows ALSO land in their
        per-adapter TTFT family — the per-tenant p99 the adapter SLO
        objectives burn against."""
        req.t_first = now
        t_admit = req.t_admit if req.t_admit is not None else now
        ttft = now - req.t_submit
        self._ema_ttft_s = 0.8 * self._ema_ttft_s + 0.2 * ttft
        self._timer.mark("kt.req.first_frame", rid)
        try:
            from kubetorch_tpu.observability.prometheus import (
                adapter_series,
                record_hist,
            )

            for name, value in zip(_LIFE_HISTS, (
                    req.t_queued - req.t_submit, t_admit - req.t_queued,
                    now - t_admit, ttft)):
                acc = self._life[name]
                acc[0] += value
                acc[1] += 1
                record_hist(name, value, trace_id=req.trace)
            if adapter is not None:
                record_hist(adapter_series(adapter, "ttft_seconds"), ttft)
        # ktlint: disable=KT004 -- metrics must never break the driver tick
        except Exception:  # noqa: BLE001
            pass

    # ------------------------------------------------------------ driver
    def _forget_locked(self, rid: int) -> None:
        self._sinks.pop(rid, None)
        self._deadlines.pop(rid, None)
        self._req.pop(rid, None)

    def _check_session_free_locked(self, session_id: str) -> None:
        if session_id in self._live_sessions:
            raise ValueError(
                f"session {session_id} already has a live generation on "
                f"this engine — one row per session (a racing retry must "
                f"not decode the same session twice)")

    def _note_parked_locked(self, session_id: str) -> None:
        """Track a session as having a store blob. One bounded LRU site
        for every producer (park / deadline-offload / restore); an
        entry evicted to keep the bound takes its blob with it."""
        self._parked_sessions.pop(session_id, None)
        if len(self._parked_sessions) >= 8192:
            victim = next(iter(self._parked_sessions))
            del self._parked_sessions[victim]
            self._drop_session_async(victim)
        self._parked_sessions[session_id] = True

    def _bump_session_seq_locked(self, session_id: str) -> None:
        """Advance the session's activity sequence (supersedes any
        in-flight background offload). Bounded like ``_exec_counts``:
        at 'millions of users' scale an unbounded per-session dict is a
        slow OOM. Re-bumps re-insert the key (LRU, not FIFO — a hot
        session is never the eviction victim), and values come from the
        global counter so recreation can't collide with a captured one."""
        self._session_seq.pop(session_id, None)
        if len(self._session_seq) >= 4096:
            self._session_seq.pop(next(iter(self._session_seq)))
        self._seq_counter += 1
        self._session_seq[session_id] = self._seq_counter

    def _release_locked(self, rid: int) -> None:
        """Forget a rid AND release its KV-pool holdings (ledger blocks
        + prefix refcount + session single-flight slot) — every path
        that frees a row goes through here so the accounting can never
        leak."""
        self._forget_locked(rid)
        meta = self._rid_meta.pop(rid, None)
        if meta and meta.get("session"):
            self._live_sessions.discard(meta["session"])
        if (meta and meta.get("adapter") is not None
                and self._adapter_pool is not None):
            self._adapter_pool.release(meta["adapter"])
        self._kv.release_row(rid)

    def _adapter_evicted_locked(self, name: str, slot: int) -> None:
        """Pool eviction hook (same lock hold as the evicting call):
        drop the departing adapter's name-keyed prefix entries and free
        their device KV. Live rows pin the adapter in the pool, so
        every entry here is cold by construction."""
        del slot
        for entry in self._kv.prefixes.remove_by_adapter(name):
            try:
                self.engine.drop_prefix(entry.pid)
            # ktlint: disable=KT004 -- ledger already dropped it; a failed device free must not block the evict
            except Exception:  # noqa: BLE001
                pass

    def _resolve_adapter_name_locked(self, name: str) -> int:
        """Adapter NAME → resident device slot, or shed.

        Not resident → ensure a background fetch is underway and raise
        a typed retryable :class:`ServerOverloaded` whose Retry-After
        comes from the pool's load-time EMA (minus fetch time already
        elapsed) — decoding rows never wait on a cold adapter's store
        fetch. A sticky fetch failure surfaces as a non-retryable
        ``ValueError`` (and the re-request behind it starts a fresh
        fetch, so a transient store fault self-heals)."""
        pool = self._adapter_pool
        if pool is None:
            raise ValueError(
                f"program names adapter {name!r} but the engine has no "
                f"adapter pool (construct DecodeEngine with "
                f"adapter_pool=)")
        err = pool.load_error(name)
        if err is not None:
            pool.request(name)      # clears the sticky error; refetches
            raise ValueError(
                f"adapter {name!r} failed to load: {err} (a fresh "
                f"fetch was started)")
        slot = pool.request(name)
        if slot is not None:
            return slot
        retry_after = pool.load_eta(name)
        self._sheds += 1
        _record_engine("shed")
        _record_adapter(name, "shed")
        tracing.record_span(
            "server.shed", 0.0,
            attrs={"transport": "engine", "adapter": name,
                   "reason": "adapter_cold",
                   "retry_after_s": retry_after})
        raise ServerOverloaded(
            f"adapter {name!r} is not resident (load in flight in the "
            f"background)", retry_after=retry_after)

    def _resolve_adapter_locked(
            self, prog: GenerationProgram) -> Optional[int]:
        if prog.adapter is None:
            return None
        return self._resolve_adapter_name_locked(prog.adapter)

    def _plan_locked(self, prog: GenerationProgram) -> List[Dict[str, Any]]:
        """Split each prompt by the pool's prefix rule and annotate with
        the cache state — the shed check prices the program from this
        (prefix hits cost only their suffix) before anything is
        submitted or registered."""
        rule = self._kv.split
        # (speculative engines share prefixes like any other: a prefix
        # hit splices the KV block AND seeds the row's draft haystack
        # from the shared tokens — the gate that once excluded them is
        # gone)
        auto = (rule is not None and prog.prefix_id is None
                and hasattr(self.engine, "register_prefix"))
        # cache identity: the stable NAME for pool-managed adapters
        # (slots recycle across evict/load cycles — see
        # kvpool.prefix_key), the raw slot int otherwise
        ident = (prog.adapter if prog.adapter is not None
                 else prog.adapter_id)
        plan: List[Dict[str, Any]] = []
        for p in prog.prompts:
            # (naive-token accounting happens at SUBMIT, not here — a
            # shed-and-retried program must not count twice)
            prefix, suffix = (kvpool.split_prompt(p, rule) if auto
                              else ([], list(p)))
            key = (kvpool.prefix_key(prefix, ident)
                   if prefix else None)
            # peek, not lookup: planning must not bump the hit count or
            # LRU position — only the admission path's lookup does
            entry = self._kv.prefixes.peek(key) if key else None
            plan.append({"prefix": prefix, "suffix": suffix,
                         "key": key, "entry": entry})
        return plan

    def _make_room_locked(self, blocks: int,
                          protect: frozenset = frozenset()) -> bool:
        """LRU-evict cold (refcount-0) prefixes until ``blocks`` fit the
        budget, freeing their device KV on the engine (never the pids in
        ``protect``). → whether the room exists now. A STRUCTURAL
        impossibility (blocks > the whole budget) returns False without
        evicting anything — flushing the entire cache for a request that
        can never fit would be pure thrash."""
        if (self._kv.ledger.budget
                and blocks > self._kv.ledger.budget):
            return False
        for victim in self._kv.prefixes.evict_for(blocks, protect):
            try:
                self.engine.drop_prefix(victim.pid)
            # ktlint: disable=KT004 -- ledger already dropped it; a failed device free must not block admission
            except Exception:  # noqa: BLE001
                pass
        return (not self._kv.ledger.budget
                or self._kv.free_blocks >= blocks)

    def _ensure_prefix_locked(self, prefix: List[int], adapter_id: int,
                              key: str,
                              protect: frozenset = frozenset(),
                              adapter: Optional[str] = None) -> tuple:
        """Hit → ``(pid, False)``. Miss → LRU-evict cold prefixes
        (never ``protect``) to make room under the budget, prefill the
        prefix ONCE (``engine.prefix_fill`` span), register it in the
        pool → ``(pid, True)``. ``(None, False)`` when the budget cannot
        fit it even after eviction — the caller serves the prompt
        unshared rather than shedding. The explicit registered flag is
        the caller's accounting signal (inferring it from cache size
        breaks when the insert itself LRU-evicted an entry)."""
        entry = self._kv.prefixes.lookup(key)
        if entry is not None:
            _record_engine("prefix_hit")
            return entry.pid, False
        need = kvpool.blocks_for(len(prefix), self._kv.block_tokens)
        if not self._make_room_locked(need, protect):
            return None, False
        t0 = time.perf_counter()
        pid = self.engine.register_prefix(prefix, adapter_id=adapter_id)
        tracing.record_span(
            "engine.prefix_fill", time.perf_counter() - t0,
            attrs={"tokens": len(prefix), "adapter_id": adapter_id})
        _record_engine("prefix_miss")
        # the cache entry binds to the stable identity (name when pool-
        # managed) — the device fill above used the CURRENT slot, but
        # the entry must outlive slot assignments only for its own name
        self._kv.prefixes.insert(
            key, pid, len(prefix),
            adapter if adapter is not None else adapter_id)
        return pid, True

    def _restore_locked(self, prog: GenerationProgram,
                        state: Dict[str, Any],
                        handoff: bool = False) -> int:
        """Splice a parked session's (or, with ``handoff=True``, an
        exported handoff row's) fetched state into a free row. No free
        row / no block headroom → typed ``ServerOverloaded`` (the blob
        stays put; the client retries after ``retry_after``) — a
        restore must never evict a LIVE row to make room.

        A state blob exported under a NAMED adapter carries the name
        binding (``adapter_name`` leaf): the adapter must be resident
        before the import — a miss kicks the pool load and sheds typed
        (blob stays put; the retry converges once the load lands) —
        and the exported slot int is REWRITTEN to the adapter's current
        slot, which may differ from the one it was exported under
        (cross-pod, the slots are unrelated by construction)."""
        what = (f"handoff {prog.handoff_id}" if handoff
                else f"session {prog.session_id}")
        binding = state.pop("adapter_name", None)
        name = (_decode_adapter_name(binding) if binding is not None
                else None)
        if name is None:
            name = prog.adapter
        elif prog.adapter is not None and prog.adapter != name:
            raise ValueError(
                f"{what} was exported under adapter {name!r}; the "
                f"resume names {prog.adapter!r} — a row's adapter "
                f"binding is fixed at "
                f"{'export' if handoff else 'park'}")
        slot = None
        if name is not None:
            slot = self._resolve_adapter_name_locked(name)
            import numpy as np

            sc = np.asarray(state["scalars"])
            if sc.ndim == 1 and sc.shape[0] > 3:
                sc = np.array(sc)
                sc[3] = slot
                state["scalars"] = sc
        ctx, emitted, max_new = kvpool.state_summary(state)
        need = self._kv.row_cost(min(ctx + (max_new - emitted),
                                     self._row_cap_tokens))
        if self._kv.ledger.budget and need > self._kv.ledger.budget:
            # structural: no amount of waiting frees enough blocks — a
            # retryable shed here would loop forever
            raise ValueError(
                f"restored {what} needs {need} KV "
                f"blocks — more than the whole {self._kv.ledger.budget}-"
                f"block budget (KT_KV_HBM_BUDGET)")
        max_delay = env_float("KT_MAX_QUEUE_DELAY_S")
        if (self.engine.free_rows < 1
                or not self._make_room_locked(need)):
            retry_after = retry_after_estimate(
                max(1, need), 1,
                max(self._ema_block_s, self._ema_row_s),
                cap_s=max_delay)
            self._sheds += 1
            _record_engine("shed")
            if name is not None:
                _record_adapter(name, "shed")
            raise ServerOverloaded(
                f"no free row/blocks to restore "
                f"{what} into ({need} blocks needed)",
                retry_after=retry_after)
        if not handoff:
            self._check_session_free_locked(prog.session_id)
        # block_tokens travels so the engine's geometry guard can refuse
        # typed on a block-size mismatch (cross-tier heterogeneity)
        rid = self.engine.import_row(
            state, block_tokens=self._kv.block_tokens)
        blocks = self._kv.reserve_row(
            rid, min(ctx + (max_new - emitted), self._row_cap_tokens))
        self._rid_meta[rid] = {"blocks": blocks,
                               "session": prog.session_id,
                               "adapter": name}
        if name is not None:
            self._adapter_pool.acquire(name)
        if not handoff:
            self._live_sessions.add(prog.session_id)
            self._bump_session_seq_locked(prog.session_id)
        return rid

    def _shed_check_locked(self, prog: GenerationProgram,
                           plan: List[Dict[str, Any]]) -> None:
        """Admission control in KV BLOCKS (with PR 9's row estimate and
        queue-length backstop retained): every program is priced at its
        worst-case block footprint — suffix + token budget, plus its
        prefix block when the prefix is not already cached — against
        the ledger's free blocks (cold refcount-0 prefixes count as
        reclaimable). The budget is a hard bound: HBM does not
        oversubscribe, it OOMs — so exceeding it sheds typed with a
        Retry-After computed from the block-free-rate EMA instead of
        letting the grid fall over. A prefix-HIT program costs only its
        suffix, which is what lets N same-prefix programs through a
        budget a row-accounted scheduler would have shed them under."""
        eng = self.engine
        waiting = int(eng.queued)
        n_new = len(plan)
        max_delay = env_float("KT_MAX_QUEUE_DELAY_S")
        hard_cap = self._max_waiting and (
            waiting + n_new > self._max_waiting)
        # PR 9's row-free estimate — still the binding constraint when
        # rows, not HBM, are scarce (short contexts, deep queue)
        est_delay = 0.0
        if eng.free_rows < n_new:
            est_delay = (waiting + n_new) * max(0.01, self._ema_row_s)
            if est_delay and getattr(eng, "spec", False):
                # price the live rows' verify cost at their CURRENT k:
                # a row at lookahead k spends k verify positions per
                # pass but only lands tokens_per_pass of them, so the
                # batch's effective service rate scales by
                # tokens_per_pass / k_mean. Well-adapted speculation
                # (accepts land, or the throttle collapsed k to 1)
                # prices at ~1x; badly-landing drafts price the queue
                # slower and shed sooner — verify waste is not free
                # row-time at the margin. tokens-per-pass comes from
                # the tick-delta EMA (recent rounds), NOT the engine's
                # lifetime average: k_mean is instantaneous, and after
                # a regime shift the cumulative ratio would misprice
                # admission for hours against rows that adapted in
                # seconds.
                ss = eng.spec_stats
                k_mean = max(1.0, float(ss.get("k_mean") or 1.0))
                recent = (self._spec_tpp_ema
                          if self._spec_tpp_ema is not None
                          else float(ss.get("tokens_per_pass") or 1.0))
                tpp = min(k_mean, max(1.0, recent))
                est_delay *= k_mean / tpp
        # KV-block pricing
        need = 0
        new_pfx: Dict[str, int] = {}
        for item in plan:
            need += self._kv.row_cost(min(
                len(item["suffix"]) + prog.max_new_tokens,
                self._row_cap_tokens))
            if item["prefix"] and item["entry"] is None:
                new_pfx[item["key"]] = kvpool.blocks_for(
                    len(item["prefix"]), self._kv.block_tokens)
        need += sum(new_pfx.values())
        short = 0
        if self._kv.ledger.budget:
            if need > self._kv.ledger.budget:
                # structural: the program can NEVER fit — reject
                # non-retryable instead of a Retry-After loop
                raise ValueError(
                    f"program needs {need} KV blocks — more than the "
                    f"whole {self._kv.ledger.budget}-block budget "
                    f"(KT_KV_HBM_BUDGET); shrink the prompt/token "
                    f"budget or raise the budget")
            # refcount-0 prefixes count as reclaimable — EXCEPT the ones
            # this very program is about to decode under (evicting a
            # plan's own hit to admit its row would turn the hit into a
            # dangling prefix_id)
            hit_pids = {item["entry"].pid for item in plan
                        if item["entry"] is not None}
            cold = sum(e.blocks
                       for e in self._kv.prefixes._entries.values()
                       if e.refs == 0 and e.pid not in hit_pids)
            short = max(0, need - (self._kv.free_blocks + cold))
        if hard_cap or est_delay > max_delay or short:
            ema = self._ema_block_s if short else self._ema_row_s
            retry_after = retry_after_estimate(
                max(short, waiting + n_new), 1, ema, cap_s=max_delay)
            self._sheds += 1
            _record_engine("shed")
            if prog.adapter is not None:
                _record_adapter(prog.adapter, "shed")
            tracing.record_span(
                "server.shed", 0.0,
                attrs={"transport": "engine", "queue_depth": waiting,
                       "kv_blocks_short": short,
                       "retry_after_s": retry_after})
            if short:
                raise ServerOverloaded(
                    f"KV budget exhausted: program needs {need} blocks, "
                    f"{short} short of the {self._kv.ledger.budget}-block "
                    f"HBM budget (KT_KV_HBM_BUDGET)",
                    retry_after=retry_after)
            raise ServerOverloaded(
                f"engine queue {waiting} deep, no row expected free "
                f"within {max_delay}s (est. {est_delay:.2f}s)",
                retry_after=retry_after)

    def _work_pending_locked(self) -> bool:
        # a finished adapter fetch is driver work even with zero live
        # rows: its install happens at the tick boundary, and the shed
        # tenant's retries stay cold until it runs
        if self._adapter_pool is not None and self._adapter_pool.has_staged():
            return True
        return bool(self.engine.pending)

    def _drive(self) -> None:
        timer = self._timer
        with self._wake:
            while True:
                while not self._stop and not self._work_pending_locked():
                    with timer("idle"):
                        self._wake.wait(timeout=self._poll_s)
                if self._stop:
                    return
                try:
                    self._tick_locked()
                # ktlint: disable=KT004 -- counted + reported per-sink; the loop must survive one bad tick
                except Exception as exc:  # noqa: BLE001
                    _record_engine("tick_error")
                    # a broken device step poisons every live program:
                    # fail their streams typed rather than hang them.
                    # Deliver to EVERY sink before any engine cleanup —
                    # evict() touches the same (possibly broken) device
                    # state that just raised, and a second raise here
                    # would kill the driver thread for good
                    for rid, sink in list(self._sinks.items()):
                        sink.put((rid, exc))
                    for rid in list(self._sinks):
                        try:
                            self.engine.evict(rid)
                        # ktlint: disable=KT004 -- device already faulted; the stream was failed above
                        except Exception:  # noqa: BLE001
                            pass
                        self._release_locked(rid)
                # Hand the lock over. This thread takes it again at once
                # and Python's locks are not fair: a submit, park or
                # stats call waiting on it otherwise starves until the
                # batch drains (a park then finds its row already
                # finished). Yielding the GIL lets the thread the release
                # just woke run first. Timed as its own phase: what the
                # driver pays to get the lock and the GIL back.
                with timer("handover"):
                    self._wake.release()
                    try:
                        # ktlint: disable=KT008 -- the lock is released around the yield
                        time.sleep(0)
                    finally:
                        self._wake.acquire()

    def _tick_locked(self) -> None:
        eng = self.engine
        # flight-record baseline: per-tick deltas of the cumulative
        # scheduler counters (cheap tuple of ints, taken before any
        # tick work so the record covers exactly this tick)
        fl_prev = (self._admitted, self._prefill_chunks, self._evictions,
                   self._parks, self._handoffs + self._handoff_imports,
                   self._sheds, getattr(eng, "prefill_tokens", 0),
                   getattr(eng, "_spec_rounds", 0),
                   getattr(eng, "_spec_emitted", 0))
        with self._timer as timer:
            with timer("evict"):
                self._evict_expired_locked()
                # cold-adapter installs (finished background fetches)
                if self._adapter_pool is not None:
                    self._adapter_pool.admit_ready()
            # ---- per-row admission into the live batch ---------------
            if eng.queued and eng.free_rows:
                with timer("admit") as phase:
                    rids = eng.admit(self._admit_rows or None)
                for rid in rids:
                    req = self._req.get(rid)
                    if req is not None:
                        # the phase's start: when the rid left the queue
                        req.t_admit = phase.t0
                        timer.mark("kt.req.admit", rid)
                if rids:
                    self._admitted += len(rids)
                    _record_engine("admit", len(rids))
                    timer.span("engine.admit", phase.last_s, ("rows",),
                               len(rids))
            # ---- one chunked-prefill dispatch, interleaved -----------
            prefill_dt = 0.0
            if eng.prefilling_rows:
                with timer("prefill") as phase:
                    eng.prefill_step()
                prefill_dt = phase.last_s
                self._prefill_s += prefill_dt
                self._prefill_chunks += 1
                _record_engine("prefill_chunk")
                timer.span("engine.prefill", prefill_dt, ("rows",),
                           eng.prefilling_rows)
            # ---- handoff exports (disaggregated prefill tier) --------
            # BEFORE the decode step: a handoff row must ship with zero
            # locally-emitted tokens, and the export-publish runs in the
            # background so row N's wire time overlaps row N+1's prefill
            with timer("handoff"):
                self._handoff_scan_locked()
            # ---- one decode chunk ------------------------------------
            # the generator times its own halves through ``tick_phase``:
            # the dispatch, the one blocking read, and its share of
            # ``route`` (trimming the chunk into events, freeing rows)
            # (and, between the two, the read and routing of the first
            # tokens this tick's admissions drew: ``_first_frames_locked``)
            events = eng.decode_step() if self._phase != "prefill" else []
            with timer("route"):
                device_dt = 0.0
                timer.tokens += sum(len(t) for _, t, _ in events)
                decode_tokens = timer.tokens
                # a chunk ran in this tick (its rows may all have finished
                # on their first frame, ahead of its end: no event is left)
                chunk = timer("decode_dispatch")
                if chunk.t0 >= timer.t0:
                    # the host's wall from the chunk's dispatch to the end
                    # of its read (the first tokens' read and routing lie
                    # between the two), not device time: the device also
                    # works off what admission left in its queue, and
                    # the host's own dispatch
                    sync = timer("decode_sync")
                    device_dt = sync.t0 + sync.last_s - chunk.t0
                    self._steps += 1
                    self._device_s += device_dt
                    _record_engine("step")
                    _record_engine("device_seconds", device_dt)
                    timer.span("engine.step", device_dt,
                               ("rows", "tokens"), len(events),
                               decode_tokens)
                self._route_locked(events)
            # ---- the instrumentation's own bill ----------------------
            with timer("publish"):
                self._spec_tick_locked()
                self._publish_gauges()
                self._flight_append_locked(
                    timer.t0, fl_prev, prefill_dt + device_dt,
                    decode_tokens)

    def _evict_expired_locked(self) -> None:
        """Deadline eviction, row-granular."""
        eng = self.engine
        now = time.time()
        for rid, dl in list(self._deadlines.items()):
            if now > dl:
                meta = self._rid_meta.get(rid) or {}
                session = meta.get("session")
                state = None
                if session is not None and hasattr(eng, "export_row"):
                    # a deadlined SESSION row parks instead of burning:
                    # export now (cheap device→host slice), offload in
                    # the background — the loop must not block on store
                    # I/O — and the stream still fails typed so the
                    # client knows the budget passed; a resume with the
                    # same session_id picks up where the deadline hit
                    try:
                        with self._timer("evict_sync"):
                            state = eng.export_row(
                                rid, block_tokens=self._kv.block_tokens)
                    except (KeyError, ValueError):
                        state = None
                if state is not None and meta.get("adapter") is not None:
                    state = dict(state)
                    state["adapter_name"] = _encode_adapter_name(
                        meta["adapter"])
                eng.evict(rid)
                sink = self._sinks.get(rid)
                self._release_locked(rid)
                self._evictions += 1
                _record_engine("evict")
                if state is not None:
                    self._offload_async(session, state)
                if sink is not None:
                    # "parking", not "parked": the offload runs in the
                    # background off the driver tick — an IMMEDIATE
                    # resume may race it and fall back to a re-prefill
                    # (the explicit park() path is the durable one)
                    sink.put((rid, DeadlineExceeded(
                        f"generation {rid} passed its deadline "
                        f"mid-stream"
                        + (f" (session {session} parking in background)"
                           if state is not None else ""),
                        deadline=dl)))

    def _first_frames_locked(self, events) -> None:
        """The generator's ``first_frames`` hook: ``events`` hold the one
        token each that this tick's admissions drew, read when the last
        prefill ended. Called inside the generator's ``route`` phase with
        the decode chunk queued behind the admissions, so the frames leave
        (and a request of one token finishes) while the device works, and
        the driver only then blocks on the chunk."""
        self._timer.tokens += sum(len(t) for _, t, _ in events)
        _record_engine("first_token_at_admit", len(events))
        self._route_locked(events)

    def _route_locked(self, events) -> None:
        """Frames to their sinks, and the row-free accounting."""
        eng = self.engine
        freed = 0
        blocks_freed = 0
        tnow = time.perf_counter()
        for rid, toks, done in events:
            self._tokens += len(toks)
            aname = (self._rid_meta.get(rid) or {}).get("adapter")
            if toks:
                _record_engine("tokens", len(toks))
                if aname is not None:
                    # per-tenant throughput: the fleet plane rolls the
                    # name-keyed counter into an adapter tok/s series
                    _record_adapter(aname, "tokens", len(toks))
                req = self._req.get(rid)
                if req is not None and req.t_first is None:
                    self._first_frame_locked(rid, req, tnow, aname)
            sink = self._sinks.get(rid)
            if sink is not None:
                sink.put((rid, ([int(t) for t in toks], bool(done))))
            if done:
                freed += 1
                if aname is not None:
                    _record_adapter(aname, "generations")
                meta = self._rid_meta.get(rid) or {}
                blocks_freed += meta.get("blocks", 0)
                if (meta.get("session")
                        and meta["session"] in self._parked_sessions):
                    # the session ran to completion: its parked blob is
                    # now STALE — drop it, or the next program with this
                    # session_id would restore a finished row instead of
                    # prefilling its new prompt. (Only sessions that
                    # actually parked/restored pay the store round-trips
                    # — most sessions never have a blob.)
                    self._parked_sessions.pop(meta["session"], None)
                    self._drop_session_async(meta["session"])
                self._release_locked(rid)
        if freed:
            t_free = time.time()
            if self._last_free_t is not None:
                gap = max(1e-4, (t_free - self._last_free_t) / freed)
                self._ema_row_s = 0.8 * self._ema_row_s + 0.2 * gap
                if blocks_freed:
                    # the block-admission clock: seconds per KV block
                    # returned to the ledger
                    bgap = max(1e-5, (t_free - self._last_free_t)
                               / blocks_freed)
                    self._ema_block_s = (0.8 * self._ema_block_s
                                         + 0.2 * bgap)
            self._last_free_t = t_free
        if not eng.pending:
            # going idle: the NEXT free event's gap would include the
            # whole idle stretch and poison the row-free EMA (one long
            # lull measured as a minutes-long est_delay → spurious
            # sheds on the next burst)
            self._last_free_t = None

    def _flight_append_locked(self, tick_t0: float, prev: tuple,
                              device_dt: float,
                              decode_tokens: int) -> None:
        """One flight record for the tick that just ran: stamps, the
        host/device decomposition, per-tick scheduler deltas, load, the
        devstats window's MFU/MBU, and the live programs' trace ids —
        the join key against PR-4 spans. One ring-slot tuple write; its
        share of a tick is ``tick_publish_ms`` (PERF.md section 5)."""
        fl = self._flight
        if fl is None:
            return
        try:
            eng = self.engine
            a0, p0, e0, k0, h0, s0, pt0, sr0, se0 = prev
            tick_s = time.perf_counter() - tick_t0
            trace_ids = tuple(sorted(
                {r.trace for r in self._req.values() if r.trace}))[:8]
            fl.append(
                time.time(), time.monotonic(), tick_s, device_dt,
                max(0.0, tick_s - device_dt),
                self._admitted - a0, self._prefill_chunks - p0,
                getattr(eng, "prefill_tokens", 0) - pt0, decode_tokens,
                getattr(eng, "_spec_rounds", 0) - sr0,
                getattr(eng, "_spec_emitted", 0) - se0,
                self._evictions - e0, self._parks - k0,
                self._handoffs + self._handoff_imports - h0,
                self._sheds - s0, int(eng.queued), int(eng.active_rows),
                (float(self._kv.free_blocks) if self._kv.ledger.budget
                 else None),
                self._mfu, self._mbu, trace_ids)
        # ktlint: disable=KT004 -- the black box must never fail the tick it records
        except Exception:  # noqa: BLE001
            pass

    def _spec_tick_locked(self) -> None:
        """Aggregate-lookahead throttle + spec telemetry, once per
        driver tick. Occupancy ≥ ``KT_SPEC_OCCUPANCY_THROTTLE`` means
        the batch is compute-bound — verify positions now displace
        decode FLOPs instead of riding free on the weight stream — so
        every row's lookahead caps at 1 (k decays to plain decode
        immediately); when occupancy falls back into the latency
        regime the cap lifts and per-row EMAs regrow the k's."""
        eng = self.engine
        if not getattr(eng, "spec", False):
            return
        slots = int(getattr(eng, "max_slots", 0) or 0)
        if slots and hasattr(eng, "set_spec_cap"):
            occ = (eng.active_rows + eng.prefilling_rows) / slots
            capped = occ >= self._spec_throttle
            if capped != self._spec_capped:
                self._spec_capped = capped
                eng.set_spec_cap(1 if capped else 0)
        ss = getattr(eng, "spec_stats", None) or {}
        _record_engine("spec_k_cap", float(ss.get("k_cap", 0)))
        deltas: Dict[str, float] = {}
        for event, key in (("spec_rounds", "rounds"),
                           ("spec_emitted", "emitted"),
                           ("spec_drafted", "drafted"),
                           ("spec_verify_waste", "verify_waste")):
            cur = float(ss.get(key, 0.0))
            d = cur - self._spec_prev.get(key, 0.0)
            if d > 0:
                _record_engine(event, d)
                deltas[key] = d
            self._spec_prev[key] = cur
        if deltas.get("rounds"):
            # recent tokens-per-pass for the shed check's verify
            # pricing (0.25 ≈ the lookahead EMA's horizon)
            tick_tpp = deltas.get("emitted", 0.0) / deltas["rounds"]
            self._spec_tpp_ema = (
                tick_tpp if self._spec_tpp_ema is None
                else 0.75 * self._spec_tpp_ema + 0.25 * tick_tpp)
        _record_engine("spec_accept_rate",
                       float(ss.get("accept_rate", 0.0)))
        # per-row lookahead distribution (fleet-mergeable buckets),
        # only on ticks that actually ran verify rounds — an idle or
        # stalled batch must not re-sample unchanged k's every poll
        if deltas.get("rounds"):
            ks = (eng.spec_row_ks()
                  if hasattr(eng, "spec_row_ks") else [])
            if ks:
                try:
                    from kubetorch_tpu.observability.prometheus import (
                        record_hist_batch,
                    )

                    record_hist_batch("engine_spec_k", ks,
                                      buckets=_SPEC_K_BUCKETS)
                # ktlint: disable=KT004 -- metrics must never break the driver tick
                except Exception:  # noqa: BLE001
                    pass

    def _offload_async(self, session_id: str,
                       state: Dict[str, Any]) -> None:
        """Background session offload (deadline parks): the driver tick
        must not block on store I/O. One short-lived thread per park —
        deadline parks are rare by construction. Guarded by the session
        sequence: if a NEWER program claims the session while the
        publish is in flight, the stale blob is refused (or dropped
        right after landing) instead of shadowing the new generation."""
        quantized = bool(getattr(self.engine, "kv_quantized", False))
        seq0 = self._session_seq.get(session_id, 0)

        def _superseded() -> bool:
            # ABSENT is not superseded: the bounded seq dict may have
            # LRU-evicted an idle session's entry while this offload was
            # in flight — refusing then would silently lose the ONLY
            # copy of the state (the row is already evicted). A genuine
            # supersession re-inserts the key with a newer value.
            with self._wake:
                return self._session_seq.get(session_id, seq0) != seq0

        def _push():
            try:
                # _offload_lock: this check+publish(+drop) must not
                # interleave with an explicit park()'s — a stale
                # background publish landing OVER a newer durable park
                # (then dropping it) would break the parked sentinel's
                # promise
                with self._offload_lock:
                    if _superseded():
                        # a resubmit claimed the session while we
                        # queued: refuse to publish state it has moved
                        # past. Observable: a span, not a silent return.
                        tracing.record_span(
                            "kv.park_superseded", 0.0,
                            attrs={"session": session_id})
                        return
                    kvpool.offload_session(session_id, state, quantized)
                    if _superseded():
                        # a newer program claimed the session WHILE we
                        # published — and may already have completed,
                        # so its completion-drop cannot have seen our
                        # blob. The claim means the client moved past
                        # the parked state (it restored nothing — the
                        # blob wasn't there yet): drop it rather than
                        # let it shadow the session's next program.
                        kvpool.drop_session(session_id)
                        tracing.record_span(
                            "kv.park_superseded", 0.0,
                            attrs={"session": session_id,
                                   "at": "landed"})
                        return
                    with self._wake:  # counters share the scheduler lock
                        self._parks += 1
                        self._note_parked_locked(session_id)
            # ktlint: disable=KT004 -- counted; a failed park only costs
            # the session its resume (the client re-prefills)
            except Exception:  # noqa: BLE001
                _record_engine("tick_error")

        threading.Thread(
            target=contextvars.copy_context().run, args=(_push,),
            name="kt-kv-offload", daemon=True).start()

    def _drop_session_async(self, session_id: str) -> None:
        """Invalidate a completed session's parked blob (store I/O off
        the driver tick; best-effort — a failed delete only means one
        stale restore, which the single-flight check keeps coherent)."""

        def _drop():
            try:
                kvpool.drop_session(session_id)
            # ktlint: disable=KT004 -- best-effort invalidation
            except Exception:  # noqa: BLE001
                pass

        threading.Thread(
            target=contextvars.copy_context().run, args=(_drop,),
            name="kt-kv-drop", daemon=True).start()

    def _handoff_scan_locked(self) -> None:
        """Export every decode-active row that carries a handoff
        binding: slice its state off the device, evict the row, and
        publish in the BACKGROUND (one short-lived thread per export —
        the driver tick must not block on wire time, and the next
        program's prefill runs while the publish is in flight). The stream's
        handoff sentinel is delivered only after the publish lands —
        the same durable-then-sentinel discipline as park()."""
        if not hasattr(self.engine, "export_row"):
            return
        for rid, meta in list(self._rid_meta.items()):
            ho = meta.get("handoff")
            if not ho:
                continue
            try:
                with self._timer("handoff_sync"):
                    state = self.engine.export_row(
                        rid, block_tokens=self._kv.block_tokens)
            except (KeyError, ValueError):
                continue          # queued / mid-prefill — next tick
            if meta.get("adapter") is not None:
                # the blob carries the NAME (cross-pod, slot ints are
                # unrelated; the decode pod re-resolves and rewrites)
                state = dict(state)
                state["adapter_name"] = _encode_adapter_name(
                    meta["adapter"])
            self.engine.evict(rid)
            sink = self._sinks.get(rid)
            self._release_locked(rid)
            self._handoff_async(rid, dict(ho), state, sink)

    def _handoff_async(self, rid: int, ho: Dict[str, Any],
                       state: Dict[str, Any], sink) -> None:
        quantized = bool(getattr(self.engine, "kv_quantized", False))

        def _push():
            try:
                kvpool.offload_handoff(ho["id"], state, quantized,
                                       store_url=ho.get("store_url"))
            # ktlint: disable=KT004 -- reported to the stream; the row is
            # gone either way and the client must not wait on a decode
            # pod that will never see the blob
            except Exception as exc:  # noqa: BLE001
                _record_engine("tick_error")
                if sink is not None:
                    sink.put((rid, RuntimeError(
                        f"handoff {ho['id']} failed to publish: {exc}")))
                return
            with self._wake:
                self._handoffs += 1
            if sink is not None:
                # sentinel only AFTER the blob is durable at the decode
                # pod: when the client sees {'handoff': True}, the
                # import cannot lose state
                sink.put((rid, {"handoff": ho["id"]}))

        threading.Thread(
            target=contextvars.copy_context().run, args=(_push,),
            name="kt-kv-handoff", daemon=True).start()

    def _await_handoff(self, handoff_id: str) -> Optional[Dict[str, Any]]:
        """Decode-side poll for the prefill pod's export. The chaos
        hook (``KT_CHAOS=handoff-drop``) simulates THIS pod dying
        mid-handoff: a typed retryable raise the caller re-routes (the
        exported blob is still in the store — another decode pod, or
        the monolithic fallback, picks it up)."""
        from kubetorch_tpu.resilience import chaos

        if chaos.maybe(chaos.HANDOFF_DROP, handoff_id):
            self._sheds += 1
            _record_engine("shed")
            raise ServerOverloaded(
                f"decode pod dropped mid-handoff of {handoff_id} "
                f"(chaos) — re-route the import",
                retry_after=0.0)
        timeout = env_float("KT_HANDOFF_TIMEOUT_S")
        poll = max(0.0005, env_float("KT_HANDOFF_POLL_S"))
        deadline = time.perf_counter() + max(0.0, timeout)
        while True:
            state = kvpool.restore_handoff(handoff_id)
            if state is not None:
                return state
            if time.perf_counter() >= deadline:
                return None
            time.sleep(poll)

    def _drop_handoff_async(self, handoff_id: str) -> None:
        """Invalidate an imported handoff blob (store I/O off the
        serving path; best-effort — a failed delete only costs store
        rent until the key is reused or GC'd)."""

        def _drop():
            try:
                kvpool.drop_handoff(handoff_id)
            # ktlint: disable=KT004 -- best-effort invalidation
            except Exception:  # noqa: BLE001
                pass

        threading.Thread(
            target=contextvars.copy_context().run, args=(_drop,),
            name="kt-kv-drop", daemon=True).start()

    def _row_eta_locked(self) -> float:
        """Earliest expected row-free time, the decode-tier routing
        currency (gauged as ``engine_row_eta_seconds``): 0 with a free
        row, else queue depth against the row-free EMA, repriced by the
        live batch's speculation state exactly as the shed check prices
        admission — a decode pod whose drafts are landing frees rows
        faster than its raw EMA says."""
        eng = self.engine
        if eng.free_rows > 0:
            return 0.0
        eta = (int(eng.queued) + 1) * max(0.01, self._ema_row_s)
        if getattr(eng, "spec", False):
            ss = eng.spec_stats
            k_mean = max(1.0, float(ss.get("k_mean") or 1.0))
            recent = (self._spec_tpp_ema
                      if self._spec_tpp_ema is not None
                      else float(ss.get("tokens_per_pass") or 1.0))
            eta *= k_mean / min(k_mean, max(1.0, recent))
        return eta

    def _publish_gauges(self) -> None:
        eng = self.engine
        _record_engine("queue_depth", float(eng.queued))
        _record_engine("active_rows", float(eng.active_rows))
        _record_engine("free_rows", float(eng.free_rows))
        _record_engine("prefilling_rows", float(eng.prefilling_rows))
        _record_engine("kv_blocks_used", float(self._kv.used_blocks))
        if self._kv.ledger.budget:
            _record_engine("kv_blocks_free", float(self._kv.free_blocks))
        _record_engine("phase", float(_PHASE_CODE[self._phase]))
        _record_engine("row_eta_seconds", self._row_eta_locked())
        self._publish_utilization()

    def _publish_utilization(self) -> None:
        """Window MFU/MBU off the generator's devstats surface + HBM
        occupancy off ``memory_stats()``. All three gauge families are
        conditional (absent, not zero): no devstats surface, unknown
        chip peaks, or an empty measurement window publish nothing."""
        eng = self.engine
        snap_fn = getattr(eng, "devstats_snapshot", None)
        peaks_fn = getattr(eng, "devstats_peaks", None)
        if snap_fn is not None and peaks_fn is not None:
            try:
                snap = snap_fn()
                peaks = peaks_fn()
                wall = self._device_s + self._prefill_s
                f0, b0, w0 = self._util_prev
                util = devstats.utilization(
                    snap["flops_total"] - f0, snap["bytes_total"] - b0,
                    wall - w0, peaks)
                if util is not None:
                    self._mfu, self._mbu = util
                    self._util_prev = (snap["flops_total"],
                                       snap["bytes_total"], wall)
                    _record_engine("mfu", self._mfu)
                    _record_engine("mbu", self._mbu)
            # ktlint: disable=KT004 -- utilization is best-effort; the driver tick must survive it
            except Exception:  # noqa: BLE001
                pass
        now = time.monotonic()
        if now - self._hbm_t >= 0.5:      # memory_stats at ~2 Hz, not
            self._hbm_t = now             # per-tick — it's a runtime RPC
            hbm = devstats.hbm_stats()
            if hbm is not None:
                _record_engine("hbm_used_bytes", hbm["hbm_used_bytes"])
                _record_engine("hbm_limit_bytes", hbm["hbm_limit_bytes"])


class SimRollingEngine:
    """Host-only twin of :class:`RollingGenerator`'s driving surface.

    Token emission is a pure function of (prompt, index) — see
    :meth:`expected_tokens` — so byte-identity across PR-8 replay is
    assertable from the client side without a model; ``step_s`` models
    the per-decode-chunk device time (one sleep per chunk regardless of
    occupancy, like a real batched step). Used by the scheduler's and
    the engine's tests; the scheduler above cannot tell it from the
    real thing. It gives counts and identities, never a rate.
    """

    kv_quantized = False

    def __init__(self, max_slots: int = 8, steps_per_call: int = 8,
                 prefill_chunk: Optional[int] = None,
                 step_s: float = 0.0, prefill_s: Optional[float] = None,
                 max_len: int = 2048, spec_k: int = 0,
                 spec_accept=None, spec_ema_alpha: float = 0.25,
                 adapter_slots: int = 0, adapter_write_s: float = 0.0):
        if spec_k < 0 or spec_k == 1:
            raise ValueError("spec_k must be 0 (off) or >= 2")
        self.max_slots = max_slots
        # named-adapter twin surface: `adapter_slots` fixed device
        # slots an AdapterPool installs into via load_adapter_slot
        # (adapter_write_s models the dynamic-slice device write)
        self.adapter_slots = int(adapter_slots)
        self.adapter_write_s = float(adapter_write_s)
        self._adapter_names: Dict[int, Any] = {}   # slot -> loaded tree
        self.max_len = max_len
        self.steps_per_call = steps_per_call
        self.prefill_chunk = prefill_chunk
        self.step_s = step_s
        self.prefill_s = prefill_s if prefill_s is not None else step_s
        self._queue: List[dict] = []
        self._rows: Dict[int, dict] = {}        # rid -> active request
        self._prefilling: Dict[int, dict] = {}  # rid -> request
        self._free = list(range(max_slots))
        self._next_rid = 0
        # mirrors RollingGenerator's prefix surface: pid -> tokens;
        # emission stays a pure function of (prefix + suffix, index) so
        # shared-prefix streams are byte-assertable too
        self._prefixes: Dict[int, dict] = {}
        self._next_prefix_id = 0
        # prompt tokens run through a "prefill" (suffix only for
        # prefixed submits; a registered prefix counts once)
        self.prefill_tokens = 0
        # speculative surface (mirrors RollingGenerator): each decode
        # step becomes steps_per_call verify ROUNDS; per-row lookahead
        # adapts through the shared LookaheadState machine against a
        # SCRIPTED accept rate (`spec_accept`: float, or
        # callable(prompt) -> rate — deterministic, so the scheduler and
        # the adaptation logic run CPU-only). Emission stays
        # the same pure function of (prompt, index): speculation
        # changes how many tokens land per chunk, never which — the
        # spec-on ≡ spec-off byte-identity the greedy engine pins.
        self.spec_k = int(spec_k)
        self.spec = self.spec_k > 1
        self.spec_cap = 0
        self.spec_ema_alpha = float(spec_ema_alpha)
        self._spec_accept = spec_accept
        self._spec_state: Dict[int, Any] = {}   # rid -> LookaheadState
        self._spec_rounds = 0
        self._spec_emitted = 0
        self._spec_drafted = 0
        # rid -> lookahead at completion (the tests' convergence probe;
        # bounded — oldest entries drop)
        self.spec_k_done: Dict[int, int] = {}
        # device-truth twin (observability/devstats.py): nominal
        # per-token FLOPs / per-dispatch HBM bytes plus settable "chip"
        # peaks, so the MFU/MBU plane (gauges -> flight records ->
        # `ktpu top` columns) runs CPU-only and deterministically.
        # Defaults model a ~1B-param bf16 model on a nominal chip.
        self.sim_flops_per_token = 2.0e9
        self.sim_bytes_per_dispatch = 2.0e9
        self.peak_flops = 100e12
        self.peak_bw = 1.0e12
        self._devstats = devstats.AnalyticCosts()
        # the serving engine installs its phase timer and its dispatch
        # hook here, as on RollingGenerator; hand-driven, both are no-ops
        self.tick_phase = contextlib.nullcontext
        self.dispatched = devstats.no_dispatch

    # -------------------------------------------------------- interface
    @staticmethod
    def expected_tokens(prompt: List[int], n: int) -> List[int]:
        """Ground truth for byte-identity assertions: the exact token
        stream a request with this prompt emits (``prompt`` includes any
        shared prefix — prefixed submits emit as if the full
        prefix+suffix prompt had been submitted plain)."""
        seed = ",".join(str(int(t)) for t in prompt)
        return [int.from_bytes(
            hashlib.sha256(f"{seed}:{i}".encode()).digest()[:4],
            "little") % 32000 for i in range(n)]

    def load_adapter_slot(self, slot: int, adapter: Any) -> None:
        """Host twin of ``RollingGenerator.load_adapter_slot``: record
        the write (``adapter`` is whatever the pool's loader produced —
        the sim never reads it) and charge the simulated device-write
        time: the write costs wall time while decode keeps stepping —
        the real engine's shape exactly."""
        if not self.adapter_slots:
            raise ValueError("sim engine has no adapter slots "
                             "(construct with adapter_slots=)")
        if not 0 <= int(slot) < self.adapter_slots:
            raise ValueError(f"adapter slot {slot} out of range "
                             f"({self.adapter_slots} slots)")
        if self.adapter_write_s:
            time.sleep(self.adapter_write_s)
        self._adapter_names[int(slot)] = adapter

    def register_prefix(self, tokens, adapter_id: int = -1) -> int:
        pid = self._next_prefix_id
        self._next_prefix_id += 1
        self._prefixes[pid] = {"tokens": [int(t) for t in tokens],
                               "adapter_id": int(adapter_id)}
        self.prefill_tokens += len(tokens)
        return pid

    def drop_prefix(self, prefix_id: int) -> bool:
        return self._prefixes.pop(prefix_id, None) is not None

    def prefix_len(self, prefix_id: int) -> int:
        return len(self._prefixes[prefix_id]["tokens"])

    def submit(self, prompt, max_new_tokens: int = 128,
               prefix_id: Optional[int] = None, adapter_id: int = -1,
               **_ignored) -> int:
        head: List[int] = []
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise KeyError(f"unknown prefix_id {prefix_id}")
            entry = self._prefixes[prefix_id]
            if entry["adapter_id"] != int(adapter_id):
                raise ValueError(
                    f"prefix {prefix_id} was registered with adapter "
                    f"{entry['adapter_id']}; submit passed {adapter_id}")
            if not prompt:
                raise ValueError("prefixed submit needs >= 1 suffix token")
            head = entry["tokens"]
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append({"rid": rid,
                            "prompt": head + [int(t) for t in prompt],
                            "n": int(max_new_tokens), "emitted": 0,
                            "consumed": 0, "head": len(head),
                            "suffix": len(prompt), "slot": None})
        return rid

    def admit(self, max_rows: Optional[int] = None) -> List[int]:
        admitted: List[int] = []
        rows_before = len(self._rows)
        while self._free and self._queue and (
                max_rows is None or len(admitted) < max_rows):
            req = self._queue.pop(0)
            req["slot"] = self._free.pop(0)
            admitted.append(req["rid"])
            self.prefill_tokens += req.get("suffix", len(req["prompt"]))
            # a prefixed row's head is already "computed" — only the
            # suffix consumes prefill chunks
            req["consumed"] = req.get("head", 0)
            if (self.prefill_chunk is not None
                    and len(req["prompt"]) - req["consumed"]
                    > self.prefill_chunk):
                self._prefilling[req["rid"]] = req
            else:
                req["consumed"] = len(req["prompt"])
                self._rows[req["rid"]] = req
        if len(self._rows) > rows_before:
            # the one-shot admissions' prefill, which names no bucket
            self.dispatched("admit", None)
        return admitted

    def prefill_step(self) -> List[int]:
        if not self._prefilling:
            return []
        self.dispatched("prefill_ext", self.prefill_chunk)
        if self.prefill_s:
            time.sleep(self.prefill_s)
        activated = []
        chunk_toks = 0
        for rid, req in list(self._prefilling.items()):
            before = req["consumed"]
            req["consumed"] = min(len(req["prompt"]),
                                  req["consumed"] + self.prefill_chunk)
            chunk_toks += req["consumed"] - before
            if req["consumed"] >= len(req["prompt"]):
                del self._prefilling[rid]
                self._rows[rid] = req
                activated.append(rid)
        self._devstats.count(chunk_toks * self.sim_flops_per_token,
                             self.sim_bytes_per_dispatch)
        return activated

    def decode_step(self):
        if not self._rows:
            return []
        # the real generator's three stretches of a chunk: the dispatch
        # (only said here), the blocking read (the modelled device
        # time), and trimming the chunk into events
        with self.tick_phase("decode_dispatch"):
            self.dispatched("decode", self.steps_per_call)
        with self.tick_phase("decode_sync"):
            if self.step_s:
                time.sleep(self.step_s)
        with self.tick_phase("route"):
            return self._emit_events()

    def _emit_events(self):
        events = []
        for rid, req in list(self._rows.items()):
            if self.spec:
                n_new = self._spec_row_step(rid, req)
            else:
                n_new = min(self.steps_per_call,
                            req["n"] - req["emitted"])
            toks = self.expected_tokens(
                req["prompt"], req["emitted"] + n_new)[req["emitted"]:]
            req["emitted"] += n_new
            done = req["emitted"] >= req["n"]
            events.append((rid, toks, done))
            if done:
                self._free.append(req["slot"])
                del self._rows[rid]
                st = self._spec_state.pop(rid, None)
                if st is not None:
                    if len(self.spec_k_done) >= 4096:
                        self.spec_k_done.pop(next(iter(self.spec_k_done)))
                    self.spec_k_done[rid] = st.k
        self._devstats.count(
            sum(len(t) for _, t, _ in events) * self.sim_flops_per_token,
            self.sim_bytes_per_dispatch)
        return events

    def devstats_snapshot(self) -> Dict[str, float]:
        """Same surface as ``RollingGenerator.devstats_snapshot`` —
        analytic costs instead of compiled ``cost_analysis()``."""
        return self._devstats.snapshot()

    def devstats_peaks(self) -> Tuple[float, float]:
        return (self.peak_flops, self.peak_bw)

    # ------------------------------------------------------ spec twin
    def _accept_rate(self, prompt) -> float:
        r = self._spec_accept
        if callable(r):
            r = r(prompt)
        return max(0.0, min(1.0, float(r or 0.0)))

    def _spec_row_step(self, rid: int, req: dict) -> int:
        """One decode step = ``steps_per_call`` verify rounds for this
        row at its adaptive lookahead: the scripted accept rate feeds a
        deterministic fractional accumulator (rate × (k−1) drafts land
        per round on average), the same tokens land that plain decode
        would (pure function of (prompt, index)), and the shared
        ``LookaheadState`` observes/adapts exactly as the real engine's
        host loop does."""
        st = self._spec_state.get(rid)
        if st is None:
            st = self._spec_state[rid] = LookaheadState(
                self.spec_k, self.spec_cap)
        rate = self._accept_rate(req["prompt"])
        emitted = 0
        k_used = st.k
        for _ in range(self.steps_per_call):
            self._spec_rounds += 1
            self._spec_drafted += k_used - 1
            req["acc_frac"] = (req.get("acc_frac", 0.0)
                               + rate * (k_used - 1))
            a = min(int(req["acc_frac"]), k_used - 1)
            req["acc_frac"] -= a
            emit = 1 + a
            st.observe(emit, k_used, alpha=self.spec_ema_alpha)
            emitted += emit
        st.adapt(self.spec_k, self.spec_cap)
        emitted = min(emitted, req["n"] - req["emitted"])
        self._spec_emitted += emitted
        return emitted

    def set_spec_cap(self, cap: int) -> None:
        if self.spec:
            self.spec_cap = max(0, int(cap))

    def spec_row_ks(self):
        # lock-free readers (stats/control frames) race the driver's
        # admit/free — snapshot, like RollingGenerator.spec_row_ks
        if not self.spec:
            return []
        rows = self._rows
        return [st.k for rid, st in list(self._spec_state.items())
                if rid in rows]

    @property
    def spec_stats(self) -> Dict[str, float]:
        if not self.spec:
            return {}
        return spec_stats_dict(self._spec_rounds, self._spec_emitted,
                               self._spec_drafted, self.spec_row_ks(),
                               self.spec_k, self.spec_cap)

    def step(self):
        self.admit()
        self.prefill_step()
        return self.decode_step()

    def export_row(self, rid: int, block_tokens: int = 16) -> dict:
        """Host-only twin of ``RollingGenerator.export_row``: the same
        tree shape (per-block ``kv`` leaves + the ``scalars`` header
        ``[ctx, emitted, max_new]``), with KV block content a pure
        function of (prompt, block index) — byte-STABLE across re-parks,
        so the delta-manifest skip path is exercised for real."""
        import numpy as np

        req = self._rows.get(rid)
        if req is None:
            raise KeyError(f"rid {rid} is not decode-active")
        bt = max(1, int(block_tokens))
        ctx = len(req["prompt"]) + req["emitted"]
        nblocks = kvpool.padded_blocks(ctx, bt, self.max_len)
        seed = ",".join(str(t) for t in req["prompt"])
        kv = {f"{b:05d}": np.frombuffer(
            hashlib.sha256(f"kv:{seed}:{b}".encode()).digest(),
            np.uint8).reshape(4, 8).copy() for b in range(nblocks)}
        state = {
            "kv": {"k": kv},
            "prompt": np.asarray(req["prompt"], np.int64),
            "scalars": np.asarray(
                [ctx, req["emitted"], req["n"]], np.int64),
            # the real engine's geometry leaf (import refuses typed on
            # any axis mismatch): [block_tokens, max_len, lora_slots]
            "geom": np.asarray([bt, self.max_len, self.adapter_slots],
                               np.int64),
        }
        if self.spec:
            # the sim's "draft context" is the lookahead/EMA pair — the
            # same leaves the real engine parks, so park/resume keeps a
            # spec session's adaptation state CPU-only too
            st = self._spec_state.get(rid) or LookaheadState(
                self.spec_k, self.spec_cap)
            state["spec"] = np.asarray([0, 0, st.k], np.int64)
            state["spec_ema"] = np.asarray([st.ema], np.float32)
        return state

    def import_row(self, state: dict,
                   block_tokens: Optional[int] = None) -> int:
        import numpy as np

        geom = state.get("geom")
        if geom is not None:
            from kubetorch_tpu.exceptions import KVGeometryMismatch

            g = [int(x) for x in np.asarray(geom).reshape(-1)]
            exported = {"block_tokens": g[0], "max_len": g[1],
                        "lora_slots": g[2] if len(g) > 2 else 0}
            importer = {"block_tokens": (int(block_tokens)
                                         if block_tokens else g[0]),
                        "max_len": int(self.max_len),
                        "lora_slots": int(self.adapter_slots)}
            for axis in ("block_tokens", "max_len", "lora_slots"):
                if exported[axis] != importer[axis]:
                    raise KVGeometryMismatch(
                        f"cannot import row: exported geometry "
                        f"(block_tokens={exported['block_tokens']}, "
                        f"max_len={exported['max_len']}, "
                        f"lora_slots={exported['lora_slots']}) does "
                        f"not match importing engine geometry "
                        f"(block_tokens={importer['block_tokens']}, "
                        f"max_len={importer['max_len']}, "
                        f"lora_slots={importer['lora_slots']}): "
                        f"{axis} mismatch",
                        axis=axis, exported=exported, importer=importer)
        if not self._free:
            raise RuntimeError("no free row to import into")
        scalars = [int(x) for x in np.asarray(state["scalars"])]
        prompt = [int(t) for t in np.asarray(state["prompt"])]
        rid = self._next_rid
        self._next_rid += 1
        self._rows[rid] = {"rid": rid, "prompt": prompt,
                           "n": scalars[2], "emitted": scalars[1],
                           "consumed": len(prompt), "head": 0,
                           "suffix": 0, "slot": self._free.pop(0)}
        if self.spec and "spec" in state:
            k0 = int(np.asarray(state["spec"])[-1])
            ema0 = float(np.asarray(state["spec_ema"]).reshape(-1)[0])
            self._spec_state[rid] = LookaheadState(
                self.spec_k, self.spec_cap, k0=k0 or None, ema0=ema0)
        return rid

    def evict(self, rid: int) -> bool:
        for i, req in enumerate(self._queue):
            if req["rid"] == rid:
                self._queue.pop(i)
                return True
        req = self._prefilling.pop(rid, None) or self._rows.pop(rid, None)
        if req is None:
            return False
        self._spec_state.pop(rid, None)
        self._free.append(req["slot"])
        return True

    # ------------------------------------------------------------ state
    @property
    def pending(self) -> int:
        return len(self._queue) + len(self._rows) + len(self._prefilling)

    @property
    def queued(self) -> int:
        return len(self._queue)

    @property
    def free_rows(self) -> int:
        return len(self._free)

    @property
    def active_rows(self) -> int:
        return len(self._rows)

    @property
    def prefilling_rows(self) -> int:
        return len(self._prefilling)
