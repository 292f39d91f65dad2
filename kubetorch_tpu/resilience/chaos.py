"""Deterministic, seedable fault injection for the recovery path.

The recovery code (liveness, drain, emergency checkpoint, gang restart)
is exactly the code that never runs in a healthy deployment — so it must
be *driven* in tests and benches, not waited for. ``ChaosPolicy`` is the
driver: a seeded policy decides, reproducibly, which worker dies, which
connection drops, where latency lands, and which heartbeat arrives
corrupted.

Determinism contract: every decision is a pure function of
``(seed, kind, context, n)`` where ``n`` counts prior draws for that
``(kind, context)`` pair — the draw is a SHA-256 hash, not a shared RNG
stream, so concurrent injection points cannot perturb each other's
sequences and a test that kills "the worker the policy picks" kills the
same worker on every run and every machine.

Injection points:

- ``tests/fake_k8s.py`` — ``fake.chaos = ChaosPolicy(...)``: the pod
  lifecycle tick fails Running pods the policy selects (spot preemption
  without a cluster);
- ``serving/channel.py`` — drop-connection / inject-latency on the
  pipelined call channel (reconnect + ``ChannelInterrupted`` coverage);
- the pod heartbeat loop — corrupt-heartbeat (controller-side rejection
  counters);
- benches — ``KT_CHAOS="kill-worker=1,seed=42"`` activates a policy via
  :func:`active` without code changes.
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from kubetorch_tpu.config import env_str

ENV = "KT_CHAOS"

# canonical fault kinds (dashed, as they appear in KT_CHAOS=)
KILL_WORKER = "kill-worker"
DROP_CONNECTION = "drop-connection"
INJECT_LATENCY = "inject-latency"
CORRUPT_HEARTBEAT = "corrupt-heartbeat"
# partition: sever the call-channel WebSocket mid-stream (the frame being
# delivered is lost WITH the connection — the replay path must resume
# from the client's ack cursor, not token zero). Injected in the channel
# client's frame-receive path; ``max_events=N`` makes it "N partitions".
PARTITION = "partition"
# slow-pod: inject queue delay on the pod server before dispatch —
# drives admission control (queue-delay shedding) and the drain-timeout
# bound under a pod that is alive but drowning.
SLOW_POD = "slow-pod"
# controller-kill: kill the CONTROL plane mid-flight (ISSUE 15). The
# data plane must not notice; the harness
# (tests/test_controller_crash.py, tests/fleet_sim.py) draws the kill
# moment from the policy so "when the controller dies" is seeded and
# reproducible.
CONTROLLER_KILL = "controller-kill"
# ws-flap: sever the pod↔controller WebSocket (the liveness/telemetry
# channel, NOT the data-plane call channel) — drives the reconnect
# loop's full-jitter backoff, the POST heartbeat fallback, the bounded
# telemetry backlog, and the controller's idempotent re-registration.
# Injected in the pod's heartbeat notify path.
WS_FLAP = "ws-flap"
# handoff-drop: a decode pod dies mid-handoff (ISSUE 17) — the prefill
# pod's exported row never imports on the paired pod. Injected in the
# decode-side handoff await (DecodeEngine._await_handoff), keyed by
# handoff id: the first paired pod raises typed-retryable, and the
# caller re-routes the import to another decode pod (the blob is still
# in the store) or falls back to monolithic same-pod decode.
HANDOFF_DROP = "handoff-drop"
# scale-storm: a seeded offered-load spike mid-trace (ISSUE 20) — the
# fleet simulator multiplies its arrival rate while the policy says the
# storm is on, driving the scaler's ramp/cooldown machinery through a
# burst it did not forecast. Keyed by trace-tick context so the storm
# window is reproducible.
SCALE_STORM = "scale-storm"
# pod-lag: a slow-provisioning replica — the scaler asked for a pod and
# the backend takes much longer than the modeled cold start to deliver
# it. Drawn per new pod name; drives the cold-start-budget guard (no
# repeated scale-ups while replicas are still warming).
POD_LAG = "pod-lag"
KINDS = (KILL_WORKER, DROP_CONNECTION, INJECT_LATENCY, CORRUPT_HEARTBEAT,
         PARTITION, SLOW_POD, CONTROLLER_KILL, WS_FLAP, HANDOFF_DROP,
         SCALE_STORM, POD_LAG)


class ChaosPolicy:
    """Seeded fault-injection policy. Rates are per-draw probabilities in
    [0, 1]; ``max_events`` caps the total number of injected faults (a
    policy that should kill exactly one worker uses ``max_events=1``).

    >>> policy = ChaosPolicy(seed=42, kill_worker=1.0, max_events=1)
    >>> policy.pick(KILL_WORKER, ["pod-0", "pod-1", "pod-2"])
    ... # same pod for seed=42, forever
    """

    def __init__(self, seed: int = 0, *, kill_worker: float = 0.0,
                 drop_connection: float = 0.0, inject_latency: float = 0.0,
                 corrupt_heartbeat: float = 0.0, partition: float = 0.0,
                 slow_pod: float = 0.0, controller_kill: float = 0.0,
                 ws_flap: float = 0.0, handoff_drop: float = 0.0,
                 scale_storm: float = 0.0, pod_lag: float = 0.0,
                 latency_s: float = 0.05,
                 max_events: Optional[int] = None):
        self.seed = int(seed)
        self.rates: Dict[str, float] = {
            KILL_WORKER: float(kill_worker),
            DROP_CONNECTION: float(drop_connection),
            INJECT_LATENCY: float(inject_latency),
            CORRUPT_HEARTBEAT: float(corrupt_heartbeat),
            PARTITION: float(partition),
            SLOW_POD: float(slow_pod),
            CONTROLLER_KILL: float(controller_kill),
            WS_FLAP: float(ws_flap),
            HANDOFF_DROP: float(handoff_drop),
            SCALE_STORM: float(scale_storm),
            POD_LAG: float(pod_lag),
        }
        self.latency_s = float(latency_s)
        self.max_events = max_events
        self.events: List[Tuple[str, str]] = []  # injected (kind, context)
        self._draws: Dict[Tuple[str, str], int] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------ draws
    def _uniform(self, kind: str, context: str, n: int) -> float:
        digest = hashlib.sha256(
            f"{self.seed}:{kind}:{context}:{n}".encode()).digest()
        return int.from_bytes(digest[:8], "big") / float(1 << 64)

    def decide(self, kind: str, context: str = "") -> bool:
        """One reproducible draw: inject this fault here, now?"""
        rate = self.rates.get(kind, 0.0)
        if rate <= 0.0:
            return False
        with self._lock:
            if (self.max_events is not None
                    and len(self.events) >= self.max_events):
                return False
            n = self._draws.get((kind, context), 0)
            self._draws[(kind, context)] = n + 1
            hit = rate >= 1.0 or self._uniform(kind, context, n) < rate
            if hit:
                self.events.append((kind, context))
            return hit

    def pick(self, kind: str, candidates: Sequence[str]) -> Optional[str]:
        """Deterministically select ONE candidate (the victim): the
        candidate whose hash draw is smallest. Independent of candidate
        order and of any other draws — "which worker dies" is a pure
        function of the seed and the candidate set."""
        if not candidates:
            return None
        return min(sorted(candidates),
                   key=lambda c: self._uniform(kind, c, -1))

    def latency(self) -> float:
        return self.latency_s

    def maybe_sleep(self, context: str = "") -> float:
        """Inject latency if the policy says so; returns the slept time."""
        if self.decide(INJECT_LATENCY, context):
            time.sleep(self.latency_s)
            return self.latency_s
        return 0.0

    # ------------------------------------------------------------- env
    @classmethod
    def from_env(cls, value: Optional[str] = None) -> Optional["ChaosPolicy"]:
        """Parse ``KT_CHAOS`` (or an explicit string):
        ``"kill-worker=1,drop-connection=0.3,seed=42,latency=0.01,max=3"``.
        A bare kind name means rate 1.0. Returns None when unset/empty."""
        raw = value if value is not None else env_str(ENV)
        raw = (raw or "").strip()
        if not raw:
            return None
        kwargs: Dict[str, float] = {}
        seed, latency_s, max_events = 0, 0.05, None
        for clause in filter(None, (c.strip() for c in raw.split(","))):
            key, _, val = clause.partition("=")
            key = key.strip().lower()
            try:
                num = float(val) if val else 1.0
            except ValueError:
                continue
            if key == "seed":
                seed = int(num)
            elif key in ("latency", "latency_s"):
                latency_s = num
            elif key in ("max", "max_events"):
                max_events = int(num)
            elif key.replace("_", "-") in KINDS:
                kwargs[key.replace("-", "_")] = num
        return cls(seed=seed, latency_s=latency_s, max_events=max_events,
                   **kwargs)


# ---------------------------------------------------------------- ambient
# Process-level active policy: injection points call ``active()`` (lazy
# KT_CHAOS parse, cached) or ``maybe(kind, ctx)``; ``install()`` overrides
# for tests. All no-ops when chaos is off — the hot path pays one None
# check.
_active: Optional[ChaosPolicy] = None
_parsed_env: Optional[str] = None
_lock = threading.Lock()


def install(policy: Optional[ChaosPolicy]) -> Optional[ChaosPolicy]:
    """Set (or clear, with None) the process's active chaos policy."""
    global _active, _parsed_env
    with _lock:
        _active = policy
        _parsed_env = env_str(ENV)
    return policy


def active() -> Optional[ChaosPolicy]:
    """The process's active policy: installed one, else lazily parsed
    from ``KT_CHAOS`` (re-parsed when the env var changes, so tests can
    monkeypatch it)."""
    global _active, _parsed_env
    env = env_str(ENV)
    with _lock:
        if env != _parsed_env:
            _active = ChaosPolicy.from_env(env)
            _parsed_env = env
        return _active


def maybe(kind: str, context: str = "") -> bool:
    """``active().decide(...)`` with the no-policy fast path."""
    policy = active()
    return policy.decide(kind, context) if policy is not None else False
