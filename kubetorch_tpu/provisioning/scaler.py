"""Controller-side fleet autoscaler: the loop that finally closes
ROADMAP item 4 ("orchestrator" in the title, nothing in-repo ever
changed a replica count).

Every sensor and actuator already exists — this module only wires the
loop. Signals come from the controller's fleet store rollups (queue
depth, row occupancy, KV-block pressure — PR 13) and the SLO burn-rate
engine; policy is Gavel-style per-tier sizing (a disaggregated service's
prefill and decode tiers are sized independently off ``engine_phase``)
with hysteresis + cooldown flap guards; actuation goes through the
provisioning backend's ``scale`` (a K8s Deployment replica merge-patch,
or the LocalBackend's in-place subprocess resize — the loop is
e2e-testable without a cluster).

Crash safety follows the PR 15 discipline: desired counts, cooldown /
settle deadlines, and manual overrides live in durable controller-DB
rows, every actuated decision is an append-only ``scale_decisions`` row,
and a restarted controller resumes mid-cooldown instead of re-deriving a
fresh opinion and flapping the fleet (``tests/test_fleet_smoke.py``
asserts zero spurious decisions across a seeded mid-ramp controller
kill).

Guard order per service, checked before any actuation:

1. rejoin quarantine active → the controller is looking at restored
   state, not a measured fleet; scaling on it is the restart storm the
   quarantine exists to prevent;
2. restart-budget backoff active → the resilience layer owns this gang
   right now; resizing would race the pending gang restart;
3. manual override row present → the operator pinned the count
   (``ktpu scale <svc> <n>``); the scaler enforces the pin until
   ``ktpu scale <svc> --auto`` clears it;
4. cold-start settle window open and replicas still warming → no
   repeated scale-ups while the last one is provisioning+restoring;
5. scale-down cooldown / direction-reversal window → no flaps.
"""

from __future__ import annotations

import contextvars
import logging
import math
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from kubetorch_tpu.config import env_float
from kubetorch_tpu.provisioning.autoscaling import AutoscalingConfig

logger = logging.getLogger(__name__)

UP, DOWN = 1, -1

_CFG_FIELDS = ("target", "metric", "window", "min_scale", "max_scale",
               "initial_scale", "scale_to_zero_grace",
               "container_concurrency")


def autoscaling_from_pool(pool: Dict[str, Any]) -> Optional[AutoscalingConfig]:
    """The pool row's ``compute`` JSON carries the deploy-time
    ``Compute.autoscale(...)`` dict; None when the service never asked
    for autoscaling (the scaler then leaves it alone unless an operator
    override pins it)."""
    raw = ((pool or {}).get("compute") or {}).get("autoscaling")
    if not isinstance(raw, dict):
        return None
    try:
        return AutoscalingConfig(
            **{k: raw[k] for k in _CFG_FIELDS if k in raw})
    except (TypeError, ValueError):
        return None


def _duration_s(value: Optional[str]) -> Optional[float]:
    """'30m' / '2h' / '45s' → seconds (the pool-TTL grammar)."""
    if not value:
        return None
    m = re.fullmatch(r"(\d+(?:\.\d+)?)([smhd]?)", str(value).strip())
    if not m:
        return None
    return float(m.group(1)) * {"": 1, "s": 1, "m": 60, "h": 3600,
                                "d": 86400}[m.group(2)]


class FleetScaler:
    """One controller-resident scale loop over every managed service.

    ``tick()`` is synchronous and cheap apart from actuation; the
    controller runs it from the resilience sweep in an executor and
    passes ``actuate_in_thread=True`` so a slow backend (LocalBackend
    waits for pod readiness) never stalls the sweep cadence. The
    virtual-time fleet simulator (``tests/fleet_sim.py``) passes a
    ``clock`` and a sim backend and keeps actuation inline — every
    decision is then a pure function of the trace."""

    def __init__(self, db, fleet, *, slo=None, restart_policy=None,
                 grace_remaining: Optional[Callable[[], float]] = None,
                 backend_for: Optional[Callable[[Optional[str]], Any]] = None,
                 on_event: Optional[Callable[[str, str, str], None]] = None,
                 clock: Callable[[], float] = time.time,
                 actuate_in_thread: bool = False,
                 target_occupancy: Optional[float] = None,
                 hysteresis: Optional[float] = None,
                 cooldown_s: Optional[float] = None,
                 cold_start_budget_s: Optional[float] = None,
                 eval_window_s: Optional[float] = None):
        self.db = db
        self.fleet = fleet
        self.slo = slo
        self.restart_policy = restart_policy
        self._grace_remaining = grace_remaining
        self._backend_for = backend_for
        self.on_event = on_event
        self._now = clock
        self.actuate_in_thread = actuate_in_thread
        self.target_occupancy = (
            target_occupancy if target_occupancy is not None
            else env_float("KT_SCALE_TARGET_OCCUPANCY"))
        self.hysteresis = (hysteresis if hysteresis is not None
                          else env_float("KT_SCALE_HYSTERESIS"))
        self.cooldown_s = (cooldown_s if cooldown_s is not None
                           else env_float("KT_SCALE_COOLDOWN_S"))
        self.cold_start_budget_s = (
            cold_start_budget_s if cold_start_budget_s is not None
            else env_float("KT_SCALE_COLD_START_BUDGET_S"))
        self.eval_window_s = (eval_window_s if eval_window_s is not None
                              else env_float("KT_SCALE_EVAL_WINDOW_S"))
        # runtime state (all durable pieces mirrored in scaler_state /
        # scale_overrides rows; the rest is re-derivable)
        self._desired: Dict[str, int] = {}
        self._actual: Dict[str, int] = {}
        self._cooldown_until: Dict[str, float] = {}
        self._settle_until: Dict[str, float] = {}
        self._last_direction: Dict[str, int] = {}
        self._last_decision_ts: Dict[str, float] = {}
        self._idle_since: Dict[str, float] = {}
        self._pending_up: Dict[str, tuple] = {}   # svc -> (t0, target)
        self._overrides: Dict[str, int] = {}
        self._actuating: set = set()
        self._lock = threading.Lock()
        # counters (joined to the controller /metrics scrape)
        self.decisions_total = 0
        self.scale_ups_total = 0
        self.scale_downs_total = 0
        self.flaps_total = 0
        self.blocked_total = 0
        self.reconciles_total = 0
        self.cold_starts_total = 0
        self.cold_starts_over_budget_total = 0
        self.last_cold_start_s: Dict[str, float] = {}
        self.last_reason: Dict[str, str] = {}
        self._restore()

    # ----------------------------------------------------- durability
    def _restore(self) -> None:
        now = self._now()
        try:
            states = self.db.load_scaler_states()
            overrides = dict(self.db.load_scale_overrides())
        except Exception as exc:  # noqa: BLE001 — a fresh DB has no rows
            logger.debug("scaler state restore failed: %r", exc)
            return
        with self._lock:
            self._overrides = overrides
        for service, row in states.items():
            self._desired[service] = int(row.get("desired") or 0)
            for key, store in (("cooldown_until", self._cooldown_until),
                               ("settle_until", self._settle_until)):
                until = row.get(key)
                if until and float(until) > now:
                    store[service] = float(until)
            self._last_direction[service] = int(
                row.get("last_direction") or 0)
            self.last_reason[service] = row.get("last_reason") or ""
            settle = self._settle_until.get(service)
            if settle is not None:
                # killed mid-warm-up: keep charging the in-flight
                # scale-up against the same budget window
                self._pending_up[service] = (
                    settle - self.cold_start_budget_s,
                    self._desired[service])
        # flap-guard clock: the append-only decision log is the durable
        # record of when each service last decided — without it a
        # restarted controller would treat a fresh reversal as
        # guard-free and flap where the old one would have held
        try:
            recent = self.db.load_scale_decisions(limit=1000)
        except Exception as exc:  # noqa: BLE001
            logger.debug("scale decision restore failed: %r", exc)
            recent = []
        for d in recent:   # newest-first: first hit per service wins
            svc = d.get("service")
            if svc and svc not in self._last_decision_ts:
                self._last_decision_ts[svc] = float(d.get("ts") or 0.0)

    def _persist(self, service: str) -> None:
        try:
            self.db.save_scaler_state(
                service, self._desired.get(service, 0),
                cooldown_until=self._cooldown_until.get(service),
                settle_until=self._settle_until.get(service),
                last_direction=self._last_direction.get(service, 0),
                last_reason=self.last_reason.get(service, ""))
        except Exception as exc:  # noqa: BLE001 — durability is best-effort
            logger.debug("scaler persist for %s failed: %r", service, exc)

    # -------------------------------------------------------- signals
    def signals(self, service: str) -> Dict[str, Any]:
        """Fleet-rolled scaling inputs over the eval window: live pods
        partitioned by serving tier (``engine_phase``), per-tier demand
        (decoding rows + queued programs) and row capacity, plus the
        fleet-wide KV-block pressure fraction."""
        rollup = self.fleet.fleet(service, window_s=self.eval_window_s)
        gauges = rollup.get("gauges") or {}
        pods_meta = rollup.get("pods") or {}

        def by_pod(name: str) -> Dict[str, float]:
            return (gauges.get(name) or {}).get("by_pod") or {}

        phase = by_pod("engine_phase")
        active = by_pod("engine_active_rows")
        free = by_pod("engine_free_rows")
        queue = by_pod("engine_queue_depth")
        kv_used = by_pod("kv_blocks_used")
        kv_free = by_pod("kv_blocks_free")
        live = sorted(p for p, m in pods_meta.items()
                      if not m.get("stale"))
        tiers: Dict[str, Dict[str, Any]] = {}
        for pod in live:
            label = {0: "prefill", 1: "decode"}.get(phase.get(pod), "mixed")
            tier = tiers.setdefault(
                label, {"pods": [], "demand": 0.0, "rows": 0.0})
            tier["pods"].append(pod)
            tier["demand"] += (float(active.get(pod, 0.0))
                               + float(queue.get(pod, 0.0)))
            tier["rows"] += (float(active.get(pod, 0.0))
                             + float(free.get(pod, 0.0)))
        ku = sum(float(kv_used.get(p, 0.0)) for p in live)
        kf = sum(float(kv_free.get(p, 0.0)) for p in live)
        return {
            "live": live,
            "tiers": tiers,
            "demand": sum(t["demand"] for t in tiers.values()),
            "capacity_rows": sum(t["rows"] for t in tiers.values()),
            "kv_pressure": ku / (ku + kf) if (ku + kf) > 0 else None,
        }

    def _desired_from_signals(self, sig: Dict[str, Any],
                              current: int) -> Optional[tuple]:
        """(raw desired, reason) from the rollup, or None when nothing
        is observable (no live pods — the caller falls back to the
        recorded/initial count). Tiers size independently (Gavel-style
        heterogeneity: a prefill tier's backlog must not buy decode
        replicas) and sum into the service's replica count."""
        tiers = sig["tiers"]
        if not tiers:
            return None
        desired = 0
        parts = []
        for label in sorted(tiers):
            tier = tiers[label]
            cap = tier["rows"] / max(1, len(tier["pods"]))
            if cap <= 0:
                cap = 1.0
            want = math.ceil(
                tier["demand"] / (cap * self.target_occupancy))
            if tier["demand"] > 0:
                want = max(1, want)
            desired += want
            parts.append(f"{label}={want}")
        reason = (f"occupancy {sig['demand']:g} rows over "
                  f"{sig['capacity_rows']:g} capacity "
                  f"({', '.join(parts)})")
        # pressure signals ride on top of the occupancy plan: they can
        # only ADD a replica, never remove one
        kv = sig.get("kv_pressure")
        if kv is not None and kv > self.target_occupancy:
            desired = max(desired, current + 1)
            reason = f"kv-pressure {kv:.2f} > {self.target_occupancy:g}"
        if self.slo is not None:
            try:
                breached = [o.get("name") for o in self.slo.status(None)
                            if o.get("breached")
                            and o.get("service") == sig.get("service")]
            except Exception:  # noqa: BLE001 — advisory signal only
                breached = []
            if breached:
                desired = max(desired, current + 1)
                reason = f"slo-burn {','.join(str(b) for b in breached)}"
        return desired, reason

    # ----------------------------------------------------------- tick
    def tick(self, pools: Optional[List[Dict[str, Any]]] = None,
             actuals: Optional[Dict[str, int]] = None) -> List[dict]:
        """One pass over every managed service; returns the actuated
        decisions. ``actuals`` overrides the observed replica count per
        service (the sim backend knows; production reads non-stale
        fleet pods)."""
        if pools is None:
            pools = self.db.list_pools()
        decisions = []
        for pool in pools:
            service = pool.get("service_name")
            if not service:
                continue
            cfg = autoscaling_from_pool(pool)
            override = self._overrides.get(service)
            if cfg is None and override is None:
                continue  # not managed, not pinned
            decision = self._tick_service(
                service, pool, cfg, override,
                actual=(actuals or {}).get(service))
            if decision is not None:
                decisions.append(decision)
        return decisions

    def _tick_service(self, service: str, pool: Dict[str, Any],
                      cfg: Optional[AutoscalingConfig],
                      override: Optional[int],
                      actual: Optional[int] = None) -> Optional[dict]:
        now = self._now()
        sig = self.signals(service)
        sig["service"] = service
        if actual is None:
            actual = len(sig["live"])
        self._actual[service] = actual
        self._note_cold_start(service, actual, now)
        if service in self._actuating:
            return None  # an actuation is already in flight
        current = self._desired.get(service)
        if current is None:
            current = actual

        if override is not None:
            target, reason, kind = override, "manual override", "override"
        else:
            computed = self._desired_from_signals(sig, current)
            if computed is None:
                raw = current if current > 0 else (
                    cfg.initial_scale if cfg.initial_scale is not None
                    else cfg.min_scale)
                reason = ("initial-scale" if current <= 0
                          else "no telemetry; holding")
            else:
                raw, reason = computed
            kind = "auto"
            # idle tracking for scale-to-zero grace
            if sig["demand"] <= 0 and sig["tiers"]:
                self._idle_since.setdefault(service, now)
            elif sig["demand"] > 0:
                self._idle_since.pop(service, None)
            target = self._clamp(raw, cfg)
            if target == 0 and current > 0:
                grace = (_duration_s(cfg.scale_to_zero_grace)
                         if cfg.scale_to_zero_grace else self.cooldown_s)
                idle = now - self._idle_since.get(service, now)
                if cfg.min_scale > 0 or idle < (grace or 0.0):
                    # the last replica is reaped only after the grace:
                    # a between-bursts lull must not cold-start the
                    # next burst
                    target = max(1, cfg.min_scale)
                    reason = (f"idle {idle:.0f}s < scale-to-zero grace "
                              f"{grace:g}s; holding last replica")
                    # surface the hold in status() even though no
                    # decision is minted while target == current
                    self.last_reason[service] = reason
                else:
                    reason = (f"idle {idle:.0f}s >= grace {grace:g}s; "
                              f"scale to zero")
            if target != current and not self._outside_deadband(
                    sig, current, target):
                return None  # inside the hysteresis band: hold

        if target == current:
            self._maybe_reconcile(service, pool, current, actual, now)
            return None
        blocked = self._blocked(service, current, target, now,
                                is_override=override is not None)
        if blocked:
            self.blocked_total += 1
            self.last_reason[service] = f"blocked: {blocked}"
            return None
        return self._actuate(service, pool, current, target, reason,
                             kind, now)

    def _clamp(self, raw: int, cfg: Optional[AutoscalingConfig]) -> int:
        raw = max(0, int(raw))
        if cfg is None:
            return raw
        raw = max(raw, cfg.min_scale)
        if cfg.max_scale > 0:
            raw = min(raw, cfg.max_scale)
        return raw

    def _outside_deadband(self, sig: Dict[str, Any], current: int,
                          target: int) -> bool:
        """Hysteresis: near the setpoint, hold. Scale-from-zero and
        scale-to-zero always pass — the deadband is an occupancy notion
        and needs a running fleet on both sides."""
        if current <= 0 or target <= 0:
            return True
        cap = sig["capacity_rows"]
        if cap <= 0:
            return True
        occupancy = sig["demand"] / cap
        if target > current:
            return occupancy > self.target_occupancy * (1 + self.hysteresis)
        return occupancy < self.target_occupancy * (1 - self.hysteresis)

    def _blocked(self, service: str, current: int, target: int,
                 now: float, is_override: bool) -> Optional[str]:
        if self._grace_remaining is not None:
            grace = self._grace_remaining()
            if grace > 0:
                return f"rejoin quarantine ({grace:.1f}s left)"
        if self.restart_policy is not None:
            backoff = self.restart_policy.backoff_remaining(service, now)
            if backoff > 0:
                return f"restart backoff ({backoff:.1f}s left)"
        if is_override:
            return None  # operator pins skip the flap guards
        direction = UP if target > current else DOWN
        if direction == DOWN and now < self._cooldown_until.get(
                service, 0.0):
            return (f"scale-down cooldown "
                    f"({self._cooldown_until[service] - now:.1f}s left)")
        last_dir = self._last_direction.get(service, 0)
        if (last_dir and direction != last_dir
                and now - self._last_decision_ts.get(service, 0.0)
                < self.cooldown_s):
            return "direction reversal inside cooldown (flap guard)"
        if (direction == UP and now < self._settle_until.get(service, 0.0)
                and self._actual.get(service, 0) < current):
            return "cold-start budget open; replicas still warming"
        return None

    def _actuate(self, service: str, pool: Dict[str, Any], current: int,
                 target: int, reason: str, kind: str,
                 now: float) -> Optional[dict]:
        direction = UP if target > current else DOWN
        last_dir = self._last_direction.get(service, 0)
        if (last_dir and direction != last_dir
                and now - self._last_decision_ts.get(service, 0.0)
                < self.cooldown_s):
            # only overrides can reach here (the guard stops auto
            # decisions); count the flap so the fleet test's zero-flap
            # floor is a count, not an assumption
            self.flaps_total += 1
        self._desired[service] = target
        self._last_direction[service] = direction
        self._last_decision_ts[service] = now
        self.last_reason[service] = reason
        if direction == DOWN:
            self._cooldown_until[service] = now + self.cooldown_s
        else:
            self._settle_until[service] = now + self.cold_start_budget_s
            self._pending_up[service] = (now, target)
        self.decisions_total += 1
        if direction == UP:
            self.scale_ups_total += 1
        else:
            self.scale_downs_total += 1
        # durable intent BEFORE the backend call: a controller killed
        # mid-actuation restores the decision and reconciles, instead
        # of re-deciding (and double-counting) it
        try:
            self.db.record_scale_decision(service, current, target,
                                          reason, kind=kind, ts=now)
        except Exception as exc:  # noqa: BLE001
            logger.debug("scale decision persist for %s failed: %r",
                         service, exc)
        self._persist(service)
        self._event(service, "ScaleUp" if direction == UP else "ScaleDown",
                    f"{current} -> {target} replicas ({kind}): {reason}")
        self._run_backend_scale(service, pool, target)
        return {"service": service, "from": current, "to": target,
                "reason": reason, "kind": kind, "ts": now}

    def _maybe_reconcile(self, service: str, pool: Dict[str, Any],
                         desired: int, actual: int, now: float) -> None:
        """Desired == recorded but the fleet drifted (an actuation the
        previous controller incarnation never finished, a pod the
        backend lost): re-issue the backend call without minting a new
        decision — reconciliation is idempotent enforcement of the
        recorded intent, not a scale event."""
        if actual == desired or service in self._actuating:
            return
        if now < self._settle_until.get(service, 0.0):
            return  # still inside the cold-start budget: let it warm
        self.reconciles_total += 1
        self._run_backend_scale(service, pool, desired)

    def _run_backend_scale(self, service: str, pool: Dict[str, Any],
                           target: int) -> None:
        backend_name = (pool or {}).get("backend") or None
        self._actuating.add(service)

        def call():
            try:
                backend = self._backend(backend_name)
                backend.scale(service, target)
            except Exception as exc:  # noqa: BLE001 — surfaced as an event,
                self._event(service, "ScaleFailed",   # never a crashed tick
                            f"backend scale to {target} failed: "
                            f"{type(exc).__name__}: {exc}")
            finally:
                self._actuating.discard(service)

        if self.actuate_in_thread:
            threading.Thread(target=contextvars.copy_context().run,
                             args=(call,), daemon=True,
                             name=f"kt-scale-{service}").start()
        else:
            call()

    def _backend(self, name: Optional[str]):
        if self._backend_for is not None:
            return self._backend_for(name)
        from kubetorch_tpu.provisioning.backend import get_backend

        return get_backend(name)

    def _note_cold_start(self, service: str, actual: int,
                         now: float) -> None:
        pending = self._pending_up.get(service)
        if pending is None:
            return
        t0, target = pending
        if actual >= target:
            wall = now - t0
            self._pending_up.pop(service, None)
            self._settle_until.pop(service, None)
            self.cold_starts_total += 1
            self.last_cold_start_s[service] = wall
            if wall > self.cold_start_budget_s:
                self.cold_starts_over_budget_total += 1
                self._event(service, "ColdStartOverBudget",
                            f"scale-up settled in {wall:.1f}s "
                            f"(budget {self.cold_start_budget_s:g}s)")
            self._persist(service)
        elif now >= self._settle_until.get(service, 0.0):
            # budget elapsed with replicas still missing: stop charging
            # this scale-up (the guard lifts; a repeat decision may fire)
            self._pending_up.pop(service, None)
            self.cold_starts_over_budget_total += 1

    # ------------------------------------------------- operator surface
    def set_override(self, service: str, replicas: int,
                     pool: Optional[Dict[str, Any]] = None) -> dict:
        """Durable manual pin + immediate actuation (``ktpu scale``)."""
        replicas = max(0, int(replicas))
        with self._lock:
            self._overrides[service] = replicas
        self.db.set_scale_override(service, replicas)
        pool = pool or self.db.get_pool(service) or {}
        current = self._desired.get(
            service, self._actual.get(service, 0))
        if current == replicas:
            return {"service": service, "replicas": replicas,
                    "changed": False}
        now = self._now()
        decision = self._actuate(service, pool, current, replicas,
                                 "manual override", "override", now)
        return {"service": service, "replicas": replicas,
                "changed": decision is not None}

    def clear_override(self, service: str) -> bool:
        with self._lock:
            had = self._overrides.pop(service, None) is not None
        self.db.clear_scale_override(service)
        return had

    def request_capacity(self, service: str, n: int = 1) -> dict:
        """Router scale-from-zero hook: a routable-pod miss on a managed
        service parks the program and asks the scaler for capacity. The
        ask is idempotent — repeated parks while the cold start is in
        flight never stack decisions."""
        pool = self.db.get_pool(service)
        if pool is None:
            return {"ok": False, "error": "no such pool"}
        cfg = autoscaling_from_pool(pool)
        override = self._overrides.get(service)
        if cfg is None and override is None:
            return {"ok": False, "error": "service is not autoscaled"}
        now = self._now()
        current = self._desired.get(service, 0)
        want = self._clamp(max(int(n), 1), cfg)
        if override is not None:
            want = override
        if current >= want or want <= 0:
            return {"ok": True, "desired": max(current, want),
                    "pending": service in self._actuating
                    or service in self._pending_up,
                    "retry_after_s": self.cold_start_budget_s}
        blocked = self._blocked(service, current, want, now,
                                is_override=False)
        if blocked:
            self.blocked_total += 1
            return {"ok": False, "error": blocked}
        self._actuate(service, pool, current, want,
                      f"scale-from-zero park (want {want})",
                      "scale-from-zero", now)
        return {"ok": True, "desired": want, "pending": True,
                "retry_after_s": self.cold_start_budget_s}

    def drop(self, service: str) -> None:
        """Forget a torn-down service — memory and durable rows."""
        for store in (self._desired, self._actual, self._cooldown_until,
                      self._settle_until, self._last_direction,
                      self._last_decision_ts, self._idle_since,
                      self._pending_up, self.last_cold_start_s,
                      self.last_reason):
            store.pop(service, None)
        with self._lock:
            self._overrides.pop(service, None)
        try:
            self.db.clear_scaler_state(service)
        except Exception as exc:  # noqa: BLE001
            logger.debug("scaler durable drop for %s failed: %r",
                         service, exc)

    def status(self, service: Optional[str] = None) -> Dict[str, Any]:
        now = self._now()
        services = ([service] if service
                    else sorted(set(self._desired) | set(self._overrides)
                                | set(self._actual)))
        out = {}
        for svc in services:
            out[svc] = {
                "desired": self._desired.get(svc),
                "actual": self._actual.get(svc),
                "override": self._overrides.get(svc),
                "cooldown_remaining_s": round(max(
                    0.0, self._cooldown_until.get(svc, 0.0) - now), 3),
                "settle_remaining_s": round(max(
                    0.0, self._settle_until.get(svc, 0.0) - now), 3),
                "last_reason": self.last_reason.get(svc, ""),
                "last_cold_start_s": self.last_cold_start_s.get(svc),
            }
        return out

    def _event(self, service: str, reason: str, message: str) -> None:
        if self.on_event is None:
            return
        try:
            self.on_event(service, reason, message)
        # ktlint: disable=KT004 -- event sink contract: never break a tick
        except Exception:  # noqa: BLE001
            pass

    def prom_samples(self) -> List[tuple]:
        """(name, labels, value) rows for the controller's /metrics
        exposition — the ``scaler_*`` family."""
        now = self._now()
        samples = [
            ("scaler_decisions_total", {}, self.decisions_total),
            ("scaler_scale_ups_total", {}, self.scale_ups_total),
            ("scaler_scale_downs_total", {}, self.scale_downs_total),
            ("scaler_flaps_total", {}, self.flaps_total),
            ("scaler_blocked_total", {}, self.blocked_total),
            ("scaler_reconciles_total", {}, self.reconciles_total),
            ("scaler_cold_starts_total", {}, self.cold_starts_total),
            ("scaler_cold_starts_over_budget_total", {},
             self.cold_starts_over_budget_total),
            ("scaler_overrides_active", {}, len(self._overrides)),
        ]
        for svc in sorted(set(self._desired) | set(self._actual)):
            labels = {"service": svc}
            samples.append(("scaler_desired_replicas", labels,
                            self._desired.get(svc, 0)))
            samples.append(("scaler_actual_replicas", labels,
                            self._actual.get(svc, 0)))
            samples.append(("scaler_cooldown_remaining_s", labels, round(
                max(0.0, self._cooldown_until.get(svc, 0.0) - now), 3)))
            cold = self.last_cold_start_s.get(svc)
            if cold is not None:
                samples.append(("scaler_cold_start_seconds", labels,
                                round(cold, 4)))
        return samples
