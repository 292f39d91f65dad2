"""Virtual-time fleet simulator (test support for test_fleet_smoke.py).

The only closed loop over the REAL control plane: a file-backed
``Database`` (so the mid-trace controller kill has durable rows to
resume from), a ``FleetStore`` on an injected clock, the real
``FleetScaler`` and the real ``select_route``. Only the pods are
``SimRollingEngine`` instances behind a backend that models cold starts
(inflated for some pods by the seeded ``pod-lag`` chaos kind). No wall
clock, no sleeps: every component reads ``SimClock.now``, so ten
simulated minutes cost seconds and the outcome is the same on any host.

What a run returns is COUNTS (decisions, replicas, programs, cold-start
seconds of the virtual clock against the budget). It gives no rate: a
CPU or sim run never does (ROADMAP aim 1).
"""

from __future__ import annotations

import os
import random
from typing import Dict, List

from kubetorch_tpu.controller.db import Database
from kubetorch_tpu.controller.router import select_route
from kubetorch_tpu.observability.fleetstore import FleetStore
from kubetorch_tpu.provisioning.scaler import FleetScaler
from kubetorch_tpu.resilience.chaos import POD_LAG, SCALE_STORM, ChaosPolicy
from kubetorch_tpu.serving.engine import SimRollingEngine

SVC = "fleet-svc"
SLOTS = 8
STEPS_PER_CALL = 8
MAX_NEW = 32
COLD_START_S = 8.0
COLD_START_BUDGET_S = 30.0
LAG_FACTOR = 2.5           # a pod-lag hit: this many cold starts long
COOLDOWN_S = 30.0
DURATION_S = 600           # the tracking trace, in one-second ticks
KILL_AT_S = 280.0
CHAOS_SEED = 13
ROUTED_PROGRAMS = 300
FIRST_TOKEN_LIMIT_S = 5.0
POD_SPEEDS = (2, 2, 1, 1)  # decode steps a virtual second


class SimClock:
    """The fleet's only notion of time; every component gets ``now``."""

    def __init__(self, t0: float = 1_700_000_000.0):
        self.t = t0

    def now(self) -> float:
        return self.t


class SimPod:
    def __init__(self, name: str, ready_at: float = 0.0):
        self.name = name
        self.ready_at = ready_at
        self.eng = SimRollingEngine(max_slots=SLOTS,
                                    steps_per_call=STEPS_PER_CALL,
                                    step_s=0.0)
        self.rid2idx: Dict[int, int] = {}


class SimFleetBackend:
    """The provisioning backend the scaler actuates against: a pod
    becomes ready ``COLD_START_S`` of virtual time after the scale call
    (``pod-lag`` chaos inflates individual pods). Reaping prefers idle
    pods; programs on a reaped busy pod are returned for resubmission
    (the drain the real backends do)."""

    name = "sim"

    def __init__(self, clock: SimClock, policy: ChaosPolicy):
        self.clock = clock
        self.policy = policy
        self.pods: List[SimPod] = []
        self.cold_starts: List[float] = []   # virtual seconds to ready
        self.lagged_pods = 0
        self.lost_programs: List[int] = []
        self._counter = 0

    def scale(self, service: str, replicas: int) -> dict:
        replicas = max(0, int(replicas))
        while len(self.pods) > replicas:
            victim = min(self.pods, key=lambda p: (p.eng.pending, p.name))
            self.pods.remove(victim)
            self.lost_programs.extend(victim.rid2idx.values())
        now = self.clock.now()
        while len(self.pods) < replicas:
            name = f"{service}-{self._counter}"
            self._counter += 1
            cold = COLD_START_S
            if self.policy.decide(POD_LAG, name):
                cold *= LAG_FACTOR
                self.lagged_pods += 1
            self.pods.append(SimPod(name, now + cold))
            self.cold_starts.append(cold)
        return {"replicas": replicas}

    def ready_pods(self) -> List[SimPod]:
        now = self.clock.now()
        return [p for p in self.pods if p.ready_at <= now]


def _prompt(idx: int) -> List[int]:
    return [300 + idx] + [7] * 15


def _offered_load(policy: ChaosPolicy, t: float) -> float:
    """Programs a second at virtual time ``t``: ramp from 0.5 to 8 over
    200 s, plateau to 400 s, ramp down by 480 s, then idle. A seeded
    ``scale-storm`` triples a 20 s block, except around the controller
    kill, so that what follows the kill shows the RESUME and not a
    burst that happened to coincide."""
    if t < 200.0:
        lam = 0.5 + 7.5 * (t / 200.0)
    elif t < 400.0:
        lam = 8.0
    elif t < 480.0:
        lam = 8.0 * (480.0 - t) / 80.0
    else:
        return 0.0
    in_guard = KILL_AT_S - 20.0 <= t <= KILL_AT_S + 40.0
    if not in_guard and policy.decide(SCALE_STORM, f"block-{int(t // 20.0)}"):
        lam *= 3.0
    return lam


def diurnal_arrivals() -> List[float]:
    """Seeded non-homogeneous Poisson arrivals by thinning: candidates
    at ``lam_max`` (above a tripled plateau), accepted with probability
    ``lam(t) / lam_max``."""
    lam_max = 25.0
    policy = ChaosPolicy(seed=CHAOS_SEED, scale_storm=0.15, pod_lag=0.3)
    rnd = random.Random(CHAOS_SEED)
    out, t = [], 0.0
    while True:
        t += rnd.expovariate(lam_max)
        if t >= DURATION_S:
            return out
        if rnd.random() < _offered_load(policy, t) / lam_max:
            out.append(t)


def run_tracking(arrivals: List[float], db_dir: str, kill: bool) -> dict:
    """One pass of the scaler over ``arrivals``, from zero replicas and
    back to zero. ``kill=True`` throws the scaler and its database
    handle away at ``KILL_AT_S`` and rebuilds both from the durable
    rows; ``kill=False`` is the control. A faithful resume makes the
    two runs' decision logs EQUAL."""
    clock = SimClock()
    t_base = clock.now()
    # a policy of its own for each run: decide() keeps a draw counter a
    # context, so a shared one would let one run's pod-lag draws shift
    # the other's
    backend = SimFleetBackend(
        clock, ChaosPolicy(seed=CHAOS_SEED, scale_storm=0.15, pod_lag=0.3))
    fleet = FleetStore(stale_after_s=5.0, clock=clock.now)
    db_path = os.path.join(db_dir, f"controller-{int(kill)}.db")
    db = Database(db_path)
    db.upsert_pool(SVC, namespace="default", backend="sim",
                   compute={"autoscaling": {
                       "min_scale": 0, "max_scale": 8, "initial_scale": 0,
                       "metric": "concurrency",
                       "scale_to_zero_grace": "40s"}})

    def mk_scaler(database):
        return FleetScaler(
            database, fleet, backend_for=lambda name: backend,
            clock=clock.now, target_occupancy=0.75, hysteresis=0.1,
            cooldown_s=COOLDOWN_S, cold_start_budget_s=COLD_START_BUDGET_S,
            eval_window_s=10.0)

    scaler = mk_scaler(db)
    flaps = 0
    next_arrival = 0
    backlog: List[int] = []
    parked = 0
    peak = 0
    killed = False
    decisions_at_kill = 0
    scaled_to_zero = False

    for t in range(DURATION_S):
        clock.t = t_base + t
        if kill and not killed and t >= KILL_AT_S:
            killed = True
            decisions_at_kill = len(db.load_scale_decisions(
                SVC, limit=100000))
            db = Database(db_path)
            flaps += scaler.flaps_total
            scaler = mk_scaler(db)

        while (next_arrival < len(arrivals)
               and arrivals[next_arrival] <= t):
            backlog.append(next_arrival)
            next_arrival += 1
        backlog.extend(backend.lost_programs)
        backend.lost_programs.clear()

        ready = backend.ready_pods()
        if backlog and not ready:
            # scale-from-zero: the router parks these programs behind a
            # capacity ask; the simulator calls the same hook
            if scaler.request_capacity(SVC).get("ok"):
                parked += len(backlog)
        elif ready:
            for idx in backlog:
                pod = min(ready, key=lambda p: (p.eng.pending, p.name))
                pod.rid2idx[pod.eng.submit(
                    _prompt(idx), max_new_tokens=MAX_NEW)] = idx
            backlog.clear()

        # one engine tick a ready pod, and its telemetry frame into the
        # REAL fleet store (what the scaler reads)
        for pod in ready:
            for rid, _toks, done in pod.eng.step():
                if done:
                    # a later reap requeues only what is still in flight
                    pod.rid2idx.pop(rid, None)
            fleet.ingest(SVC, pod.name, {"ts": clock.now(), "m": {
                "engine_phase": 2,
                "engine_active_rows": pod.eng.active_rows,
                "engine_free_rows": pod.eng.free_rows,
                "engine_queue_depth": pod.eng.queued,
            }, "full": True})

        if t % 2 == 0:       # the scaler rides the resilience sweep
            scaler.tick(actuals={SVC: len(ready)})

        peak = max(peak, len(backend.pods))
        if t > 500 and not backend.pods:
            scaled_to_zero = True

    rows = sorted(db.load_scale_decisions(SVC, limit=100000),
                  key=lambda d: d["ts"])
    # reversals inside the cooldown over ALL durable rows (the scaler's
    # in-memory counter does not survive the kill)
    durable_flaps = 0
    for prev, cur in zip(rows, rows[1:]):
        d_prev = cur["from_replicas"] - prev["from_replicas"]
        d_cur = cur["to_replicas"] - cur["from_replicas"]
        if d_prev * d_cur < 0 and cur["ts"] - prev["ts"] < COOLDOWN_S:
            durable_flaps += 1
    return {
        "decisions": [(round(d["ts"] - t_base, 3), d["from_replicas"],
                       d["to_replicas"], d["kind"]) for d in rows],
        "flaps": flaps + scaler.flaps_total + durable_flaps,
        "parked": parked,
        "peak_replicas": peak,
        "cold_starts": list(backend.cold_starts),
        "lagged_pods": backend.lagged_pods,
        "decisions_at_kill": decisions_at_kill,
        "scaled_to_zero": scaled_to_zero,
    }


def run_routing(routed: bool) -> int:
    """A fixed fleet of two fast pods and two at half speed under one
    seeded arrival list (``ROUTED_PROGRAMS`` at ten a second), routed by
    ``select_route`` (earliest ETA) or dealt round-robin. Returns how
    many programs had their first token within ``FIRST_TOKEN_LIMIT_S``
    of virtual time after arriving."""
    n_programs, speeds = ROUTED_PROGRAMS, POD_SPEEDS
    rnd = random.Random(17)
    arrive, t_acc = [], 0.0
    for _ in range(n_programs):
        t_acc += rnd.expovariate(10.0)
        arrive.append(t_acc)
    pods = [SimPod(f"pod-{i}") for i in range(len(speeds))]
    first_tok: Dict[int, float] = {}
    n_done = 0
    i, t, rr = 0, 0.0, 0
    while n_done < n_programs:
        while i < n_programs and arrive[i] <= t:
            if routed:
                # the view the controller's rollup gives the router: ETA
                # is the backlog over the pod's speed
                rollup = {
                    "pods": {p.name: {"stale": False} for p in pods},
                    "gauges": {
                        "engine_phase": {"by_pod": {
                            p.name: 2 for p in pods}},
                        "engine_row_eta_seconds": {"by_pod": {
                            p.name: p.eng.pending / (speeds[k] * SLOTS)
                            for k, p in enumerate(pods)}},
                        "engine_queue_depth": {"by_pod": {
                            p.name: p.eng.queued for p in pods}},
                    },
                }
                name = select_route(rollup)["pod"]
                target = next(p for p in pods if p.name == name)
            else:
                target = pods[rr % len(pods)]
                rr += 1
            target.rid2idx[target.eng.submit(
                _prompt(i), max_new_tokens=MAX_NEW)] = i
            i += 1
        for k, pod in enumerate(pods):
            pod.eng.admit()
            pod.eng.prefill_step()
            for _ in range(speeds[k]):
                if not pod.eng.active_rows:
                    break
                for rid, toks, done in pod.eng.decode_step():
                    idx = pod.rid2idx[rid]
                    if toks:
                        first_tok.setdefault(idx, t + 1.0)
                    n_done += bool(done)
        t += 1.0
    return sum(1 for idx in range(n_programs)
               if first_tok[idx] - arrive[idx] <= FIRST_TOKEN_LIMIT_S)
