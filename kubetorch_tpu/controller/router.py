"""Fleet router: pure target selection over a fleet-store rollup.

PR 17 put phase-aware routing inline in the controller's
``POST /route/generate`` handler; this module lifts the policy out into
a pure function so the virtual-time fleet simulator
(``tests/fleet_sim.py``) and the tests can route against a rollup dict
without an HTTP server in the loop, and so the handler's job shrinks to
transport + the scale-from-zero park.

Policy (BandPilot-style contention-aware dispatch — route to where the
program will RUN soonest, not to the emptiest queue):

- ``prefix_hit`` + a decode tier → ``decode-only`` to the earliest
  speculation-aware row-free ETA (``engine_row_eta_seconds``, the
  engine's own pricing of its decode horizon);
- a prefill AND a decode tier → ``disagg``: prefill by shallowest
  queue (prefill is compute-bound — queue depth IS its backlog),
  decode by earliest ETA;
- otherwise → ``monolithic`` to the min-ETA mixed/live pod;
- no live candidates → ``None`` (the caller decides between 503 and a
  scale-from-zero park).

Backpressure: a pod actively shedding admissions
(``engine_sheds_total`` / ``admission_shed_total`` counter rate > 0
over the rollup window) advertises that its admission gate is closed —
the router deprioritizes it within its tier unless every candidate is
shedding. The shed signal rides telemetry the pods already publish;
nothing new crosses the wire.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

SHED_COUNTERS = ("engine_sheds_total", "admission_shed_total")


class RouterStats:
    """Controller-lifetime routing counters (the ``router_*`` metric
    family on the /metrics scrape)."""

    def __init__(self):
        self.by_mode: Dict[str, int] = {}
        self.parked_total = 0
        self.unroutable_total = 0
        self.backpressure_skips_total = 0

    def note(self, mode: str) -> None:
        self.by_mode[mode] = self.by_mode.get(mode, 0) + 1

    def prom_samples(self) -> List[Tuple[str, dict, float]]:
        samples = [
            ("router_parked_total", {}, self.parked_total),
            ("router_unroutable_total", {}, self.unroutable_total),
            ("router_backpressure_skips_total", {},
             self.backpressure_skips_total),
        ]
        for mode in sorted(self.by_mode):
            samples.append(("router_routes_total", {"mode": mode},
                            self.by_mode[mode]))
        return samples


def _by_pod(rollup: Dict[str, Any], kind: str, name: str,
            value_key: str) -> Dict[str, float]:
    return (((rollup.get(kind) or {}).get(name) or {})
            .get(value_key) or {})


def shedding_pods(rollup: Dict[str, Any]) -> set:
    """Pods whose admission gate shed work during the rollup window."""
    shedding = set()
    for counter in SHED_COUNTERS:
        for pod, rate in _by_pod(rollup, "counters", counter,
                                 "by_pod").items():
            # counter by_pod carries per-pod increase over the window
            if float(rate or 0.0) > 0.0:
                shedding.add(pod)
    return shedding


def select_route(rollup: Dict[str, Any], *, prefix_hit: bool = False,
                 exclude: Iterable[str] = (),
                 stats: Optional[RouterStats] = None) -> Optional[dict]:
    """Pick routing targets from one service's fleet rollup; None when
    nothing is routable. The returned dict carries ``mode`` plus
    ``pod`` / ``prefill`` / ``decode`` keys — the handoff id is the
    transport layer's business."""
    gauges = rollup.get("gauges") or {}
    pods_meta = rollup.get("pods") or {}
    exclude = set(exclude)

    def by_pod(name) -> Dict[str, float]:
        return (gauges.get(name) or {}).get("by_pod") or {}

    phase = by_pod("engine_phase")
    eta = by_pod("engine_row_eta_seconds")
    queue = by_pod("engine_queue_depth")
    live = [p for p, m in sorted(pods_meta.items())
            if p not in exclude and not m.get("stale")]
    shedding = shedding_pods(rollup)

    def prefer_clear(pods: List[str]) -> List[str]:
        """Shed-aware tier view: pods with an open admission gate beat
        shedding ones; a fully-shedding tier stays routable (a shed is
        backpressure, not death)."""
        clear = [p for p in pods if p not in shedding]
        if clear and len(clear) < len(pods) and stats is not None:
            stats.backpressure_skips_total += len(pods) - len(clear)
        return clear or pods

    prefill = prefer_clear([p for p in live if phase.get(p) == 0])
    decode = prefer_clear([p for p in live if phase.get(p) == 1])
    mixed = prefer_clear([p for p in live if phase.get(p) not in (0, 1)])

    def eta_key(p):
        return (float(eta.get(p, 0.0)), p)

    def queue_key(p):
        return (float(queue.get(p, 0.0)), p)

    if prefix_hit and decode:
        route = {"mode": "decode-only",
                 "decode": min(decode, key=eta_key)}
    elif prefill and decode:
        route = {"mode": "disagg",
                 "prefill": min(prefill, key=queue_key),
                 "decode": min(decode, key=eta_key)}
    else:
        pool = mixed or prefer_clear(live)
        if not pool:
            if stats is not None:
                stats.unroutable_total += 1
            return None
        route = {"mode": "monolithic", "pod": min(pool, key=eta_key)}
    if stats is not None:
        stats.note(route["mode"])
    return route
