"""Controller-resident fleet telemetry store: push-based time series.

Every signal the serving stack emits today dies at the pod boundary —
PR 9's ``engine_*`` occupancy gauges, PR 10's ``kv_*``/``prefix_*``
counters, PR 8's ``replay_*``/``admission_*`` families are all scraped
per pod with no retention and no cross-replica aggregation, and the
controller's ``/metrics/query/{service}`` is a latest-snapshot proxy.
The autoscaling/fleet-routing direction (ROADMAP item 5, BandPilot /
Gavel in PAPERS.md) needs these signals *at the controller as history*:
measured, retained, fleet-aggregated throughput/latency series a
placement policy can query.

This module is that store. **Ingest**: pods piggyback compact metric
delta frames on the controller-WS heartbeat (fallback: batched
``POST /telemetry``); each frame carries the pid-merged snapshot of the
pod's counters/gauges plus named-histogram buckets. **Storage**: one
ring per ``(service, pod, metric)`` with three time tiers — raw frames
(``KT_FLEET_RAW_S``), 10 s buckets (``KT_FLEET_MID_S``), 1 m buckets
(``KT_FLEET_RETAIN_S``) — plus counter-reset detection: a restarted
pod's counters step DOWN, and the store splices a monotonic adjusted
series (offset += last value at the step) so windowed rates never go
negative and never double-count. **Query**: fleet rollups per service —
rate/increase across pods for counters, sum of latest non-stale values
for gauges, bucket-merge for histograms so TTFT p99 is computable
ACROSS replicas — plus aligned range series for ramps, and exposition
samples joined into the controller's Prometheus scrape.

Everything is stdlib + in-memory (same trade as ``log_sink.LogSink``);
a clock is injectable throughout so rollup semantics are unit-testable
without sleeping.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from kubetorch_tpu.config import env_float

# counter detection mirrors prometheus.py: these suffixes accumulate,
# everything else is a point-in-time gauge
_COUNTER_SUFFIXES = ("_total", "_bucket", "_count", "_sum")

# metric-name prefixes a pod includes in its telemetry frames — the
# signal families the fleet plane exists for. One definition, imported
# by the pod server's frame builder, so pods and docs can't drift.
FRAME_PREFIXES = ("engine_", "kv_", "prefix_", "serving_", "replay_",
                  "admission_", "resilience_", "http_", "telemetry_",
                  "trace_", "ws_", "hbm_")


def is_counter(name: str) -> bool:
    return name.endswith(_COUNTER_SUFFIXES)


def _hkey(base: str, le: Any) -> str:
    """Series key of one histogram bucket counter (``le`` kept exact —
    it round-trips through queries for bucket-merge)."""
    return f"{base}_bucket:{le}"


# ------------------------------------------------------------------ frames
def build_frame(metrics: Dict[str, Any],
                hists: Optional[Dict[str, Dict[str, Any]]] = None,
                last_sent: Optional[Dict[str, Any]] = None,
                full: bool = False,
                ts: Optional[float] = None,
                prefixes: Tuple[str, ...] = FRAME_PREFIXES) -> dict:
    """One compact telemetry frame from a pod's merged metrics dict +
    named-histogram snapshot.

    Delta semantics: with ``last_sent`` (the mutable dict of values the
    pod last shipped) only CHANGED keys are included — unchanged
    counters/gauges cost zero bytes on the heartbeat, which is what
    keeps the piggyback small on an idle pod.
    ``last_sent`` is updated in place for the keys shipped; callers
    roll it back (or pass ``full=True`` next frame) when the send
    fails. Histograms ship whenever their ``count`` moved.
    """
    out_m: Dict[str, float] = {}
    last_sent = last_sent if last_sent is not None else {}
    for name, value in (metrics or {}).items():
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            continue
        if not name.startswith(prefixes):
            continue
        if full or last_sent.get(name) != value:
            out_m[name] = float(value)
            last_sent[name] = value
    out_h: Dict[str, dict] = {}
    for base, h in (hists or {}).items():
        count = float(h.get("count", 0.0))
        hist_key = f"__hist__{base}"
        if full or last_sent.get(hist_key) != count:
            out_h[base] = {"le": list(h.get("le") or ()),
                           "b": [float(b) for b in
                                 (h.get("buckets") or ())],
                           "sum": float(h.get("sum", 0.0)),
                           "count": count}
            last_sent[hist_key] = count
    frame: dict = {"ts": time.time() if ts is None else ts}
    if out_m:
        frame["m"] = out_m
    if out_h:
        frame["h"] = out_h
    if full:
        frame["full"] = True
    return frame


# ------------------------------------------------------------------ series
class _Series:
    """One (service, pod, metric) ring with reset splicing + 3 tiers.

    Stored values are ADJUSTED for counters: ``adj = raw + offset``
    where ``offset`` grows by the last pre-reset value each time the
    raw value steps down (pod restart). Rates/increases computed from
    adjusted values are monotone-correct through any number of
    restarts. Gauges store raw values and skip reset logic.
    """

    __slots__ = ("kind", "raw", "t10", "t60", "last_raw", "offset",
                 "raw_s", "mid_s", "retain_s")

    def __init__(self, kind: str, raw_s: float, mid_s: float,
                 retain_s: float):
        self.kind = kind
        self.raw: deque = deque()    # (ts, adjusted value)
        self.t10: deque = deque()    # (bucket_end_ts, last adjusted)
        self.t60: deque = deque()
        self.last_raw: Optional[float] = None
        self.offset = 0.0
        self.raw_s = raw_s
        self.mid_s = mid_s
        self.retain_s = retain_s

    def ingest(self, ts: float, value: float) -> bool:
        """Append one sample; returns True when a counter reset was
        detected (caller records the annotation + metric)."""
        reset = False
        if self.kind == "counter":
            if self.last_raw is not None and value < self.last_raw:
                # restart: splice — everything the old incarnation
                # counted is kept in the offset, the new incarnation
                # counts from zero on top of it
                self.offset += self.last_raw
                reset = True
            self.last_raw = value
            value = value + self.offset
        if self.raw and ts < self.raw[-1][0]:
            ts = self.raw[-1][0]    # clock skew: never go backwards
        self.raw.append((ts, value))
        self._downsample(ts, value)
        self._prune(ts)
        return reset

    def _downsample(self, ts: float, value: float) -> None:
        # last-value-in-bucket for both tiers: counters need exactly
        # the last adjusted value to compute increases across bucket
        # boundaries; gauges get their most recent reading
        for tier, width in ((self.t10, 10.0), (self.t60, 60.0)):
            bucket = (ts // width) * width + width
            if tier and tier[-1][0] == bucket:
                tier[-1] = (bucket, value)
            else:
                tier.append((bucket, value))

    def _prune(self, now: float) -> None:
        for tier, keep in ((self.raw, self.raw_s),
                           (self.t10, self.mid_s),
                           (self.t60, self.retain_s)):
            while tier and tier[0][0] < now - keep:
                tier.popleft()

    def _tiers(self):
        return (self.raw, self.t10, self.t60)

    def value_at(self, ts: float) -> Optional[float]:
        """Latest adjusted value at or before ``ts`` across all tiers
        (finest tier that still covers ``ts`` wins). Newest-first scan,
        no allocation: queries overwhelmingly target the tail (now, or
        a window start inside the raw ring), and rollups run this for
        every (metric x pod) series on every scrape/sweep."""
        for tier in self._tiers():
            if not tier or tier[0][0] > ts:
                continue
            for t, value in reversed(tier):
                if t <= ts:
                    return value
        return None

    def latest(self) -> Optional[Tuple[float, float]]:
        for tier in self._tiers():
            if tier:
                return tier[-1]
        return None

    def first_at_or_after(self, ts: float) -> Optional[Tuple[float, float]]:
        best: Optional[Tuple[float, float]] = None
        for tier in self._tiers():
            cand: Optional[Tuple[float, float]] = None
            for entry in reversed(tier):
                if entry[0] < ts:
                    break
                cand = entry
            if cand is not None and (best is None or cand[0] < best[0]):
                best = cand
        return best

    def increase(self, t0: float, t1: float) -> float:
        """Counter increase over ``[t0, t1]`` on the adjusted series.
        A series that first appeared inside the window counts from its
        first in-window sample (pre-history isn't charged to the
        window); never negative by construction."""
        end = self.value_at(t1)
        if end is None:
            return 0.0
        start = self.value_at(t0)
        if start is None:
            first = self.first_at_or_after(t0)
            if first is None or first[0] > t1:
                return 0.0
            start = first[1]
        return max(0.0, end - start)


class _PodState:
    __slots__ = ("series", "last_ts", "frames", "resets", "hist_les")

    def __init__(self):
        self.series: Dict[str, _Series] = {}
        self.last_ts = 0.0
        self.frames = 0
        self.resets: deque = deque(maxlen=32)   # reset timestamps
        # histogram base -> bucket bounds (for bucket-merge queries)
        self.hist_les: Dict[str, List[float]] = {}


class FleetStore:
    """Per-service, per-pod metric rings + fleet rollups (see module
    docstring). Thread-safe: ingest lands on the controller loop, but
    queries also arrive from executor threads (dashboard gather) and
    the tests drive it from plain threads."""

    def __init__(self, raw_s: Optional[float] = None,
                 mid_s: Optional[float] = None,
                 retain_s: Optional[float] = None,
                 stale_after_s: Optional[float] = None,
                 clock: Callable[[], float] = time.time):
        self.raw_s = raw_s if raw_s is not None else \
            env_float("KT_FLEET_RAW_S")
        self.mid_s = mid_s if mid_s is not None else \
            env_float("KT_FLEET_MID_S")
        self.retain_s = retain_s if retain_s is not None else \
            env_float("KT_FLEET_RETAIN_S")
        self.stale_after_s = stale_after_s if stale_after_s is not None \
            else env_float("KT_FLEET_STALE_S")
        self.clock = clock
        self._lock = threading.Lock()
        self._pods: Dict[str, Dict[str, _PodState]] = {}
        self.frames_total = 0
        self.samples_total = 0
        self.resets_total = 0

    # ---------------------------------------------------------- ingest
    def ingest(self, service: str, pod: str, frame: dict) -> int:
        """One telemetry frame (see :func:`build_frame`); returns the
        number of samples ingested. Malformed frames ingest what they
        can — a garbled histogram must not drop the counters riding
        the same frame."""
        if not service or not pod or not isinstance(frame, dict):
            return 0
        ts = frame.get("ts")
        if not isinstance(ts, (int, float)):
            ts = self.clock()
        n = 0
        with self._lock:
            state = self._pods.setdefault(service, {}).setdefault(
                pod, _PodState())
            state.last_ts = max(state.last_ts, float(ts))
            state.frames += 1
            self.frames_total += 1
            metrics = frame.get("m")
            if isinstance(metrics, dict):
                for name, value in metrics.items():
                    if isinstance(value, bool) or \
                            not isinstance(value, (int, float)):
                        continue
                    n += self._ingest_one_locked(state, float(ts),
                                                 str(name), float(value))
            hists = frame.get("h")
            if isinstance(hists, dict):
                for base, h in hists.items():
                    n += self._ingest_hist_locked(state, float(ts),
                                                  str(base), h)
            self.samples_total += n
        return n

    def _ingest_one_locked(self, state: _PodState, ts: float,
                           name: str, value: float,
                           kind: Optional[str] = None) -> int:
        series = state.series.get(name)
        if series is None:
            if kind is None:
                kind = "counter" if is_counter(name) else "gauge"
            series = state.series[name] = _Series(
                kind, self.raw_s, self.mid_s, self.retain_s)
        if series.ingest(ts, value):
            state.resets.append(ts)
            self.resets_total += 1
        return 1

    def _ingest_hist_locked(self, state: _PodState, ts: float,
                            base: str, h: Any) -> int:
        if not isinstance(h, dict):
            return 0
        les = list(h.get("le") or ())
        buckets = list(h.get("b") or h.get("buckets") or ())
        if len(les) != len(buckets):
            return 0
        state.hist_les[base] = [float(le) for le in les]
        n = 0
        # each bucket is its own counter series (kind FORCED — the
        # ":le" key suffix defeats name-based detection): reset
        # splicing comes for free, a restarted pod steps every bucket
        # down together
        for le, count in zip(les, buckets):
            n += self._ingest_one_locked(state, ts, _hkey(base, le),
                                         float(count), kind="counter")
        n += self._ingest_one_locked(state, ts, f"{base}_count",
                                     float(h.get("count", 0.0)))
        n += self._ingest_one_locked(state, ts, f"{base}_sum",
                                     float(h.get("sum", 0.0)))
        return n

    # ----------------------------------------------------------- admin
    def services(self) -> List[str]:
        with self._lock:
            return sorted(self._pods)

    def pods(self, service: str) -> List[str]:
        with self._lock:
            return sorted(self._pods.get(service) or {})

    def knows(self, service: str, pod: str) -> bool:
        """Membership test without ``pods``'s sorted copy — this sits
        on the heartbeat resync-hint path, which the WHOLE fleet hits
        every beat during a controller outage/recovery."""
        with self._lock:
            return pod in (self._pods.get(service) or {})

    def drop(self, service: str) -> None:
        """Teardown hook (cascading delete, same contract as
        ``LogSink.drop_stream``)."""
        with self._lock:
            self._pods.pop(service, None)

    def metric_names(self, service: str) -> List[str]:
        with self._lock:
            names: set = set()
            for state in (self._pods.get(service) or {}).values():
                names.update(k for k in state.series if ":" not in k)
            return sorted(names)

    def pod_annotations(self, service: str) -> Dict[str, dict]:
        """Per-pod staleness + restart annotations, the blind-polling
        fix for ``/metrics/query/{service}`` and the dashboard: a
        restarted replica reads as "reset 12 s ago" instead of a
        silent rate glitch."""
        now = self.clock()
        out: Dict[str, dict] = {}
        with self._lock:
            for pod, state in (self._pods.get(service) or {}).items():
                age = round(now - state.last_ts, 3) if state.last_ts \
                    else None
                ann = {"age_s": age,
                       "stale": bool(age is None
                                     or age > self.stale_after_s),
                       "frames": state.frames,
                       "resets": len(state.resets)}
                if state.resets:
                    ann["last_reset_age_s"] = round(
                        now - state.resets[-1], 3)
                out[pod] = ann
        return out

    # ----------------------------------------------------------- query
    def fleet(self, service: str, window_s: float = 60.0,
              now: Optional[float] = None) -> dict:
        """Cross-pod rollup over the trailing window: counters →
        fleet rate + increase (per-pod breakdown included), gauges →
        sum of latest non-stale values, histograms → bucket-merged
        increases with interpolated p50/p90/p99 (so TTFT p99 is a
        FLEET number, not a per-pod one)."""
        now = self.clock() if now is None else now
        window_s = max(1.0, float(window_s))
        t0 = now - window_s
        with self._lock:
            pods = dict(self._pods.get(service) or {})
            counters: Dict[str, dict] = {}
            gauges: Dict[str, dict] = {}
            hist_les: Dict[str, List[float]] = {}
            pod_meta: Dict[str, dict] = {}
            for pod, state in pods.items():
                age = (now - state.last_ts) if state.last_ts else None
                stale = bool(age is None or age > self.stale_after_s)
                pod_meta[pod] = {
                    "age_s": round(age, 3) if age is not None else None,
                    "stale": stale,
                    "resets": len(state.resets)}
                if state.resets:
                    pod_meta[pod]["last_reset_age_s"] = round(
                        now - state.resets[-1], 3)
                hist_les.update(state.hist_les)
                for name, series in state.series.items():
                    if ":" in name:
                        continue    # histogram buckets merge below
                    if series.kind == "counter":
                        inc = series.increase(t0, now)
                        entry = counters.setdefault(
                            name, {"increase": 0.0, "by_pod": {}})
                        entry["increase"] += inc
                        entry["by_pod"][pod] = round(inc / window_s, 6)
                    else:
                        latest = series.latest()
                        entry = gauges.setdefault(
                            name, {"sum": 0.0, "by_pod": {}})
                        value = latest[1] if latest else 0.0
                        entry["by_pod"][pod] = value
                        if not stale:
                            entry["sum"] += value
            hists: Dict[str, dict] = {}
            for base, les in hist_les.items():
                merged = [0.0] * len(les)
                count = 0.0
                total_sum = 0.0
                by_pod_p99: Dict[str, float] = {}
                for pod, state in pods.items():
                    pod_buckets = []
                    for i, le in enumerate(les):
                        series = state.series.get(_hkey(base, le))
                        inc = series.increase(t0, now) if series else 0.0
                        merged[i] += inc
                        pod_buckets.append(inc)
                    cs = state.series.get(f"{base}_count")
                    pc = cs.increase(t0, now) if cs else 0.0
                    count += pc
                    ss = state.series.get(f"{base}_sum")
                    total_sum += ss.increase(t0, now) if ss else 0.0
                    if pc > 0:
                        by_pod_p99[pod] = round(
                            hist_quantile(0.99, les, pod_buckets, pc), 6)
                if count <= 0 and not any(merged):
                    continue
                hists[base] = {
                    "count": round(count, 6),
                    "sum": round(total_sum, 6),
                    "rate": round(count / window_s, 6),
                    "buckets": [[le, round(b, 6)]
                                for le, b in zip(les, merged)],
                    "p50": round(hist_quantile(0.50, les, merged,
                                               count), 6),
                    "p90": round(hist_quantile(0.90, les, merged,
                                               count), 6),
                    "p99": round(hist_quantile(0.99, les, merged,
                                               count), 6),
                    "by_pod_p99": by_pod_p99,
                }
        for name, entry in counters.items():
            entry["rate"] = round(entry["increase"] / window_s, 6)
            entry["increase"] = round(entry["increase"], 6)
        for entry in gauges.values():
            entry["sum"] = round(entry["sum"], 6)
        return {"service": service, "ts": now, "window_s": window_s,
                "pods": pod_meta, "counters": counters,
                "gauges": gauges, "histograms": hists}

    def range(self, service: str, metrics: Iterable[str],
              start: Optional[float] = None, end: Optional[float] = None,
              step: float = 10.0) -> dict:
        """Aligned fleet series for ramps/autoscaler input: for each
        step boundary, counters report the fleet per-second rate over
        the preceding step and gauges the cross-pod sum at the
        boundary. Resolution below the downsample tiers is whatever
        raw frames provide."""
        now = self.clock()
        end = now if end is None else float(end)
        step = max(1.0, float(step))
        if start is None:
            start = end - 300.0
        start = max(float(start), end - self.retain_s)
        ticks: List[float] = []
        t = start + step
        while t <= end + 1e-9:
            ticks.append(t)
            t += step
        series_out: Dict[str, list] = {}
        with self._lock:
            pods = dict(self._pods.get(service) or {})
            for name in metrics:
                name = str(name)
                rows = []
                counter = is_counter(name)
                for tick in ticks:
                    total = 0.0
                    for state in pods.values():
                        series = state.series.get(name)
                        if series is None:
                            continue
                        if counter:
                            total += series.increase(tick - step, tick)
                        else:
                            value = series.value_at(tick)
                            total += value if value is not None else 0.0
                    rows.append([round(tick, 3),
                                 round(total / step, 6) if counter
                                 else round(total, 6)])
                series_out[name] = rows
        return {"service": service, "start": start, "end": end,
                "step": step, "series": series_out}

    # ------------------------------------------------------ exposition
    def prom_samples(self, window_s: float = 60.0):
        """Fleet rollups joined into the controller's Prometheus
        scrape: ``fleet_<counter-base>_per_s`` rates,
        ``fleet_<gauge>`` sums, ``fleet_<hist>_p99`` quantiles, plus
        the store's own ingest/reset totals."""
        yield "fleet_frames_total", {}, self.frames_total
        yield "fleet_samples_total", {}, self.samples_total
        yield "fleet_resets_total", {}, self.resets_total
        for service in self.services():
            roll = self.fleet(service, window_s=window_s)
            labels = {"service": service}
            stale = sum(1 for p in roll["pods"].values() if p["stale"])
            yield "fleet_pods", labels, len(roll["pods"])
            yield "fleet_stale_pods", labels, stale
            for name, entry in roll["counters"].items():
                base = name[:-6] if name.endswith("_total") else name
                yield f"fleet_{base}_per_s", labels, entry["rate"]
            for name, entry in roll["gauges"].items():
                yield f"fleet_{name}", labels, entry["sum"]
            for base, h in roll["histograms"].items():
                yield f"fleet_{base}_p99", labels, h["p99"]
                yield f"fleet_{base}_per_s", labels, h["rate"]


def hist_quantile(q: float, les: List[float], buckets: List[float],
                  count: Optional[float] = None) -> float:
    """``histogram_quantile``-style linear interpolation over
    cumulative bucket increases (``buckets[i]`` counts observations
    ≤ ``les[i]``). Observations above the last bound clamp to it, as
    Prometheus does."""
    if not les:
        return 0.0
    total = count if count is not None else (buckets[-1] if buckets
                                             else 0.0)
    total = max(total, buckets[-1] if buckets else 0.0)
    if total <= 0:
        return 0.0
    rank = q * total
    prev_le, prev_count = 0.0, 0.0
    for le, cum in zip(les, buckets):
        if cum >= rank:
            if cum <= prev_count:
                return float(le)
            frac = (rank - prev_count) / (cum - prev_count)
            return float(prev_le + (le - prev_le) * frac)
        prev_le, prev_count = float(le), float(cum)
    return float(les[-1])
