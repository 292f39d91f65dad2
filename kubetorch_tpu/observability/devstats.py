"""Device-truth utilization accounting: FLOPs/HBM-byte cost capture
for compiled executables, hardware peak tables, and HBM occupancy.

The serving plane answers "how hard is the hardware actually working"
with two ratios:

* **MFU** (model FLOPs utilization) — FLOPs the dispatched executables
  were compiled to perform, divided by (measured dispatch wall x the
  chip's peak FLOP/s).
* **MBU** (memory-bandwidth utilization) — HBM bytes the executables
  touch, divided by (measured dispatch wall x peak HBM bandwidth).

The numerators come from XLA itself: every jitted executable exposes
``cost_analysis()`` after compilation, so the per-dispatch FLOPs/bytes
are *compiler truth*, not a hand-derived roofline formula. The
:class:`ExecutableCosts` accumulator lazily captures that analysis once
per (kind, static-shape key) — a mixed spec-k engine dispatching
``decode_spec`` at widths 2 and 4 attributes each dispatch to the right
executable — then counts dispatches. The denominator (dispatch wall)
is measured by the engine driver around the same calls.

``SimRollingEngine`` gets an analytic twin (:class:`AnalyticCosts`)
with the same snapshot surface so the whole utilization plane runs
CPU-only in the tests.

HBM occupancy rides the same module: :func:`hbm_stats` reads
``device.memory_stats()`` without ever *initializing* a backend (the
same guard as ``process_worker._maybe_device_stats`` — a metrics hook
must not acquire devices), returning ``None`` gracefully on CPU-only
processes where the runtime reports no memory stats.
"""
from __future__ import annotations

import sys
import threading
from typing import Any, Dict, Optional, Tuple

# ------------------------------------------------------------------
# Hardware peaks, keyed by substrings of ``device.device_kind``.
# (peak dense FLOP/s in the serving dtype (bf16), peak HBM bytes/s).
# Sources: published TPU spec sheets (the v5e row equals
# ``benchmark/peaks.json``, the yardstick the benchmark reads).
# Unknown kinds (CPU hosts, unrecognized accelerators) map to None — the
# engine then publishes *no* MFU/MBU gauge rather than a made-up one, the
# same absent-not-zero semantics as ``kv_blocks_free``.
_PEAKS: Tuple[Tuple[str, Tuple[float, float]], ...] = (
    ("v5 lite", (197e12, 819e9)),
    ("v5e", (197e12, 819e9)),
    ("v5litepod", (197e12, 819e9)),
    ("v5p", (459e12, 2765e9)),
    ("v6e", (918e12, 1640e9)),
    ("v6 lite", (918e12, 1640e9)),
    ("v4", (275e12, 1228e9)),
    ("v3", (123e12, 900e9)),
    ("v2", (45e12, 700e9)),
)


def peaks_for_kind(device_kind: str) -> Optional[Tuple[float, float]]:
    """(peak_flops, peak_bytes_per_s) for a ``device_kind`` string, or
    None when the kind is unknown (CPU / unrecognized accelerator)."""
    kind = (device_kind or "").lower()
    if "tpu" not in kind and not kind.startswith("v"):
        return None
    for needle, peaks in _PEAKS:
        if needle in kind:
            return peaks
    return None


def device_peaks() -> Optional[Tuple[float, float]]:
    """Peaks for THIS process's default device, or None. Never
    initializes a backend: an uninitialized jax (or no jax at all)
    reads as "no accelerator", exactly like :func:`hbm_stats`."""
    jax = sys.modules.get("jax")
    try:
        if jax is None:
            return None
        xla_bridge = sys.modules.get("jax._src.xla_bridge")
        if xla_bridge is None or not getattr(xla_bridge, "_backends", None):
            return None
        devices = jax.local_devices()
        if not devices:
            return None
        return peaks_for_kind(getattr(devices[0], "device_kind", ""))
    # ktlint: disable=KT004 -- metrics introspection must never raise into the serving path
    except Exception:  # noqa: BLE001
        return None


def hbm_stats() -> Optional[Dict[str, float]]:
    """``hbm_used_bytes``/``hbm_limit_bytes`` summed over local devices,
    or None when no initialized backend reports memory stats (CPU). The
    backend-initialization guard mirrors the worker metrics hook: a
    bare ``import jax`` must not trigger device acquisition."""
    jax = sys.modules.get("jax")
    try:
        if jax is None:
            return None
        xla_bridge = sys.modules.get("jax._src.xla_bridge")
        if xla_bridge is None or not getattr(xla_bridge, "_backends", None):
            return None
        used = limit = 0.0
        seen = False
        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}
            if "bytes_in_use" in stats:
                seen = True
                used += float(stats.get("bytes_in_use", 0) or 0)
                limit += float(stats.get("bytes_limit", 0) or 0)
        if not seen:
            return None
        return {"hbm_used_bytes": used, "hbm_limit_bytes": limit}
    # ktlint: disable=KT004 -- metrics introspection must never raise into the serving path
    except Exception:  # noqa: BLE001
        return None


def watch_compiles() -> Dict[str, float]:
    """Count this process's XLA compiles from here on, off
    ``jax.monitoring``: seconds spent in the backend compile step (a
    persistent-cache hit spends only its retrieval there) and the
    persistent cache's hits and misses. Returns the live counter dict."""
    import jax

    stats = {"backend_compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            stats["backend_compile_s"] += seconds

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return stats


def cost_from_analysis(analysis: Any) -> Tuple[float, float]:
    """(flops, bytes) out of a ``cost_analysis()`` result. XLA returns
    either a dict or a one-element list of dicts depending on version;
    missing keys read as 0.0 (some backends report flops only)."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    if not isinstance(analysis, dict):
        return 0.0, 0.0
    flops = float(analysis.get("flops", 0.0) or 0.0)
    bytes_ = float(analysis.get("bytes accessed", 0.0) or 0.0)
    return flops, bytes_


def no_dispatch(kind: str, key: Any) -> None:
    """A generator's ``dispatched`` hook while no serving engine drives
    it (warm-up, hand-driven use): the engine installs its tick timer's
    there, and hears of every executable the generator queues."""


class ExecutableCosts:
    """Per-(kind, key) compiled-cost table + dispatch accumulator.

    ``call(kind, key, fn, *args, **kwargs)`` wraps a jitted dispatch
    site: the first time a (kind, key) pair is seen it lowers and
    compiles ``fn`` *for the same arguments* and captures the
    executable's ``cost_analysis()`` — lowering only reads avals, so
    this is safe even when the real call donates its buffers, and XLA's
    compilation cache makes the extra compile a one-time cache hit —
    then every call (including the first) adds one dispatch's worth of
    FLOPs/bytes to the running totals before invoking ``fn``.

    Capture failures degrade, never raise: a backend without
    ``cost_analysis`` records a zero-cost entry and keeps counting
    dispatches, so the snapshot surface stays intact and the engine
    simply publishes no utilization gauge (0 FLOPs -> peaks gate it).

    Capture is also skipped outright (zero-cost entries, dispatches
    still counted) when :func:`device_peaks` knows no peaks for this
    process's chip: without peaks no MFU/MBU gauge can ever publish,
    so paying one extra compile per executable — the dominant cost of
    the whole plane on the CPU test/CI path — would buy nothing.
    ``force_capture=True`` overrides (tests of the capture path).
    """

    def __init__(self, force_capture: bool = False) -> None:
        self._lock = threading.Lock()
        self._costs: Dict[Tuple[str, Any], Tuple[float, float]] = {}
        self._flops = 0.0
        self._bytes = 0.0
        self._dispatches = 0
        self._captured = 0
        self._force = force_capture

    def call(self, kind: str, key: Any, fn, *args, **kwargs):
        entry = self._costs.get((kind, key))
        if entry is None:
            entry = self._capture(kind, key, fn, args, kwargs)
        with self._lock:
            self._flops += entry[0]
            self._bytes += entry[1]
            self._dispatches += 1
        return fn(*args, **kwargs)

    def _capture(self, kind: str, key: Any, fn, args,
                 kwargs) -> Tuple[float, float]:
        entry = (0.0, 0.0)
        try:
            if self._force or device_peaks() is not None:
                compiled = fn.lower(*args, **kwargs).compile()
                entry = cost_from_analysis(compiled.cost_analysis())
        # ktlint: disable=KT004 -- cost capture is best-effort; the dispatch must proceed
        except Exception:  # noqa: BLE001
            pass
        with self._lock:
            self._costs[(kind, key)] = entry
            if entry != (0.0, 0.0):
                self._captured += 1
        return entry

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {
                "flops_total": self._flops,
                "bytes_total": self._bytes,
                "dispatches_total": float(self._dispatches),
                "captured_executables": float(self._captured),
            }

    def per_key_costs(self) -> Dict[Tuple[str, Any], Tuple[float, float]]:
        """The captured (flops, bytes) per-dispatch cost table, keyed by
        (kind, static key) — one executable's bytes (e.g. the decode
        chunk's) instead of the blended totals."""
        with self._lock:
            return dict(self._costs)


class AnalyticCosts:
    """The CPU twin: same snapshot surface as :class:`ExecutableCosts`,
    fed by analytic per-dispatch costs instead of ``cost_analysis()``.
    ``SimRollingEngine`` counts each simulated prefill/decode dispatch
    here with nominal FLOPs/bytes so the MFU/MBU plane (gauges, flight
    records, ``ktpu top`` columns) exercises end-to-end without an
    accelerator — and deterministically, for the reconciliation test."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._flops = 0.0
        self._bytes = 0.0
        self._dispatches = 0

    def count(self, flops: float, bytes_: float) -> None:
        with self._lock:
            self._flops += float(flops)
            self._bytes += float(bytes_)
            self._dispatches += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {
                "flops_total": self._flops,
                "bytes_total": self._bytes,
                "dispatches_total": float(self._dispatches),
                "captured_executables": 0.0,
            }


def utilization(flops: float, bytes_: float, wall_s: float,
                peaks: Optional[Tuple[float, float]],
                ) -> Optional[Tuple[float, float]]:
    """(mfu, mbu) for a window of work, clamped to [0, 1]; None when
    peaks are unknown or the window carries no measured wall."""
    if peaks is None or wall_s <= 0.0:
        return None
    peak_flops, peak_bw = peaks
    mfu = min(1.0, max(0.0, flops / (wall_s * peak_flops))) \
        if peak_flops > 0 else 0.0
    mbu = min(1.0, max(0.0, bytes_ / (wall_s * peak_bw))) \
        if peak_bw > 0 else 0.0
    return mfu, mbu
