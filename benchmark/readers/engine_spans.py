"""Readers of the serving engine's own timing (``DecodeEngine.stats()``):
the request-lifecycle sums and the tick-phase seconds, as deltas over the
window. Means are exact (sum / count); no percentile is taken from a coarse
bucket ladder. A program without these counters (the parent of the PR that
added them, a rehearsal on another engine) gives ``None`` for each."""

# the host's share of a tick that admits nothing: every phase but the two
# that wait (decode_sync, idle) and the two that run only on work arriving
# (admit, prefill)
HOST_PHASES = ("evict", "handoff", "decode_dispatch", "route", "publish",
               "handover")


def _mean_ms(d, total, count):
    if not d.get(count):
        return None
    return 1e3 * d[total] / d[count]


def _lifecycle_ms(ctx, name):
    return _mean_ms(ctx.get("stats_delta") or {}, f"{name}_sum",
                    f"{name}_count")


def engine_queue_wait_ms(ctx):
    """Queued in the generator until the tick that admits the row starts
    its admission."""
    return _lifecycle_ms(ctx, "engine_queue_wait_seconds")


def engine_admit_to_first_ms(ctx):
    """Start of a row's admission to its first frame leaving the engine."""
    return _lifecycle_ms(ctx, "engine_admit_to_first_seconds")


def ttft_outside_engine_ms(ctx):
    """The client's mean time from SENDING a request to its first frame,
    over requests whose first frame arrived in the window, minus the
    engine's own mean time to first token (``generate()``'s entry to the
    first frame routed): channel, pod server, worker threads, both ways."""
    inside = _lifecycle_ms(ctx, "engine_ttft_seconds")
    seen = [(r.frames[0][0] - r.sent) * 1e3 for r in ctx.get("records", [])
            if r.frames and r.sent is not None
            and 0 <= r.frames[0][0] < ctx["seconds"]]
    if inside is None or not seen:
        return None
    return sum(seen) / len(seen) - inside


def tick_host_ms(ctx):
    """Host seconds of the phases every decoding tick pays, per tick."""
    d = ctx.get("stats_delta") or {}
    if not d.get("ticks") or any(f"tick_{p}_s" not in d
                                 for p in HOST_PHASES):
        return None
    return 1e3 * sum(d[f"tick_{p}_s"] for p in HOST_PHASES) / d["ticks"]


def admit_host_ms(ctx):
    """Host time of one admission, up to the return of its dispatches."""
    return _mean_ms(ctx.get("stats_delta") or {}, "tick_admit_s",
                    "tick_admit_n")


def tick_publish_ms(ctx):
    """What the instrumentation on the tick costs a tick: spec telemetry,
    gauges, the flight record."""
    return _mean_ms(ctx.get("stats_delta") or {}, "tick_publish_s", "ticks")
