"""Readers of the device trace (``benchmark.trace.summarise``)."""

import statistics


def _modules(ctx, kinds):
    t = ctx.get("trace") or {}
    return [m for m in (t.get("modules") or {}).values()
            if m["kind"] in kinds]


def device_idle(ctx):
    """1 - union of device-operation intervals / traced window."""
    t = ctx.get("trace") or {}
    if not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def tick_gap_p50_ms(ctx):
    """Median gap between the end of a decode executable and the start of
    the next device work."""
    gaps = ((ctx.get("trace") or {}).get("gap_after_ms") or {}).get("decode")
    return statistics.median(gaps) if gaps else None


def decode_step_dev_ms(ctx):
    """Device time of the decode executable / decode steps it ran."""
    mods = _modules(ctx, ("decode",))
    calls = sum(m["calls"] for m in mods)
    if not calls:
        return None
    total_ms = sum(m["total_s"] for m in mods) * 1e3
    return total_ms / (calls * ctx["deployment"]["steps_per_call"])


def prefill_chunk_dev_ms(ctx):
    """Median device time of one prefill executable call (admission or
    chunk)."""
    ms = [x for m in _modules(ctx, ("prefill",))
          for x in m["ms"]]
    return statistics.median(ms) if ms else None


def prefill_dev_ms_per_ktok(ctx):
    """Device time of prefill executables per 1000 prompt tokens executed
    while the trace ran."""
    mods = _modules(ctx, ("prefill",))
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "prefill_tokens_executed", 0)
    if not mods or not toks:
        return None
    return sum(m["total_s"] for m in mods) * 1e3 / (toks / 1000.0)


def collective_exposed(ctx):
    """Time in collective operations during which no other operation runs
    on that device, as a share of the traced window."""
    t = ctx.get("trace") or {}
    if not t.get("collective_s") or not t.get("window_s"):
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
