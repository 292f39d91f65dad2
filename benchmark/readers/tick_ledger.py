"""Readers of the tick's own account (``DecodeEngine.stats()``, PR 37): every
tick filed under ONE class by what it held (``tick_class_<c>_n`` / ``_wall_s``
/ ``_sync_s`` / ``_starved_s`` / ``_tokens``; ``plain``, ``chunk``, ``admit``,
``empty``, ``b<p_pad>`` for a bucketed admission) and the host seconds in
which the device had nothing to do (``tick_starved_s``, and by phase), as
deltas over the window. They are exact sums, so a mean is sum / count. A
program without the counters (the parent of the PR that added them) gives
``None`` for each, and the line leaves the metric out."""

from benchmark.readers.engine_spans import _mean_ms

PREFIX = "tick_class_"


def _ticks(d):
    """{class: ticks in the window}."""
    return {k[len(PREFIX):-2]: v for k, v in d.items()
            if k.startswith(PREFIX) and k.endswith("_n")}


def _class_ms(d, name):
    return _mean_ms(d, f"{PREFIX}{name}_wall_s", f"{PREFIX}{name}_n")


def tail_tick_ms(ctx):
    """Mean wall of the ticks that held an admission at the largest bucket
    that HAD ticks in the window (not the largest the engine knows): the
    tick every decoding row waits out, ``steps_per_call`` tokens long."""
    d = ctx.get("stats_delta") or {}
    held = [int(name[1:]) for name, n in _ticks(d).items()
            if n > 0 and name[0] == "b" and name[1:].isdigit()]
    return _class_ms(d, f"b{max(held)}") if held else None


def admit_stall_ms(ctx):
    """What the largest admission costs every decoding row: the tail tick
    minus the mean wall of a tick that holds a decode chunk and nothing
    else."""
    tail = tail_tick_ms(ctx)
    plain = _class_ms(ctx.get("stats_delta") or {}, "plain")
    if tail is None or plain is None:
        return None
    return tail - plain


def tick_starved_ms(ctx):
    """Host time a tick in which the device had nothing to do, over the
    ticks that dispatched anything."""
    d = ctx.get("stats_delta") or {}
    ticks = sum(n for name, n in _ticks(d).items() if name != "empty")
    if "tick_starved_s" not in d or not ticks:
        return None
    return 1e3 * d["tick_starved_s"] / ticks


def starved_over_idle(ctx):
    """The share of the device's idle that the program's own account
    explains: starved seconds between the traced span's two snapshots /
    (the trace's span - the device's busy time). The snapshots are a tick
    narrower than the trace, so a little over 1 is possible."""
    trace = ctx.get("trace") or {}
    d = ctx.get("trace_stats_delta") or {}
    idle = (trace.get("window_s") or 0.0) - (trace.get("busy_s") or 0.0)
    if "tick_starved_s" not in d or idle <= 0.0:
        return None
    return d["tick_starved_s"] / idle


def engine_lock_wait_ms(ctx):
    """``generate()``'s entry until the request is queued in the generator:
    the wait for the scheduler lock the driver holds through a tick."""
    return _mean_ms(ctx.get("stats_delta") or {},
                    "engine_lock_wait_seconds_sum",
                    "engine_lock_wait_seconds_count")
