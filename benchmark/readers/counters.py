"""Readers of the program's counters, taken as deltas over the window."""


def compile_s(ctx):
    """Backend-compile seconds in the process that holds the chip, up to the
    window (``devstats.watch_compiles``); a cache hit spends its load here."""
    return (ctx.get("compile_before") or {}).get("backend_compile_s")


def rows_per_step(ctx):
    """Rows that emitted a token per decode step: tokens / (decode chunks x
    steps_per_call), from ``DecodeEngine.stats()`` deltas."""
    d = ctx.get("stats_delta") or {}
    steps = d.get("steps", 0) * ctx["deployment"]["steps_per_call"]
    return d["tokens"] / steps if steps else None
