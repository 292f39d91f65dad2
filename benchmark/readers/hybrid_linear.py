"""Readers of what the hybrid linear-attention decoder adds to the program:
its counters (``RollingGenerator.stats()`` -> ``DecodeEngine.stats()``) and
its Pallas kernel in the device trace. A program without them (the parent of
the PR that added them, another family's cell) gives ``None`` for each."""

from benchmark import families

KERNEL = "gated_delta_prefill"


def decode_state_rows_over_live(ctx):
    """Rows whose recurrent state a decode step read and wrote / rows that
    decoded, over the window's decode steps: 1.0 is the least; a step that
    carries every row of the grid, idle ones held, shows as ``max_slots /
    live``."""
    d = ctx.get("stats_delta") or {}
    if not d.get("decode_state_rows_live"):
        return None
    return d.get("decode_state_rows_touched", 0) / d["decode_state_rows_live"]


def prefill_scan_over_prompt(ctx):
    """Positions the admissions' recurrent scans walked (a layer; rounded up
    by bucket and chunk) / prompt tokens those admissions held, over the
    window: 1.0 is the least, the bucket's padding shows above it."""
    d = ctx.get("stats_delta") or {}
    if not d.get("linear_scan_prompt_tokens"):
        return None
    return d.get("linear_scan_positions", 0) / d["linear_scan_prompt_tokens"]


def gated_delta_prefill_roofline(ctx):
    """Least time of the chunked scan (``opcounts/hybrid_linear.py``: the
    larger of three ``dk x dv`` products a head a token at the compute peak
    and one read of q, k, v, decay, beta plus one write of o at the
    bandwidth peak, over the real prompt tokens of the traced span) / device
    time of ``%gated_delta_prefill.*`` among the summary's heaviest
    operations; None where none of its call sites is among them."""
    ops_ = ((ctx.get("trace") or {}).get("device_ops")) or []
    secs = sum(s for name, s in ops_ if KERNEL in name)
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "linear_scan_prompt_tokens")
    if not secs or not toks or "config" not in ctx:
        return None
    ops = getattr(families.load(ctx["config"]), "ops", None)
    count = getattr(ops, "gated_delta_least_seconds", None)
    if count is None:
        return None
    return 100.0 * count(ctx["dims"], ctx["peaks"], toks) / secs
