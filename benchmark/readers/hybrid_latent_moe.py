"""Readers of what the hybrid latent-attention decoder adds to the program:
its counters (``RollingGenerator.stats()`` -> ``DecodeEngine.stats()``) and
its Pallas kernels in the device trace. A program without them (the parent of
the PR that added them, another family's cell) gives ``None`` for each."""

from benchmark import families
from benchmark.readers.latent_moe import _kernel_seconds

PREFILL, STEP = "kda_prefill", "kda_step"


def _least(ctx, name):
    """The family's own count ``name`` (its ``ops`` module), or None for a
    family that has none."""
    if "config" not in ctx or "dims" not in ctx or not ctx.get("peaks"):
        return None
    ops = getattr(families.load(ctx["config"]), "ops", None)
    return getattr(ops, name, None)


def moe_held_over_routed(ctx):
    """Pairs whose expert this chip holds / pairs the router made, over the
    window's decode steps and expert layers: the share of the expert layer's
    work that is done HERE (0.25 for a quarter of the experts under even
    routing); the rest is the absent holders', computed by nobody."""
    d = ctx.get("stats_delta") or {}
    if not d.get("moe_assignments_step"):
        return None
    return d.get("moe_assignments_held", 0) / d["moe_assignments_step"]


def kda_prefill_roofline(ctx):
    """Least time of the chunked KDA scan (``opcounts/hybrid_latent_moe.py``:
    the larger of three ``dk x dv`` products a head a token at the compute
    peak and one read of q, k, v, decays, beta plus one write of o at the
    bandwidth peak, over the real prompt tokens of the traced span) / device
    time of ``%kda_prefill.*`` among the summary's heaviest operations."""
    secs = _kernel_seconds(ctx, PREFILL)
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "linear_scan_prompt_tokens")
    count = _least(ctx, "kda_prefill_least_seconds")
    if not secs or not toks or count is None:
        return None
    return 100.0 * count(ctx["dims"], ctx["peaks"], toks) / secs


def kda_step_roofline(ctx):
    """Least time of the decode steps' state update (the decoding rows'
    state once each way at the bandwidth peak) / device time of
    ``%kda_step.*`` in the traced span."""
    secs = _kernel_seconds(ctx, STEP)
    rows = (ctx.get("trace_stats_delta") or {}).get("decode_state_rows_live")
    count = _least(ctx, "kda_step_least_seconds")
    if not secs or not rows or count is None:
        return None
    return 100.0 * count(ctx["dims"], ctx["peaks"], rows) / secs
