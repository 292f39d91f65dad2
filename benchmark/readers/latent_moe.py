"""Readers of what the latent-attention / routed-expert decoder adds to the
program: its counters (``RollingGenerator.stats()`` -> ``DecodeEngine
.stats()``) and its Pallas kernels in the device trace. A program without
them (the parent of the PR that added them, another family's cell) gives
``None`` for each."""

from benchmark import families

GROUPED, LATENT_DECODE = "moe_grouped_matmul", "latent_decode_attention"


def _least(ctx, name):
    """The family's own count ``name`` (its ``ops`` module), or None for a
    family that has none."""
    if "config" not in ctx or "dims" not in ctx:
        return None
    ops = getattr(families.load(ctx["config"]), "ops", None)
    return getattr(ops, name, None)


def moe_experts_touched_share(ctx):
    """Experts given at least one token / experts, over the window's decode
    steps and expert layers: what the traffic leaves a step to skip."""
    d = ctx.get("stats_delta") or {}
    if not d.get("moe_expert_slots"):
        return None
    return 100.0 * d["moe_experts_touched"] / d["moe_expert_slots"]


def decode_kv_read_over_live(ctx):
    """Cache positions decode attention fetched / positions the decoding
    rows held, over the window: 1.0 is the least, the block rounding of the
    ragged kernel shows as a little more, the whole grid as a lot."""
    d = ctx.get("stats_delta") or {}
    if not d.get("decode_kv_positions_live"):
        return None
    return d["decode_kv_positions_read"] / d["decode_kv_positions_live"]


def _kernel_seconds(ctx, needle):
    """Device seconds of the operations named ``needle`` in the traced
    span, from the summary's heaviest operations; None when none of them is
    among those (the summary keeps ten names)."""
    ops_ = ((ctx.get("trace") or {}).get("device_ops")) or []
    hits = [s for name, s in ops_ if needle in name]
    return sum(hits) if hits else None


def moe_grouped_roofline(ctx):
    """Least time of the grouped expert products / their device time in
    the traced span. Decode steps count the bytes of the experts they
    touched, prefills the larger of their flops and one read of every
    expert (``opcounts/latent_moe.py``)."""
    secs = _kernel_seconds(ctx, GROUPED)
    delta = ctx.get("trace_stats_delta") or {}
    count = _least(ctx, "grouped_least_seconds")
    if not secs or not delta.get("moe_expert_slots") or count is None:
        return None
    d = ctx["dims"]
    pairs = (delta.get("prefill_tokens_executed", 0) * d["K"]
             * (d["L"] - d["Ld"]))
    least = count(d, ctx["peaks"], delta["moe_experts_touched"], pairs,
                  delta.get("admitted_rows", 0))
    return 100.0 * least / secs


def mla_decode_roofline(ctx):
    """Least time of the absorbed decode attention (the live latents read
    once a layer a step) / the kernel's device time in the traced span."""
    secs = _kernel_seconds(ctx, LATENT_DECODE)
    delta = ctx.get("trace_stats_delta") or {}
    count = _least(ctx, "latent_decode_least_seconds")
    if not secs or not delta.get("decode_kv_positions_live") or count is None:
        return None
    steps = ctx["deployment"]["steps_per_call"]
    least = count(ctx["dims"], ctx["peaks"],
                  delta["decode_kv_positions_live"] * steps)
    return 100.0 * least / secs
