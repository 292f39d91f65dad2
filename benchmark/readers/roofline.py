"""Shares of the chip's published peaks: least work from shapes
(``benchmark/opcounts``) over measured time, against ``peaks.json``."""

from benchmark.opcounts import llama_dense as ops
from benchmark.readers import device


def decode_hbm_roofline(ctx):
    """Bytes a decode step NEEDS (int8 weights, scales, bf16 head, the live
    keys and values of the active rows) / device time of a step / peak
    bandwidth. Decode is bandwidth-bound; the grid the program reads beyond
    the live positions is its own cost and lowers this share."""
    step_ms = device.decode_step_dev_ms(ctx)
    live = (ctx.get("trace_live") or {}).get("positions")
    if not step_ms or live is None:
        return None
    need = ops.decode_step_bytes(ctx["dims"], ctx["config"]["kv_dtype"], live)
    return 100.0 * need / (step_ms / 1e3) / ctx["peaks"]["hbm_bytes_per_s"]


def prefill_mfu(ctx):
    """Flops the prompts NEEDED (2 x params x tokens + causal attention) /
    device time of the prefill executables / bf16 peak. Padded slots and
    chunk positions past a prompt's end are waste and show here."""
    mods = device._modules(ctx, ("prefill",))
    d = ctx.get("trace_stats_delta") or {}
    toks = d.get("prefill_tokens_executed", 0)
    if not mods or not toks:
        return None
    # attention of the traced tokens at the mix's mean prompt length: a
    # chunk at depth p attends p positions, the mean over a prompt is n/2
    mean_len = ctx.get("mean_prompt_len") or 0.0
    flops = ops.prefill_flops(ctx["dims"], toks, toks * mean_len)
    secs = sum(m["total_s"] for m in mods)
    return 100.0 * flops / secs / ctx["peaks"]["bf16_flops"]


def trainer_mfu(ctx):
    """Model flops per token (forward + backward, recomputation not
    counted) x tokens per second per chip / bf16 peak."""
    rate = ctx.get("train_tok_s_chip")
    if not rate:
        return None
    per_tok = ops.train_flops_per_token(ctx["dims"], ctx["seq"])
    return 100.0 * per_tok * rate / ctx["peaks"]["bf16_flops"]
