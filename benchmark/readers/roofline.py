"""Shares of the chip's published peaks: the least work of the run's
architecture (its family's counts, ``benchmark/families``) over measured
time, against ``peaks.json``."""

from benchmark import families
from benchmark.readers import device


def decode_hbm_roofline(ctx):
    """Bytes a decode step NEEDS (int8 weights, scales, bf16 head, the live
    keys and values of the active rows) / device time of a step / peak
    bandwidth. Decode is bandwidth-bound; the grid the program reads beyond
    the live positions is its own cost and lowers this share."""
    step_ms = device.decode_step_dev_ms(ctx)
    need = step_ms and families.load(ctx["config"]).decode_step_bytes(ctx)
    if not need:
        return None
    return 100.0 * need / (step_ms / 1e3) / ctx["peaks"]["hbm_bytes_per_s"]


def prefill_mfu(ctx):
    """Flops the prompts NEEDED (2 x params x tokens + causal attention) /
    device time of the prefill executables / bf16 peak. Padded slots and
    chunk positions past a prompt's end are waste and show here."""
    mods = device._modules(ctx, ("prefill",))
    flops = mods and families.load(ctx["config"]).prefill_flops(ctx)
    if not flops:
        return None
    secs = sum(m["total_s"] for m in mods)
    return 100.0 * flops / secs / ctx["peaks"]["bf16_flops"]


def trainer_mfu(ctx):
    """Model flops per token (forward + backward, recomputation not
    counted) x tokens per second per chip / bf16 peak."""
    rate = ctx.get("train_tok_s_chip")
    if not rate:
        return None
    per_tok = families.load(ctx["config"]).train_flops_per_token(ctx)
    return 100.0 * per_tok * rate / ctx["peaks"]["bf16_flops"]
