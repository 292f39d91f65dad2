"""Readers of what the window / routed-expert decoder adds to the program:
its counters (``RollingGenerator.stats()`` -> ``DecodeEngine.stats()``) and
its kernels' call sites in the device trace. A program without them (the
parent of the PR that added them, another family's cell) gives ``None`` for
each."""

from benchmark.readers.latent_moe import _kernel_seconds, _least

BANDED, RAGGED = "admit_window_attention", "ragged_decode_attention"


def decode_window_read_over_live(ctx):
    """Ring positions decode attention fetched / ring positions the
    decoding rows held (``min(depth, window)`` a row), over the window: 1.0
    is the least, the block rounding of the ragged kernel shows as a little
    more, the whole ring of every slot as a lot."""
    d = ctx.get("stats_delta") or {}
    if not d.get("decode_window_positions_live"):
        return None
    return (d.get("decode_window_positions_read", 0)
            / d["decode_window_positions_live"])


def prefill_window_blocks_over_band(ctx):
    """Key blocks the window layers' admission attention computed / key
    blocks the band touches, over the window's admissions: 1.0 is the
    least; the whole lower triangle of a 16384 bucket is 2.3."""
    d = ctx.get("stats_delta") or {}
    if not d.get("prefill_window_key_blocks_band"):
        return None
    return (d.get("prefill_window_key_blocks", 0)
            / d["prefill_window_key_blocks_band"])


def window_prefill_roofline(ctx):
    """Least time of the window layers' banded admission attention (its
    pairs' flops at the compute peak) / device time of
    ``%admit_window_attention.*`` in the traced span."""
    secs = _kernel_seconds(ctx, BANDED)
    count = _least(ctx, "window_prefill_least_seconds")
    band_pairs = _least(ctx, "band_pairs")
    window = (ctx.get("dims") or {}).get("W")
    if not secs or count is None or band_pairs is None or not window:
        return None
    # the admissions of the traced span are not told apart by length: the
    # span's prompt tokens at the run's own mix of lengths
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "prefill_tokens_executed", 0)
    lens = [r.prompt_len for r in ctx.get("records") or []
            if r.prompt_len > window]
    total = sum(r.prompt_len for r in ctx.get("records") or [])
    if not toks or not lens or not total:
        return None
    pairs = sum(band_pairs(n, window) for n in lens) * toks / total
    return 100.0 * count(ctx["dims"], ctx["peaks"], pairs) / secs


def ragged_decode_roofline(ctx):
    """Least time of the ragged decode attention over the full leaves and
    the rings (the live keys and values read once a layer a step) / the
    kernel's device time in the traced span."""
    secs = _kernel_seconds(ctx, RAGGED)
    delta = ctx.get("trace_stats_delta") or {}
    count = _least(ctx, "ragged_decode_least_seconds")
    if (not secs or count is None
            or not delta.get("decode_window_positions_live")):
        return None
    steps = ctx["deployment"]["steps_per_call"]
    return 100.0 * count(
        ctx["dims"], ctx["peaks"],
        delta["decode_kv_positions_live"] * steps,
        delta["decode_window_positions_live"] * steps) / secs
