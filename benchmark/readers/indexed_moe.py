"""Readers of what the indexed-attention / routed-expert decoder adds to the
program: its counters (``RollingGenerator.stats()`` -> ``DecodeEngine
.stats()``) and its kernels' call sites in the device trace. A program
without them (the parent of the PR that added them, another family's cell)
gives ``None`` for each."""

from benchmark.readers.latent_moe import _kernel_seconds, _least

SELECT, ADMIT, DECODE = ("index_select", "admit_indexed_attention",
                         "indexed_decode_attention")


def decode_sparse_read_over_chosen(ctx):
    """K/V positions decode attention fetched / positions the decoding
    rows' queries chose (``min(depth + 1, topk)`` a row a layer a step),
    over the window: 1.0 is the least (a fetch of the chosen alone); a row
    read to its depth and masked is ``depth / topk``."""
    d = ctx.get("stats_delta") or {}
    if not d.get("decode_sparse_positions_chosen"):
        return None
    return (d.get("decode_sparse_positions_read", 0)
            / d["decode_sparse_positions_chosen"])


def prefill_index_pairs_over_needed(ctx):
    """(query, key) pairs the admissions' index scored / pairs the real
    prompts needed scored (``s <= t`` of their queries at ``t >= topk``),
    over the window's admissions: 1.0 is the least; block rounding and the
    queries under ``topk`` that share a block with one past it show as
    more."""
    d = ctx.get("stats_delta") or {}
    if not d.get("prefill_index_pairs_needed"):
        return None
    return (d.get("prefill_index_pairs_scored", 0)
            / d["prefill_index_pairs_needed"])


def _span_pairs(ctx, pairs_of):
    """``pairs_of(length, topk)`` over the prompts the traced span admitted.
    Its admissions are not told apart by length: the span's prompt tokens at
    the run's own mix of lengths."""
    topk = (ctx.get("dims") or {}).get("topk")
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "prefill_tokens_executed", 0)
    lens = [r.prompt_len for r in ctx.get("records") or []]
    if not topk or not toks or not lens or pairs_of is None:
        return None
    return sum(pairs_of(n, topk) for n in lens) * toks / sum(lens)


def _admission_roofline(ctx, kernel, least, pairs_of):
    secs = _kernel_seconds(ctx, kernel)
    count = _least(ctx, least)
    pairs = _span_pairs(ctx, _least(ctx, pairs_of))
    if not secs or count is None or not pairs:
        return None
    return 100.0 * count(ctx["dims"], ctx["peaks"], pairs) / secs


def index_select_roofline(ctx):
    """Least time of the admission's index-and-choice kernel (the flops of
    the pairs that have to be scored, at the compute peak) / device time of
    ``%index_select.*`` in the traced span."""
    return _admission_roofline(ctx, SELECT, "index_select_least_seconds",
                               "index_pairs")


def admit_indexed_attention_roofline(ctx):
    """Least time of the admission's attention under the choice (the flops
    of the chosen pairs, at the compute peak) / device time of
    ``%admit_indexed_attention.*`` in the traced span."""
    return _admission_roofline(ctx, ADMIT, "admit_attention_least_seconds",
                               "chosen_pairs")


def indexed_decode_attention_roofline(ctx):
    """Least time of the decode attention under the choice (K and V of the
    chosen positions read once, at the bandwidth peak) / device time of
    ``%indexed_decode_attention.*`` in the traced span."""
    secs = _kernel_seconds(ctx, DECODE)
    delta = ctx.get("trace_stats_delta") or {}
    count = _least(ctx, "indexed_decode_least_seconds")
    if (not secs or count is None
            or not delta.get("decode_sparse_positions_chosen")):
        return None
    return 100.0 * count(ctx["dims"], ctx["peaks"],
                         delta["decode_sparse_positions_chosen"]) / secs
