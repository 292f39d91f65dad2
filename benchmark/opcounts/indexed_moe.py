"""Operations and bytes of the indexed-attention / routed-expert decoder,
from shapes and from what a step touched.

The LEAST work the algorithm needs, so that a share of a peak cannot pass
100%: an expert no token chose, a position a query did not choose (read to
mask it), an index score of a query that chooses everything, the block
rounding of a kernel, padded prompt positions and the threshold search's
passes are the program's own costs and are not counted here.
"""

from __future__ import annotations

WEIGHT_BYTES = 2        # bf16 weights and cache; router and index w float32
KIND = "indexed_attention"


def attn_params(d: dict) -> int:
    return (d["E"] * (d["H"] + 2 * d["Hkv"]) * d["D"]
            + d["H"] * d["D"] * d["E"])


def index_params(d: dict) -> int:
    """The index's bf16 projections: its queries and its one key."""
    return d["E"] * (d["Hi"] + 1) * d["Di"]


def expert_params(d: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * d["E"] * d["Mx"]


def fixed_weight_bytes(d: dict) -> int:
    """What every decode step reads whatever it routes: attention, the
    index, the float32 router and index weights of every layer, the head."""
    return (WEIGHT_BYTES * (d["L"] * (attn_params(d) + index_params(d))
                            + d["E"] * d["V"])
            + 4 * d["L"] * d["E"] * (d["X"] + d["Hi"]))


def kv_bytes_per_position(d: dict) -> int:
    """Keys and values of one position of one layer."""
    return WEIGHT_BYTES * 2 * d["Hkv"] * d["D"]


def index_bytes_per_position(d: dict) -> int:
    """The index key of one position of one layer."""
    return WEIGHT_BYTES * d["Di"]


def decode_step_bytes(d: dict, experts_touched: float, live_positions: float,
                      chosen_positions: float) -> float:
    """``experts_touched``: experts given at least one token, summed over
    the layers of ONE step; ``live_positions``: positions the active rows
    hold (every layer's index scores each: its key is read);
    ``chosen_positions``: positions the rows' queries chose, summed over the
    layers of one step (``min(depth + 1, topk)`` a row a layer: their K and
    V are read)."""
    return (fixed_weight_bytes(d)
            + WEIGHT_BYTES * expert_params(d) * experts_touched
            + d["L"] * index_bytes_per_position(d) * live_positions
            + kv_bytes_per_position(d) * chosen_positions)


def chosen_pairs(length: int, topk: int) -> float:
    """(query, chosen key) pairs of one sequence: ``min(t + 1, topk)`` a
    query."""
    n = min(length, topk)
    return n * (n + 1) / 2.0 + max(0, length - topk) * float(topk)


def index_pairs(length: int, topk: int) -> float:
    """(query, key) pairs the index has to score for one sequence: ``s <=
    t`` of the queries at ``t >= topk`` (the others choose everything)."""
    if length <= topk:
        return 0.0
    return (length * (length + 1) - topk * (topk + 1)) / 2.0


def prefill_flops(d: dict, prompt_tokens: int, attended_pairs: float,
                  scored_pairs: float) -> float:
    """Forward over whole prompts: 2 flops a parameter a token multiplies
    (K of X experts, the router, the index's projections; the head reads one
    position a prompt and is left out), 4 D flops a (query, chosen key) pair
    a query head (``attended_pairs``: ``chosen_pairs``), 2 Di flops a scored
    pair an index head (``scored_pairs``: ``index_pairs``)."""
    per_token = d["L"] * (attn_params(d) + index_params(d)
                          + d["E"] * (d["X"] + d["Hi"])
                          + d["K"] * expert_params(d))
    return (2.0 * per_token * prompt_tokens
            + d["L"] * (4.0 * d["H"] * d["D"] * attended_pairs
                        + 2.0 * d["Hi"] * d["Di"] * scored_pairs))


# ---------------------------------------------------- the kernels' least
def grouped_least_seconds(d: dict, peaks: dict, decode_touched: float,
                          prefill_pairs: float, prefills: float) -> float:
    """Least time of the grouped expert products, under the name and
    arguments ``readers/latent_moe.py::moe_grouped_roofline`` asks its
    family for (``opcounts/window_moe.py``'s rule)."""
    eb = WEIGHT_BYTES * expert_params(d)
    decode = decode_touched * eb / peaks["hbm_bytes_per_s"]
    prefill = max(2.0 * expert_params(d) * prefill_pairs / peaks["bf16_flops"],
                  prefills * d["L"] * d["X"] * eb / peaks["hbm_bytes_per_s"])
    return decode + prefill


def index_select_least_seconds(d: dict, peaks: dict,
                               scored_pairs: float) -> float:
    """Least time of the admission's index-and-choice kernel: the flops of
    the pairs that HAVE to be scored at the compute peak (its index keys are
    64 lanes a position, read once a query block: far under the bandwidth
    peak; the threshold search is the program's own cost), over the
    layers."""
    return (d["L"] * 2.0 * d["Hi"] * d["Di"] * scored_pairs
            / peaks["bf16_flops"])


def admit_attention_least_seconds(d: dict, peaks: dict,
                                  attended_pairs: float) -> float:
    """Least time of the admission's attention under the choice: the flops
    of the (query, chosen key) pairs at the compute peak, over the layers."""
    return (d["L"] * 4.0 * d["H"] * d["D"] * attended_pairs
            / peaks["bf16_flops"])


def indexed_decode_least_seconds(d: dict, peaks: dict,
                                 chosen_position_steps: float) -> float:
    """Least time of the decode attention under the choice: K and V of the
    chosen positions read once (``chosen_position_steps``: the program's
    count, a row a layer a step; 8 query heads a kv head over a block are
    far under the compute peak)."""
    return (kv_bytes_per_position(d) * chosen_position_steps
            / peaks["hbm_bytes_per_s"])
