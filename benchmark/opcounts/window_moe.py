"""Operations and bytes of the window / routed-expert decoder, from shapes
and from what a step touched.

The LEAST work the algorithm needs, so that a share of a peak cannot pass
100%: an expert no token chose, a position past a row's depth or behind its
window, the block rounding of a kernel's work list, padded prompt positions
and key blocks the band does not touch are the program's own costs and are
not counted here.
"""

from __future__ import annotations

WEIGHT_BYTES = 2        # bf16 weights and cache; the router is float32
FULL, WINDOW = "full_attention", "window_attention"


def attn_params(d: dict) -> int:
    return (d["E"] * (d["H"] + 2 * d["Hkv"]) * d["D"]
            + d["H"] * d["D"] * d["E"])


def expert_params(d: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * d["E"] * d["Mx"]


def layers(d: dict, kind: str) -> int:
    return d["kinds"].count(kind)


def fixed_weight_bytes(d: dict) -> int:
    """What every decode step reads whatever it routes: attention and the
    float32 router of every layer, the head."""
    return (WEIGHT_BYTES * (d["L"] * attn_params(d) + d["E"] * d["V"])
            + 4 * d["L"] * d["E"] * d["X"])


def kv_bytes_per_position(d: dict) -> int:
    """Keys and values of one position of one layer."""
    return WEIGHT_BYTES * 2 * d["Hkv"] * d["D"]


def decode_step_bytes(d: dict, experts_touched: float, live_positions: float,
                      live_window_positions: float) -> float:
    """``experts_touched``: experts given at least one token, summed over
    the layers of ONE step; ``live_positions``: positions the active rows
    hold (what a full layer reads); ``live_window_positions``: the sum of
    ``min(depth, window)`` over them (what a window layer reads)."""
    return (fixed_weight_bytes(d)
            + WEIGHT_BYTES * expert_params(d) * experts_touched
            + kv_bytes_per_position(d) * (
                layers(d, FULL) * live_positions
                + layers(d, WINDOW) * live_window_positions))


def band_pairs(length: int, window: int) -> float:
    """(query, key) pairs of one sequence under the band ``i - window < j
    <= i``."""
    n = min(length, window)
    return n * (n + 1) / 2.0 + max(0, length - window) * float(window)


def prefill_flops(d: dict, prompt_tokens: int, causal_pairs: float,
                  window_pairs: float) -> float:
    """Forward over whole prompts: 2 flops a parameter a token multiplies
    (K of X experts and the router; the head reads one position a prompt
    and is left out), plus attention: 4 D flops a (query, key) pair a query
    head, over the lower triangle in a full layer (``causal_pairs``) and
    over the band in a window layer (``window_pairs``)."""
    per_token = d["L"] * (attn_params(d) + d["E"] * d["X"]
                          + d["K"] * expert_params(d))
    return (2.0 * per_token * prompt_tokens
            + 4.0 * d["H"] * d["D"] * (layers(d, FULL) * causal_pairs
                                       + layers(d, WINDOW) * window_pairs))


# ---------------------------------------------------- the kernels' least
def grouped_least_seconds(d: dict, peaks: dict, decode_touched: float,
                          prefill_pairs: float, prefills: float) -> float:
    """Least time of the grouped expert products (gate/up and down), under
    the name and arguments ``readers/latent_moe.py::moe_grouped_roofline``
    asks its family for: decode steps are bound by the bytes of the experts
    touched; a prefill by the larger of its pairs' flops and one read of
    every expert of every layer."""
    eb = WEIGHT_BYTES * expert_params(d)
    decode = decode_touched * eb / peaks["hbm_bytes_per_s"]
    prefill = max(2.0 * expert_params(d) * prefill_pairs / peaks["bf16_flops"],
                  prefills * d["L"] * d["X"] * eb / peaks["hbm_bytes_per_s"])
    return decode + prefill


def ragged_decode_least_seconds(d: dict, peaks: dict, full_position_steps:
                                float, window_position_steps: float) -> float:
    """Least time of the ragged decode attention over both kinds of leaf:
    the live keys and values read once a layer a step (7 query heads a kv
    head over a block are far under the compute peak)."""
    return (kv_bytes_per_position(d)
            * (layers(d, FULL) * full_position_steps
               + layers(d, WINDOW) * window_position_steps)
            / peaks["hbm_bytes_per_s"])


def window_prefill_least_seconds(d: dict, peaks: dict,
                                 window_pairs: float) -> float:
    """Least time of the window layers' banded admission attention: its
    pairs' flops at the compute peak (its K and V are read a few times a
    query head, far under the bandwidth peak at 1024-key blocks)."""
    return (4.0 * d["H"] * d["D"] * layers(d, WINDOW) * window_pairs
            / peaks["bf16_flops"])
