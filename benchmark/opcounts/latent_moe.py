"""Operations and bytes of the latent-attention / routed-expert decoder,
from shapes and from what a step touched.

The LEAST work the algorithm needs, so that a share of a peak cannot pass
100%: an expert no token chose, a grid position past a row's depth, the
lane padding of the cache's leaf, padded prompt positions and the rows of a
row tile that belong to another expert are the program's own costs and are
not counted here.
"""

from __future__ import annotations

WEIGHT_BYTES = 2        # bf16 weights and cache; the router is float32


def attn_params(d: dict) -> int:
    return (d["E"] * d["H"] * (d["dn"] + d["dr"]) + d["E"] * (d["r"] + d["dr"])
            + d["r"] * d["H"] * (d["dn"] + d["dv"]) + d["H"] * d["dv"] * d["E"])


def expert_params(d: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * d["E"] * d["Mx"]


def shared_params(d: dict) -> int:
    return 3 * d["E"] * d["Ns"] * d["Mx"]


def fixed_weight_bytes(d: dict) -> int:
    """What every decode step reads whatever it routes: attention of every
    layer, the dense layers' SwiGLU, the expert layers' shared expert and
    float32 router, the head."""
    n_moe = d["L"] - d["Ld"]
    return (WEIGHT_BYTES * (d["L"] * attn_params(d)
                            + d["Ld"] * 3 * d["E"] * d["Md"]
                            + n_moe * shared_params(d)
                            + d["E"] * d["V"])
            + 4 * n_moe * d["E"] * d["X"])


def latent_bytes_per_position(d: dict) -> int:
    """Latent and rope key of one position of one layer."""
    return WEIGHT_BYTES * (d["r"] + d["dr"])


def decode_step_bytes(d: dict, experts_touched: float,
                      live_positions: float) -> float:
    """``experts_touched``: experts given at least one token, summed over
    the expert layers of ONE step; ``live_positions``: positions the active
    rows hold."""
    return (fixed_weight_bytes(d)
            + WEIGHT_BYTES * expert_params(d) * experts_touched
            + d["L"] * latent_bytes_per_position(d) * live_positions)


def prefill_flops(d: dict, prompt_tokens: int,
                  sum_len_squared: float) -> float:
    """Forward over whole prompts: 2 flops a parameter a token multiplies
    (K of X experts; the head reads one position a prompt and is left out),
    plus causal attention over the lower triangle with key width dn + dr
    beside value width dv."""
    n_moe = d["L"] - d["Ld"]
    per_token = (d["L"] * attn_params(d) + d["Ld"] * 3 * d["E"] * d["Md"]
                 + n_moe * (shared_params(d) + d["E"] * d["X"]
                            + d["K"] * expert_params(d)))
    return (2.0 * per_token * prompt_tokens
            + d["L"] * d["H"] * (d["dn"] + d["dr"] + d["dv"])
            * sum_len_squared)


# ---------------------------------------------------- the kernels' least
def grouped_least_seconds(d: dict, peaks: dict, decode_touched: float,
                          prefill_pairs: float, prefills: float) -> float:
    """Least time of the grouped expert products (gate/up and down):
    decode steps are bound by the bytes of the experts touched; a prefill by
    the larger of its pairs' flops and one read of every expert."""
    eb = WEIGHT_BYTES * expert_params(d)
    decode = decode_touched * eb / peaks["hbm_bytes_per_s"]
    n_moe = d["L"] - d["Ld"]
    prefill = max(2.0 * expert_params(d) * prefill_pairs / peaks["bf16_flops"],
                  prefills * n_moe * d["X"] * eb / peaks["hbm_bytes_per_s"])
    return decode + prefill


def latent_decode_least_seconds(d: dict, peaks: dict,
                                live_position_steps: float) -> float:
    """Least time of the absorbed decode attention: the live positions'
    latents read once a layer a step (the products over 32 heads a block
    are far under the compute peak)."""
    return (d["L"] * latent_bytes_per_position(d) * live_position_steps
            / peaks["hbm_bytes_per_s"])
