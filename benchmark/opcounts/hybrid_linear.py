"""Operations and bytes of the hybrid linear-attention decoder, from shapes
and from what a step touched.

The LEAST work the algorithm needs, so that a share of a peak cannot pass
100%: a grid position past a row's depth, the state of a row that does not
decode, padded prompt positions, the chunk's own triangular work and the
lane padding of the kernel's blocks are the program's own costs and are not
counted here.
"""

from __future__ import annotations

WEIGHT_BYTES = 2        # bf16 weights and K/V
STATE_BYTES = 4         # the recurrent state is float32


def linear_params(d: dict) -> int:
    """The mixer of one linear layer: q, k, v, gate, out, a and b, conv."""
    E, H, dk, dv = d["E"], d["Hl"], d["dk"], d["dv"]
    chan = H * (2 * dk + dv)
    return E * chan + 2 * E * H * dv + 2 * E * H + d["K"] * chan


def full_params(d: dict) -> int:
    return 4 * d["E"] * d["H"] * d["D"]


def mlp_params(d: dict) -> int:
    return 3 * d["E"] * d["M"]


def layer_counts(d: dict) -> tuple:
    n_lin = sum(k == "linear_attention" for k in d["kinds"])
    return n_lin, len(d["kinds"]) - n_lin


def weight_bytes(d: dict) -> int:
    """What every decode step reads: every layer's matrices and the head
    (the embedding is gathered: one row a token)."""
    n_lin, n_full = layer_counts(d)
    return WEIGHT_BYTES * (n_lin * linear_params(d) + n_full * full_params(d)
                           + len(d["kinds"]) * mlp_params(d)
                           + d["E"] * d["V"])


def kv_bytes_per_position(d: dict) -> int:
    """K and V of one position over the full-attention layers."""
    return layer_counts(d)[1] * 2 * d["H"] * d["D"] * WEIGHT_BYTES


def state_bytes_per_row(d: dict) -> int:
    """The recurrent state of one row over the linear layers (the
    convolution's tail is 3% of it and left out of the least)."""
    return layer_counts(d)[0] * d["Hl"] * d["dk"] * d["dv"] * STATE_BYTES


def decode_step_bytes(d: dict, live_positions: float,
                      live_rows: float) -> float:
    """Weights and head once, the live K/V of the full layers read once,
    the live rows' state read once and written once."""
    return (weight_bytes(d) + kv_bytes_per_position(d) * live_positions
            + 2 * state_bytes_per_row(d) * live_rows)


def prefill_flops(d: dict, prompt_tokens: int,
                  sum_len_squared: float) -> float:
    """Forward over whole prompts: 2 flops a parameter a token multiplies
    (the head reads one position a prompt and is left out), causal attention
    over the lower triangle in the full layers, and the delta rule's three
    ``dk x dv`` products a head a token in the linear ones."""
    n_lin, n_full = layer_counts(d)
    per_token = (n_lin * linear_params(d) + n_full * full_params(d)
                 + len(d["kinds"]) * mlp_params(d))
    return (2.0 * per_token * prompt_tokens
            + n_full * d["H"] * 2 * d["D"] * sum_len_squared
            + n_lin * scan_flops_per_token(d) * prompt_tokens)


# ---------------------------------------------------- the kernel's least
def scan_flops_per_token(d: dict) -> int:
    """``w S``, ``q S`` and ``k^T v'``: three ``dk x dv`` products a head a
    token, two flops a multiply-add."""
    return 6 * d["Hl"] * d["dk"] * d["dv"]


def scan_bytes_per_token(d: dict) -> int:
    """One read of q, k, v (the compute dtype), the decay and beta
    (float32) and one write of o (the compute dtype), a head a token."""
    return d["Hl"] * (WEIGHT_BYTES * (2 * d["dk"] + 2 * d["dv"]) + 2 * 4)


def gated_delta_least_seconds(d: dict, peaks: dict,
                              prompt_tokens: float) -> float:
    """Least time of the chunked scan over ``prompt_tokens`` real tokens in
    every linear layer: the larger of its flops at the compute peak and its
    bytes at the bandwidth peak."""
    n_lin = layer_counts(d)[0]
    return n_lin * prompt_tokens * max(
        scan_flops_per_token(d) / peaks["bf16_flops"],
        scan_bytes_per_token(d) / peaks["hbm_bytes_per_s"])
