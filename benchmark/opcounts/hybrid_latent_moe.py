"""Operations and bytes of the hybrid latent-attention decoder (KDA layers
beside MLA layers, a share of the experts held), from shapes and from what a
step touched.

The LEAST work the algorithm needs, so that a share of a peak cannot pass
100%: an expert no token chose, a pair sorted or gathered for an absent
expert, a grid position past a row's depth, the state of a row that does not
decode, padded prompt positions, the chunk's own triangular work (the pair
products, the inverse, their float32 passes), the lane padding of the latent
leaf are the program's own costs and are not counted here.
"""

from __future__ import annotations

WEIGHT_BYTES = 2        # bf16 weights and latent plane; the router is float32
STATE_BYTES = 4         # the recurrent state is float32


def layer_counts(d: dict) -> tuple:
    """(KDA layers, MLA layers, expert layers) among the layers run."""
    n_mla = sum(k == "mla_moe" for k in d["kinds"])
    n_dense = sum(k == "kda_dense" for k in d["kinds"])
    return len(d["kinds"]) - n_mla, n_mla, len(d["kinds"]) - n_dense


def kda_params(d: dict) -> int:
    """One KDA mixer: q | k | v, the decay's and the gate's projections,
    out, beta, the convolution."""
    E, H, dk, dv = d["E"], d["H"], d["dk"], d["dv"]
    chan = H * (2 * dk + dv)
    return E * chan + E * H * dk + 2 * E * H * dv + E * H + d["K"] * chan


def mla_params(d: dict) -> int:
    E, H = d["E"], d["H"]
    return (E * H * (d["dn"] + d["dr"]) + E * (d["r"] + d["dr"])
            + d["r"] * H * (d["dn"] + d["dvh"]) + E * H + H * d["dvh"] * E)


def expert_params(d: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * d["E"] * d["Mx"]


def fixed_weight_bytes(d: dict) -> int:
    """What every decode step reads whatever it routes: every mixer, the
    dense layers' SwiGLU, the shared experts and the float32 routers, the
    head's slice (the embedding is gathered: one row a token)."""
    n_kda, n_mla, n_moe = layer_counts(d)
    n_dense = len(d["kinds"]) - n_moe
    return (WEIGHT_BYTES * (n_kda * kda_params(d) + n_mla * mla_params(d)
                            + n_dense * 3 * d["E"] * d["Md"]
                            + n_moe * d["Ns"] * expert_params(d)
                            + d["E"] * d["V"])
            + 4 * n_moe * d["E"] * d["Xr"])


def latent_bytes_per_position(d: dict) -> int:
    """Latent and rope key of one position over the MLA layers."""
    return layer_counts(d)[1] * WEIGHT_BYTES * (d["r"] + d["dr"])


def state_bytes_per_row(d: dict) -> int:
    """The recurrent state of one row over the KDA layers (the convolution's
    tail is 0.6% of it and left out of the least)."""
    return layer_counts(d)[0] * d["H"] * d["dk"] * d["dv"] * STATE_BYTES


def decode_step_bytes(d: dict, experts_touched: float, live_positions: float,
                      live_rows: float) -> float:
    """``experts_touched``: held experts given at least one token, summed
    over the expert layers of ONE step; the live latent positions read once;
    the live rows' state read once and written once."""
    return (fixed_weight_bytes(d)
            + WEIGHT_BYTES * expert_params(d) * experts_touched
            + latent_bytes_per_position(d) * live_positions
            + 2 * state_bytes_per_row(d) * live_rows)


def prefill_flops(d: dict, prompt_tokens: int,
                  sum_len_squared: float) -> float:
    """Forward over whole prompts: 2 flops a parameter a token multiplies
    (of the ``Kx`` chosen experts the share ``X / Xr`` that an even router
    sends here; the head reads one position a prompt and is left out), the
    MLA layers' causal attention over the lower triangle, and the delta
    rule's three ``dk x dv`` products a head a token in the KDA layers."""
    n_kda, n_mla, n_moe = layer_counts(d)
    n_dense = len(d["kinds"]) - n_moe
    per_token = (n_kda * kda_params(d) + n_mla * mla_params(d)
                 + n_dense * 3 * d["E"] * d["Md"]
                 + n_moe * (d["Ns"] * expert_params(d) + d["E"] * d["Xr"]
                            + d["Kx"] * d["X"] / d["Xr"] * expert_params(d)))
    return (2.0 * per_token * prompt_tokens
            + n_mla * d["H"] * (d["dn"] + d["dr"] + d["dvh"])
            * sum_len_squared
            + n_kda * scan_flops_per_token(d) * prompt_tokens)


# ---------------------------------------------------- the kernels' least
def scan_flops_per_token(d: dict) -> int:
    """``(e^G k) S``, ``(e^G q) S`` and ``k^T v'``: three ``dk x dv``
    products a head a token, two flops a multiply-add."""
    return 6 * d["H"] * d["dk"] * d["dv"]


def scan_bytes_per_token(d: dict) -> int:
    """One read of q, k, v (the compute dtype), the decays (float32, one a
    channel) and beta (float32) and one write of o (the compute dtype), a
    head a token."""
    return d["H"] * (WEIGHT_BYTES * (2 * d["dk"] + 2 * d["dv"])
                     + 4 * d["dk"] + 4)


def kda_prefill_least_seconds(d: dict, peaks: dict,
                              prompt_tokens: float) -> float:
    """Least time of the chunked scan over ``prompt_tokens`` real tokens in
    every KDA layer: the larger of its flops at the compute peak and its
    bytes at the bandwidth peak."""
    return layer_counts(d)[0] * prompt_tokens * max(
        scan_flops_per_token(d) / peaks["bf16_flops"],
        scan_bytes_per_token(d) / peaks["hbm_bytes_per_s"])


def kda_step_least_seconds(d: dict, peaks: dict, row_steps: float) -> float:
    """Least time of the decode steps' state update: the state of every row
    that decoded, once each way, in every KDA layer (``row_steps``: rows
    decoding, summed over steps)."""
    return 2 * state_bytes_per_row(d) * row_steps / peaks["hbm_bytes_per_s"]
