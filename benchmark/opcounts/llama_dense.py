"""Operations and bytes of the dense Llama/Mistral decoder, from shapes.

The LEAST work the algorithm needs, so that a share of a peak cannot pass
100%: padded slots, recomputation under remat, positions of the KV grid
beyond what a row has written and dequantised copies are the program's own
costs and are not counted here.
"""

from __future__ import annotations


def layer_matmul_params(d: dict) -> int:
    attn = d["E"] * (d["H"] + 2 * d["Hkv"]) * d["D"] + d["H"] * d["D"] * d["E"]
    return attn + 3 * d["E"] * d["M"]


def matmul_params(d: dict) -> int:
    """Parameters that a token multiplies: the layers and the untied head
    (the embedding is a lookup)."""
    return d["L"] * layer_matmul_params(d) + d["E"] * d["V"]


def total_params(d: dict) -> int:
    return matmul_params(d) + d["V"] * d["E"] + (2 * d["L"] + 1) * d["E"]


def prefill_flops(d: dict, prompt_tokens: int, sum_len_squared: float) -> float:
    """Forward over whole prompts: 2 flops per matmul parameter per token,
    plus causal attention, QK^T and PV over the lower triangle:
    2 * H * D * n^2 a layer for a prompt of n tokens."""
    return (2.0 * matmul_params(d) * prompt_tokens
            + 2.0 * d["L"] * d["H"] * d["D"] * sum_len_squared)


def kv_bytes_per_position(d: dict, kv_dtype: str) -> int:
    """K and V of one position over all layers; int8 carries one f32 scale
    per head vector."""
    per_vec = d["D"] + 4 if kv_dtype == "int8" else 2 * d["D"]
    return 2 * d["L"] * d["Hkv"] * per_vec


def serving_weight_bytes(d: dict) -> int:
    """int8 matrices, their bf16 per-channel scales, bf16 head; what one
    decode step has to read whatever the batch."""
    outs = (d["H"] + 2 * d["Hkv"]) * d["D"] + d["E"] + 2 * d["M"] + d["E"]
    return (d["L"] * layer_matmul_params(d) + 2 * d["L"] * outs
            + 2 * d["E"] * d["V"])


def decode_step_bytes(d: dict, kv_dtype: str, live_positions: float) -> float:
    """Bytes one decode step needs: the weights once, and the keys and
    values the active rows have actually written."""
    return (serving_weight_bytes(d)
            + kv_bytes_per_position(d, kv_dtype) * live_positions)


def train_flops_per_token(d: dict, seq: int) -> float:
    """Forward + backward model flops per token (copy of
    ``bench.py::_train_flops_per_token``): 6 per matmul parameter, the
    embedding lookup excluded, plus causal attention's 6 * L * S * H * D.
    Recomputation under remat is not counted."""
    return 6.0 * matmul_params(d) + 6.0 * d["L"] * seq * d["H"] * d["D"]
