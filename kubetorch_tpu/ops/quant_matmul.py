"""Pallas TPU kernel: int8-weight matmul — OPT-IN (``KT_QMM_DECODE=1``).

Measured on v5e (B=64, 8B shapes, differenced-repeat timing to cancel
dispatch overhead):

- standalone per-layer weight arrays: **743 GB/s** effective stream (91%
  of the 819 GB/s HBM peak) — the kernel clearly beats a standalone XLA
  dot there;
- under the model's real structure (``lax.scan`` over **stacked**
  ``[L, K, N]`` weights): kernel **380 GB/s** vs XLA fused-dequant einsum
  **583 GB/s**. A pallas call is a custom call, and custom-call operands
  must be materialized buffers — each layer's weight slice is copied out
  of the stacked array before the kernel runs (extra read+write of every
  weight byte), while XLA fuses the scan's dynamic-slice AND the
  ``convert × scale`` dequant directly into the dot's operand read.

The decode path therefore uses the einsum (``llama._wload``) by default;
set ``KT_QMM_DECODE=1`` to re-enable the kernel for experiments or for
model layouts with unstacked weights. Kept (with tests) as the measured
record of why the "obvious" kernel is not the fast path on TPU — the
8B decode win came from keeping the KV cache in the scan carry plus this
einsum fusion, not from hand-written matmuls.

Numerics: ``out == (x @ w_int8.astype(bf16)) * scale`` with f32
accumulation — associativity-equal to the XLA path's
``x @ (w_int8 * scale)``.

No reference analogue (the reference ships no serving compute, SURVEY.md
§2.7).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Per-kernel VMEM budget (bytes). The hard scoped-vmem limit observed on
# v5e is 16 MiB; stay under it with room for Mosaic's own scratch.
_VMEM_BUDGET = 12 * 1024 * 1024


def _kernel(x_ref, w_ref, s_ref, o_ref):
    w = w_ref[...].astype(jnp.bfloat16)
    acc = jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def pick_block_n(b: int, k: int, n: int) -> Optional[int]:
    """Largest lane-aligned column block whose double-buffered weight tile
    plus resident activation fits the VMEM budget; None if none divides N."""
    for bn in (512, 256, 128):
        if n % bn:
            continue
        need = 2 * k * bn + 2 * b * k + 4 * b * bn + 2 * bn
        if need <= _VMEM_BUDGET:
            return bn
    return None


def int8_matmul(x: jax.Array, w_q: jax.Array, scale: jax.Array, *,
                block_n: Optional[int] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """``x @ (w_q * scale)`` with the dequant fused into the stream.

    x: [B, K] float (bf16/f32); w_q: [K, N] int8; scale: [N] or [1, N] in
    any float dtype. Returns [B, N] in ``x.dtype``.
    """
    B, K = x.shape
    Kw, N = w_q.shape
    if Kw != K:
        raise ValueError(f"x K={K} vs w K={Kw}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bn = block_n or pick_block_n(B, K, N)
    if bn is None:
        raise ValueError(f"no block size divides N={N}")
    scale2d = scale.reshape(1, N)
    return pl.pallas_call(
        _kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((B, K), lambda j: (0, 0)),
            pl.BlockSpec((K, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((B, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        interpret=interpret,
    )(x, w_q, scale2d)


def decode_matmul_viable(x: jax.Array, w: jax.Array, scale) -> bool:
    """Trace-time gate for the kernel path: explicitly enabled
    (``KT_QMM_DECODE=1`` — see module docstring: the einsum beats this
    kernel under scanned stacked weights), int8 weights, a decode-shaped
    (few-token) activation, a real TPU backend, and no live multi-device
    mesh (under GSPMD an unpartitioned pallas call would force operand
    all-gathers — the einsum path stays sharding-transparent)."""
    from kubetorch_tpu.config import env_bool

    if not env_bool("KT_QMM_DECODE"):
        return False
    if scale is None or w.dtype != jnp.int8:
        return False
    tokens = 1
    for d in x.shape[:-1]:
        tokens *= d
    if tokens > 256:
        return False  # compute-bound regime: MXU-friendly einsum wins
    if jax.default_backend() == "cpu":
        return False
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and mesh.size > 1:
        return False
    return pick_block_n(tokens, x.shape[-1], w.shape[-1]) is not None
