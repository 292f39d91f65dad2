"""Weight-only int8 quantization for serving.

Decode on TPU is HBM-bandwidth-bound: every generated token streams the full
parameter set through the MXU once, so byte-halving the weights is worth up
to ~2× decode throughput (v5e: 819 GB/s HBM — see BASELINE.md decode rows).
This module quantizes the transformer matmul weights per output channel to
int8 with a bf16 scale; the model's weight loads (``llama._wload``) fuse the
``int8 → compute-dtype convert × scale`` into the einsum operand read, so
the dequantized matrix is never materialized in HBM.

No reference analogue (the reference ships no model/serving compute at all,
SURVEY.md §2.7); this is part of the owned compute stack.

Usage::

    qparams = quantize_params(params)
    gen = Generator(qparams, cfg, mesh=mesh)   # everything else unchanged

Norms, embeddings, and the router stay in the original dtype: they are a
tiny fraction of the bytes and the quality-sensitive parts.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

# Stacked-layer matmul weights: [L, ..., in_axis, out_axis]. Scales reduce
# over the input axis (second-to-last), one scale per output channel.
QUANT_KEYS: Sequence[str] = (
    "wq", "wk", "wv", "wo",
    "w_gate", "w_up", "w_down",
    "we_gate", "we_up", "we_down",
)


# --- shared absmax/127 rounding core ---------------------------------------
# One int8 quantization implementation for the three call sites that used
# to carry their own copy: the serving weight quantizer below (per-output-
# channel scales), the 8-bit Adam moments (training/quant_opt.py, per-block
# scales), and the quantized dcn allreduce (parallel/collectives.py, per-
# block scales + stochastic rounding). Scale *derivation* stays per-site —
# weight quantization floors absmax at 1e-8, the block paths map absmax==0
# to scale 1.0 — because changing either would silently move bits under
# checkpoints and optimizer state already in the wild.


def quantize_with_scale(x: jax.Array, scale: jax.Array,
                        key: Optional[jax.Array] = None) -> jax.Array:
    """``clip(round(x / scale), ±127)`` as int8 — the shared rounding core.

    ``key``: switch round-to-nearest to *stochastic* rounding
    (``floor(y + u)``, ``u ~ U[0, 1)``): E[q·scale] == x exactly, which
    kills the accumulation bias nearest-rounding builds up when the same
    values are re-quantized every hop of a reduction (EQuARX)."""
    y = x / scale
    if key is None:
        q = jnp.round(y)
    else:
        q = jnp.floor(y + jax.random.uniform(key, y.shape, jnp.float32))
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def block_shape(shape, block: int) -> int:
    """Effective block length along the last axis: ``block`` when it
    divides the axis, else the whole axis (tiny or indivisible)."""
    last = shape[-1] if shape else 1
    if last >= block and last % block == 0:
        return block
    return last


def block_quantize(x: jax.Array, block: int,
                   key: Optional[jax.Array] = None):
    """x [..., n] → (int8 [..., n], f32 scales [..., n//b]) with
    per-block absmax/127 scales along the last axis (zero blocks get
    scale 1.0). ``key`` enables stochastic rounding (see
    :func:`quantize_with_scale`)."""
    b = block_shape(x.shape, block)
    if x.ndim == 0:
        q, s = block_quantize(x[None], block, key)
        return q[0], s[0]
    blocks = x.reshape(x.shape[:-1] + (x.shape[-1] // b, b))
    absmax = jnp.max(jnp.abs(blocks), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = quantize_with_scale(blocks, scale[..., None], key)
    return q.reshape(x.shape), scale.astype(jnp.float32)


def block_dequantize(q: jax.Array, scale: jax.Array, block: int):
    """Inverse of :func:`block_quantize` into float32."""
    b = block_shape(q.shape, block)
    if q.ndim == 0:
        return block_dequantize(q[None], scale[None], block)[0]
    blocks = q.reshape(q.shape[:-1] + (q.shape[-1] // b, b))
    return (blocks.astype(jnp.float32) * scale[..., None]).reshape(q.shape)


def _quantize_leaf(w: jax.Array):
    """→ (int8 weights, per-output-channel scale in w.dtype)."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = quantize_with_scale(w.astype(jnp.float32), scale)
    return q, scale.astype(w.dtype)


# The decode-layout fuse groups — single source of truth shared by
# fuse_decode_layers (weights), lora.stack_adapters (adapter factors),
# and lora.validate_adapter_targets (the fused/unfused mismatch hint).
FUSE_GROUPS = (("wqkv", ("wq", "wk", "wv")),
               ("wgu", ("w_gate", "w_up")))


def fuse_decode_layers(layers: Dict[str, Any]) -> Dict[str, Any]:
    """Pack same-input quantized projections into single weights.

    ``wq+wk+wv → wqkv`` and ``w_gate+w_up → wgu`` (scales concatenated the
    same way). Decode then issues one weight-streaming kernel call where it
    issued three (QKV) / two (gate·up): at 32 layers × 128 steps the fixed
    per-call cost is a measurable slice of the decode step, and larger
    column counts keep the DMA pipeline full longer.

    Serving-only layout: ``llama._block_cached`` / ``_mlp`` read the fused
    keys when present; the training forward and ``dequantize_params`` do
    not (keep the unfused tree for anything but a Generator).
    """
    layers = dict(layers)
    for fused, parts in FUSE_GROUPS:
        if not all(p in layers and p + "_scale" in layers for p in parts):
            continue
        layers[fused] = jnp.concatenate([layers[p] for p in parts], axis=-1)
        layers[fused + "_scale"] = jnp.concatenate(
            [layers[p + "_scale"] for p in parts], axis=-1)
        for p in parts:
            del layers[p], layers[p + "_scale"]
    return layers


def quantize_params(params: Dict[str, Any],
                    keys: Sequence[str] = QUANT_KEYS,
                    quantize_unembed: bool = False) -> Dict[str, Any]:
    """Return a params tree with matmul weights int8-quantized.

    Quantized entries are replaced in place and a ``<name>_scale`` sibling
    is added; all other leaves (embedding, norms, router) pass through
    untouched. The result feeds any cached-forward / Generator path — the
    training step must keep full-precision params.

    ``quantize_unembed``: also quantize the [E, V] output projection
    (untied ``lm_head`` in place; tied embeddings get a dedicated int8
    ``unembed_q`` copy so token-embedding *lookups* keep full precision).
    Off by default: measured **slower** on v5e (2,540 vs 2,708 tok/s
    decode on the 0.8B bench) — XLA materializes the dequantized [E, V]
    matrix for this einsum instead of fusing the convert into the operand
    read, unlike the per-layer weights where the fusion holds.
    """
    layers = dict(params["layers"])
    for name in keys:
        if name not in layers:
            continue
        q, scale = _quantize_leaf(layers[name])
        layers[name] = q
        layers[name + "_scale"] = scale
    out = dict(params)
    out["layers"] = layers
    if quantize_unembed:
        if "lm_head" in out:
            q, scale = _quantize_leaf(out["lm_head"])
            out["lm_head"] = q
            out["lm_head_scale"] = scale
        else:
            q, scale = _quantize_leaf(out["embedding"].T)
            out["unembed_q"] = q
            out["unembed_scale"] = scale
    return out


def quantized_logical_axes(cfg, base: Optional[Dict[str, Any]] = None,
                           quantize_unembed: bool = False):
    """Logical-axis tree matching :func:`quantize_params` output.

    Scales keep the layer axis and replicate the rest (they are ~1/in_dim
    the weight's size — sharding them buys nothing). ``quantize_unembed``
    must match the value passed to :func:`quantize_params` — it decides
    whether the tree carries lm_head/unembed scale entries at all.
    """
    from kubetorch_tpu.models import llama

    axes = base or llama.param_logical_axes(cfg)
    layers = dict(axes["layers"])
    for name in QUANT_KEYS:
        if name not in layers:
            continue
        w_axes = layers[name]
        layers[name + "_scale"] = ("layer",) + (None,) * (len(w_axes) - 1)
    out = dict(axes)
    out["layers"] = layers
    if quantize_unembed:
        if "lm_head" in out:
            out["lm_head_scale"] = (None, None)
        else:
            out["unembed_q"] = ("embed_fsdp", "vocab")
            out["unembed_scale"] = (None, None)
    return out


def dequantize_params(params: Dict[str, Any],
                      dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Materialize full-precision weights back (debug / quality checks)."""
    layers = dict(params["layers"])
    if "wqkv" in layers or "wgu" in layers:
        raise ValueError(
            "fused decode layout (wqkv/wgu) cannot be dequantized — keep "
            "the unfused tree for debugging; fusion is serving-only")
    for name in list(layers):
        if name.endswith("_scale"):
            base = name[: -len("_scale")]
            layers[base] = (layers[base].astype(dtype)
                            * layers[name].astype(dtype))
            del layers[name]
    out = dict(params)
    out["layers"] = layers
    # tied-unembed int8 copy is derived data; the bf16 embedding is the truth
    out.pop("unembed_q", None)
    out.pop("unembed_scale", None)
    if "lm_head_scale" in out:
        out["lm_head"] = (out["lm_head"].astype(dtype)
                          * out.pop("lm_head_scale").astype(dtype))
    return out


def init_quantized(key: jax.Array, cfg,
                   keys: Sequence[str] = QUANT_KEYS,
                   fuse: bool = False, shardings=None) -> Dict[str, Any]:
    """Random params initialized *directly* in int8-quantized form.

    For serving-scale benchmarks and smoke tests of models whose bf16 tree
    exceeds HBM: a Llama-3-8B bf16 tree is ~16 GB — it cannot be
    materialized on a 16 GB v5e chip to be quantized after the fact, but
    the int8 form (~7 GB matmul weights + bf16 embeddings/norms/head)
    fits. Weight *values* are random (throughput doesn't depend on them);
    scales mimic a trained model's magnitude (absmax ≈ 4σ of a 1/√in_dim
    dense init) so logits land in a realistic range for the sampling path.
    The unembedding stays bf16 — int8 there is measured slower (see
    :func:`quantize_params`).

    ``shardings``: a tree of shardings matching the output (built from
    :func:`quantized_logical_axes`) — the tree is then made directly
    sharded over the mesh, never whole on one device.
    """
    pdt = cfg.storage_dtype
    L, E, H, Hkv, D, M, V = (cfg.n_layers, cfg.embed_dim, cfg.n_heads,
                             cfg.n_kv_heads, cfg.head_dim, cfg.mlp_dim,
                             cfg.vocab_size)
    shapes = {
        "wq": (L, E, H * D), "wk": (L, E, Hkv * D), "wv": (L, E, Hkv * D),
        "wo": (L, H * D, E),
    }
    if cfg.moe is None:
        shapes.update({"w_gate": (L, E, M), "w_up": (L, E, M),
                       "w_down": (L, M, E)})
    else:
        X, Me = cfg.moe.num_experts, cfg.moe.expert_mlp_dim
        shapes.update({"we_gate": (L, X, E, Me), "we_up": (L, X, E, Me),
                       "we_down": (L, X, Me, E)})

    def build(key):
        ks = iter(jax.random.split(key, len(shapes) + 4))
        layers: Dict[str, Any] = {
            "attn_norm": jnp.ones((L, E), pdt),
            "mlp_norm": jnp.ones((L, E), pdt),
        }
        for name, shape in shapes.items():
            in_dim = shape[-2]
            if name in keys:
                layers[name] = jax.random.randint(
                    next(ks), shape, -127, 128, jnp.int8)
                layers[name + "_scale"] = jnp.full(
                    shape[:-2] + (1, shape[-1]),
                    4.0 / (in_dim ** 0.5) / 127.0, pdt)
            else:
                # not selected for quantization: full-precision, matching
                # quantize_params' behavior on a keys subset
                layers[name] = jax.random.normal(
                    next(ks), shape, pdt) * (in_dim ** -0.5)
        if cfg.moe is not None:
            layers["router"] = jax.random.normal(
                next(ks), (L, E, cfg.moe.num_experts), pdt) * 0.02
        out: Dict[str, Any] = {
            "embedding": jax.random.normal(next(ks), (V, E), pdt)
            * (E ** -0.5),
            "layers": layers,
            "final_norm": jnp.ones((E,), pdt),
        }
        if not cfg.tie_embeddings:
            out["lm_head"] = jax.random.normal(
                next(ks), (E, V), pdt) * (E ** -0.5)
        if fuse:
            out["layers"] = fuse_decode_layers(out["layers"])
        return out

    return jax.jit(build, out_shardings=shardings)(key)
