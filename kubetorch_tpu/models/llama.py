"""Llama-3-style decoder, functional and mesh-parallel.

Design notes (TPU-first, not a torch translation):

- **Stacked + scanned layers**: every per-layer weight has a leading
  ``[n_layers, ...]`` dim and the forward pass is one ``lax.scan`` — compile
  time stays O(1) in depth and XLA sees a single fused block body.
- **Logical axes**: :func:`param_logical_axes` returns a pytree (same
  structure as params) of logical-axis tuples; combined with
  :class:`~kubetorch_tpu.parallel.sharding.ShardingRules` this yields
  NamedShardings for any dp/fsdp/tp/sp/ep layout.
- **GQA + RoPE + SwiGLU**, float32 softmax/norm accumulation, bf16 weights.
- **Optional MoE** (top-k router, expert axis sharded over ``ep``): two
  dispatch engines — ``dense`` (every expert on every token, exact) and
  ``capacity`` (GShard-style fixed-capacity scatter/gather dispatch,
  num_experts/top_k fewer FLOPs at static shapes; +35% measured).

The reference framework has no model code at all (SURVEY.md §2.7 — parallelism
and models live in user examples); this module is the "flagship model" a
TPU-native framework must own to hit BASELINE.md targets #3/#5.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from kubetorch_tpu.models.configs import LlamaConfig
from kubetorch_tpu.ops import apply_rope, dot_product_attention, rms_norm, rope_angles
from kubetorch_tpu.ops import decode_attention, grid_write, quant_matmul
from kubetorch_tpu.ops.cached_attention import (
    cached_attn, cached_attn_merged, cached_attn_merged_q, cached_attn_q,
    cached_attn_ragged)
from kubetorch_tpu.ops.flash_attention import (
    prefill_attention, prefill_engages)
from kubetorch_tpu.parallel.sharding import ShardingRules, shard_constraint

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _dense_init(key, shape, dtype, in_axis=-2):
    fan_in = shape[in_axis]
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * (fan_in ** -0.5)).astype(dtype)


def init(key: jax.Array, cfg: LlamaConfig) -> Params:
    """Initialize parameters (host-side; wrap in jit with out_shardings to
    initialize directly sharded on a mesh)."""
    pdt = cfg.storage_dtype
    L, E, H, Hkv, D, M, V = (cfg.n_layers, cfg.embed_dim, cfg.n_heads,
                             cfg.n_kv_heads, cfg.head_dim, cfg.mlp_dim,
                             cfg.vocab_size)
    keys = jax.random.split(key, 16)
    layers: Params = {
        "attn_norm": jnp.ones((L, E), pdt),
        "wq": _dense_init(keys[0], (L, E, H * D), pdt),
        "wk": _dense_init(keys[1], (L, E, Hkv * D), pdt),
        "wv": _dense_init(keys[2], (L, E, Hkv * D), pdt),
        "wo": _dense_init(keys[3], (L, H * D, E), pdt),
        "mlp_norm": jnp.ones((L, E), pdt),
    }
    if cfg.moe is None:
        layers.update({
            "w_gate": _dense_init(keys[4], (L, E, M), pdt),
            "w_up": _dense_init(keys[5], (L, E, M), pdt),
            "w_down": _dense_init(keys[6], (L, M, E), pdt),
        })
    else:
        n_exp, em = cfg.moe.num_experts, cfg.moe.expert_mlp_dim
        layers.update({
            "router": _dense_init(keys[7], (L, E, n_exp), jnp.float32),
            "we_gate": _dense_init(keys[8], (L, n_exp, E, em), pdt),
            "we_up": _dense_init(keys[9], (L, n_exp, E, em), pdt),
            "we_down": _dense_init(keys[10], (L, n_exp, em, E), pdt,
                                   in_axis=-2),
        })
    params: Params = {
        "embedding": (jax.random.normal(keys[11], (V, E), jnp.float32)
                      * 0.02).astype(pdt),
        "layers": layers,
        "final_norm": jnp.ones((E,), pdt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(keys[12], (E, V), pdt)
    return params


def param_logical_axes(cfg: LlamaConfig) -> Params:
    """Pytree of logical-axis tuples matching :func:`init`'s structure."""
    layers = {
        "attn_norm": ("layer", "embed"),
        "wq": ("layer", "embed_fsdp", "heads"),
        "wk": ("layer", "embed_fsdp", "kv_heads"),
        "wv": ("layer", "embed_fsdp", "kv_heads"),
        "wo": ("layer", "heads", "embed_fsdp"),
        "mlp_norm": ("layer", "embed"),
    }
    if cfg.moe is None:
        layers.update({
            "w_gate": ("layer", "embed_fsdp", "mlp"),
            "w_up": ("layer", "embed_fsdp", "mlp"),
            "w_down": ("layer", "mlp", "embed_fsdp"),
        })
    else:
        layers.update({
            "router": ("layer", "embed", None),
            "we_gate": ("layer", "expert", "embed_fsdp", "mlp"),
            "we_up": ("layer", "expert", "embed_fsdp", "mlp"),
            "we_down": ("layer", "expert", "mlp", "embed_fsdp"),
        })
    axes = {
        "embedding": ("vocab", "embed_fsdp"),
        "layers": layers,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed_fsdp", "vocab")
    return axes


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _moe_block(x, layer, cfg: LlamaConfig, rules: ShardingRules):
    if cfg.moe.dispatch == "capacity":
        return _moe_block_capacity(x, layer, cfg, rules)
    if cfg.moe.dispatch != "dense":
        raise ValueError(f"unknown moe dispatch {cfg.moe.dispatch!r}")
    return _moe_block_dense(x, layer, cfg, rules)


def _wload(layer, name: str, dt):
    """Load a matmul weight in compute dtype.

    When the params tree came through ``models.quant.quantize_params`` the
    entry is int8 with a ``<name>_scale`` sibling; the convert × scale here
    fuses into the consuming einsum's operand read, so decode streams half
    the HBM bytes and never materializes the dequantized matrix.
    """
    w = layer[name].astype(dt)
    scale = layer.get(name + "_scale")
    if scale is not None:
        w = w * scale.astype(dt)
    return w


def _proj(x, layer, name: str, dt):
    """``x [..., K] @ layer[name] [K, N] → [..., N]``.

    The fused-dequant einsum (``_wload``) is the fast path even for int8
    decode: XLA fuses the layer scan's dynamic-slice and the
    ``convert × scale`` into the dot's operand read (583 GB/s measured on
    v5e, vs 380 GB/s for a pallas kernel whose custom-call operands force
    the weight slice to materialize — see ``ops/quant_matmul.py``). The
    kernel remains available behind ``KT_QMM_DECODE=1``.
    """
    w = layer[name]
    scale = layer.get(name + "_scale")
    if quant_matmul.decode_matmul_viable(x, w, scale):
        lead = x.shape[:-1]
        out = quant_matmul.int8_matmul(
            x.reshape(-1, x.shape[-1]), w, scale)
        return out.reshape(*lead, w.shape[-1])
    return jnp.einsum("...k,kn->...n", x, _wload(layer, name, dt))


def _moe_router(x, layer, moe):
    """Softmax router → renormalized top-k (values [.., k], indices [.., k])."""
    gates = jax.nn.softmax(
        jnp.einsum("...e,en->...n", x.astype(jnp.float32),
                   layer["router"].astype(jnp.float32)), axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, moe.top_k)
    top_vals = top_vals / (jnp.sum(top_vals, axis=-1, keepdims=True) + 1e-9)
    return gates, top_vals, top_idx


def _moe_block_dense(x, layer, cfg: LlamaConfig, rules: ShardingRules):
    """Top-k MoE, every expert evaluated densely; sharded over ``ep``.

    Weighting is equivalent to the capacity path's renormalized top-k
    (``_moe_router``) expressed as a dense [.., n_exp] mask."""
    moe = cfg.moe
    gates = jax.nn.softmax(
        jnp.einsum("bse,en->bsn", x.astype(jnp.float32),
                   layer["router"].astype(jnp.float32)), axis=-1)
    thresh = jax.lax.top_k(gates, moe.top_k)[0][..., -1:]
    masked = jnp.where(gates >= thresh, gates, 0.0)
    weights = masked / (jnp.sum(masked, axis=-1, keepdims=True) + 1e-9)

    # Dense expert evaluation: [B,S,n_exp,em]; expert dim rides the ep axis,
    # the contraction over n_exp below becomes a psum over ep under jit.
    h_gate = jnp.einsum("bse,xem->bsxm", x, _wload(layer, "we_gate", x.dtype))
    h_up = jnp.einsum("bse,xem->bsxm", x, _wload(layer, "we_up", x.dtype))
    h = jax.nn.silu(h_gate) * h_up
    h = shard_constraint(h, rules, "batch", "seq", "expert", "mlp")
    out = jnp.einsum("bsxm,xme,bsx->bse", h, _wload(layer, "we_down", x.dtype),
                     weights.astype(x.dtype))
    return out


def _moe_block_capacity(x, layer, cfg: LlamaConfig, rules: ShardingRules):
    """Fixed-capacity token dispatch (GShard-style), static shapes.

    Tokens scatter into a per-expert buffer [X, C, E] (slot position =
    running count of that expert's assignments; overflow beyond capacity C
    is dropped via OOB scatter mode). Experts run ordinary [C, E] matmuls —
    num_experts/top_k fewer FLOPs than dense — and kept slots gather back
    weighted by their renormalized gates. No [tokens, X, C] one-hot is ever
    materialized (GShard's einsum formulation costs O(n·X·C) memory; the
    scatter form is O(n·K + X·C·E)).

    This dispatch DROPS tokens: an assignment past an expert's capacity
    contributes nothing, so its output cannot match a reference that
    computes every chosen expert. Kept for the dense decoder's users who
    train with it; the dropless layer (sorted pairs, grouped products over
    uneven groups) is ``models/latent_moe.py``'s.
    """
    moe = cfg.moe
    B, S, E = x.shape
    n = B * S
    K, X = moe.top_k, moe.num_experts
    x2d = x.reshape(n, E)

    _, top_vals, top_idx = _moe_router(x2d, layer, moe)

    cap = int(np.ceil(n * K / X * moe.capacity_factor))
    e_flat = top_idx.reshape(-1)                        # [n*K] token-major
    # slot position within its expert = how many earlier slots chose it
    onehot = (e_flat[:, None] == jnp.arange(X)[None, :]).astype(jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1)              # [n*K, X]
    pos_flat = jnp.take_along_axis(pos, e_flat[:, None], axis=1)[:, 0]
    keep = pos_flat < cap
    # OOB position → mode="drop" discards overflow tokens
    pos_safe = jnp.where(keep, pos_flat, cap)

    tok = jnp.repeat(jnp.arange(n), K)
    buf = jnp.zeros((X, cap, E), x.dtype)
    buf = buf.at[e_flat, pos_safe].set(x2d[tok], mode="drop")
    buf = shard_constraint(buf, rules, "expert", None, None)

    h = jax.nn.silu(jnp.einsum("xce,xem->xcm", buf, _wload(layer, "we_gate", x.dtype))) \
        * jnp.einsum("xce,xem->xcm", buf, _wload(layer, "we_up", x.dtype))
    h = shard_constraint(h, rules, "expert", None, "mlp")
    y = jnp.einsum("xcm,xme->xce", h, _wload(layer, "we_down", x.dtype))  # [X, C, E]

    gathered = y.at[e_flat, pos_safe].get(
        mode="drop", fill_value=0.0)                     # [n*K, E]
    gathered = gathered * (keep[:, None]
                           * top_vals.reshape(-1)[:, None]).astype(x.dtype)
    out = gathered.reshape(n, K, E).sum(axis=1)
    return out.reshape(B, S, E)


def _remat_policy(cfg: LlamaConfig):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "dots_and_attn":
        # Additionally save the attention output so the backward never
        # re-runs the flash forward kernel (costs B*S*E bf16 per layer).
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("attn_out"))
    if cfg.remat_policy == "dots_no_mlp":
        # Save the narrow per-layer intermediates (qkv projections, attn
        # output, mlp output) but NOT the wide gate/up MLP activations
        # (B*S*mlp_dim each — the bulk of "dots" memory); those recompute
        # in backward. ~4x less activation memory for ~2 extra MLP matmuls
        # — the policy that unlocks larger per-chip batches.
        return jax.checkpoint_policies.save_only_these_names(
            "qkv_q", "qkv_k", "qkv_v", "attn_out", "mlp_out")
    if cfg.remat_policy != "nothing":
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; options: "
            "'nothing', 'dots', 'dots_and_attn', 'dots_no_mlp'")
    return jax.checkpoint_policies.nothing_saveable


def _block(x, layer, sin, cos, cfg: LlamaConfig, rules: ShardingRules,
           segment_ids=None, mesh=None):
    """One decoder block. ``x``: [B, S, E] in compute dtype."""
    dt = cfg.compute_dtype
    B, S, E = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    h = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    q = checkpoint_name(jnp.einsum(
        "bse,ehd->bshd", h, _wload(layer, "wq", dt).reshape(E, H, D)),
        "qkv_q")
    k = checkpoint_name(jnp.einsum(
        "bse,ehd->bshd", h, _wload(layer, "wk", dt).reshape(E, Hkv, D)),
        "qkv_k")
    v = checkpoint_name(jnp.einsum(
        "bse,ehd->bshd", h, _wload(layer, "wv", dt).reshape(E, Hkv, D)),
        "qkv_v")
    q = apply_rope(q, None, cfg.rope_theta, sin=sin, cos=cos)
    k = apply_rope(k, None, cfg.rope_theta, sin=sin, cos=cos)

    ring = (mesh is not None and mesh.shape.get("sp", 1) > 1
            and segment_ids is None)
    if ring:
        # Sequence-parallel exact attention: KV stays seq-sharded and rotates
        # over the sp ring (parallel/ring.py) — no all-gather of KV.
        from kubetorch_tpu.parallel.ring import ring_attention

        q = shard_constraint(q, rules, "batch", "seq", "heads", None)
        k = shard_constraint(k, rules, "batch", "seq", "kv_heads", None)
        v = shard_constraint(v, rules, "batch", "seq", "kv_heads", None)
        attn = ring_attention(q, k, v, mesh, causal=True)
    else:
        q = shard_constraint(q, rules, "batch", "seq", "heads", None)
        # kv gathered over seq (XLA inserts the all-gather when sp shards seq)
        k = shard_constraint(k, rules, "batch", None, "kv_heads", None)
        v = shard_constraint(v, rules, "batch", None, "kv_heads", None)
        impl = cfg.attn_impl
        if impl == "auto":
            # Flash wins decisively once XLA's materialized S×S scores
            # dominate HBM traffic (measured +46% train throughput at
            # S=2048 on v5e — fwd + both Pallas backward kernels).
            impl = "flash" if (S >= 2048 and S % 512 == 0
                               and D % 128 == 0) else "xla"
        if impl == "flash" and segment_ids is None:
            attn = _flash_per_shard(q, k, v, rules)
        else:
            attn = dot_product_attention(q, k, v, causal=True,
                                         segment_ids=segment_ids)
    attn = checkpoint_name(attn.reshape(B, S, H * D), "attn_out")
    x = x + jnp.einsum("bsf,fe->bse", attn, _wload(layer, "wo", dt))
    x = shard_constraint(x, rules, "batch", "seq", None)

    x = x + _mlp(x, layer, cfg, rules)
    return shard_constraint(x, rules, "batch", "seq", None)


def _flash_per_shard(q, k, v, rules: ShardingRules):
    """Causal flash attention under whatever mesh is active. XLA cannot
    partition a Mosaic kernel ("wrap the call in a shard_map"), so on a
    multi-device mesh the kernel runs once per shard: attention is
    independent across batch and (kv-)heads, which is all these layouts
    shard here (sequence parallelism takes the ring path). Mosaic wants
    EVERY mesh axis manual, size one or not; axes an enclosing shard_map
    already made manual stay as they are."""
    from jax.sharding import PartitionSpec

    from kubetorch_tpu.ops.flash_attention import flash_attention

    mesh = jax.sharding.get_abstract_mesh()
    split = {a for a in mesh.axis_names if a not in mesh.manual_axes}
    if mesh.empty or mesh.size == 1 or not split:
        return flash_attention(q, k, v, causal=True)

    def spec(heads: str) -> PartitionSpec:
        dims = []
        for entry in rules.pspec("batch", None, heads, None):
            names = (entry,) if isinstance(entry, str) else (entry or ())
            dims.append(tuple(a for a in names if a in split) or None)
        return PartitionSpec(*dims)

    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        in_specs=(spec("heads"), spec("kv_heads"), spec("kv_heads")),
        out_specs=spec("heads"), axis_names=split, check_vma=False,
    )(q, k, v)


def _mlp(x, layer, cfg: LlamaConfig, rules: ShardingRules, lctx=None):
    """SwiGLU (or MoE) sublayer incl. its pre-norm; returns the residual.
    ``lctx``: per-slot LoRA deltas (multi-adapter serving)."""
    dt = cfg.compute_dtype
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
    if cfg.moe is None:
        if "wgu" in layer:
            # serving layout: gate and up share one weight stream
            gate, up = jnp.split(
                _proj(h, layer, "wgu", dt) + _lora_apply(h, lctx, "wgu"),
                2, axis=-1)
        else:
            gate = _proj(h, layer, "w_gate", dt) \
                + _lora_apply(h, lctx, "w_gate")
            up = _proj(h, layer, "w_up", dt) + _lora_apply(h, lctx, "w_up")
        ff = shard_constraint(jax.nn.silu(gate) * up, rules,
                              "batch", "seq", "mlp")
        out = _proj(ff, layer, "w_down", dt) \
            + _lora_apply(ff, lctx, "w_down")
    else:
        out = _moe_block(h, layer, cfg, rules).astype(dt)
    return checkpoint_name(out, "mlp_out")


def hidden_states(
    params: Params,
    tokens: jax.Array,                      # [B, S] int32
    cfg: LlamaConfig,
    rules: Optional[ShardingRules] = None,
    segment_ids: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    mesh=None,
) -> jax.Array:
    """Decoder stack → final-norm hidden states ``[B, S, E]`` (compute dtype).

    Pass ``mesh`` (with an sp axis > 1) to engage ring attention for
    sequence-parallel long-context training.
    """
    rules = rules or ShardingRules.default()
    dt = cfg.compute_dtype
    B, S = tokens.shape
    # Gather from a table whose embed dim is unsharded at use: looking up
    # straight from the ("vocab","embed_fsdp") at-rest layout makes the
    # output embed-sharded, and XLA can only reach the batch-sharded
    # constraint below via involuntary full rematerialization. Dropping
    # the fsdp embed sharding first costs one all-gather of the local
    # vocab shard; the vocab(tp) sharding stays (masked gather + psum).
    emb = shard_constraint(params["embedding"].astype(dt), rules,
                           "vocab", None)
    x = emb[tokens]
    x = shard_constraint(x, rules, "batch", "seq", None)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    block = _block
    if cfg.remat:
        block = jax.checkpoint(
            _block, policy=_remat_policy(cfg), static_argnums=(4, 5, 7))

    def scan_body(carry, layer):
        return block(carry, layer, sin, cos, cfg, rules, segment_ids,
                     mesh), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def unembedding(params: Params, cfg: LlamaConfig) -> jax.Array:
    """The [E, V] output projection (tied → embedding transpose).

    Prefers the int8 forms ``models.quant.quantize_params`` installs:
    ``unembed_q`` (tied — keeps the bf16 embedding table for lookups) or an
    in-place quantized ``lm_head``."""
    dt = cfg.compute_dtype
    if "unembed_q" in params:
        return (params["unembed_q"].astype(dt)
                * params["unembed_scale"].astype(dt))
    if not cfg.tie_embeddings:
        head = params["lm_head"].astype(dt)
        scale = params.get("lm_head_scale")
        return head * scale.astype(dt) if scale is not None else head
    return params["embedding"].T.astype(dt)


def forward(
    params: Params,
    tokens: jax.Array,                      # [B, S] int32
    cfg: LlamaConfig,
    rules: Optional[ShardingRules] = None,
    segment_ids: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    mesh=None,
) -> jax.Array:
    """Full-sequence forward pass → logits ``[B, S, vocab]`` (float32)."""
    rules = rules or ShardingRules.default()
    x = hidden_states(params, tokens, cfg, rules, segment_ids, positions,
                      mesh)
    logits = jnp.einsum("bse,ev->bsv", x, unembedding(params, cfg))
    logits = shard_constraint(logits, rules, "batch", "seq", "vocab")
    return logits.astype(jnp.float32)


def forward_pipeline(
    params: Params,
    tokens: jax.Array,
    cfg: LlamaConfig,
    mesh,
    n_microbatches: int = 2,
    positions: Optional[jax.Array] = None,
    rules=None,
) -> jax.Array:
    """Pipeline-parallel forward: layers grouped into ``pp`` stages, GPipe
    microbatching via :func:`kubetorch_tpu.parallel.pipeline.pipeline_apply`.

    Embedding/unembedding run outside the pipeline (replicated); the decoder
    stack streams through stages. Layer count must divide the pp axis size.

    ``rules`` should be the stage-consistent
    :meth:`~kubetorch_tpu.parallel.sharding.ShardingRules.pipeline` variant
    (the default here) **and** the same rules the train state was
    initialized with — then the stacked layer params enter the pipeline's
    shard_map in their at-rest sharding (stage dim on pp, weight dims on
    fsdp, gathered ZeRO-style inside the body) and XLA inserts no
    resharding at the boundary. Batch rows shard over (dp, fsdp): each
    data-parallel group pipelines its own rows, so fsdp is simultaneously
    data-parallel and param-sharded.
    """
    from kubetorch_tpu.parallel.pipeline import pipeline_apply
    from kubetorch_tpu.parallel.sharding import ShardingRules

    rules = rules or ShardingRules.pipeline()
    pp = mesh.shape["pp"]
    L = cfg.n_layers
    if L % pp:
        raise ValueError(f"n_layers {L} not divisible by pp {pp}")
    # Inside shard_map the mesh axes are consumed — use unsharded rules.
    null_rules = ShardingRules(rules=tuple(
        (name, None) for name, _ in rules.rules))

    dt = cfg.compute_dtype
    B, S = tokens.shape
    emb = shard_constraint(params["embedding"].astype(dt), rules,
                           "vocab", None)  # see hidden_states
    x = emb[tokens]
    x = shard_constraint(x, rules, "batch", "seq", None)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    # [L, ...] -> [pp, L/pp, ...] stage-major layer grouping. When the
    # layer dim is pp-sharded at rest (pipeline rules), this reshape is a
    # local split — no cross-device movement.
    stage_layers = jax.tree.map(
        lambda a: a.reshape((pp, L // pp) + a.shape[1:]), params["layers"])
    # Per-leaf at-rest specs for the stacked layout: logical
    # ("stage", "layer", *weight_axes) — "stage"→pp, "layer" drops (pp
    # already consumed), weight axes keep their fsdp placement.
    layer_axes = param_logical_axes(cfg)["layers"]
    stage_specs = jax.tree.map(
        lambda ax: rules.pspec("stage", *ax), layer_axes,
        is_leaf=lambda x: isinstance(x, tuple))

    block = _block
    if cfg.remat:
        block = jax.checkpoint(
            _block, policy=_remat_policy(cfg), static_argnums=(4, 5))

    def stage_fn(stage_params, h):
        def body(carry, layer):
            return block(carry, layer, sin, cos, cfg, null_rules, None), None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    batch_axes = rules.mesh_axes("batch")
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    x = pipeline_apply(stage_fn, stage_layers, x, mesh, n_microbatches,
                       param_specs=stage_specs, batch_axes=batch_axes)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = (params["embedding"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(dt)
    return jnp.einsum("bse,ev->bsv", x, head).astype(jnp.float32)


# --------------------------------------------------------------------------
# KV-cache inference path (prefill + single-token decode)
# --------------------------------------------------------------------------

def init_cache(cfg: LlamaConfig, batch: int, max_len: int,
               dtype=None, quantized: bool = False) -> Dict[str, jax.Array]:
    """Preallocated KV cache: ``{"k","v"}`` of [L, B, max_len, Hkv, D].

    Static shapes — the decode step compiles once and runs for any sequence
    shorter than ``max_len``. The reference has no inference path at all
    (orchestration only); on TPU the framework owns it (BASELINE #5 rollouts).

    ``quantized=True``: int8 K/V with per-vector float32 absmax scales
    (``"ks"``/``"vs"`` of [L, B, max_len, Hkv] — one scale per head-vector,
    1.6% overhead at D=128). Halves the KV stream AND residency; the
    dequant folds into the attention einsums exactly like the int8 weight
    path (scale is per key row, so ``scores·scale`` and ``(p·scale)·V``
    are algebraically exact factorizations — see
    ``ops/cached_attention.py::cached_attn_q``).
    """
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    if quantized:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(shape[:-1], jnp.float32),
                "vs": jnp.zeros(shape[:-1], jnp.float32)}
    dt = jnp.dtype(dtype) if dtype is not None else cfg.compute_dtype
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _kv_quantize(x: jax.Array):
    """[B, T, Hkv, D] → (int8 same shape, f32 scale [B, T, Hkv]):
    symmetric per-head-vector absmax."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def merge_chunk_into_grid(cache: Dict[str, jax.Array],
                          chunk: Dict[str, jax.Array],
                          start: jax.Array, count: jax.Array
                          ) -> Dict[str, jax.Array]:
    """Write chunk cols ``[0, count[b])`` into grid slots
    ``start[b] + col`` for every layer — the ONLY per-sequence-offset
    cache write in the decode paths, amortized over a whole chunk.

    A loop over the rows of slice updates (``ops/grid_write.py``): each
    row's ``[L, 1, K]`` window is read, the chunk's columns selected in and
    the window written back in place, so the bytes moved follow
    ``rows x K`` and the rest of the grid is never touched. ``B`` scalar
    offsets make ``B`` contiguous writes, not a scatter: generic gathers and
    scatters with computed index maps serialize on TPU (measured
    ~1.8 s/step — 50× the whole decode step — when this was a full-cache
    take_along_axis), which is why this was a one-hot select over every
    layer's whole ``[B, M]`` plane until PR 28 (30% of the decode
    executable at 7B serving scale). An int8 grid quantizes the (tiny)
    chunk first and lands the int8 values and their f32 scales as they
    are. Shared by rolling decode (uniform count = chunk size for active
    slots), chunked prefill (count = the row's tokens of this chunk) and
    speculative verify (count = accepted prefix; rejected drafts never
    land, so there is no rollback).
    """
    if "ks" in cache:
        qk, sk = _kv_quantize(chunk["k"])
        qv, sv = _kv_quantize(chunk["v"])
        cols = {"k": qk, "v": qv, "ks": sk, "vs": sv}
    else:
        cols = {"k": chunk["k"], "v": chunk["v"]}
    return grid_write.write_columns(cache, cols, start, count)


def _block_cached_chunk_q(x, layer, li, sin, cos, gk_all, gv_all, gks_all,
                          gvs_all, ek_all, ev_all, col, gmask, emask,
                          cfg: LlamaConfig, rules: ShardingRules,
                          lctx=None, items=None):
    """Chunk-mode decoder block over a QUANTIZED read-only grid; the
    step's K/V land bf16 at uniform chunk column ``col``. ``items``
    (``gmask`` as the ragged kernel's work list) selects that kernel for
    the grid half."""
    dt = cfg.compute_dtype
    B, T, _ = x.shape
    H, D = cfg.n_heads, cfg.head_dim
    q, k, v = _qkv_proj(x, layer, sin, cos, cfg, lctx)

    cdt = ek_all.dtype
    ek_all = jax.lax.dynamic_update_slice(
        ek_all, k.astype(cdt)[None], (li, 0, col, 0, 0))
    ev_all = jax.lax.dynamic_update_slice(
        ev_all, v.astype(cdt)[None], (li, 0, col, 0, 0))
    ek = jax.lax.dynamic_index_in_dim(ek_all, li, 0, keepdims=False)
    ev = jax.lax.dynamic_index_in_dim(ev_all, li, 0, keepdims=False)
    if items is not None:
        attn = cached_attn_ragged(q, gk_all, gv_all, gks_all, gvs_all, li,
                                  items, ek, ev, emask)
    else:
        gk = jax.lax.dynamic_index_in_dim(gk_all, li, 0, keepdims=False)
        gv = jax.lax.dynamic_index_in_dim(gv_all, li, 0, keepdims=False)
        gks = jax.lax.dynamic_index_in_dim(gks_all, li, 0, keepdims=False)
        gvs = jax.lax.dynamic_index_in_dim(gvs_all, li, 0, keepdims=False)
        attn = cached_attn_merged_q(q, gk, gv, gks, gvs, ek, ev, gmask,
                                    emask)
    attn = attn.reshape(B, T, H * D)
    x = x + _proj(attn, layer, "wo", dt) \
        + _lora_apply(attn, lctx, "wo")
    x = x + _mlp(x, layer, cfg, rules, lctx)
    return x, ek_all, ev_all


def _block_cached_chunk(x, layer, li, sin, cos, gk_all, gv_all, ek_all,
                        ev_all, col, gmask, emask, cfg: LlamaConfig,
                        rules: ShardingRules, lctx=None, items=None):
    """Chunk-mode decoder block: the stacked grid caches are READ-ONLY;
    this step's K/V lands at uniform column ``col`` of the small stacked
    chunk caches (a plain dynamic-update-slice — no per-sequence offsets,
    so no full-layer rewrite), and attention merges grid + chunk.
    ``items`` as in ``_block_cached_chunk_q``."""
    dt = cfg.compute_dtype
    B, T, _ = x.shape
    H, D = cfg.n_heads, cfg.head_dim
    q, k, v = _qkv_proj(x, layer, sin, cos, cfg, lctx)

    cdt = ek_all.dtype
    ek_all = jax.lax.dynamic_update_slice(
        ek_all, k.astype(cdt)[None], (li, 0, col, 0, 0))
    ev_all = jax.lax.dynamic_update_slice(
        ev_all, v.astype(cdt)[None], (li, 0, col, 0, 0))
    ek = jax.lax.dynamic_index_in_dim(ek_all, li, 0, keepdims=False)
    ev = jax.lax.dynamic_index_in_dim(ev_all, li, 0, keepdims=False)
    if items is not None:
        attn = cached_attn_ragged(q, gk_all, gv_all, None, None, li, items,
                                  ek, ev, emask)
    else:
        gk = jax.lax.dynamic_index_in_dim(gk_all, li, 0, keepdims=False)
        gv = jax.lax.dynamic_index_in_dim(gv_all, li, 0, keepdims=False)
        attn = cached_attn_merged(q, gk, gv, ek, ev, gmask, emask)
    attn = attn.reshape(B, T, H * D)
    x = x + _proj(attn, layer, "wo", dt) \
        + _lora_apply(attn, lctx, "wo")
    x = x + _mlp(x, layer, cfg, rules, lctx)
    return x, ek_all, ev_all


def _lora_apply(h, lctx, name):
    """Per-slot batched low-rank delta for multi-adapter serving.

    ``lctx = (lora_layer, slots [B] int32, scale)`` — the layer's
    stacked adapters ride the decode scan's xs (``forward_cached``);
    ``slots`` indexes each sequence's adapter along the stacked axis
    (−1 = base model). GATHER select, not a one-hot matmul: each row
    reads exactly its own rank-r factors (`jnp.take` along the adapter
    axis), so the select cost is O(rank) per row no matter how many
    adapters are resident — the one-hot einsum it replaced streamed
    ALL n adapters' factors through the MXU every step, growing
    linearly with pool occupancy. Base rows gather slot 0 (the index
    must stay in range) and mask their delta to zero.
    Returns 0 when the target isn't adapted — additions fold away.
    """
    if lctx is None:
        return 0
    lora_layer, slots, scale = lctx
    ab = lora_layer.get(name)
    if ab is None:
        return 0
    sel = jnp.maximum(slots, 0)
    a = jnp.take(ab["a"], sel, axis=0).astype(jnp.float32)   # [B, K, r]
    b = jnp.take(ab["b"], sel, axis=0).astype(jnp.float32)   # [B, r, N]
    z = jnp.einsum("btk,bkr->btr", h.astype(jnp.float32), a)
    d = jnp.einsum("btr,brn->btn", z, b)
    d = jnp.where((slots >= 0)[:, None, None], d, 0.0)
    return (d * scale).astype(h.dtype)


def _qkv_proj(x, layer, sin, cos, cfg: LlamaConfig, lctx=None):
    """Norm → QKV projection (fused ``wqkv`` serving layout or separate
    weights) → RoPE. The shared front half of every cached decoder-block
    variant — bf16 grid, chunk-mode, and quantized-cache — so a layout
    change can't silently diverge them. ``lctx``: per-slot LoRA deltas
    (applied pre-RoPE, exactly where the base projection lands)."""
    dt = cfg.compute_dtype
    B, T, _ = x.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    h = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    if "wqkv" in layer:
        qkv = _proj(h, layer, "wqkv", dt) + _lora_apply(h, lctx, "wqkv")
        q, k, v = jnp.split(qkv, [H * D, H * D + Hkv * D], axis=-1)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, Hkv, D)
        v = v.reshape(B, T, Hkv, D)
    else:
        q = (_proj(h, layer, "wq", dt)
             + _lora_apply(h, lctx, "wq")).reshape(B, T, H, D)
        k = (_proj(h, layer, "wk", dt)
             + _lora_apply(h, lctx, "wk")).reshape(B, T, Hkv, D)
        v = (_proj(h, layer, "wv", dt)
             + _lora_apply(h, lctx, "wv")).reshape(B, T, Hkv, D)
    q = apply_rope(q, None, cfg.rope_theta, sin=sin, cos=cos)
    k = apply_rope(k, None, cfg.rope_theta, sin=sin, cos=cos)
    return q, k, v


def _block_cached_q(x, layer, li, sin, cos, ck_all, cv_all, ks_all, vs_all,
                    write_at, mask, cfg: LlamaConfig, rules: ShardingRules,
                    lctx=None, own_causal: bool = False):
    """Decoder block over a QUANTIZED cache (int8 K/V + per-vector
    scales). Scalar ``write_at`` only — used by the static Generator's
    uniform slots AND by rolling admission prefills over a private
    quantized own-cache (``RollingGenerator(kv_dtype="int8")``, which
    splices the rows into the int8 grid): this step's K/V quantize on
    write, attention dequants via scale folding. ``own_causal``: the call
    is a prompt's own causal self-attention from position 0
    (``forward_cached`` decides), so the cache is written as always and
    attention runs in the flash kernel on the K/V just projected."""
    dt = cfg.compute_dtype
    B, T, _ = x.shape
    H, D = cfg.n_heads, cfg.head_dim
    q, k, v = _qkv_proj(x, layer, sin, cos, cfg, lctx)

    kq, kscale = _kv_quantize(k)
    vq, vscale = _kv_quantize(v)
    ck_all = jax.lax.dynamic_update_slice(
        ck_all, kq[None], (li, 0, write_at, 0, 0))
    cv_all = jax.lax.dynamic_update_slice(
        cv_all, vq[None], (li, 0, write_at, 0, 0))
    ks_all = jax.lax.dynamic_update_slice(
        ks_all, kscale[None], (li, 0, write_at, 0))
    vs_all = jax.lax.dynamic_update_slice(
        vs_all, vscale[None], (li, 0, write_at, 0))
    if own_causal:
        attn = prefill_attention(q, k, v)
    else:
        ck = jax.lax.dynamic_index_in_dim(ck_all, li, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, li, 0, keepdims=False)
        ks = jax.lax.dynamic_index_in_dim(ks_all, li, 0, keepdims=False)
        vs = jax.lax.dynamic_index_in_dim(vs_all, li, 0, keepdims=False)
        attn = cached_attn_q(q, ck, cv, ks, vs, mask)
    attn = attn.reshape(B, T, H * D)
    x = x + _proj(attn, layer, "wo", dt) \
        + _lora_apply(attn, lctx, "wo")
    x = x + _mlp(x, layer, cfg, rules, lctx)
    return x, ck_all, cv_all, ks_all, vs_all


def _block_cached(x, layer, li, sin, cos, ck_all, cv_all, write_at, mask,
                  cfg: LlamaConfig, rules: ShardingRules, lctx=None,
                  own_causal: bool = False):
    """One decoder block in cache mode, updating the stacked ``[L, ...]``
    cache in place at layer ``li``.

    Writes this step's K/V into the cache at slot ``write_at``, a SCALAR
    (uniform across the batch: prompts are right-padded to a common length;
    rows at depths of their own go through chunk mode and the once-a-chunk
    merge), then attends the full cache under ``mask``.
    Returns (x, ck_all, cv_all).

    The stacked caches ride the layer scan's *carry*, not its xs/ys: a ys
    output would allocate (and fill) a fresh stacked cache buffer every
    forward — +2 × cache bytes of pure HBM traffic per decode step, ~7 ms
    of the 8B B=64 step — while dynamic-update-slice on a carry aliases in
    place under the compiled while loop. ``own_causal`` as in
    ``_block_cached_q``.
    """
    dt = cfg.compute_dtype
    B, T, _ = x.shape
    H, D = cfg.n_heads, cfg.head_dim
    q, k, v = _qkv_proj(x, layer, sin, cos, cfg, lctx)

    cdt = ck_all.dtype
    # a [1, B, T, Hkv, D] in-place write, no full-cache rewrite
    ck_all = jax.lax.dynamic_update_slice(
        ck_all, k.astype(cdt)[None], (li, 0, write_at, 0, 0))
    cv_all = jax.lax.dynamic_update_slice(
        cv_all, v.astype(cdt)[None], (li, 0, write_at, 0, 0))
    ck = jax.lax.dynamic_index_in_dim(ck_all, li, 0, keepdims=False)
    cv = jax.lax.dynamic_index_in_dim(cv_all, li, 0, keepdims=False)

    if own_causal:
        attn = prefill_attention(q, k, v)
    else:
        attn = cached_attn(q, ck, cv, mask)
    attn = attn.reshape(B, T, H * D)
    x = x + _proj(attn, layer, "wo", dt) \
        + _lora_apply(attn, lctx, "wo")
    x = x + _mlp(x, layer, cfg, rules, lctx)
    return x, ck_all, cv_all


def forward_cached(
    params: Params,
    tokens: jax.Array,        # [B, T] int32 (prefill: padded prompt; decode: 1)
    positions: jax.Array,     # [B, T] int32 RoPE positions per token
    cache: Dict[str, jax.Array],
    write_at,                 # cache slot for tokens[:, 0]: a scalar
    mask: jax.Array,          # [B, T, max_len] bool attention mask
    cfg: LlamaConfig,
    rules: Optional[ShardingRules] = None,
    unembed_positions: Optional[jax.Array] = None,  # [B] — logits only there
    chunk: Optional[Dict[str, jax.Array]] = None,   # [L,B,K,Hkv,D] stacked
    chunk_col=None,                                 # scalar: uniform column
    chunk_mask: Optional[jax.Array] = None,         # [B, T, K] bool
    lora: Optional[Dict[str, Any]] = None,          # multi-adapter serving
    grid_depth: Optional[jax.Array] = None,         # [B]: mask as a length
    causal_lens: Optional[jax.Array] = None,        # [B]: mask as causal
):
    """Forward with KV cache → (logits [B, T, V] float32, new cache).

    ``unembed_positions`` restricts the unembedding matmul to one position
    per sequence (logits come back [B, 1, V]). Prefill only needs the last
    real token's logits; materializing [B, P, V] float32 there is pure HBM
    waste (4.2 GB at B=64, P=128, V=128k — an OOM on a 16 GB chip that
    never needed to happen).

    ``chunk`` mode (rolling decode): ``cache`` is READ-ONLY and this
    step's K/V is written at the uniform ``chunk_col`` of the small
    stacked chunk caches; attention spans grid (under ``mask``) plus
    chunk (under ``chunk_mask``). The returned cache dict is the updated
    CHUNK, not the grid — the caller merges it into the grid once per
    decode chunk (``RollingGenerator._decode_impl``). This exists because
    per-sequence grid writes rewrite whole cache layers every step.

    Chunk mode has two implementations of one attention. A caller whose
    ``mask`` is a plain prefix mask says so by passing it as a length too:
    ``grid_depth`` [B] with ``mask[b, 0, m] == (m < grid_depth[b])``. With
    it, one query position (``T == 1``), the TPU backend, the grid on one
    device and a ``max_len`` a key block divides
    (``ops.decode_attention.engages``), the grid half runs in the ragged
    Pallas kernel, which reads each row's K/V only to its depth
    (``cached_attn_ragged``). Everything else — chunked prefill and
    speculative verify (``T > 1``), CPU, a mesh — runs the einsum pair
    over all ``max_len`` positions (``cached_attn_merged_q`` /
    ``cached_attn_merged``), which is also the kernel's oracle. The shape
    decides; there is no switch.

    The third case is a prompt's own prefill. A caller whose ``mask`` is
    causal from position 0 under a length says so by passing the lengths
    too: ``causal_lens`` [B] with ``mask[b, t, m] == (m <= t) & (m <
    causal_lens[b])`` (``RollingGenerator._prefill_impl``). With it, no
    ``chunk``, a static ``write_at == 0``, ``T`` equal to the private
    cache's length (every key there is one this call projects), a bucket of
    at least ``flash_attention._PREFILL_MIN`` positions that the kernel
    tiles, and the TPU backend on one device
    (``ops.flash_attention.prefill_engages``), attention runs in the
    blocked flash kernel on the K and V the projection just made, which
    skips the blocks above the diagonal and never writes the ``[heads, T,
    T]`` scores; the cache is written (quantised on write for int8) as
    always. Under a causal mask the length is redundant for every real
    query, so the kernel takes none; the rows of padding compute finite
    values nobody reads, as they do under the einsum. Everything else (a
    prefix-extended admission, whose queries also see a prefix; the static
    ``Generator``, whose cache is longer than ``T``; short buckets; CPU; a
    mesh) runs the einsum pair over the cache (``cached_attn_q`` /
    ``cached_attn``, all five in ``ops/cached_attention.py``), the kernel's
    oracle (tests/test_prefill_flash.py).
    """
    rules = rules or ShardingRules.default()
    dt = cfg.compute_dtype
    x = params["embedding"].astype(dt)[tokens]
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    n_layers = cache["k"].shape[0]
    # multi-adapter serving: lora = {"adapters": {name: {"a": [L,n,K,r],
    # "b": [L,n,r,N]}}, "slots": [B] int32 (−1 = base), "scale": float};
    # the stacked adapter tree rides each layer scan's xs and
    # _lora_apply gathers the per-slot delta at every adapted
    # projection (select cost independent of n).
    ltree = lora["adapters"] if lora is not None else None
    # the ragged kernel's work list, made once for all layers; None: the
    # einsum pair, under ``mask``
    items = None
    if chunk is not None and grid_depth is not None and \
            decode_attention.engages(
                tokens.shape[1], cache["k"].shape[2], cfg.n_kv_heads,
                cfg.head_dim, cache["k"].dtype):
        items = decode_attention.plan(grid_depth, cache["k"].shape[2])

    own_causal = (
        chunk is None and causal_lens is not None
        and prefill_engages(tokens.shape[1], cache["k"].shape[2], write_at,
                            cfg.n_heads, cfg.n_kv_heads, cfg.head_dim))

    def lctx_of(lslice):
        if lora is None:
            return None
        return (lslice, lora["slots"], lora["scale"])

    if "ks" in cache and chunk is not None:
        # quantized READ-ONLY grid + bf16 chunk (rolling decode at int8
        # serving density): the returned dict is the updated CHUNK
        grid_k, grid_v = cache["k"], cache["v"]
        grid_ks, grid_vs = cache["ks"], cache["vs"]

        def scan_chunk_q(carry, inp):
            x, ek_all, ev_all = carry
            layer, li, lslice = inp
            x, ek_all, ev_all = _block_cached_chunk_q(
                x, layer, li, sin, cos, grid_k, grid_v, grid_ks, grid_vs,
                ek_all, ev_all, chunk_col, mask, chunk_mask, cfg, rules,
                lctx_of(lslice), items)
            return (x, ek_all, ev_all), None

        (x, new_k, new_v), _ = jax.lax.scan(
            scan_chunk_q, (x, chunk["k"], chunk["v"]),
            (params["layers"], jnp.arange(n_layers), ltree))
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        if unembed_positions is not None:
            x = jnp.take_along_axis(
                x, unembed_positions[:, None, None], axis=1)
        logits = jnp.einsum("bse,ev->bsv", x, unembedding(params, cfg))
        return logits.astype(jnp.float32), {"k": new_k, "v": new_v}

    if "ks" in cache:
        # quantized cache (int8 + per-vector scales): scalar write_at
        # (static Generator path)

        def scan_q(carry, inp):
            x, ck_all, cv_all, ks_all, vs_all = carry
            layer, li, lslice = inp
            x, ck_all, cv_all, ks_all, vs_all = _block_cached_q(
                x, layer, li, sin, cos, ck_all, cv_all, ks_all, vs_all,
                write_at, mask, cfg, rules, lctx_of(lslice), own_causal)
            return (x, ck_all, cv_all, ks_all, vs_all), None

        (x, new_k, new_v, new_ks, new_vs), _ = jax.lax.scan(
            scan_q, (x, cache["k"], cache["v"], cache["ks"], cache["vs"]),
            (params["layers"], jnp.arange(n_layers), ltree))
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        if unembed_positions is not None:
            x = jnp.take_along_axis(
                x, unembed_positions[:, None, None], axis=1)
        logits = jnp.einsum("bse,ev->bsv", x, unembedding(params, cfg))
        return logits.astype(jnp.float32), {
            "k": new_k, "v": new_v, "ks": new_ks, "vs": new_vs}

    if chunk is not None:
        grid_k, grid_v = cache["k"], cache["v"]

        def scan_chunk(carry, inp):
            x, ek_all, ev_all = carry
            layer, li, lslice = inp
            x, ek_all, ev_all = _block_cached_chunk(
                x, layer, li, sin, cos, grid_k, grid_v, ek_all, ev_all,
                chunk_col, mask, chunk_mask, cfg, rules, lctx_of(lslice),
                items)
            return (x, ek_all, ev_all), None

        (x, new_k, new_v), _ = jax.lax.scan(
            scan_chunk, (x, chunk["k"], chunk["v"]),
            (params["layers"], jnp.arange(n_layers), ltree))
    else:
        def scan_body(carry, inp):
            x, ck_all, cv_all = carry
            layer, li, lslice = inp
            x, ck_all, cv_all = _block_cached(x, layer, li, sin, cos,
                                              ck_all, cv_all,
                                              write_at, mask, cfg, rules,
                                              lctx_of(lslice), own_causal)
            return (x, ck_all, cv_all), None

        (x, new_k, new_v), _ = jax.lax.scan(
            scan_body, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(n_layers), ltree))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if unembed_positions is not None:
        x = jnp.take_along_axis(x, unembed_positions[:, None, None], axis=1)
    logits = jnp.einsum("bse,ev->bsv", x, unembedding(params, cfg))
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}


def num_params(cfg: LlamaConfig) -> int:
    """Analytic parameter count (for MFU/bench reporting)."""
    E, H, Hkv, D, M, V, L = (cfg.embed_dim, cfg.n_heads, cfg.n_kv_heads,
                             cfg.head_dim, cfg.mlp_dim, cfg.vocab_size,
                             cfg.n_layers)
    attn = E * H * D + 2 * E * Hkv * D + H * D * E
    if cfg.moe is None:
        ff = 3 * E * M
    else:
        ff = (cfg.moe.num_experts * 3 * E * cfg.moe.expert_mlp_dim
              + E * cfg.moe.num_experts)
    per_layer = attn + ff + 2 * E
    total = L * per_layer + V * E + E
    if not cfg.tie_embeddings:
        total += E * V
    return total
