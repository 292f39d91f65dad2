"""A decoder whose layers are of two kinds, three to one: gated-delta
linear-attention layers beside full-attention layers (the ``olmo_hybrid``
public config): the serving engine's third decoder (``models/decoder.py``)
and the first whose cache has leaves with no position axis.

Every block is ``x <- x + norm(f(x))`` (the Olmo 2/3 convention: RMSNorm on
the block's OUTPUT, none on its input), twice a layer: the mixer, then a
SwiGLU of ``mlp_dim``. The residual stream is float32; every product reads
it in the compute dtype.

- *Linear layer* (``layer_types[l] == "linear_attention"``), on ``x_t``:
  ``[q~ | k~ | v~] = W_qkv x`` (H x dk, H x dk, H x dv); a depthwise causal
  convolution of ``conv_width`` taps over time on every channel (zero
  history before a sequence's first token), then SiLU; a head's ``q = q /
  |q| dk^-1/2``, ``k = k / |k|``; ``beta = 2 sigmoid(W_b x)`` (the 2 is
  ``neg_eigval``), ``alpha = exp(-exp(A_log) softplus(W_a x + dt_bias))``;
  the gated delta rule ``S <- alpha (I - beta k k^T) S + beta k v^T``, ``o =
  S^T q`` (``ops/gated_delta.py``); output ``W_o [RMSNorm_head(o) * silu(W_g
  x)]``. **What a ROW keeps of such a layer has no positions: the state
  ``S`` (H x dk x dv, float32) and the convolution's tail (the last
  ``conv_width - 1`` inputs of ``[q~ | k~ | v~]``).**
- *Full layer*: ``n_heads`` query and key/value heads of ``head_dim``,
  RMSNorm over the whole q and k projections, causal softmax attention, no
  rotary embedding (the config's ``rope_theta`` is null: the linear layers
  carry the order). It keeps K and V a position, as the dense decoder does,
  and attends through the same paths: the flash kernel at a long bucket's
  admission (``ops/flash_attention.py``), the ragged kernel at a decode step
  (``ops/decode_attention.py``), the einsum pair elsewhere
  (``ops/cached_attention.py``).

One function runs a linear layer over ``T`` tokens from a state and a tail,
with each row's count of REAL tokens: a bucketed prefill (state and tail
zero, counts the prompt lengths: padding up to the bucket leaves the state
as the last real token left it), a decode step (``T = 1``, count 1 for a
row that decodes and 0 for one that does not: its state is held) and a
prefill chunk are that one function. ``T = 1`` takes ``gated_delta.step``
(on one TPU device its kernel, ``gated_delta.step_rows``, which works on the
stacked state leaf in place and fetches only the rows that decode), anything
longer the chunked scan (``gated_delta.prefill_scan``). On one TPU device a
scan its chunk of 128 divides (every bucket of an admission) is one kernel,
``gated_delta_prefill``: it takes ``q``, ``k``, ``v`` in the compute dtype
and the decays and steps a token, and builds a chunk's triangular inverse
and factors in VMEM in the grid step that uses them; beside it XLA only
lays ``q``, ``k``, ``v`` and ``o`` out a head at a time and sums ``log
alpha`` inside each chunk. Elsewhere (the CPU, a mesh, a prefill chunk of
another length) XLA makes the chunks' factors and a ``lax.scan`` walks them:
the kernel's oracle.

Layers of one kind are stacked (``params["linear"]``, ``params["full"]``)
and scanned by index; cache leaves are stacked over the layers of the kind
that keeps them (``k``, ``v``: ``[L_full, B, M, H', D]``, ``H'`` the heads
rounded up to the TPU's sublane tile, ``decode_attention.kv_heads_stored``:
the ragged
kernel's DMA cuts whole tiles, and 30 heads are not; ``state``:
``[L_linear, B, H, dk, dv]``; ``conv``: ``[L_linear, B, conv_width - 1,
channels]``). In chunk mode the row-state leaves ride in the chunk: the
grid is read-only there, the state is not.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from kubetorch_tpu.models.configs import HybridLinearConfig
from kubetorch_tpu.models.decoder import (CacheLeaf, Decoder, embed,
                                          layer_at, refusal, scan_runs,
                                          unembed)
from kubetorch_tpu.ops import (decode_attention, flash_attention,
                               gated_delta, grid_write)
from kubetorch_tpu.ops.cached_attention import (cached_attn,
                                                cached_attn_merged,
                                                cached_attn_ragged)
from kubetorch_tpu.ops.norms import rms_norm

Params = Dict[str, Any]
LINEAR, FULL = "linear_attention", "full_attention"
# the stacks' names in the parameter tree
STACK = {LINEAR: "linear", FULL: "full"}
ROW_LEAVES = ("state", "conv")
_LABEL = ("the hybrid linear-attention decoder "
          "(models/hybrid_linear.py)")
# what RollingGenerator can be asked for that this decoder does not carry
_REFUSED = {
    "kv_dtype": "an int8 K/V cache beside the float32 state "
                "(kv_dtype='int8')",
    "spec": "speculative decode (spec_k > 1): a rejected draft would need "
            "the recurrent state rolled back",
    "adapters": "LoRA adapters",
    "mesh": "a tensor-parallel mesh",
    "prefix": "prefix reuse (register_prefix / prefix split / prefix "
              "cache): a prefix's end would need a snapshot of the "
              "recurrent state",
    "handoff": "disaggregated prefill/decode handoff",
}


# ------------------------------------------------------------------ init
def layer_shapes(cfg: HybridLinearConfig, kind: str) -> Dict[str, tuple]:
    """leaf -> shape of ONE layer of ``kind``; matrices are ``[in, out]``,
    ``q | k | v`` and gate and up fused along the output."""
    E, M = cfg.embed_dim, cfg.mlp_dim
    out = {"attn_norm": (E,), "mlp_norm": (E,),
           "w_gu": (E, 2 * M), "w_down": (M, E)}
    if kind == LINEAR:
        H, dv = cfg.linear_heads, cfg.linear_value_dim
        out.update({"wqkv": (E, cfg.conv_channels),
                    "conv_w": (cfg.conv_width, cfg.conv_channels),
                    "wab": (E, 2 * H), "a_log": (H,), "dt_bias": (H,),
                    "wg": (E, H * dv), "o_norm": (dv,), "wo": (H * dv, E)})
    else:
        HD = cfg.n_heads * cfg.head_dim
        out.update({"wqkv": (E, 3 * HD), "q_norm": (HD,), "k_norm": (HD,),
                    "wo": (HD, E)})
    return out


# leaves kept in float32 whatever the storage dtype: they set the decay
FLOAT32_LEAVES = ("wab", "a_log", "dt_bias")


def init(key: jax.Array, cfg: HybridLinearConfig) -> Params:
    """Random parameters (1/sqrt(fan_in) matrices, unit norms; ``A ~ U(0,
    16)``, ``dt`` log-uniform in [1e-3, 1e-1], the gated-delta convention)."""
    dt = cfg.storage_dtype
    f32 = jnp.float32

    def leaf(k, name, shape, n):
        if name.endswith("norm"):
            return jnp.ones((n,) + shape, dt)
        if name == "a_log":
            return jnp.log(jax.random.uniform(k, (n,) + shape, f32, 1e-3, 16.))
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                k, (n,) + shape, f32, jnp.log(1e-3), jnp.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))     # softplus^-1(step)
        fan_in = shape[-2] if name != "conv_w" else 1
        w = jax.random.normal(k, (n,) + shape, f32) * fan_in ** -0.5
        return w if name in FLOAT32_LEAVES else w.astype(dt)

    params: Params = {}
    k_emb, k_head, key = jax.random.split(key, 3)
    params["embedding"] = jax.random.normal(
        k_emb, (cfg.vocab_size, cfg.embed_dim), f32).astype(dt)
    params["final_norm"] = jnp.ones((cfg.embed_dim,), dt)
    params["lm_head"] = (jax.random.normal(
        k_head, (cfg.embed_dim, cfg.vocab_size), f32)
        * cfg.embed_dim ** -0.5).astype(dt)
    for kind, n in ((LINEAR, cfg.n_linear_layers), (FULL, cfg.n_full_layers)):
        shapes = layer_shapes(cfg, kind)
        keys = jax.random.split(jax.random.fold_in(key, len(kind)),
                                len(shapes))
        params[STACK[kind]] = {
            name: leaf(k, name, shape, n)
            for k, (name, shape) in zip(keys, shapes.items())}
    return params


# ---------------------------------------------------------- linear layer
def _short_conv(x, tail, w, counts):
    """Depthwise causal convolution with history. ``x`` [B,T,C] this call's
    inputs, ``tail`` [B,K-1,C] the inputs before them (zeros at a
    sequence's start), ``w`` [K,C] (``w[K-1]`` weighs the current input),
    ``counts`` [B] each row's real tokens of this call -> (y [B,T,C], new
    tail: the last K-1 inputs up to each row's last real token; a row with
    no real token keeps its tail)."""
    K = w.shape[0]
    T = x.shape[1]
    with jax.named_scope("linear_conv"):
        seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        w = w.astype(jnp.float32)
        y = sum(seq[:, j:j + T].astype(jnp.float32) * w[j] for j in range(K))
        # token t sits at seq[t + K - 1]: the last K-1 real ones start at
        # seq[counts]
        at = counts[:, None] + jnp.arange(K - 1)[None, :]       # [B,K-1]
        new_tail = jnp.take_along_axis(seq, at[:, :, None], axis=1)
    return jax.nn.silu(y), new_tail.astype(tail.dtype)


def _unit(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _step_kernel_engages(cfg: HybridLinearConfig) -> bool:
    return gated_delta.step_engages(
        cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim)


def _step_plan(T: int, counts, cfg: HybridLinearConfig):
    """The decoding rows of a one-token call as the step kernel's work list
    (``gated_delta.step_plan``), or None where the XLA step or the scan
    runs: more than one token, the CPU, a mesh."""
    if T == 1 and _step_kernel_engages(cfg):
        return gated_delta.step_plan(counts > 0)
    return None


def _linear_mixer(x, layer, states, i, tail, counts, plan,
                  cfg: HybridLinearConfig):
    """The gated-delta mixer over ``T`` tokens on layer ``i`` of the stacked
    state leaf. ``x`` [B,T,E] in the compute dtype, ``states``
    [L,B,H,dk,dv] float32, ``tail`` [B,K-1,C], ``counts`` [B], ``plan``
    from ``_step_plan`` -> (out [B,T,E], the leaf with layer ``i``
    advanced, new tail)."""
    B, T, _ = x.shape
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    dt = cfg.compute_dtype
    f32 = jnp.float32
    valid = jnp.arange(T)[None, :] < counts[:, None]                # [B,T]
    qkv = jnp.einsum("bte,en->btn", x, layer["wqkv"].astype(dt))
    qkv, tail = _short_conv(qkv, tail, layer["conv_w"], counts)     # f32
    q = _unit(qkv[..., :H * dk].reshape(B, T, H, dk)) * dk ** -0.5
    k = _unit(qkv[..., H * dk:2 * H * dk].reshape(B, T, H, dk))
    v = qkv[..., 2 * H * dk:].reshape(B, T, H, dv)
    ab = jnp.einsum("bte,en->btn", x.astype(f32), layer["wab"].astype(f32),
                    precision=jax.lax.Precision.HIGHEST)
    log_alpha = -jnp.exp(layer["a_log"].astype(f32)) * jax.nn.softplus(
        ab[..., :H] + layer["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(ab[..., H:]) * (2.0 if cfg.neg_eigval else 1.0)
    # a position no real token occupies leaves the state as it was
    log_alpha = jnp.where(valid[..., None], log_alpha, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    with jax.named_scope("linear_attention_decode" if T == 1
                         else "linear_attention_prefill"):
        if plan is not None:
            # the kernel works on the leaf in place: no slice of it is made
            o, states = gated_delta.step_rows(
                q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0],
                states, i, plan)
            o = o[:, None]
        else:
            state = layer_at(states, i)
            if T == 1:
                o, state = gated_delta.step(q[:, 0], k[:, 0], v[:, 0],
                                            log_alpha[:, 0], beta[:, 0],
                                            state)
                o = o[:, None]
            else:
                o, state = gated_delta.prefill_scan(q, k, v, log_alpha,
                                                    beta, state)
            states = _put(states, state, i)
    # gate and output stay flat ``[.., H * dv]``: a head axis of 192 beside
    # the weights would have XLA re-lay the weight stacks out, a copy a call
    gate = jnp.einsum("bte,en->btn", x, layer["wg"].astype(dt))
    o = rms_norm(o, layer["o_norm"], cfg.rms_eps).reshape(B, T, H * dv)
    o = (o * jax.nn.silu(gate.astype(f32))).astype(dt)
    out = jnp.einsum("btn,ne->bte", o, layer["wo"].astype(dt))
    return out, states, tail


# ------------------------------------------------------------ full layer
def _full_qkv(x, layer, cfg: HybridLinearConfig):
    B, T, _ = x.shape
    H, D = cfg.n_heads, cfg.head_dim
    qkv = jnp.einsum("bte,en->btn", x, layer["wqkv"].astype(
        cfg.compute_dtype))
    q = rms_norm(qkv[..., :H * D], layer["q_norm"], cfg.rms_eps)
    k = rms_norm(qkv[..., H * D:2 * H * D], layer["k_norm"], cfg.rms_eps)
    return (q.reshape(B, T, H, D), k.reshape(B, T, H, D),
            qkv[..., 2 * H * D:].reshape(B, T, H, D))


def _swiglu(x, layer, dt):
    h = jnp.einsum("bte,en->btn", x, layer["w_gu"].astype(dt))
    half = h.shape[-1] // 2
    return jnp.einsum("btm,me->bte",
                      jax.nn.silu(h[..., :half]) * h[..., half:],
                      layer["w_down"].astype(dt))


def _residual(x, out, norm, cfg: HybridLinearConfig):
    """``x + norm(out)``, the stream in float32."""
    return x + rms_norm(out.astype(jnp.float32), norm, cfg.rms_eps)


# ------------------------------------------------------------- the stack
def layer_kinds(cfg: HybridLinearConfig) -> Tuple[str, ...]:
    return cfg.layer_types


def _scan_layers(params, cfg: HybridLinearConfig, carry, body):
    """Run ``body(carry, layer, i, kind) -> carry`` over the layers in
    order (``scan_runs``), ``layer`` the kind's leaves at index ``i`` of its
    stack."""
    def layer(kind, i):
        return {name: layer_at(leaf, i)
                for name, leaf in params[STACK[kind]].items()}

    return scan_runs(
        cfg.layer_types, carry, lambda carry, kind, at, j: body(
            carry, layer(kind, at + j), at + j, kind))


def _put(stack, row, i):
    return jax.lax.dynamic_update_index_in_dim(
        stack, row.astype(stack.dtype), i, 0)


def _linear_block(x, rows, layer, i, counts, plan, cfg: HybridLinearConfig):
    """A linear layer on the stream; ``rows`` = (state, conv) stacks."""
    state, conv = rows
    out, state, t = _linear_mixer(x.astype(cfg.compute_dtype), layer, state,
                                  i, layer_at(conv, i), counts, plan, cfg)
    x = _residual(x, out, layer["attn_norm"], cfg)
    return x, (state, _put(conv, t, i))


def _mlp_block(x, layer, cfg: HybridLinearConfig):
    return _residual(x, _swiglu(x.astype(cfg.compute_dtype), layer,
                                cfg.compute_dtype), layer["mlp_norm"], cfg)


def init_cache(cfg: HybridLinearConfig, batch: int, max_len: int, dtype=None,
               quantized: bool = False) -> Dict[str, jax.Array]:
    """``k``, ``v`` [L_full,B,M,H',D] (the compute dtype; ``H'`` =
    ``decode_attention.kv_heads_stored``), ``state`` [L_linear,B,H,dk,dv]
    float32 and ``conv`` [L_linear,B,K-1,C]: zeros, a sequence's start."""
    if quantized:
        raise refusal(_LABEL, _REFUSED, "kv_dtype")
    dt = jnp.dtype(dtype) if dtype is not None else cfg.compute_dtype
    kv = (cfg.n_full_layers, batch, max_len,
          decode_attention.kv_heads_stored(cfg.n_kv_heads, dt), cfg.head_dim)
    Ll = cfg.n_linear_layers
    return {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
            "state": jnp.zeros((Ll, batch, cfg.linear_heads,
                                cfg.linear_key_dim, cfg.linear_value_dim),
                               jnp.float32),
            "conv": jnp.zeros((Ll, batch, cfg.conv_width - 1,
                               cfg.conv_channels), dt)}


def merge_chunk_into_grid(cache, chunk, start, count):
    """The chunk's K/V columns land at each row's depth
    (``ops/grid_write.py``); the row-state leaves of the chunk ARE the new
    ones (the forward held them for every row with nothing to land)."""
    kv = grid_write.write_columns(
        {n: cache[n] for n in ("k", "v")},
        {n: chunk[n] for n in ("k", "v")}, start, count)
    return {**kv, **{n: chunk[n] for n in ROW_LEAVES}}


def forward(params: Params, tokens: jax.Array, cfg: HybridLinearConfig):
    """Uncached forward of whole sequences: tokens [B,T] -> logits [B,T,V]
    float32 (tests; the serving paths are ``forward_cached``)."""
    B, T = tokens.shape
    own = init_cache(cfg, B, T)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool))[None],
                            (B, T, T))
    logits, _, _ = forward_cached(params, tokens, None, own, 0, mask, cfg)
    return logits


def forward_cached(params: Params, tokens, positions, cache, write_at, mask,
                   cfg: HybridLinearConfig, rules=None,
                   unembed_positions=None, chunk=None, chunk_col=None,
                   chunk_mask=None, lora=None, grid_depth=None,
                   causal_lens=None):
    """``llama.forward_cached``'s contract over K/V a position and state a
    row -> (logits [B,T,V] float32, new cache or chunk, ``{}``).

    ``positions`` is not read (no rotary embedding). A token is REAL where
    it attends to itself (``mask[b,t,t]``; in chunk mode ``chunk_mask[b,t,
    chunk_col + t]``): real tokens are a prefix of a row's ``T``, and only
    they move the row's state and convolution tail.

    Without ``chunk`` (a bucketed prefill into a private cache): K/V are
    written at ``[0, T)`` and the state and tail start from what the private
    cache holds (zeros) and end at each row's last real token; ``write_at``
    must be the literal 0 and the cache as long as the call (prefix reuse is
    not carried). With ``causal_lens`` and a bucket the flash kernel tiles,
    the full layers attend through it (``prefill_flash_engages``). With
    ``chunk`` (decode steps, prefill chunks): the grid is read-only, this
    call's K/V land at column ``chunk_col`` of the chunk, and the chunk's
    ``state`` and ``conv`` leaves are read, advanced for the real tokens and
    returned; ``grid_depth`` [B] lets one query position a row take the
    ragged kernel."""
    if lora is not None:
        raise refusal(_LABEL, _REFUSED, "adapters")
    B, T = tokens.shape
    H, D = cfg.n_heads, cfg.head_dim
    dt = cfg.compute_dtype
    x = embed(params, tokens)

    if chunk is None:
        M = cache["k"].shape[2]
        if not (isinstance(write_at, int) and write_at == 0 and M == T):
            raise refusal(_LABEL, _REFUSED, "prefix")
        real = jnp.diagonal(mask, axis1=1, axis2=2)                 # [B,T]
        counts = jnp.sum(real, axis=1, dtype=jnp.int32)
        plan = _step_plan(T, counts, cfg)
        flash = causal_lens is not None and flash_attention.prefill_engages(
            T, M, write_at, H, cfg.n_kv_heads, D)

        def body(carry, layer, i, kind):
            x, kv, rows = carry
            if kind == LINEAR:
                x, rows = _linear_block(x, rows, layer, i, counts, plan, cfg)
            else:
                q, k, v = _full_qkv(x.astype(dt), layer, cfg)
                kv = tuple(jax.lax.dynamic_update_slice(
                    g, decode_attention.pad_heads(
                        new.astype(g.dtype), g.shape[3])[None],
                    (i, 0, 0, 0, 0)) for g, new in zip(kv, (k, v)))
                if flash:
                    attn = flash_attention.prefill_attention(q, k, v)
                else:
                    attn = cached_attn(q, k, v, mask)
                out = jnp.einsum("btn,ne->bte", attn.reshape(B, T, H * D),
                                 layer["wo"].astype(dt))
                x = _residual(x, out, layer["attn_norm"], cfg)
            return _mlp_block(x, layer, cfg), kv, rows

        x, kv, rows = _scan_layers(
            params, cfg, (x, (cache["k"], cache["v"]),
                          (cache["state"], cache["conv"])), body)
        return (unembed(x, params, cfg, unembed_positions),
                {"k": kv[0], "v": kv[1], "state": rows[0], "conv": rows[1]},
                {})

    M = cache["k"].shape[2]
    items = None
    block = decode_attention.ragged_key_block(
        M, cache["k"].shape[3], D, cache["k"].dtype)
    if grid_depth is not None and T == 1 and block is not None:
        items = decode_attention.plan(grid_depth, M, block)
    own = jax.lax.dynamic_slice_in_dim(chunk_mask, chunk_col, T, axis=2)
    counts = jnp.sum(jnp.diagonal(own, axis1=1, axis2=2), axis=1,
                     dtype=jnp.int32)
    plan = _step_plan(T, counts, cfg)

    def body(carry, layer, i, kind):
        x, cols, rows = carry
        if kind == LINEAR:
            x, rows = _linear_block(x, rows, layer, i, counts, plan, cfg)
        else:
            q, k, v = _full_qkv(x.astype(dt), layer, cfg)
            cols = tuple(jax.lax.dynamic_update_slice(
                c, decode_attention.pad_heads(
                    new.astype(c.dtype), c.shape[3])[None],
                (i, 0, chunk_col, 0, 0)) for c, new in zip(cols, (k, v)))
            ek, ev = layer_at(cols[0], i), layer_at(cols[1], i)
            # the padded heads ask with zeros and are dropped after
            q = decode_attention.pad_heads(q, ek.shape[2])
            if items is not None:
                # float32 queries: the kernel's q block is [G, D] a kv head,
                # and with one query head a kv head a bfloat16 row is half
                # a tile, which the TPU's compiler will not slice (the
                # kernel rounds them to its operand dtype itself)
                attn = cached_attn_ragged(
                    q.astype(jnp.float32), cache["k"], cache["v"], None,
                    None, i, items, ek, ev, chunk_mask).astype(dt)
            else:
                attn = cached_attn_merged(
                    q, layer_at(cache["k"], i), layer_at(cache["v"], i), ek,
                    ev, mask, chunk_mask)
            out = jnp.einsum("btn,ne->bte",
                             attn[:, :, :H].reshape(B, T, H * D),
                             layer["wo"].astype(dt))
            x = _residual(x, out, layer["attn_norm"], cfg)
        return _mlp_block(x, layer, cfg), cols, rows

    x, cols, rows = _scan_layers(
        params, cfg, (x, (chunk["k"], chunk["v"]),
                      (chunk["state"], chunk["conv"])), body)
    return (unembed(x, params, cfg, unembed_positions),
            {"k": cols[0], "v": cols[1], "state": rows[0], "conv": rows[1]},
            {})


class HybridLinearDecoder(Decoder):
    """``models/decoder.py``'s interface over this module."""

    label, refused = _LABEL, _REFUSED
    layer_kinds = staticmethod(layer_kinds)
    init_cache = staticmethod(init_cache)
    merge_chunk_into_grid = staticmethod(merge_chunk_into_grid)
    forward_cached = staticmethod(forward_cached)

    @staticmethod
    def cache_leaves(cfg: HybridLinearConfig, quantized: bool = False):
        if quantized:
            raise refusal(_LABEL, _REFUSED, "kv_dtype")
        vec = (decode_attention.kv_heads_stored(
            cfg.n_kv_heads, cfg.compute_dtype), cfg.head_dim)
        return {
            LINEAR: (
                CacheLeaf("state", (cfg.linear_heads, cfg.linear_key_dim,
                                    cfg.linear_value_dim), jnp.float32,
                          False),
                CacheLeaf("conv", (cfg.conv_width - 1, cfg.conv_channels),
                          cfg.compute_dtype, False)),
            FULL: (CacheLeaf("k", vec, cfg.compute_dtype),
                   CacheLeaf("v", vec, cfg.compute_dtype))}

    @staticmethod
    def init_cache_like(cfg, cache, batch, max_len):
        return init_cache(cfg, batch, max_len, dtype=cache["k"].dtype)

    @staticmethod
    def init_chunk(cfg, cache, batch, cols):
        """The K/V columns a chunk writes, zeros; the row-state leaves as
        the grid holds them (a chunk is made for the grid's own rows)."""
        L, _, _, Hkv, D = cache["k"].shape
        zeros = jnp.zeros((L, batch, cols, Hkv, D), cache["k"].dtype)
        return {"k": zeros, "v": zeros,
                **{n: cache[n] for n in ROW_LEAVES}}

    @staticmethod
    def ragged_block(cfg, max_len, cache, spec: bool) -> Optional[int]:
        if spec:
            return None
        return decode_attention.ragged_key_block(
            max_len, cache["k"].shape[3], cfg.head_dim, cache["k"].dtype)

    @staticmethod
    def prefill_flash_engages(cfg, p_pad: int) -> bool:
        return flash_attention.prefill_engages(
            p_pad, p_pad, 0, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)

    @staticmethod
    def state_rows_touched(cfg, rows: int, live: int) -> int:
        """Where the step kernel engages (``gated_delta.step_engages``:
        one TPU device) a decode step reads and writes the state of the
        rows that decode, once; where the XLA step runs, of every row of
        the grid: a row that does not decode is held by ``alpha = 1, beta
        = 0``, not skipped."""
        return live if _step_kernel_engages(cfg) else rows

    @staticmethod
    def scan_positions(cfg, rows: int, length: int) -> int:
        """Positions the linear layers' scan walks for ``rows`` rows of
        ``length`` (padded) tokens, a layer."""
        return rows * gated_delta.scan_positions(length)
