"""A decoder whose attention CHOOSES its keys: a learned index scores every
earlier position and each query attends its best ``index_topk``, every
layer ending in routed SwiGLU experts (the ``KeyeVL2`` public config's
language model, ``sa_config``): the serving engine's fifth decoder
(``models/decoder.py``) and the first whose layer keeps a leaf that
attention itself never reads.

The layer, on its input ``x`` (the float32 residual stream), at position
``t``:

1. ``h = RMSNorm(x)``; ``[q | k | v] = h W_qkv`` (``n_heads`` query heads
   over ``n_kv_heads`` key/value heads of ``head_dim``, no bias). ``q`` and
   ``k`` take an RMSNorm over the head with a learned weight, then rope over
   the whole head (``rope_theta``, halves layout).
2. The index: ``qI = h W_qI`` (``index_heads`` of ``index_dim``), ``kI =
   LayerNorm(h W_kI)`` (ONE key of ``index_dim``, weight and bias), both
   rotated over their whole width with the same theta; ``w = h W_w``
   (``index_heads`` weights, float32). ``I[t, s] = sum_j w[t, j] relu(qI[t,
   j] . kI[s])`` in float32, and ``S_t`` is the ``index_topk`` positions ``s
   <= t`` of largest ``I[t, s]`` (all of them while ``t + 1 <=
   index_topk``; equal scores to the lower position).
3. Softmax attention over ``S_t`` alone at ``head_dim ** -0.5``; ``x' = x +
   attn W_o``.
4. ``m = RMSNorm(x')``; ``p = softmax(m W_r)`` over all experts in float32,
   the ``top_k`` largest renormalised to sum 1; ``x_next = x' + sum_e p_e
   W_down^e (silu(W_gate^e m) * (W_up^e m))``: dropless, the pairs sorted by
   expert and the two products grouped (``models/experts.py``, the shared
   expert layer: ``routed_experts``; an admission's tokens in one pass where
   memory lets them, ``admitted_experts``).

**What a row keeps**: three positional leaves along ``max_len``, ``k`` and
``v`` (``[L, B, M, Hkv, D]``) and the index key ``ik`` (``[L, B, M, Di]``,
normed and rotated, the compute dtype), written at admission and at the
merge like any leaf (``ops/grid_write.py`` knows no leaf by name); ``ik`` is
read by the choice, never by attention.

- *Admission* (a bucketed prefill into a private cache). A bucket of at most
  ``index_topk`` positions chooses nothing: causal attention as any decoder
  (the flash kernel where it engages). A longer one, with ``causal_lens``
  given, on one TPU device: ``index_select`` makes the choice a block of
  queries at a time (scores, the exact threshold and the tie position in
  VMEM, out as a mask) and ``admit_indexed_attention`` attends under it
  (``ops/indexed_attention.py``). Elsewhere plain ``jnp``, a block of
  queries at a time: the oracle.
- *Decode and prefill chunks* (chunk mode, the grid read-only): a query
  scores the grid's ``ik`` and the chunk's own columns, ONE choice spans
  both (``indexed_attention.decode_choice``) and one softmax spans both.
  One query position a row on one TPU device reads K and V through
  ``indexed_decode_attention`` (the ragged kernel with the choice as a
  mask: each row read to its depth); everything else through the einsum
  pair with the same mask.

Layers are stacked (``params["layers"]``) and scanned by index, the expert
stacks closed over and handed to the grouped product with the layer's index.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from kubetorch_tpu.models.configs import IndexedMoEConfig
from kubetorch_tpu.models import experts
from kubetorch_tpu.models.decoder import (CacheLeaf, Decoder, embed,
                                          layer_at, refusal, unembed)
from kubetorch_tpu.ops import (decode_attention, flash_attention, grid_write,
                               indexed_attention)
from kubetorch_tpu.ops.cached_attention import (cached_attn,
                                                cached_attn_merged)
from kubetorch_tpu.ops.norms import rms_norm
from kubetorch_tpu.ops.rope import apply_rope, rope_angles

Params = Dict[str, Any]
KIND = "indexed_attention"
LEAVES = ("k", "v", "ik")
# the index's counters of a decode step, a layer: positions it scored, the
# positions the decoding rows' queries chose, the K/V positions attention
# fetched for them
INDEX_COUNTERS = ("decode_index_positions_scored",
                  "decode_sparse_positions_chosen",
                  "decode_sparse_positions_read")
# and of an admission, counted on the host (``prefill_counters``)
PREFILL_COUNTERS = ("prefill_index_pairs_scored",
                    "prefill_index_pairs_needed")
COUNTERS = experts.COUNTERS + INDEX_COUNTERS + PREFILL_COUNTERS
# the leaves of a layer that are sliced a layer; the expert stacks are not
_SMALL = ("attn_norm", "wqkv", "q_norm", "k_norm", "wo", "wiq", "wik",
          "ik_norm", "ik_bias", "wiw", "router", "mlp_norm")

# queries a pass of the plain-jnp admission (scores [block, T] float32)
_QUERY_BLOCK = 512
_LABEL = ("the indexed-attention / routed-expert decoder "
          "(models/indexed_moe.py)")
# what RollingGenerator can be asked for that this decoder does not carry
_REFUSED = {
    "kv_dtype": "an int8 K/V cache (kv_dtype='int8'): the index key would "
                "need scales of its own and the choice would move",
    "spec": "speculative decode (spec_k > 1)",
    "adapters": "LoRA adapters",
    "mesh": "a tensor- or expert-parallel mesh",
    "prefix": "prefix reuse (register_prefix / prefix split / prefix "
              "cache): a query's chosen positions would span the prefix "
              "and the row's own in one choice",
    "handoff": "disaggregated prefill/decode handoff tiers",
}


# ------------------------------------------------------------------ init
def layer_shapes(cfg: IndexedMoEConfig) -> Dict[str, tuple]:
    """leaf -> shape of ONE layer; matrices are ``[in, out]``, ``q | k | v``
    and gate and up fused along the output, experts ``[X, in, out]``."""
    E, D = cfg.embed_dim, cfg.head_dim
    Hi, Di = cfg.index_heads, cfg.index_dim
    X, Mx = cfg.n_experts, cfg.expert_mlp_dim
    return {"attn_norm": (E,), "mlp_norm": (E,),
            "wqkv": (E, (cfg.n_heads + 2 * cfg.n_kv_heads) * D),
            "q_norm": (D,), "k_norm": (D,), "wo": (cfg.n_heads * D, E),
            "wiq": (E, Hi * Di), "wik": (E, Di), "ik_norm": (Di,),
            "ik_bias": (Di,), "wiw": (E, Hi), "router": (E, X),
            "we_gu": (X, E, 2 * Mx), "we_down": (X, Mx, E)}


def init(key: jax.Array, cfg: IndexedMoEConfig) -> Params:
    """Random parameters (1/sqrt(fan_in) matrices, unit norms, zero bias);
    the router and the index's weight projection stay float32."""
    dt = cfg.storage_dtype
    f32 = jnp.float32
    n = cfg.n_layers

    def leaf(k, name, shape):
        if name.endswith("norm"):
            return jnp.ones((n,) + shape, dt)
        if name == "ik_bias":
            return jnp.zeros((n,) + shape, dt)
        w = jax.random.normal(k, (n,) + shape, f32) * shape[-2] ** -0.5
        return w if name in ("router", "wiw") else w.astype(dt)

    k_emb, k_head, key = jax.random.split(key, 3)
    shapes = layer_shapes(cfg)
    return {
        "embedding": jax.random.normal(
            k_emb, (cfg.vocab_size, cfg.embed_dim), f32).astype(dt),
        "final_norm": jnp.ones((cfg.embed_dim,), dt),
        "lm_head": (jax.random.normal(
            k_head, (cfg.embed_dim, cfg.vocab_size), f32)
            * cfg.embed_dim ** -0.5).astype(dt),
        "layers": {name: leaf(k, name, shape) for k, (name, shape) in zip(
            jax.random.split(key, len(shapes)), shapes.items())}}


# ------------------------------------------------------------- the layer
def route(m, router, cfg: IndexedMoEConfig):
    """m [n,E] -> (experts [n,K] int32, weights [n,K] f32): the softmax over
    ALL experts in float32 at the highest matmul precision (a bf16 pass
    would flip near-tied choices), the ``top_k`` largest renormalised."""
    with jax.named_scope("moe_route"):
        p = jax.nn.softmax(jnp.matmul(
            m.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        top, chosen = jax.lax.top_k(p, cfg.top_k)
        return (chosen.astype(jnp.int32),
                top / jnp.sum(top, axis=-1, keepdims=True))


def _held_bytes(cfg) -> int:
    """What the attention of the LONGEST admission holds at its peak (the
    generator's memory is laid out for that bucket; a shorter one may use
    as much): the int8 choice beside the mask it is made under, q and the
    attended, and the residual stream (2.95 GB at 32768, where the
    executable compiled for v5e reads 3.12: PR 43)."""
    L, it = cfg.max_seq_len, jnp.dtype(cfg.compute_dtype).itemsize
    return (2 * L * L + 2 * L * cfg.n_heads * cfg.head_dim * it
            + L * cfg.embed_dim * 4)


def _layer_norm(x, weight, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _project(h, layer, angles, cfg: IndexedMoEConfig):
    """h [B,T,E] (normed, compute dtype) -> q [B,T,H,D], k, v [B,T,Hkv,D]
    (q and k normed a head and rotated) and the index's qI [B,T,Hi,Di], kI
    [B,T,Di] (normed, both rotated), w [B,T,Hi] float32."""
    B, T, _ = h.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hi, Di = cfg.index_heads, cfg.index_dim
    (sin, cos), (isin, icos) = angles
    qkv = jnp.einsum("bte,en->btn", h, layer["wqkv"].astype(h.dtype))
    q = qkv[..., :H * D].reshape(B, T, H, D)
    k = qkv[..., H * D:(H + Hkv) * D].reshape(B, T, Hkv, D)
    v = qkv[..., (H + Hkv) * D:].reshape(B, T, Hkv, D)
    q = apply_rope(rms_norm(q, layer["q_norm"], cfg.rms_eps), None,
                   sin=sin, cos=cos)
    k = apply_rope(rms_norm(k, layer["k_norm"], cfg.rms_eps), None,
                   sin=sin, cos=cos)
    qi = jnp.einsum("bte,en->btn", h, layer["wiq"].astype(h.dtype)
                    ).reshape(B, T, Hi, Di)
    ki = _layer_norm(
        jnp.einsum("bte,en->btn", h, layer["wik"].astype(h.dtype)),
        layer["ik_norm"], layer["ik_bias"], cfg.rms_eps)
    qi = apply_rope(qi, None, sin=isin, cos=icos)
    ki = apply_rope(ki[:, :, None, :], None, sin=isin, cos=icos)[:, :, 0]
    w = jnp.matmul(h.astype(jnp.float32), layer["wiw"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return q, k, v, qi, ki, w


def _angles(positions, cfg: IndexedMoEConfig):
    return (rope_angles(positions, cfg.head_dim, cfg.rope_theta),
            rope_angles(positions, cfg.index_dim, cfg.rope_theta))


def layer_kinds(cfg: IndexedMoEConfig) -> Tuple[str, ...]:
    return (KIND,) * cfg.n_layers


def _block(x, valid, stack, i, angles, attend, cfg: IndexedMoEConfig):
    """One layer on the stream x [B,T,E] float32. ``attend(q, k, v, qi, ki,
    w)`` -> ([B,T,H,D], the cache leaves with k, v and ki kept) is the
    caller's choice and attention. Returns (x, those leaves, the expert
    layer's counters)."""
    B, T, E = x.shape
    dt = cfg.compute_dtype
    layer = {k: layer_at(stack[k], i) for k in _SMALL}
    h = rms_norm(x, layer["attn_norm"], cfg.rms_eps).astype(dt)
    attn, kept = attend(*_project(h, layer, angles, cfg))
    x = x + jnp.einsum(
        "btn,ne->bte", attn.reshape(B, T, -1).astype(dt),
        layer["wo"].astype(dt)).astype(x.dtype)
    m = rms_norm(x, layer["mlp_norm"], cfg.rms_eps).astype(dt)
    chosen, weights = route(m.reshape(B * T, E), layer["router"], cfg)
    y, counters = experts.experts(
        m.reshape(B * T, E), valid.reshape(-1), chosen, weights, stack, i,
        cfg, jax.nn.silu, _held_bytes(cfg))
    return x + y.reshape(B, T, E).astype(x.dtype), kept, counters


def _scan_layers(params, cfg: IndexedMoEConfig, carry, body):
    """Run ``body(carry, stack, i) -> carry`` over the layers: one
    ``lax.scan`` over the layer's index, the stacks closed over."""
    stack = params["layers"]
    return jax.lax.scan(
        lambda carry, i: (body(carry, stack, i), None), carry,
        jnp.arange(cfg.n_layers, dtype=jnp.int32))[0]


# ----------------------------------------------------------- the cache
def init_cache(cfg: IndexedMoEConfig, batch: int, max_len: int, dtype=None,
               quantized: bool = False) -> Dict[str, jax.Array]:
    """``k``, ``v`` [L,B,max_len,Hkv,D] and the index key ``ik``
    [L,B,max_len,Di], the compute dtype."""
    if quantized:
        raise refusal(_LABEL, _REFUSED, "kv_dtype")
    dt = jnp.dtype(dtype) if dtype is not None else cfg.compute_dtype
    kv = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    # a buffer each: the generator donates every leaf
    return {"k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
            "ik": jnp.zeros(kv[:3] + (cfg.index_dim,), dt)}


def merge_chunk_into_grid(cache, chunk, start, count):
    """The chunk's columns land at each row's depth in all three leaves
    (``ops/grid_write.py``)."""
    return grid_write.write_columns(cache, chunk, start, count)


def forward(params: Params, tokens: jax.Array, cfg: IndexedMoEConfig):
    """Uncached forward of whole sequences: tokens [B,T] -> logits [B,T,V]
    float32 (tests; the serving paths are ``forward_cached``)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool))[None],
                            (B, T, T))
    logits, _, _ = forward_cached(params, tokens, positions,
                                  init_cache(cfg, B, T), 0, mask, cfg)
    return logits


def _admit_plain(q, k, v, qi, ki, w, mask, cfg: IndexedMoEConfig):
    """The choice and the attention of a whole prefill in plain ``jnp``, a
    block of queries at a time (their scores are [block, T] float32)."""
    B, T = q.shape[:2]

    def some(args):
        qb, qib, wb, mb = args
        keep = indexed_attention.choice_mask(qib, ki, wb, mb,
                                             cfg.index_topk)
        return cached_attn(qb, k, v, keep)

    block = _QUERY_BLOCK
    if T <= block or T % block:
        return some((q, qi, w, mask))
    pieces = T // block
    out = jax.lax.map(some, tuple(
        a.reshape((B, pieces, block) + a.shape[2:]).swapaxes(0, 1)
        for a in (q, qi, w, mask)))
    return out.swapaxes(0, 1).reshape(q.shape)


def _join_chunk(q, acc_g, m_g, l_g, ek, ev, emask, grid_dtype):
    """The grid's un-normalised half (``indexed_decode_attention``) and the
    chunk's few columns under one softmax, by the log-sum-exp rule
    (``cached_attention.cached_attn_ragged``'s join and its operand dtypes,
    with two operations more, so it is not that function: a chunk column
    outside ``emask`` weighs exactly 0 and the denominator is clamped)."""
    B, _, H, D = q.shape
    Hkv = ek.shape[2]
    G = H // Hkv
    odt = jnp.float32 if grid_dtype == jnp.float32 else jnp.bfloat16
    qg = q.reshape(B, Hkv, G, D).astype(odt)
    se = jnp.einsum("bkgd,bckd->bkgc", qg, ek.astype(odt),
                    preferred_element_type=jnp.float32) * (D ** -0.5)
    keep = emask[:, 0, None, None, :]
    se = jnp.where(keep, se, -1e30)
    m_g, l_g = m_g.reshape(B, Hkv, G), l_g.reshape(B, Hkv, G)
    m = jnp.maximum(m_g, jnp.max(se, axis=-1))
    pe = jnp.where(keep, jnp.exp(se - m[..., None]), 0.0)
    wg = jnp.exp(m_g - m)
    out = (wg[..., None] * acc_g.reshape(B, Hkv, G, D)
           + jnp.einsum("bkgc,bckd->bkgd", pe.astype(odt), ev.astype(odt),
                        preferred_element_type=jnp.float32))
    out = out / jnp.maximum(wg * l_g + jnp.sum(pe, axis=-1), 1e-30)[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)


def forward_cached(params: Params, tokens, positions, cache, write_at, mask,
                   cfg: IndexedMoEConfig, rules=None, unembed_positions=None,
                   chunk=None, chunk_col=None, chunk_mask=None, lora=None,
                   grid_depth=None, causal_lens=None):
    """``llama.forward_cached``'s contract over K, V and an index key a
    position -> (logits [B,T,V] float32, new cache or chunk, counters).

    A token is REAL where it attends to itself (``mask[b,t,t]``; in chunk
    mode where ``chunk_mask`` admits anything): real tokens are a prefix of
    a row's ``T``, and only they are given to experts.

    Without ``chunk`` (a bucketed prefill into a private cache): the three
    leaves take positions ``[0, T)``; ``write_at`` must be the literal 0 and
    the cache as long as the call (prefix reuse is not carried). ``mask`` is
    what a query may see at all; the choice is made among those. With
    ``causal_lens`` (the mask is causal under a length) a bucket past
    ``index_topk`` takes the two admission kernels where they engage, a
    shorter one the flash kernel (``prefill_flash_engages``).
    With ``chunk`` (decode steps, prefill chunks): the grid is read-only and
    this call's K, V and index key land at column ``chunk_col`` of the
    chunk, whose column ``c`` holds position ``depth + c``; ``mask`` must be
    a prefix mask (``m < depth[b]``, as both callers build it);
    ``grid_depth`` [B] (that length, handed in) lets one query position a
    row take ``indexed_decode_attention``.

    ``counters``: in chunk mode the expert layers' counts and the index's
    (``INDEX_COUNTERS``) over the rows the chunk mask admits, summed over
    layers; ``{}`` for a prefill (the generator counts a prefill's on the
    host)."""
    if lora is not None:
        raise refusal(_LABEL, _REFUSED, "adapters")
    B, T = tokens.shape
    H, Hkv, D, topk = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                       cfg.index_topk)
    angles = _angles(positions, cfg)
    x = embed(params, tokens)

    def keep(leaves, i, new, at):
        """The layer's k, v and ki into row ``i`` of the stacked leaves, at
        position (or column) ``at``."""
        return {n: jax.lax.dynamic_update_slice(
            leaves[n], a.astype(leaves[n].dtype)[None],
            (i, 0, at) + (0,) * (a.ndim - 2)) for n, a in zip(LEAVES, new)}

    if chunk is None:
        M = cache["k"].shape[2]
        if not (isinstance(write_at, int) and write_at == 0 and M == T):
            raise refusal(_LABEL, _REFUSED, "prefix")
        real = jnp.diagonal(mask, axis1=1, axis2=2)                 # [B,T]
        own = causal_lens is not None
        select = own and indexed_attention.admit_engages(T, topk, H, Hkv, D)
        flash = (own and T <= topk and flash_attention.prefill_engages(
            T, M, write_at, H, Hkv, D))

        def body(carry, stack, i):
            x, leaves = carry

            def attend(q, k, v, qi, ki, w):
                kept = keep(leaves, i, (k, v, ki), 0)
                with jax.named_scope("indexed_attention_prefill"):
                    if select:
                        return indexed_attention.admit_attention(
                            q, k, v, qi, ki, w, causal_lens, topk), kept
                    if flash:
                        return flash_attention.prefill_attention(
                            q, k, v), kept
                    if T <= topk:   # every query sees all it may
                        return cached_attn(q, k, v, mask), kept
                    return _admit_plain(q, k, v, qi, ki, w, mask, cfg), kept

            x, leaves, _ = _block(x, real, stack, i, angles, attend, cfg)
            return x, leaves

        x, leaves = _scan_layers(params, cfg, (x, dict(cache)), body)
        return unembed(x, params, cfg, unembed_positions), leaves, {}

    M, C = cache["k"].shape[2], chunk["k"].shape[2]
    depth = (grid_depth if grid_depth is not None
             else jnp.sum(mask[:, 0, :], axis=-1, dtype=jnp.int32))
    items = None
    if grid_depth is not None and indexed_attention.engages(
            T, M, Hkv, D, cache["k"].dtype):
        items = decode_attention.plan(depth, M)
        block = decode_attention.block_for(M)
        read = jnp.sum(-(-depth // block) * block, dtype=jnp.int32)
    else:
        read = jnp.int32(B * M)
    # rows this call computes for: those with anything to attend to
    valid = jnp.any(chunk_mask, axis=2)                             # [B,T]
    # what each query may see at all: its row's depth and the chunk's
    # columns its mask admits
    may_see = depth[:, None] + jnp.sum(chunk_mask, axis=2, dtype=jnp.int32)
    step = {"decode_index_positions_scored": jnp.int32(B * T * (M + C)),
            "decode_sparse_positions_chosen": jnp.sum(
                jnp.where(valid, jnp.minimum(may_see, topk), 0),
                dtype=jnp.int32),
            "decode_sparse_positions_read": read}
    totals = {name: jnp.zeros((), jnp.int32) for name in COUNTERS}

    def body(carry, stack, i):
        x, cols, totals = carry

        def attend(q, k, v, qi, ki, w):
            kept = keep(cols, i, (k, v, ki), chunk_col)
            ek, ev, eik = (layer_at(kept[n], i) for n in LEAVES)
            with jax.named_scope("indexed_attention_decode"):
                keys, echosen, v_thr, p_tie = indexed_attention.decode_choice(
                    qi, w, layer_at(cache["ik"], i), eik, depth, chunk_mask,
                    topk,
                    kernel=items is not None)
                if items is not None:
                    acc, m, l = indexed_attention.indexed_decode_attention(
                        q[:, 0], cache["k"], cache["v"], i, items,
                        keys[:, 0], v_thr[:, 0], p_tie[:, 0],
                        interpret=jax.default_backend() != "tpu")
                    return _join_chunk(q, acc, m, l, ek, ev, echosen,
                                       cache["k"].dtype), kept
                return cached_attn_merged(
                    q, layer_at(cache["k"], i), layer_at(cache["v"], i), ek,
                    ev, indexed_attention.chosen(keys, v_thr, p_tie) & mask,
                    echosen), kept

        x, cols, counters = _block(x, valid, stack, i, angles, attend, cfg)
        counters = {**counters, **step}
        return x, cols, {name: totals[name] + counters.get(name, 0)
                         for name in totals}

    x, cols, totals = _scan_layers(params, cfg, (x, dict(chunk), totals),
                                   body)
    return unembed(x, params, cfg, unembed_positions), cols, totals


class IndexedMoEDecoder(Decoder):
    """``models/decoder.py``'s interface over this module."""

    counters = COUNTERS
    label, refused = _LABEL, _REFUSED
    layer_kinds = staticmethod(layer_kinds)
    init_cache = staticmethod(init_cache)
    merge_chunk_into_grid = staticmethod(merge_chunk_into_grid)
    forward_cached = staticmethod(forward_cached)

    @staticmethod
    def cache_leaves(cfg: IndexedMoEConfig, quantized: bool = False):
        if quantized:
            raise refusal(_LABEL, _REFUSED, "kv_dtype")
        vec, dt = (cfg.n_kv_heads, cfg.head_dim), cfg.compute_dtype
        return {KIND: (CacheLeaf("k", vec, dt), CacheLeaf("v", vec, dt),
                       CacheLeaf("ik", (cfg.index_dim,), dt))}

    @staticmethod
    def init_cache_like(cfg, cache, batch, max_len):
        return init_cache(cfg, batch, max_len, dtype=cache["k"].dtype)

    @staticmethod
    def ragged_block(cfg, max_len, cache, spec: bool) -> Optional[int]:
        """The key block ``indexed_decode_attention`` reads K and V in (each
        row to its depth, whatever it chose), or None where the einsum pair
        streams the grid whole."""
        if spec or not indexed_attention.engages(
                1, max_len, cfg.n_kv_heads, cfg.head_dim, cache["k"].dtype):
            return None
        return decode_attention.block_for(max_len)

    @staticmethod
    def prefill_flash_engages(cfg, p_pad: int) -> bool:
        """Whether a bucket's admission attends through a blocked kernel:
        the flash kernel to ``index_topk`` positions, the two admission
        kernels of ``ops/indexed_attention.py`` past it."""
        if p_pad <= cfg.index_topk:
            return flash_attention.prefill_engages(
                p_pad, p_pad, 0, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
        return indexed_attention.admit_engages(
            p_pad, cfg.index_topk, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)

    @staticmethod
    def expert_admission(cfg: IndexedMoEConfig, lens, p_pad: int):
        return experts.expert_admission(cfg, lens, p_pad, _held_bytes(cfg),
                                        cfg.n_layers)

    @staticmethod
    def prefill_counters(cfg: IndexedMoEConfig, prompt_tokens: int):
        """The expert layers' pairs (``experts.moe_assignments``) and the
        index's: the (query, key)
        pairs a prompt NEEDS scored (``s <= t`` of its queries at ``t >=
        index_topk``: the others choose everything) and those
        ``index_select`` scores for it at a bucketed admission (its blocks
        round up, and a block that reaches past ``index_topk`` scores all
        its queries), a layer."""
        n, k = prompt_tokens, cfg.index_topk
        needed = (n * (n + 1) - k * (k + 1)) // 2 if n > k else 0
        return {"moe_assignments": experts.moe_assignments(
                    cfg, n, cfg.n_layers),
                "prefill_index_pairs_needed": needed * cfg.n_layers,
                "prefill_index_pairs_scored": cfg.n_layers * (
                    indexed_attention.select_pairs(n, k) if n > k else 0)}
