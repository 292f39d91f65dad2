"""A decoder whose attention layers are of two kinds, one to three:
full-attention layers with no position encoding beside window layers that
rotate and see the last ``window`` positions, every layer ending in routed
ReGLU experts chosen BEFORE attention (the ``smallthinker`` public config):
the serving engine's fourth decoder (``models/decoder.py``) and the first
whose cache holds positional leaves of different lengths.

The layer, on its input ``x`` (the float32 residual stream):

1. ``r = x W_r`` in float32: the router reads the layer's INPUT, ahead of
   the input norm and of attention. The ``top_k`` largest of ``r`` are the
   token's experts and ``softmax`` over those chosen scores their weights
   (softmax over all experts renormalised over the chosen is that).
2. ``h = RMSNorm(x)``; ``[q | k | v] = h W_qkv`` (``n_heads`` query heads
   over ``n_kv_heads`` key/value heads of ``head_dim``, no bias, no q/k
   norm). A *window* layer rotates ``q`` and ``k`` (``rope_theta``, the
   whole head, halves layout) and query ``i`` sees key ``j`` iff ``i -
   window < j <= i``; a *full* layer rotates nothing and sees ``j <= i``.
   Softmax at ``head_dim ** -0.5``; ``x' = x + attn W_o``.
3. ``m = RMSNorm(x')``; ``x_next = x' + sum_e p_e W_down^e (relu(W_gate^e
   m) * (W_up^e m))`` over the chosen experts: dropless, the pairs sorted by
   expert and the two products grouped (``models/experts.py``, the shared
   expert layer, with ReLU as the gate's activation: ``routed_experts`` over
   ``ops/grouped_matmul.py``, which reads only the experts given a token; an
   admission's tokens go through in one pass where memory lets them,
   ``admitted_experts``).

**What a row keeps.** A full layer keeps K and V of every position (``k``,
``v``: ``[L_full, B, max_len, Hkv, D]``). A window layer can never again see
a position ``window`` behind the row's depth, so it keeps a RING (``wk``,
``wv``: ``[L_window, B, window, Hkv, D]``, ``CacheLeaf.span``): position
``p`` lies at slot ``p % window``, rotated keys as they were written. A row
at depth ``d`` holds its last ``min(d, window)`` positions in slots ``[0,
min(d, window))``; a softmax does not care in which order it meets its keys,
so the ring is read as it lies and never unrolled.

- *Admission* (a bucketed prefill into a private cache): the layer attends
  over the K and V it just projected (the flash kernel where it engages,
  with the band for a window layer so that key blocks outside it are
  neither fetched nor computed: ``ops/flash_attention.py``; the masked
  einsum pair elsewhere), the full leaves take every position and the
  private ring takes the prompt's last ``window`` positions in ring order
  (``ring_positions``), which ``grid_write.write_rows`` lands as it lands any
  leaf.
- *Decode and prefill chunks* (chunk mode): the grid is read-only, the
  call's K and V land in the chunk's columns, and one softmax spans leaf
  and chunk. A window layer reads its ring less the few oldest entries that
  have left the query's window (at step ``j`` of a decode chunk the query
  sits ``j`` past the depth the ring was written to). On one TPU device
  with one query position a row both kinds take the ragged kernel
  (``ops/decode_attention.py``: ``plan`` over ``max_len`` for the full
  leaves, ``ring_plan`` over the ring), each row read to ``depth`` and to
  ``min(depth, window)``; everywhere else the einsum pair with the same
  masks, the oracle. The merge lands a chunk's columns at the depth
  (``write_columns``) and modulo the span (``write_columns_ring``).

Layers of one kind are stacked (``params["full"]``, ``params["window"]``)
and scanned by index, the expert stacks closed over and handed to the
grouped product with the layer's index, never sliced.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from kubetorch_tpu.models.configs import WindowMoEConfig
from kubetorch_tpu.models import experts
from kubetorch_tpu.models.decoder import (CacheLeaf, Decoder, embed,
                                          layer_at, refusal, scan_runs,
                                          unembed)
from kubetorch_tpu.ops import decode_attention, flash_attention, grid_write
from kubetorch_tpu.ops.cached_attention import (cached_attn,
                                                cached_attn_merged,
                                                cached_attn_ragged)
from kubetorch_tpu.ops.norms import rms_norm
from kubetorch_tpu.ops.rope import apply_rope, rope_angles

Params = Dict[str, Any]
FULL, WINDOW = "full_attention", "window_attention"
# the stacks' names in the parameter tree, and each kind's K and V leaves
STACK = {FULL: "full", WINDOW: "window"}
KV = {FULL: ("k", "v"), WINDOW: ("wk", "wv")}
# the leaves of a layer that are sliced a layer; the expert stacks are not
_SMALL = ("attn_norm", "wqkv", "wo", "router", "mlp_norm")

_LABEL = "the window / routed-expert decoder (models/window_moe.py)"
# what RollingGenerator can be asked for that this decoder does not carry
_REFUSED = {
    "kv_dtype": "an int8 K/V cache (kv_dtype='int8'): a ring's scales would "
                "ride the ring",
    "spec": "speculative decode (spec_k > 1): a rejected draft's positions "
            "would have overwritten the ring's oldest",
    "adapters": "LoRA adapters",
    "mesh": "a tensor- or expert-parallel mesh",
    "prefix": "prefix reuse (register_prefix / prefix split / prefix "
              "cache): a prefix longer than the window has no ring of its "
              "own to splice, and one splice would have to land mid-ring",
    "handoff": "disaggregated prefill/decode handoff tiers",
}


# ------------------------------------------------------------------ init
def layer_shapes(cfg: WindowMoEConfig) -> Dict[str, tuple]:
    """leaf -> shape of ONE layer (both kinds have one shape); matrices are
    ``[in, out]``, ``q | k | v`` and gate and up fused along the output,
    experts ``[X, in, out]``."""
    E, D = cfg.embed_dim, cfg.head_dim
    X, Mx = cfg.n_experts, cfg.expert_mlp_dim
    return {"attn_norm": (E,), "mlp_norm": (E,),
            "wqkv": (E, (cfg.n_heads + 2 * cfg.n_kv_heads) * D),
            "wo": (cfg.n_heads * D, E), "router": (E, X),
            "we_gu": (X, E, 2 * Mx), "we_down": (X, Mx, E)}


def init(key: jax.Array, cfg: WindowMoEConfig) -> Params:
    """Random parameters (1/sqrt(fan_in) matrices, unit norms); the router
    stays float32."""
    dt = cfg.storage_dtype
    f32 = jnp.float32

    def leaf(k, name, shape, n):
        if name.endswith("norm"):
            return jnp.ones((n,) + shape, dt)
        w = jax.random.normal(k, (n,) + shape, f32) * shape[-2] ** -0.5
        return w if name == "router" else w.astype(dt)

    params: Params = {}
    k_emb, k_head, key = jax.random.split(key, 3)
    params["embedding"] = jax.random.normal(
        k_emb, (cfg.vocab_size, cfg.embed_dim), f32).astype(dt)
    params["final_norm"] = jnp.ones((cfg.embed_dim,), dt)
    params["lm_head"] = (jax.random.normal(
        k_head, (cfg.embed_dim, cfg.vocab_size), f32)
        * cfg.embed_dim ** -0.5).astype(dt)
    shapes = layer_shapes(cfg)
    for kind, n in ((FULL, cfg.n_full_layers), (WINDOW, cfg.n_window_layers)):
        keys = jax.random.split(jax.random.fold_in(key, len(kind)),
                                len(shapes))
        params[STACK[kind]] = {
            name: leaf(k, name, shape, n)
            for k, (name, shape) in zip(keys, shapes.items())}
    return params


# ------------------------------------------------------------- the layer
def route(x, router, cfg: WindowMoEConfig):
    """x [n,E] float32, the layer's INPUT -> (experts [n,K] int32, weights
    [n,K] f32). Scores are float32 at the highest matmul precision (a bf16
    pass would flip near-tied choices); the weights are the softmax over
    the chosen scores."""
    with jax.named_scope("moe_route"):
        scores = jnp.matmul(x.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        top, chosen = jax.lax.top_k(scores, cfg.top_k)
        return chosen.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def _held_bytes(cfg) -> int:
    """What the attention of the LONGEST admission holds at its peak (the
    generator's memory is laid out for that bucket; a shorter one may use
    as much): the mask, the fused projection beside its float32 rotation,
    q rotated, head-major, attended and back, and the residual stream
    (1.36 GB at 16384, as the executable compiled for v5e reads: PR 43)."""
    L, it = cfg.max_seq_len, jnp.dtype(cfg.compute_dtype).itemsize
    heads = cfg.n_heads * cfg.head_dim
    qkv = heads + 2 * cfg.n_kv_heads * cfg.head_dim
    return (L * L + L * qkv * (it + 4) + 4 * L * heads * it
            + L * cfg.embed_dim * 4)


def _qkv(h, layer, sin, cos, kind: str, cfg: WindowMoEConfig):
    """h [B,T,E] (normed, compute dtype) -> q [B,T,H,D], k, v [B,T,Hkv,D];
    a window layer's q and k rotated by their positions."""
    B, T, _ = h.shape
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = jnp.einsum("bte,en->btn", h, layer["wqkv"].astype(h.dtype))
    q = qkv[..., :H * D].reshape(B, T, H, D)
    k = qkv[..., H * D:(H + Hkv) * D].reshape(B, T, Hkv, D)
    v = qkv[..., (H + Hkv) * D:].reshape(B, T, Hkv, D)
    if kind == WINDOW:
        q = apply_rope(q, None, sin=sin, cos=cos)
        k = apply_rope(k, None, sin=sin, cos=cos)
    return q, k, v


def ring_positions(depth, span: int):
    """[B, span] the position each slot of a ring holds for rows that have
    written positions ``[0, depth)``, and whether it holds any (slot ``s``
    of a row at depth ``d``: the largest ``p < d`` with ``p % span == s``)."""
    s = jnp.arange(span)[None, :]
    last = depth[:, None] - 1
    return s + span * (jnp.maximum(last - s, 0) // span), s <= last


def layer_kinds(cfg: WindowMoEConfig) -> Tuple[str, ...]:
    return cfg.layer_types


def _scan_layers(params, cfg: WindowMoEConfig, carry, body):
    """Run ``body(carry, stack, i, kind) -> carry`` over the layers in
    order, ``stack`` the kind's stacked leaves and ``i`` the layer's index in
    them (``decoder.scan_runs``: each kind's layer is compiled once a place
    in the repeating unit)."""
    return scan_runs(
        cfg.layer_types, carry, lambda carry, kind, at, j: body(
            carry, params[STACK[kind]], at + j, kind))


def _small(stack, i):
    """Layer ``i``'s leaves out of a kind's stack, less the experts'."""
    return {k: layer_at(stack[k], i) for k in _SMALL}


def _block(x, valid, stack, i, kind, sin, cos, attend, cfg: WindowMoEConfig):
    """One layer on the stream x [B,T,E] float32. ``attend(q, k, v)`` ->
    ([B,T,H,D], the cache leaves with k and v kept) is the caller's
    attention. Returns (x, those leaves, the expert layer's counters)."""
    B, T, E = x.shape
    dt = cfg.compute_dtype
    layer = _small(stack, i)
    chosen, weights = route(x.reshape(B * T, E), layer["router"], cfg)
    h = rms_norm(x, layer["attn_norm"], cfg.rms_eps).astype(dt)
    attn, kept = attend(*_qkv(h, layer, sin, cos, kind, cfg))
    x = x + jnp.einsum(
        "btn,ne->bte", attn.reshape(B, T, -1).astype(dt),
        layer["wo"].astype(dt)).astype(x.dtype)
    m = rms_norm(x, layer["mlp_norm"], cfg.rms_eps).astype(dt)
    y, counters = experts.experts(
        m.reshape(B * T, E), valid.reshape(-1), chosen, weights, stack, i,
        cfg, jax.nn.relu, _held_bytes(cfg))
    return x + y.reshape(B, T, E).astype(x.dtype), kept, counters


# ----------------------------------------------------------- the cache
def init_cache(cfg: WindowMoEConfig, batch: int, max_len: int, dtype=None,
               quantized: bool = False) -> Dict[str, jax.Array]:
    """``k``, ``v`` [L_full,B,max_len,Hkv,D] and the rings ``wk``, ``wv``
    [L_window,B,min(max_len, window),Hkv,D] (a cache shorter than the
    window, a short bucket's private one, keeps every position: slot =
    position), the compute dtype."""
    if quantized:
        raise refusal(_LABEL, _REFUSED, "kv_dtype")
    dt = jnp.dtype(dtype) if dtype is not None else cfg.compute_dtype
    vec = (cfg.n_kv_heads, cfg.head_dim)
    full = (cfg.n_full_layers, batch, max_len) + vec
    ring = (cfg.n_window_layers, batch, min(max_len, cfg.window)) + vec
    # a buffer each: the generator donates every leaf
    return {"k": jnp.zeros(full, dt), "v": jnp.zeros(full, dt),
            "wk": jnp.zeros(ring, dt), "wv": jnp.zeros(ring, dt)}


def merge_chunk_into_grid(cache, chunk, start, count):
    """The chunk's columns land at each row's depth in the full leaves and
    at the depth modulo the span in the rings (``ops/grid_write.py``)."""
    full = grid_write.write_columns(
        {n: cache[n] for n in KV[FULL]}, {n: chunk[n] for n in KV[FULL]},
        start, count)
    ring = grid_write.write_columns_ring(
        {n: cache[n] for n in KV[WINDOW]},
        {n: chunk[n] for n in KV[WINDOW]}, start, count)
    return {**full, **ring}


def forward(params: Params, tokens: jax.Array, cfg: WindowMoEConfig):
    """Uncached forward of whole sequences: tokens [B,T] -> logits [B,T,V]
    float32 (tests; the serving paths are ``forward_cached``)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool))[None],
                            (B, T, T))
    logits, _, _ = forward_cached(params, tokens, positions,
                                  init_cache(cfg, B, T), 0, mask, cfg)
    return logits


def forward_cached(params: Params, tokens, positions, cache, write_at, mask,
                   cfg: WindowMoEConfig, rules=None, unembed_positions=None,
                   chunk=None, chunk_col=None, chunk_mask=None, lora=None,
                   grid_depth=None, causal_lens=None):
    """``llama.forward_cached``'s contract over K/V a position and a ring of
    them -> (logits [B,T,V] float32, new cache or chunk, counters).

    A token is REAL where it attends to itself (``mask[b,t,t]``; in chunk
    mode where ``chunk_mask`` admits anything): real tokens are a prefix of
    a row's ``T``, and only they are given to experts.

    Without ``chunk`` (a bucketed prefill into a private cache): K/V of
    ``[0, T)`` go to the full leaves and the last ``window`` real positions
    to the private ring in ring order; ``write_at`` must be the literal 0
    and the cache as long as the call (prefix reuse is not carried). With
    ``causal_lens`` and a bucket the flash kernel tiles, attention runs
    through it, banded for a window layer (``prefill_flash_engages``).
    With ``chunk`` (decode steps, prefill chunks of at most ``window``
    columns): the grid is read-only and this call's K/V land at column
    ``chunk_col`` of the chunk; ``mask`` must be a prefix mask (``m <
    depth[b]``, as both callers build it), whose length is the depth the
    rings were written to; ``grid_depth`` [B] (that length, handed in) lets
    one query position a row take the ragged kernel.

    ``counters``: in chunk mode the expert layers' counts over the rows the
    chunk mask admits, summed over layers; ``{}`` for a prefill (the
    generator counts a prefill's on the host)."""
    if lora is not None:
        raise refusal(_LABEL, _REFUSED, "adapters")
    B, T = tokens.shape
    H, Hkv, D, W = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.window
    sin, cos = rope_angles(positions, D, cfg.rope_theta)
    x = embed(params, tokens)
    totals = {name: jnp.zeros((), jnp.int32) for name in experts.COUNTERS}

    def add(totals, counters):
        return {name: totals[name] + counters.get(name, 0)
                for name in totals}

    if chunk is None:
        M = cache["k"].shape[2]
        if not (isinstance(write_at, int) and write_at == 0 and M == T):
            raise refusal(_LABEL, _REFUSED, "prefix")
        real = jnp.diagonal(mask, axis1=1, axis2=2)                 # [B,T]
        flash = causal_lens is not None and flash_attention.prefill_engages(
            T, M, write_at, H, Hkv, D)
        # the private ring of a bucket under the window is the bucket; a
        # longer one takes the prompt's last ``window`` positions, each at
        # its slot
        order = None if cache["wk"].shape[2] == T else ring_positions(
            jnp.sum(real, axis=1, dtype=jnp.int32), W)[0]
        band = None
        if not flash and T > W:
            t = jnp.arange(T)
            band = mask & (t[:, None] - t[None, :] < W)[None]

        def body(carry, stack, i, kind):
            x, leaves = carry

            def attend(q, k, v):
                if kind == WINDOW and order is not None:
                    held = tuple(jnp.take_along_axis(
                        a, order[:, :, None, None], axis=1) for a in (k, v))
                else:
                    held = (k, v)
                kept = {**leaves, **{
                    n: jax.lax.dynamic_update_slice(
                        leaves[n], new.astype(leaves[n].dtype)[None],
                        (i, 0, 0, 0, 0))
                    for n, new in zip(KV[kind], held)}}
                with jax.named_scope("window_attention_prefill"
                                     if kind == WINDOW
                                     else "full_attention_prefill"):
                    if flash:
                        return flash_attention.prefill_attention(
                            q, k, v, W if kind == WINDOW and T > W else None
                        ), kept
                    return cached_attn(
                        q, k, v,
                        band if kind == WINDOW and band is not None
                        else mask), kept

            x, leaves, _ = _block(x, real, stack, i, kind, sin, cos, attend,
                                  cfg)
            return x, leaves

        x, leaves = _scan_layers(params, cfg, (x, dict(cache)), body)
        return unembed(x, params, cfg, unembed_positions), leaves, {}

    M, span = cache["k"].shape[2], cache["wk"].shape[2]
    C = chunk["k"].shape[2]
    if C > span:
        raise ValueError(f"a chunk of {C} columns is wider than the ring "
                         f"of {span} positions")
    depth = (grid_depth if grid_depth is not None
             else jnp.sum(mask[:, 0, :], axis=-1, dtype=jnp.int32))
    items = None
    if grid_depth is not None and decode_attention.engages(
            T, M, Hkv, D, cache["k"].dtype) and decode_attention.engages(
            T, span, Hkv, D, cache["wk"].dtype):
        items = {FULL: decode_attention.plan(depth, M),
                 WINDOW: decode_attention.ring_plan(
                     depth, positions[:, 0] - depth, span)}
        ring_mask = None
    else:
        held, there = ring_positions(depth, span)
        ring_mask = (there[:, None, :]
                     & (held[:, None, :] > positions[:, :, None] - W)
                     & jnp.any(mask, axis=-1, keepdims=True))       # [B,T,W]
    # rows this call computes for: those with anything to attend to
    valid = jnp.any(chunk_mask, axis=2)                             # [B,T]

    def body(carry, stack, i, kind):
        x, cols, totals = carry
        gk, gv = (cache[n] for n in KV[kind])

        def attend(q, k, v):
            kept = {**cols, **{
                n: jax.lax.dynamic_update_slice(
                    cols[n], new.astype(cols[n].dtype)[None],
                    (i, 0, chunk_col, 0, 0))
                for n, new in zip(KV[kind], (k, v))}}
            ek, ev = (layer_at(kept[n], i) for n in KV[kind])
            with jax.named_scope("window_attention_decode"
                                 if kind == WINDOW
                                 else "full_attention_decode"):
                if items is not None:
                    # float32 queries: 7 query heads a kv head are not a
                    # whole bfloat16 tile (``decode_attention.engages``)
                    return cached_attn_ragged(
                        q.astype(jnp.float32), gk, gv, None, None, i,
                        items[kind], ek, ev, chunk_mask
                    ).astype(q.dtype), kept
                return cached_attn_merged(
                    q, layer_at(gk, i), layer_at(gv, i), ek, ev,
                    ring_mask if kind == WINDOW else mask,
                    chunk_mask), kept

        x, cols, counters = _block(x, valid, stack, i, kind, sin, cos,
                                   attend, cfg)
        return x, cols, add(totals, counters)

    x, cols, totals = _scan_layers(params, cfg, (x, dict(chunk), totals),
                                   body)
    return unembed(x, params, cfg, unembed_positions), cols, totals


class WindowMoEDecoder(Decoder):
    """``models/decoder.py``'s interface over this module."""

    counters = experts.COUNTERS
    label, refused = _LABEL, _REFUSED
    layer_kinds = staticmethod(layer_kinds)
    init_cache = staticmethod(init_cache)
    merge_chunk_into_grid = staticmethod(merge_chunk_into_grid)
    forward_cached = staticmethod(forward_cached)

    @staticmethod
    def cache_leaves(cfg: WindowMoEConfig, quantized: bool = False):
        if quantized:
            raise refusal(_LABEL, _REFUSED, "kv_dtype")
        vec, dt = (cfg.n_kv_heads, cfg.head_dim), cfg.compute_dtype
        return {FULL: tuple(CacheLeaf(n, vec, dt) for n in KV[FULL]),
                WINDOW: tuple(CacheLeaf(n, vec, dt, True, cfg.window)
                              for n in KV[WINDOW])}

    @staticmethod
    def init_cache_like(cfg, cache, batch, max_len):
        return init_cache(cfg, batch, max_len, dtype=cache["k"].dtype)

    @staticmethod
    def ragged_block(cfg, max_len, cache, spec: bool) -> Optional[int]:
        """The key block the ragged kernel reads a leaf of ``max_len``
        positions in (the grid's for ``k`` / ``v``, the span for a ring),
        or None where the einsum pair streams it whole."""
        if spec or not decode_attention.engages(
                1, max_len, cfg.n_kv_heads, cfg.head_dim, cache["k"].dtype):
            return None
        return decode_attention.block_for(max_len)

    @staticmethod
    def prefill_flash_engages(cfg, p_pad: int) -> bool:
        return flash_attention.prefill_engages(
            p_pad, p_pad, 0, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)

    @staticmethod
    def window_key_blocks(cfg, p_pad: int) -> Tuple[int, int]:
        """(visited, band) key blocks of one row's admission at ``p_pad``
        positions, a window layer, summed over its query heads."""
        visited, band = flash_attention.prefill_key_blocks(
            p_pad, cfg.window,
            WindowMoEDecoder.prefill_flash_engages(cfg, p_pad))
        return cfg.n_heads * visited, cfg.n_heads * band

    @staticmethod
    def expert_admission(cfg: WindowMoEConfig, lens, p_pad: int):
        return experts.expert_admission(cfg, lens, p_pad, _held_bytes(cfg),
                                        cfg.n_layers)

    @staticmethod
    def prefill_counters(cfg: WindowMoEConfig, prompt_tokens: int):
        return {"moe_assignments": experts.moe_assignments(
            cfg, prompt_tokens, cfg.n_layers)}
