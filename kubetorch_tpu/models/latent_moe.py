"""A decoder with latent (compressed) attention and dropless sigmoid-routed
experts: the serving engine's second decoder (``models/decoder.py``).

The layer, with ``n = RMSNorm(x)`` (the DeepSeek-V3 family's public configs;
``LatentMoEConfig`` names the sizes):

- *Attention, every layer.* ``q = n W_q`` -> H heads of ``dn + dr``, split
  ``q_nope | q_rope``; ``[c ; k_r] = n W_kva`` with ``c`` of ``kv_latent_dim``
  and ONE rope key ``k_r`` of ``dr`` a position, shared by all heads;
  ``c <- RMSNorm(c)``; rope on ``q_rope`` and ``k_r`` (pairs (0,1),(2,3),..:
  the published weights are interleaved). ``[k_nope,h ; v_h] = c W_kvb,h``.
  ``score_h(t,s) = (q_nope,h(t).k_nope,h(s) + q_rope,h(t).k_r(s)) /
  sqrt(dn + dr)``, causal softmax, ``o_h = sum p v_h``, ``attn = [o_h] W_o``.
  **The cache holds ``(c, k_r)``: ``kv_latent_dim + dr`` numbers a position a
  layer, nothing a head**, as ONE leaf ``ckr = [c | k_r | 0]`` padded to the
  TPU's 128-lane tile (an array whose minor dimension is 64 is stored at 128
  anyway, and a block of it cannot be sliced by a kernel's DMA; one leaf is
  one DMA an item, one select a merge).
- Two attention paths over that cache. *Prefill* expands ``k_nope`` and
  ``v`` from ``c`` and runs blocked attention with key width ``dn + dr``
  beside value width ``dv`` (``ops/latent_attention.py``'s flash kernel on
  the TPU, the masked einsum elsewhere). *Decode* (and every chunk-mode
  forward) absorbs: ``q~_h = W_kb,h^T q_nope,h``, ``score = q~_h.c(s) +
  q_rope,h.k_r(s)``, ``ctx_h = sum p c(s)``, ``o_h = W_vb,h ctx_h``: a step
  reads each position's latent once for all heads and never makes K or V.
  On one TPU device the grid half runs in the ragged kernel of
  ``ops/latent_attention.py``, each row read to its own depth, on
  ``ops/decode_attention.py``'s work list; the einsum over all positions is
  its oracle and every other backend's path.
- *Feed-forward.* The first ``n_dense_layers`` layers: SwiGLU of
  ``dense_mlp_dim``. The others, with ``m = RMSNorm(h)``: ``s = sigmoid(m
  W_g)`` in float32; the ``top_k`` experts are the largest of ``s + b``
  (``b`` a weight that only selects); weights ``g_i = routed_scale * s_i /
  sum_chosen s_j``; ``y = sum_i g_i E_i(m) + S(m)`` with ``S`` one SwiGLU of
  ``n_shared_experts * expert_mlp_dim``. Group-limited routing (``n_group``
  > 1) is not carried: the configuration object has no such field. **No
  token is dropped and no expert computes a token it was not given**: the
  step's (token, expert) pairs are sorted by expert and the gate/up and
  down products are grouped matrix products over the uneven groups
  (``models/experts.py``: ``routed_experts``, shared with the fourth and
  fifth decoders, over ``ops/grouped_matmul.py``), which read only the
  experts given a token.

Layers of one kind are stacked (``params["dense"]``, ``params["moe"]``) and
scanned by INDEX, the stacks closed over: a scanned slice handed to a Pallas
call would be copied first (1.2 GB of experts a layer a step), so the
kernels take the stack and the layer index, as the decode attention takes
the cache.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from kubetorch_tpu.models import experts
from kubetorch_tpu.models.configs import LatentMoEConfig
from kubetorch_tpu.models.decoder import (CacheLeaf, Decoder, embed,
                                          layer_at, refusal, unembed)
from kubetorch_tpu.ops import grid_write, latent_attention
from kubetorch_tpu.ops.norms import rms_norm
from kubetorch_tpu.ops.rope import rope_angles

Params = Dict[str, Any]
_LABEL = "the latent-attention decoder (models/latent_moe.py)"
# what RollingGenerator can be asked for that this decoder does not carry
_REFUSED = {
    "kv_dtype": "an int8 latent cache (kv_dtype='int8')",
    "spec": "speculative decode (spec_k > 1)",
    "adapters": "LoRA adapters",
    "mesh": "a tensor- or expert-parallel mesh",
    "prefix": "prefix reuse (register_prefix / prefix split / prefix cache)",
    "handoff": "disaggregated prefill/decode handoff",
}


# ------------------------------------------------------------------ init
def _attn_shapes(cfg: LatentMoEConfig) -> Dict[str, Tuple[int, ...]]:
    E, H = cfg.embed_dim, cfg.n_heads
    return {"wq": (E, H * cfg.qk_head_dim),
            "wkv_a": (E, cfg.kv_latent_dim + cfg.qk_rope_dim),
            "wkv_b": (cfg.kv_latent_dim,
                      H * (cfg.qk_nope_dim + cfg.v_head_dim)),
            "wo": (H * cfg.v_head_dim, E)}


def layer_shapes(cfg: LatentMoEConfig, kind: str) -> Dict[str, tuple]:
    """leaf -> shape of ONE layer of ``kind`` (``dense`` | ``moe``);
    matrices are ``[in, out]``, experts ``[X, in, out]``, gate and up fused
    along the output."""
    E = cfg.embed_dim
    out = dict(_attn_shapes(cfg))
    out.update({"attn_norm": (E,), "mlp_norm": (E,),
                "kv_norm": (cfg.kv_latent_dim,)})
    if kind == "dense":
        out.update({"w_gu": (E, 2 * cfg.dense_mlp_dim),
                    "w_down": (cfg.dense_mlp_dim, E)})
    else:
        Mx, Ms = cfg.expert_mlp_dim, cfg.n_shared_experts * cfg.expert_mlp_dim
        out.update({"router": (E, cfg.n_experts),
                    "router_bias": (cfg.n_experts,),
                    "we_gu": (cfg.n_experts, E, 2 * Mx),
                    "we_down": (cfg.n_experts, Mx, E),
                    "ws_gu": (E, 2 * Ms), "ws_down": (Ms, E)})
    return out


def init(key: jax.Array, cfg: LatentMoEConfig) -> Params:
    """Random parameters (1/sqrt(fan_in) matrices, unit norms); the router
    and its selection bias stay float32."""
    dt = cfg.storage_dtype

    def leaf(k, name, shape, n):
        if name.endswith("norm"):
            return jnp.ones((n,) + shape, dt)
        if name == "router_bias":
            return 0.1 * jax.random.normal(k, (n,) + shape, jnp.float32)
        w = jax.random.normal(k, (n,) + shape, jnp.float32) * shape[-2] ** -0.5
        return w if name == "router" else w.astype(dt)

    params: Params = {}
    k_emb, k_head, key = jax.random.split(key, 3)
    params["embedding"] = jax.random.normal(
        k_emb, (cfg.vocab_size, cfg.embed_dim), jnp.float32).astype(dt)
    params["final_norm"] = jnp.ones((cfg.embed_dim,), dt)
    params["lm_head"] = (jax.random.normal(
        k_head, (cfg.embed_dim, cfg.vocab_size), jnp.float32)
        * cfg.embed_dim ** -0.5).astype(dt)
    for kind, n in (("dense", cfg.n_dense_layers), ("moe", cfg.n_moe_layers)):
        shapes = layer_shapes(cfg, kind)
        keys = jax.random.split(jax.random.fold_in(key, len(kind)),
                                len(shapes))
        params[kind] = {name: leaf(k, name, shape, n)
                        for k, (name, shape) in zip(keys, shapes.items())}
    return params


# ------------------------------------------------------------- attention
def _rope_pairs(x, sin, cos):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by angle
    ``i``; returns them de-interleaved, ``[rotated evens, rotated odds]``,
    as the published implementation lays them out. Queries and keys take
    the same permutation, so scores do not see it. ``sin``/``cos``
    broadcast against ``x[..., ::2]``."""
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1).astype(x.dtype)


def ckr_width(cfg: LatentMoEConfig) -> int:
    """Width of the cache's one leaf: latent + rope key, rounded up to the
    lane tile."""
    return -(-(cfg.kv_latent_dim + cfg.qk_rope_dim) // 128) * 128


def _pack(c, k_r, cfg: LatentMoEConfig, dtype):
    """``[c | k_r | 0]`` along the last axis, in the cache's dtype."""
    pad = ckr_width(cfg) - c.shape[-1] - k_r.shape[-1]
    return jnp.concatenate(
        [c.astype(dtype), k_r.astype(dtype),
         jnp.zeros(c.shape[:-1] + (pad,), dtype)], axis=-1)


def _attn_inputs(x, layer, sin, cos, cfg: LatentMoEConfig):
    """x [B,T,E] (already normed) -> q_nope [B,T,H,dn], q_rope [B,T,H,dr],
    c [B,T,r] (normed), k_r [B,T,dr] (roped)."""
    B, T, _ = x.shape
    H, dn, dr, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                    cfg.kv_latent_dim)
    dt = cfg.compute_dtype
    q = jnp.einsum("bte,en->btn", x, layer["wq"].astype(dt)).reshape(
        B, T, H, dn + dr)
    kva = jnp.einsum("bte,en->btn", x, layer["wkv_a"].astype(dt))
    c = rms_norm(kva[..., :r], layer["kv_norm"], cfg.rms_eps)
    q_rope = _rope_pairs(q[..., dn:], sin[:, :, None, :], cos[:, :, None, :])
    k_r = _rope_pairs(kva[..., r:], sin, cos)
    return q[..., :dn], q_rope, c, k_r


def _kvb(layer, cfg: LatentMoEConfig):
    """W_kvb [r, H*(dn+dv)] -> (W_kb [r,H,dn], W_vb [r,H,dv])."""
    w = layer["wkv_b"].astype(cfg.compute_dtype).reshape(
        cfg.kv_latent_dim, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def _attn_expand(q_nope, q_rope, c, k_r, layer, mask, cfg: LatentMoEConfig):
    """Attention of T tokens over themselves, K and V expanded from the
    latent (the prefill path). ``mask`` [B,T,T] is causal and clipped to
    each row's real tokens; the flash kernel applies the causal half alone,
    which differs only at padded positions, whose outputs nobody reads."""
    B, T, H, dn = q_nope.shape
    w_kb, w_vb = _kvb(layer, cfg)
    k_nope = jnp.einsum("btr,rhd->bthd", c, w_kb)
    v = jnp.einsum("btr,rhd->bthd", c, w_vb)
    scale = cfg.qk_head_dim ** -0.5
    with jax.named_scope("latent_attention_prefill"):
        if latent_attention.prefill_engages(T):
            out = latent_attention.prefill_attention(
                q_nope, q_rope, k_nope, k_r, v, scale)
        else:
            f32 = jnp.float32
            s = (jnp.einsum("bthd,bshd->bhts", q_nope.astype(f32),
                            k_nope.astype(f32))
                 + jnp.einsum("bthd,bsd->bhts", q_rope.astype(f32),
                              k_r.astype(f32))) * scale
            p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
            out = jnp.einsum("bhts,bshd->bthd", p, v.astype(f32)).astype(
                q_nope.dtype)
    return out.reshape(B, T, H * cfg.v_head_dim)


def _attn_absorbed(q_nope, q_rope, layer, li, grid, chunk, gmask, emask,
                   items, cfg: LatentMoEConfig):
    """Absorbed attention over the read-only grid (``grid`` [L,B,M,W]
    stacked, layer ``li``) plus the chunk's few columns (``chunk``
    [B,K,W]); one softmax spans both. With ``items`` (the grid mask as the
    ragged kernel's work list, one query position) the grid half runs in the
    kernel and joins by the log-sum-exp rule; otherwise the einsum over all
    positions, the kernel's oracle."""
    B, T, H, _ = q_nope.shape
    r = cfg.kv_latent_dim
    w_kb, w_vb = _kvb(layer, cfg)
    scale = cfg.qk_head_dim ** -0.5
    # operands in the cache's precision, f32 accumulation (as the dense
    # decoder's cached attention)
    odt = jnp.float32 if grid.dtype == jnp.float32 else jnp.bfloat16
    f32 = jnp.float32
    # the query in the cache's own coordinates: [W_kb^T q_nope | q_rope | 0]
    q = _pack(jnp.einsum("bthd,rhd->bthr", q_nope, w_kb), q_rope, cfg, odt)
    chunk = chunk.astype(odt)
    se = jnp.einsum("bthw,bkw->bhtk", q, chunk,
                    preferred_element_type=f32) * scale
    se = jnp.where(emask[:, None], se, -1e30)
    with jax.named_scope("latent_attention_decode"):
        if items is not None:
            acc_g, m_g, l_g = latent_attention.ragged_decode_attention(
                q[:, 0], grid, li, items, r, scale,
                interpret=jax.default_backend() != "tpu")
            se = se[:, :, 0]                                    # [B,H,K]
            m = jnp.maximum(m_g, jnp.max(se, axis=-1))
            pe = jnp.exp(se - m[..., None])
            wg = jnp.exp(m_g - m)
            ctx = (wg[..., None] * acc_g
                   + jnp.einsum("bhk,bkr->bhr", pe.astype(odt),
                                chunk[..., :r], preferred_element_type=f32))
            ctx = (ctx / (wg * l_g + jnp.sum(pe, axis=-1))[..., None]
                   )[:, None]                                   # [B,1,H,r]
        else:
            g = jax.lax.dynamic_index_in_dim(grid, li, 0, False).astype(odt)
            sg = jnp.einsum("bthw,bmw->bhtm", q, g,
                            preferred_element_type=f32) * scale
            sg = jnp.where(gmask[:, None], sg, -1e30)
            p = jax.nn.softmax(jnp.concatenate([sg, se], axis=-1), axis=-1)
            M = g.shape[1]
            # a float grid may hold anything past a row's depth
            gc = jnp.where(jnp.any(gmask, axis=1)[:, :, None], g[..., :r], 0)
            ctx = (jnp.einsum("bhtm,bmr->bthr", p[..., :M].astype(odt), gc,
                              preferred_element_type=f32)
                   + jnp.einsum("bhtk,bkr->bthr", p[..., M:].astype(odt),
                                chunk[..., :r], preferred_element_type=f32))
    out = jnp.einsum("bthr,rhd->bthd", ctx.astype(cfg.compute_dtype), w_vb)
    return out.reshape(B, T, H * cfg.v_head_dim)


# ---------------------------------------------------------- feed-forward
def _swiglu(x, w_gu, w_down, dt):
    h = jnp.einsum("...e,en->...n", x, w_gu.astype(dt))
    half = h.shape[-1] // 2
    return jnp.einsum("...m,me->...e",
                      jax.nn.silu(h[..., :half]) * h[..., half:],
                      w_down.astype(dt))


def route(m, router, bias, cfg: LatentMoEConfig):
    """m [n,E] -> (experts [n,K] int32, weights [n,K] f32). Scores are
    float32 at the highest matmul precision (a bf16 pass would flip near-tied
    choices); the bias enters the choice and not the weight."""
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.matmul(
            m.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                  cfg.top_k)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), w * cfg.routed_scale


def _feed_forward(x, valid, stack, i, kind, cfg: LatentMoEConfig):
    """x [B,T,E] (the float32 residual stream after attention) -> (x + ffn,
    counters). ``stack`` is the kind's stacked leaves, ``i`` the layer's
    index. The router reads the norm in float32; the products read it in
    the compute dtype."""
    dt = cfg.compute_dtype

    def at(name):
        return layer_at(stack[name], i)

    m32 = rms_norm(x, at("mlp_norm"), cfg.rms_eps)          # float32
    m = m32.astype(dt)
    if kind == "dense":
        return x + _swiglu(m, at("w_gu"), at("w_down"), dt).astype(
            x.dtype), {}
    B, T, E = m.shape
    chosen, weights = route(m32.reshape(B * T, E), at("router"),
                            at("router_bias"), cfg)
    y, counters = experts.routed_experts(
        m.reshape(B * T, E), valid.reshape(-1), chosen, weights,
        stack["we_gu"], stack["we_down"], i, cfg)
    with jax.named_scope("moe_shared"):
        shared = _swiglu(m, at("ws_gu"), at("ws_down"), dt)
    return (x + y.reshape(B, T, E).astype(x.dtype)
            + shared.astype(x.dtype)), counters


# ------------------------------------------------------------- the stack
def layer_kinds(cfg: LatentMoEConfig) -> Tuple[str, ...]:
    return ("dense",) * cfg.n_dense_layers + ("moe",) * cfg.n_moe_layers


_ATTN_LEAVES = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")


def _attn_layer(stack, i):
    """Layer ``i``'s attention leaves out of a kind's stack."""
    return {k: layer_at(stack[k], i) for k in _ATTN_LEAVES}


def _scan_layers(params, cfg: LatentMoEConfig, x, extra, body):
    """Run ``body(x, extra, stack, i, li, kind) -> (x, extra, counters)``
    over the layers: one ``lax.scan`` a kind over the layer's index, the
    stacks closed over. Returns (x, extra, summed counters)."""
    totals = {name: jnp.zeros((), jnp.int32) for name in experts.COUNTERS}
    first = 0
    for kind, n in (("dense", cfg.n_dense_layers),
                    ("moe", cfg.n_moe_layers)):
        if not n:
            continue
        stack = params[kind]

        def step(carry, i, stack=stack, kind=kind, first=first):
            x, extra, totals = carry
            x, extra, counters = body(x, extra, stack, i, first + i, kind)
            totals = {name: totals[name] + counters.get(name, 0)
                      for name in totals}
            return (x, extra, totals), None

        (x, extra, totals), _ = jax.lax.scan(
            step, (x, extra, totals), jnp.arange(n, dtype=jnp.int32))
        first += n
    return x, extra, totals


def _angles(positions, cfg: LatentMoEConfig):
    return rope_angles(positions, cfg.qk_rope_dim, cfg.rope_theta)


def forward(params: Params, tokens: jax.Array, cfg: LatentMoEConfig):
    """Uncached forward of whole sequences: tokens [B,T] -> logits [B,T,V]
    float32 (tests; the serving paths are ``forward_cached``)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    sin, cos = _angles(positions, cfg)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool))[None],
                            (B, T, T))
    valid = jnp.ones((B, T), bool)

    def body(x, extra, stack, i, li, kind):
        layer = _attn_layer(stack, i)
        h = rms_norm(x, layer["attn_norm"], cfg.rms_eps).astype(
            cfg.compute_dtype)
        qn, qr, c, kr = _attn_inputs(h, layer, sin, cos, cfg)
        attn = _attn_expand(qn, qr, c, kr, layer, mask, cfg)
        x = x + jnp.einsum("btn,ne->bte", attn, layer["wo"].astype(
            cfg.compute_dtype)).astype(x.dtype)
        x, counters = _feed_forward(x, valid, stack, i, kind, cfg)
        return x, extra, counters

    x = embed(params, tokens)
    x, _, _ = _scan_layers(params, cfg, x, (), body)
    return unembed(x, params, cfg)


def init_cache(cfg: LatentMoEConfig, batch: int, max_len: int, dtype=None,
               quantized: bool = False) -> Dict[str, jax.Array]:
    """``{"ckr": [L,B,M,W]}``: the normed latent and the roped shared key
    of every position of every layer, ``[c | k_r | 0]`` (``ckr_width``)."""
    if quantized:
        raise refusal(_LABEL, _REFUSED, "kv_dtype")
    dt = jnp.dtype(dtype) if dtype is not None else cfg.compute_dtype
    return {"ckr": jnp.zeros(
        (cfg.n_layers, batch, max_len, ckr_width(cfg)), dt)}


def merge_chunk_into_grid(cache, chunk, start, count):
    """Write chunk columns ``[0, count[b])`` into grid positions
    ``start[b] + col`` of every layer and leaf:
    ``llama.merge_chunk_into_grid``'s row loop of slice updates
    (``ops/grid_write.py``; why that is not a scatter: its docstring), over
    leaves of one vector a position. Only the rows' windows are read and
    written, never a layer's whole ``[B, M]`` plane."""
    return grid_write.write_columns(cache, chunk, start, count)


def forward_cached(params: Params, tokens, positions, cache, write_at, mask,
                   cfg: LatentMoEConfig, rules=None, unembed_positions=None,
                   chunk=None, chunk_col=None, chunk_mask=None, lora=None,
                   grid_depth=None, causal_lens=None):
    """``llama.forward_cached``'s contract over the latent cache ->
    (logits [B,T,V] float32, new cache or chunk, counters).

    Without ``chunk`` (a bucketed prefill into a private cache): the tokens
    attend to themselves through the EXPAND path and their ``(c, k_r)``
    are written at positions ``[0, T)``; ``write_at`` must be the literal 0
    and the cache as long as the call (prefix reuse, which would write
    behind a spliced prefix, is not carried); ``causal_lens`` (the caller's
    statement that ``mask`` is causal from 0 under a length) is accepted and
    changes nothing: this prefill is that by construction. With ``chunk``
    (decode steps, prefill chunks): the grid is read-only, this call's
    ``(c, k_r)`` land at column ``chunk_col`` of the chunk, and attention is
    the ABSORBED
    path over grid and chunk; ``grid_depth`` [B] (the grid mask as a
    length) lets one query position a row take the ragged kernel.

    ``counters``: in chunk mode the expert layers' counts over the rows the
    chunk mask admits, summed over layers; ``{}`` for a prefill (the
    generator counts a prefill's on the host)."""
    if lora is not None:
        raise refusal(_LABEL, _REFUSED, "adapters")
    B, T = tokens.shape
    sin, cos = _angles(positions, cfg)
    x = embed(params, tokens)

    def normed(x, layer):
        return rms_norm(x, layer["attn_norm"], cfg.rms_eps).astype(
            cfg.compute_dtype)

    def project_out(x, attn, layer):
        return x + jnp.einsum("btn,ne->bte", attn, layer["wo"].astype(
            cfg.compute_dtype)).astype(x.dtype)

    if chunk is None:
        M = cache["ckr"].shape[2]
        if not (isinstance(write_at, int) and write_at == 0 and M == T):
            # a private cache is prefilled from position 0 only
            raise refusal(_LABEL, _REFUSED, "prefix")
        # a position no real token occupies is given to no expert
        valid = jnp.any(mask, axis=1)                               # [B,T]

        def body(x, grid, stack, i, li, kind):
            layer = _attn_layer(stack, i)
            qn, qr, c, kr = _attn_inputs(normed(x, layer), layer, sin, cos,
                                         cfg)
            grid = jax.lax.dynamic_update_slice(
                grid, _pack(c, kr, cfg, grid.dtype)[None], (li, 0, 0, 0))
            x = project_out(x, _attn_expand(qn, qr, c, kr, layer, mask, cfg),
                            layer)
            x, _ = _feed_forward(x, valid, stack, i, kind, cfg)
            return x, grid, {}

        x, grid, _ = _scan_layers(params, cfg, x, cache["ckr"], body)
        return (unembed(x, params, cfg, unembed_positions),
                {"ckr": grid}, {})

    items = None
    if grid_depth is not None and latent_attention.decode_engages(
            T, cache["ckr"].shape[2]):
        items = latent_attention.plan(grid_depth, cache["ckr"].shape[2])
    # rows this call computes for: those with anything to attend to
    valid = jnp.any(chunk_mask, axis=2)                             # [B,T]

    def body(x, cols, stack, i, li, kind):
        layer = _attn_layer(stack, i)
        qn, qr, c, kr = _attn_inputs(normed(x, layer), layer, sin, cos, cfg)
        cols = jax.lax.dynamic_update_slice(
            cols, _pack(c, kr, cfg, cols.dtype)[None],
            (li, 0, chunk_col, 0))
        attn = _attn_absorbed(
            qn, qr, layer, li, cache["ckr"],
            jax.lax.dynamic_index_in_dim(cols, li, 0, False),
            mask, chunk_mask, items, cfg)
        x = project_out(x, attn, layer)
        x, counters = _feed_forward(x, valid, stack, i, kind, cfg)
        return x, cols, counters

    x, cols, counters = _scan_layers(params, cfg, x, chunk["ckr"], body)
    return (unembed(x, params, cfg, unembed_positions),
            {"ckr": cols}, counters)


class LatentMoEDecoder(Decoder):
    """``models/decoder.py``'s interface over this module."""

    counters = experts.COUNTERS
    label, refused = _LABEL, _REFUSED
    layer_kinds = staticmethod(layer_kinds)
    init_cache = staticmethod(init_cache)
    forward_cached = staticmethod(forward_cached)
    merge_chunk_into_grid = staticmethod(merge_chunk_into_grid)

    @staticmethod
    def cache_leaves(cfg: LatentMoEConfig, quantized: bool = False):
        leaves = (CacheLeaf("ckr", (ckr_width(cfg),), cfg.compute_dtype),)
        return {"dense": leaves, "moe": leaves}

    @staticmethod
    def init_cache_like(cfg, cache, batch, max_len):
        return init_cache(cfg, batch, max_len, dtype=cache["ckr"].dtype)

    @staticmethod
    def ragged_block(cfg, max_len, cache, spec: bool) -> Optional[int]:
        if not latent_attention.decode_engages(1, max_len):
            return None
        return latent_attention.block_for(max_len)

    @staticmethod
    def prefill_flash_engages(cfg, p_pad: int) -> bool:
        """Nothing here is chosen by the caller's ``causal_lens``: the
        expand path takes its own kernel by its own rule
        (``latent_attention.prefill_engages``)."""
        return False

    @staticmethod
    def prefill_counters(cfg: LatentMoEConfig, prompt_tokens: int):
        return {"moe_assignments": experts.moe_assignments(
            cfg, prompt_tokens, cfg.n_moe_layers)}

    @staticmethod
    def expert_admission(cfg: LatentMoEConfig, lens, p_pad: int):
        """Every bucket in one pass: this decoder has no pieces."""
        return experts.expert_admission(cfg, lens, p_pad, None,
                                        cfg.n_moe_layers)
