"""A decoder whose mixers are of two kinds, five to one: delta-rule layers
whose state decays a CHANNEL (KDA) beside latent-attention layers (MLA), most
of them in front of group-limited sigmoid-routed experts of which this
program holds a SHARE (the ``bailing_hybrid`` public config): the serving
engine's sixth decoder (``models/decoder.py``), the first whose cache keeps
row-state leaves beside a LATENT plane and the first to run the local part
of expert parallelism.

Pre-norm residual blocks on the float32 stream: ``x' = x + mixer(RMSNorm(x))``,
``x_next = x' + ffn(RMSNorm(x'))``. A layer's kind names both halves:
``kda_dense`` | ``kda_moe`` | ``mla_moe`` (``HybridLatentMoEConfig``).

- *KDA mixer*, on ``n = RMSNorm(x)``: ``[q~ | k~ | v~] = n W_qkv`` (H x dk,
  H x dk, H x dv); a depthwise causal convolution of ``conv_width`` taps over
  time on every channel (zero history before a sequence's first token), then
  SiLU; a head's ``q = q~ / |q~| dk^-1/2``, ``k = k~ / |k~|``; the decay, a
  channel: ``a = decay_lower_bound sigmoid(exp(A_log_h) (n W_f + dt_bias))``
  in ``(decay_lower_bound, 0)^(H x dk)``, ``alpha = exp(a)``; ``beta =
  sigmoid(n W_b)``, one a head; ``S <- (I - beta k k^T) Diag(alpha) S + beta
  k v^T``, ``o = S^T q`` (``ops/kda.py``); out ``W_o [RMSNorm_head(o) *
  sigmoid(n W_g)]``. **What a ROW keeps of such a layer has no positions:
  the state ``S`` (H x dk x dv, float32) and the convolution's tail.** One
  function runs it over ``T`` tokens from a state and a tail with each row's
  count of REAL tokens (a bucketed prefill, a decode step with count 1 or 0,
  a prefill chunk); ``T = 1`` takes ``kda.step`` (on one TPU device its
  kernel ``kda_step`` on the stacked state leaf in place, the rows that
  decode alone), anything longer the chunked scan ``kda.prefill_scan`` (on
  one TPU device the kernel ``kda_prefill``). A bucket longer than
  ``_SEGMENT`` tokens goes through the mixer in segments that hand state and
  tail on: exact (it is the recurrence's own property), and the projections,
  the convolution and the decays of one segment are all that is held.
- *MLA mixer*: ``q = n W_q`` -> H heads of ``dn + dr``; ``[c ; k_r] = n
  W_kva``, ``c <- RMSNorm(c)``; rope on ``q_rope`` and ``k_r`` (interleaved
  pairs); ``[k_nope,h ; v_h] = c W_kvb,h``; ``score_h(t,s) = (q_nope.k_nope
  + q_rope.k_r) / sqrt(dn + dr)``, causal softmax; then the head gate, one
  number a head: ``o_h <- o_h sigmoid(n W_gate)_h``; ``attn = [o_h] W_o``.
  The cache holds ``ckr = [c | k_r | 0]`` a position, padded to the lane
  tile. Prefill expands K and V from ``c`` (``ops/latent_attention.py``'s
  flash kernel on the TPU); decode and every chunk-mode forward absorb
  (``q~_h = W_kb,h^T q_nope,h`` against the latent itself; the ragged kernel
  reads each row to its own depth). These are this module's own equations
  over the shared ``ops/``, as the second decoder's are its own.
- *Feed-forward.* ``kda_dense``: a SwiGLU of ``dense_mlp_dim``. The others,
  with ``m = RMSNorm(x')``: ``s = sigmoid(m W_r)`` over ALL
  ``n_experts_routed`` in float32 at the highest matmul precision; the
  experts lie in ``n_group`` groups of consecutive ones, a group's score is
  the sum of its two largest ``s + b``, the ``topk_group`` best groups stay
  and the ``top_k`` largest ``s + b`` among their experts are chosen (``b``
  only selects); ``g_i = routed_scale s_i / sum_chosen s_j``; ``y = sum_{i
  chosen and HELD} g_i E_i(m) + S(m)``. **This program holds the experts
  ``experts_held`` and no others** (``models/experts.py``, ``held_first``):
  the weights are normalised over all chosen, a pair whose expert is absent
  is given to no expert, and what it would add is left out: it is another
  holder's term, and the exchange that would bring it is not here.

Layers of one kind are stacked (``params[kind]``) and scanned by index;
``state`` and ``conv`` are stacked over the KDA layers of both kinds in
layer order (the dense ones lead), ``ckr`` over the MLA layers. In chunk mode
the row-state leaves ride in the chunk: the grid is read-only there, the
state is not.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from kubetorch_tpu.models import experts
from kubetorch_tpu.models.configs import HybridLatentMoEConfig
from kubetorch_tpu.models.decoder import (CacheLeaf, Decoder, embed,
                                          layer_at, refusal, scan_runs,
                                          unembed)
from kubetorch_tpu.ops import grid_write, kda, latent_attention
from kubetorch_tpu.ops.norms import rms_norm
from kubetorch_tpu.ops.rope import rope_angles

Params = Dict[str, Any]
KDA_DENSE, KDA_MOE, MLA_MOE = "kda_dense", "kda_moe", "mla_moe"
ROW_LEAVES = ("state", "conv")
# most tokens the KDA mixer takes at once; a longer bucket goes in segments
_SEGMENT = 8192
# the expert layer's device counters, then this decoder's own: pairs whose
# expert is held, tokens that chose at least one held group, and the pairs of
# the decode steps alone (``moe_assignments`` also holds the admissions',
# counted on the host, where what is held cannot be known)
COUNTERS = experts.COUNTERS + ("moe_assignments_held", "moe_groups_held_hits",
                               "moe_assignments_step")
_LABEL = ("the hybrid latent-attention decoder "
          "(models/hybrid_latent_moe.py)")
# what RollingGenerator can be asked for that this decoder does not carry
_REFUSED = {
    "kv_dtype": "an int8 latent plane beside the float32 state "
                "(kv_dtype='int8')",
    "spec": "speculative decode (spec_k > 1): a rejected draft would need "
            "the recurrent state rolled back",
    "adapters": "LoRA adapters",
    "mesh": "a tensor- or expert-parallel mesh (the share of the experts is "
            "held without its exchange)",
    "prefix": "prefix reuse (register_prefix / prefix split / prefix "
              "cache): a prefix's end would need a snapshot of the "
              "recurrent state",
    "handoff": "disaggregated prefill/decode handoff",
}


# ------------------------------------------------------------------ init
def layer_shapes(cfg: HybridLatentMoEConfig, kind: str) -> Dict[str, tuple]:
    """leaf -> shape of ONE layer of ``kind``; matrices are ``[in, out]``,
    experts ``[X, in, out]`` (X the experts HELD), ``q | k | v`` and gate and
    up fused along the output."""
    E = cfg.embed_dim
    out: Dict[str, tuple] = {"attn_norm": (E,), "mlp_norm": (E,)}
    if kind == MLA_MOE:
        H = cfg.n_heads
        out.update({"wq": (E, H * cfg.qk_head_dim),
                    "wkv_a": (E, cfg.kv_latent_dim + cfg.qk_rope_dim),
                    "kv_norm": (cfg.kv_latent_dim,),
                    "wkv_b": (cfg.kv_latent_dim,
                              H * (cfg.qk_nope_dim + cfg.v_head_dim)),
                    "wgate": (E, H), "wo": (H * cfg.v_head_dim, E)})
    else:
        H, dk, dv = cfg.kda_heads, cfg.kda_key_dim, cfg.kda_value_dim
        out.update({"wqkv": (E, cfg.conv_channels),
                    "conv_w": (cfg.conv_width, cfg.conv_channels),
                    "wf": (E, H * dk), "a_log": (H,), "dt_bias": (H * dk,),
                    "wb": (E, H), "wg": (E, H * dv), "o_norm": (dv,),
                    "wo": (H * dv, E)})
    if kind == KDA_DENSE:
        out.update({"w_gu": (E, 2 * cfg.dense_mlp_dim),
                    "w_down": (cfg.dense_mlp_dim, E)})
    else:
        Mx, Ms = cfg.expert_mlp_dim, cfg.n_shared_experts * cfg.expert_mlp_dim
        out.update({"router": (E, cfg.n_experts_routed),
                    "router_bias": (cfg.n_experts_routed,),
                    "we_gu": (cfg.n_experts, E, 2 * Mx),
                    "we_down": (cfg.n_experts, Mx, E),
                    "ws_gu": (E, 2 * Ms), "ws_down": (Ms, E)})
    return out


# leaves kept in float32 whatever the storage dtype: the router, its bias,
# and the decay's two learned vectors
FLOAT32_LEAVES = ("router", "router_bias", "a_log", "dt_bias")


def init(key: jax.Array, cfg: HybridLatentMoEConfig) -> Params:
    """Random parameters (1/sqrt(fan_in) matrices, unit norms; ``exp(A_log)``
    in [0.5, 2] and ``dt_bias`` ~ N(0, 1): a gate that uses its range)."""
    dt = cfg.storage_dtype
    f32 = jnp.float32

    def leaf(k, name, shape, n):
        if name.endswith("norm"):
            return jnp.ones((n,) + shape, dt)
        if name == "a_log":
            return jax.random.uniform(k, (n,) + shape, f32, -0.7, 0.7)
        if name == "dt_bias":
            return jax.random.normal(k, (n,) + shape, f32)
        if name == "router_bias":
            return 0.1 * jax.random.normal(k, (n,) + shape, f32)
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
    for kind in cfg.KINDS:
        n = cfg.layer_types.count(kind)
        if not n:
            continue
        shapes = layer_shapes(cfg, kind)
        keys = jax.random.split(
            jax.random.fold_in(key, cfg.KINDS.index(kind)), len(shapes))
        params[kind] = {name: leaf(k, name, shape, n)
                        for k, (name, shape) in zip(keys, shapes.items())}
    return params


# ------------------------------------------------------------- KDA mixer
def _short_conv(x, tail, w, counts):
    """Depthwise causal convolution with history. ``x`` [B,T,C] this call's
    inputs, ``tail`` [B,K-1,C] the inputs before them (zeros at a
    sequence's start), ``w`` [K,C] (``w[K-1]`` weighs the current input),
    ``counts`` [B] each row's real tokens of this call -> (SiLU(y) [B,T,C]
    float32, new tail: the last K-1 inputs up to each row's last real token;
    a row with no real token keeps its tail)."""
    K = w.shape[0]
    T = x.shape[1]
    with jax.named_scope("kda_conv"):
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


def _step_kernel_engages(cfg: HybridLatentMoEConfig) -> bool:
    return kda.step_engages(cfg.kda_heads, cfg.kda_key_dim,
                            cfg.kda_value_dim)


def _step_plan(T: int, counts, cfg: HybridLatentMoEConfig):
    """The decoding rows of a one-token call as the step kernel's work list
    (``kda.step_plan``), or None where the XLA step or the scan runs."""
    if T == 1 and _step_kernel_engages(cfg):
        return kda.step_plan(counts > 0)
    return None


def _kda_inputs(n, layer, tail, counts, cfg: HybridLatentMoEConfig):
    """n [B,T,E] (normed, the compute dtype) -> q, k [B,T,H,dk], v
    [B,T,H,dv] (the compute dtype), log decay [B,T,H,dk] and beta [B,T,H]
    (float32; a position no real token occupies holds the state: 0 and 0),
    the new tail."""
    B, T, _ = n.shape
    H, dk, dv = cfg.kda_heads, cfg.kda_key_dim, cfg.kda_value_dim
    dt, f32 = cfg.compute_dtype, jnp.float32
    valid = (jnp.arange(T)[None, :] < counts[:, None])[..., None]  # [B,T,1]
    qkv = jnp.einsum("bte,en->btn", n, layer["wqkv"].astype(dt))
    qkv, tail = _short_conv(qkv, tail, layer["conv_w"], counts)     # f32
    q = _unit(qkv[..., :H * dk].reshape(B, T, H, dk)) * dk ** -0.5
    k = _unit(qkv[..., H * dk:2 * H * dk].reshape(B, T, H, dk))
    v = qkv[..., 2 * H * dk:].reshape(B, T, H, dv)
    f = jnp.einsum("bte,en->btn", n, layer["wf"].astype(dt),
                   preferred_element_type=f32)
    rate = jnp.repeat(jnp.exp(layer["a_log"].astype(f32)), dk)     # [H dk]
    a = cfg.decay_lower_bound * jax.nn.sigmoid(
        rate * (f + layer["dt_bias"].astype(f32)))
    beta = jax.nn.sigmoid(jnp.einsum(
        "bte,en->btn", n, layer["wb"].astype(dt),
        preferred_element_type=f32))
    a = jnp.where(valid, a, 0.0).reshape(B, T, H, dk)
    beta = jnp.where(valid, beta, 0.0)
    return q.astype(dt), k.astype(dt), v.astype(dt), a, beta, tail


def _kda_output(n, o, layer, cfg: HybridLatentMoEConfig):
    """``W_o [RMSNorm_head(o) * sigmoid(n W_g)]``; o [B,T,H,dv] float32."""
    B, T = o.shape[:2]
    dt = cfg.compute_dtype
    gate = jnp.einsum("bte,en->btn", n, layer["wg"].astype(dt))
    o = rms_norm(o, layer["o_norm"], cfg.rms_eps).reshape(B, T, -1)
    o = (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
    return jnp.einsum("btn,ne->bte", o, layer["wo"].astype(dt))


def _kda_tokens(n, layer, state, tail, counts, cfg: HybridLatentMoEConfig):
    """The mixer over ``T`` tokens from one layer's ``state`` [B,H,dk,dv]
    and ``tail`` -> (out [B,T,E], state, tail)."""
    q, k, v, a, beta, tail = _kda_inputs(n, layer, tail, counts, cfg)
    with jax.named_scope("kda_scan" if n.shape[1] > 1 else "kda_step"):
        if n.shape[1] == 1:
            o, state = kda.step(q[:, 0], k[:, 0], v[:, 0], a[:, 0],
                                beta[:, 0], state)
            o = o[:, None]
        else:
            o, state = kda.prefill_scan(q, k, v, a, beta, state)
    return _kda_output(n, o, layer, cfg), state, tail


def _kda_mixer(n, layer, states, i, tail, counts, plan,
               cfg: HybridLatentMoEConfig):
    """The KDA mixer on layer ``i`` of the stacked state leaf. ``n`` [B,T,E]
    normed, ``states`` [L,B,H,dk,dv] float32, ``tail`` [B,K-1,C], ``counts``
    [B], ``plan`` from ``_step_plan`` -> (out [B,T,E], the leaf with layer
    ``i`` advanced, new tail)."""
    B, T, E = n.shape
    if plan is not None:
        q, k, v, a, beta, tail = _kda_inputs(n, layer, tail, counts, cfg)
        with jax.named_scope("kda_step"):
            # the kernel works on the leaf in place: no slice of it is made
            o, states = kda.step_rows(q[:, 0], k[:, 0], v[:, 0], a[:, 0],
                                      beta[:, 0], states, i, plan)
        return _kda_output(n, o[:, None], layer, cfg), states, tail
    state = layer_at(states, i)
    if T <= _SEGMENT or T % _SEGMENT:
        out, state, tail = _kda_tokens(n, layer, state, tail, counts, cfg)
    else:
        def one(carry, seg):
            x, s = seg
            out, state, tail = _kda_tokens(
                x, layer, *carry, jnp.clip(counts - s * _SEGMENT, 0,
                                           _SEGMENT), cfg)
            return (state, tail), out

        S = T // _SEGMENT
        (state, tail), out = jax.lax.scan(
            one, (state, tail),
            (jnp.swapaxes(n.reshape(B, S, _SEGMENT, E), 0, 1),
             jnp.arange(S, dtype=jnp.int32)))
        out = jnp.swapaxes(out, 0, 1).reshape(B, T, E)
    return out, _put(states, state, i), tail


# ------------------------------------------------------------- MLA mixer
def _rope_pairs(x, sin, cos):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by angle
    ``i``; returns them de-interleaved, ``[rotated evens, rotated odds]``:
    queries and keys take the same permutation, so scores do not see it."""
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1).astype(x.dtype)


def ckr_width(cfg: HybridLatentMoEConfig) -> int:
    """Width of the latent leaf: latent + rope key, rounded up to the lane
    tile."""
    return -(-(cfg.kv_latent_dim + cfg.qk_rope_dim) // 128) * 128


def _pack(c, k_r, cfg: HybridLatentMoEConfig, dtype):
    """``[c | k_r | 0]`` along the last axis, in the cache's dtype."""
    pad = ckr_width(cfg) - c.shape[-1] - k_r.shape[-1]
    return jnp.concatenate(
        [c.astype(dtype), k_r.astype(dtype),
         jnp.zeros(c.shape[:-1] + (pad,), dtype)], axis=-1)


def _mla_inputs(n, layer, sin, cos, cfg: HybridLatentMoEConfig):
    """n [B,T,E] (normed) -> q_nope [B,T,H,dn], q_rope [B,T,H,dr], c
    [B,T,r] (normed), k_r [B,T,dr] (roped), the head gate [B,T,H] float32."""
    B, T, _ = n.shape
    H, dn, dr, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                    cfg.kv_latent_dim)
    dt = cfg.compute_dtype
    q = jnp.einsum("bte,en->btn", n, layer["wq"].astype(dt)).reshape(
        B, T, H, dn + dr)
    kva = jnp.einsum("bte,en->btn", n, layer["wkv_a"].astype(dt))
    c = rms_norm(kva[..., :r], layer["kv_norm"], cfg.rms_eps)
    q_rope = _rope_pairs(q[..., dn:], sin[:, :, None, :], cos[:, :, None, :])
    k_r = _rope_pairs(kva[..., r:], sin, cos)
    gate = jax.nn.sigmoid(jnp.einsum(
        "bte,en->btn", n, layer["wgate"].astype(dt),
        preferred_element_type=jnp.float32))
    return q[..., :dn], q_rope, c, k_r, gate


def _kvb(layer, cfg: HybridLatentMoEConfig):
    """W_kvb [r, H*(dn+dv)] -> (W_kb [r,H,dn], W_vb [r,H,dv])."""
    w = layer["wkv_b"].astype(cfg.compute_dtype).reshape(
        cfg.kv_latent_dim, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def _mla_expand(q_nope, q_rope, c, k_r, layer, mask,
                cfg: HybridLatentMoEConfig):
    """Attention of T tokens over themselves, K and V expanded from the
    latent (the prefill path) -> [B,T,H,dv]. ``mask`` [B,T,T] is causal and
    clipped to each row's real tokens; the flash kernel applies the causal
    half alone, which differs only at padded positions."""
    w_kb, w_vb = _kvb(layer, cfg)
    k_nope = jnp.einsum("btr,rhd->bthd", c, w_kb)
    v = jnp.einsum("btr,rhd->bthd", c, w_vb)
    scale = cfg.qk_head_dim ** -0.5
    with jax.named_scope("latent_attention_prefill"):
        if latent_attention.prefill_engages(q_nope.shape[1]):
            return latent_attention.prefill_attention(
                q_nope, q_rope, k_nope, k_r, v, scale)
        f32 = jnp.float32
        s = (jnp.einsum("bthd,bshd->bhts", q_nope.astype(f32),
                        k_nope.astype(f32))
             + jnp.einsum("bthd,bsd->bhts", q_rope.astype(f32),
                          k_r.astype(f32))) * scale
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", p, v.astype(f32)).astype(
            q_nope.dtype)


def _mla_absorbed(q_nope, q_rope, layer, li, grid, chunk, gmask, emask,
                  items, cfg: HybridLatentMoEConfig):
    """Absorbed attention over the read-only grid (``grid`` [L,B,M,W]
    stacked, layer ``li``) plus the chunk's few columns (``chunk``
    [B,K,W]); one softmax spans both -> [B,T,H,dv]. With ``items`` (the grid
    mask as the ragged kernel's work list, one query position) the grid half
    runs in the kernel and joins by the log-sum-exp rule; otherwise the
    einsum over all positions, the kernel's oracle."""
    r = cfg.kv_latent_dim
    w_kb, w_vb = _kvb(layer, cfg)
    scale = cfg.qk_head_dim ** -0.5
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
    return jnp.einsum("bthr,rhd->bthd", ctx.astype(cfg.compute_dtype), w_vb)


def _mla_out(x, attn, gate, layer, cfg: HybridLatentMoEConfig):
    """The head gate, then ``W_o`` onto the stream: attn [B,T,H,dv]."""
    B, T = attn.shape[:2]
    dt = cfg.compute_dtype
    with jax.named_scope("mla_gate"):
        attn = (attn.astype(jnp.float32) * gate[..., None]).astype(dt)
    return x + jnp.einsum("btn,ne->bte", attn.reshape(B, T, -1),
                          layer["wo"].astype(dt)).astype(x.dtype)


# ---------------------------------------------------------- feed-forward
def _swiglu(x, w_gu, w_down, dt):
    h = jnp.einsum("...e,en->...n", x, w_gu.astype(dt))
    half = h.shape[-1] // 2
    return jnp.einsum("...m,me->...e",
                      jax.nn.silu(h[..., :half]) * h[..., half:],
                      w_down.astype(dt))


def route(m, router, bias, cfg: HybridLatentMoEConfig):
    """m [n,E] -> (experts [n,K] int32 among all ``n_experts_routed``,
    weights [n,K] f32, kept [n,G] bool: the groups that stayed). Scores are
    float32 at the highest matmul precision; the bias enters both choices
    (of groups, then of experts) and never the weight. Equal scores go to
    the lower index (``lax.top_k``'s order), groups and experts alike."""
    G = cfg.n_group
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.matmul(
            m.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        biased = (scores + bias.astype(jnp.float32)).reshape(
            scores.shape[0], G, -1)
        best2 = jnp.sum(jax.lax.top_k(biased, 2)[0], axis=-1)       # [n,G]
        _, groups = jax.lax.top_k(best2, cfg.topk_group)
        kept = jnp.any(groups[:, :, None] == jnp.arange(G)[None, None, :],
                       axis=1)                                       # [n,G]
        _, chosen = jax.lax.top_k(
            jnp.where(kept[:, :, None], biased, -jnp.inf).reshape(
                scores.shape), cfg.top_k)
        w = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), w * cfg.routed_scale, kept


def _held_bytes(cfg: HybridLatentMoEConfig) -> int:
    """What the KDA mixer holds for one segment at its peak (``q | k | v``
    before and after the convolution, the decays and their running sums,
    ``o`` and the stream): the room an expert pass may take beside it."""
    it = jnp.dtype(cfg.compute_dtype).itemsize
    T = min(_SEGMENT, cfg.max_seq_len)
    return T * (cfg.conv_channels * (it + 4)
                + 4 * cfg.kda_heads * cfg.kda_key_dim * 4
                + cfg.embed_dim * 4)


def _feed_forward(x, valid, stack, i, kind, cfg: HybridLatentMoEConfig):
    """x [B,T,E] (the float32 stream after the mixer) -> (x + ffn,
    counters). The router reads the norm in float32; the products read it
    in the compute dtype."""
    dt = cfg.compute_dtype

    def at(name):
        return layer_at(stack[name], i)

    m32 = rms_norm(x, at("mlp_norm"), cfg.rms_eps)          # float32
    m = m32.astype(dt)
    if kind == KDA_DENSE:
        return x + _swiglu(m, at("w_gu"), at("w_down"), dt).astype(
            x.dtype), {}
    B, T, E = m.shape
    chosen, weights, kept = route(m32.reshape(B * T, E), at("router"),
                                  at("router_bias"), cfg)
    first, count = cfg.experts_held
    y, counters = experts.experts(
        m.reshape(B * T, E), valid.reshape(-1), chosen, weights, stack, i,
        cfg, jax.nn.silu, _held_bytes(cfg), held_first=first)
    if counters:
        size = cfg.n_experts_routed // cfg.n_group
        held = kept[:, first // size:(first + count) // size]
        counters = {
            **counters, "moe_assignments_step": counters["moe_assignments"],
            "moe_groups_held_hits": jnp.sum(
                jnp.any(held, axis=1) & valid.reshape(-1), dtype=jnp.int32)}
    with jax.named_scope("moe_shared"):
        shared = _swiglu(m, at("ws_gu"), at("ws_down"), dt)
    return (x + y.reshape(B, T, E).astype(x.dtype)
            + shared.astype(x.dtype)), counters


# ------------------------------------------------------------- the stack
def layer_kinds(cfg: HybridLatentMoEConfig) -> Tuple[str, ...]:
    return cfg.layer_types


def _put(stack, row, i):
    return jax.lax.dynamic_update_index_in_dim(
        stack, row.astype(stack.dtype), i, 0)


def _scan_layers(params, cfg: HybridLatentMoEConfig, carry, body):
    """Run ``body(carry, stack, i, kind) -> carry`` over the layers in order
    (``scan_runs``), ``stack`` the kind's stacked leaves and ``i`` the
    layer's index among its kind."""
    return scan_runs(
        cfg.layer_types, carry, lambda carry, kind, at, j: body(
            carry, params[kind], at + j, kind))


def _kda_index(i, kind, cfg: HybridLatentMoEConfig):
    """A KDA layer's index in the row-state leaves, which are stacked over
    both KDA kinds in layer order: the dense layers lead."""
    return i if kind == KDA_DENSE else i + cfg.layer_types.count(KDA_DENSE)


def _layer(stack, i, names):
    return {k: layer_at(stack[k], i) for k in names}


_KDA_LEAVES = ("attn_norm", "wqkv", "conv_w", "wf", "a_log", "dt_bias",
               "wb", "wg", "o_norm", "wo")
_MLA_LEAVES = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wgate", "wo")


def _normed(x, layer, cfg):
    return rms_norm(x, layer["attn_norm"], cfg.rms_eps).astype(
        cfg.compute_dtype)


def _kda_block(x, rows, stack, i, kind, counts, plan,
               cfg: HybridLatentMoEConfig):
    """A KDA mixer on the stream; ``rows`` = (state, conv) stacks."""
    state, conv = rows
    layer = _layer(stack, i, _KDA_LEAVES)
    li = _kda_index(i, kind, cfg)
    out, state, tail = _kda_mixer(_normed(x, layer, cfg), layer, state, li,
                                  layer_at(conv, li), counts, plan, cfg)
    return x + out.astype(x.dtype), (state, _put(conv, tail, li))


def init_cache(cfg: HybridLatentMoEConfig, batch: int, max_len: int,
               dtype=None, quantized: bool = False) -> Dict[str, jax.Array]:
    """``ckr`` [L_mla,B,M,W] (the compute dtype), ``state``
    [L_kda,B,H,dk,dv] float32 and ``conv`` [L_kda,B,K-1,C]: zeros, a
    sequence's start."""
    if quantized:
        raise refusal(_LABEL, _REFUSED, "kv_dtype")
    dt = jnp.dtype(dtype) if dtype is not None else cfg.compute_dtype
    Lk = cfg.n_kda_layers
    return {"ckr": jnp.zeros((cfg.n_mla_layers, batch, max_len,
                              ckr_width(cfg)), dt),
            "state": jnp.zeros((Lk, batch, cfg.kda_heads, cfg.kda_key_dim,
                                cfg.kda_value_dim), jnp.float32),
            "conv": jnp.zeros((Lk, batch, cfg.conv_width - 1,
                               cfg.conv_channels), dt)}


def merge_chunk_into_grid(cache, chunk, start, count):
    """The chunk's latent columns land at each row's depth
    (``ops/grid_write.py``); the row-state leaves of the chunk ARE the new
    ones (the forward held them for every row with nothing to land)."""
    ckr = grid_write.write_columns({"ckr": cache["ckr"]},
                                   {"ckr": chunk["ckr"]}, start, count)
    return {**ckr, **{n: chunk[n] for n in ROW_LEAVES}}


def forward(params: Params, tokens: jax.Array, cfg: HybridLatentMoEConfig):
    """Uncached forward of whole sequences: tokens [B,T] -> logits [B,T,V]
    float32 (tests; the serving paths are ``forward_cached``)."""
    B, T = tokens.shape
    own = init_cache(cfg, B, T)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool))[None],
                            (B, T, T))
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    logits, _, _ = forward_cached(params, tokens, positions, own, 0, mask,
                                  cfg)
    return logits


def forward_cached(params: Params, tokens, positions, cache, write_at, mask,
                   cfg: HybridLatentMoEConfig, rules=None,
                   unembed_positions=None, chunk=None, chunk_col=None,
                   chunk_mask=None, lora=None, grid_depth=None,
                   causal_lens=None):
    """``llama.forward_cached``'s contract over a latent a position and a
    state a row -> (logits [B,T,V] float32, new cache or chunk, counters).

    A token is REAL where it attends to itself (``mask[b,t,t]``; in chunk
    mode ``chunk_mask[b,t,chunk_col + t]``): real tokens are a prefix of a
    row's ``T``, and only they move the row's state and convolution tail or
    are given to an expert.

    Without ``chunk`` (a bucketed prefill into a private cache): the MLA
    layers attend through the EXPAND path and write ``(c, k_r)`` at ``[0,
    T)``, the KDA layers start from what the private cache holds (zeros) and
    end at each row's last real token; ``write_at`` must be the literal 0
    and the cache as long as the call (prefix reuse is not carried);
    ``causal_lens`` is accepted and changes nothing. With ``chunk`` (decode
    steps, prefill chunks): the grid is read-only, this call's ``(c, k_r)``
    land at column ``chunk_col`` of the chunk, attention is the ABSORBED
    path over grid and chunk (``grid_depth`` [B] lets one query position a
    row take the ragged kernel), and the chunk's ``state`` and ``conv`` are
    read, advanced for the real tokens and returned.

    ``counters``: in chunk mode the expert layers' counts summed over
    layers; ``{}`` for a prefill (the generator counts a prefill's on the
    host)."""
    if lora is not None:
        raise refusal(_LABEL, _REFUSED, "adapters")
    B, T = tokens.shape
    sin, cos = rope_angles(positions, cfg.qk_rope_dim, cfg.rope_theta)
    x = embed(params, tokens)

    if chunk is None:
        M = cache["ckr"].shape[2]
        if not (isinstance(write_at, int) and write_at == 0 and M == T):
            raise refusal(_LABEL, _REFUSED, "prefix")
        real = jnp.diagonal(mask, axis1=1, axis2=2)                 # [B,T]
        counts = jnp.sum(real, axis=1, dtype=jnp.int32)
        plan = _step_plan(T, counts, cfg)

        def body(carry, stack, i, kind):
            x, grid, rows = carry
            if kind == MLA_MOE:
                layer = _layer(stack, i, _MLA_LEAVES)
                qn, qr, c, kr, gate = _mla_inputs(_normed(x, layer, cfg),
                                                  layer, sin, cos, cfg)
                grid = jax.lax.dynamic_update_slice(
                    grid, _pack(c, kr, cfg, grid.dtype)[None], (i, 0, 0, 0))
                x = _mla_out(x, _mla_expand(qn, qr, c, kr, layer, mask, cfg),
                             gate, layer, cfg)
            else:
                x, rows = _kda_block(x, rows, stack, i, kind, counts, plan,
                                     cfg)
            x, _ = _feed_forward(x, real, stack, i, kind, cfg)
            return x, grid, rows

        x, grid, rows = _scan_layers(
            params, cfg, (x, cache["ckr"], (cache["state"], cache["conv"])),
            body)
        return (unembed(x, params, cfg, unembed_positions),
                {"ckr": grid, "state": rows[0], "conv": rows[1]}, {})

    M = cache["ckr"].shape[2]
    items = None
    if grid_depth is not None and latent_attention.decode_engages(T, M):
        items = latent_attention.plan(grid_depth, M)
    own = jax.lax.dynamic_slice_in_dim(chunk_mask, chunk_col, T, axis=2)
    real = jnp.diagonal(own, axis1=1, axis2=2)                      # [B,T]
    counts = jnp.sum(real, axis=1, dtype=jnp.int32)
    plan = _step_plan(T, counts, cfg)
    totals = {name: jnp.zeros((), jnp.int32) for name in COUNTERS}

    def body(carry, stack, i, kind):
        x, cols, rows, totals = carry
        if kind == MLA_MOE:
            layer = _layer(stack, i, _MLA_LEAVES)
            qn, qr, c, kr, gate = _mla_inputs(_normed(x, layer, cfg), layer,
                                              sin, cos, cfg)
            cols = jax.lax.dynamic_update_slice(
                cols, _pack(c, kr, cfg, cols.dtype)[None],
                (i, 0, chunk_col, 0))
            attn = _mla_absorbed(
                qn, qr, layer, i, cache["ckr"], layer_at(cols, i), mask,
                chunk_mask, items, cfg)
            x = _mla_out(x, attn, gate, layer, cfg)
        else:
            x, rows = _kda_block(x, rows, stack, i, kind, counts, plan, cfg)
        x, counters = _feed_forward(x, real, stack, i, kind, cfg)
        totals = {name: totals[name] + counters.get(name, 0)
                  for name in totals}
        return x, cols, rows, totals

    x, cols, rows, totals = _scan_layers(
        params, cfg, (x, chunk["ckr"], (chunk["state"], chunk["conv"]),
                      totals), body)
    return (unembed(x, params, cfg, unembed_positions),
            {"ckr": cols, "state": rows[0], "conv": rows[1]}, totals)


class HybridLatentMoEDecoder(Decoder):
    """``models/decoder.py``'s interface over this module."""

    counters = COUNTERS
    label, refused = _LABEL, _REFUSED
    layer_kinds = staticmethod(layer_kinds)
    init_cache = staticmethod(init_cache)
    merge_chunk_into_grid = staticmethod(merge_chunk_into_grid)
    forward_cached = staticmethod(forward_cached)

    @staticmethod
    def cache_leaves(cfg: HybridLatentMoEConfig, quantized: bool = False):
        if quantized:
            raise refusal(_LABEL, _REFUSED, "kv_dtype")
        rows = (CacheLeaf("state", (cfg.kda_heads, cfg.kda_key_dim,
                                    cfg.kda_value_dim), jnp.float32, False),
                CacheLeaf("conv", (cfg.conv_width - 1, cfg.conv_channels),
                          cfg.compute_dtype, False))
        return {KDA_DENSE: rows, KDA_MOE: rows,
                MLA_MOE: (CacheLeaf("ckr", (ckr_width(cfg),),
                                    cfg.compute_dtype),)}

    @staticmethod
    def init_cache_like(cfg, cache, batch, max_len):
        return init_cache(cfg, batch, max_len, dtype=cache["ckr"].dtype)

    @staticmethod
    def init_chunk(cfg, cache, batch, cols):
        """The latent columns a chunk writes, zeros; the row-state leaves as
        the grid holds them (a chunk is made for the grid's own rows)."""
        L, _, _, W = cache["ckr"].shape
        return {"ckr": jnp.zeros((L, batch, cols, W), cache["ckr"].dtype),
                **{n: cache[n] for n in ROW_LEAVES}}

    @staticmethod
    def ragged_block(cfg, max_len, cache, spec: bool) -> Optional[int]:
        if not latent_attention.decode_engages(1, max_len):
            return None
        return latent_attention.block_for(max_len)

    @staticmethod
    def prefill_flash_engages(cfg, p_pad: int) -> bool:
        """Nothing here is chosen by the caller's ``causal_lens``: the MLA
        layers' expand path takes its kernel by its own rule
        (``latent_attention.prefill_engages``)."""
        return False

    @staticmethod
    def prefill_counters(cfg: HybridLatentMoEConfig, prompt_tokens: int):
        return {"moe_assignments": experts.moe_assignments(
            cfg, prompt_tokens, cfg.n_moe_layers)}

    @staticmethod
    def expert_admission(cfg: HybridLatentMoEConfig, lens, p_pad: int):
        return experts.expert_admission(cfg, lens, p_pad, _held_bytes(cfg),
                                        cfg.n_moe_layers)

    @staticmethod
    def state_rows_touched(cfg, rows: int, live: int) -> int:
        """Where the step kernel engages (``kda.step_engages``: one TPU
        device) a decode step reads and writes the state of the rows that
        decode, once; where the XLA step runs, of every row of the grid."""
        return live if _step_kernel_engages(cfg) else rows

    @staticmethod
    def scan_positions(cfg, rows: int, length: int) -> int:
        """Positions the KDA layers' scan walks for ``rows`` rows of
        ``length`` (padded) tokens, a layer."""
        return rows * kda.scan_positions(length)
