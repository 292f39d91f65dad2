"""Attention over a cache of keys and values, grouped-query, in plain XLA:
what every decoder with K and V a position attends through wherever no
kernel engages, and the join of the ragged decode kernel's half with a decode
chunk's few columns.

Five functions, by what the cache is. They ask shapes and dtypes only: heads,
head size and lengths come from the operands, so the dense decoder
(``models/llama.py``) and the decoders whose layers keep K and V
(``models/hybrid_linear.py``, ``models/window_moe.py``,
``models/indexed_moe.py``) call them as they are.

- ``cached_attn`` / ``cached_attn_q``: queries over ONE cache (a float one /
  an int8 one with a scale a head vector) under a mask: a bucketed prefill
  over its private cache, the static generator's step.
- ``cached_attn_merged`` / ``cached_attn_merged_q``: queries over a
  read-only grid AND a small chunk, one softmax spanning both, the grid
  only ever read: every chunk-mode forward (decode steps, prefill chunks,
  speculative verify). They contract against all positions of the grid and
  mask afterwards; they are also the numerics ORACLE of the next.
- ``cached_attn_ragged``: the same for ONE query position a row with the
  grid half in the ragged kernel (``ops/decode_attention.py``), each row
  read only to its depth; the chunk's columns are scored here and the halves
  join by the log-sum-exp rule (tests/test_decode_attention.py holds it to
  the merged pair).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kubetorch_tpu.ops.decode_attention import ragged_decode_attention


def cached_attn_q(q, ck, cv, ks, vs, mask):
    """Quantized-KV attention: ck/cv int8 [B,M,Hkv,D], ks/vs f32
    [B,M,Hkv]. The int8→f32 convert fuses into the einsum operand read
    (the property the int8 weight path relies on); scales apply per key
    row AFTER the contraction (K side) and fold into the probabilities
    BEFORE it (V side) — both exact."""
    B, T, H, D = q.shape
    Hkv = ck.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, D)
    # int8 operands converted to bf16 (not f32) with f32 accumulation:
    # the convert then fuses into the contraction's operand read the same
    # way the int8 weight einsums do — an f32 cast materializes a
    # 4×-the-cache copy per step instead.
    s = jnp.einsum("btkgd,bmkd->bkgtm", qg.astype(jnp.bfloat16),
                   ck.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    s = s * ks.transpose(0, 2, 1)[:, :, None, None, :]      # [B,Hkv,1,1,M]
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = p * vs.transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bkgtm,bmkd->btkgd", p.astype(jnp.bfloat16),
                     cv.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, D).astype(q.dtype)


def cached_attn(q, ck, cv, mask):
    """q: [B,T,H,D]; ck/cv: [B,M,Hkv,D]; mask: [B,T,M] bool → [B,T,H,D].

    Grouped-query einsum form — no materialized [B,M,H,D] repeat of KV.
    T is small (prefill ≤ M, decode 1), so scores [B,Hkv,G,T,M] stay modest
    and XLA fuses the softmax chain.
    """
    B, T, H, D = q.shape
    Hkv = ck.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, D)
    s = jnp.einsum("btkgd,bmkd->bkgtm", qg.astype(jnp.float32),
                   ck.astype(jnp.float32)) * (D ** -0.5)
    s = jnp.where(mask[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgtm,bmkd->btkgd", p, cv.astype(jnp.float32))
    return out.reshape(B, T, H, D).astype(q.dtype)


def cached_attn_merged(q, gk, gv, ek, ev, gmask, emask):
    """Attention over a read-only grid cache PLUS a small chunk cache,
    without materializing their concatenation.

    q: [B,T,H,D]; gk/gv: [B,M,Hkv,D] (grid); ek/ev: [B,K,Hkv,D] (chunk);
    gmask: [B,T,M]; emask: [B,T,K]. Scores over both sources concatenate
    (tiny: [B,Hkv,G,T,M+K] float32), one softmax spans them, and the two
    value contractions sum — so the multi-GB grid is only ever *read*.
    This is what lets rolling decode defer per-sequence cache writes to a
    once-per-chunk merge instead of rewriting cache layers every step
    (the one-hot write was ~2× the whole step at 8B serving scale).
    """
    B, T, H, D = q.shape
    Hkv = gk.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, D).astype(jnp.float32)
    sg = jnp.einsum("btkgd,bmkd->bkgtm", qg,
                    gk.astype(jnp.float32)) * (D ** -0.5)
    se = jnp.einsum("btkgd,bmkd->bkgtm", qg,
                    ek.astype(jnp.float32)) * (D ** -0.5)
    sg = jnp.where(gmask[:, None, None, :, :], sg, -1e30)
    se = jnp.where(emask[:, None, None, :, :], se, -1e30)
    p = jax.nn.softmax(jnp.concatenate([sg, se], axis=-1), axis=-1)
    M = gk.shape[1]
    out = (jnp.einsum("bkgtm,bmkd->btkgd", p[..., :M],
                      gv.astype(jnp.float32))
           + jnp.einsum("bkgtm,bmkd->btkgd", p[..., M:],
                        ev.astype(jnp.float32)))
    return out.reshape(B, T, H, D).astype(q.dtype)


def cached_attn_merged_q(q, gk, gv, gks, gvs, ek, ev, gmask, emask):
    """Merged grid+chunk attention over a QUANTIZED grid.

    gk/gv int8 [B,M,Hkv,D] with per-vector scales gks/gvs [B,M,Hkv];
    ek/ev bf16 chunk [B,K,Hkv,D]. Exactly `cached_attn_merged` with the
    int8 path's scale folding (scores·ks after the QK contraction,
    p·vs before the PV one) applied to the grid half only — one softmax
    spans both sources, so rolling decode can run the serving grid at
    half the cache bytes and residency.

    Which path runs when: this einsum pair (with ``cached_attn_merged``
    for a float grid) contracts against all ``M`` positions of every slot
    and masks afterwards. It serves every chunk-mode forward with more
    than one query position (chunked prefill, speculative verify), every
    backend but the TPU, and a grid sharded over a mesh; it is also the
    numerics ORACLE of ``cached_attn_ragged``, which single-position
    decode on one TPU device takes instead (tests/test_decode_attention.py
    holds the two together)."""
    B, T, H, D = q.shape
    Hkv = gk.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, D)
    qb = qg.astype(jnp.bfloat16)
    sg = jnp.einsum("btkgd,bmkd->bkgtm", qb, gk.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32) * (D ** -0.5)
    sg = sg * gks.transpose(0, 2, 1)[:, :, None, None, :]
    se = jnp.einsum("btkgd,bmkd->bkgtm", qb, ek.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32) * (D ** -0.5)
    sg = jnp.where(gmask[:, None, None, :, :], sg, -1e30)
    se = jnp.where(emask[:, None, None, :, :], se, -1e30)
    p = jax.nn.softmax(jnp.concatenate([sg, se], axis=-1), axis=-1)
    M = gk.shape[1]
    pg = (p[..., :M] * gvs.transpose(0, 2, 1)[:, :, None, None, :]
          ).astype(jnp.bfloat16)
    out = (jnp.einsum("bkgtm,bmkd->btkgd", pg, gv.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgtm,bmkd->btkgd",
                        p[..., M:].astype(jnp.bfloat16),
                        ev.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32))
    return out.reshape(B, T, H, D).astype(q.dtype)


def cached_attn_ragged(q, gk_all, gv_all, gks_all, gvs_all, li, items,
                       ek, ev, emask):
    """Merged grid+chunk attention for ONE query position a row, the grid
    half read only to each row's depth.

    ``gk_all``/``gv_all`` are the STACKED planes [L,B,M,Hkv,D] (never a
    sliced layer: handed to a custom call that is a copy of the layer),
    ``gks_all``/``gvs_all`` their scales or None, ``items`` the kernel's
    work list ``decode_attention.plan(depth, M)`` for ``depth`` [B], the
    grid mask as a length (``m < depth[b]``; 0 for a row that is not
    decoding). The Pallas kernel (``ops/decode_attention.py``) returns
    the grid half un-normalised with its running max and sum; the chunk's
    few columns are scored here in XLA and the halves join by the
    log-sum-exp rule, so one softmax spans both exactly as in
    ``cached_attn_merged_q`` / ``cached_attn_merged``, whose operand
    dtypes this keeps (bf16 operands over an int8 or bf16 grid, f32
    accumulation). A row at depth 0 with its chunk masked too comes out
    finite and meaningless, as it does there."""
    B, _, H, D = q.shape
    Hkv = ek.shape[2]
    G = H // Hkv
    acc_g, m_g, l_g = ragged_decode_attention(
        q[:, 0], gk_all, gv_all, gks_all, gvs_all, li, items,
        interpret=jax.default_backend() != "tpu")
    odt = jnp.float32 if gk_all.dtype == jnp.float32 else jnp.bfloat16
    qg = q.reshape(B, Hkv, G, D).astype(odt)
    se = jnp.einsum("bkgd,bckd->bkgc", qg, ek.astype(odt),
                    preferred_element_type=jnp.float32) * (D ** -0.5)
    se = jnp.where(emask[:, 0, None, None, :], se, -1e30)
    m_g, l_g = m_g.reshape(B, Hkv, G), l_g.reshape(B, Hkv, G)
    m = jnp.maximum(m_g, jnp.max(se, axis=-1))
    pe = jnp.exp(se - m[..., None])
    wg = jnp.exp(m_g - m)
    out = (wg[..., None] * acc_g.reshape(B, Hkv, G, D)
           + jnp.einsum("bkgc,bckd->bkgd", pe.astype(odt), ev.astype(odt),
                        preferred_element_type=jnp.float32))
    out = out / (wg * l_g + jnp.sum(pe, axis=-1))[..., None]
    return out.reshape(B, 1, H, D).astype(q.dtype)
