"""Ring attention: exact attention over sequence-sharded Q/K/V.

Long-context path (SURVEY.md §5.7 — absent from the reference; first-class
here). Each ``sp`` shard holds a sequence chunk of Q/K/V; KV chunks rotate
around the ring via ``jax.lax.ppermute`` while each device folds the incoming
chunk into its local queries' online softmax state. Exact (not approximate)
attention with O(S_local) memory per device and ICI-only communication; XLA
overlaps each ppermute with the next chunk's compute.

Two chunk engines, picked by shape:

- **flash** (tileable shapes: D%128==0, S_local%8==0): each visiting chunk
  runs the Pallas flash kernel; per-chunk (out, lse) results merge by
  online-softmax weights. A chunk is *diagonal* (causal kernel), *past*
  (non-causal kernel), or *future* (skipped outright via ``lax.cond`` — no
  FLOPs). Backward is a second ring rotation reusing the flash backward
  kernels per chunk: dq accumulates locally, dk/dv ride around the ring with
  their chunk.
- **einsum fallback** for non-tileable shapes: XLA-materialized per-chunk
  scores with offset-based masking (differentiable by construction).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from kubetorch_tpu.ops.flash_attention import (
    _STATS,
    _flash_backward,
    auto_block_k,
    flash_attention_with_lse,
    flash_bwd_delta,
    flash_tileable,
)

# flash bodies (pallas interpret mode) trip the VMA checker
_NOCHECK = {"check_vma": False}

_NEG_INF = -1e30


def _chunk_scores(q, k, v, q_off, k_off, scale, causal):
    """One KV chunk vs local Q. q: [B,S,H,D], k/v: [B,T,Hkv,D].
    Returns (o_unnorm [B,S,H,D], m [B,S,H], l [B,S,H]) in float32."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    # H splits as (Hkv, group): head index = kv_head * group + g
    qg = q.reshape(B, S, Hkv, group, D).astype(jnp.float32)
    s = jnp.einsum("bshgd,bthd->bshgt",
                   qg * scale, k.astype(jnp.float32))   # [B,S,Hkv,group,T]
    if causal:
        q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (S, T), 0)
        k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (S, T), 1)
        mask = (q_pos >= k_pos)[None, :, None, None, :]
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)                              # [B,S,Hkv,group]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                              # [B,S,Hkv,group]
    o = jnp.einsum("bshgt,bthd->bshgd", p, v.astype(jnp.float32))
    return (o.reshape(B, S, H, D), m.reshape(B, S, H), l.reshape(B, S, H))


def _ring_body(q, k, v, *, axis_name: str, scale: float, causal: bool,
               mesh_axes: tuple = ()):
    """Runs inside shard_map: q/k/v are local [B, S_local, H(,kv), D]."""
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    s_local = S

    acc = jnp.zeros((B, S, H, D), jnp.float32)
    m = jnp.full((B, S, H), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, S, H), jnp.float32)
    # shard_map VMA typing: scan carries must enter as 'varying' over the
    # same axes as the inputs, since the loop body makes them
    # device-varying (ppermute / axis_index).
    if mesh_axes:
        acc, m, l = jax.lax.pcast((acc, m, l), mesh_axes, to="varying")
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(i, carry):
        acc, m, l, k_cur, v_cur = carry
        src = (idx - i) % sp                      # whose chunk we hold now
        o_c, m_c, l_c = _chunk_scores(
            q, k_cur, v_cur,
            q_off=idx * s_local, k_off=src * s_local,
            scale=scale, causal=causal)
        m_new = jnp.maximum(m, m_c)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_c - m_new)
        acc = acc * alpha[..., None] + o_c * beta[..., None]
        l = l * alpha + l_c * beta
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, m_new, l, k_next, v_next

    acc, m, l, _, _ = jax.lax.fori_loop(
        0, sp, step, (acc, m, l, k, v))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# --------------------------------------------------------------------------
# flash chunk engine
# --------------------------------------------------------------------------

def _flash_chunk(q, k_cur, v_cur, src, idx, scale, interpret, causal):
    """One visiting KV chunk through the flash kernel → (o f32, lse f32).

    o is the chunk-normalized output [B,S,H,D]; lse [B,S,H] makes results
    mergeable. Future chunks (src > idx) are skipped entirely.
    """
    B, S, H, D = q.shape

    def masked(_k, _v):
        return (jnp.zeros((B, S, H, D), jnp.float32),
                jnp.full((B, S, H), _NEG_INF, jnp.float32))

    def run(causal_chunk):
        def f(k_c, v_c):
            out, lse = flash_attention_with_lse(
                q, k_c, v_c, causal=causal_chunk, scale=scale,
                interpret=interpret)
            # lse [B,H,S] -> [B,S,H] to match the merge layout
            return out.astype(jnp.float32), lse.transpose(0, 2, 1)
        return f

    if not causal:
        return run(False)(k_cur, v_cur)
    return jax.lax.cond(
        src > idx, masked,
        lambda k_c, v_c: jax.lax.cond(
            src == idx, run(True), run(False), k_c, v_c),
        k_cur, v_cur)


def _merge(o, lse, o_c, lse_c):
    """Online-softmax merge of two chunk-normalized results."""
    m = jnp.maximum(lse, lse_c)
    w = jnp.exp(lse - m)
    w_c = jnp.exp(lse_c - m)
    denom = jnp.maximum(w + w_c, 1e-30)
    o = (o * w[..., None] + o_c * w_c[..., None]) / denom[..., None]
    return o, m + jnp.log(denom)


def _ring_fwd_flash(q, k, v, *, axis_name, scale, interpret, causal):
    """Forward ring pass with flash chunks. Returns (out, lse)."""
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(i, carry):
        o, lse, k_cur, v_cur = carry
        src = (idx - i) % sp
        o_c, lse_c = _flash_chunk(q, k_cur, v_cur, src, idx, scale,
                                  interpret, causal)
        o, lse = _merge(o, lse, o_c, lse_c)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, lse, k_next, v_next

    o0 = jnp.zeros((B, S, H, D), jnp.float32)
    lse0 = jnp.full((B, S, H), _NEG_INF, jnp.float32)
    o, lse, _, _ = jax.lax.fori_loop(0, sp, step, (o0, lse0, k, v))
    return o.astype(q.dtype), lse


def _ring_bwd_flash(q, k, v, out, lse, g, *, axis_name, scale, interpret,
                    causal):
    """Backward ring pass: per-chunk flash backward kernels.

    dq accumulates on the query's home device; each KV chunk's dk/dv
    accumulate while the chunk travels and arrive home after the full
    rotation (sp steps of shift-by-1 = identity).
    """
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    # [B,S,H,*] -> kernel layout [B,H,S,*]; lse to narrow-lane stats
    qT, outT, gT = (x.transpose(0, 2, 1, 3) for x in (q, out, g))
    lseT = jnp.broadcast_to(lse.transpose(0, 2, 1)[..., None],
                            lse.shape[:1] + (lse.shape[2], lse.shape[1])
                            + (_STATS,))
    # loop-invariant: same delta for every visiting chunk
    deltaT = flash_bwd_delta(gT, outT)

    def chunk_bwd(k_cur, v_cur, src):
        def masked(_k, _v):
            return (jnp.zeros_like(qT), jnp.zeros_like(_k),
                    jnp.zeros_like(_v))

        def run(causal_chunk):
            def f(k_c, v_c):
                return _flash_backward(
                    qT, k_c, v_c, outT, lseT, gT, scale=scale,
                    causal=causal_chunk,
                    block_q=min(512, qT.shape[2]),
                    block_k=auto_block_k(k_c.shape[2]),
                    interpret=interpret, delta=deltaT)
            return f

        if not causal:
            return run(False)(k_cur, v_cur)
        return jax.lax.cond(
            src > idx, masked,
            lambda k_c, v_c: jax.lax.cond(
                src == idx, run(True), run(False), k_c, v_c),
            k_cur, v_cur)

    def step(i, carry):
        dq, dk_cur, dv_cur, k_cur, v_cur = carry
        src = (idx - i) % sp
        dq_c, dk_c, dv_c = chunk_bwd(k_cur, v_cur, src)
        dq = dq + dq_c.astype(jnp.float32)
        dk_cur = dk_cur + dk_c.astype(jnp.float32)
        dv_cur = dv_cur + dv_c.astype(jnp.float32)
        rotate = lambda x: jax.lax.ppermute(x, axis_name, perm)
        return dq, rotate(dk_cur), rotate(dv_cur), rotate(k_cur), rotate(v_cur)

    kT, vT = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    dq0 = jnp.zeros(qT.shape, jnp.float32)
    dkv0 = jnp.zeros(kT.shape, jnp.float32)
    dq, dk, dv, _, _ = jax.lax.fori_loop(
        0, sp, step, (dq0, dkv0, dkv0, kT, vT))
    back = lambda x, ref: x.astype(ref.dtype).transpose(0, 2, 1, 3)
    return back(dq, q), back(dk, k), back(dv, v)


def _make_flash_ring(axis_name: str, scale: float, interpret: bool,
                     causal: bool):
    """Differentiable shard-local flash ring (custom VJP)."""
    kw = dict(axis_name=axis_name, scale=scale, interpret=interpret,
              causal=causal)

    @jax.custom_vjp
    def ring(q, k, v):
        out, _ = _ring_fwd_flash(q, k, v, **kw)
        return out

    def fwd(q, k, v):
        out, lse = _ring_fwd_flash(q, k, v, **kw)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        q, k, v, out, lse = res
        return _ring_bwd_flash(q, k, v, out, lse, g, **kw)

    ring.defvjp(fwd, bwd)
    return ring


def _ring_body_flash(q, k, v, *, axis_name, scale, interpret, causal):
    return _make_flash_ring(axis_name, scale, interpret, causal)(q, k, v)


def ring_attention(
    q: jax.Array,                  # [B, S, Hq, D] sharded on sp along S
    k: jax.Array,                  # [B, S, Hkv, D]
    v: jax.Array,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    batch_axes=("dcn", "dp", "fsdp"),  # match LOGICAL_AXIS_RULES "batch"
    head_axis: Optional[str] = "tp",
) -> jax.Array:
    """Sequence-parallel exact attention over ``mesh[axis_name]``."""
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5

    def fit(size: int, axes) -> Optional[tuple]:
        """Keep only mesh axes whose product divides ``size``."""
        used, prod = [], 1
        for ax in (axes if isinstance(axes, (tuple, list)) else (axes,)):
            if ax and size % (prod * mesh.shape[ax]) == 0:
                used.append(ax)
                prod *= mesh.shape[ax]
        return tuple(used) or None

    b_axes = fit(q.shape[0], batch_axes)
    h_axis = fit(k.shape[2], head_axis)
    h_axis = h_axis[0] if h_axis else None
    spec_q = P(b_axes, axis_name, h_axis, None)
    spec_kv = P(b_axes, axis_name, h_axis, None)
    spec_axes = set()
    for part in (b_axes or ()), (axis_name,), ((h_axis,) if h_axis else ()):
        spec_axes.update(a for a in part if a)

    # Per-shard shapes decide the chunk engine (Pallas flash vs einsum).
    sp_size = mesh.shape[axis_name]
    b_div = 1
    for ax in (b_axes or ()):
        b_div *= mesh.shape[ax]
    h_div = mesh.shape[h_axis] if h_axis else 1
    local_q = (q.shape[0] // b_div, q.shape[1] // sp_size,
               q.shape[2] // h_div, D)
    local_kv = (k.shape[0] // b_div, k.shape[1] // sp_size,
                k.shape[2] // h_div, D)
    if flash_tileable(local_q, local_kv):
        # check_vma=False: pallas calls (esp. interpret-mode) inside
        # shard_map trip JAX's varying-manual-axes checker (hlo interpreter
        # dynamic_slice VMA mismatch); disabling the check is the
        # upstream-documented workaround, and without the checker no
        # pcast/vma bookkeeping is needed in the body.
        body = functools.partial(
            _ring_body_flash, axis_name=axis_name, scale=scale,
            causal=causal, interpret=jax.default_backend() == "cpu")
        return shard_map(
            body, mesh=mesh,
            in_specs=(spec_q, spec_kv, spec_kv),
            out_specs=spec_q, **_NOCHECK,
        )(q, k, v)
    body = functools.partial(
        _ring_body, axis_name=axis_name, scale=scale, causal=causal,
        mesh_axes=tuple(sorted(spec_axes)))
    return shard_map(
        body, mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv),
        out_specs=spec_q,
    )(q, k, v)
