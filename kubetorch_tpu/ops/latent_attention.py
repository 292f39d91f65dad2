"""Pallas attention over a latent (compressed) key/value cache, for TPU.

Two kernels, one a path of ``models/latent_moe.py``:

``prefill_attention`` — blocked causal attention of T tokens over
themselves with key width ``dn + dr`` beside value width ``dv`` (scores of
32 heads x 8192^2 in float32 would be 8.6 GB: the plain einsum cannot be the
path). The rope key is one a position, shared by the heads. Queries and keys
go in as ``[B, T, H * 256]``: ``[nope | rope | 0]`` a head, padded to two
lane tiles so that one MXU contraction scores a block and every block is
lane-aligned in the layout the projections already produce (no
``[B,H,T,D]`` transposes); values and the output are ``[B, T, H * dv]``.
Online softmax over key blocks; a key block above the diagonal is neither
fetched (its index clamps to the last live one) nor computed.

``ragged_decode_attention`` — the ABSORBED path of a decode step: one query
position a row, each row's latent stream read only to its own depth. It
walks ``ops/decode_attention.py``'s work list (``plan``, ``block_for``:
shared, not copied) with the same hand-rolled double-buffered DMAs, but an
item here is one ``[block, W]`` block of the cache's one leaf (``[c | k_r |
0]`` a position, W a multiple of the lane tile) feeding ALL heads at once:
the heads are the matrix's rows (``[H, W]`` against ``[block, W]``), scores
and the ``[H, r]`` context are two MXU products a block, and nothing a head
is ever read. It
returns the un-normalised context with its running max and sum, as the dense
kernel does, so the caller joins the decode chunk's few columns by the
log-sum-exp rule. The dense kernel's body is untouched by this file.

Use ``interpret=True`` for tests on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubetorch_tpu.ops.decode_attention import block_for, plan  # noqa: F401

_NEG_INF = -1e30
_LANES = 128
_QK_PAD = 256        # nope | rope | zeros, a head: two lane tiles
_PREFILL_BLOCK = 512

# Test hook, as ``decode_attention._FORCE_INTERPRET``: take the decode
# kernel (in interpret mode) wherever ``decode_engages`` is asked.
_FORCE_INTERPRET = False


def _one_tpu_device() -> bool:
    mesh = jax.sharding.get_abstract_mesh()
    return jax.default_backend() == "tpu" and (mesh.empty or mesh.size == 1)


def prefill_engages(t: int) -> bool:
    """The flash kernel takes a prefill whose length its blocks divide, on
    one TPU device; the masked einsum takes the rest (short buckets, CPU)."""
    return t % _PREFILL_BLOCK == 0 and _one_tpu_device()


def decode_engages(t: int, max_len: int) -> bool:
    """As ``decode_attention.engages``: one query position, a grid a key
    block divides, one TPU device."""
    if t != 1 or block_for(max_len) is None:
        return False
    return _FORCE_INTERPRET or _one_tpu_device()


# ------------------------------------------------------------- prefill
def _prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                    block: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki <= qi)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [block, block]
        q_pos = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ki * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[...]                                 # [block, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + \
            jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == qi)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...][:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _prefill_attention(q_nope, q_rope, k_nope, k_rope, v, *, scale: float,
                       interpret: bool):
    B, T, H, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], v.shape[-1]
    if dn + dr > _QK_PAD or dv % _LANES:
        raise ValueError(f"head sizes {dn}+{dr} | {dv} outside the kernel's "
                         f"padding ({_QK_PAD}) and lane tile ({_LANES})")
    block = _PREFILL_BLOCK
    zeros = jnp.zeros((B, T, H, _QK_PAD - dn - dr), q_nope.dtype)
    q = jnp.concatenate([q_nope * scale, q_rope * scale, zeros],
                        axis=-1).astype(q_nope.dtype).reshape(
                            B, T, H * _QK_PAD)
    k = jnp.concatenate([
        k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, dr)).astype(
            k_nope.dtype), zeros], axis=-1).reshape(B, T, H * _QK_PAD)
    v = v.reshape(B, T, H * dv)
    n = T // block
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, block=block),
        grid=(B, H, n, n),
        in_specs=[
            pl.BlockSpec((1, block, _QK_PAD),
                         lambda b, h, qi, ki: (b, qi, h)),
            # a key block above the diagonal re-names the diagonal one:
            # nothing is fetched for it
            pl.BlockSpec((1, block, _QK_PAD),
                         lambda b, h, qi, ki: (b, jnp.minimum(ki, qi), h)),
            pl.BlockSpec((1, block, dv),
                         lambda b, h, qi, ki: (b, jnp.minimum(ki, qi), h)),
        ],
        out_specs=pl.BlockSpec((1, block, dv),
                               lambda b, h, qi, ki: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct((B, T, H * dv), q_nope.dtype),
        scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="latent_prefill_attention",
        interpret=interpret,
    )(q, k, v)
    return out.reshape(B, T, H, dv)


def prefill_attention(q_nope, q_rope, k_nope, k_rope, v, scale: float,
                      interpret=None):
    """Causal attention of T tokens over themselves. ``q_nope`` / ``k_nope``
    [B,T,H,dn], ``q_rope`` [B,T,H,dr], ``k_rope`` [B,T,dr] (one a position),
    ``v`` [B,T,H,dv]; scores ``(q_nope.k_nope + q_rope.k_rope) * scale``.
    Returns [B,T,H,dv]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _prefill_attention(q_nope, q_rope, k_nope, k_rope, v,
                              scale=float(scale), interpret=interpret)


# -------------------------------------------------------------- decode
def _decode_kernel(li_ref, depth_ref, row_ref, blk_ref, n_ref, q_ref, g_hbm,
                   acc_ref, m_ref, l_ref, buf, sem, *, block: int, r: int,
                   sm_scale: float):
    """One call a layer: walk the live (row, block) items, each item's
    ``[block, W]`` cache block double-buffered from HBM by hand."""
    li = li_ref[0]
    n = n_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def copy(i, slot):
        at = pl.multiple_of(blk_ref[i] * block, block)
        return pltpu.make_async_copy(
            g_hbm.at[li, row_ref[i], pl.ds(at, block)], buf.at[slot],
            sem.at[slot])

    @pl.when(n > 0)
    def _first():
        copy(0, 0).start()

    def body(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n)
        def _next():
            copy(i + 1, 1 - slot).start()

        copy(i, slot).wait()
        row = row_ref[i]
        start, depth = blk_ref[i] * block, depth_ref[row]
        g = buf[slot]                                       # [block, W]
        s = jax.lax.dot_general(
            q_ref[row].astype(g.dtype), g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [H, block]
        live = (start + jax.lax.broadcasted_iota(
            jnp.int32, (1, block), 1)) < depth
        live_rows = (start + jax.lax.broadcasted_iota(
            jnp.int32, (block, 1), 0)) < depth
        s = jnp.where(live, s, _NEG_INF)
        m_prev = m_ref[row][:, :1]                          # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[row] = jnp.broadcast_to(
            alpha * l_ref[row][:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape[1:])
        m_ref[row] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        # a float grid can hold anything past a row's depth; 0 x NaN would
        # reach the context
        c = g[:, :r]
        c = jnp.where(live_rows, c, jnp.zeros_like(c))
        acc_ref[row] = alpha * acc_ref[row] + jax.lax.dot_general(
            p.astype(g.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n, body, 0)


@functools.partial(jax.jit, static_argnames=("r", "sm_scale", "interpret"))
def _ragged_decode(q, grid_all, layer, items, *, r: int, sm_scale: float,
                   interpret: bool):
    B, H, W = q.shape
    M = grid_all.shape[2]
    depth, row, blk, n = items
    block = (B * M) // row.shape[0]

    def full(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    stats = jax.ShapeDtypeStruct((B, H, _LANES), jnp.float32)
    acc, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, block=block, r=r,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(1,),
            in_specs=[full(B, H, W), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[full(B, H, r), full(B, H, _LANES),
                       full(B, H, _LANES)],
            scratch_shapes=[pltpu.VMEM((2, block, W), grid_all.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((B, H, r), jnp.float32), stats,
                   stats],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="latent_decode_attention",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), depth, row, blk, n,
      q, grid_all)
    return acc, m[..., 0], l[..., 0]


def ragged_decode_attention(q, grid_all, layer, items, r: int,
                            sm_scale: float, interpret: bool = False):
    """Absorbed attention of one query position a row over the stacked
    latent grid. ``grid_all`` [L,B,M,W] holds ``[c | k_r | 0]`` a position
    (``c`` the first ``r``); ``q`` [B,H,W] is the query in the same
    coordinates, ``[W_kb^T q_nope | q_rope | 0]``, so one contraction over W
    scores a block; ``layer`` a scalar; ``items`` = ``plan(depth, M)``: row
    ``b`` attends positions ``m < depth[b]``. Returns ``(acc [B,H,r], m
    [B,H], l [B,H])`` float32: the un-normalised context ``sum_m exp(s_m -
    m) c_m``, the running max and the sum. A row at depth 0 returns ``(0,
    -1e30, 0)``."""
    return _ragged_decode(q, grid_all, layer, items, r=int(r),
                          sm_scale=float(sm_scale), interpret=interpret)
