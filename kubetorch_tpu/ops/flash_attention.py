"""Pallas flash attention for TPU: online-softmax tiling, O(S) memory.

Forward kernel keeps running (max, sum, acc) in VMEM scratch across the KV
grid dimension (innermost), so the S×S score matrix never materializes in
HBM — the standard flash pattern mapped to TPU tiling constraints
((8,128)/f32 tiles, MXU matmuls with float32 accumulation, grid ordered so
KV is the contraction dim). The forward also emits per-row logsumexp stats
(narrow [B,H,S,8] layout — see ``_STATS``) as the residual for the backward;
the forward-only primal skips them entirely.

Backward is two flash kernels (FlashAttention-2 decomposition):
``dq`` iterates KV blocks per Q block; ``dk/dv`` iterates (q-head × Q-block)
per KV block, folding the GQA group into the innermost accumulation axis so
grouped query heads sum into their KV head without a second pass. Neither
materializes scores in HBM.

GQA costs no data movement: the K/V BlockSpec index maps fold the
query-head → kv-head mapping (``h // group``) so kv blocks are simply fetched
per query head.

Causal: blocks strictly above the diagonal are skipped in all three kernels
(~2x fewer effective blocks).

Use ``interpret=True`` (automatic on CPU) for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubetorch_tpu.ops.attention import dot_product_attention

_NEG_INF = -1e30
_LANES = 128   # in-kernel row stats live replicated across the TPU lane tile
_STATS = 8     # HBM stats (lse/delta) keep a narrow 8-lane trailing dim:
               # Mosaic requires the last block dim to be 128-divisible OR
               # equal to the full array dim — 8 satisfies the latter at
               # 16x less HBM traffic than lane-replicated stats


def band_first_block(qi, block_q: int, block_k: int, window: int):
    """First key block a query block's band touches: query ``i`` sees key
    ``j`` iff ``i - window < j <= i``, so block ``qi``'s first query sees
    from ``qi * block_q - window + 1`` (at 0 where that is negative)."""
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k


def band_blocks(n_q: int, block_q: int, block_k: int, window: int):
    """Key blocks each query block's band touches, on the host: from its
    first query's oldest key's block to the diagonal's."""
    return [(qi * block_q + block_q - 1) // block_k
            - max(qi * block_q - window + 1, 0) // block_k + 1
            for qi in range(n_q)]


def band_width(n_q: int, block_q: int, block_k: int, window: int) -> int:
    """Most key blocks any query block's band touches: the key axis of a
    banded call's grid."""
    return max(band_blocks(n_q, block_q, block_k, window))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scratch, l_scratch,
                acc_scratch, *, scale: float, causal: bool,
                block_q: int, block_k: int, window: Optional[int] = None):
    """Forward kernel. ``lse_ref`` is None in the forward-only (primal)
    variant — no residual stats are written then. ``window`` (with
    ``causal``): the key axis of the grid is the BAND's, key block
    ``band_first_block(qi) + ki``, so a block wholly outside the band is
    neither fetched nor computed."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    # the key block this step holds (the grid's own without a window)
    kb = ki if window is None else (
        band_first_block(qi, block_q, block_k, window) + ki)

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    # Causal: a KV block strictly above the diagonal contributes nothing —
    # skip its matmuls entirely (~2x fewer effective blocks).
    block_live = (not causal) or (kb * block_k <= qi * block_q + block_q - 1)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [block_q, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [block_k, D]
        v = v_ref[0, 0].astype(jnp.float32)          # [block_k, D]

        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [block_q, block_k]

        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            seen = q_pos >= k_pos
            if window is not None:
                seen = seen & (q_pos - k_pos < window)
            s = jnp.where(seen, s, _NEG_INF)

        m_prev = m_scratch[:]                         # [block_q, 128]
        row_max = jnp.max(s, axis=1, keepdims=True)   # [block_q, 1]
        m_new = jnp.maximum(m_prev, row_max)          # broadcast over lanes
        p = jnp.exp(s - m_new[:, :1])                 # [block_q, block_k]
        correction = jnp.exp(m_prev - m_new)          # [block_q, 128]
        l_new = l_scratch[:] * correction + jnp.sum(
            p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [block_q, D]
        acc_scratch[:] = (acc_scratch[:]
                          * correction[:, :acc_scratch.shape[1]] + pv)
        m_scratch[:] = m_new
        l_scratch[:] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = l_scratch[:][:, :1]
        o_ref[0, 0] = (acc_scratch[:] / jnp.maximum(denom, 1e-30)).astype(
            o_ref.dtype)
        if lse_ref is not None:
            # lse = m + log(l) per row, stored narrow ([bq, 8] slice of
            # the lane-replicated scratch) — see _STATS.
            lse = m_scratch[:] + jnp.log(jnp.maximum(l_scratch[:], 1e-30))
            lse_ref[0, 0] = lse[:, :_STATS]


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    scale: float, causal: bool, block_q: int, block_k: int,
    interpret: bool, with_lse: bool = True, name: Optional[str] = None,
    window: Optional[int] = None,
):
    """[B,H,S,D] layout. Returns (out, lse[B,H,S,_STATS] f32) — lse is None
    when ``with_lse=False`` (forward-only: skips the residual writes).
    ``name`` names the custom call in a device trace (training's stays
    unnamed, so its executables are the ones they were). ``window`` (causal
    self-attention only, ``S == T``): query ``i`` sees key ``j`` iff ``i -
    window < j <= i``, and the grid's key axis shrinks to the band
    (``band_width``), each query block starting at its own first block."""
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    group = Hq // Hkv
    nq = S // block_q
    nk = T // block_k
    if window is None:
        def kv_at(b, h, qi, ki):
            return (b, h // group, ki, 0)
        extra = {}
    else:
        assert causal and S == T
        nk = band_width(nq, block_q, block_k, window)

        def kv_at(b, h, qi, ki):
            # past the diagonal: the diagonal's block again (no new fetch)
            return (b, h // group, jnp.minimum(
                band_first_block(qi, block_q, block_k, window) + ki,
                (qi * block_q + block_q - 1) // block_k), 0)
        extra = {"window": window}

    out_shape = [jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, block_q, D),
                              lambda b, h, qi, ki: (b, h, qi, 0))]
    if with_lse:
        out_shape.append(
            jax.ShapeDtypeStruct((B, Hq, S, _STATS), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, block_q, _STATS),
                                      lambda b, h, qi, ki: (b, h, qi, 0)))
        kernel = _fwd_kernel
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, *scratch, **kw):
            _fwd_kernel(q_ref, k_ref, v_ref, o_ref, None, *scratch, **kw)

    grid = (B, Hq, nq, nk)
    res = pl.pallas_call(
        functools.partial(
            kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, **extra),
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_at),
            pl.BlockSpec((1, 1, block_k, D), kv_at),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            # row stats live replicated across the 128-lane dim (TPU tile)
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((block_q, D), jnp.float32),        # output accumulator
        ],
        interpret=interpret,
        name=name,
    )(q, k, v)
    return (res[0], res[1]) if with_lse else (res[0], None)


def flash_bwd_delta(g, out):
    """delta_i = rowsum(dO_i · O_i) in the narrow-lane stats layout.

    Loop-invariant wrt the KV chunk — ring attention computes it once and
    reuses it across all ring steps of the backward pass."""
    B, Hq, S, _ = g.shape
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return jnp.broadcast_to(delta[..., None], (B, Hq, S, _STATS))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scratch, *, scale: float, causal: bool,
               block_q: int, block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    block_live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)           # [bk, D]
        do = do_ref[0, 0].astype(jnp.float32)         # [bq, D]
        lse = lse_ref[0, 0][:, :1]                    # [bq, 1]
        delta = delta_ref[0, 0][:, :1]                # [bq, 1]

        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        ds = p * (dp - delta) * scale
        dq_scratch[:] = dq_scratch[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, D]

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scratch[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scratch, dv_scratch, *,
                scale: float, causal: bool, block_q: int, block_k: int,
                group: int):
    ki = pl.program_id(2)
    j = pl.program_id(3)                 # j = qi * group + g (qi-major)
    nj = pl.num_programs(3)
    qi = j // group

    @pl.when(j == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    block_live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)           # [bk, D]
        do = do_ref[0, 0].astype(jnp.float32)         # [bq, D]
        lse = lse_ref[0, 0][:, :1]                    # [bq, 1]
        delta = delta_ref[0, 0][:, :1]                # [bq, 1]

        s = jax.lax.dot_general(
            q * scale, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bk]
        dv_scratch[:] = dv_scratch[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        ds = p * (dp - delta) * scale                 # [bq, bk]
        dk_scratch[:] = dk_scratch[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, D]

    @pl.when(j == nj - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, *, scale, causal, block_q, block_k,
                    interpret, delta=None):
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    group = Hq // Hkv
    nq = S // block_q
    nk = T // block_k

    if delta is None:
        delta = flash_bwd_delta(g, out)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, _STATS),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, _STATS),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    # dk/dv: grid folds the GQA group into the innermost axis (qi-major) so
    # all query heads of a KV head accumulate into one scratch pass.
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, group=group),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, T, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, T, D), v.dtype),
        ],
        grid=(B, Hkv, nk, nq * group),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, ki, j: (b, h * group + j % group,
                                              j // group, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, j: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, j: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, ki, j: (b, h * group + j % group,
                                              j // group, 0)),
            pl.BlockSpec((1, 1, block_q, _STATS),
                         lambda b, h, ki, j: (b, h * group + j % group,
                                              j // group, 0)),
            pl.BlockSpec((1, 1, block_q, _STATS),
                         lambda b, h, ki, j: (b, h * group + j % group,
                                              j // group, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, j: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, j: (b, h, ki, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_forward(q, k, v, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret, with_lse=False)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(q, k, v, out, lse, g, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def auto_block_k(T: int, requested: Optional[int] = None) -> int:
    """KV block size: 1024 when it divides T (measured ~+1.2% train
    throughput over 512 at S=2048 on v5e), else 512 — never silently
    shrink coverage for shapes only 512 divides."""
    if requested is not None:
        return min(requested, T)
    if T >= 1024 and T % 1024 == 0:
        return 1024
    if T >= 512 and T % 512 == 0:
        return 512
    # Small or non-dividing T: cap at 512; flash_tileable rejects shapes
    # this doesn't divide (they take the XLA attention path).
    return min(512, T)


def auto_block_q(S: int, requested: Optional[int] = None) -> int:
    """Query block size: 1024 when it divides S (measured +1.6% train
    throughput over 512 at S=2048 on v5e — bigger MXU tiles amortize the
    online-softmax bookkeeping), else the 512 ladder as for KV."""
    if requested is not None:
        return min(requested, S)
    if S >= 1024 and S % 1024 == 0:
        return 1024
    if S >= 512 and S % 512 == 0:
        return 512
    return min(512, S)


def flash_tileable(q_shape, k_shape, block_q: Optional[int] = None,
                   block_k: Optional[int] = None) -> bool:
    """True when [B,S,H,D] / [B,T,Hkv,D] shapes fit the kernel tiling."""
    B, S, Hq, D = q_shape
    T, Hkv = k_shape[1], k_shape[2]
    bq, bk = auto_block_q(S, block_q), auto_block_k(T, block_k)
    return (S % bq == 0 and T % bk == 0 and D % 128 == 0
            and Hq % Hkv == 0 and bq % 8 == 0 and bk % 8 == 0)


def flash_attention_with_lse(
    q: jax.Array,                 # [B, S, Hq, D] — must be tileable
    k: jax.Array,                 # [B, T, Hkv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,   # None = auto (1024 when it divides S)
    block_k: Optional[int] = None,   # None = auto (1024 when it divides T)
    interpret: Optional[bool] = None,
):
    """Forward-only flash returning (out [B,S,H,D], lse [B,H,S] f32).

    The lse output makes results mergeable across KV chunks (online-softmax
    combine) — ring attention folds per-chunk flash results this way.
    Differentiation goes through the plain :func:`flash_attention` path;
    this variant is for inference/manual-combine callers.
    """
    B, S, Hq, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_q = auto_block_q(S, block_q)
    block_k = auto_block_k(k.shape[1], block_k)
    out, lse = _flash_forward(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


def flash_attention(
    q: jax.Array,                 # [B, S, Hq, D]
    k: jax.Array,                 # [B, T, Hkv, D]
    v: jax.Array,                 # [B, T, Hkv, D]
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,   # None = auto (1024 when it divides S)
    block_k: Optional[int] = None,   # None = auto (1024 when it divides T)
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention in the model's [B, S, H, D] layout.

    Falls back to the XLA path when shapes don't tile cleanly (sequence not
    divisible by block, tiny head_dim) — callers never need to special-case.
    """
    B, S, Hq, D = q.shape
    T = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if not flash_tileable(q.shape, k.shape, block_q, block_k):
        return dot_product_attention(q, k, v, causal=causal, scale=scale)
    block_q = auto_block_q(S, block_q)
    block_k = auto_block_k(T, block_k)
    out = _flash(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), scale, causal, block_q, block_k, interpret)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Serving: a prompt's own causal self-attention at admission
# ---------------------------------------------------------------------------

# Test hook, as ``decode_attention._FORCE_INTERPRET``: take the kernel (in
# interpret mode) wherever ``prefill_engages`` is asked, whatever the backend.
_FORCE_INTERPRET = False

# Shortest prompt bucket that takes the kernel, from the v5e (32 heads over
# 8 KV heads x 128, ms a layer, einsum pair over an int8 private cache
# against this kernel; PERF.md, PR 30): 0.084 / 0.104 at 256 and 0.121 /
# 0.150 at 512, where the pair's scores are a few MB and it is ahead;
# 0.705 / 0.232 at 1024; 2.45 / 0.61 at 2048.
_PREFILL_MIN = 1024


def _one_tpu_device() -> bool:
    mesh = jax.sharding.get_abstract_mesh()
    return jax.default_backend() == "tpu" and (mesh.empty or mesh.size == 1)


def prefill_engages(t: int, cache_len: int, write_at, n_heads: int,
                    n_kv_heads: int, head_dim: int) -> bool:
    """Whether a cached forward whose caller has stated its mask as causal
    from position 0 (``forward_cached``'s ``causal_lens``) attends through
    ``prefill_attention``, from what the code can see: the call fills a
    private cache of its own length from a static position 0 (so the K and V
    it just projected are all there is to attend to), the bucket is long
    enough to gain, the kernel tiles the shape, and the TPU backend holds the
    call on one device. Everything else runs the einsum pair over the
    cache, which is also the oracle."""
    if not (isinstance(write_at, int) and write_at == 0 and t == cache_len
            and t >= _PREFILL_MIN):
        return False
    if not flash_tileable((1, t, n_heads, head_dim),
                          (1, t, n_kv_heads, head_dim)):
        return False
    return _FORCE_INTERPRET or _one_tpu_device()


def prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      window: Optional[int] = None) -> jax.Array:
    """Causal self-attention of ``[B, T, H, D]`` queries over the
    ``[B, T, Hkv, D]`` keys and values of the same positions: the training
    kernel's forward as it is (no residual statistics, no gradient), under a
    name a device trace can show. Padding past a prompt's end needs no
    length mask: a real query sees only keys at or before itself, and what
    the padded queries compute is read by nobody. ``window``: a window
    layer's band (query ``i`` sees ``i - window < j <= i``): the key blocks
    wholly outside it are skipped (``prefill_key_blocks`` counts them),
    under a name of its own."""
    T, D = q.shape[1], q.shape[3]
    name = ("admit_flash_attention" if window is None
            else "admit_window_attention")
    extra = {} if window is None else {"window": window}
    with jax.named_scope(name):
        out, _ = _flash_forward(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale=D ** -0.5, causal=True,
            block_q=auto_block_q(T), block_k=auto_block_k(T),
            interpret=not _one_tpu_device(), with_lse=False,
            name=name, **extra)
        return out.transpose(0, 2, 1, 3)


def prefill_key_blocks(t: int, window: int, banded: bool):
    """``(visited, band)`` on the host: the (query block, key block) pairs a
    window layer's admission attention computes for one row of ``t``
    positions a head, and the pairs the band touches, both at the kernel's
    blocks (one block where ``t`` is shorter than one). ``banded``: the
    kernel ran with the band; otherwise every pair of the square is
    computed (the einsum pair masks afterwards)."""
    bq, bk = auto_block_q(t), auto_block_k(t)
    nq, nk = max(1, t // bq), max(1, t // bk)
    band = sum(band_blocks(nq, bq, bk, window))
    return (band if banded else nq * nk), band
