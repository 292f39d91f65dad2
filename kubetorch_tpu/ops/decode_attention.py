"""Pallas ragged decode attention for TPU: one query position per row over
the READ-ONLY stacked serving grid, each row read only to its own depth.

The einsum pair it stands in for (``ops/cached_attention.py``:
``cached_attn_merged_q`` / ``cached_attn_merged``) contracts ``q`` against
all ``max_len`` positions of every slot and masks afterwards, so a decode
step streams the whole grid whatever is live. Here the grid planes stay in
HBM as they are — ``[L, B, M, Hkv, D]`` K/V and, for an int8 grid,
``[L, B, M, Hkv]`` scales — and one call a layer walks a work list of the
live ``(row, key block)`` items (``plan``, made once a decode step from the
rows' depths and handed in as
scalar-prefetch operands beside the layer index): each item's K/V block and
scales are copied HBM -> VMEM by the kernel's own double-buffered DMAs, the
next item's in flight while this one is computed. A block at or past a row's
depth is in no item, so it is neither fetched nor computed, and a row at
depth 0 costs nothing at all. (A BlockSpec grid over ``(row, block)`` was
measured first and lost: its dead grid steps cost ~0.3 us each and a row's
first block waited for its own DMA; PERF.md, PR 25.)

The kernel returns the UN-normalised output with its running max and sum
(f32), so the caller joins it with the decode chunk's few bf16 columns by the
log-sum-exp rule and one softmax still spans grid and chunk. Numerics are the
einsum pair's: K/V converted in VMEM to bf16 operands (int8 and bf16 grids;
an f32 grid keeps f32), f32 accumulation, scores × ``ks`` after QK and
probabilities × ``vs`` before PV, f32 running max / sum.

Layouts (read off the compiled decode executable for v5e): an int8 plane is
``{4,3,2,1,0:T(8,128)(4,1)}`` — per position one (Hkv, D) tile with four
heads packed into each 32-bit sublane word — which is the layout Mosaic
infers for the 5-D operand, so the planes go in with no copy. A head's
``[block, D]`` matrix is a strided sublane load of the block's 32-bit words
plus a shift (``_head_planes``). The scales are ``{2,3,1,0:T(8,128)}``:
position-minor, so the caller's ``[L, B, Hkv, M]`` transpose is a bitcast.

A RING (``ring_plan``): a window layer keeps a row's last ``span``
positions, position ``p`` at slot ``p % span`` of a ``[L, B, span, Hkv, D]``
leaf. Keys carry their own rotation and a softmax does not care in which
order it meets them, so the ring is read as it lies: slots ``[0, min(depth,
span))`` as a plane is read to its depth, less the few oldest entries that
have left the window of a query ``step`` positions past the depth (the decode
chunk holds the positions since, beside the ring): a run of slots from
``lo``, modulo the span. The work list carries that run as two more scalars a
row, and the kernel masks it; the plane-reading call is the one it was.

Use ``interpret=True`` for tests on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128   # running max / sum live replicated across the lane tile
_BLOCKS = (512, 256, 128)   # key positions an item; see ``block_for``

# Test hook: run the kernel in interpret mode wherever ``engages`` is asked
# (tests/test_rolling.py drives a toy engine through it on CPU).
_FORCE_INTERPRET = False


def block_for(max_len: int) -> Optional[int]:
    """Largest block the grid's length divides by, or None. 512 positions
    an item, from the v5e (32 x 2048 int8, ms a decode step; PERF.md, PR
    25): 256 is as fast over a chat batch's short rows (1.26 against 1.34)
    and a fifth slower over full rows (7.7 against 6.3); 1024 reads twice
    the live positions of short rows (2.0) for nothing on full ones (6.2)."""
    for block in _BLOCKS:
        if max_len % block == 0:
            return block
    return None


def engages(t: int, max_len: int, n_kv_heads: int, head_dim: int,
            dtype) -> bool:
    """Whether the decode forward takes the kernel, from what the code can
    see: one query position, the TPU backend, the whole grid on one device,
    and shapes the kernel's loads cover. Everything else runs the einsum
    pair, which is also the oracle.

    What the kernel asks of its CALLER beyond this (read off the TPU's
    compiler, ``tests/test_decode_attention.py``): the query block is ``[G,
    D]`` a kv head, ``G`` the query heads a kv head, so where ``G`` rows of
    the queries' dtype are not whole sublane tiles (one query head a kv
    head; 7 of them) the queries go in as float32, whose rows the compiler
    slices singly (the kernel rounds them to its operand dtype itself); and
    kv heads that do not fill whole tiles are stored rounded up by the
    decoder (``kv_heads_stored`` below, the queries padded to match:
    ``pad_heads``), never by the kernel, whose key block then has to fit
    beside them (``ragged_key_block``). 4 bfloat16 kv heads are two whole
    words a position and go in as they lie."""
    pack = 4 // jnp.dtype(dtype).itemsize
    if (t != 1 or block_for(max_len) is None or head_dim % 128
            or pack < 1 or n_kv_heads % pack):
        return False
    if _FORCE_INTERPRET:
        return True
    mesh = jax.sharding.get_abstract_mesh()
    return jax.default_backend() == "tpu" and (mesh.empty or mesh.size == 1)


def kv_heads_stored(n_kv_heads: int, dtype) -> int:
    """Heads a position of ``k`` / ``v`` is stored at: ``n_kv_heads``
    rounded up to the sublane tile of the cache's dtype (8 rows of 32-bit
    words: 8 float32 heads, 16 bfloat16 ones). An array whose heads do not
    fill whole tiles is stored padded anyway, and a kernel's DMA cannot cut
    a ragged tile (30 heads: refused by the TPU's compiler); the padded
    heads hold zeros and their outputs are dropped."""
    tile = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return -(-n_kv_heads // tile) * tile


# the ragged kernel double-buffers a key block of K and of V in VMEM
_RAGGED_BUFFER_BYTES = 10 << 20


def ragged_key_block(max_len: int, heads: int, head_dim: int,
                     dtype) -> Optional[int]:
    """The key block of the kernel over a cache of ``heads`` stored kv
    heads, or None where it does not engage: the largest of ``_BLOCKS``
    that the grid's length divides by AND whose two double-buffered
    ``[block, heads, head_dim]`` planes stay inside the kernel's VMEM (the
    dense decoder's 512 keys x 8 heads is 4 MB; x 32 heads it is 17 MB, over
    the 16 MB a kernel may hold)."""
    if not engages(1, max_len, heads, head_dim, dtype):
        return None
    for block in _BLOCKS:
        if max_len % block == 0 and (4 * block * heads * head_dim
                                     * jnp.dtype(dtype).itemsize
                                     <= _RAGGED_BUFFER_BYTES):
            return block
    return None


def pad_heads(x, heads: int):
    """[B,T,H,D] -> [B,T,heads,D], zeros after the real heads."""
    return jnp.pad(x, ((0, 0), (0, 0), (0, heads - x.shape[2]), (0, 0)))


def _head_planes(ref, operand_dtype):
    """VMEM block ``[block, Hkv, D]`` of the grid's dtype -> one
    ``[block, D]`` matrix a kv head, in ``operand_dtype``. Sub-word dtypes
    are packed along Hkv, so head ``h`` of every position is byte/half
    ``h % pack`` of the words in sublane row ``h // pack``: a strided load of
    32-bit words, then a shift."""
    block, hkv, d = ref.shape[-3:]
    pack = 4 // ref.dtype.itemsize
    flat = ref.reshape(block * hkv, d)
    if pack == 1:
        return [flat[pl.ds(h, block, stride=hkv), :].astype(operand_dtype)
                for h in range(hkv)]
    words = flat.bitcast(jnp.int32)                  # [block*hkv/pack, d]
    rows = hkv // pack
    out = []
    for w in range(rows):
        x = words[pl.ds(w, block, stride=rows), :]   # [block, d] int32
        for i in range(pack):
            if ref.dtype == jnp.int8:
                y = ((x << (24 - 8 * i)) >> 24).astype(jnp.float32)
            else:                                    # bf16: the high half
                y = pltpu.bitcast(
                    x << 16 if i == 0 else x & jnp.int32(-65536),
                    jnp.float32)
            out.append(y.astype(operand_dtype))
    return out


def _attend_block(q_ref, k_ref, v_ref, ks_ref, vs_ref, acc_ref, m_ref, l_ref,
                  start, depth, *, sm_scale: float, dead=None):
    """Fold one key block of one row into its running (acc, m, l).
    ``q_ref`` [Hkv, G, D]; ``k_ref`` / ``v_ref`` [..., block, Hkv, D];
    ``ks_ref`` / ``vs_ref`` [Hkv, block] or None; ``acc_ref`` [Hkv, G, D],
    ``m_ref`` / ``l_ref`` [Hkv, G, 128] (replicated over the lane tile: a
    [G, 1] plane cannot be sliced by row); the block holds positions
    ``start ..`` of which those below ``depth`` are live (at least one).
    ``dead`` = (lo, count, span) of a ring: the ``count`` slots from ``lo``,
    modulo ``span``, hold positions the query's window has left."""
    block = k_ref.shape[-3]
    scaled = ks_ref is not None
    operand = jnp.float32 if k_ref.dtype == jnp.float32 else jnp.bfloat16
    k_heads = _head_planes(k_ref, operand)
    v_heads = _head_planes(v_ref, operand)
    def alive(shape, axis):
        at = start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        if dead is None:
            return at < depth
        lo, count, span = dead
        past = jnp.where(at >= lo, at - lo, at - lo + span)
        return (at < depth) & (past >= count)

    live = alive((1, block), 1)                              # [1, block]
    if not scaled:
        # a float grid can hold anything past a row's depth; 0 x NaN
        # would reach the output through PV
        live_rows = alive((block, 1), 0)
    # Stage by stage across the heads, not head by head: eight independent
    # QK matmuls, then eight softmax updates, then eight PV matmuls. The
    # same operations in head order ran 1.5-1.7x slower on the v5e (each
    # head's matmul -> reduce -> exp -> matmul chain waited on itself).
    heads = range(len(k_heads))
    scores = []
    for h in heads:
        s = jax.lax.dot_general(
            q_ref[h].astype(operand), k_heads[h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [G, block]
        if scaled:
            s = s * ks_ref[h:h + 1, :]
        scores.append(jnp.where(live, s, _NEG_INF))
    probs, alphas = [], []
    for h in heads:
        m_prev = m_ref[h][:, :1]                             # [G, 1]
        m_new = jnp.maximum(m_prev,
                            jnp.max(scores[h], axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores[h] - m_new)
        l_ref[h] = jnp.broadcast_to(
            alpha * l_ref[h][:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape[1:])
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        probs.append(p)
        alphas.append(alpha)
    for h in heads:
        p, v = probs[h], v_heads[h]
        if scaled:
            p = jnp.where(live, p * vs_ref[h:h + 1, :], 0.0)
        else:
            v = jnp.where(live_rows, v, jnp.zeros_like(v))
        acc_ref[h] = alphas[h] * acc_ref[h] + jax.lax.dot_general(
            p.astype(operand), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _kernel(li_ref, depth_ref, row_ref, blk_ref, n_ref, *rest, block: int,
            sm_scale: float, scaled: bool, ring: Optional[int] = None):
    """One call a layer: walk the live (row, block) items, double-buffering
    each item's planes from HBM by hand. ``ring``: the span of a ring leaf,
    whose work list carries two more scalars a row (``ring_plan``)."""
    if ring is not None:
        lo_ref, gone_ref, *rest = rest
    q_ref, k_hbm, v_hbm, *rest = rest
    if scaled:
        (ks_hbm, vs_hbm, acc_ref, m_ref, l_ref, kbuf, vbuf, ksbuf, vsbuf,
         sem) = rest
    else:
        acc_ref, m_ref, l_ref, kbuf, vbuf, sem = rest
    li = li_ref[0]
    n = n_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def copies(i, slot):
        row = row_ref[i]
        at = pl.multiple_of(blk_ref[i] * block, block)
        out = [pltpu.make_async_copy(k_hbm.at[li, row, pl.ds(at, block)],
                                     kbuf.at[slot], sem.at[slot, 0]),
               pltpu.make_async_copy(v_hbm.at[li, row, pl.ds(at, block)],
                                     vbuf.at[slot], sem.at[slot, 1])]
        if scaled:
            out += [pltpu.make_async_copy(
                        ks_hbm.at[li, row, :, pl.ds(at, block)],
                        ksbuf.at[slot], sem.at[slot, 2]),
                    pltpu.make_async_copy(
                        vs_hbm.at[li, row, :, pl.ds(at, block)],
                        vsbuf.at[slot], sem.at[slot, 3])]
        return out

    @pl.when(n > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def body(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n)
        def _next():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        row = row_ref[i]
        _attend_block(q_ref.at[row], kbuf.at[slot], vbuf.at[slot],
                      ksbuf.at[slot] if scaled else None,
                      vsbuf.at[slot] if scaled else None,
                      acc_ref.at[row], m_ref.at[row], l_ref.at[row],
                      blk_ref[i] * block, depth_ref[row], sm_scale=sm_scale,
                      dead=(None if ring is None
                            else (lo_ref[row], gone_ref[row], ring)))
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def plan(depth, max_len: int, block: Optional[int] = None):
    """The kernel's work list from the rows' depths, made once a decode step
    (it is the same for every layer): ``(depth, row, blk, n)`` int32 — the
    live ``(row, key block)`` items in row order, row ``b`` holding blocks
    ``0 .. ceil(depth[b] / block) - 1``, and their number. Entries at or
    past ``n`` are never read."""
    block = block or block_for(max_len)
    B = depth.shape[0]
    depth = depth.astype(jnp.int32)
    nb = (depth + block - 1) // block
    ends = jnp.cumsum(nb)
    item = jnp.arange(B * (max_len // block), dtype=jnp.int32)
    row = jnp.minimum((item[:, None] >= ends[None, :]).sum(axis=1),
                      B - 1).astype(jnp.int32)
    blk = (item - (ends - nb)[row]).astype(jnp.int32)
    return depth, row, blk, ends[-1:].astype(jnp.int32)


def ring_plan(depth, step, span: int, block: Optional[int] = None):
    """The work list over a RING of ``span`` positions (position ``p`` at
    slot ``p % span``): row ``b`` has written positions ``[0, depth[b])``, so
    its ring holds the last ``min(depth, span)`` of them in slots ``[0,
    min(depth, span))``, and its query sits ``step`` (a scalar or ``[B]``)
    positions past its depth and sees position ``p`` iff ``p > depth + step
    - span``. ``plan``'s four over the held slots, then ``(lo, gone)``: the
    ``gone[b]`` oldest entries, in slots ``lo[b] ..`` modulo the span, have
    left that window and are masked (at depth 0: no item at all)."""
    depth = depth.astype(jnp.int32)
    held = jnp.minimum(depth, span)
    lo = jnp.maximum(depth - span, 0) % span
    gone = jnp.clip(held + step - span + 1, 0, span)
    return plan(held, span, block) + (lo.astype(jnp.int32),
                                      gone.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_decode_attention(q, k_all, v_all, ks_all, vs_all, layer, items,
                            *, interpret: bool = False):
    """Attention of one query position a row over the stacked grid.

    ``q`` [B, H, D]; ``k_all`` / ``v_all`` [L, B, M, Hkv, D] (int8, bf16 or
    f32); ``ks_all`` / ``vs_all`` [L, B, M, Hkv] f32 or None; ``layer`` a
    scalar int32; ``items`` = ``plan(depth, M)`` for ``depth`` [B] int32,
    the positions ``m < depth[b]`` being the ones row ``b`` attends (0: the
    row is skipped).

    Returns ``(acc [B, H, D], m [B, H], l [B, H])``, all f32: the
    un-normalised output ``sum_m exp(s_m - m) v_m``, the running max and the
    sum ``sum_m exp(s_m - m)``. A row at depth 0 returns ``(0, -1e30, 0)``.
    """
    B, H, D = q.shape
    _, _, M, Hkv, _ = k_all.shape
    G = H // Hkv
    depth, row, blk, n, *ring = items
    block = (B * M) // row.shape[0]
    scaled = ks_all is not None

    def full(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [full(B, Hkv, G, D), hbm, hbm]
    operands = [q.reshape(B, Hkv, G, D), k_all, v_all]
    scratch = [pltpu.VMEM((2, block, Hkv, D), k_all.dtype),
               pltpu.VMEM((2, block, Hkv, D), v_all.dtype)]
    if scaled:
        # position-minor on the chip already: a bitcast, not a transpose
        in_specs += [hbm, hbm]
        operands += [ks_all.transpose(0, 1, 3, 2),
                     vs_all.transpose(0, 1, 3, 2)]
        scratch += [pltpu.VMEM((2, Hkv, block), jnp.float32),
                    pltpu.VMEM((2, Hkv, block), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA((2, 4))]
    stats = jax.ShapeDtypeStruct((B, Hkv, G, _LANES), jnp.float32)
    acc, m, l = pl.pallas_call(
        functools.partial(_kernel, block=block, sm_scale=D ** -0.5,
                          scaled=scaled, **({"ring": M} if ring else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5 + len(ring), grid=(1,), in_specs=in_specs,
            out_specs=[full(B, Hkv, G, D), full(B, Hkv, G, _LANES),
                       full(B, Hkv, G, _LANES)],
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
                   stats, stats],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="ragged_decode_attention",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), depth, row, blk, n,
      *ring, *operands)
    return (acc.reshape(B, H, D), m[..., 0].reshape(B, H),
            l[..., 0].reshape(B, H))
