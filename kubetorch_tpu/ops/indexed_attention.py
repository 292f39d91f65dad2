"""Attention that CHOOSES its keys by a learned index, for TPU: the index's
score of every earlier position, the exact choice of the ``topk`` best a
query, and attention over exactly those (``models/indexed_moe.py``).

The mathematics (one query ``t`` of one row). The index holds ``Hi`` small
queries ``qI[t, j]`` of ``Di``, one small key ``kI[s]`` of ``Di`` a position
and ``Hi`` float32 weights ``w[t, j]``; ``I[t, s] = sum_j w[t, j] relu(qI[t,
j] . kI[s])`` in float32 (a zero of either sign is +0). ``S_t`` is the
``topk`` positions ``s <= t`` of largest ``I[t, s]``, every ``s <= t`` while
``t + 1 <= topk``, equal scores to the LOWER position (``lax.top_k``'s
order). Attention is the softmax over ``S_t`` alone.

**The choice is a threshold, not a sort.** A float32's bits, with the low 31
flipped where the sign is set, are an int32 that orders as the float does
(``order_keys``). The ``topk``-th largest of a row of such keys is found
exactly by 32 compare-and-count passes, a bit a pass from the sign down
(``kth_choice``: the largest ``v`` with ``count(key >= v) >= topk``); the
keys equal to ``v`` are ties, of which the lowest ``topk - count(key > v)``
positions belong, so a second search of ``log2(N)`` passes finds the position
``p`` of the last tie that does. ``S_t = {s: key > v, or key == v and s <=
p}``: a test a position, the same set ``lax.top_k`` returns, with no sort
and no index list. A row with fewer than ``topk`` valid positions comes out
as ``v`` = the least int32 (what an invalid position holds) and ``p = -1``:
everything valid, nothing else.

Four Pallas kernels, each under a name a device trace shows, and plain
``jnp`` with the same semantics wherever they do not engage (the CPU, a
mesh, several query positions a row: the oracle of
``tests/test_indexed_moe.py``):

- ``index_select`` (admission): a block of 128 queries against the row's
  index keys to the diagonal, 512 at a time: the scores become order keys in
  a VMEM scratch ``[128, T]``, the threshold and the tie position are found
  there (47 passes over VMEM, none over HBM), and the choice leaves as an
  int8 mask ``[T, T]`` (written to the diagonal; what lies past it is never
  read). Query blocks under ``topk`` or past the prompt's end choose nothing:
  their mask is the causal one.
- ``admit_indexed_attention`` (admission): the flash kernel's forward
  (``ops/flash_attention.py``: online softmax, key blocks past the diagonal
  skipped) with that mask ANDed into the causal one, bfloat16 operands as
  they come and float32 accumulation.
- ``index_choice`` (a decode step): the same search over ``[B, M + C]``
  order keys (2 MB at 16 rows of 32768) that lie in VMEM whole, every tile
  at a static offset.
- ``indexed_decode_attention`` (a decode step: one query a row over the
  READ-ONLY stacked grid): the ragged kernel (``ops/decode_attention.py``:
  a work list of live ``(row, key block)`` items, each row's K and V read
  only to its depth by the kernel's own double-buffered copies) with the
  choice as a mask: the row's order keys ``[B, M]`` stay in VMEM, its
  ``(v, p)`` ride the scalar prefetch, and a position is live iff it is
  under the depth AND chosen. It returns the un-normalised output with its
  running max and sum, so the chunk's few columns (which compete in the
  same choice) join by the log-sum-exp rule.

A decode step's SCORES are XLA's (``decode_choice``): the index keys of a
layer are 128 bytes a position and are read whole (``[B, M, Di]``: every
slot to ``max_len``, 24% of the decode executable in the cell that has it:
PERF.md section 5, PR 42). Both ways of reading K and V for the chosen were
measured on the v5e at 16 rows (ms a layer a step, PERF.md section 6, PR
42): the masked ragged read 0.65 / 0.92 / 1.63 at 8192 / 16384 / 32760
positions a row, a fetch of the 2048 chosen by prefetched indices (4096
one-position copies a row) 1.55 at every depth: the masked read ships.

Use ``interpret=True`` for tests on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubetorch_tpu.ops.decode_attention import _head_planes, block_for

_NEG_INF = -1e30
_INT_MIN = -2 ** 31
_LANES = 128
# admission: queries a block of the choice (their order keys are a VMEM
# scratch [block, T] int32: 16 MB at 32768 positions), keys a pass; queries
# and keys a block of the attention
SELECT_BLOCK_Q, SELECT_BLOCK_K = 128, 512
ATTEND_BLOCK = 1024
_SELECT_VMEM_BYTES = 100 << 20

# Test hook, as ``decode_attention._FORCE_INTERPRET``: take the kernels (in
# interpret mode) wherever ``engages`` / ``admit_engages`` is asked.
_FORCE_INTERPRET = False


def _one_tpu_device() -> bool:
    mesh = jax.sharding.get_abstract_mesh()
    return jax.default_backend() == "tpu" and (mesh.empty or mesh.size == 1)


# ------------------------------------------------------- plain jnp: oracle
def index_scores(qi, ki, w):
    """``qi`` [..., T, Hi, Di], ``ki`` [..., S, Di], ``w`` [..., T, Hi]
    float32 -> ``I`` [..., T, S] float32, zeros of either sign as +0."""
    s = jnp.einsum("...thd,...sd->...ths", qi, ki,
                   preferred_element_type=jnp.float32)
    scores = jnp.einsum("...ths,...th->...ts", jax.nn.relu(s),
                        w.astype(jnp.float32))
    return jnp.where(scores == 0, 0.0, scores)


def order_keys(scores, valid):
    """float32 scores -> int32 keys that order as the scores do; a position
    that is not ``valid`` holds the least int32, under every real score."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.int32)
    keys = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jnp.where(valid, keys, _INT_MIN)


def kth_choice(keys, k: int):
    """``keys`` [..., N] int32 (``order_keys``) -> ``(v, p)`` [...] int32:
    position ``s`` is among the ``k`` largest, ties to the lower position,
    iff ``keys[s] > v or (keys[s] == v and s <= p)``. Fewer than ``k`` valid
    positions: ``v`` the least int32 and ``p = -1`` (every valid position,
    no other). 32 + 1 + ``log2(N)`` compare-and-count passes, no sort."""
    n = keys.shape[-1]

    def count(pred):
        return jnp.sum(pred, axis=-1, dtype=jnp.int32)

    v = jnp.where(count(keys >= 0) >= k, 0, _INT_MIN).astype(jnp.int32)

    def value_bit(i, v):
        cand = v | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(keys >= cand[..., None]) >= k, cand, v)

    v = jax.lax.fori_loop(0, 31, value_bit, v)
    need = k - count(keys > v[..., None])
    tie = keys == v[..., None]
    at = jnp.arange(n, dtype=jnp.int32)
    bits = max(1, (n - 1).bit_length())

    def position_bit(i, p):
        cand = p | jnp.left_shift(jnp.int32(1), bits - 1 - i)
        return jnp.where(count(tie & (at < cand[..., None])) < need, cand, p)

    p = jax.lax.fori_loop(0, bits, position_bit, jnp.zeros_like(v))
    return v, jnp.where(v == _INT_MIN, -1, p)


def chosen(keys, v, p):
    """The choice as a mask over ``keys`` [..., N]: ``(v, p)`` [...] of
    ``kth_choice``, a key's index its position."""
    at = jnp.arange(keys.shape[-1], dtype=jnp.int32)
    v, p = v[..., None], p[..., None]
    return (keys > v) | ((keys == v) & (at <= p))


def choice_mask(qi, ki, w, valid, k: int):
    """The whole choice in plain ``jnp``: ``valid`` [..., T, S] bool (which
    positions a query may see at all) -> the chosen among them, [..., T,
    S] bool."""
    keys = order_keys(index_scores(qi, ki, w), valid)
    return chosen(keys, *kth_choice(keys, k)) & valid


# ------------------------------------------------ admission: the choice
def admit_engages(t: int, topk: int, n_heads: int, n_kv_heads: int,
                  head_dim: int) -> bool:
    """Whether a prompt's own prefill of ``t`` positions (a private cache
    from position 0, the mask stated as causal) chooses and attends through
    the two admission kernels: the bucket is past ``topk`` (under it every
    query sees everything and there is nothing to choose), the blocks tile
    it, and one TPU device holds the call."""
    if (t <= topk or t % ATTEND_BLOCK or topk % SELECT_BLOCK_Q
            or head_dim % 128 or n_heads % n_kv_heads):
        return False
    return _FORCE_INTERPRET or _one_tpu_device()


def select_pairs(length: int, topk: int, block_q: int = SELECT_BLOCK_Q,
                 block_k: int = SELECT_BLOCK_K) -> int:
    """(query, key) pairs ``index_select`` scores for one row of ``length``
    real positions, on the host: the query blocks that reach past ``topk``
    and start under the row's end, each against the key blocks to its
    diagonal."""
    return sum(block_q * (q0 // block_k + 1) * block_k
               for q0 in range(0, length, block_q) if q0 + block_q > topk)


def _kth_in_vmem(keys_ref, n, block_k: int, topk: int, pos_bits: int):
    """``kth_choice`` over order keys that lie in VMEM: ``keys_ref`` [rows,
    N] int32, of which key blocks ``[0, n)`` of ``block_k`` positions count
    (``n`` may be traced). Returns ``(v, p)`` [rows, 128] int32, each row's
    value replicated across the lane tile; ``p`` is the raw tie position
    (the caller turns it into -1 where ``v`` is the least int32)."""
    rows = keys_ref.shape[0]
    tiles = block_k // _LANES
    shape = (rows, _LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def count(pred):
        """Positions a row for which ``pred(keys, position)`` holds."""
        def some(c, cnt):
            for j in range(tiles):
                at = c * block_k + j * _LANES
                if not isinstance(at, int):
                    at = pl.multiple_of(at, _LANES)
                cnt = cnt + jnp.where(
                    pred(keys_ref[:, pl.ds(at, _LANES)], at + lane), 1, 0)
            return cnt

        cnt = jnp.zeros(shape, jnp.int32)
        if isinstance(n, int):
            # a few rows of a known length (a decode step's 16): every tile
            # at its own static offset; a loop's step costs more than the
            # two registers a tile of 16 rows holds
            for c in range(n):
                cnt = some(c, cnt)
        else:
            cnt = jax.lax.fori_loop(0, n, some, cnt)
        return jnp.broadcast_to(jnp.sum(cnt, axis=1, keepdims=True), shape)

    v = jnp.where(count(lambda key, _: key >= 0) >= topk, 0,
                  _INT_MIN).astype(jnp.int32)

    def value_bit(i, v):
        cand = v | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count(lambda key, _: key >= cand) >= topk, cand, v)

    v = jax.lax.fori_loop(0, 31, value_bit, v)
    need = topk - count(lambda key, _: key > v)

    def position_bit(i, p):
        cand = p | jnp.left_shift(jnp.int32(1), pos_bits - 1 - i)
        return jnp.where(
            count(lambda key, at: (key == v) & (at < cand)) < need, cand, p)

    return v, jax.lax.fori_loop(0, pos_bits, position_bit,
                                jnp.zeros(shape, jnp.int32))


def _select_kernel(lens_ref, qi_ref, w_ref, kit_ref, mask_ref, keys_ref, *,
                   topk: int, block_q: int, block_k: int, heads: int,
                   pos_bits: int):
    """One block of queries: scores -> order keys in ``keys_ref`` -> the
    threshold and the tie position -> the mask, to the diagonal's block.
    Row values live replicated across the lane tile (``[block_q, 128]``)."""
    q0 = pl.program_id(1) * block_q
    n = q0 // block_k + 1                     # key blocks to the diagonal's
    tiles = block_k // _LANES
    shape = (block_q, _LANES)
    row = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    choose = (q0 + block_q > topk) & (q0 < lens_ref[pl.program_id(0)])

    def tile_at(c, j):
        return pl.multiple_of(c * block_k + j * _LANES, _LANES)

    @pl.when(jnp.logical_not(choose))
    def _causal():
        def write(c, carry):
            for j in range(tiles):
                at = tile_at(c, j)
                mask_ref[0, :, pl.ds(at, _LANES)] = jnp.where(
                    at + lane <= row, 1, 0).astype(mask_ref.dtype)
            return carry

        jax.lax.fori_loop(0, n, write, 0)

    @pl.when(choose)
    def _choose():
        def score(c, carry):
            at = pl.multiple_of(c * block_k, block_k)
            kit = kit_ref[0, :, pl.ds(at, block_k)]           # [Di, block_k]
            acc = jnp.zeros((block_q, block_k), jnp.float32)
            w = w_ref[0]                                      # [block_q, Hi]
            for j in range(heads):
                s = jax.lax.dot_general(
                    qi_ref[0, j], kit, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
            acc = jnp.where(acc == 0, 0.0, acc)
            bits = pltpu.bitcast(acc, jnp.int32)
            keys = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
            full = (block_q, block_k)
            seen = (at + jax.lax.broadcasted_iota(jnp.int32, full, 1)
                    <= q0 + jax.lax.broadcasted_iota(jnp.int32, full, 0))
            keys_ref[:, pl.ds(at, block_k)] = jnp.where(seen, keys, _INT_MIN)
            return carry

        jax.lax.fori_loop(0, n, score, 0)

        v, p = _kth_in_vmem(keys_ref, n, block_k, topk, pos_bits)

        def write(c, carry):
            for j in range(tiles):
                at = tile_at(c, j)
                key, col = keys_ref[:, pl.ds(at, _LANES)], at + lane
                keep = ((key > v) | ((key == v) & (col <= p))) & (col <= row)
                mask_ref[0, :, pl.ds(at, _LANES)] = jnp.where(
                    keep, 1, 0).astype(mask_ref.dtype)
            return carry

        jax.lax.fori_loop(0, n, write, 0)


@functools.partial(jax.jit, static_argnames=("topk", "block_q", "block_k",
                                             "interpret"))
def index_select(qi, ki, w, lens, *, topk: int,
                 block_q: Optional[int] = None,
                 block_k: Optional[int] = None, interpret: bool = False):
    """The choice of every query of ``B`` rows of ``T`` positions, as a
    mask. ``qi`` [B, T, Hi, Di] and ``ki`` [B, T, Di] (normed, rotated, the
    compute dtype), ``w`` [B, T, Hi] float32, ``lens`` [B] int32 (a row's
    real positions) -> int8 [B, T, T]: 1 where query ``t`` chose position
    ``s``, VALID ONLY to the block of the diagonal (``s < (t // block_q *
    block_q // block_k + 1) * block_k``; the rest is never written and never
    read: the attention ANDs the causal mask in). A query block under
    ``topk`` or past ``lens`` holds the causal mask."""
    B, T, Hi, Di = qi.shape
    block_q = min(block_q or SELECT_BLOCK_Q, T)
    block_k = min(block_k or SELECT_BLOCK_K, T)
    if T % block_k or block_k % block_q or block_k % _LANES or block_q % 32:
        raise ValueError(f"{T} positions do not tile by ({block_q}, "
                         f"{block_k})")
    with jax.named_scope("index_select"):
        return pl.pallas_call(
            functools.partial(
                _select_kernel, topk=topk, block_q=block_q, block_k=block_k,
                heads=Hi, pos_bits=max(1, (T - 1).bit_length())),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B, T // block_q),
                in_specs=[
                    pl.BlockSpec((1, Hi, block_q, Di),
                                 lambda b, i, *_: (b, 0, i, 0)),
                    pl.BlockSpec((1, block_q, Hi),
                                 lambda b, i, *_: (b, i, 0)),
                    pl.BlockSpec((1, Di, T), lambda b, i, *_: (b, 0, 0)),
                ],
                out_specs=pl.BlockSpec((1, block_q, T),
                                       lambda b, i, *_: (b, i, 0)),
                scratch_shapes=[pltpu.VMEM((block_q, T), jnp.int32)]),
            out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.int8),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_SELECT_VMEM_BYTES),
            name="index_select",
            interpret=interpret,
        )(lens.astype(jnp.int32), qi.transpose(0, 2, 1, 3),
          w.astype(jnp.float32),
          ki.transpose(0, 2, 1))


# --------------------------------------------- admission: the attention
def _attend_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, block_q: int, block_k: int):
    """``flash_attention._fwd_kernel``'s forward, causal, with the choice
    (``mask_ref`` [block_q, block_k] int8) ANDed into the mask; the operands
    go to the MXU in the dtype they come in."""
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * block_k <= qi * block_q + block_q - 1)
    def _compute():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        keep = (q_pos >= k_pos) & (mask_ref[0].astype(jnp.int32) != 0)
        s = jnp.where(keep, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a block may hold nothing a query chose: exp(-1e30 - -1e30) = 1
        # would count every masked key of it
        p = jnp.where(keep, jnp.exp(s - m_new[:, :1]), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * correction + jnp.sum(
            p, axis=1, keepdims=True)
        acc_ref[...] = (
            acc_ref[...] * correction[:, :acc_ref.shape[1]]
            + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(
            l_ref[...][:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def admit_indexed_attention(q, k, v, mask, *, block: Optional[int] = None,
                            interpret: bool = False):
    """Causal self-attention of ``q`` [B, T, H, D] over ``k``, ``v`` [B, T,
    Hkv, D] of the same positions, each query over the positions ``mask``
    [B, T, T] int8 marks for it (``index_select``; read to the diagonal's
    block only) -> [B, T, H, D]."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    block = min(block or ATTEND_BLOCK, T)
    n = T // block

    def kv_at(b, h, qi, ki):
        # past the diagonal: the diagonal's block again (no new fetch)
        return (b, h // group, jnp.minimum(ki, qi), 0)

    with jax.named_scope("admit_indexed_attention"):
        out = pl.pallas_call(
            functools.partial(_attend_kernel, scale=D ** -0.5,
                              block_q=block, block_k=block),
            grid=(B, H, n, n),
            in_specs=[
                pl.BlockSpec((1, 1, block, D),
                             lambda b, h, qi, ki: (b, h, qi, 0)),
                pl.BlockSpec((1, 1, block, D), kv_at),
                pl.BlockSpec((1, 1, block, D), kv_at),
                pl.BlockSpec((1, block, block),
                             lambda b, h, qi, ki: (b, qi,
                                                   jnp.minimum(ki, qi))),
            ],
            out_specs=pl.BlockSpec((1, 1, block, D),
                                   lambda b, h, qi, ki: (b, h, qi, 0)),
            out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                            pltpu.VMEM((block, _LANES), jnp.float32),
                            pltpu.VMEM((block, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            name="admit_indexed_attention",
            interpret=interpret,
        )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
          v.transpose(0, 2, 1, 3), mask)
    return out.transpose(0, 2, 1, 3)


def admit_attention(q, k, v, qi, ki, w, lens, topk: int):
    """A prompt's own prefill through both kernels (``admit_engages``)."""
    interpret = not _one_tpu_device()
    mask = index_select(qi, ki, w, lens, topk=topk, interpret=interpret)
    return admit_indexed_attention(q, k, v, mask, interpret=interpret)


# ------------------------------------------------------ decode: the choice
def decode_choice(qi, w, grid_ik, chunk_ik, depth, chunk_mask, topk: int,
                  kernel: bool = False):
    """One choice over a row's grid positions and the chunk's columns.
    ``qi`` [B, T, Hi, Di], ``w`` [B, T, Hi]; ``grid_ik`` [B, M, Di] (valid
    under ``depth`` [B]); ``chunk_ik`` [B, C, Di], column ``c`` holding
    position ``depth + c`` and valid where ``chunk_mask`` [B, T, C]. Returns
    ``(grid_keys [B, T, M] int32, chunk_chosen [B, T, C] bool, v [B, T], p
    [B, T])``: query ``t`` chose grid position ``s`` iff ``s < depth`` and
    ``chosen(grid_keys, v, p)`` holds there.

    Ties go to the lower POSITION: a grid position lies under its row's
    depth and a chunk column at or past it, so the concatenation's order is
    the positions' order, and ``p`` (an index into it: ``M`` or more where
    every tie of the grid belongs) reads as a position on the grid.
    ``kernel`` (one query a row): the threshold search runs in
    ``index_choice``, not in XLA."""
    B, T = qi.shape[:2]
    M = grid_ik.shape[1]
    under = jnp.arange(M, dtype=jnp.int32)[None, :] < depth[:, None]
    keys = jnp.concatenate(
        [order_keys(index_scores(qi, grid_ik, w),
                    jnp.broadcast_to(under[:, None, :], (B, T, M))),
         order_keys(index_scores(qi, chunk_ik, w), chunk_mask)], axis=-1)
    if kernel:
        v, p = index_choice(keys[:, 0], topk=topk,
                            interpret=not _one_tpu_device())
        v, p = v[:, None], p[:, None]
    else:
        v, p = kth_choice(keys, topk)
    return (keys[..., :M], chosen(keys, v, p)[..., M:] & chunk_mask, v, p)


def _choice_kernel(keys_ref, v_ref, p_ref, *, topk: int, block_k: int,
                   pos_bits: int):
    v, p = _kth_in_vmem(keys_ref, keys_ref.shape[1] // block_k, block_k,
                        topk, pos_bits)
    v_ref[...] = v
    p_ref[...] = p


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def index_choice(keys, *, topk: int, interpret: bool = False):
    """``kth_choice`` of every row of ``keys`` [B, N] int32 in one kernel:
    the keys lie in VMEM (2 MB at 16 rows of 32768) and the 32 + 1 + log2(N)
    passes run there, where XLA's are as many fusions over HBM. Returns
    ``(v, p)`` [B] int32."""
    B, N = keys.shape
    pad = -N % SELECT_BLOCK_K
    if pad:
        keys = jnp.pad(keys, ((0, 0), (0, pad)), constant_values=_INT_MIN)
    n = N + pad

    def full(*shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    out = jax.ShapeDtypeStruct((B, _LANES), jnp.int32)
    with jax.named_scope("index_choice"):
        v, p = pl.pallas_call(
            functools.partial(_choice_kernel, topk=topk,
                              block_k=SELECT_BLOCK_K,
                              pos_bits=max(1, (n - 1).bit_length())),
            grid=(1,), in_specs=[full(B, n)],
            out_specs=[full(B, _LANES), full(B, _LANES)],
            out_shape=[out, out],
            name="index_choice",
            interpret=interpret,
        )(keys)
    v, p = v[:, 0], p[:, 0]
    return v, jnp.where(v == _INT_MIN, -1, p)


# --------------------------------------------------- decode: the attention
def engages(t: int, max_len: int, n_kv_heads: int, head_dim: int,
            dtype) -> bool:
    """``decode_attention.engages``'s rule for the ragged kernel with the
    choice as a mask: one query position a row, shapes its loads cover, one
    TPU device."""
    pack = 4 // jnp.dtype(dtype).itemsize
    if (t != 1 or block_for(max_len) is None or head_dim % 128
            or pack < 1 or n_kv_heads % pack):
        return False
    return _FORCE_INTERPRET or _one_tpu_device()


def _decode_block(q_ref, k_ref, v_ref, keys_ref, acc_ref, m_ref, l_ref,
                  start, depth, v_thr, p_tie, *, sm_scale: float):
    """``decode_attention._attend_block`` over a float grid with the choice:
    the block holds positions ``start ..``; one is live iff it lies under
    ``depth`` and its order key (``keys_ref`` [1, block]) passes ``(v_thr,
    p_tie)``. V rows past the depth are zeroed (a float grid can hold
    anything there); a position under it that was not chosen has weight 0."""
    block = k_ref.shape[-3]
    operand = jnp.float32 if k_ref.dtype == jnp.float32 else jnp.bfloat16
    k_heads = _head_planes(k_ref, operand)
    v_heads = _head_planes(v_ref, operand)
    at = start + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    keys = keys_ref[...]
    live = (at < depth) & ((keys > v_thr) | ((keys == v_thr) & (at <= p_tie)))
    live_rows = (start + jax.lax.broadcasted_iota(
        jnp.int32, (block, 1), 0)) < depth
    heads = range(len(k_heads))
    scores = []
    for h in heads:
        s = jax.lax.dot_general(
            q_ref[h].astype(operand), k_heads[h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [G, block]
        scores.append(jnp.where(live, s, _NEG_INF))
    probs, alphas = [], []
    for h in heads:
        m_prev = m_ref[h][:, :1]                             # [G, 1]
        m_new = jnp.maximum(m_prev,
                            jnp.max(scores[h], axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a block may hold nothing the row chose
        p = jnp.where(live, jnp.exp(scores[h] - m_new), 0.0)
        l_ref[h] = jnp.broadcast_to(
            alpha * l_ref[h][:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape[1:])
        m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        probs.append(p)
        alphas.append(alpha)
    for h in heads:
        v = jnp.where(live_rows, v_heads[h], jnp.zeros_like(v_heads[h]))
        acc_ref[h] = alphas[h] * acc_ref[h] + jax.lax.dot_general(
            probs[h].astype(operand), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _decode_kernel(li_ref, depth_ref, row_ref, blk_ref, n_ref, thr_ref,
                   tie_ref, q_ref, keys_ref, k_hbm, v_hbm, acc_ref, m_ref,
                   l_ref, kbuf, vbuf, sem, *, block: int, sm_scale: float):
    """``decode_attention._kernel`` over a float grid: walk the live (row,
    block) items, double-buffering each item's planes from HBM by hand."""
    li = li_ref[0]
    n = n_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def copies(i, slot):
        row = row_ref[i]
        at = pl.multiple_of(blk_ref[i] * block, block)
        return [pltpu.make_async_copy(k_hbm.at[li, row, pl.ds(at, block)],
                                      kbuf.at[slot], sem.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[li, row, pl.ds(at, block)],
                                      vbuf.at[slot], sem.at[slot, 1])]

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
        at = pl.multiple_of(blk_ref[i] * block, block)
        _decode_block(q_ref.at[row], kbuf.at[slot], vbuf.at[slot],
                      keys_ref.at[pl.ds(row, 1), pl.ds(at, block)],
                      acc_ref.at[row], m_ref.at[row], l_ref.at[row],
                      at, depth_ref[row], thr_ref[row], tie_ref[row],
                      sm_scale=sm_scale)
        return carry

    jax.lax.fori_loop(0, n, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def indexed_decode_attention(q, k_all, v_all, layer, items, keys, v_thr,
                             p_tie, *, interpret: bool = False):
    """Attention of one query position a row over the chosen positions of
    the stacked grid. ``q`` [B, H, D]; ``k_all`` / ``v_all`` [L, B, M, Hkv,
    D] (bf16 or f32); ``layer`` a scalar; ``items`` =
    ``decode_attention.plan(depth, M)``; ``keys`` [B, M] int32 and ``v_thr``,
    ``p_tie`` [B] int32 the row's choice (``decode_choice``). Returns
    ``(acc [B, H, D], m [B, H], l [B, H])`` float32, as
    ``decode_attention.ragged_decode_attention`` does."""
    B, H, D = q.shape
    _, _, M, Hkv, _ = k_all.shape
    G = H // Hkv
    depth, row, blk, n = items
    block = (B * M) // row.shape[0]

    def full(*shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    stats = jax.ShapeDtypeStruct((B, Hkv, G, _LANES), jnp.float32)
    with jax.named_scope("indexed_decode_attention"):
        acc, m, l = pl.pallas_call(
            functools.partial(_decode_kernel, block=block,
                              sm_scale=D ** -0.5),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=7, grid=(1,),
                in_specs=[full(B, Hkv, G, D), full(B, M), hbm, hbm],
                out_specs=[full(B, Hkv, G, D), full(B, Hkv, G, _LANES),
                           full(B, Hkv, G, _LANES)],
                scratch_shapes=[
                    pltpu.VMEM((2, block, Hkv, D), k_all.dtype),
                    pltpu.VMEM((2, block, Hkv, D), v_all.dtype),
                    pltpu.SemaphoreType.DMA((2, 2))]),
            out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
                       stats, stats],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            name="indexed_decode_attention",
            interpret=interpret,
        )(jnp.reshape(layer, (1,)).astype(jnp.int32), depth, row, blk, n,
          v_thr.astype(jnp.int32), p_tie.astype(jnp.int32),
          q.reshape(B, Hkv, G, D), keys, k_all, v_all)
    return (acc.reshape(B, H, D), m[..., 0].reshape(B, H),
            l[..., 0].reshape(B, H))
