"""The gated delta rule of the linear-attention layers
(``models/hybrid_linear.py``): a recurrent state a head a row, no positions.

A head keeps ``S`` of ``dk x dv`` (float32). A token with key ``k`` (unit
norm), value ``v``, query ``q``, decay ``alpha`` in (0, 1] and step ``beta``
in [0, 2):

    S <- alpha (I - beta k k^T) S + beta k v^T        o = S^T q

``alpha = 1, beta = 0`` leaves ``S`` as it was, bit for bit: that is how a
padded position of a bucket and a row that is not decoding pass through.

Four forms of the one rule:

- ``step``: one token a row (a decode step), in XLA: two passes over the
  state of EVERY row (``S^T [k, q]`` read together, then the update), a row
  that does not decode held by ``alpha = 1, beta = 0``. The oracle of
  ``step_rows``, and the decode step wherever that does not engage.
- ``step_rows``: the same step as the Pallas kernel ``gated_delta_step`` on
  one TPU device, over the stacked state leaf ``[L, B, H, dk, dv]`` in place
  (``input_output_aliases``: no slice of the leaf is made). Its grid is the
  rows that decode (``step_plan``, scalar-prefetched beside the layer
  index): a row's ``H x [dk, dv]`` block is fetched once, ``S^T k``, ``S^T
  q``, the update and the output are made from the copy in VMEM in float32
  on the vector unit, and the block is written back once. A row that does
  not decode is neither fetched nor written.
- ``recurrence``: ``step`` over the tokens of a sequence, in order: the
  oracle of the tests.
- ``prefill_scan``: the chunked form of an admission. Inside a chunk of ``C``
  tokens the rule is the WY form: with ``g`` the running sum of ``log
  alpha``, ``A[i,j] = beta_i exp(g_i - g_j) k_i.k_j`` (j < i) and ``T = (I +
  A)^-1``, the chunk acts on the state it starts from through ``w = T (beta
  e^g k)`` and ``u = T (beta v)``: ``v' = u - w S``, ``o = (e^g q) S +
  tril(q k^T e^(g_i - g_j)) v'``, ``S <- e^(g_C) S + (e^(g_C - g) k)^T v'``.
  ``T`` is made by forward substitution in ``_SOLVE_BLOCK``-row diagonal
  blocks, merged upward by products (a Neumann series loses digits where
  ``beta k.k`` nears 2). Two carriers of the one form:

  - On one TPU device, over a scan the chunk divides (every bucket of an
    admission): the Pallas kernel ``gated_delta_prefill``, a grid step a
    (row, block of ``_HEAD_BLOCK`` heads, chunk). The step takes the chunk's
    ``q``, ``k``, ``v`` as the mixer hands them over (bfloat16, head sizes
    unpadded) and builds everything above in VMEM: the decays, ``[q; k]
    k^T``, ``A``, ``T`` (the diagonal blocks solved side by side in two
    vregs, a column at a time; three merge levels on the rows they change),
    then ``v' = T (beta (v - e^g k S))`` (``T`` taken out of ``u - w S``:
    one product for three), ``o`` and the new state. The state block stays
    in VMEM from a row's first chunk to its last. A head's steps hang on
    each other and the heads' do not, so every line of the kernel is
    written for all heads of the block at once (``[H, ., .]`` arrays,
    batched products): the compiler fills one head's waits with another's
    work, and a bucket's executable traces a sixth of the operations a
    loop over heads would (a process traces every bucket at start-up: the
    cell's ``setup_s``). XLA's part is the layout alone: ``q``,
    ``k``, ``v`` a head at a time with the tokens along the lanes (``[B, H,
    d, T]``; the kernel turns a chunk's blocks itself), ``o`` back, and the
    per-token scalars ``g`` (a cumulative sum inside each chunk) and
    ``beta`` (``[B, T, H]`` float32) in the two layouts the kernel reads
    them in, along the lanes and down the rows. Nothing a (head, chunk)
    pair in float32 (``A``, ``T``, ``p``: ``[c, c]``; ``w``, ``u``, ``qg``,
    ``kd``: ``[c, dk | dv]``) exists outside the kernel.
  - Elsewhere (the CPU, a mesh of more than one device, a scan the chunk
    does not divide: the hybrid's chunked prefill): ``_factors`` makes those
    matrices for all chunks at once in XLA and a ``lax.scan`` over the
    chunks runs the three lines that meet the state. It is the kernel's
    oracle in the tests, which is why it stays as it was written.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128          # tokens a chunk: every matrix of the kernel lane-aligned
_LANES = 128
_SOLVE_BLOCK = 16    # rows solved by substitution before blocks are merged
_HEAD_BLOCK = 6      # most heads a grid step of the prefill kernel takes
_HIGHEST = jax.lax.Precision.HIGHEST

_STEP_VMEM = 100 << 20   # most a decode step's kernel may ask of VMEM

# Test hook, as ``decode_attention._FORCE_INTERPRET``: take the kernels (in
# interpret mode) wherever ``prefill_engages`` / ``step_engages`` are asked.
_FORCE_INTERPRET = False


def _one_tpu_device() -> bool:
    mesh = jax.sharding.get_abstract_mesh()
    return jax.default_backend() == "tpu" and (mesh.empty or mesh.size == 1)


def prefill_engages(t: int) -> bool:
    """The kernel takes a scan whose length its chunk divides, on one TPU
    device; the ``lax.scan`` over chunks takes the rest."""
    return t % CHUNK == 0 and (_FORCE_INTERPRET or _one_tpu_device())


def _step_vmem_bytes(heads: int, dk: int, dv: int) -> int:
    """What ``gated_delta_step`` holds in VMEM: a row's state block as the
    chip tiles it (8 x 128 float32), in and out, each double-buffered, and
    room for the small operands and the compiler's own temporaries."""
    block = heads * -(-dk // 8) * 8 * -(-dv // _LANES) * _LANES * 4
    return 4 * block + (8 << 20)


def step_engages(heads: int, dk: int, dv: int) -> bool:
    """The kernel takes a decode step on one TPU device where a row's state
    block fits its VMEM; the XLA ``step`` takes the rest."""
    return (_step_vmem_bytes(heads, dk, dv) <= _STEP_VMEM
            and (_FORCE_INTERPRET or _one_tpu_device()))


def scan_positions(t: int) -> int:
    """Positions a scan over ``t`` tokens walks: ``t`` rounded up to the
    chunk (a scan shorter than one chunk is one chunk of its own length)."""
    c = min(CHUNK, t)
    return -(-t // c) * c


# ------------------------------------------------------------ one token
def step(q, k, v, log_alpha, beta, state):
    """One token a row. ``q``, ``k`` [B,H,dk], ``v`` [B,H,dv], ``log_alpha``,
    ``beta`` [B,H], ``state`` [B,H,dk,dv] float32 -> (o [B,H,dv] float32,
    new state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    alpha = jnp.exp(log_alpha.astype(f32))[..., None]
    # S^T k and S^T q in one pass over the state
    r = jnp.einsum("bhjk,bhkv->bhjv", jnp.stack([k, q], axis=2), state,
                   precision=_HIGHEST)
    u = beta.astype(f32)[..., None] * (v - alpha * r[:, :, 0])
    new = alpha[..., None] * state + k[..., :, None] * u[..., None, :]
    o = alpha * r[:, :, 1] + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, new


def step_plan(live):
    """The kernel's work list from the rows that decode (``live`` [B] bool),
    made once a decode step (it is the same for every layer): ``(rows [B],
    n [1])`` int32, the decoding rows first, in row order, and their number.
    An entry at or past ``n`` repeats the last decoding row (row 0 where
    none decodes), so the block index of a grid step with no work does not
    move and nothing is fetched or written for it."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n = jnp.sum(live, dtype=jnp.int32)
    at = jnp.minimum(jnp.arange(live.shape[0]), jnp.maximum(n - 1, 0))
    return order[at], n[None]


def _step_kernel(li_ref, rows_ref, n_ref, alpha_ref, beta_ref, kq_ref,
                 kqt_ref, v_ref, s0_ref, o0_ref, o_ref, s_ref):
    """Grid step ``j``: the ``j``-th decoding row's state block of layer
    ``li``, every head of it. ``alpha_ref``, ``beta_ref``, ``kq_ref``
    (SMEM, [B * H]) hold the decay, the step and ``k.q`` of every (row,
    head); ``kqt_ref`` [dk, 2H] the row's keys and queries as columns;
    ``v_ref`` [H, dv]. ``o0_ref`` is the zeros the output aliases (never
    read: a row that does not decode keeps them)."""
    del li_ref, o0_ref
    heads = v_ref.shape[0]
    j = pl.program_id(0)
    n = n_ref[0]

    @pl.when(j < n)
    def _decodes():
        base = rows_ref[j] * heads
        for h in range(heads):
            alpha, beta = alpha_ref[base + h], beta_ref[base + h]
            s = s0_ref[h]                                       # [dk, dv]
            k = kqt_ref[:, h:h + 1]                             # [dk, 1]
            q = kqt_ref[:, heads + h:heads + h + 1]
            r_k = jnp.sum(s * k, axis=0, keepdims=True)         # [1, dv]
            r_q = jnp.sum(s * q, axis=0, keepdims=True)
            u = beta * (v_ref[h:h + 1, :] - alpha * r_k)
            s_ref[h] = alpha * s + k * u
            o_ref[h:h + 1, :] = alpha * r_q + kq_ref[base + h] * u

    # no row decodes: the one block the grid holds goes back as it came
    @pl.when(jnp.logical_and(n == 0, j == 0))
    def _held():
        s_ref[...] = s0_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def step_rows(q, k, v, log_alpha, beta, states, layer, plan):
    """``step`` for the rows of ``plan`` (``step_plan``) on layer ``layer``
    of the stacked leaf ``states`` [L,B,H,dk,dv] float32, in place. ``q``,
    ``k`` [B,H,dk], ``v`` [B,H,dv], ``log_alpha``, ``beta`` [B,H] -> (o
    [B,H,dv] float32, the leaf). A row outside the plan keeps its state bit
    for bit, in every layer, and its ``o`` is zeros. Off the TPU the kernel
    is interpreted."""
    _, B, H, dk, dv = states.shape
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    rows, n = plan
    kqt = jnp.concatenate([k, q], axis=1).swapaxes(1, 2)        # [B,dk,2H]

    def row(*tail):                 # a [B, *tail] operand, a row a step
        return pl.BlockSpec(
            (None,) + tail,
            lambda j, li, rows, *_: (rows[j],) + (0,) * len(tail))

    block = pl.BlockSpec((None, None, H, dk, dv),
                         lambda j, li, rows, *_: (li[0], rows[j], 0, 0, 0))
    o, states = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(B,),
            in_specs=[row(dk, 2 * H), row(H, dv), block,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[row(H, dv), block]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        # operands count from the scalar-prefetched ones
        input_output_aliases={8: 1, 9: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_step_vmem_bytes(H, dk, dv)),
        name="gated_delta_step",
        interpret=jax.default_backend() != "tpu",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows, n,
      jnp.exp(log_alpha.astype(f32)).ravel(), beta.astype(f32).ravel(),
      jnp.sum(k * q, axis=-1).ravel(), kqt, v, states,
      jnp.zeros((B, H, dv), f32))
    return o, states


def recurrence(q, k, v, log_alpha, beta, state):
    """``step`` over ``T`` tokens in order. ``q``, ``k`` [B,T,H,dk], ``v``
    [B,T,H,dv], ``log_alpha``, ``beta`` [B,T,H] -> (o [B,T,H,dv] float32,
    final state)."""
    def one(state, tok):
        o, state = step(*tok, state)
        return state, o

    state, o = jax.lax.scan(
        one, state.astype(jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_alpha, beta)))
    return jnp.moveaxis(o, 0, 1), state


# ------------------------------------------- the chunks' own part (XLA)
def _inv_unit_lower(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., n, n]:
    forward substitution a row at a time up to ``_SOLVE_BLOCK`` rows, then
    ``[[X11, 0], [-X22 a21 X11, X22]]`` up the halves."""
    n = a.shape[-1]
    if n <= _SOLVE_BLOCK:
        eye = jnp.eye(n, dtype=a.dtype)
        rows = [jnp.broadcast_to(eye[0], a.shape[:-2] + (n,))]
        for i in range(1, n):
            done = jnp.stack(rows, axis=-2)                     # [..., i, n]
            rows.append(eye[i] - jnp.einsum(
                "...j,...jk->...k", a[..., i, :i], done, precision=_HIGHEST))
        return jnp.stack(rows, axis=-2)
    h = n // 2
    x11 = _inv_unit_lower(a[..., :h, :h])
    x22 = _inv_unit_lower(a[..., h:, h:])
    x21 = -jnp.einsum("...ij,...jk,...kl->...il", x22, a[..., h:, :h], x11,
                      precision=_HIGHEST)
    top = jnp.concatenate([x11, jnp.zeros_like(x21.swapaxes(-1, -2))], -1)
    return jnp.concatenate([top, jnp.concatenate([x21, x22], -1)], -2)


def _factors(q, k, v, log_alpha, beta, c: int):
    """What a chunk needs that does not depend on the state, for all chunks
    at once, float32. Inputs [B,T,H,*] with ``T`` a multiple of ``c``;
    every result is [B,H,N,...]: ``w`` [c,dk], ``u`` [c,dv], ``qg`` [c,dk],
    ``kd`` [c,dk], ``p`` [c,c], ``dec`` []."""
    f32 = jnp.float32
    B, T, H, _ = q.shape

    def chunks(x):                                  # [B,T,H,..] -> [B,H,N,c,..]
        x = x.astype(f32).reshape((B, T // c, c, H) + x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta)[..., None]                              # [B,H,N,c,1]
    g = jnp.cumsum(chunks(log_alpha), axis=-1)                  # [B,H,N,c]
    lower = jnp.tril(jnp.ones((c, c), bool))
    # e^(g_i - g_j) at and below the diagonal, 0 above (masked before the
    # exponential: above it the difference is positive and unbounded)
    gam = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                            -jnp.inf))
    kk = jnp.einsum("...ik,...jk->...ij", k, k, precision=_HIGHEST)
    a = jnp.where(jnp.tril(lower, -1), beta * gam * kk, 0.0)
    t = _inv_unit_lower(a)
    eg = jnp.exp(g)[..., None]
    wu = jnp.einsum("...ij,...jd->...id", t,
                    jnp.concatenate([beta * eg * k, beta * v], axis=-1),
                    precision=_HIGHEST)
    dk = k.shape[-1]
    return {"w": wu[..., :dk], "u": wu[..., dk:], "qg": eg * q,
            "kd": jnp.exp(g[..., -1:] - g)[..., None] * k,
            "p": gam * jnp.einsum("...ik,...jk->...ij", q, k,
                                  precision=_HIGHEST),
            "dec": jnp.exp(g[..., -1])}


# --------------------------------------- the chunks in order: two forms
def _scan_chunks(f, state):
    """The oracle: ``lax.scan`` over the chunks. -> (o [B,H,N,c,dv],
    final state)."""
    def one(s, x):
        vn = x["u"] - jnp.einsum("bhck,bhkv->bhcv", x["w"], s,
                                 precision=_HIGHEST)
        o = (jnp.einsum("bhck,bhkv->bhcv", x["qg"], s, precision=_HIGHEST)
             + jnp.einsum("bhcj,bhjv->bhcv", x["p"], vn, precision=_HIGHEST))
        s = x["dec"][..., None, None] * s + jnp.einsum(
            "bhck,bhcv->bhkv", x["kd"], vn, precision=_HIGHEST)
        return s, o

    state, o = jax.lax.scan(
        one, state, {n: jnp.moveaxis(x, 2, 0) for n, x in f.items()})
    return jnp.moveaxis(o, 0, 2), state


def _dot(a, b):
    """``a[h] b[h]`` for every head ``h`` ([H, m, k] x [H, k, n]) in float32
    at ``HIGHEST``. Two bfloat16 operands take the MXU's one native pass: a
    product of two bfloat16 numbers is exact in float32, so that pass IS
    the ``HIGHEST`` result of the same numbers widened (its five further
    passes would multiply zeros)."""
    exact = a.dtype == b.dtype == jnp.bfloat16
    if not exact:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                               precision=None if exact else _HIGHEST,
                               preferred_element_type=jnp.float32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _odd_blocks(x, blk: int):
    """The rows of each ``x[h]`` that lie in its odd ``blk``-row blocks,
    stacked."""
    return jnp.concatenate([x[:, i:i + blk]
                            for i in range(blk, x.shape[1], 2 * blk)], axis=1)


def _inv_unit_lower_vmem(a):
    """``_inv_unit_lower`` inside the kernel: ``(I + a[h])^-1`` for the
    strictly lower-triangular ``a`` [H, c, c] (``c`` a multiple of
    ``_SOLVE_BLOCK``, a power of two), every step for all heads at once: a
    head's steps hang on each other, the heads' do not, and the compiler
    fills one's waits with another's work.

    The diagonal blocks are laid side by side, ``d[h, r, blk b + s] = a[h,
    blk b + r, blk b + s]`` ([blk, c] a head: two vregs), and solved
    together: row ``r`` of every block's inverse is ``e_r - sum_{j<r} a[r,
    j] x_j``, taken a column ``j`` at a time (``a[., j]`` spread over its
    block's lanes by a lane gather). The blocks go back on the diagonal and
    are merged upward: ``X21 = -X22 a21 X11`` for every pair of a level at
    once, on the half of the rows a level changes (those of the odd
    blocks), ``a21`` picked out of them by a mask."""
    H, c, _ = a.shape
    blk = _SOLVE_BLOCK
    seg, off = _iota((blk, c), 1) // blk, _iota((blk, c), 1) % blk
    d = sum(jnp.where(seg == b, a[:, b * blk:(b + 1) * blk], 0.0)
            for b in range(c // blk))                            # [H, blk, c]
    x = jnp.broadcast_to(jnp.where(off == _iota((blk, c), 0), 1.0, 0.0),
                         d.shape)
    lane = _iota((H * blk, c), 1)
    first = lane - lane % blk           # a lane's block's first lane
    for j in range(blk - 1):
        # lane j of a block -> every lane of it; rows <= j of column j are 0
        cj = jnp.take_along_axis(d.reshape(H * blk, c), first + j, axis=1)
        x = x - cj.reshape(d.shape) * x[:, j:j + 1]
    x = jnp.concatenate([jnp.where(seg == b, x, 0.0)
                         for b in range(c // blk)], axis=1)      # [H, c, c]
    half = (c // 2, c)
    while blk < c:
        # the odd blocks' rows: block 2p + 1 of the level sits at p here
        left = _iota(half, 1) // blk == 2 * (_iota(half, 0) // blk)
        m = _dot(jnp.where(left, _odd_blocks(a, blk), 0.0), x)  # a21 X11
        zero = jnp.zeros((H, blk, c), jnp.float32)
        m = jnp.concatenate([y for i in range(0, c // 2, blk)
                             for y in (zero, m[:, i:i + blk])], axis=1)
        odd = _odd_blocks(x, blk)
        new = odd - _dot(odd, m)                                # [X21, X22]
        x = jnp.concatenate([y for i in range(0, c // 2, blk)
                             for y in (x[:, 2 * i:2 * i + blk],
                                       new[:, i:i + blk])], axis=1)
        blk *= 2
    return x


def _kernel(q_ref, k_ref, v_ref, g_ref, cols_ref, s0_ref, o_ref, s_ref):
    """One chunk of the block's heads of one row, every line for all heads
    at once ([H, ., .] arrays): the chunk's factors from its ``q``, ``k``
    ([H, dk, c]), ``v`` ([H, dv, c]: the tokens along the lanes, turned
    here) and per-token scalars (``g_ref`` [H, 1, c]: the running sum of
    ``log alpha`` along the lanes; ``cols_ref`` [c, 2 H]: the same sum, then
    ``beta``, a head a column), then the three lines that meet the state;
    ``o_ref`` [H, dv, c]. The state block's index does not move along the
    chunk axis, so ``s_ref`` stays in VMEM from a row's first chunk to its
    last and is written back once."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    f32 = jnp.float32
    H, _, c = q_ref.shape
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    g_row = g_ref[...]                                          # [H, 1, c]
    g = jnp.stack([cols_ref[:, h:h + 1] for h in range(H)])     # [H, c, 1]
    beta = jnp.stack([cols_ref[:, H + h:H + h + 1] for h in range(H)])
    # e^(g_i - g_j) at and below the diagonal, 0 above (masked before the
    # exponential: above it the difference is positive, unbounded)
    gam = jnp.where(col <= row,
                    jnp.exp(jnp.where(col <= row, g - g_row, 0.0)), 0.0)
    # [q; k] as it arrived (bfloat16), and [q; k] k^T from it
    qk = jnp.concatenate([jnp.swapaxes(q_ref[...], 1, 2),
                          jnp.swapaxes(k_ref[...], 1, 2)], axis=1)
    qk_kk = _dot(qk, k_ref[...])                                # [H, 2c, c]
    t = _inv_unit_lower_vmem(
        jnp.where(col < row, beta * gam * qk_kk[:, c:], 0.0))
    eg = jnp.exp(g)
    s = s_ref[...]                                              # [H, dk, dv]
    # e^g comes out of the products with the state
    qs_ks = _dot(qk, s)                                         # [H, 2c, dv]
    # v' = u - w S = T (beta v) - T (beta e^g k) S, with T taken out
    v = jnp.swapaxes(v_ref[...], 1, 2).astype(f32)
    vn = _dot(t, beta * (v - eg * qs_ks[:, c:]))                # [H, c, dv]
    o = eg * qs_ks[:, :c] + _dot(gam * qk_kk[:, :c], vn)
    o_ref[...] = jnp.swapaxes(o, 1, 2)
    g_end = g_row[:, :, c - 1:]                                 # [H, 1, 1]
    dec = jnp.exp(jnp.broadcast_to(g_end, (H, 1, s.shape[2])))
    # (e^(g_C - g) k)^T v' = k^T (e^(g_C - g) v')
    s_ref[...] = dec * s + _dot(k_ref[...], jnp.exp(g_end - g) * vn)


def _pad_to(x, axis: int, n: int):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad) if n != x.shape[axis] else x


def _prefill_vmem_bytes(hb: int, c: int, dk: int, dv: int) -> int:
    """What ``gated_delta_prefill`` holds in VMEM: a grid step's blocks
    (``q``, ``k``, ``v`` in bfloat16 and ``o`` in float32 with the chunk
    along the lanes; the state in and out, its ``dv`` rounded up to the lane
    tile), each double-buffered, and room for the float32 matrices of the
    block's heads that do not fit the registers (``[hb, c, c]``, ``[hb, c,
    dv]``)."""
    blocks = hb * (c * (2 * dk + dv) * 2 + c * dv * 4
                   + 2 * dk * -(-dv // _LANES) * _LANES * 4)
    return 2 * blocks + (8 << 20)


def _kernel_chunks(q, k, v, log_alpha, beta, state, interpret: bool):
    """The kernel over a scan of whole chunks. XLA's part: ``q``, ``k``,
    ``v`` laid out a head at a time with the tokens along the lanes
    (``[B, H, d, T]``: the layout XLA's own normalisation of ``q`` and ``k``
    reduces in, so one copy a tensor is left where ``[B, H, T, d]`` takes
    two), and the per-token scalars (the running sum of ``log alpha`` inside
    each chunk, ``beta``) in the two layouts the kernel reads them in.
    -> (o [B,H,dv,T], final state)."""
    B, T, H, dk = q.shape
    dv, c, f32 = v.shape[-1], CHUNK, jnp.float32
    N = T // c
    hb = max(h for h in range(1, _HEAD_BLOCK + 1) if H % h == 0)
    g = jnp.cumsum(log_alpha.astype(f32).reshape(B, N, c, H), axis=2)
    g_rows = jnp.moveaxis(g.reshape(B, N, c, H // hb, hb, 1), 2, 5)
    cols = jnp.concatenate([g.reshape(B, T, H // hb, hb),
                            beta.astype(f32).reshape(B, T, H // hb, hb)], -1)

    def tokens(d):
        return pl.BlockSpec((None, hb, d, c), lambda b, h, n: (b, h, 0, n))

    whole = pl.BlockSpec((None, hb, dk, dv), lambda b, h, n: (b, h, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(B, H // hb, N),
        in_specs=[tokens(dk), tokens(dk), tokens(dv),
                  pl.BlockSpec((None, None, None, hb, 1, c),
                               lambda b, h, n: (b, n, h, 0, 0, 0)),
                  pl.BlockSpec((None, None, c, 2 * hb),
                               lambda b, h, n: (b, h, n, 0)),
                  whole],
        out_specs=[tokens(dv), whole],
        out_shape=[jax.ShapeDtypeStruct((B, H, dv, T), f32),
                   jax.ShapeDtypeStruct((B, H, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_prefill_vmem_bytes(hb, c, dk, dv)),
        name="gated_delta_prefill",
        interpret=interpret,
    )(*(jnp.transpose(x, (0, 2, 3, 1)) for x in (q, k, v)), g_rows,
      jnp.moveaxis(cols, 2, 1), state)


def prefill_scan(q, k, v, log_alpha, beta, state, kernel=None):
    """The rule over ``T`` tokens a row, chunked. ``q``, ``k`` [B,T,H,dk],
    ``v`` [B,T,H,dv], ``log_alpha``, ``beta`` [B,T,H], ``state``
    [B,H,dk,dv] float32 -> (o [B,T,H,dv] float32, final state). A position
    with ``log_alpha = 0`` and ``beta = 0`` leaves the state untouched (its
    ``o`` is read by nobody); ``T`` need not be a multiple of the chunk.
    ``kernel``: None asks ``prefill_engages``; True / False force the Pallas
    kernel (interpreted off the TPU; its chunk is always ``CHUNK``, a
    shorter scan padded up to it) / the ``lax.scan``."""
    B, T, H, _ = q.shape
    c = min(CHUNK, T)
    if kernel is None:
        kernel = prefill_engages(-(-T // c) * c)
    if kernel:
        c = CHUNK
    Tp = -(-T // c) * c
    q, k, v, log_alpha, beta = (_pad_to(x, 1, Tp)
                                for x in (q, k, v, log_alpha, beta))
    state = state.astype(jnp.float32)
    if kernel:
        o, state = _kernel_chunks(q, k, v, log_alpha, beta, state,
                                  interpret=jax.default_backend() != "tpu")
        o = jnp.transpose(o, (0, 3, 1, 2))                      # [B,T,H,dv]
    else:
        o, state = _scan_chunks(_factors(q, k, v, log_alpha, beta, c), state)
        o = jnp.moveaxis(o, 1, 3).reshape(B, Tp, H, -1)
    return o[:, :T], state
