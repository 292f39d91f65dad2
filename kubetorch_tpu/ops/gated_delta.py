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
  Everything but those three lines is independent of the state and is made
  for all chunks at once in XLA (``_factors``; the triangular inverse by
  forward substitution in 16-row blocks, merged by products: a Neumann
  series loses digits where ``beta k.k`` nears 2). The three lines, which
  have to follow the chunks in order, are the Pallas kernel
  ``gated_delta_prefill`` on one TPU device (the state stays in VMEM across
  a row's chunks, ``_HEAD_BLOCK`` heads a grid step) and a ``lax.scan``
  elsewhere, the kernel's oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128          # tokens a chunk: every matrix of the kernel lane-aligned
_LANES = 128
_SOLVE_BLOCK = 16    # rows solved by substitution before blocks are merged
_HEAD_BLOCK = 6      # most heads a grid step of the kernel takes
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


def _kernel(w_ref, u_ref, qg_ref, kdt_ref, p_ref, dec_ref, s0_ref, o_ref,
            s_ref, *, heads: int):
    """One chunk of ``heads`` heads of one row. The state block's index does
    not move along the chunk axis, so ``s_ref`` stays in VMEM from a row's
    first chunk to its last and is written back once."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32,
                       precision=_HIGHEST)

    for h in range(heads):
        s = s_ref[h]                                            # [dk, dv]
        vn = u_ref[h] - dot(w_ref[h], s)                        # [c, dv]
        o_ref[h] = dot(qg_ref[h], s) + dot(p_ref[h], vn)
        s_ref[h] = dec_ref[h] * s + dot(kdt_ref[h], vn)


def _pad_to(x, axis: int, n: int):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad) if n != x.shape[axis] else x


def _kernel_chunks(f, state, interpret: bool):
    """The kernel over the factors: head sizes padded to the lane tile
    (zeros: a padded key column meets a zero state row), ``kd`` handed over
    transposed so that every product is a plain ``[m,k] x [k,n]``."""
    B, H, N, c, dk = f["w"].shape
    dv = f["u"].shape[-1]
    dkp, dvp = -(-dk // _LANES) * _LANES, -(-dv // _LANES) * _LANES
    hb = max(h for h in range(1, _HEAD_BLOCK + 1) if H % h == 0)
    w, qg = _pad_to(f["w"], -1, dkp), _pad_to(f["qg"], -1, dkp)
    kdt = _pad_to(f["kd"], -1, dkp).swapaxes(-1, -2)            # [..,dkp,c]
    u = _pad_to(f["u"], -1, dvp)
    dec = jnp.broadcast_to(f["dec"][..., None, None], (B, H, N, 1, dvp))
    s0 = _pad_to(_pad_to(state, -1, dvp), -2, dkp)

    def chunked(*tail):
        return pl.BlockSpec((None, hb, None) + tail,
                            lambda b, h, n: (b, h, n, 0, 0))

    whole = pl.BlockSpec((None, hb, dkp, dvp), lambda b, h, n: (b, h, 0, 0))
    o, s = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        grid=(B, H // hb, N),
        in_specs=[chunked(c, dkp), chunked(c, dvp), chunked(c, dkp),
                  chunked(dkp, c), chunked(c, c), chunked(1, dvp), whole],
        out_specs=[chunked(c, dvp), whole],
        out_shape=[jax.ShapeDtypeStruct((B, H, N, c, dvp), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, dkp, dvp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="gated_delta_prefill",
        interpret=interpret,
    )(w, u, qg, kdt, f["p"], dec, s0)
    return o[..., :dv], s[..., :dk, :dv]


def prefill_scan(q, k, v, log_alpha, beta, state, kernel=None):
    """The rule over ``T`` tokens a row, chunked. ``q``, ``k`` [B,T,H,dk],
    ``v`` [B,T,H,dv], ``log_alpha``, ``beta`` [B,T,H], ``state``
    [B,H,dk,dv] float32 -> (o [B,T,H,dv] float32, final state). A position
    with ``log_alpha = 0`` and ``beta = 0`` leaves the state untouched (its
    ``o`` is read by nobody); ``T`` need not be a multiple of the chunk.
    ``kernel``: None asks ``prefill_engages``; True / False force the Pallas
    kernel (interpreted off the TPU) / the ``lax.scan``."""
    B, T, H, _ = q.shape
    c = min(CHUNK, T)
    Tp = -(-T // c) * c
    if kernel is None:
        kernel = prefill_engages(Tp)
    f = _factors(*(_pad_to(x, 1, Tp) for x in (q, k, v, log_alpha, beta)), c)
    state = state.astype(jnp.float32)
    if kernel:
        o, state = _kernel_chunks(f, state,
                                  interpret=jax.default_backend() != "tpu")
    else:
        o, state = _scan_chunks(f, state)
    o = jnp.moveaxis(o, 1, 3).reshape(B, Tp, H, -1)             # [B,T,H,dv]
    return o[:, :T], state
