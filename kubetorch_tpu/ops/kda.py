"""The delta rule whose state decays a CHANNEL (KDA, Kimi Delta Attention,
arXiv:2510.26692): the recurrent layers of ``models/hybrid_latent_moe.py``.

A head keeps ``S`` of ``dk x dv`` (float32). A token with key ``k`` (unit
norm), value ``v``, query ``q``, step ``beta`` in [0, 1] and a decay
``alpha`` in (0, 1]^dk, one number a KEY CHANNEL (a row of ``S``), given as
``log alpha`` (``a``, never under ``LOG_DECAY_FLOOR`` a token):

    S <- (I - beta k k^T) Diag(alpha) S + beta k v^T        o = S^T q

``ops/gated_delta.py`` is the same rule with ONE decay a head; there the
decay is a scalar that leaves every dot product, here it sits inside them.
``a = 0, beta = 0`` leaves ``S`` as it was, bit for bit: that is how a padded
position of a bucket and a row that is not decoding pass through.

Four forms of the one rule, as ``ops/gated_delta.py`` has them:

- ``step``: one token a row in XLA (every row of the grid, an idle one held
  by ``a = 0, beta = 0``): the oracle of ``step_rows`` and the decode step
  wherever that does not engage.
- ``step_rows``: the same step as the Pallas kernel ``kda_step`` on one TPU
  device, over the stacked state leaf ``[L, B, H, dk, dv]`` in place, its
  grid the rows that decode (``gated_delta.step_plan``'s work list): a row
  that does not decode is neither fetched nor written.
- ``recurrence``: ``step`` over the tokens in order, the tests' oracle.
- ``prefill_scan``: the chunked WY form of an admission. With ``G`` the
  running sum of ``a`` inside a chunk (a vector of ``dk`` a token),

      A[i,j] = beta_i sum_c k_i[c] k_j[c] e^(G_i[c] - G_j[c])    (j < i)
      P[i,j] =        sum_c q_i[c] k_j[c] e^(G_i[c] - G_j[c])    (j <= i)

  ``T = (I + A)^-1``, ``v' = T (beta (v - (e^G k) S))``, ``o = (e^G q) S +
  P v'``, ``S <- Diag(e^(G_C)) S + (e^(G_C - G) k)^T v'``. **The factor
  cannot leave the dot product, and ``e^(G_i)`` and ``e^(-G_j)`` apart leave
  float32** (a chunk of 128 tokens at the floor spans ``e^640``), so every
  pair ``(i, j)`` is referenced to a position BETWEEN the two: the chunk is
  halved three times down to sub-blocks of ``_SUB`` = 16 tokens; a pair
  whose two tokens part at a level takes the running sum at the END of the
  left half as its reference ``R``, so ``k_i e^(G_i - R)`` and ``k_j e^(R -
  G_j)`` are both at most 1 (a factor that underflows belongs to a product
  that is under ``1e-38`` anyway). Rows are scaled where they lie in a right
  half and zeroed elsewhere, columns the other way round: a level is one
  matrix product, right for the pairs that part at it, and a pair takes the
  level it parts at by a select on its two positions. Inside a sub-block both
  tokens take the sub-block's MIDDLE as reference: either factor stays
  within ``e^(+-8 x 5) = e^(+-40)`` and the product of two of them that are
  no pair (masked out afterwards) within ``e^80 < 3.4e38``, which is what
  the floor of -5 a token beside 16 buys. Two carriers of the one form:

  - On one TPU device, over a scan the chunk divides with head sizes of
    whole lane tiles: the Pallas kernel ``kda_prefill``, a grid step a (row,
    block of ``_HEAD_BLOCK`` heads, chunk), every line written for all heads
    of the block at once (``[H, ., .]`` arrays: a process traces every
    bucket at start-up). It reads ``q | k | v`` as the mixer holds them
    (``[B, T, H x d]``: a head is a lane-aligned slice of a block, so XLA
    lays nothing out again), the running sums ``G`` (XLA: a cumulative sum
    inside each chunk) and ``beta``; the state block stays in VMEM from a
    row's first chunk to its last.
  - Elsewhere (the CPU, a mesh, a scan the chunk does not divide): a
    ``lax.scan`` over chunks of ``_XLA_CHUNK`` tokens that forms ``e^(G_i -
    G_j)`` a pair a channel outright (the difference is taken before the
    exponential, so nothing needs a reference): the kernel's oracle, and
    ``[B, H, c, c, dk]`` float32 a chunk, which is why it is no serving path
    at the published widths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubetorch_tpu.ops.gated_delta import (_STEP_VMEM, _dot,
                                           _inv_unit_lower,
                                           _inv_unit_lower_vmem, _iota,
                                           _one_tpu_device, _pad_to,
                                           _step_vmem_bytes, step_plan)

CHUNK = 128          # tokens a chunk of the kernel
_XLA_CHUNK = 64      # tokens a chunk of the XLA form ([c, c, dk] a head)
_SUB = 16            # tokens a sub-block: both of a pair's factors apart
_LANES = 128
_HEAD_BLOCK = 4      # most heads a grid step of the prefill kernel takes
_HIGHEST = jax.lax.Precision.HIGHEST
# the least ``log alpha`` a token the chunked form is safe for: a sub-block's
# span ``_SUB * 5 = 80`` keeps ``e^80`` inside float32 (``e^88.7``)
LOG_DECAY_FLOOR = -5.0

_PREFILL_VMEM = 96 << 20

# Test hook, as ``gated_delta._FORCE_INTERPRET``: take the kernels (in
# interpret mode) wherever ``prefill_engages`` / ``step_engages`` are asked.
_FORCE_INTERPRET = False

__all__ = ["CHUNK", "LOG_DECAY_FLOOR", "step", "step_plan", "step_rows",
           "recurrence", "prefill_scan", "prefill_engages", "step_engages",
           "scan_positions"]


def prefill_engages(t: int, dk: int, dv: int) -> bool:
    """The kernel takes a scan whose length its chunk divides, on one TPU
    device, where a head is whole lane tiles; the ``lax.scan`` the rest."""
    if t % CHUNK:
        return False
    return _FORCE_INTERPRET or (dk % _LANES == 0 and dv % _LANES == 0
                                and _one_tpu_device())


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
    """One token a row. ``q``, ``k``, ``log_alpha`` [B,H,dk], ``v``
    [B,H,dv], ``beta`` [B,H], ``state`` [B,H,dk,dv] float32 -> (o [B,H,dv]
    float32, new state)."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    s = jnp.exp(log_alpha.astype(f32))[..., None] * state
    # S^T k and S^T q in one pass over the decayed state
    r = jnp.einsum("bhjk,bhkv->bhjv", jnp.stack([k, q], axis=2), s,
                   precision=_HIGHEST)
    u = beta.astype(f32)[..., None] * (v - r[:, :, 0])
    new = s + k[..., :, None] * u[..., None, :]
    o = r[:, :, 1] + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, new


def _step_kernel(li_ref, rows_ref, n_ref, beta_ref, kq_ref, kqa_ref, v_ref,
                 s0_ref, o0_ref, o_ref, s_ref):
    """Grid step ``j``: the ``j``-th decoding row's state block of layer
    ``li``, every head of it. ``beta_ref``, ``kq_ref`` (SMEM, [B * H]) hold
    the step and ``k.q`` of every (row, head); ``kqa_ref`` [dk, 3H] the
    row's keys, queries and decays as columns; ``v_ref`` [H, dv].
    ``o0_ref`` is the zeros the output aliases (never read)."""
    del li_ref, o0_ref
    heads = v_ref.shape[0]
    j = pl.program_id(0)
    n = n_ref[0]

    @pl.when(j < n)
    def _decodes():
        base = rows_ref[j] * heads
        for h in range(heads):
            beta = beta_ref[base + h]
            k = kqa_ref[:, h:h + 1]                             # [dk, 1]
            q = kqa_ref[:, heads + h:heads + h + 1]
            s = kqa_ref[:, 2 * heads + h:2 * heads + h + 1] * s0_ref[h]
            r_k = jnp.sum(s * k, axis=0, keepdims=True)         # [1, dv]
            r_q = jnp.sum(s * q, axis=0, keepdims=True)
            u = beta * (v_ref[h:h + 1, :] - r_k)
            s_ref[h] = s + k * u
            o_ref[h:h + 1, :] = r_q + kq_ref[base + h] * u

    # no row decodes: the one block the grid holds goes back as it came
    @pl.when(jnp.logical_and(n == 0, j == 0))
    def _held():
        s_ref[...] = s0_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def step_rows(q, k, v, log_alpha, beta, states, layer, plan):
    """``step`` for the rows of ``plan`` (``step_plan``) on layer ``layer``
    of the stacked leaf ``states`` [L,B,H,dk,dv] float32, in place -> (o
    [B,H,dv] float32, the leaf). A row outside the plan keeps its state bit
    for bit, in every layer, and its ``o`` is zeros. Off the TPU the kernel
    is interpreted."""
    _, B, H, dk, dv = states.shape
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    rows, n = plan
    kqa = jnp.concatenate([k, q, jnp.exp(log_alpha.astype(f32))],
                          axis=1).swapaxes(1, 2)                # [B,dk,3H]

    def row(*tail):                 # a [B, *tail] operand, a row a step
        return pl.BlockSpec(
            (None,) + tail,
            lambda j, li, rows, *_: (rows[j],) + (0,) * len(tail))

    block = pl.BlockSpec((None, None, H, dk, dv),
                         lambda j, li, rows, *_: (li[0], rows[j], 0, 0, 0))
    o, states = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(B,),
            in_specs=[row(dk, 3 * H), row(H, dv), block,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[row(H, dv), block]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        # operands count from the scalar-prefetched ones
        input_output_aliases={7: 1, 8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_step_vmem_bytes(H, dk, dv)),
        name="kda_step",
        interpret=jax.default_backend() != "tpu",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows, n,
      beta.astype(f32).ravel(), jnp.sum(k * q, axis=-1).ravel(), kqa, v,
      states, jnp.zeros((B, H, dv), f32))
    return o, states


def recurrence(q, k, v, log_alpha, beta, state):
    """``step`` over ``T`` tokens in order. ``q``, ``k``, ``log_alpha``
    [B,T,H,dk], ``v`` [B,T,H,dv], ``beta`` [B,T,H] -> (o [B,T,H,dv] float32,
    final state)."""
    def one(state, tok):
        o, state = step(*tok, state)
        return state, o

    state, o = jax.lax.scan(
        one, state.astype(jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_alpha, beta)))
    return jnp.moveaxis(o, 0, 1), state


# ------------------------------------------------- the chunks in XLA
def _scan_chunks(q, k, v, log_alpha, beta, state, c: int):
    """The oracle: ``lax.scan`` over chunks of ``c`` tokens, a chunk's
    matrices made inside its step with ``e^(G_i - G_j)`` formed a pair a
    channel. Inputs [B,T,H,*], ``T`` a multiple of ``c`` -> (o [B,T,H,dv],
    final state)."""
    f32 = jnp.float32
    B, T, H, _ = q.shape

    def chunks(x):                              # [B,T,H,..] -> [N,B,H,c,..]
        x = x.astype(f32).reshape((B, T // c, c, H) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    lower = jnp.tril(jnp.ones((c, c), bool))

    def one(s, x):
        q, k, v, a, beta = x
        beta = beta[..., None]                                  # [B,H,c,1]
        g = jnp.cumsum(a, axis=2)                               # [B,H,c,dk]
        # masked before the exponential: above the diagonal the difference
        # is positive and unbounded
        gam = jnp.exp(jnp.where(lower[..., None],
                                g[..., :, None, :] - g[..., None, :, :],
                                -jnp.inf))                      # [B,H,c,c,dk]
        kg = k[..., None, :, :] * gam                           # k_j e^(..)
        kk = jnp.einsum("bhik,bhijk->bhij", k, kg, precision=_HIGHEST)
        p = jnp.einsum("bhik,bhijk->bhij", q, kg, precision=_HIGHEST)
        t = _inv_unit_lower(jnp.where(jnp.tril(lower, -1), beta * kk, 0.0))
        eg = jnp.exp(g)
        vn = jnp.einsum("bhij,bhjv->bhiv", t, beta * (v - jnp.einsum(
            "bhck,bhkv->bhcv", eg * k, s, precision=_HIGHEST)),
            precision=_HIGHEST)
        o = (jnp.einsum("bhck,bhkv->bhcv", eg * q, s, precision=_HIGHEST)
             + jnp.einsum("bhij,bhjv->bhiv", p, vn, precision=_HIGHEST))
        end = g[..., -1:, :]                                    # [B,H,1,dk]
        s = (jnp.swapaxes(jnp.exp(end), 2, 3) * s + jnp.einsum(
            "bhck,bhcv->bhkv", jnp.exp(end - g) * k, vn, precision=_HIGHEST))
        return s, o

    state, o = jax.lax.scan(
        one, state, tuple(chunks(x) for x in (q, k, v, log_alpha, beta)))
    # [N,B,H,c,dv] -> [B,T,H,dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)
    return o.reshape(B, T, H, -1), state


# ------------------------------------------------- the chunks' kernel
def _dot_nt(a, b):
    """``a[h] b[h]^T`` for every head ([H, m, k] x [H, n, k]), float32 at
    ``HIGHEST``."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((2,), (2,)), ((0,), (0,))), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def _spread(rows, n: int):
    """``rows`` ([H, 1, d] each) with every one repeated down ``n`` rows."""
    return jnp.concatenate(
        [jnp.broadcast_to(r, (r.shape[0], n, r.shape[2])) for r in rows],
        axis=1)


def _pair_products(qk, k, g):
    """``sum_c x_i[c] k_j[c] e^(G_i[c] - G_j[c])`` for the rows ``x`` of
    ``qk`` ([H, 2c, dk]: the chunk's queries, then its keys), the keys ``k``
    [H, c, dk] and the running sums ``g`` [H, c, dk], float32 -> [H, 2c, c]:
    valid at and below each sub-block's diagonal and everywhere left of the
    sub-block, zero right of it (the module docstring has the references)."""
    H, c, dk = k.shape
    at = _iota((c, dk), 0)
    both = lambda x: jnp.concatenate([x, x], axis=1)

    # inside a sub-block: both tokens against the sub-block's MIDDLE, so
    # that neither factor leaves [e^-40, e^40] (against its start the row's
    # would fall to e^-80, where a small q or k is no longer a normal number)
    mid = _SUB // 2 - 1
    d = g - _spread([g[:, s + mid:s + mid + 1] for s in range(0, c, _SUB)],
                    _SUB)
    own = _dot_nt(qk * both(jnp.exp(d)), k * jnp.exp(-d))
    # across sub-blocks: a level a halving, the reference the end of the
    # left half; rows count in a right half, columns in a left one. A
    # level's product is right where the pair parts AT that level (zero
    # where it has not parted yet, anything where it parted lower down), so
    # the levels are chosen from the widest down
    row, col = _iota((2 * c, c), 0) % c, _iota((2 * c, c), 1)
    out = None
    half = c // 2
    while half >= _SUB:
        ref = _spread([g[:, e + half - 1:e + half]
                       for e in range(0, c, 2 * half)], 2 * half)
        right = (at // half) % 2 == 1
        d = g - ref
        far = _dot_nt(
            qk * both(jnp.where(right, jnp.exp(jnp.where(right, d, 0.0)),
                                0.0)),
            k * jnp.where(right, 0.0, jnp.exp(jnp.where(right, 0.0, -d))))
        out = far if out is None else jnp.where(
            row // (2 * half) == col // (2 * half), far, out)
        half //= 2
    sub = row // _SUB == col // _SUB
    return jnp.where(sub, own, 0.0 if out is None else out)


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_ref):
    """One chunk of the block's heads of one row, every line for all heads
    at once. ``q_ref``, ``k_ref``, ``g_ref`` [c, H dk] and ``v_ref`` [c, H
    dv] as the mixer holds them (a head a lane slice), ``g`` the running sum
    of ``log alpha`` inside the chunk; ``beta_ref`` [c, H]; ``o_ref`` [c, H
    dv]. The state block's index does not move along the chunk axis, so
    ``s_ref`` stays in VMEM from a row's first chunk to its last."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        s_ref[...] = s0_ref[...]

    f32 = jnp.float32
    H, dk, dv = s_ref.shape
    c = q_ref.shape[0]

    def heads(ref, d):                          # [c, H d] -> [H, c, d]
        return jnp.stack([ref[:, h * d:(h + 1) * d] for h in range(H)])

    q, k = heads(q_ref, dk).astype(f32), heads(k_ref, dk).astype(f32)
    v, g = heads(v_ref, dv).astype(f32), heads(g_ref, dk)
    beta = jnp.stack([beta_ref[:, h:h + 1] for h in range(H)])  # [H, c, 1]
    qk = jnp.concatenate([q, k], axis=1)                        # [H, 2c, dk]
    pairs = _pair_products(qk, k, g)                            # [H, 2c, c]
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    t = _inv_unit_lower_vmem(jnp.where(col < row, beta * pairs[:, c:], 0.0))
    p = jnp.where(col <= row, pairs[:, :c], 0.0)
    eg = jnp.exp(g)
    s = s_ref[...]                                              # [H, dk, dv]
    qs_ks = _dot(qk * jnp.concatenate([eg, eg], axis=1), s)     # [H, 2c, dv]
    vn = _dot(t, beta * (v - qs_ks[:, c:]))                     # [H, c, dv]
    o = qs_ks[:, :c] + _dot(p, vn)
    for h in range(H):
        o_ref[:, h * dv:(h + 1) * dv] = o[h]
    end = g[:, c - 1:c]                                         # [H, 1, dk]
    # the state's rows decay by e^(G_C): a row of ``end`` turned to a column
    dec = jnp.swapaxes(jnp.broadcast_to(jnp.exp(end), (H, dv, dk)), 1, 2)
    s_ref[...] = dec * s + _dot(jnp.swapaxes(k * jnp.exp(end - g), 1, 2), vn)


def _kernel_chunks(q, k, v, log_alpha, beta, state, interpret: bool):
    """The kernel over a scan of whole chunks. XLA's part: the running sum
    of ``log alpha`` inside each chunk and ``beta`` a head block. Inputs
    [B,T,H,*] -> (o [B,T,H,dv], final state)."""
    B, T, H, dk = q.shape
    dv, c, f32 = v.shape[-1], CHUNK, jnp.float32
    N = T // c
    hb = max(h for h in range(1, _HEAD_BLOCK + 1) if H % h == 0)
    g = jnp.cumsum(log_alpha.astype(f32).reshape(B, N, c, H * dk), axis=2)
    beta = jnp.moveaxis(beta.astype(f32).reshape(B, T, H // hb, hb), 2, 1)

    def tokens(d):
        return pl.BlockSpec((None, c, hb * d), lambda b, h, n: (b, n, h))

    whole = pl.BlockSpec((None, hb, dk, dv), lambda b, h, n: (b, h, 0, 0))
    o, state = pl.pallas_call(
        _kernel,
        grid=(B, H // hb, N),
        in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk),
                  pl.BlockSpec((None, None, c, hb),
                               lambda b, h, n: (b, h, n, 0)),
                  whole],
        out_specs=[tokens(dv), whole],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * dv), f32),
                   jax.ShapeDtypeStruct((B, H, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM),
        name="kda_prefill",
        interpret=interpret,
    )(q.reshape(B, T, H * dk), k.reshape(B, T, H * dk),
      v.reshape(B, T, H * dv), g.reshape(B, T, H * dk), beta, state)
    return o.reshape(B, T, H, dv), state


def prefill_scan(q, k, v, log_alpha, beta, state, kernel=None):
    """The rule over ``T`` tokens a row, chunked. ``q``, ``k``,
    ``log_alpha`` [B,T,H,dk], ``v`` [B,T,H,dv], ``beta`` [B,T,H], ``state``
    [B,H,dk,dv] float32 -> (o [B,T,H,dv] float32, final state).
    ``log_alpha`` must not lie under ``LOG_DECAY_FLOOR``. A position with
    ``log_alpha = 0`` and ``beta = 0`` leaves the state untouched (its ``o``
    is read by nobody); ``T`` need not be a multiple of the chunk; a scan
    cut in segments that hand the state on is the one scan. ``kernel``:
    None asks ``prefill_engages``; True / False force the Pallas kernel
    (interpreted off the TPU; a shorter scan padded up to ``CHUNK``) / the
    ``lax.scan``."""
    T = q.shape[1]
    if kernel is None:
        kernel = prefill_engages(T, q.shape[-1], v.shape[-1])
    c = CHUNK if kernel else min(_XLA_CHUNK, T)
    Tp = -(-T // c) * c
    q, k, v, log_alpha, beta = (_pad_to(x, 1, Tp)
                                for x in (q, k, v, log_alpha, beta))
    state = state.astype(jnp.float32)
    if kernel:
        o, state = _kernel_chunks(q, k, v, log_alpha, beta, state,
                                  interpret=jax.default_backend() != "tpu")
    else:
        o, state = _scan_chunks(q, k, v, log_alpha, beta, state, c)
    return o[:, :T], state
