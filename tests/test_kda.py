"""``ops/kda.py``: the delta rule with a decay a CHANNEL. The chunked scan's
two carriers (the ``lax.scan`` over XLA-built chunks and the Pallas kernel
``kda_prefill``, interpreted here) and the decode step's kernel
(``kda_step``) against the token recurrence, which is the rule as written.

Tolerance. Everything is float32 at the highest matmul precision, so the
forms differ by summation order, by the triangular solve and by the kernel's
references alone: outputs and states agree to 2e-5 of values of order 0.1-1
over 256-400 tokens (read: 3e-7 to 1e-5). A bfloat16 state, a scalar decay or
a reference on the wrong side of a pair moves them by 1e-3 to 1
(``test_what_the_tolerance_refuses``). The kernels are COMPILED for a
described v5e at the cell's shapes in ``tests/test_decode_attention.py`` (the
one file that loads the TPU's compiler).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetorch_tpu.ops import kda

TOL = 2e-5


def unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def inputs(B, T, H, dk, dv, decay="mixed", seed=0, dtype=jnp.float32):
    """q, k unit vectors (q scaled), v, the log decay a channel, beta in
    (0, 1), a random state. ``decay``: ``mixed`` (every channel somewhere in
    (-5, 0)), ``floor`` (every channel at the lower bound, every token),
    ``none`` (every channel at ~0: nothing is forgotten)."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    a = {"mixed": kda.LOG_DECAY_FLOOR * jax.nn.sigmoid(
        3 * jax.random.normal(ks[3], (B, T, H, dk)) - 1),
        "floor": jnp.full((B, T, H, dk), kda.LOG_DECAY_FLOOR),
        "none": jnp.full((B, T, H, dk), -1e-6)}[decay]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    state = jax.random.normal(ks[5], (B, H, dk, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), a, beta,
            state)


def scan(kernel):
    return jax.jit(lambda *a: kda.prefill_scan(*a, kernel=kernel))


# the published head shape (one chunk and a half more: 3 chunks of the
# kernel, 4 of the XLA form with the last one padded) and a small one whose
# length no chunk divides
SHAPES = {"published": (2, 320, 2, 128, 128), "small": (1, 200, 2, 8, 16)}


@pytest.mark.level("unit")
@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("decay", ["mixed", "floor", "none"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_chunked_scan_equals_the_recurrence(shape, decay, kernel):
    """Both carriers, at both ends of the decay: every channel at the lower
    bound for whole chunks (``e^(-5 x 128)`` a chunk: a factor taken against
    the chunk's start would be 0 or inf) and every channel at ~0 (every pair
    of a chunk counts, across all three levels of references)."""
    args = inputs(*SHAPES[shape], decay=decay)
    o, s = scan(kernel)(*args)
    o_ref, s_ref = jax.jit(kda.recurrence)(*args)
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.asarray(o_ref)).max() > 0.02
    assert np.abs(np.asarray(o - o_ref)).max() < TOL
    assert np.abs(np.asarray(s - s_ref)).max() < TOL


@pytest.mark.level("unit")
def test_what_the_tolerance_refuses():
    args = inputs(1, 256, 2, 128, 128)
    q, k, v, a, beta, state = args
    o_ref, s_ref = jax.jit(kda.recurrence)(*args)

    def gap(o, s):
        return max(np.abs(np.asarray(o - o_ref)).max(),
                   np.abs(np.asarray(s - s_ref)).max())

    def rounded(state, tok):
        o, state = kda.step(*tok, state)
        return jax.lax.reduce_precision(state, 8, 7), o

    s16, o16 = jax.lax.scan(rounded, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, beta)))
    scalar = jnp.broadcast_to(jnp.mean(a, -1, keepdims=True), a.shape)
    refused = {
        "a bfloat16 state": gap(jnp.moveaxis(o16, 0, 1), s16),
        "a scalar decay": gap(*scan(True)(q, k, v, scalar, beta, state)),
        "bfloat16 q, k, v": gap(*scan(True)(
            *(x.astype(jnp.bfloat16) for x in (q, k, v)), a, beta, state)),
    }
    assert all(g > 50 * TOL for g in refused.values()), refused


@pytest.mark.level("unit")
@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_segments_that_hand_the_state_on_are_the_one_scan(kernel):
    q, k, v, a, beta, state = inputs(1, 384, 2, 128, 128, seed=3)
    o, s = scan(kernel)(q, k, v, a, beta, state)
    outs = []
    for at in (0, 128, 256):
        part, state = scan(kernel)(*(x[:, at:at + 128]
                                     for x in (q, k, v, a, beta)), state)
        outs.append(part)
    assert np.abs(np.asarray(jnp.concatenate(outs, 1) - o)).max() < TOL
    assert np.abs(np.asarray(state - s)).max() < TOL


@pytest.mark.level("unit")
@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_a_padded_position_holds_the_state_bit_for_bit(kernel):
    """``a = 0, beta = 0``: a scan over nothing but padding returns the
    state it was given, every bit of it; real tokens followed by padding end
    where the real tokens ended."""
    q, k, v, a, beta, state = inputs(2, 256, 2, 128, 128, seed=5)
    _, held = scan(kernel)(q, k, v, jnp.zeros_like(a), jnp.zeros_like(beta),
                           state)
    assert (np.asarray(held) == np.asarray(state)).all()
    real = (jnp.arange(256) < 100)[None, :, None]
    _, padded = scan(kernel)(q, k, v, jnp.where(real[..., None], a, 0.0),
                             jnp.where(real, beta, 0.0), state)
    _, short = scan(kernel)(*(x[:, :100] for x in (q, k, v, a, beta)), state)
    assert np.abs(np.asarray(padded - short)).max() < TOL
    o, one = jax.jit(kda.step)(q[:, 0], k[:, 0], v[:, 0],
                               jnp.zeros_like(a[:, 0]),
                               jnp.zeros_like(beta[:, 0]), state)
    assert (np.asarray(one) == np.asarray(state)).all()


@pytest.mark.level("unit")
@pytest.mark.parametrize("live", [(True, False, True, True), (False,) * 4,
                                  (True,) * 4], ids=["some", "none", "all"])
@pytest.mark.parametrize("shape", [(4, 128, 128), (3, 8, 16)],
                         ids=["published", "small"])
def test_step_rows_equals_step_and_leaves_idle_rows_untouched(shape, live):
    """The kernel on layer 1 of a stacked leaf of three, in place: the
    decoding rows as ``step`` gives them, every other row and every other
    layer bit for bit as they were, an idle row's output zeros."""
    H, dk, dv = shape
    B, L = len(live), 3
    q, k, v, a, beta, _ = inputs(B, 1, H, dk, dv, seed=7)
    states = jax.random.normal(jax.random.key(9), (L, B, H, dk, dv))
    live = jnp.asarray(live)
    o, new = jax.jit(lambda *x: kda.step_rows(
        *x, 1, kda.step_plan(live)))(q[:, 0], k[:, 0], v[:, 0], a[:, 0],
                                     beta[:, 0], states)
    o_ref, s_ref = kda.step(q[:, 0], k[:, 0], v[:, 0], a[:, 0], beta[:, 0],
                            states[1])
    rows = np.asarray(live)
    assert np.abs(np.asarray(o - o_ref))[rows].max(initial=0) < TOL
    assert np.abs(np.asarray(new[1] - s_ref))[rows].max(initial=0) < TOL
    assert (np.asarray(new[1])[~rows] == np.asarray(states[1])[~rows]).all()
    assert (np.asarray(o)[~rows] == 0).all()
    assert (np.asarray(new[0]) == np.asarray(states[0])).all()
    assert (np.asarray(new[2]) == np.asarray(states[2])).all()
