"""The gated delta rule (``ops/gated_delta.py``): its chunked form, as the
``lax.scan`` over chunks of XLA-made factors and as the Pallas kernel that
builds a chunk's factors itself (interpret mode), against the rule itself
one token at a time; and the decode step's kernel over the stacked state
leaf (``step_rows``, interpret mode) against the XLA ``step``.

Tolerance. All three are float32 sums of the same products in different
orders; the chunked forms also invert a unit triangular matrix a chunk
(forward substitution, exact in exact arithmetic), the ``lax.scan`` form in
XLA and the kernel inside itself. Outputs are O(1); they agree to ~2e-6 on
these seeds, and 2e-5 is held. What the chunking could get wrong (a decay
applied on the wrong side of a token, the diagonal left out of the
intra-chunk product, a chunk boundary's state) moves outputs by 1e-2 or
more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetorch_tpu.ops import gated_delta as gd

TOL = 2e-5
B, H, DK, DV = 2, 4, 8, 16


def draw(T, seed=0, b=B, h=H, dk=DK, dv=DV):
    """Inputs as the layer makes them: unit keys with a common positive
    part (SiLU's), queries of norm dk^-1/2, decays from A ~ U(0, 16), beta
    in (0, 2)."""
    ks = jax.random.split(jax.random.key(seed), 7)
    q = jax.random.normal(ks[0], (b, T, h, dk))
    k = jax.nn.silu(jax.random.normal(ks[1], (b, T, h, dk)))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, T, h, dv))
    log_alpha = -16 * jax.random.uniform(ks[3], (b, T, h)) * jax.nn.softplus(
        jax.random.normal(ks[4], (b, T, h)) - 4)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[5], (b, T, h)))
    state = jax.random.normal(ks[6], (b, h, dk, dv))
    return q, k, v, log_alpha, beta, state


def close(a, b):
    return float(jnp.abs(a - b).max()) < TOL


# T: under one solve block, under one chunk, one chunk, not a multiple of
# the chunk, several chunks (the kernel's chunk is always 128: a shorter
# scan is padded up to it with held positions)
@pytest.mark.parametrize("T", [5, 37, 128, 300, 384])
@pytest.mark.parametrize("kernel", [False, True])
def test_chunked_scan_equals_the_recurrence(T, kernel):
    args = draw(T, seed=T)
    want_o, want_s = gd.recurrence(*args)
    got_o, got_s = gd.prefill_scan(*args, kernel=kernel)
    assert got_o.shape == want_o.shape == (B, T, H, DV)
    assert float(jnp.abs(want_o).max()) > 0.5
    assert close(got_o, want_o) and close(got_s, want_s)


# The published head shape (30 heads of 96 keys x 192 values: five head
# blocks, lane dims of three quarters and one and a half tiles); q, k, v in
# bfloat16 as the mixer hands them over (the kernel then takes [q; k] k^T
# in the MXU's one native pass); a nonzero start state carried over four
# chunks of one row.
SHAPES = {
    "published_heads": dict(T=256, b=1, h=30, dk=96, dv=192),
    "bfloat16_qkv": dict(T=256, b=1, h=6, dk=96, dv=192, dtype=jnp.bfloat16),
    "four_chunks_from_a_state": dict(T=512, b=1),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernel", [False, True])
def test_chunked_scan_equals_the_recurrence_at_the_cells_shapes(shape, kernel):
    shape = dict(SHAPES[shape])
    T, dtype = shape.pop("T"), shape.pop("dtype", jnp.float32)
    q, k, v, *rest, state = draw(T, seed=T, **shape)
    args = (*(x.astype(dtype) for x in (q, k, v)), *rest, state)
    want_o, want_s = gd.recurrence(*args)
    got_o, got_s = gd.prefill_scan(*args, kernel=kernel)
    assert got_o.shape == want_o.shape == q.shape[:3] + v.shape[3:]
    assert float(jnp.abs(want_o).max()) > 0.25
    assert float(jnp.abs(state).max()) > 1.0        # the state it starts from
    assert close(got_o, want_o) and close(got_s, want_s)


def test_step_is_the_rule_as_written():
    *token, S = draw(1, seed=3)
    q, k, v, la, beta = (x[:, 0] for x in token)
    o, new = gd.step(q, k, v, la, beta, S)
    a, b_ = jnp.exp(la)[..., None, None], beta[..., None, None]
    kk = k[..., :, None] * k[..., None, :]                       # k k^T
    want = a * (S - b_ * jnp.einsum("bhij,bhjv->bhiv", kk, S)) \
        + b_ * k[..., :, None] * v[..., None, :]
    assert close(new, want)
    assert close(o, jnp.einsum("bhkv,bhk->bhv", want, q))


@pytest.mark.parametrize("kernel", [False, True])
def test_padding_past_a_rows_end_leaves_its_state_untouched(kernel):
    """Rows of different lengths in one padded call: ``log_alpha = 0, beta
    = 0`` past a row's end. Each row's final state is the state at ITS last
    real token, and its real outputs are those of a call of its own."""
    T, lens = 256, (256, 131)
    q, k, v, la, beta, S = draw(T, seed=9)
    live = jnp.arange(T)[None, :, None] < jnp.asarray(lens)[:, None, None]
    la, beta = jnp.where(live, la, 0.0), jnp.where(live, beta, 0.0)
    got_o, got_s = gd.prefill_scan(q, k, v, la, beta, S, kernel=kernel)
    for row, n in enumerate(lens):
        one = tuple(x[row:row + 1, :n] for x in (q, k, v, la, beta))
        want_o, want_s = gd.recurrence(*one, S[row:row + 1])
        assert close(got_o[row:row + 1, :n], want_o), row
        assert close(got_s[row:row + 1], want_s), row


def test_a_held_token_is_bit_for_bit_no_token():
    *token, S = draw(1, seed=4)
    q, k, v, la, beta = (x[:, 0] for x in token)
    _, new = gd.step(q, k, v, jnp.zeros_like(la), jnp.zeros_like(beta), S)
    assert np.array_equal(np.asarray(new), np.asarray(S))


@pytest.mark.parametrize("kernel", [False, True])
def test_a_row_that_ends_inside_a_chunk_keeps_its_state_bit_for_bit(kernel):
    """Three chunks, the row's real tokens end inside the middle one: the
    wholly held third chunk hands the state on bit for bit (what a bucket's
    padding relies on), so the scan ends where a scan of two chunks ends;
    and the positions held inside the middle chunk change nothing a scan of
    the real tokens alone would not give."""
    T, n = 384, 200
    q, k, v, la, beta, S = draw(T, seed=13, b=1)
    live = (jnp.arange(T) < n)[None, :, None]
    la, beta = jnp.where(live, la, 0.0), jnp.where(live, beta, 0.0)
    _, full = gd.prefill_scan(q, k, v, la, beta, S, kernel=kernel)
    _, two = gd.prefill_scan(*(x[:, :256] for x in (q, k, v, la, beta)), S,
                             kernel=kernel)
    assert np.array_equal(np.asarray(full), np.asarray(two))
    _, want = gd.recurrence(*(x[:, :n] for x in (q, k, v, la, beta)), S)
    assert close(full, want)
    assert float(jnp.abs(full - S).max()) > 1e-2


def test_triangular_inverse_where_a_series_would_lose_its_digits():
    """Keys that nearly coincide and ``beta`` near 2: ``I + A`` has entries
    near 2 all below the diagonal, its inverse stays O(1), and the powers
    of ``A`` a Neumann series would sum reach 1e30."""
    n = 128
    a = jnp.tril(jnp.full((n, n), 1.9, jnp.float32), -1)
    inv = gd._inv_unit_lower(a[None])[0]
    err = jnp.abs(inv @ (jnp.eye(n) + a) - jnp.eye(n)).max()
    assert float(err) < 1e-3 and float(jnp.abs(inv).max()) < 4.0


@pytest.mark.parametrize("kernel", [False, True])
def test_coincident_keys_go_through_the_scan_as_through_the_rule(kernel):
    """The same ill-conditioned inverse THROUGH ``prefill_scan``: every key
    of a head the same unit vector, ``beta`` 1.9, one chunk of 128, so the
    substitution and the merges inside the kernel (and those of
    ``_inv_unit_lower`` under the ``lax.scan``) meet ``A`` = 1.9 below the
    diagonal times the decays. ``o`` and the state stay finite and agree
    with the rule a token at a time to 1e-3."""
    T = 128
    q, k, v, la, beta, S = draw(T, seed=11)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta, la = jnp.full_like(beta, 1.9), jnp.full_like(la, -1e-3)
    want_o, want_s = gd.recurrence(q, k, v, la, beta, S)
    got_o, got_s = gd.prefill_scan(q, k, v, la, beta, S, kernel=kernel)
    assert bool(jnp.isfinite(got_o).all() & jnp.isfinite(got_s).all())
    assert float(jnp.abs(got_o - want_o).max()) < 1e-3
    assert float(jnp.abs(got_s - want_s).max()) < 1e-3
    assert float(jnp.abs(want_s).max()) > 1.0


def test_kernel_engages_only_where_its_chunk_divides_the_scan(monkeypatch):
    assert not gd.prefill_engages(256)               # the CPU
    monkeypatch.setattr(gd, "_FORCE_INTERPRET", True)
    assert gd.prefill_engages(256) and gd.prefill_engages(4096)
    assert not gd.prefill_engages(64) and not gd.prefill_engages(200)
    # what ``linear_scan_positions`` counts: the chunk's rounding
    assert [gd.scan_positions(t) for t in (16, 128, 200, 4096)] == [
        16, 128, 256, 4096]


# ---- the decode step's kernel over the stacked leaf (PR 36)

LIVE = {"every": lambda b: np.ones(b, bool),
        "some": lambda b: np.arange(b) % 3 != 1,
        "none": lambda b: np.zeros(b, bool)}


# the published head shape (96 keys x 192 values: a lane dim of one and a
# half tiles) and a lane-aligned one
@pytest.mark.parametrize("dk,dv", [(96, 192), (128, 128)])
@pytest.mark.parametrize("h", [5, 30])
@pytest.mark.parametrize("b", [1, 16])
@pytest.mark.parametrize("live", sorted(LIVE))
def test_step_kernel_equals_the_step_on_the_rows_that_decode(dk, dv, h, b,
                                                             live):
    """Layer by layer of a stacked leaf, the layer index traced: outputs
    and new state of a decoding row within float32 rounding of ``step``;
    the state of a row that does not decode bit-identical to its input, in
    every layer; its output zeros."""
    layers, seed = 3, dk + h + b
    q, k, v, log_alpha, beta = (x[:, 0] for x in draw(
        1, seed, b, h, dk, dv)[:5])
    states = jax.random.normal(jax.random.key(seed + 1),
                               (layers, b, h, dk, dv))
    on = LIVE[live](b)
    # what the mixer hands over for a row that does not decode
    log_alpha = jnp.where(on[:, None], log_alpha, 0.0)
    beta = jnp.where(on[:, None], beta, 0.0)
    kernel = jax.jit(lambda s, i: gd.step_rows(
        q, k, v, log_alpha, beta, s, i, gd.step_plan(jnp.asarray(on))))
    got = states
    for layer in (2, 0):
        before = got
        o, got = kernel(before, jnp.int32(layer))
        want_o, want_s = gd.step(q, k, v, log_alpha, beta, before[layer])
        assert o.shape == (b, h, dv) and got.shape == states.shape
        if on.any():
            assert close(o[on], want_o[on])
            assert close(got[layer][on], want_s[on])
        assert not np.asarray(o)[~on].any()
        assert np.array_equal(got[layer][~on], before[layer][~on])
        others = [i for i in range(layers) if i != layer]
        assert np.array_equal(got[jnp.asarray(others)],
                              before[jnp.asarray(others)])
    assert np.array_equal(got[1], states[1])                # never asked
    if on.any():
        assert float(jnp.abs(got[0][on] - states[0][on]).max()) > 1e-2


def test_step_plan_lists_the_decoding_rows_first_and_then_stands_still():
    rows, n = gd.step_plan(jnp.asarray([False, True, True, False, True]))
    assert rows.dtype == n.dtype == jnp.int32
    assert rows.tolist() == [1, 2, 4, 4, 4] and n.tolist() == [3]
    rows, n = gd.step_plan(jnp.zeros(4, bool))
    assert rows.tolist() == [0, 0, 0, 0] and n.tolist() == [0]
    rows, n = gd.step_plan(jnp.ones(3, bool))
    assert rows.tolist() == [0, 1, 2] and n.tolist() == [3]


def test_step_kernel_engages_by_platform_and_block_size(monkeypatch):
    assert not gd.step_engages(30, 96, 192)                     # the CPU
    monkeypatch.setattr(gd, "_FORCE_INTERPRET", True)
    assert gd.step_engages(30, 96, 192) and gd.step_engages(2, 8, 16)
    # a row's block, in and out and double-buffered, has to fit VMEM
    assert not gd.step_engages(64, 256, 512)
