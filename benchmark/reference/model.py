"""The decoder in straightforward ``jax.numpy`` float32: no cache, no
batching, no kernels. One sequence at a time, one layer at a time.

``lower`` switches on the lower-precision *controls* the benchmark's limits
are set against (never used in a benchmark run's own reference):
``"w4"`` re-quantises every matrix to int4 per output channel, ``"fp8"``
rounds both operands of every matrix multiplication to float8_e4m3.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [T, H, D]; rotate (x1, x2) halves by position / theta^(2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _straight_through(x, rounded):
    """``rounded`` forward, identity backward: the usual way a lower
    precision is trained through."""
    return x + jax.lax.stop_gradient(rounded - x)


def _fake_int(x, bits, axis):
    top = 2.0 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis, keepdims=True), 1e-30) / top
    return _straight_through(
        x, jnp.clip(jnp.round(x / scale), -top, top) * scale)


def _fake_fp8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis, keepdims=True), 1e-30) / 448.
    return _straight_through(
        x, (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale)


def matmul(x, w, lower=None):
    if lower == "w4":
        w = _fake_int(w, 4, 0)
    elif lower == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    elif lower is not None:
        raise ValueError(f"unknown control {lower!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def attention(q, k, v):
    """Causal grouped-query attention. q [T, H, D], k/v [T, Hkv, D]; one
    key/value head at a time so the [G, T, T] scores fit beside the rest."""
    T, H, D = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(T, Hkv, H // Hkv, D).transpose(1, 2, 0, 3)   # [Hkv,G,T,D]
    mask = jnp.tril(jnp.ones((T, T), bool))

    def one(args):
        qh, kh, vh = args                         # [G,T,D], [T,D], [T,D]
        s = jnp.einsum("gtd,sd->gts", qh, kh, precision=HIGHEST) * D ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("gts,sd->gtd", p, vh, precision=HIGHEST)

    # checkpoint: a backward pass recomputes a head's scores instead of
    # keeping every head's [G, T, T] probabilities (4 GB at T = 4096)
    out = jax.lax.map(jax.checkpoint(one),
                      (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(T, H * D)           # [T, H*D]


def block(x, w, positions, d, lower=None):
    """One decoder layer on one sequence. x [T, E]; w: plain matrices."""
    T = x.shape[0]
    h = rms_norm(x, w["attn_norm"], d["eps"])
    q = matmul(h, w["wq"], lower).reshape(T, d["H"], d["D"])
    k = matmul(h, w["wk"], lower).reshape(T, d["Hkv"], d["D"])
    v = matmul(h, w["wv"], lower).reshape(T, d["Hkv"], d["D"])
    q, k = rope(q, positions, d["theta"]), rope(k, positions, d["theta"])
    x = x + matmul(attention(q, k, v), w["wo"], lower)
    h = rms_norm(x, w["mlp_norm"], d["eps"])
    ff = jax.nn.silu(matmul(h, w["w_gate"], lower)) * matmul(
        h, w["w_up"], lower)
    return x + matmul(ff, w["w_down"], lower)


def head(x, final_norm, lm_head, d, lower=None):
    return matmul(rms_norm(x, final_norm, d["eps"]), lm_head, lower)
