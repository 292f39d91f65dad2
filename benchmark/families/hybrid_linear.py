"""Gated-delta linear-attention layers beside full-attention layers, three
to one: the family of the ``olmo_hybrid`` public config, run by the
program's ``models/hybrid_linear.py``.

Here: the sizes under their published keys, the program's configuration
object, bf16 weights from the seed in the program's layout, the PLAIN
float32 reference of one layer (below: the recurrence one token at a time,
never chunked; the convolution as a direct sum; full softmax attention a
head at a time; no cache, no kernel), its lower-precision controls, and the
least work (``benchmark/opcounts/hybrid_linear.py``).

The layer (what the config's keys do not say is the configuration file's
``assumed``): every block is ``x + RMSNorm(f(x))``, the mixer then a SwiGLU.
Linear mixer on ``x_t``: ``q~, k~, v~ = W x`` (H x dk, H x dk, H x dv); a
depthwise causal convolution of ``linear_conv_kernel_dim`` taps over time on
every channel, zero history, then SiLU; a head's ``q = q/|q| dk^-1/2``, ``k
= k/|k|``; ``beta = 2 sigmoid(W_b x)``, ``alpha = exp(-exp(A_log)
softplus(W_a x + dt_bias))``; ``S_0 = 0``, ``S_t = alpha_t (I - beta_t k_t
k_t^T) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``; out ``W_o
[RMSNorm_head(o_t) * silu(W_g x_t)]``. Full mixer: H heads of D, RMSNorm
over the whole q and k projections, causal softmax, no rotary embedding.

Departure of the reference from a published implementation: none in the
mathematics; the scorer pads a sequence to a bucket AFTER its tokens, and
every operation here is causal, so the padding changes no real position.
"""

from __future__ import annotations

from pathlib import Path

from benchmark.families.latent_moe import (_attention, _key, _norm,
                                           _uniform)
from benchmark.opcounts import hybrid_linear as ops

PROGRAM_FILE = (Path(__file__).resolve().parents[2]
                / "kubetorch_tpu" / "models" / "hybrid_linear.py")
LINEAR, FULL = "linear_attention", "full_attention"
STACK = {LINEAR: "linear", FULL: "full"}
KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "layer_types",
        "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "linear_allow_neg_eigval", "vocab_size",
        "rms_norm_eps", "compute_dtype", "weights_dtype", "state_dtype")
# published keys whose only supported value is the one given
FIXED = {"attention_bias": False, "tie_word_embeddings": False,
         "hidden_act": "silu", "rope_parameters": {"rope_theta": None},
         "state_dtype": "float32"}
# ``A ~ U(0, 16)``, ``dt`` log-uniform in [1e-3, 1e-1]: the gated-delta
# convention for the decay's two learned vectors (the file's ``assumed``)
A_MAX, DT_MIN, DT_MAX = 16.0, 1e-3, 1e-1


def dims(config: dict) -> dict:
    if not PROGRAM_FILE.is_file():
        raise LookupError(
            "this checkout's program has no models/hybrid_linear.py: it "
            "cannot run a configuration of family 'hybrid_linear'")
    missing = [k for k in KEYS if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    for key, only in FIXED.items():
        if key in config and config[key] != only:
            raise ValueError(
                f"family hybrid_linear carries {key} = {only!r} only, the "
                f"configuration says {config[key]!r}")
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("family hybrid_linear carries as many linear key "
                         "heads as value heads only")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("family hybrid_linear carries multi-head full "
                         "attention only (num_key_value_heads = "
                         "num_attention_heads)")
    L = config["num_hidden_layers"]
    # the file holds the published list whole; the layers run are its first
    kinds = tuple(config["layer_types"][:L])
    if len(kinds) != L or set(kinds) - {LINEAR, FULL}:
        raise ValueError(f"layer_types must name {L} layers of "
                         f"{LINEAR} | {FULL}")
    H = config["num_attention_heads"]
    return {"E": config["hidden_size"], "L": L, "kinds": kinds, "H": H,
            "D": config.get("head_dim") or config["hidden_size"] // H,
            "Hl": config["linear_num_key_heads"],
            "dk": config["linear_key_head_dim"],
            "dv": config["linear_value_head_dim"],
            "K": config["linear_conv_kernel_dim"],
            "neg": bool(config["linear_allow_neg_eigval"]),
            "M": config["intermediate_size"], "V": config["vocab_size"],
            "eps": float(config["rms_norm_eps"]),
            "dtype": config["weights_dtype"]}


def controls() -> tuple:
    """``fp8``: float8_e4m3 operands in every product with a weight matrix
    (the step below the bf16 compute the file states); ``state_bf16``: the
    recurrent state rounded to bfloat16 between tokens (the step below the
    float32 the file states for it)."""
    return ("fp8", "state_bf16")


def layer_kinds(d: dict) -> tuple:
    return d["kinds"]


def program_config(config: dict, path: str, deployment: dict = None):
    from kubetorch_tpu.models import HybridLinearConfig

    if path != "serve":
        raise NotImplementedError(
            "family hybrid_linear has no training path: the chunked scan "
            "has no backward and the trainer does not carry this decoder")
    d = dims(config)
    return HybridLinearConfig(
        vocab_size=d["V"], embed_dim=d["E"], layer_types=d["kinds"],
        n_heads=d["H"], n_kv_heads=d["H"], head_dim=d["D"],
        linear_heads=d["Hl"], linear_key_dim=d["dk"],
        linear_value_dim=d["dv"], conv_width=d["K"], neg_eigval=d["neg"],
        mlp_dim=d["M"], rms_eps=d["eps"],
        max_seq_len=deployment["max_len"], dtype=config["compute_dtype"],
        param_dtype=config["weights_dtype"])


# ------------------------------------------------ weights from the seed
def _shapes(d: dict, kind: str) -> dict:
    """leaf -> (shape, fan_in) of one layer's matrices in the program's
    layout (``q | k | v`` and gate and up fused along the output). No
    residual scaling: every block's output is normed before the add."""
    E, M = d["E"], d["M"]
    out = {"w_gu": ((E, 2 * M), E), "w_down": ((M, E), M)}
    if kind == LINEAR:
        H, dk, dv = d["Hl"], d["dk"], d["dv"]
        chan = H * (2 * dk + dv)
        out.update({"wqkv": ((E, chan), E), "conv_w": ((d["K"], chan), d["K"]),
                    "wg": ((E, H * dv), E), "wo": ((H * dv, E), H * dv)})
    else:
        HD = d["H"] * d["D"]
        out.update({"wqkv": ((E, 3 * HD), E), "wo": ((HD, E), HD)})
    return out


def _norms(d: dict, kind: str) -> dict:
    out = {"attn_norm": d["E"], "mlp_norm": d["E"]}
    if kind == LINEAR:
        out["o_norm"] = d["dv"]
    else:
        out.update({"q_norm": d["H"] * d["D"], "k_norm": d["H"] * d["D"]})
    return out


def _layer(key, layer, d: dict, kind: str) -> dict:
    """Layer ``layer`` (its index in the whole stack; may be traced) of
    ``kind`` in the program's layout and dtype. What sets the decay
    (``wab``, ``a_log``, ``dt_bias``) is float32, as the program keeps it."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(d["dtype"])
    f32 = jnp.float32
    out = {name: _uniform(_key(key, name, layer), shape, fan_in ** -0.5, dt)
           for name, (shape, fan_in) in _shapes(d, kind).items()}
    for name, n in _norms(d, kind).items():
        out[name] = _norm(_key(key, name, layer), n, dt)
    if kind == LINEAR:
        H = d["Hl"]
        out["wab"] = jax.random.normal(
            _key(key, "wab", layer), (d["E"], 2 * H), f32) * d["E"] ** -0.5
        out["a_log"] = jnp.log(jax.random.uniform(
            _key(key, "a_log", layer), (H,), f32, 1e-4, A_MAX))
        step = jnp.exp(jax.random.uniform(
            _key(key, "dt_bias", layer), (H,), f32, jnp.log(DT_MIN),
            jnp.log(DT_MAX)))
        out["dt_bias"] = step + jnp.log(-jnp.expm1(-step))  # softplus^-1
    return out


def _globals(key, d: dict) -> dict:
    import jax.numpy as jnp

    dt = jnp.dtype(d["dtype"])
    return {"embedding": _uniform(_key(key, "embedding"),
                                  (d["V"], d["E"]), 1.0, dt),
            "final_norm": _norm(_key(key, "final_norm"), d["E"], dt),
            "lm_head": _uniform(_key(key, "lm_head"), (d["E"], d["V"]),
                                d["E"] ** -0.5, dt)}


def serving_tree(seed: int, d: dict) -> dict:
    """The program's tree: globals, and one stack ``[n, ...]`` a kind, each
    layer drawn under its index in the whole stack."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    def build(key):
        tree = _globals(key, d)
        for kind in (LINEAR, FULL):
            at = [l for l, k in enumerate(d["kinds"]) if k == kind]
            if at:
                tree[STACK[kind]] = jax.lax.map(
                    lambda l, kind=kind: _layer(key, l, d, kind),
                    jnp.asarray(at, jnp.int32))
        return tree
    return jax.jit(build)(weights.root_key(seed))


# ----------------------------------------------------------- the reference
def reference_globals(key, d: dict, path: str) -> dict:
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda x: x.astype(jnp.float32), _globals(key, d))


def reference_layer(key, layer, d: dict, kind: str, path: str) -> dict:
    """The very values the program's tree holds, as the plain float32
    matrices ``block`` multiplies by: the fused leaves split."""
    import jax
    import jax.numpy as jnp

    w = jax.tree.map(lambda x: x.astype(jnp.float32),
                     _layer(key, layer, d, kind))
    fused = w.pop("w_gu")
    w["w_gate"], w["w_up"] = fused[:, :d["M"]], fused[:, d["M"]:]
    qkv = w.pop("wqkv")
    if kind == LINEAR:
        nk = d["Hl"] * d["dk"]
        w["wq"], w["wk"], w["wv"] = qkv[:, :nk], qkv[:, nk:2 * nk], qkv[:, 2 * nk:]
        ab = w.pop("wab")
        w["wa"], w["wb"] = ab[:, :d["Hl"]], ab[:, d["Hl"]:]
    else:
        n = d["H"] * d["D"]
        w["wq"], w["wk"], w["wv"] = qkv[:, :n], qkv[:, n:2 * n], qkv[:, 2 * n:]
    return w


def _conv(x, w):
    """Depthwise causal convolution, directly: ``y[t] = sum_j w[j] x[t - (K
    - 1) + j]`` with zeros before the sequence. x [T, C], w [K, C]."""
    import jax.numpy as jnp

    K, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return sum(padded[j:j + T] * w[j] for j in range(K))


def _delta_rule(q, k, v, alpha, beta, state_bf16: bool):
    """The recurrence, one token at a time. q, k [T,H,dk]; v [T,H,dv];
    alpha, beta [T,H] -> o [T,H,dv]. ``S_0 = 0``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.model import HIGHEST

    def one(S, tok):
        q_t, k_t, v_t, a_t, b_t = tok
        kS = jnp.einsum("hk,hkv->hv", k_t, S, precision=HIGHEST)
        S = (a_t[:, None, None] * (S - b_t[:, None, None]
                                   * k_t[:, :, None] * kS[:, None, :])
             + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :])
        if state_bf16:
            # ``reduce_precision``, not a cast there and back: on the TPU
            # XLA drops a pair of casts that only loses precision
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=HIGHEST)

    S0 = jnp.zeros(k.shape[1:] + v.shape[2:], jnp.float32)
    return jax.lax.scan(one, S0, (q, k, v, alpha, beta))[1]


def _unit(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def block(x, w, positions, d: dict, lower, kind: str):
    """One layer on one sequence, x [T, E], float32 at the highest matmul
    precision. ``lower``: None, or one of ``controls()``. ``positions`` is
    not read: no layer of this family has a rotary embedding."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import model

    if lower not in (None,) + controls():
        raise ValueError(f"unknown control {lower!r}")
    how = "fp8" if lower == "fp8" else None
    T = x.shape[0]
    if kind == LINEAR:
        H, dk, dv = d["Hl"], d["dk"], d["dv"]
        qkv = jnp.concatenate(
            [model.matmul(x, w[n], how) for n in ("wq", "wk", "wv")], -1)
        qkv = jax.nn.silu(_conv(qkv, w["conv_w"]))
        q = _unit(qkv[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
        k = _unit(qkv[:, H * dk:2 * H * dk].reshape(T, H, dk))
        v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
        # the decay's own products stay float32 whatever the control: the
        # program keeps them so
        beta = jax.nn.sigmoid(model.matmul(x, w["wb"])) * (
            2.0 if d["neg"] else 1.0)
        alpha = jnp.exp(-jnp.exp(w["a_log"]) * jax.nn.softplus(
            model.matmul(x, w["wa"]) + w["dt_bias"]))
        o = _delta_rule(q, k, v, alpha, beta, lower == "state_bf16")
        gate = model.matmul(x, w["wg"], how).reshape(T, H, dv)
        o = model.rms_norm(o, w["o_norm"], d["eps"]) * jax.nn.silu(gate)
        mixed = model.matmul(o.reshape(T, H * dv), w["wo"], how)
    else:
        H, D = d["H"], d["D"]
        q = model.rms_norm(model.matmul(x, w["wq"], how), w["q_norm"],
                           d["eps"]).reshape(T, H, D)
        k = model.rms_norm(model.matmul(x, w["wk"], how), w["k_norm"],
                           d["eps"]).reshape(T, H, D)
        v = model.matmul(x, w["wv"], how).reshape(T, H, D)
        mixed = model.matmul(_attention(q, k, v, D ** -0.5), w["wo"], how)
    h = x + model.rms_norm(mixed, w["attn_norm"], d["eps"])
    ff = jax.nn.silu(model.matmul(h, w["w_gate"], how)) * model.matmul(
        h, w["w_up"], how)
    return h + model.rms_norm(model.matmul(ff, w["w_down"], how),
                              w["mlp_norm"], d["eps"])


def head(x, final_norm, lm_head, d: dict, lower):
    from benchmark.reference import model

    return model.head(x, final_norm, lm_head, d,
                      "fp8" if lower == "fp8" else None)


# ------------------------------------- least work, from the run's context
def decode_step_bytes(ctx: dict):
    """The least bytes of one decode step: every layer's weights and the
    head once, the live K/V of the full layers, the live rows' recurrent
    state read and written once. ``None`` without a traced span."""
    live = ctx.get("trace_live") or {}
    if live.get("positions") is None:
        return None
    return ops.decode_step_bytes(ctx["dims"], live["positions"],
                                 live["rows"])


def prefill_flops(ctx: dict):
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "prefill_tokens_executed", 0)
    if not toks:
        return None
    mean_len = ctx.get("mean_prompt_len") or 0.0
    return ops.prefill_flops(ctx["dims"], toks, toks * mean_len)
