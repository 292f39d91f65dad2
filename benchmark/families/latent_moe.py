"""Latent attention with sigmoid-routed experts beside shared ones: the
family of the DeepSeek-V3-style public configs (``model_type:
deepseek_v3``), run by the program's ``models/latent_moe.py``.

Here: the sizes under their published keys, the program's configuration
object, bf16 weights from the seed in the program's layout, the PLAIN
float32 reference of one layer (below: never absorbed, no cache, no kernel,
experts one at a time over the tokens given to them), its lower-precision
controls, and the least work (``benchmark/opcounts/latent_moe.py``).

Departures of the reference from the published implementation, each also a
comment where it happens: (1) rope rotates the interleaved pairs in place
instead of de-interleaving first: the same scores, since queries and keys
take the same permutation; (2) every expert runs over the whole sequence
with a gate that is zero for the tokens it was not given, instead of
gathering them: the same sum; (3) group-limited routing is refused, not
computed (``n_group`` = ``topk_group`` = 1 is the identity).
"""

from __future__ import annotations

import zlib
from pathlib import Path

from benchmark.opcounts import latent_moe as ops

PROGRAM_FILE = (Path(__file__).resolve().parents[2]
                / "kubetorch_tpu" / "models" / "latent_moe.py")
KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
        "routed_scaling_factor", "norm_topk_prob", "vocab_size",
        "rope_theta", "rms_norm_eps", "compute_dtype", "weights_dtype")
# published keys whose only supported value is the one given
FIXED = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
         "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "rope_interleave": True, "rope_scaling": None,
         "attention_bias": False, "tie_word_embeddings": False,
         "moe_layer_freq": 1, "hidden_act": "silu"}


def dims(config: dict) -> dict:
    if not PROGRAM_FILE.is_file():
        raise LookupError(
            "this checkout's program has no models/latent_moe.py: it "
            "cannot run a configuration of family 'latent_moe'")
    missing = [k for k in KEYS if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    for key, only in FIXED.items():
        if key in config and config[key] != only:
            raise ValueError(
                f"family latent_moe carries {key} = {only!r} only, the "
                f"configuration says {config[key]!r}")
    if config["first_k_dense_replace"] > config["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace exceeds num_hidden_layers")
    return {"E": config["hidden_size"], "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"],
            "dn": config["qk_nope_head_dim"],
            "dr": config["qk_rope_head_dim"], "dv": config["v_head_dim"],
            "r": config["kv_lora_rank"], "Md": config["intermediate_size"],
            "Mx": config["moe_intermediate_size"],
            "X": config["n_routed_experts"],
            "K": config["num_experts_per_tok"],
            "Ns": config["n_shared_experts"],
            "Ld": config["first_k_dense_replace"],
            "scale": float(config["routed_scaling_factor"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "V": config["vocab_size"], "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "bias_std": float(config.get("router_bias_std", 0.05)),
            "dtype": config["weights_dtype"]}


def controls() -> tuple:
    """``fp8``: float8_e4m3 operands in every matrix product but the
    router's (the step below the bf16 compute the file states);
    ``fp8_experts``: in the routed experts' products alone."""
    return ("fp8", "fp8_experts")


def layer_kinds(d: dict) -> tuple:
    return ("dense",) * d["Ld"] + ("moe",) * (d["L"] - d["Ld"])


def program_config(config: dict, path: str, deployment: dict = None):
    from kubetorch_tpu.models import LatentMoEConfig

    if path != "serve":
        raise NotImplementedError(
            "family latent_moe has no training path: the trainer does not "
            "carry this decoder")
    d = dims(config)
    return LatentMoEConfig(
        vocab_size=d["V"], embed_dim=d["E"], n_layers=d["L"],
        n_heads=d["H"], qk_nope_dim=d["dn"], qk_rope_dim=d["dr"],
        v_head_dim=d["dv"], kv_latent_dim=d["r"], dense_mlp_dim=d["Md"],
        n_dense_layers=d["Ld"], n_experts=d["X"], top_k=d["K"],
        expert_mlp_dim=d["Mx"], n_shared_experts=d["Ns"],
        routed_scale=d["scale"], norm_topk=d["norm_topk"],
        rope_theta=d["theta"], rms_eps=d["eps"],
        max_seq_len=deployment["max_len"], dtype=config["compute_dtype"],
        param_dtype=config["weights_dtype"])


# ------------------------------------------------ weights from the seed
def _shapes(d: dict, kind: str) -> dict:
    """leaf -> (shape, fan_in, gain) of one layer in the program's layout
    (gate and up fused along the output; experts ``[X, in, out]``). The
    residual outputs are scaled by 1/sqrt(2L), as ``benchmark/weights.py``
    does, so the stream stays O(1) through the depth."""
    res = (2 * d["L"]) ** -0.5
    E, H = d["E"], d["H"]
    out = {"wq": ((E, H * (d["dn"] + d["dr"])), E, 1.0),
           "wkv_a": ((E, d["r"] + d["dr"]), E, 1.0),
           "wkv_b": ((d["r"], H * (d["dn"] + d["dv"])), d["r"], 1.0),
           "wo": ((H * d["dv"], E), H * d["dv"], res)}
    if kind == "dense":
        out.update({"w_gu": ((E, 2 * d["Md"]), E, 1.0),
                    "w_down": ((d["Md"], E), d["Md"], res)})
    else:
        Ms = d["Ns"] * d["Mx"]
        out.update({"we_gu": ((d["X"], E, 2 * d["Mx"]), E, 1.0),
                    "we_down": ((d["X"], d["Mx"], E), d["Mx"], res),
                    "ws_gu": ((E, 2 * Ms), E, 1.0),
                    "ws_down": ((Ms, E), Ms, res)})
    return out


def _key(key, name: str, layer=None):
    import jax

    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return key if layer is None else jax.random.fold_in(key, layer)


def _uniform(key, shape, std: float, dtype):
    """Uniform with deviation ``std`` from ONE 16-bit draw a weight (a
    normal costs a 32-bit draw and an erfinv over five billion weights)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint16), jnp.int16)
    return (bits.astype(jnp.float32) * (std * 3 ** 0.5 / 32768.0)
            ).astype(dtype)


def _norm(key, n, dtype):
    import jax
    import jax.numpy as jnp

    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(dtype)


def _layer(key, layer, d: dict, kind: str) -> dict:
    """Layer ``layer`` (its index in the whole stack; may be traced) of
    ``kind`` in the program's layout and dtype. The router and its
    selection bias are float32 (scores are float32 by the architecture)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(d["dtype"])
    out = {name: _uniform(_key(key, name, layer), shape,
                          gain * fan_in ** -0.5, dt)
           for name, (shape, fan_in, gain) in _shapes(d, kind).items()}
    for name, n in (("attn_norm", d["E"]), ("mlp_norm", d["E"]),
                    ("kv_norm", d["r"])):
        out[name] = _norm(_key(key, name, layer), n, dt)
    if kind == "moe":
        out["router"] = jax.random.normal(
            _key(key, "router", layer), (d["E"], d["X"]),
            jnp.float32) * d["E"] ** -0.5
        out["router_bias"] = d["bias_std"] * jax.random.normal(
            _key(key, "router_bias", layer), (d["X"],), jnp.float32)
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
    """The program's tree: globals, and one stack ``[n, ...]`` a kind."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    def build(key):
        tree = _globals(key, d)
        first = 0
        for kind, n in (("dense", d["Ld"]), ("moe", d["L"] - d["Ld"])):
            if n:
                tree[kind] = jax.lax.map(
                    lambda l, kind=kind: _layer(key, l, d, kind),
                    jnp.arange(first, first + n))
            first += n
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

    def halves(name, gate, up):
        fused = w.pop(name)
        half = fused.shape[-1] // 2
        w[gate], w[up] = fused[..., :half], fused[..., half:]

    if kind == "dense":
        halves("w_gu", "w_gate", "w_up")
    else:
        halves("we_gu", "we_gate", "we_up")
        halves("ws_gu", "ws_gate", "ws_up")
    return w


def _rope_pairs(x, positions, theta):
    """x [T, ..., dr]: rotate the pairs (x[2i], x[2i+1]) by ``position *
    theta^(-2i/dr)``. Departure (1): the published code de-interleaves the
    pairs into halves first and rotates those; the result here is the same
    vector in the interleaved order, and a score is a dot product of two
    vectors in one order."""
    import jax.numpy as jnp

    dr = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # [T,dr/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (dr // 2,))
    pairs = x.reshape(x.shape[:-1] + (dr // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _attention(q, k, v, scale):
    """Causal attention, a head at a time. q, k [T, H, dqk]; v [T, H, dv]
    -> [T, H * dv]. Always the expanded form: keys and values a head."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.model import HIGHEST

    T = q.shape[0]
    mask = jnp.tril(jnp.ones((T, T), bool))

    def one(args):
        qh, kh, vh = args
        s = jnp.einsum("td,sd->ts", qh, kh, precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        return jnp.einsum("ts,sd->td", p, vh, precision=HIGHEST)

    out = jax.lax.map(one, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                            v.transpose(1, 0, 2)))                # [H,T,dv]
    return out.transpose(1, 0, 2).reshape(T, -1)


def block(x, w, positions, d: dict, lower, kind: str):
    """One layer on one sequence, x [T, E], float32 at the highest matmul
    precision. ``lower``: None, or one of ``controls()``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import model

    if lower not in (None,) + controls():
        raise ValueError(f"unknown control {lower!r}")
    every = "fp8" if lower == "fp8" else None      # all products but routing
    expert = "fp8" if lower else None              # the routed experts'
    T = x.shape[0]
    H, dn, dr, dv, r = d["H"], d["dn"], d["dr"], d["dv"], d["r"]
    n = model.rms_norm(x, w["attn_norm"], d["eps"])
    q = model.matmul(n, w["wq"], every).reshape(T, H, dn + dr)
    kva = model.matmul(n, w["wkv_a"], every)
    c = model.rms_norm(kva[:, :r], w["kv_norm"], d["eps"])
    k_r = _rope_pairs(kva[:, r:], positions, d["theta"])          # [T, dr]
    q_rope = _rope_pairs(q[..., dn:], positions, d["theta"])
    kv = model.matmul(c, w["wkv_b"], every).reshape(T, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (T, H, dr))], -1)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    attn = _attention(q, k, kv[..., dn:], (dn + dr) ** -0.5)
    h = x + model.matmul(attn, w["wo"], every)
    m = model.rms_norm(h, w["mlp_norm"], d["eps"])

    def swiglu(gate, up, down, how):
        ff = jax.nn.silu(model.matmul(m, gate, how)) * model.matmul(
            m, up, how)
        return model.matmul(ff, down, how)

    if kind == "dense":
        return h + swiglu(w["w_gate"], w["w_up"], w["w_down"], every)
    # scores in float32 whatever the control: the architecture states them
    s = jax.nn.sigmoid(model.matmul(m, w["router"]))              # [T, X]
    # the bias chooses; it does not weigh
    _, chosen = jax.lax.top_k(s + w["router_bias"][None, :], d["K"])
    g = jnp.take_along_axis(s, chosen, axis=-1)                   # [T, K]
    if d["norm_topk"]:
        g = g / jnp.sum(g, -1, keepdims=True)
    g = g * d["scale"]

    def one_expert(y, e_w):
        e, gate, up, down = e_w
        # departure (2): the expert sees every token, weighted 0 where it
        # was not chosen
        ge = jnp.sum(jnp.where(chosen == e, g, 0.0), -1)          # [T]
        return y + ge[:, None] * swiglu(gate, up, down, expert), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(d["X"]), w["we_gate"], w["we_up"], w["we_down"]))
    shared = swiglu(w["ws_gate"], w["ws_up"], w["ws_down"], every)
    return h + routed + shared


def head(x, final_norm, lm_head, d: dict, lower):
    from benchmark.reference import model

    return model.head(x, final_norm, lm_head, d,
                      "fp8" if lower == "fp8" else None)


# ------------------------------------- least work, from the run's context
def decode_step_bytes(ctx: dict):
    """The least bytes of one decode step: attention, shared-expert, router
    and dense-layer weights and the head once; the experts the step's rows
    TOUCHED (from the program's counter, not all of them); the live latent
    positions of the active rows. ``None`` without a traced span."""
    live = (ctx.get("trace_live") or {}).get("positions")
    delta = ctx.get("trace_stats_delta") or {}
    if live is None or not delta.get("moe_expert_slots"):
        return None
    d = ctx["dims"]
    steps = delta["moe_expert_slots"] / (d["X"] * (d["L"] - d["Ld"]))
    return ops.decode_step_bytes(
        d, delta["moe_experts_touched"] / steps, live)


def prefill_flops(ctx: dict):
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "prefill_tokens_executed", 0)
    if not toks:
        return None
    mean_len = ctx.get("mean_prompt_len") or 0.0
    return ops.prefill_flops(ctx["dims"], toks, toks * mean_len)
