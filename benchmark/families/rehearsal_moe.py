"""A second family made of new files only: proof that the door is one.

The program's own softmax top-k expert layer
(``LlamaConfig(moe=MoEConfig(..., dispatch="dense"))``) at toy size in the
unfused float32 layout, with weights from the seed, its own float32
reference of router and experts, and its own least-work counts. A toy for
``--rehearsal 1`` on the CPU, never a cell; it stands for no model.
"""

from __future__ import annotations

import zlib

KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "vocab_size", "rope_theta",
        "rms_norm_eps", "num_local_experts", "num_experts_per_tok",
        "moe_intermediate_size")


def dims(config: dict) -> dict:
    missing = [k for k in KEYS if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    return {"E": config["hidden_size"], "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "V": config["vocab_size"], "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "X": config["num_local_experts"],
            "K": config["num_experts_per_tok"],
            "Mx": config["moe_intermediate_size"]}


def controls() -> tuple:
    """bfloat16 operands: the step below the float32 the file states."""
    return ("bf16",)


def layer_kinds(d: dict) -> tuple:
    return ("moe",) * d["L"]


def program_config(config: dict, path: str, deployment: dict = None):
    from kubetorch_tpu.models import LlamaConfig
    from kubetorch_tpu.models.configs import MoEConfig

    d = dims(config)
    return LlamaConfig(
        vocab_size=d["V"], embed_dim=d["E"], n_layers=d["L"], n_heads=d["H"],
        n_kv_heads=d["Hkv"], head_dim=d["D"], mlp_dim=d["Mx"],
        rope_theta=d["theta"], rms_eps=d["eps"], tie_embeddings=False,
        max_seq_len=deployment["max_len"], remat=False,
        dtype=config["compute_dtype"], param_dtype=config["weights_dtype"],
        moe=MoEConfig(num_experts=d["X"], top_k=d["K"],
                      expert_mlp_dim=d["Mx"], dispatch="dense"))


# ------------------------------------------------ weights from the seed
def _shapes(d: dict) -> dict:
    """leaf -> (shape, fan_in, gain); the two residual outputs are scaled
    by 1/sqrt(2L) as ``benchmark/weights.py`` does."""
    res = (2 * d["L"]) ** -0.5
    hd, kd = d["H"] * d["D"], d["Hkv"] * d["D"]
    return {"wq": ((d["E"], hd), d["E"], 1.0),
            "wk": ((d["E"], kd), d["E"], 1.0),
            "wv": ((d["E"], kd), d["E"], 1.0),
            "wo": ((hd, d["E"]), hd, res),
            "router": ((d["E"], d["X"]), d["E"], 1.0),
            "we_gate": ((d["X"], d["E"], d["Mx"]), d["E"], 1.0),
            "we_up": ((d["X"], d["E"], d["Mx"]), d["E"], 1.0),
            "we_down": ((d["X"], d["Mx"], d["E"]), d["Mx"], res)}


def _draw(key, name: str, shape, scale: float, layer=None):
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    return jax.random.normal(key, shape, jnp.float32) * scale


def _layer(key, layer, d: dict) -> dict:
    out = {name: _draw(key, name, shape, gain * fan_in ** -0.5, layer)
           for name, (shape, fan_in, gain) in _shapes(d).items()}
    for name in ("attn_norm", "mlp_norm"):
        out[name] = 1.0 + _draw(key, name, (d["E"],), 0.1, layer)
    return out


def _globals(key, d: dict) -> dict:
    return {"embedding": _draw(key, "embedding", (d["V"], d["E"]), 1.0),
            "final_norm": 1.0 + _draw(key, "final_norm", (d["E"],), 0.1),
            "lm_head": _draw(key, "lm_head", (d["E"], d["V"]),
                             d["E"] ** -0.5)}


def serving_tree(seed: int, d: dict) -> dict:
    """The program's unfused float32 tree, layers stacked [L, ...]."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    def build(key):
        tree = _globals(key, d)
        tree["layers"] = jax.lax.map(
            lambda l: _layer(key, l, d), jnp.arange(d["L"]))
        return tree
    return jax.jit(build)(weights.root_key(seed))


# ----------------------------------------------------------- the reference
def reference_globals(key, d: dict, path: str) -> dict:
    return _globals(key, d)


def reference_layer(key, layer, d: dict, kind: str, path: str) -> dict:
    return _layer(key, layer, d)


def _matmul(x, w, lower):
    import jax.numpy as jnp

    from benchmark.reference import model

    if lower == "bf16":
        x, w = (a.astype(jnp.bfloat16).astype(jnp.float32) for a in (x, w))
    elif lower is not None:
        raise ValueError(f"unknown control {lower!r}")
    return model.matmul(x, w)


def block(x, w, positions, d: dict, lower, kind: str):
    """One layer on one sequence, x [T, E]: the dense family's attention,
    then softmax router -> top-k -> renormalised gates over the chosen
    experts' SwiGLU outputs, an expert at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import model

    T = x.shape[0]
    h = model.rms_norm(x, w["attn_norm"], d["eps"])
    q = _matmul(h, w["wq"], lower).reshape(T, d["H"], d["D"])
    k = _matmul(h, w["wk"], lower).reshape(T, d["Hkv"], d["D"])
    v = _matmul(h, w["wv"], lower).reshape(T, d["Hkv"], d["D"])
    q = model.rope(q, positions, d["theta"])
    k = model.rope(k, positions, d["theta"])
    x = x + _matmul(model.attention(q, k, v), w["wo"], lower)
    h = model.rms_norm(x, w["mlp_norm"], d["eps"])
    gates = jax.nn.softmax(_matmul(h, w["router"], lower), -1)     # [T, X]
    top, chosen = jax.lax.top_k(gates, d["K"])                     # [T, K]
    top = top / jnp.sum(top, -1, keepdims=True)
    for e in range(d["X"]):
        gate = jnp.sum(jnp.where(chosen == e, top, 0.0), -1)       # [T]
        ff = jax.nn.silu(_matmul(h, w["we_gate"][e], lower)) * _matmul(
            h, w["we_up"][e], lower)
        x = x + gate[:, None] * _matmul(ff, w["we_down"][e], lower)
    return x


def head(x, final_norm, lm_head, d: dict, lower):
    from benchmark.reference import model

    return _matmul(model.rms_norm(x, final_norm, d["eps"]), lm_head, lower)


# ------------------------------------- least work, from the run's context
def _attn_params(d: dict) -> int:
    return d["E"] * (d["H"] + 2 * d["Hkv"]) * d["D"] + d["H"] * d["D"] * d["E"]


def decode_step_bytes(ctx: dict):
    """Weights a step has to read, in the type they are stored in:
    attention, router and head once, and no more experts than the active
    rows can choose; the live keys and values in the type the configuration
    states for the cache (``kv_dtype`` ``"bf16"`` is ``RollingGenerator``'s
    word for a grid that is not quantised: it holds ``compute_dtype``)."""
    live = ctx.get("trace_live") or {}
    if live.get("positions") is None:
        return None
    d, config = ctx["dims"], ctx["config"]
    size = {"float32": 4, "bfloat16": 2}
    if config["kv_dtype"] != "bf16":
        raise ValueError(f"no cache bytes for kv_dtype {config['kv_dtype']!r}")
    experts = min(d["X"], d["K"] * max(1.0, live.get("rows", 1.0)))
    layer = _attn_params(d) + d["E"] * d["X"] + experts * 3 * d["E"] * d["Mx"]
    kv = 2 * d["L"] * d["Hkv"] * d["D"] * live["positions"]
    return (size[config["weights_dtype"]] * (d["L"] * layer + d["E"] * d["V"])
            + size[config["compute_dtype"]] * kv)


def prefill_flops(ctx: dict):
    """2 flops a parameter a token multiplies (K experts of X), plus causal
    attention at the mix's mean prompt length."""
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "prefill_tokens_executed", 0)
    if not toks:
        return None
    d = ctx["dims"]
    layer = _attn_params(d) + d["E"] * d["X"] + d["K"] * 3 * d["E"] * d["Mx"]
    mean_len = ctx.get("mean_prompt_len") or 0.0
    return (2.0 * (d["L"] * layer + d["E"] * d["V"]) * toks
            + 2.0 * d["L"] * d["H"] * d["D"] * toks * mean_len)
