"""Weights from ``--seed``, made on the device by the benchmark itself.

The program's own initialisers are not used: the float32 reference has to
rebuild the very same numbers without taking anything the program made, so
both sides call this module. Every layer's leaves come from
``fold_in(leaf_key, layer)``; the stacked tree the program runs is a
``lax.map`` over layers of the same per-layer function the reference calls
one layer at a time, so the two agree bit for bit.

Conditioning: unit-variance embeddings, 1/sqrt(fan_in) matrices, and the two
residual outputs (``wo``, ``w_down``) scaled by 1/sqrt(2L), so the residual
stream stays O(1) through the depth as a trained model's does. The program's
``quant.init_quantized`` draws matrices with a gain of ~2.3, which at 32
layers turns bf16 rounding into 0.9 logit-std of noise (PERF.md, PR 21) and
leaves nothing for a reference to resolve.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from benchmark.weights_dims import dims_of as dims  # noqa: E402,F401


def llama_config_keys(config: dict) -> dict:
    """The same sizes under the program's ``LlamaConfig`` field names."""
    d = dims(config)
    return {"vocab_size": d["V"], "embed_dim": d["E"], "n_layers": d["L"],
            "n_heads": d["H"], "n_kv_heads": d["Hkv"], "head_dim": d["D"],
            "mlp_dim": d["M"], "rope_theta": d["theta"], "rms_eps": d["eps"],
            "tie_embeddings": bool(config.get("tie_word_embeddings", False))}


def root_key(seed: int):
    """The run's key. Made OUTSIDE any jitted function and handed in as an
    argument: a seed closed over becomes a constant of the program, and every
    new seed then compiles the 7 B-weight generator again (12 s a run on the
    v5e, chip run PR 23). ``--seed`` may pass 2**31; the high bits fold in."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _matrix_shapes(d: dict) -> dict:
    """name -> (fan_in, fan_out, gain) in the fused serving layout."""
    res = (2 * d["L"]) ** -0.5
    return {"wqkv": (d["E"], (d["H"] + 2 * d["Hkv"]) * d["D"], 1.0),
            "wo": (d["H"] * d["D"], d["E"], res),
            "wgu": (d["E"], 2 * d["M"], 1.0),
            "w_down": (d["M"], d["E"], res)}


def _norm(key, n):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


# ------------------------------------------------------------- serving
def serving_layer(key, layer, d: dict) -> dict:
    """One layer in the program's fused int8 layout: ``w`` int8 [in, out]
    uniform on -128..127 (one 8-bit draw each: ``randint`` costs several
    times as much over 7 G weights) and ``w_scale`` bf16 [1, out], varied by
    channel around the value that gives the matrix its 1/sqrt(fan_in)
    deviation."""
    out = {}
    for name, (fan_in, fan_out, gain) in _matrix_shapes(d).items():
        kq, ks = jax.random.split(jax.random.fold_in(_key(key, name), layer))
        out[name] = jax.lax.bitcast_convert_type(
            jax.random.bits(kq, (fan_in, fan_out), jnp.uint8), jnp.int8)
        base = gain * fan_in ** -0.5 / 73.9     # uniform bytes: std 73.9
        out[name + "_scale"] = (base * jax.random.uniform(
            ks, (1, fan_out), jnp.float32, 0.75, 1.25)).astype(jnp.bfloat16)
    for name in ("attn_norm", "mlp_norm"):
        out[name] = _norm(jax.random.fold_in(_key(key, name), layer), d["E"])
    return out


def serving_globals(key, d: dict) -> dict:
    return {"embedding": jax.random.normal(
                _key(key, "embedding"), (d["V"], d["E"]), jnp.bfloat16),
            "final_norm": _norm(_key(key, "final_norm"), d["E"]),
            "lm_head": (jax.random.normal(
                _key(key, "lm_head"), (d["E"], d["V"]), jnp.float32)
                * d["E"] ** -0.5).astype(jnp.bfloat16)}


def serving_tree(seed: int, d: dict) -> dict:
    """The whole int8 tree, stacked [L, ...], in one jitted call."""
    def build(key):
        tree = serving_globals(key, d)
        tree["layers"] = jax.lax.map(
            lambda l: serving_layer(key, l, d), jnp.arange(d["L"]))
        return tree
    return jax.jit(build)(root_key(seed))


def dense_f32(layer: dict, d: dict) -> dict:
    """A serving layer as the plain float32 matrices the reference
    multiplies by: int8 times scale, the fused leaves split by column."""
    def deq(name):
        return (layer[name].astype(jnp.float32)
                * layer[name + "_scale"].astype(jnp.float32))
    hd, kd = d["H"] * d["D"], d["Hkv"] * d["D"]
    wqkv, wgu = deq("wqkv"), deq("wgu")
    return {"wq": wqkv[:, :hd], "wk": wqkv[:, hd:hd + kd],
            "wv": wqkv[:, hd + kd:], "wo": deq("wo"),
            "w_gate": wgu[:, :d["M"]], "w_up": wgu[:, d["M"]:],
            "w_down": deq("w_down"),
            "attn_norm": layer["attn_norm"].astype(jnp.float32),
            "mlp_norm": layer["mlp_norm"].astype(jnp.float32)}


# ------------------------------------------------------------ training
def _train_shapes(d: dict) -> dict:
    res = (2 * d["L"]) ** -0.5
    hd, kd = d["H"] * d["D"], d["Hkv"] * d["D"]
    return {"wq": (d["E"], hd, 1.0), "wk": (d["E"], kd, 1.0),
            "wv": (d["E"], kd, 1.0), "wo": (hd, d["E"], res),
            "w_gate": (d["E"], d["M"], 1.0), "w_up": (d["E"], d["M"], 1.0),
            "w_down": (d["M"], d["E"], res)}


def training_layer(key, layer, d: dict) -> dict:
    out = {}
    for name, (fan_in, fan_out, gain) in _train_shapes(d).items():
        k = jax.random.fold_in(_key(key, "train." + name), layer)
        out[name] = (jax.random.normal(k, (fan_in, fan_out), jnp.float32)
                     * gain * fan_in ** -0.5).astype(jnp.bfloat16)
    for name in ("attn_norm", "mlp_norm"):
        out[name] = _norm(
            jax.random.fold_in(_key(key, "train." + name), layer), d["E"])
    return out


def training_globals(key, d: dict) -> dict:
    return {"embedding": (jax.random.normal(
                _key(key, "train.embedding"), (d["V"], d["E"]), jnp.float32)
                * 0.02).astype(jnp.bfloat16),
            "final_norm": _norm(_key(key, "train.final_norm"), d["E"]),
            "lm_head": (jax.random.normal(
                _key(key, "train.lm_head"), (d["E"], d["V"]), jnp.float32)
                * d["E"] ** -0.5).astype(jnp.bfloat16)}


def training_tree(key, d: dict) -> dict:
    """bf16 parameters in the program's training layout from
    ``root_key(seed)`` (traceable: the trainer jits it as its ``init_fn``)."""
    tree = training_globals(key, d)
    tree["layers"] = jax.lax.map(
        lambda l: training_layer(key, l, d), jnp.arange(d["L"]))
    return tree


def batch_tokens(seed: int, step: int, rows: int, seq: int, vocab: int):
    """Step ``step``'s rows, all different: [rows, seq + 1] int32 (numpy)."""
    import numpy as np

    rng = np.random.default_rng([seed, 7919, step])
    return rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)


def sample_positions(seed: int, leaf: str, size: int, n: int = 4096):
    """``n`` flat positions of a leaf, drawn from the seed (numpy): where
    the first gradient is read on both sides of the comparison."""
    import numpy as np

    rng = np.random.default_rng([seed, 49979687, zlib.crc32(leaf.encode())])
    return rng.integers(0, size, min(n, size))
