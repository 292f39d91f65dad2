"""The dense GQA/RoPE/SwiGLU decoder (Llama, Mistral): the first family.

It only NAMES what the benchmark already holds: sizes in ``weights_dims.py``,
weights from a seed in ``weights.py``, the float32 layer in
``reference/model.py``, least work in ``opcounts/llama_dense.py``. Those
files and their jitted functions are as they were, so the executables, the
weights of a seed and the reference's numbers are what they were. The
program's configuration object is ``LlamaConfig``.
"""

from __future__ import annotations

from benchmark.opcounts import llama_dense as ops
from benchmark.weights_dims import dims_of as dims  # noqa: F401


def controls() -> tuple:
    """The ``lower`` names ``reference/model.py::matmul`` knows: int4
    weights (below the int8 served), fp8 operands (below bf16 compute)."""
    return ("w4", "fp8")


def layer_kinds(d: dict) -> tuple:
    return ("dense",) * d["L"]


def program_config(config: dict, path: str, deployment: dict = None):
    """``LlamaConfig`` of the ``serve`` path (sized by the traffic file's
    ``deployment``) or of the ``train`` path (the file's ``train`` group)."""
    from benchmark import weights
    from kubetorch_tpu.models import LlamaConfig

    keys = weights.llama_config_keys(config)
    if path == "serve":
        return LlamaConfig(**keys, max_seq_len=deployment["max_len"],
                           remat=False, dtype=config["compute_dtype"],
                           param_dtype=config["compute_dtype"])
    tr = config["train"]
    return LlamaConfig(**keys, max_seq_len=tr["seq"], remat=True,
                       remat_policy=tr["remat_policy"],
                       attn_impl=tr["attn_impl"], xent_chunk=tr["xent_chunk"],
                       dtype=config["compute_dtype"],
                       param_dtype=config["weights_dtype"])


def program_leaf(kind: str, name: str) -> str:
    """Where the program keeps leaf ``name`` of its ``kind`` layers: one
    stack [L, ...] under ``layers``."""
    return "layers/" + name


def serving_tree(seed: int, d: dict) -> dict:
    from benchmark import weights

    return weights.serving_tree(seed, d)


def training_tree(key, d: dict) -> dict:
    from benchmark import weights

    return weights.training_tree(key, d)


# ----------------------------------------------------------- the reference
def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def reference_globals(key, d: dict, path: str) -> dict:
    """``embedding``, ``final_norm``, ``lm_head`` of the ``serve`` or the
    ``train`` path in float32 (traceable)."""
    from benchmark import weights

    make = {"serve": weights.serving_globals,
            "train": weights.training_globals}[path]
    return _f32(make(key, d))


def reference_layer(key, layer, d: dict, kind: str, path: str) -> dict:
    """Layer ``layer`` (may be traced; ``kind`` is static) as the plain
    float32 matrices ``block`` multiplies by."""
    from benchmark import weights

    if path == "serve":
        return weights.dense_f32(weights.serving_layer(key, layer, d), d)
    return _f32(weights.training_layer(key, layer, d))


def block(x, w, positions, d: dict, lower, kind: str):
    from benchmark.reference import model

    return model.block(x, w, positions, d, lower)


def head(x, final_norm, lm_head, d: dict, lower):
    from benchmark.reference import model

    return model.head(x, final_norm, lm_head, d, lower)


# ------------------------------------- least work, from the run's context
def decode_step_bytes(ctx: dict):
    live = (ctx.get("trace_live") or {}).get("positions")
    if live is None:
        return None
    return ops.decode_step_bytes(ctx["dims"], ctx["config"]["kv_dtype"], live)


def prefill_flops(ctx: dict):
    """Attention of the traced tokens at the mix's mean prompt length: a
    chunk at depth p attends p positions, the mean over a prompt is n/2."""
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "prefill_tokens_executed", 0)
    if not toks:
        return None
    mean_len = ctx.get("mean_prompt_len") or 0.0
    return ops.prefill_flops(ctx["dims"], toks, toks * mean_len)


def train_flops_per_token(ctx: dict) -> float:
    return ops.train_flops_per_token(ctx["dims"], ctx["seq"])
