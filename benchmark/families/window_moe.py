"""Window and full attention mixed, with routed ReGLU experts chosen before
attention: the family of the ``smallthinker`` public configs, run by the
program's ``models/window_moe.py``.

Here: the sizes under their published keys, the program's configuration
object, bf16 weights from the seed in the program's layout, the PLAIN
float32 reference of one layer (below: no cache, no ring, no kernel, the
mask built from positions, experts one at a time over the whole sequence),
its lower-precision controls, and the least work
(``benchmark/opcounts/window_moe.py``).

The layer as written down (ISSUE 40; ``assumed`` in the configuration's
file names what the published config.json does not state): the router reads
the layer's INPUT, before the input norm and before attention; no bias, no
q/k norm; rope over the whole head in the halves layout on the window
layers, no position encoding on the full layers; the weights of the chosen
experts are the softmax over their scores.

Departures of the reference from the published implementation, each also a
comment where it happens: (1) every expert runs over the whole sequence
with a gate that is zero for the tokens it was not given, instead of
gathering them: the same sum; (2) attention runs a kv head and a block of
queries at a time so that a 16 k sequence's scores fit: the same softmax;
(3) a layer whose ``rope_layout`` and ``sliding_window_layout`` disagree
is refused, not computed (the published layouts are one list twice).
"""

from __future__ import annotations

from pathlib import Path

# seeded draws, the globals (embedding, final norm, untied head) and the head
# are the latent family's own: one uniform draw a weight, one key a name
from benchmark.families.latent_moe import (_globals, _key, _norm,  # noqa: F401
                                           _uniform, head,
                                           reference_globals)
from benchmark.opcounts import window_moe as ops

PROGRAM_FILE = (Path(__file__).resolve().parents[2]
                / "kubetorch_tpu" / "models" / "window_moe.py")
KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "moe_ffn_hidden_size",
        "moe_num_primary_experts", "moe_num_active_primary_experts",
        "sliding_window_size", "sliding_window_layout", "rope_layout",
        "rope_theta", "rms_norm_eps", "vocab_size",
        "max_position_embeddings", "compute_dtype", "weights_dtype")
# published keys whose only supported value is the one given
FIXED = {"moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
         "rope_scaling": None, "tie_word_embeddings": False}
FULL, WINDOW = ops.FULL, ops.WINDOW
STACK = {FULL: "full", WINDOW: "window"}
QUERY_BLOCK = 512       # queries a pass of the reference's attention


def dims(config: dict) -> dict:
    if not PROGRAM_FILE.is_file():
        raise LookupError(
            "this checkout's program has no models/window_moe.py: it "
            "cannot run a configuration of family 'window_moe'")
    missing = [k for k in KEYS if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    for key, only in FIXED.items():
        if key in config and config[key] != only:
            raise ValueError(
                f"family window_moe carries {key} = {only!r} only, the "
                f"configuration says {config[key]!r}")
    L = config["num_hidden_layers"]
    rope, window = config["rope_layout"], config["sliding_window_layout"]
    if len(rope) < L or len(window) < L:
        raise ValueError("the layouts are shorter than num_hidden_layers")
    # the first L entries of the published layouts (a cut of depth keeps
    # the lists whole); departure (3)
    if list(rope[:L]) != list(window[:L]):
        raise ValueError(
            "family window_moe rotates exactly the window layers: "
            "rope_layout and sliding_window_layout must agree")
    # "Ld": no leading dense layer (``readers/latent_moe.py`` counts the
    # expert layers as L - Ld)
    return {"E": config["hidden_size"], "L": L, "Ld": 0,
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "Mx": config["moe_ffn_hidden_size"],
            "X": config["moe_num_primary_experts"],
            "K": config["moe_num_active_primary_experts"],
            "W": config["sliding_window_size"],
            "kinds": tuple(WINDOW if w else FULL for w in window[:L]),
            "V": config["vocab_size"], "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "positions": config["max_position_embeddings"],
            "dtype": config["weights_dtype"]}


def controls() -> tuple:
    """``fp8``: float8_e4m3 operands in every matrix product but the
    router's (the step below the bf16 compute the file states);
    ``fp8_experts``: in the routed experts' products alone."""
    return ("fp8", "fp8_experts")


def layer_kinds(d: dict) -> tuple:
    return d["kinds"]


def program_config(config: dict, path: str, deployment: dict = None):
    from kubetorch_tpu.models import WindowMoEConfig

    if path != "serve":
        raise NotImplementedError(
            "family window_moe has no training path: the trainer does not "
            "carry this decoder")
    d = dims(config)
    if deployment["max_len"] > d["positions"]:
        raise ValueError(
            f"the deployment serves {deployment['max_len']} positions, the "
            f"configuration has {d['positions']}")
    return WindowMoEConfig(
        vocab_size=d["V"], embed_dim=d["E"], layer_types=d["kinds"],
        n_heads=d["H"], n_kv_heads=d["Hkv"], head_dim=d["D"],
        window=d["W"], rope_theta=d["theta"], n_experts=d["X"],
        top_k=d["K"], expert_mlp_dim=d["Mx"], rms_eps=d["eps"],
        max_seq_len=deployment["max_len"], dtype=config["compute_dtype"],
        param_dtype=config["weights_dtype"])


# ------------------------------------------------ weights from the seed
def _shapes(d: dict) -> dict:
    """leaf -> (shape, fan_in, gain) of one layer in the program's layout
    (q | k | v and gate and up fused along the output; experts ``[X, in,
    out]``). The residual outputs are scaled by 1/sqrt(2L), as
    ``benchmark/weights.py`` does, so the stream stays O(1) through the
    depth (and with it the router's scores, which read the stream raw)."""
    res = (2 * d["L"]) ** -0.5
    E, HD = d["E"], d["H"] * d["D"]
    return {"wqkv": ((E, HD + 2 * d["Hkv"] * d["D"]), E, 1.0),
            "wo": ((HD, E), HD, res),
            "we_gu": ((d["X"], E, 2 * d["Mx"]), E, 1.0),
            "we_down": ((d["X"], d["Mx"], E), d["Mx"], res)}


def _layer(key, layer, d: dict) -> dict:
    """Layer ``layer`` (its index in the whole stack; may be traced) in the
    program's layout and dtype; both kinds have one shape. The router is
    float32 (scores are float32 by the architecture)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(d["dtype"])
    out = {name: _uniform(_key(key, name, layer), shape,
                          gain * fan_in ** -0.5, dt)
           for name, (shape, fan_in, gain) in _shapes(d).items()}
    for name in ("attn_norm", "mlp_norm"):
        out[name] = _norm(_key(key, name, layer), d["E"], dt)
    out["router"] = jax.random.normal(
        _key(key, "router", layer), (d["E"], d["X"]),
        jnp.float32) * d["E"] ** -0.5
    return out


def serving_tree(seed: int, d: dict) -> dict:
    """The program's tree: globals, and one stack ``[n, ...]`` a kind, each
    holding its kind's layers in layer order."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    def build(key):
        tree = _globals(key, d)
        for kind, name in STACK.items():
            at = [l for l, k in enumerate(d["kinds"]) if k == kind]
            if at:
                tree[name] = jax.lax.map(lambda l: _layer(key, l, d),
                                         jnp.asarray(at, jnp.int32))
        return tree
    return jax.jit(build)(weights.root_key(seed))


# ----------------------------------------------------------- the reference
def reference_layer(key, layer, d: dict, kind: str, path: str) -> dict:
    """The very values the program's tree holds, as the plain float32
    matrices ``block`` multiplies by: the fused leaves split."""
    import jax
    import jax.numpy as jnp

    w = jax.tree.map(lambda x: x.astype(jnp.float32), _layer(key, layer, d))
    HD, KD = d["H"] * d["D"], d["Hkv"] * d["D"]
    qkv = w.pop("wqkv")
    w["wq"], w["wk"], w["wv"] = (qkv[:, :HD], qkv[:, HD:HD + KD],
                                 qkv[:, HD + KD:])
    gu = w.pop("we_gu")
    w["we_gate"], w["we_up"] = gu[..., :d["Mx"]], gu[..., d["Mx"]:]
    return w


def _attention(q, k, v, positions, window):
    """Grouped-query attention with the mask built from positions: query
    ``i`` sees key ``j`` iff ``j <= i`` and, under a window, ``i - window <
    j``. q [T, H, D]; k, v [T, Hkv, D] -> [T, H * D]. Departure (2): a kv
    head at a time, and its queries ``QUERY_BLOCK`` at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.model import HIGHEST

    T, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    bq = min(QUERY_BLOCK, T)
    if T % bq:
        raise ValueError(f"{T} positions are not whole blocks of {bq}")
    qg = q.reshape(T // bq, bq, Hkv, G, D).transpose(2, 0, 3, 1, 4)
    pos_q = positions.reshape(T // bq, bq)

    def one_head(args):
        qh, kh, vh = args                   # [nb,G,bq,D], [T,D], [T,D]

        def one_block(args):
            qb, pq = args                                   # [G,bq,D], [bq]
            seen = positions[None, :] <= pq[:, None]                # [bq,T]
            if window is not None:
                seen = seen & (pq[:, None] - positions[None, :] < window)
            s = jnp.einsum("gtd,sd->gts", qb, kh,
                           precision=HIGHEST) * D ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("gts,sd->gtd", p, vh, precision=HIGHEST)

        return jax.lax.map(one_block, (qh, pos_q))               # [nb,G,bq,D]

    out = jax.lax.map(one_head, (qg, k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2)))   # [Hkv,nb,G,bq,D]
    return out.transpose(1, 3, 0, 2, 4).reshape(T, H * D)


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
    H, Hkv, D = d["H"], d["Hkv"], d["D"]
    # the router reads the layer's input, ahead of the norm and of
    # attention; its scores are float32 whatever the control
    r = model.matmul(x, w["router"])                              # [T, X]
    top, chosen = jax.lax.top_k(r, d["K"])
    # softmax over all renormalised over the chosen = softmax of the chosen
    g = jax.nn.softmax(top, axis=-1)                              # [T, K]
    h = model.rms_norm(x, w["attn_norm"], d["eps"])
    q = model.matmul(h, w["wq"], every).reshape(T, H, D)
    k = model.matmul(h, w["wk"], every).reshape(T, Hkv, D)
    v = model.matmul(h, w["wv"], every).reshape(T, Hkv, D)
    if kind == WINDOW:
        q = model.rope(q, positions, d["theta"])
        k = model.rope(k, positions, d["theta"])
    attn = _attention(q, k, v, positions, d["W"] if kind == WINDOW else None)
    x = x + model.matmul(attn, w["wo"], every)
    m = model.rms_norm(x, w["mlp_norm"], d["eps"])

    def one_expert(y, e_w):
        e, gate, up, down = e_w
        # departure (1): the expert sees every token, weighted 0 where it
        # was not chosen
        ge = jnp.sum(jnp.where(chosen == e, g, 0.0), -1)          # [T]
        ff = jax.nn.relu(model.matmul(m, gate, expert)) * model.matmul(
            m, up, expert)
        return y + ge[:, None] * model.matmul(ff, down, expert), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(d["X"]), w["we_gate"], w["we_up"], w["we_down"]))
    return x + routed


# ------------------------------------- least work, from the run's context
def decode_step_bytes(ctx: dict):
    """The least bytes of one decode step: attention and router weights and
    the head once; the experts the step's rows TOUCHED (from the program's
    counter, not all of them); the live positions of the active rows in the
    full layers and ``min(depth, window)`` of them in the window layers
    (the span's counters give the share). ``None`` without a traced span."""
    live = (ctx.get("trace_live") or {}).get("positions")
    delta = ctx.get("trace_stats_delta") or {}
    if (live is None or not delta.get("moe_expert_slots")
            or not delta.get("decode_kv_positions_live")):
        return None
    d = ctx["dims"]
    steps = delta["moe_expert_slots"] / (d["X"] * d["L"])
    in_window = (delta.get("decode_window_positions_live", 0)
                 / delta["decode_kv_positions_live"])
    return ops.decode_step_bytes(d, delta["moe_experts_touched"] / steps,
                                 live, live * in_window)


def prefill_flops(ctx: dict):
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "prefill_tokens_executed", 0)
    lens = [r.prompt_len for r in ctx.get("records") or []]
    if not toks or not lens:
        return None
    # the span's admissions are not told apart by length: its prompt
    # tokens at the run's own mix of lengths
    d, share = ctx["dims"], toks / sum(lens)
    return ops.prefill_flops(
        d, toks, share * sum(n * (n + 1) / 2.0 for n in lens),
        share * sum(ops.band_pairs(n, d["W"]) for n in lens))
