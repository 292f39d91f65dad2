"""Grouped-query attention that CHOOSES its keys by a learned index, with
routed SwiGLU experts: the family of the ``KeyeVL2`` public config's
language model (``sa_config``), run by the program's
``models/indexed_moe.py``.

Here: the sizes under their published keys, the program's configuration
object, bf16 weights from the seed in the program's layout, the PLAIN
float32 reference of one layer (below: no cache, no kernel, no threshold
search: the scores of a block of queries, ``lax.top_k`` over the causal
prefix, a softmax over exactly those, experts one at a time over the whole
sequence), its controls, and the least work
(``benchmark/opcounts/indexed_moe.py``).

The layer as written down (ISSUE 42, section 1; ``assumed`` in the
configuration's file names what the published config.json does not state):
``q`` and ``k`` take an RMSNorm over the head with a learned weight and rope
over the whole head in the halves layout; the index holds
``indexer_num_heads`` queries of ``indexer_head_dim`` and ONE key a position
under a LayerNorm (weight and bias), both rotated over their whole width
with the same theta, and float32 weights ``w = h W_w``; ``I[t, s] = sum_j
w[t, j] relu(qI[t, j] . kI[s])``; a query attends the ``topk`` positions ``s
<= t`` of largest ``I`` (all while ``t + 1 <= topk``), equal scores to the
lower position; the router is a float32 softmax over all experts, the chosen
renormalised.

Departures of the reference from the published implementation, each also a
comment where it happens: (1) every expert runs over the whole sequence with
a gate that is zero for the tokens it was not given: the same sum; (2) the
index scores and attention run a block of queries (and a kv head) at a time
so that a 32 k sequence's scores fit: the same choice, the same softmax; (3)
``lax.top_k`` gives the ``topk``-th largest score and the set is read back
from it (greater, or equal and among the lowest positions that still fit):
the set ``lax.top_k``'s own indices name, without a scatter
(``tests/test_indexed_moe.py`` holds the two together); (4) every token is a
text token: the three position ids of ``mrope_section`` agree and the
rotation is the ordinary one.
"""

from __future__ import annotations

from pathlib import Path

# seeded draws, the globals (embedding, final norm, untied head) and the head
# are the latent family's own: one uniform draw a weight, one key a name
from benchmark.families.latent_moe import (_globals, _key, _norm,  # noqa: F401
                                           _uniform, reference_globals)
from benchmark.opcounts import indexed_moe as ops

PROGRAM_FILE = (Path(__file__).resolve().parents[2]
                / "kubetorch_tpu" / "models" / "indexed_moe.py")
KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "moe_intermediate_size",
        "num_experts", "num_experts_per_tok", "sa_config", "rope_theta",
        "rms_norm_eps", "vocab_size", "max_position_embeddings",
        "compute_dtype", "weights_dtype")
SA_KEYS = ("indexer_num_heads", "indexer_head_dim", "indexer_num_kv_heads",
           "topk")
# published keys whose only supported value is the one given
FIXED = {"norm_topk_prob": True, "attention_bias": False,
         "tie_word_embeddings": False, "hidden_act": "silu",
         "mlp_only_layers": [], "decoder_sparse_step": 1,
         "use_sliding_window": False}
KIND = ops.KIND
QUERY_BLOCK = 512       # queries a pass of the reference's index and attention
# the learned weight of q's head norm is drawn around this (k's around 1):
# attention logits of deviation ~2, a softmax that leans on tens of its
# positions, not evenly on all (``assumed_why`` in the configuration's file)
Q_NORM_GAIN = 2.0


def dims(config: dict) -> dict:
    if not PROGRAM_FILE.is_file():
        raise LookupError(
            "this checkout's program has no models/indexed_moe.py: it "
            "cannot run a configuration of family 'indexed_moe'")
    missing = [k for k in KEYS if k not in config]
    missing += [f"sa_config.{k}" for k in SA_KEYS
                if k not in config.get("sa_config", {})]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    for key, only in FIXED.items():
        if key in config and config[key] != only:
            raise ValueError(
                f"family indexed_moe carries {key} = {only!r} only, the "
                f"configuration says {config[key]!r}")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("family indexed_moe carries ONE index key a "
                         "position (indexer_num_kv_heads = 1)")
    if config.get("num_local_experts", config["num_experts"]) != config[
            "num_experts"]:
        raise ValueError("num_local_experts differs from num_experts")
    # "Ld": no leading dense layer (``readers/latent_moe.py`` counts the
    # expert layers as L - Ld)
    return {"E": config["hidden_size"], "L": config["num_hidden_layers"],
            "Ld": 0, "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "Hi": sa["indexer_num_heads"], "Di": sa["indexer_head_dim"],
            "topk": sa["topk"], "Mx": config["moe_intermediate_size"],
            "X": config["num_experts"], "K": config["num_experts_per_tok"],
            "V": config["vocab_size"], "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "positions": config["max_position_embeddings"],
            "dtype": config["weights_dtype"]}


def controls() -> tuple:
    """``fp8``: float8_e4m3 operands in every matrix product but the
    router's and the index weights' (the step below the bf16 compute the
    file states); ``last_k``: the learned choice replaced by the most recent
    ``topk`` positions, everything else as the reference: a control of the
    MECHANISM (a program that chose wrongly would read like it)."""
    return ("fp8", "last_k")


def layer_kinds(d: dict) -> tuple:
    return (KIND,) * d["L"]


def program_config(config: dict, path: str, deployment: dict = None):
    from kubetorch_tpu.models import IndexedMoEConfig

    if path != "serve":
        raise NotImplementedError(
            "family indexed_moe has no training path: the trainer does not "
            "carry this decoder")
    d = dims(config)
    if deployment["max_len"] > d["positions"]:
        raise ValueError(
            f"the deployment serves {deployment['max_len']} positions, the "
            f"configuration has {d['positions']}")
    return IndexedMoEConfig(
        vocab_size=d["V"], embed_dim=d["E"], n_layers=d["L"],
        n_heads=d["H"], n_kv_heads=d["Hkv"], head_dim=d["D"],
        index_heads=d["Hi"], index_dim=d["Di"], index_topk=d["topk"],
        rope_theta=d["theta"], n_experts=d["X"], top_k=d["K"],
        expert_mlp_dim=d["Mx"], rms_eps=d["eps"],
        max_seq_len=deployment["max_len"], dtype=config["compute_dtype"],
        param_dtype=config["weights_dtype"])


# ------------------------------------------------ weights from the seed
def _shapes(d: dict) -> dict:
    """leaf -> (shape, fan_in, gain) of one layer in the program's layout
    (q | k | v and gate and up fused along the output; experts ``[X, in,
    out]``). The residual outputs are scaled by 1/sqrt(2L), as
    ``benchmark/weights.py`` does, so the stream stays O(1) through the
    depth."""
    res = (2 * d["L"]) ** -0.5
    E, HD = d["E"], d["H"] * d["D"]
    return {"wqkv": ((E, HD + 2 * d["Hkv"] * d["D"]), E, 1.0),
            "wo": ((HD, E), HD, res),
            "wiq": ((E, d["Hi"] * d["Di"]), E, 1.0),
            "wik": ((E, d["Di"]), E, 1.0),
            "we_gu": ((d["X"], E, 2 * d["Mx"]), E, 1.0),
            "we_down": ((d["X"], d["Mx"], E), d["Mx"], res)}


def _layer(key, layer, d: dict) -> dict:
    """Layer ``layer`` (may be traced) in the program's layout and dtype.
    The router and the index's weight projection are float32 (their outputs
    are float32 by the architecture)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(d["dtype"])
    out = {name: _uniform(_key(key, name, layer), shape,
                          gain * fan_in ** -0.5, dt)
           for name, (shape, fan_in, gain) in _shapes(d).items()}
    for name, n in (("attn_norm", d["E"]), ("mlp_norm", d["E"]),
                    ("q_norm", d["D"]), ("k_norm", d["D"]),
                    ("ik_norm", d["Di"])):
        out[name] = _norm(_key(key, name, layer), n, dt)
    out["q_norm"] = (Q_NORM_GAIN * out["q_norm"].astype(jnp.float32)
                     ).astype(dt)
    out["ik_bias"] = (0.1 * jax.random.normal(
        _key(key, "ik_bias", layer), (d["Di"],), jnp.float32)).astype(dt)
    for name, n in (("router", d["X"]), ("wiw", d["Hi"])):
        out[name] = jax.random.normal(
            _key(key, name, layer), (d["E"], n),
            jnp.float32) * d["E"] ** -0.5
    return out


def serving_tree(seed: int, d: dict) -> dict:
    """The program's tree: globals, and one stack ``[L, ...]``."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    def build(key):
        tree = _globals(key, d)
        tree["layers"] = jax.lax.map(lambda l: _layer(key, l, d),
                                     jnp.arange(d["L"], dtype=jnp.int32))
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


def layer_norm(x, weight, bias, eps):
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def index_scores(qi, ki, w):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for a block of
    queries: qi [bq, Hi, Di], ki [T, Di], w [bq, Hi] -> [bq, T] float32, a
    head at a time; a zero of either sign is +0."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.model import HIGHEST

    def one_head(total, args):
        qj, wj = args                                     # [bq, Di], [bq]
        s = jnp.einsum("td,sd->ts", qj, ki, precision=HIGHEST)
        return total + wj[:, None] * jax.nn.relu(s), None

    total, _ = jax.lax.scan(
        one_head, jnp.zeros((qi.shape[0], ki.shape[0]), jnp.float32),
        (qi.transpose(1, 0, 2), w.T))
    return jnp.where(total == 0, 0.0, total)


def choice(scores, seen, topk: int):
    """The ``topk`` largest of each row of ``scores`` [bq, T] among ``seen``
    [bq, T], equal scores to the lower position, as a mask. Departure (3):
    ``lax.top_k``'s ``topk``-th value is the threshold; what lies above it
    belongs, and of what equals it the lowest positions that still fit."""
    import jax
    import jax.numpy as jnp

    k = min(topk, scores.shape[1])
    masked = jnp.where(seen, scores, -jnp.inf)
    threshold = jax.lax.top_k(masked, k)[0][:, -1:]
    above = seen & (masked > threshold)
    equal = seen & (masked == threshold)
    room = topk - jnp.sum(above, -1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, -1) <= room))


def _attention(q, k, v, qi, ki, w, positions, d: dict, last_k: bool):
    """Each query over the positions it chose. q [T, H, D]; k, v [T, Hkv,
    D]; qi [T, Hi, Di]; ki [T, Di]; w [T, Hi] -> [T, H * D]. Departure (2):
    ``QUERY_BLOCK`` queries at a time, and under them a kv head at a time.
    ``last_k`` (a control): the choice is the most recent ``topk`` positions
    whatever the index says."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.model import HIGHEST

    T, H, D = q.shape
    Hkv, topk = k.shape[1], d["topk"]
    G = H // Hkv
    bq = min(QUERY_BLOCK, T)
    if T % bq:
        raise ValueError(f"{T} positions are not whole blocks of {bq}")
    nb = T // bq
    qg = q.reshape(nb, bq, Hkv, G, D).transpose(0, 2, 3, 1, 4)
    kh, vh = k.transpose(1, 0, 2), v.transpose(1, 0, 2)      # [Hkv, T, D]

    def one_block(args):
        qb, qib, wb, pq = args            # [Hkv,G,bq,D] [bq,Hi,Di] [bq,Hi]
        seen = positions[None, :] <= pq[:, None]                    # [bq,T]
        if last_k:
            keep = seen & (pq[:, None] - positions[None, :] < topk)
        else:
            keep = choice(index_scores(qib, ki, wb), seen, topk)

        def one_head(args):
            qh, kk, vv = args                       # [G,bq,D], [T,D], [T,D]
            s = jnp.einsum("gtd,sd->gts", qh, kk,
                           precision=HIGHEST) * D ** -0.5
            p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), -1)
            return jnp.einsum("gts,sd->gtd", p, vv, precision=HIGHEST)

        return jax.lax.map(one_head, (qb, kh, vh))            # [Hkv,G,bq,D]

    out = jax.lax.map(one_block, (qg, qi.reshape(nb, bq, *qi.shape[1:]),
                                  w.reshape(nb, bq, -1),
                                  positions.reshape(nb, bq)))
    return out.transpose(0, 3, 1, 2, 4).reshape(T, H * D)


def block(x, w, positions, d: dict, lower, kind: str):
    """One layer on one sequence, x [T, E], float32 at the highest matmul
    precision. ``lower``: None, or one of ``controls()``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import model

    if lower not in (None,) + controls():
        raise ValueError(f"unknown control {lower!r}")
    every = "fp8" if lower == "fp8" else None
    T = x.shape[0]
    H, Hkv, D, Hi, Di = d["H"], d["Hkv"], d["D"], d["Hi"], d["Di"]
    h = model.rms_norm(x, w["attn_norm"], d["eps"])
    q = model.matmul(h, w["wq"], every).reshape(T, H, D)
    k = model.matmul(h, w["wk"], every).reshape(T, Hkv, D)
    v = model.matmul(h, w["wv"], every).reshape(T, Hkv, D)
    # departure (4): one position id a token, the ordinary rotation
    q = model.rope(model.rms_norm(q, w["q_norm"], d["eps"]), positions,
                   d["theta"])
    k = model.rope(model.rms_norm(k, w["k_norm"], d["eps"]), positions,
                   d["theta"])
    qi = model.rope(model.matmul(h, w["wiq"], every).reshape(T, Hi, Di),
                    positions, d["theta"])
    ki = model.rope(layer_norm(model.matmul(h, w["wik"], every),
                               w["ik_norm"], w["ik_bias"],
                               d["eps"])[:, None, :],
                    positions, d["theta"])[:, 0]
    # the index's weights are float32 whatever the control, as the router's
    wi = model.matmul(h, w["wiw"])
    attn = _attention(q, k, v, qi, ki, wi, positions, d, lower == "last_k")
    x = x + model.matmul(attn, w["wo"], every)
    m = model.rms_norm(x, w["mlp_norm"], d["eps"])
    p = jax.nn.softmax(model.matmul(m, w["router"]), axis=-1)     # [T, X]
    top, chosen = jax.lax.top_k(p, d["K"])
    g = top / jnp.sum(top, -1, keepdims=True)                     # [T, K]

    def one_expert(y, e_w):
        e, gate, up, down = e_w
        # departure (1): the expert sees every token, weighted 0 where it
        # was not chosen
        ge = jnp.sum(jnp.where(chosen == e, g, 0.0), -1)          # [T]
        ff = jax.nn.silu(model.matmul(m, gate, every)) * model.matmul(
            m, up, every)
        return y + ge[:, None] * model.matmul(ff, down, every), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(d["X"]), w["we_gate"], w["we_up"], w["we_down"]))
    return x + routed


def head(x, final_norm, lm_head, d: dict, lower):
    from benchmark.reference import model

    return model.head(x, final_norm, lm_head, d,
                      "fp8" if lower == "fp8" else None)


# ------------------------------------- least work, from the run's context
def decode_step_bytes(ctx: dict):
    """The least bytes of one decode step: attention, index and router
    weights and the head once; the experts the step's rows TOUCHED (from the
    program's counter); the index keys of the active rows' live positions
    and K and V of the positions they CHOSE (the program's counter: at most
    ``topk`` a row a layer, whatever the row holds). ``None`` without a
    traced span."""
    live = (ctx.get("trace_live") or {}).get("positions")
    delta = ctx.get("trace_stats_delta") or {}
    if (live is None or not delta.get("moe_expert_slots")
            or not delta.get("decode_sparse_positions_chosen")):
        return None
    d = ctx["dims"]
    steps = delta["moe_expert_slots"] / (d["X"] * d["L"])
    return ops.decode_step_bytes(
        d, delta["moe_experts_touched"] / steps, live,
        delta["decode_sparse_positions_chosen"] / steps)


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
        d, toks, share * sum(ops.chosen_pairs(n, d["topk"]) for n in lens),
        share * sum(ops.index_pairs(n, d["topk"]) for n in lens))
