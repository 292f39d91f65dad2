"""Delta-rule layers whose state decays a channel (KDA) beside
latent-attention layers (MLA), five to one, in front of group-limited
sigmoid-routed experts of which a SHARE is held: the family of the
``bailing_hybrid`` public config, run by the program's
``models/hybrid_latent_moe.py``.

Here: the sizes under their published keys, the program's configuration
object, bf16 weights from the seed in the program's layout, the PLAIN float32
reference of one layer (below), its controls, and the least work
(``benchmark/opcounts/hybrid_latent_moe.py``).

The layer, as ISSUE 47 wrote it down (what the config's keys do not say is
the configuration file's ``assumed``). Pre-norm residual blocks: ``x' = x +
mixer(RMSNorm(x))``, ``x_next = x' + ffn(RMSNorm(x'))``. Published layer ``i``
is an MLA layer where ``(i + 1) % layer_group_size == 0`` and a KDA layer
otherwise; the first ``first_k_dense_replace`` layers have a dense SwiGLU,
the rest the expert layer.

- KDA mixer on ``n``: ``q~, k~, v~ = n W`` (H x dk, H x dk, H x dv); a
  depthwise causal convolution of ``short_conv_kernel_size`` taps, zero
  history, then SiLU; ``q = q~/|q~| dk^-1/2``, ``k = k~/|k~|``; ``a_t =
  kda_lower_bound sigmoid(exp(A_log_h) (n W_f + dt_bias))``, one a channel;
  ``beta_t = sigmoid(n W_b)``; ``S_0 = 0``, ``S_t = (I - beta_t k_t k_t^T)
  Diag(e^(a_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` (HERE: one
  token at a time, never chunked); out ``W_o [RMSNorm_head(o_t) * sigmoid(n
  W_g)]``.
- MLA mixer: the DeepSeek-V3 form, always EXPANDED here (keys and values a
  head made from the latent, no absorption, no cache), then one gate a head:
  ``o_h <- o_h sigmoid(n W_gate)_h`` before ``W_o``.
- Expert layer on ``m``: ``s = sigmoid(m W_r)`` over all ``router_width``
  experts; groups of consecutive experts, a group's score the sum of its two
  largest ``s + b``; the ``topk_group`` best groups stay, the
  ``num_experts_per_tok`` largest ``s + b`` among their experts are chosen;
  ``g_i = routed_scaling_factor s_i / sum_chosen s_j``; ``y = sum_i g_i
  E_i(m) + S(m)``. **The same share as the program**: only the experts
  ``experts_held`` and the shared expert add to the stream; what the absent
  ones would add is left out here as there.

Departures of the reference from a published implementation: rope rotates
the interleaved pairs in place (the same scores); every held expert runs
over the whole sequence with a gate that is zero for the tokens it was not
given (the same sum); attention takes its queries in blocks (the same
softmax rows) so that 32768 positions fit.
"""

from __future__ import annotations

from pathlib import Path

from benchmark.families.latent_moe import _key, _norm, _rope_pairs, _uniform
from benchmark.opcounts import hybrid_latent_moe as ops

PROGRAM_FILE = (Path(__file__).resolve().parents[2]
                / "kubetorch_tpu" / "models" / "hybrid_latent_moe.py")
KDA_DENSE, KDA_MOE, MLA_MOE = "kda_dense", "kda_moe", "mla_moe"
KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads", "head_dim",
        "layer_group_size", "first_k_dense_replace", "short_conv_kernel_size",
        "kda_lower_bound", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "intermediate_size",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "num_experts", "num_experts_per_tok", "num_shared_experts", "n_group",
        "topk_group", "routed_scaling_factor", "norm_topk_prob",
        "vocab_size", "rope_theta", "rms_norm_eps", "compute_dtype",
        "weights_dtype")
# published keys whose only supported value is the one given
FIXED = {"q_lora_rank": None, "num_kv_heads_for_linear_attn": 0,
         "scoring_func": "sigmoid", "score_function": "sigmoid",
         "topk_method": "noaux_tc", "moe_router_enable_expert_bias": True,
         "rope_interleave": True, "rope_scaling": None, "use_mla_nope": False,
         "use_bias": False, "use_qkv_bias": False, "use_qk_norm": True,
         "tie_word_embeddings": False, "hidden_act": "silu",
         "linear_silu": True, "kda_safe_gate": True, "no_kda_lora": True,
         "use_kda_lora": False, "group_norm_size": 1, "use_nGPT": False,
         "gated_attention_proj_granularity_type": "head_wise",
         "scale_router_input": False, "value_norm": False,
         "up_proj_norm": False}
# the decay's two learned vectors, drawn as the third family's are (the
# file's ``assumed``): ``exp(A_log) ~ U(0, 16)``, ``dt_bias`` the inverse
# softplus of a step log-uniform in [1e-3, 1e-1]
A_MAX, DT_MIN, DT_MAX = 16.0, 1e-3, 1e-1
_QUERY_BLOCK = 2048     # the reference's attention takes queries in blocks


def dims(config: dict) -> dict:
    if not PROGRAM_FILE.is_file():
        raise LookupError(
            "this checkout's program has no models/hybrid_latent_moe.py: it "
            "cannot run a configuration of family 'hybrid_latent_moe'")
    missing = [k for k in KEYS if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    for key, only in FIXED.items():
        if key in config and config[key] != only:
            raise ValueError(
                f"family hybrid_latent_moe carries {key} = {only!r} only, "
                f"the configuration says {config[key]!r}")
    published = config.get("published", {})
    L = config["num_hidden_layers"]
    run = list(config.get("layers_run", range(L)))
    group = config["layer_group_size"]
    dense_to = published.get("first_k_dense_replace",
                             config["first_k_dense_replace"])
    kinds = tuple(
        MLA_MOE if (i + 1) % group == 0
        else KDA_DENSE if i < dense_to else KDA_MOE for i in run)
    if len(run) != L or sorted(run) != run:
        raise ValueError(f"layers_run must name {L} published layers in order")
    if any((i + 1) % group == 0 and i < dense_to for i in run):
        raise ValueError("a dense MLA layer is not carried")
    if kinds.count(KDA_DENSE) != config["first_k_dense_replace"]:
        raise ValueError("first_k_dense_replace must count the dense layers "
                         "among layers_run")
    width = published.get("num_experts", config["num_experts"])
    first, count = config.get("experts_held", (0, config["num_experts"]))
    if count != config["num_experts"]:
        raise ValueError("num_experts must count the experts held")
    if config["moe_shared_expert_intermediate_size"] != config[
            "moe_intermediate_size"]:
        raise ValueError("the shared expert must have the routed experts' "
                         "width")
    limits = tuple(
        float(max(config.get(name, [0] * (i + 1))[i] for name in (
            "expert_swiglu_limit_list", "share_expert_swiglu_limit_list")))
        for i in run)
    H = config["num_attention_heads"]
    return {"E": config["hidden_size"], "L": L, "kinds": kinds, "H": H,
            "dk": config["head_dim"], "dv": config["head_dim"],
            "K": config["short_conv_kernel_size"],
            "lower": float(config["kda_lower_bound"]),
            "dn": config["qk_nope_head_dim"],
            "dr": config["qk_rope_head_dim"], "dvh": config["v_head_dim"],
            "r": config["kv_lora_rank"], "Md": config["intermediate_size"],
            "Mx": config["moe_intermediate_size"],
            "Xr": width, "X": count, "first": first,
            "G": config["n_group"], "Gk": config["topk_group"],
            "Kx": config["num_experts_per_tok"],
            "Ns": config["num_shared_experts"],
            "scale": float(config["routed_scaling_factor"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "limits": limits,
            "V": config["vocab_size"], "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "bias_std": float(config.get("router_bias_std", 0.05)),
            "dtype": config["weights_dtype"]}


def controls() -> tuple:
    """``fp8``: float8_e4m3 operands in every product with a weight matrix
    but the router's (the step below the bf16 compute the file states);
    ``scalar_decay``: a head's ``dk`` log-decays replaced by their mean, what
    a gated-delta layer (one decay a head) would compute: a control of the
    MECHANISM; ``ungrouped``: the ``num_experts_per_tok`` largest ``s + b``
    over all experts, no group kept or dropped."""
    return ("fp8", "scalar_decay", "ungrouped")


def layer_kinds(d: dict) -> tuple:
    return d["kinds"]


def program_config(config: dict, path: str, deployment: dict = None):
    from kubetorch_tpu.models import HybridLatentMoEConfig

    if path != "serve":
        raise NotImplementedError(
            "family hybrid_latent_moe has no training path: the chunked scan "
            "has no backward and the trainer does not carry this decoder")
    d = dims(config)
    return HybridLatentMoEConfig(
        vocab_size=d["V"], embed_dim=d["E"], layer_types=d["kinds"],
        kda_heads=d["H"], kda_key_dim=d["dk"], kda_value_dim=d["dv"],
        conv_width=d["K"], decay_lower_bound=d["lower"], n_heads=d["H"],
        qk_nope_dim=d["dn"], qk_rope_dim=d["dr"], v_head_dim=d["dvh"],
        kv_latent_dim=d["r"], rope_theta=d["theta"], dense_mlp_dim=d["Md"],
        n_experts_routed=d["Xr"], experts_held=(d["first"], d["X"]),
        n_group=d["G"], topk_group=d["Gk"], top_k=d["Kx"],
        expert_mlp_dim=d["Mx"], n_shared_experts=d["Ns"],
        routed_scale=d["scale"], norm_topk=d["norm_topk"],
        swiglu_limits=d["limits"], rms_eps=d["eps"],
        max_seq_len=deployment["max_len"], dtype=config["compute_dtype"],
        param_dtype=config["weights_dtype"])


# ------------------------------------------------ weights from the seed
def _shapes(d: dict, kind: str) -> dict:
    """leaf -> (shape, fan_in, gain) of one layer's matrices in the program's
    layout. The residual outputs are scaled by 1/sqrt(2L), as
    ``benchmark/weights.py`` does, so the stream stays O(1) through the
    depth."""
    res = (2 * d["L"]) ** -0.5
    E, H = d["E"], d["H"]
    if kind == MLA_MOE:
        out = {"wq": ((E, H * (d["dn"] + d["dr"])), E, 1.0),
               "wkv_a": ((E, d["r"] + d["dr"]), E, 1.0),
               "wkv_b": ((d["r"], H * (d["dn"] + d["dvh"])), d["r"], 1.0),
               "wgate": ((E, H), E, 1.0),
               "wo": ((H * d["dvh"], E), H * d["dvh"], res)}
    else:
        dk, dv = d["dk"], d["dv"]
        chan = H * (2 * dk + dv)
        out = {"wqkv": ((E, chan), E, 1.0),
               "conv_w": ((d["K"], chan), d["K"], 1.0),
               "wf": ((E, H * dk), E, 1.0), "wb": ((E, H), E, 1.0),
               "wg": ((E, H * dv), E, 1.0),
               "wo": ((H * dv, E), H * dv, res)}
    if kind == KDA_DENSE:
        out.update({"w_gu": ((E, 2 * d["Md"]), E, 1.0),
                    "w_down": ((d["Md"], E), d["Md"], res)})
    else:
        Ms = d["Ns"] * d["Mx"]
        out.update({"we_gu": ((d["X"], E, 2 * d["Mx"]), E, 1.0),
                    "we_down": ((d["X"], d["Mx"], E), d["Mx"], res),
                    "ws_gu": ((E, 2 * Ms), E, 1.0),
                    "ws_down": ((Ms, E), Ms, res)})
    return out


def _norms(d: dict, kind: str) -> dict:
    out = {"attn_norm": d["E"], "mlp_norm": d["E"]}
    out.update({"kv_norm": d["r"]} if kind == MLA_MOE
               else {"o_norm": d["dv"]})
    return out


def _layer(key, layer, d: dict, kind: str) -> dict:
    """Layer ``layer`` (its index among the layers RUN; may be traced) of
    ``kind`` in the program's layout and dtype. The router, its bias and the
    decay's two vectors are float32, as the program keeps them. The held
    experts are drawn as experts ``0 .. X``: which share this is does not
    change a seeded weight."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(d["dtype"])
    f32 = jnp.float32
    out = {name: _uniform(_key(key, name, layer), shape,
                          gain * fan_in ** -0.5, dt)
           for name, (shape, fan_in, gain) in _shapes(d, kind).items()}
    for name, n in _norms(d, kind).items():
        out[name] = _norm(_key(key, name, layer), n, dt)
    if kind != MLA_MOE:
        H, dk = d["H"], d["dk"]
        out["a_log"] = jnp.log(jax.random.uniform(
            _key(key, "a_log", layer), (H,), f32, 1e-4, A_MAX))
        step = jnp.exp(jax.random.uniform(
            _key(key, "dt_bias", layer), (H * dk,), f32, jnp.log(DT_MIN),
            jnp.log(DT_MAX)))
        out["dt_bias"] = step + jnp.log(-jnp.expm1(-step))  # softplus^-1
    if kind != KDA_DENSE:
        out["router"] = jax.random.normal(
            _key(key, "router", layer), (d["E"], d["Xr"]),
            f32) * d["E"] ** -0.5
        out["router_bias"] = d["bias_std"] * jax.random.normal(
            _key(key, "router_bias", layer), (d["Xr"],), f32)
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
    layer drawn under its index among the layers run."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights

    def build(key):
        tree = _globals(key, d)
        for kind in (KDA_DENSE, KDA_MOE, MLA_MOE):
            at = [l for l, k in enumerate(d["kinds"]) if k == kind]
            if at:
                tree[kind] = jax.lax.map(
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

    def halves(name, gate, up):
        fused = w.pop(name)
        half = fused.shape[-1] // 2
        w[gate], w[up] = fused[..., :half], fused[..., half:]

    if kind == KDA_DENSE:
        halves("w_gu", "w_gate", "w_up")
    else:
        halves("we_gu", "we_gate", "we_up")
        halves("ws_gu", "ws_gate", "ws_up")
    if kind != MLA_MOE:
        qkv = w.pop("wqkv")
        nk = d["H"] * d["dk"]
        w["wq"], w["wk"], w["wv"] = (qkv[:, :nk], qkv[:, nk:2 * nk],
                                     qkv[:, 2 * nk:])
    return w


def _conv(x, w):
    """Depthwise causal convolution, directly: ``y[t] = sum_j w[j] x[t - (K
    - 1) + j]`` with zeros before the sequence. x [T, C], w [K, C]."""
    import jax.numpy as jnp

    K, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return sum(padded[j:j + T] * w[j] for j in range(K))


def _delta_rule(q, k, v, a, beta):
    """The recurrence, one token at a time. q, k, a [T,H,dk] (``a`` the log
    decay a channel); v [T,H,dv]; beta [T,H] -> o [T,H,dv]. ``S_0 = 0``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.model import HIGHEST

    def one(S, tok):
        q_t, k_t, v_t, a_t, b_t = tok
        S = jnp.exp(a_t)[:, :, None] * S                    # Diag(alpha) S
        kS = jnp.einsum("hk,hkv->hv", k_t, S, precision=HIGHEST)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - kS)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=HIGHEST)

    S0 = jnp.zeros(k.shape[1:] + v.shape[2:], jnp.float32)
    return jax.lax.scan(one, S0, (q, k, v, a, beta))[1]


def _unit(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _attention(q, k, v, scale):
    """Causal attention, a head at a time and ``_QUERY_BLOCK`` queries at a
    time (a block's softmax rows are whole: no online softmax). q, k [T, H,
    dqk]; v [T, H, dv] -> [T, H, dv]. Always the expanded form."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.model import HIGHEST

    T = q.shape[0]
    blk = _QUERY_BLOCK if T % _QUERY_BLOCK == 0 else T
    cols = jnp.arange(T)[None, :]

    def one_head(args):
        qh, kh, vh = args

        def one_block(args):
            qb, at = args
            s = jnp.einsum("td,sd->ts", qb, kh, precision=HIGHEST) * scale
            rows = at * blk + jnp.arange(blk)[:, None]
            p = jax.nn.softmax(jnp.where(cols <= rows, s, -jnp.inf), -1)
            return jnp.einsum("ts,sd->td", p, vh, precision=HIGHEST)

        out = jax.lax.map(one_block, (qh.reshape(T // blk, blk, -1),
                                      jnp.arange(T // blk)))
        return out.reshape(T, -1)

    out = jax.lax.map(one_head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2)))           # [H,T,dv]
    return out.transpose(1, 0, 2)


def choose(s, bias, d: dict, grouped: bool = True):
    """s [T, Xr] the sigmoid scores -> (chosen [T, K], weights [T, K]): the
    groups' scores, the groups kept, the experts chosen among them, the
    weights normalised over the chosen and scaled. Equal scores go to the
    lower index."""
    import jax
    import jax.numpy as jnp

    sb = s + bias[None, :]
    if grouped:
        T, G = s.shape[0], d["G"]
        per = sb.reshape(T, G, -1)
        score = jnp.sum(jax.lax.top_k(per, 2)[0], -1)             # [T, G]
        _, keep = jax.lax.top_k(score, d["Gk"])
        kept = jnp.any(keep[:, :, None] == jnp.arange(G)[None, None, :], 1)
        sb = jnp.where(kept[:, :, None], per, -jnp.inf).reshape(s.shape)
    _, chosen = jax.lax.top_k(sb, d["Kx"])
    g = jnp.take_along_axis(s, chosen, axis=-1)
    if d["norm_topk"]:
        g = g / jnp.sum(g, -1, keepdims=True)
    return chosen, g * d["scale"]


def block(x, w, positions, d: dict, lower, kind: str):
    """One layer on one sequence, x [T, E], float32 at the highest matmul
    precision. ``lower``: None, or one of ``controls()``."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import model

    if lower not in (None,) + controls():
        raise ValueError(f"unknown control {lower!r}")
    how = "fp8" if lower == "fp8" else None
    T = x.shape[0]
    n = model.rms_norm(x, w["attn_norm"], d["eps"])
    if kind == MLA_MOE:
        H, dn, dr, dv, r = d["H"], d["dn"], d["dr"], d["dvh"], d["r"]
        q = model.matmul(n, w["wq"], how).reshape(T, H, dn + dr)
        kva = model.matmul(n, w["wkv_a"], how)
        c = model.rms_norm(kva[:, :r], w["kv_norm"], d["eps"])
        k_r = _rope_pairs(kva[:, r:], positions, d["theta"])      # [T, dr]
        q_rope = _rope_pairs(q[..., dn:], positions, d["theta"])
        kv = model.matmul(c, w["wkv_b"], how).reshape(T, H, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, None, :], (T, H, dr))], -1)
        q = jnp.concatenate([q[..., :dn], q_rope], -1)
        o = _attention(q, k, kv[..., dn:], (dn + dr) ** -0.5)     # [T,H,dv]
        # one gate a head, read from the layer's normed input
        o = o * jax.nn.sigmoid(model.matmul(n, w["wgate"], how))[:, :, None]
        h = x + model.matmul(o.reshape(T, H * dv), w["wo"], how)
    else:
        H, dk, dv = d["H"], d["dk"], d["dv"]
        qkv = jnp.concatenate(
            [model.matmul(n, w[name], how) for name in ("wq", "wk", "wv")],
            -1)
        qkv = jax.nn.silu(_conv(qkv, w["conv_w"]))
        q = _unit(qkv[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
        k = _unit(qkv[:, H * dk:2 * H * dk].reshape(T, H, dk))
        v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
        rate = jnp.exp(w["a_log"])[:, None]                       # [H, 1]
        a = d["lower"] * jax.nn.sigmoid(rate * (
            model.matmul(n, w["wf"], how) + w["dt_bias"]).reshape(T, H, dk))
        if lower == "scalar_decay":
            a = jnp.broadcast_to(jnp.mean(a, -1, keepdims=True), a.shape)
        beta = jax.nn.sigmoid(model.matmul(n, w["wb"], how))
        o = _delta_rule(q, k, v, a, beta)
        gate = model.matmul(n, w["wg"], how).reshape(T, H, dv)
        o = model.rms_norm(o, w["o_norm"], d["eps"]) * jax.nn.sigmoid(gate)
        h = x + model.matmul(o.reshape(T, H * dv), w["wo"], how)
    m = model.rms_norm(h, w["mlp_norm"], d["eps"])

    def swiglu(gate, up, down):
        ff = jax.nn.silu(model.matmul(m, gate, how)) * model.matmul(
            m, up, how)
        return model.matmul(ff, down, how)

    if kind == KDA_DENSE:
        return h + swiglu(w["w_gate"], w["w_up"], w["w_down"])
    # scores in float32 whatever the control: the architecture states them
    s = jax.nn.sigmoid(model.matmul(m, w["router"]))              # [T, Xr]
    chosen, g = choose(s, w["router_bias"], d, lower != "ungrouped")

    def one_expert(y, e_w):
        e, gate, up, down = e_w
        # the expert sees every token, weighted 0 where it was not chosen
        ge = jnp.sum(jnp.where(chosen == e, g, 0.0), -1)          # [T]
        return y + ge[:, None] * swiglu(gate, up, down), None

    # the SHARE: the held experts alone; an absent expert's term is left out
    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (d["first"] + jnp.arange(d["X"]), w["we_gate"], w["we_up"],
         w["we_down"]))
    return h + routed + swiglu(w["ws_gate"], w["ws_up"], w["ws_down"])


def head(x, final_norm, lm_head, d: dict, lower):
    from benchmark.reference import model

    return model.head(x, final_norm, lm_head, d,
                      "fp8" if lower == "fp8" else None)


# ------------------------------------- least work, from the run's context
def decode_step_bytes(ctx: dict):
    """The least bytes of one decode step: the mixers', dense, shared and
    router weights and the head's slice once; the held experts the step's
    rows TOUCHED (the program's counter); the live rows' state once each
    way; the live latent positions. ``None`` without a traced span."""
    live = ctx.get("trace_live") or {}
    delta = ctx.get("trace_stats_delta") or {}
    if live.get("positions") is None or not delta.get("moe_expert_slots"):
        return None
    d = ctx["dims"]
    steps = delta["moe_expert_slots"] / (d["X"] * ops.layer_counts(d)[2])
    return ops.decode_step_bytes(
        d, delta["moe_experts_touched"] / steps, live["positions"],
        live["rows"])


def prefill_flops(ctx: dict):
    toks = (ctx.get("trace_stats_delta") or {}).get(
        "prefill_tokens_executed", 0)
    if not toks:
        return None
    mean_len = ctx.get("mean_prompt_len") or 0.0
    return ops.prefill_flops(ctx["dims"], toks, toks * mean_len)
