"""The training step in plain float32, a process of its own.

    python -m benchmark.reference.score_train <job.json>

Follows the program's first steps from the same seed: the weights are
rebuilt by the configuration's family (``benchmark/families``: the values
the program holds, in float32; one compilation a KIND of layer), the rows
are the steps' own, the loss is the mean cross-entropy over every position,
the optimizer is AdamW written out below. It reports each step's loss, the
norm of the first gradient leaf by leaf, and the norm of each leaf's change
after two updates. A third update would need both Adam moments beside the
parameters and the gradient, which one chip cannot hold in float32, so the
third step gives its loss only and the change is compared after two.

The first gradient is kept on the host between the two updates (the second
moment after one step is a function of it), so the chip never holds more
than parameters + one gradient + activations.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial


def score(job: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import families, weights

    config = json.load(open(job["config_file"]))
    fam = families.load(config, "train")
    d = fam.dims(config)
    kinds = fam.layer_kinds(d)
    # layers of one kind, in order: the program stacks them under one name
    stacks = {k: [i for i, kk in enumerate(kinds) if kk == k]
              for k in dict.fromkeys(kinds)}
    opt = config["train"]["optimizer"]
    lr, b1, b2, eps, wd = (opt[k] for k in (
        "learning_rate", "b1", "b2", "eps", "weight_decay"))
    seed, lower = job["seed"], job.get("lower")
    rows, seq = job["rows"], job["seq"]
    dev = jax.devices()[0]
    if job.get("need_platform") and dev.platform != job["need_platform"]:
        raise SystemExit(f"reference came up on {dev.platform!r}")
    positions = jnp.arange(seq)

    # One layer at a time, forward and backward: a whole-model gradient at
    # "highest" precision keeps three bf16 pieces of every weight beside
    # the float32 tree (11 GB of temporaries at 4 layers, chip run PR 23).
    key = weights.root_key(seed)
    make_globals = jax.jit(lambda key: fam.reference_globals(key, d, "train"))
    make_layer = jax.jit(
        lambda key, l, kind: fam.reference_layer(key, l, d, kind, "train"),
        static_argnames="kind")

    def init():
        return {**make_globals(key),
                "layers": [make_layer(key, l, kind=kind)
                           for l, kind in enumerate(kinds)]}

    @partial(jax.jit, static_argnames="kind")
    def block(x, w, kind):
        return fam.block(x, w, positions, d, lower, kind)

    @partial(jax.jit, static_argnames="kind")
    def block_back(x, w, gy, kind):
        _, vjp = jax.vjp(
            lambda x, w: fam.block(x, w, positions, d, lower, kind), x, w)
        return vjp(gy)                                   # (gx, gw)

    def head_loss(x, final_norm, lm_head, targets):
        logits = fam.head(x, final_norm, lm_head, d, lower)
        logz = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jnp.mean(logz - gold) / rows    # mean over rows and positions

    head_grad = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))
    embed = jax.jit(lambda table, ids: table[ids])
    scatter = jax.jit(lambda acc, ids, gx: acc.at[ids].add(gx),
                      donate_argnums=0)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)

    def value_and_grad(params, batch):
        """Loss and gradient of the mean over rows, a row at a time; each
        layer's gradient is folded into the running sum as it is made, so
        no second gradient tree ever exists."""
        loss = 0.0
        grads = {"layers": [None] * len(kinds)}

        def fold(key, g, where=None):
            where = grads if where is None else where
            where[key] = g if where[key] is None else add(where[key], g)

        for toks in batch:
            ids, targets = jnp.asarray(toks[:-1]), jnp.asarray(toks[1:])
            xs = [embed(params["embedding"], ids)]
            for w, kind in zip(params["layers"], kinds):
                xs.append(block(xs[-1], w, kind=kind))
            l, (gx, g_norm, g_head) = head_grad(
                xs.pop(), params["final_norm"], params["lm_head"], targets)
            grads.setdefault("final_norm", None)
            grads.setdefault("lm_head", None)
            fold("final_norm", g_norm)
            fold("lm_head", g_head)
            for i in reversed(range(len(kinds))):
                gx, gw = block_back(xs.pop(), params["layers"][i], gx,
                                    kind=kinds[i])
                fold(i, gw, grads["layers"])
            grads["embedding"] = scatter(
                grads["embedding"] if "embedding" in grads
                else jnp.zeros_like(params["embedding"]), ids, gx)
            loss += float(l)
        return loss, grads

    sq = jax.jit(lambda x: jnp.sum(jnp.square(x)))
    sq_diff = jax.jit(lambda a, b: jnp.sum(jnp.square(a - b)))

    def norms(tree, other=None):
        """Leaf norms under the program's names: the layers of a kind are
        stacked there, so a leaf's square sums over those layers."""
        one = (lambda a, b: float(sq(a))) if other is None else (
            lambda a, b: float(sq_diff(a, b)))
        out = {k: one(tree[k], other and other[k]) ** 0.5
               for k in ("embedding", "final_norm", "lm_head")}
        for kind, idx in stacks.items():
            for name in tree["layers"][idx[0]]:
                out[fam.program_leaf(kind, name)] = sum(
                    one(tree["layers"][i][name],
                        other and other["layers"][i][name])
                    for i in idx) ** 0.5
        return out

    @partial(jax.jit, donate_argnums=0)
    def update1(p, g):
        # bias-corrected moments after one step are g and g*g
        return p - lr * (g / (jnp.abs(g) + eps) + wd * p)

    @partial(jax.jit, donate_argnums=(0, 2))
    def update2(p, g1, g2):
        m = (b1 * (1 - b1) * g1 + (1 - b1) * g2) / (1 - b1 ** 2)
        v = (b2 * (1 - b2) * g1 * g1 + (1 - b2) * g2 * g2) / (1 - b2 ** 2)
        return p - lr * (m / (jnp.sqrt(v) + eps) + wd * p)

    def batch(step):
        return weights.batch_tokens(seed, step, rows, seq, d["V"])

    params = init()
    l0, g1 = value_and_grad(params, batch(0))
    losses = [l0]
    def sample(tree):
        """The gradient at ``weights.sample_positions``; the leaves of a
        kind's layers are stacked [n, ...] on the program's side, so a flat
        position there is (layer of the kind, position inside the layer)."""
        out = {}
        for k in ("embedding", "final_norm", "lm_head"):
            pos = weights.sample_positions(seed, k, tree[k].size)
            out[k] = np.asarray(tree[k].reshape(-1)[pos]).tolist()
        for kind, idx in stacks.items():
            for name, leaf in tree["layers"][idx[0]].items():
                where = fam.program_leaf(kind, name)
                pos = weights.sample_positions(seed, where,
                                               leaf.size * len(idx))
                flat = [np.asarray(
                    tree["layers"][i][name].reshape(-1)[pos % leaf.size])
                    for i in idx]
                out[where] = [float(flat[p // leaf.size][i])
                              for i, p in enumerate(pos)]
        return out

    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "lower": lower, "grad_norm": norms(g1), "grad_sample": sample(g1)}
    if job.get("steps", 3) >= 2:
        params = jax.tree.map(update1, params, g1)
        g1_host = jax.tree.map(np.asarray, g1)
        del g1
        l1, g2 = value_and_grad(params, batch(1))
        losses.append(l1)
        params = jax.tree.map(
            lambda p, h, g: update2(p, jnp.asarray(h), g),
            params, g1_host, g2)
        del g1_host, g2
        l2, g3 = value_and_grad(params, batch(2))
        losses.append(l2)
        del g3
        out["delta_norm"] = norms(params, init())
    out["loss"] = losses
    return out


def main():
    t0 = time.perf_counter()
    job = json.load(open(sys.argv[1]))
    out = score(job)
    out["seconds"] = time.perf_counter() - t0
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
