"""Score served tokens against the float32 reference, a process of its own.

    python -m benchmark.reference.score_serve <job.json>

The job names the configuration file, the seed, and a sample of finished
requests (prompt + the tokens the engine served). For each request the
reference runs once over prompt + served tokens, layer by layer, the layer's
weights rebuilt from the seed by the configuration's family
(``benchmark/families``; one compilation a KIND of layer, so a stack whose
layers differ in shape passes through); where the engine chose token ``s``
the number compared is ``max(logits) - logits[s]`` in units of logits (0 = the
reference's own greedy token). With ``"controls"`` in the job the same pass
is made in lower precision and the gap of the token THAT puts first is read
at every position: the readings the limit is set against.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial


def _pad(n, buckets):
    """The smallest bucket that holds ``n`` positions: few shapes, so few
    compilations; the padding comes after the tokens and changes nothing
    under a causal mask."""
    fits = [b for b in sorted(buckets) if b >= n]
    return fits[0] if fits else -(-n // 256) * 256


def score(job: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import families, weights

    config = json.load(open(job["config_file"]))
    fam = families.load(config, "serve")
    d = fam.dims(config)
    kinds = fam.layer_kinds(d)
    seed = job["seed"]
    dev = jax.devices()[0]
    if job.get("need_platform") and dev.platform != job["need_platform"]:
        raise SystemExit(f"reference came up on {dev.platform!r}")
    key = weights.root_key(seed)
    glob = jax.jit(lambda key: fam.reference_globals(key, d, "serve"))(key)

    @partial(jax.jit, static_argnames="kind")
    def layer_weights(key, l, kind):
        return fam.reference_layer(key, l, d, kind, "serve")

    @partial(jax.jit, static_argnames=("kind", "lower"), donate_argnums=0)
    def run_block(x, w, positions, kind, lower=None):
        return fam.block(x, w, positions, d, lower, kind)

    @partial(jax.jit, static_argnames="lower")
    def run_head(x, at, final_norm, lm_head, lower=None):
        return fam.head(x[at], final_norm, lm_head, d, lower)

    embed = jax.jit(lambda table, ids: table[ids])
    n_max = max(len(r["served"]) for r in job["requests"])

    modes = [None] + list(job.get("controls", []))
    rows = []
    for req in job["requests"]:
        toks = np.asarray(req["prompt"] + req["served"], np.int32)
        n_p, n_s = len(req["prompt"]), len(req["served"])
        T = _pad(len(toks) - 1, job.get("buckets", ()))          # the last served token feeds nothing
        feed = np.zeros(T, np.int32)
        feed[:len(toks) - 1] = toks[:-1]
        xs = {m: embed(glob["embedding"], jnp.asarray(feed)) for m in modes}
        positions = jnp.arange(T)
        for l, kind in enumerate(kinds):
            w = layer_weights(key, l, kind=kind)
            for m in modes:
                xs[m] = run_block(xs[m], w, positions, kind=kind, lower=m)
        at = np.zeros(n_max, np.int32)           # positions that predict
        at[:n_s] = np.arange(n_p - 1, n_p - 1 + n_s)
        at = jnp.asarray(at)
        ref = np.asarray(run_head(xs[None], at, glob["final_norm"],
                                  glob["lm_head"]))[:n_s]    # [n_s, V]
        best = ref.max(-1)
        served = toks[n_p:]
        row = {"id": req["id"], "n_prompt": n_p, "n_served": n_s,
               "gap": (best - ref[np.arange(n_s), served]).tolist(),
               "std": float(ref.std(-1).mean()),
               "exact": int((ref.argmax(-1) == served).sum())}
        for m in modes[1:]:
            low = np.asarray(run_head(
                xs[m], at, glob["final_norm"], glob["lm_head"],
                lower=m))[:n_s].argmax(-1)
            row["control_" + m] = (best - ref[np.arange(n_s), low]).tolist()
        rows.append(row)
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "requests": rows,
           "served_tokens": sum(r["n_served"] for r in rows),
           "gap_max": max(max(r["gap"]) for r in rows),
           "gap_mean": float(np.mean([g for r in rows for g in r["gap"]])),
           "exact": sum(r["exact"] for r in rows)}
    for m in modes[1:]:
        vals = [g for r in rows for g in r["control_" + m]]
        out[f"control_{m}_gap_max"] = max(vals)
        out[f"control_{m}_gap_mean"] = float(np.mean(vals))
    return out


def main():
    t0 = time.perf_counter()
    job = json.load(open(sys.argv[1]))
    out = score(job)
    out["seconds"] = time.perf_counter() - t0
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
