"""One run of a training cell: the process that holds the chips.

    python -m benchmark.train_child <job.json>

Builds ONE ``Trainer`` (the program's, on the program's ``MeshSpec``) with
the weights of the configuration's family (``benchmark/families``) as its
``init_fn``, drives it from the seed through
its first three steps by the window's own call and feed, reads what the
reference will be compared with (each step's loss, the first gradient's norm
leaf by leaf out of the optimizer's first moment, each leaf's change), and
hands the same object to the window: steps back to back, each ending in
``block_until_ready`` on the loss. The last stdout line is a JSON report.
"""

from __future__ import annotations

import json
import os
import sys
import time


def leaf_paths(tree, prefix=""):
    out = {}
    for k in sorted(tree):
        if isinstance(tree[k], dict):
            out.update(leaf_paths(tree[k], f"{prefix}{k}/"))
        else:
            out[prefix + k] = tree[k]
    return out


def build(job, wrap_step=None):
    """The trainer, its feed and its readings' helpers. ``wrap_step`` lets a
    test break the step underneath the harness."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmark import families, weights
    from kubetorch_tpu.parallel import MeshSpec, ShardingRules
    from kubetorch_tpu.training import Trainer
    from kubetorch_tpu.training.trainer import param_shardings

    config = json.load(open(job["config_file"]))
    family = families.load(config, "train")
    d = family.dims(config)
    tr, opt = config["train"], config["train"]["optimizer"]
    seed = job["seed"]
    cfg = family.program_config(config, "train")
    mesh = MeshSpec(**config["mesh"]).build()
    rules = ShardingRules.default()
    shardings = param_shardings(cfg, mesh, rules)

    def init_fn(key):
        # The trainer hands over jax.random.key(its seed): an argument, so
        # no seed is baked into a program. Folding the bits above 2**31
        # makes it weights.root_key(seed), the key the reference rebuilds.
        # bf16 values, held in the dtype the file states.
        key = jax.random.fold_in(key, seed >> 31)
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x.astype(cfg.storage_dtype), s),
            family.training_tree(key, d), shardings)

    optimizer = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                            eps=opt["eps"], weight_decay=opt["weight_decay"])
    trainer = Trainer(cfg, mesh, optimizer, seed=seed & 0x7FFFFFFF,
                      init_fn=init_fn)
    if wrap_step is not None:
        trainer._step = wrap_step(trainer._step)
    rows = tr["rows_per_chip"] * len(jax.devices())
    n_batches = job["batches"]
    host = [weights.batch_tokens(seed, i, rows, tr["seq"], d["V"])
            for i in range(n_batches)]

    def feed(step):
        toks = host[step % n_batches]
        return {"inputs": jnp.asarray(toks[:, :-1]),
                "targets": jnp.asarray(toks[:, 1:])}

    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    with jax.set_mesh(mesh):
        norms = jax.jit(lambda t: jax.tree.map(norm, t))
        change = jax.jit(lambda p, key: jax.tree.map(
            lambda a, b: norm(a.astype(jnp.float32) - b.astype(jnp.float32)),
            p, init_fn(key)))
    key = jax.random.key(seed & 0x7FFFFFFF)
    return {"trainer": trainer, "feed": feed, "norms": norms,
            "change": lambda p: change(p, key), "rows": rows, "seq": tr["seq"], "d": d,
            "b1": opt["b1"], "mesh": mesh}


def first_moment(opt_state):
    """AdamW's first moment out of optax's state tuple."""
    for part in opt_state:
        if hasattr(part, "mu"):
            return part.mu
    raise LookupError("no Adam state in the optimizer state")


def run(job, wrap_step=None):
    import jax
    import numpy as np

    from benchmark import weights
    from kubetorch_tpu.observability import devstats

    compiles = devstats.watch_compiles()
    devices = jax.devices()
    report = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "pid": os.getpid()}
    if not job.get("rehearsal") and (report["platform"] != "tpu"
                                     or report["count"] != job["chips"]):
        print(json.dumps({"refused": report}), flush=True)
        return 3
    t0 = time.perf_counter()
    b = build(job, wrap_step)
    trainer, feed = b["trainer"], b["feed"]
    report["build_s"] = time.perf_counter() - t0

    def to_f(tree):
        return {k: float(v) for k, v in leaf_paths(tree).items()}

    def step(i):
        metrics = trainer.step(feed(i))
        return float(jax.block_until_ready(metrics["loss"]))

    # ---- the first three steps, through the window's own call and feed
    losses = [step(0)]
    report["first_step_s"] = time.perf_counter() - t0 - report["build_s"]
    with jax.set_mesh(b["mesh"]):
        moment = first_moment(trainer.state["opt_state"])
        mu = to_f(b["norms"](moment))
        report["grad_norm"] = {k: v / (1.0 - b["b1"]) for k, v in mu.items()}
        # the first gradient as the optimizer got it, at seeded positions
        report["grad_sample"] = {
            leaf: (np.asarray(x.reshape(-1)[weights.sample_positions(
                job["seed"], leaf, x.size)], np.float32)
                / (1.0 - b["b1"])).tolist()
            for leaf, x in leaf_paths(moment).items()}
        losses.append(step(1))
        report["delta_norm"] = to_f(b["change"](trainer.state["params"]))
        losses.append(step(2))
        report["delta_norm_3"] = to_f(b["change"](trainer.state["params"]))
    report["loss"] = losses
    # ---- the window: the same object, steps back to back
    seconds, spec = job["seconds"], job["trace"]
    trace_at = min(spec["start_s"], 0.3 * seconds) if job["trace_on"] else None
    tracing, traced_steps = False, 0
    compile_before = dict(compiles)
    ends, window_losses = [], []
    t_open_epoch = time.time()
    t_open = time.perf_counter()
    i = 3
    while True:
        now = time.perf_counter() - t_open
        if trace_at is not None and not tracing and now >= trace_at:
            jax.profiler.start_trace(job["trace_dir"])
            tracing, trace_at = True, None
        window_losses.append(step(i))
        i += 1
        ends.append(time.perf_counter() - t_open)
        if tracing:
            traced_steps += 1
            if traced_steps >= spec["steps"]:
                jax.profiler.stop_trace()
                tracing = False
        if ends[-1] >= seconds:
            break
    if tracing:
        jax.profiler.stop_trace()
    elapsed = ends[-1]
    report.update(
        t_open_epoch=t_open_epoch, steps=len(ends), elapsed_s=elapsed,
        tokens_per_step=b["rows"] * b["seq"], rows=b["rows"], seq=b["seq"],
        step_ms_p50=float(np.median(np.diff([0.0] + ends))) * 1e3,
        window_losses_finite=bool(np.all(np.isfinite(window_losses))),
        window_loss_last=window_losses[-1],
        compile_before=compile_before,
        compile_in_window={k: compiles[k] - compile_before[k]
                           for k in compiles},
        memory=[{k: (dev.memory_stats() or {}).get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
            for dev in devices],
        n_params=int(sum(x.size for x in jax.tree.leaves(
            trainer.state["params"]))))
    if traced_steps:
        from benchmark import trace

        report["trace"] = trace.reduce_dir(job["trace_dir"])
        report["traced_steps"] = traced_steps
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run(json.load(open(sys.argv[1]))))
