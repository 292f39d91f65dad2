"""BASELINE config #4: ViT-L/16 data-parallel training, Kueue gang-scheduled.

``queue_name=`` stamps the Kueue queue label onto the JobSet and sets
``suspend`` so admission is gang-wide — the slice starts only when the whole
gang fits (reference: compute.py:1710 queue_name; SURVEY §2.7 gang row).
Training is pure data-parallel over the slice: params replicated, batch
sharded over the dp axis.
"""

from __future__ import annotations

import argparse
import json


def train_vit(model: str = "tiny", batch_per_chip: int = 8,
              steps: int = 10) -> dict:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubetorch_tpu.models import ViTConfig, vit
    from kubetorch_tpu.parallel import (
        MeshSpec, ShardingRules, named_sharding,
    )

    # remat on for the full-size model, so that batch 64 a chip fits (its
    # rate is not measured on this round's chip)
    cfg = (ViTConfig.vit_l16(remat=True) if model == "l16"
           else ViTConfig.tiny())
    n_dev = len(jax.devices())
    mesh = MeshSpec(dp=-1).build()
    rules = ShardingRules.default()

    with jax.set_mesh(mesh):
        params = vit.init(jax.random.key(0), cfg)
        opt = optax.adamw(1e-3)
        opt_state = opt.init(params)

        batch = batch_per_chip * n_dev
        rng = np.random.default_rng(0)
        images = jnp.asarray(rng.normal(
            size=(batch, cfg.image_size, cfg.image_size, 3)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, cfg.num_classes, (batch,)),
                             jnp.int32)
        data_sharding = NamedSharding(mesh, P(("dp",)))
        images = jax.device_put(images, data_sharding)
        labels = jax.device_put(labels, data_sharding)

        def loss_fn(params, images, labels):
            logits = vit.forward(params, images, cfg, rules=rules)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(
                jnp.take_along_axis(logp, labels[:, None], axis=1))

        @jax.jit
        def step(params, opt_state, images, labels):
            loss, grads = jax.value_and_grad(loss_fn)(params, images, labels)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        params, opt_state, loss = step(params, opt_state, images, labels)
        float(loss)  # compile + first step
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state, images, labels)
        loss = float(loss)
        dt = (time.perf_counter() - t0) / steps

    return {
        "model": model, "devices": n_dev, "batch": batch,
        "loss": round(loss, 4),
        "step_time_s": round(dt, 4),
        "images_per_sec": round(batch / dt, 1),
        "images_per_sec_per_chip": round(batch / dt / n_dev, 1),
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--queue", default="tpu-queue",
                        help="Kueue LocalQueue name")
    parser.add_argument("--workers", type=int, default=4)
    args = parser.parse_args()

    if args.smoke:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"  # smoke mode runs on the CPU
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        result = train_vit(model="tiny", batch_per_chip=2, steps=3)
        print(json.dumps({"example": "vit_dp_kueue", **result}))
        return

    import kubetorch_tpu as kt

    compute = kt.Compute(
        tpus="v5e-32", queue_name=args.queue,
    ).distribute("jax", workers=args.workers)
    remote = kt.fn(train_vit).to(compute)
    results = remote(model="l16", steps=50)
    first = results[0] if isinstance(results, list) else results
    print(json.dumps({"example": "vit_dp_kueue", **first}))


if __name__ == "__main__":
    main()
