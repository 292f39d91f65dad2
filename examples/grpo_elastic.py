"""BASELINE config #5: GRPO RL loop with elastic workers + weight transfer.

Two cooperating workloads, the async-GRPO topology from the reference's RL
tutorial (examples/tutorials/reinforcement_learning/async_grpo — trainer
ships LoRA weights to the inference fleet through the data plane):

- **trainer** — GRPO policy-gradient steps on a Llama policy; after every
  sync interval it publishes packed weights to the data store
  (``put_arrays``, the TPU host-staged stand-in for the reference's NCCL
  broadcast, SURVEY §7 hard-part 3).
- **sampler** — autoscaled inference workers that pull the freshest weights
  (``get_arrays``) before each generation round.

Elasticity: the sampler fleet can grow/shrink (autoscale or respawn); the
trainer never blocks on it — weight handoff is pull-based through the store.
Smoke mode runs one trainer round + one sampler round in-process.
"""

from __future__ import annotations

import argparse
import json

WEIGHTS_KEY = "grpo/policy-weights"
ADAPTER_KEY = "grpo/policy-lora"


# ---------------------------------------------------------------- trainer
def grpo_train(rounds: int = 2, group_size: int = 8, seq_len: int = 32,
               sync_every: int = 1, model: str = "tiny",
               use_lora: bool = False) -> dict:
    """GRPO: sample G completions per prompt, normalize rewards within the
    group (advantage = (r - mean) / std), ascend sum(adv * logp).

    ``use_lora=True`` is the reference's actual async-GRPO topology: the
    policy trains LoRA adapters on a frozen base, and weight sync ships
    ONLY the adapter tree (MBs, ~100× fewer bytes per round than the full
    tree) — samplers merge into their resident base."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubetorch_tpu.data_store.device_transfer import put_arrays
    from kubetorch_tpu.models import LlamaConfig, llama
    from kubetorch_tpu.models import lora as lora_mod
    from kubetorch_tpu.parallel import MeshSpec
    from kubetorch_tpu.training import Trainer

    cfg = (LlamaConfig.llama3_1b() if model == "1b" else LlamaConfig.tiny())
    mesh = MeshSpec(fsdp=-1).build()

    def grpo_loss(params, batch):
        """policy-gradient on group-normalized advantages; (loss, aux)."""
        tokens, advantages = batch["tokens"], batch["advantages"]
        logits = llama.forward(params, tokens[:, :-1], cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        seq_logp = jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=2)[..., 0].sum(-1)
        loss = -(advantages * seq_logp).mean()
        return loss, {"mean_seq_logp": seq_logp.mean()}

    if use_lora:
        from kubetorch_tpu.training.trainer import param_shardings

        lcfg = lora_mod.LoraConfig(rank=8)
        # frozen base initialized SHARDED — a plain jit would replicate
        # the full tree per device and defeat fsdp at 1B scale
        from kubetorch_tpu.parallel.sharding import ShardingRules

        base = jax.jit(
            lambda k: llama.init(k, cfg),
            out_shardings=param_shardings(cfg, mesh,
                                          ShardingRules.default())
        )(jax.random.key(0))
        trainer = Trainer.lora(cfg, mesh, base, lcfg,
                               optimizer=optax.adamw(1e-3),
                               loss_fn=grpo_loss)
    else:
        trainer = Trainer(cfg, mesh, optimizer=optax.adamw(1e-4),
                          loss_fn=grpo_loss)

    rng = np.random.default_rng(0)
    losses, published, sync_bytes = [], 0, 0
    for round_ix in range(rounds):
        # stand-in rollouts: random token groups + a toy reward
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (group_size, seq_len + 1)),
            jnp.int32)
        rewards = jnp.asarray(rng.normal(size=(group_size,)), jnp.float32)
        advantages = (rewards - rewards.mean()) / (rewards.std() + 1e-6)
        metrics = trainer.step({"tokens": tokens, "advantages": advantages})
        losses.append(float(metrics["loss"]))
        if (round_ix + 1) % sync_every == 0:
            tree = trainer.state["params"]
            if use_lora:
                lora_mod.publish_adapters(ADAPTER_KEY, tree)
            else:
                put_arrays(WEIGHTS_KEY, tree)
            sync_bytes = sum(int(x.size) * x.dtype.itemsize
                             for x in jax.tree.leaves(tree))
            published += 1

    out = {"rounds": rounds, "published": published,
           "loss_first": round(losses[0], 4),
           "loss_last": round(losses[-1], 4),
           "sync_bytes_per_round": sync_bytes}
    if use_lora:
        out["base_bytes"] = sum(int(x.size) * x.dtype.itemsize
                                for x in jax.tree.leaves(base))
    return out


# ---------------------------------------------------------------- sampler
def grpo_sample(n_prompts: int = 4, seq_len: int = 8,
                max_new_tokens: int = 8, model: str = "tiny",
                fleet_size: int = 1, use_lora: bool = False) -> dict:
    """Pull freshest policy weights, run real KV-cache rollouts.

    ``fleet_size`` > 1 tells the store how many samplers are fetching the
    same weights this round: the fetch joins a ``BroadcastWindow`` group
    and rides the rolling fan-out tree (completed peers serve later
    joiners) instead of every worker streaming from the store — the
    reference's NCCL broadcast-group role (SURVEY §3.5), host-staged.
    Rollouts run on the continuous-batching engine so staggered prompt
    lengths don't serialize."""
    import jax
    import numpy as np

    from kubetorch_tpu.data_store.device_transfer import get_arrays
    from kubetorch_tpu.data_store.types import BroadcastWindow
    from kubetorch_tpu.models import LlamaConfig, llama
    from kubetorch_tpu.models.rolling import RollingGenerator

    cfg = (LlamaConfig.llama3_1b() if model == "1b" else LlamaConfig.tiny())
    window = (BroadcastWindow(world_size=fleet_size, fanout=3)
              if fleet_size > 1 else None)
    if use_lora:
        # samplers keep the frozen base resident and pull only the tiny
        # adapter tree each round, merging locally
        from kubetorch_tpu.models import lora as lora_mod

        lcfg = lora_mod.LoraConfig(rank=8)
        base = jax.jit(lambda k: llama.init(k, cfg))(jax.random.key(0))
        template = jax.eval_shape(
            lambda: lora_mod.init(jax.random.key(0), base, lcfg))
        adapters = lora_mod.fetch_adapters(ADAPTER_KEY, template,
                                           broadcast=window)
        params = jax.jit(
            lambda b, a: lora_mod.merge(b, a, lcfg))(base, adapters)
    else:
        # abstract init (no FLOPs) recovers the param tree structure the
        # trainer packed, so the blob unflattens to a real param pytree.
        # shardings= lands each leaf on this sampler's devices as its
        # bytes arrive (streamed, pipelined restore) — no intermediate
        # full-host copy of the whole weight tree.
        template = jax.eval_shape(lambda: llama.init(jax.random.key(0), cfg))
        params = get_arrays(
            WEIGHTS_KEY, template=template, broadcast=window,
            shardings=jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    rng = np.random.default_rng(1)
    eng = RollingGenerator(params, cfg, max_slots=min(8, n_prompts),
                           steps_per_call=4)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size,
                                    int(rng.integers(2, seq_len + 1)))
                       .tolist(),
                       max_new_tokens=max_new_tokens, temperature=0.8)
            for _ in range(n_prompts)]
    out = eng.run()
    rollouts = [out[rid] for rid in rids]
    return {"sampled": len(rollouts), "rollouts": rollouts}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--rounds", type=int, default=4)
    args = parser.parse_args()

    if args.smoke:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"  # smoke mode runs on the CPU
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        train_result = grpo_train(rounds=2)
        sample_result = grpo_sample()
        # the LoRA weight-sync topology: adapter-only publish + merge
        lora_train = grpo_train(rounds=2, use_lora=True)
        lora_sample = grpo_sample(use_lora=True)
        print(json.dumps({"example": "grpo_elastic",
                          "trainer": train_result,
                          "sampler": sample_result,
                          "lora_trainer": lora_train,
                          "lora_sampler": lora_sample}))
        return

    import kubetorch_tpu as kt

    # trainer: one slice; sampler: autoscaled fleet pulling weights.
    trainer = kt.fn(grpo_train).to(
        kt.Compute(tpus="v5e-8").distribute("jax", workers=1))
    sampler = kt.fn(grpo_sample).to(
        kt.Compute(tpus="v5e-4").autoscale(min_scale=1, max_scale=4,
                                           target=2))
    train_result = trainer(rounds=args.rounds, model="1b")
    sample_result = sampler(model="1b")
    print(json.dumps({"example": "grpo_elastic",
                      "trainer": train_result, "sampler": sample_result}))


if __name__ == "__main__":
    main()
