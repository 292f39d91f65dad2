"""BASELINE config #3: Llama-3-8B FSDP pretrain on a multi-host TPU slice.

The distributed launcher path: ``.distribute("jax", workers=N)`` on a
``tpus="v5e-64"`` Compute renders a JobSet gang (one pod per TPU VM host),
the SPMD supervisor establishes the quorum and injects
``JAX_COORDINATOR_ADDRESS``/``JAX_PROCESS_ID``/``JAX_NUM_PROCESSES``, and
every process runs this train fn — ``jax.devices()`` sees the whole slice,
so the fsdp mesh spans ICI. North-star metric: **tokens/sec/chip**.

Smoke mode runs the same fn in-process on the 8-device virtual CPU mesh.
"""

from __future__ import annotations

import argparse
import json


def train(model: str = "tiny", batch_per_chip: int = 1, seq_len: int = 2048,
          steps: int = 20, checkpoint_dir: str = "") -> dict:
    import jax
    import numpy as np
    import optax

    from kubetorch_tpu.models import LlamaConfig
    from kubetorch_tpu.parallel import MeshSpec
    from kubetorch_tpu.training import CheckpointManager, Trainer

    # multi-process bootstrap happens in the supervisor (jax.distributed);
    # here the mesh simply spans every visible device.
    cfg = {
        "8b": LlamaConfig.llama3_8b,
        "1b": LlamaConfig.llama3_1b,
        "tiny": lambda: LlamaConfig.tiny(max_seq_len=max(seq_len, 128)),
    }[model]()
    n_dev = len(jax.devices())
    mesh = MeshSpec(fsdp=-1).build()

    trainer = Trainer(cfg, mesh, optimizer=optax.adamw(3e-4, b1=0.9, b2=0.95,
                                                       weight_decay=0.1))
    seq = min(seq_len, cfg.max_seq_len)
    batch = max(1, batch_per_chip * n_dev)
    # synthetic corpus through the real input pipeline: per-host sharded
    # windows + device prefetch (training/data.py). Swap `corpus` for an
    # np.memmap over a tokenized dataset for real pretraining.
    from kubetorch_tpu.training import lm_batches

    corpus = np.random.default_rng(0).integers(
        0, cfg.vocab_size, max(batch * (seq + 1) * 4, 1 << 16),
        dtype=np.int32)
    # process_count=1: benchmark() feeds full global batches from every
    # host (jit assembles them); per-host sharded feeding pairs with
    # make_array_from_process_local_data in a real multi-host input loop.
    # benchmark() reuses ONE batch, so no prefetch lookahead here — a real
    # training loop would wrap this iterator in prefetch_to_device.
    data = jax.device_put(next(lm_batches(
        corpus, batch, seq, seed=0, process_index=0, process_count=1)))

    result = trainer.benchmark(data, n_steps=steps, warmup=2)

    if checkpoint_dir and jax.process_index() == 0:
        manager = CheckpointManager(checkpoint_dir)
        manager.save(steps, trainer.state, wait=True)

    return {
        "model": model,
        "devices": n_dev,
        "mesh": dict(mesh.shape),
        "batch": batch, "seq_len": seq,
        "loss": round(result["loss"], 4),
        "step_time_s": round(result["step_time_s"], 4),
        "tokens_per_sec": round(result["tokens_per_sec"], 1),
        "tokens_per_sec_per_chip": round(result["tokens_per_sec"] / n_dev, 1),
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--model", default=None, choices=["tiny", "1b", "8b"])
    parser.add_argument("--workers", type=int, default=8,
                        help="TPU hosts (v5e-64 = 8 hosts x 8 chips)")
    parser.add_argument("--steps", type=int, default=20)
    args = parser.parse_args()

    if args.smoke:
        # same train fn, virtual CPU mesh, in-process
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"  # smoke mode runs on the CPU
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        result = train(model=args.model or "tiny", seq_len=128, steps=4)
        print(json.dumps({"example": "llama_fsdp_pretrain", **result}))
        return

    import kubetorch_tpu as kt

    compute = kt.Compute(tpus="v5e-64").distribute("jax",
                                                   workers=args.workers)
    remote = kt.fn(train).to(compute)
    results = remote(model=args.model or "8b", steps=args.steps,
                     checkpoint_dir="/tmp/llama-ckpt")
    # one result per process; rank 0's carries the numbers
    first = results[0] if isinstance(results, list) else results
    print(json.dumps({"example": "llama_fsdp_pretrain", **first}))


if __name__ == "__main__":
    main()
