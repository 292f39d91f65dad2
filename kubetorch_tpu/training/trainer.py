"""Sharded training loop for mesh-parallel models.

Everything here is mesh-driven: params are initialized *directly sharded* (jit
with out_shardings — no host-side full copy), the optimizer state inherits
param shardings through XLA propagation, and the train step is one jitted
function with donated state. Collectives (grad all-reduce over dp, param
all-gather over fsdp, tp reductions) are inserted by XLA from the sharding
annotations — the framework never issues an explicit NCCL-style call
(contrast: reference bootstraps torch.distributed and leaves this to users,
``serving/spmd/pytorch_process.py:19``).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubetorch_tpu.models.configs import LlamaConfig
from kubetorch_tpu.models import llama
from kubetorch_tpu.parallel import collectives
from kubetorch_tpu.parallel.sharding import ShardingRules, named_sharding

TrainState = Dict[str, Any]


def cross_entropy_loss(
    logits: jax.Array,               # [B, S, V] float32
    targets: jax.Array,              # [B, S] int32
    mask: Optional[jax.Array] = None # [B, S] {0,1}
):
    """Masked mean softmax cross-entropy (float32, logsumexp-stable).

    Returns ``(loss, aux)`` with token count and accuracy in ``aux``.
    """
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    per_tok = logz - gold
    if mask is None:
        mask = jnp.ones_like(targets, dtype=jnp.float32)
    mask = mask.astype(jnp.float32)
    n_tok = jnp.maximum(mask.sum(), 1.0)
    loss = (per_tok * mask).sum() / n_tok
    acc = ((jnp.argmax(logits, -1) == targets) * mask).sum() / n_tok
    return loss, {"tokens": n_tok, "accuracy": acc}


def param_shardings(cfg: LlamaConfig, mesh: Mesh, rules: ShardingRules):
    axes = llama.param_logical_axes(cfg)
    return jax.tree.map(
        lambda ax: named_sharding(mesh, rules, *ax), axes,
        is_leaf=lambda x: isinstance(x, tuple))


def init_train_state(
    key: jax.Array,
    cfg: LlamaConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rules: Optional[ShardingRules] = None,
    init_fn: Optional[Callable] = None,
) -> TrainState:
    """Initialize params + optimizer state directly sharded on ``mesh``.

    ``init_fn(key) -> params`` overrides the llama tree (LoRA adapters,
    custom heads); its output shardings are left to propagation (adapter
    trees are small — replication is the right default)."""
    rules = rules or ShardingRules.default()
    if init_fn is None:
        shardings = param_shardings(cfg, mesh, rules)
        params = jax.jit(partial(llama.init, cfg=cfg),
                         out_shardings=shardings)(key)
    else:
        params = jax.jit(init_fn)(key)
    # zeros_like-derived states inherit param shardings via propagation.
    opt_state = jax.jit(optimizer.init)(params)
    step = jax.device_put(
        jnp.zeros((), jnp.int32), NamedSharding(mesh, PartitionSpec()))
    return {"params": params, "opt_state": opt_state, "step": step}


def make_default_loss(cfg: LlamaConfig, rules: ShardingRules,
                      ring_mesh: Optional[Mesh] = None,
                      head_grad: bool = True) -> Callable:
    """The LM objective: fused chunked cross-entropy over hidden states —
    never materializes [B, S, V] float32 logits (ops/xent.py).
    ``head_grad=False``: the unembedding is frozen (LoRA fine-tuning) —
    the streaming backward skips its [E, V] gradient accumulation."""

    def default_loss(params, batch):
        from kubetorch_tpu.ops.xent import fused_cross_entropy

        x = llama.hidden_states(
            params, batch["inputs"], cfg, rules,
            segment_ids=batch.get("segment_ids"),
            positions=batch.get("positions"),  # packed rows: RoPE restarts
            mesh=ring_mesh)
        return fused_cross_entropy(
            x, llama.unembedding(params, cfg), batch["targets"],
            batch.get("mask"), chunk_size=cfg.xent_chunk,
            head_grad=head_grad)

    return default_loss


def make_train_step(
    cfg: LlamaConfig,
    optimizer: optax.GradientTransformation,
    rules: Optional[ShardingRules] = None,
    loss_fn: Optional[Callable] = None,
    mesh: Optional[Mesh] = None,
    accum_steps: int = 1,
) -> Callable[[TrainState, Dict[str, jax.Array]], tuple]:
    """Build the jitted train step. Call under ``jax.set_mesh(mesh)``
    (the Trainer does this) so PartitionSpec constraints resolve.

    ``accum_steps > 1`` splits the batch's leading dim into that many
    microbatches and accumulates grads under ``lax.scan`` — activation
    memory of one microbatch, optimizer math of the full batch.
    """
    rules = rules or ShardingRules.default()
    # Ring attention only engages when sequence parallelism is active.
    ring_mesh = (mesh if mesh is not None
                 and mesh.shape.get("sp", 1) > 1 else None)
    compute_loss = loss_fn or make_default_loss(cfg, rules, ring_mesh)
    grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

    def compute_grads(params, batch):
        if accum_steps <= 1:
            return grad_fn(params, batch)
        B = jax.tree.leaves(batch)[0].shape[0]
        if B % accum_steps:
            raise ValueError(
                f"batch dim {B} not divisible by accum_steps={accum_steps}")
        micro = jax.tree.map(
            lambda x: x.reshape((accum_steps, B // accum_steps)
                                + x.shape[1:]), batch)

        def weighted(loss, aux, g):
            # Per-microbatch losses are means over that microbatch's
            # unmasked tokens; weight by the token count (when the loss
            # reports one) so accumulation matches the full-batch mean
            # exactly even with ragged masks. Without a count, microbatches
            # weight uniformly (exact for unmasked LM batches).
            w = aux.get("tokens", jnp.float32(1.0))
            return (loss * w, jax.tree.map(lambda a: a * w, aux),
                    jax.tree.map(lambda x: x * w, g), w)

        def body(carry, mb):
            loss_sum, aux_sum, grads, w_sum = carry
            (loss, aux), g = grad_fn(params, mb)
            loss_w, aux_w, g_w, w = weighted(loss, aux, g)
            return (loss_sum + loss_w,
                    jax.tree.map(jnp.add, aux_sum, aux_w),
                    jax.tree.map(jnp.add, grads, g_w),
                    w_sum + w), None

        (loss0, aux0), g0 = grad_fn(
            params, jax.tree.map(lambda x: x[0], micro))
        loss0, aux0, g0, w0 = weighted(loss0, aux0, g0)
        g0 = jax.tree.map(jnp.add, jax.tree.map(jnp.zeros_like, params), g0)
        rest = jax.tree.map(lambda x: x[1:], micro)
        (loss_sum, aux_sum, grads, w_sum), _ = jax.lax.scan(
            body, (loss0, aux0, g0, w0), rest)
        inv = 1.0 / w_sum
        aux = jax.tree.map(lambda a: a * inv, aux_sum)
        if "tokens" in aux:
            aux["tokens"] = w_sum  # a count, not an average
        return ((loss_sum * inv, aux),
                jax.tree.map(lambda g: g * inv, grads))

    # Quantized cross-slice gradient sync (KT_COLL_DCN_CODEC=int8 on a
    # dcn>1 mesh): per-slice grads over a dcn-split batch, int8 ring
    # over the dcn axis (parallel/collectives.py). The gate is
    # Python-level, resolved when the step is built — the default f32
    # codec and every dcn=1 mesh trace exactly the graph they trace
    # today, byte-identical lowering included.
    dcn_sync = None
    if (mesh is not None and mesh.shape.get("dcn", 1) > 1
            and collectives.dcn_codec() == "int8"):
        dcn_sync = collectives.make_dcn_synced_grads(compute_grads, mesh)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        if dcn_sync is not None:
            # the step counter seeds the stochastic rounding: fresh
            # noise every step, deterministic across retraces
            (loss, aux), grads = dcn_sync(
                state["params"], batch, state["step"])
        else:
            (loss, aux), grads = compute_grads(state["params"], batch)
        updates, new_opt = optimizer.update(
            grads, state["opt_state"], state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        metrics = {
            "loss": loss,
            "grad_norm": optax.global_norm(grads),
            **aux,
        }
        new_state = {"params": new_params, "opt_state": new_opt,
                     "step": state["step"] + 1}
        return new_state, metrics

    return jax.jit(train_step, donate_argnums=(0,))


class Trainer:
    """Minimal mesh-parallel trainer: owns mesh context, state, and step.

    BASELINE configs #3 (Llama FSDP) and #4 (ViT DP) run through this class;
    the GRPO example reuses its state/step machinery.
    """

    def __init__(
        self,
        cfg: LlamaConfig,
        mesh: Mesh,
        optimizer: Optional[optax.GradientTransformation] = None,
        rules: Optional[ShardingRules] = None,
        seed: int = 0,
        loss_fn=None,
        accum_steps: int = 1,
        init_fn=None,
    ):
        """``loss_fn(params, batch) -> (loss, aux_dict)`` overrides the LM
        cross-entropy objective (RL losses, distillation, ...).
        ``accum_steps`` enables gradient accumulation over microbatches.
        ``init_fn(key) -> params`` overrides the trained tree (see
        :meth:`lora`)."""
        self.cfg = cfg
        self.mesh = mesh
        self.rules = rules or ShardingRules.default()
        self.optimizer = optimizer or optax.adamw(
            3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
        # resilience hooks (enable_checkpointing): periodic saves + the
        # preemption-path emergency save
        self.checkpoint = None
        self._store_key: Optional[str] = None
        self._ckpt_every = 0
        self._step_count = 0
        with jax.set_mesh(self.mesh):
            self.state = init_train_state(
                jax.random.key(seed), cfg, mesh, self.optimizer, self.rules,
                init_fn=init_fn)
            self._step = make_train_step(cfg, self.optimizer, self.rules,
                                         loss_fn=loss_fn, mesh=mesh,
                                         accum_steps=accum_steps)
        # When the quantized dcn ring is active, its per-step bytes are
        # static (the schedule is shape-determined) — account them once
        # here, fold into the coll_* counters per step.
        self._coll_stats = None
        if (mesh.shape.get("dcn", 1) > 1
                and collectives.dcn_codec() == "int8"):
            n_params = sum(
                x.size for x in jax.tree.leaves(self.state["params"]))
            n_dcn = int(mesh.shape["dcn"])
            ici = mesh.devices.size // n_dcn
            self._coll_stats = collectives.dcn_wire_stats(
                n_params, n_dcn, ici, collectives.dcn_block())

    @classmethod
    def lora(
        cls,
        cfg: LlamaConfig,
        mesh: Mesh,
        base_params,
        lora_cfg,
        optimizer: Optional[optax.GradientTransformation] = None,
        rules: Optional[ShardingRules] = None,
        seed: int = 0,
        accum_steps: int = 1,
        loss_fn=None,
    ) -> "Trainer":
        """LoRA fine-tuning: ``state["params"]`` is the adapter tree; the
        frozen base keeps whatever sharding the caller gave it (init it
        through ``param_shardings`` on multi-device meshes — a plainly
        jitted base replicates per device and defeats FSDP) and the loss
        differentiates through ``lora.merge`` (models/lora.py — exact
        LoRA gradients, no model-code changes). Adam state is
        adapter-sized, so configs whose full-tree optimizer state OOMs
        fine-tune comfortably.

        ``loss_fn(params, batch) -> (loss, aux)`` overrides the LM
        objective (GRPO/RL losses — see examples/grpo_elastic.py); it
        receives the MERGED params."""
        from kubetorch_tpu.models import lora as lora_mod

        rules = rules or ShardingRules.default()
        if loss_fn is None:
            ring_mesh = (mesh if mesh is not None
                         and mesh.shape.get("sp", 1) > 1 else None)
            # LoRA never targets the unembedding — skip its [E, V]
            # gradient accumulation in the streaming backward
            loss_fn = make_default_loss(cfg, rules, ring_mesh,
                                        head_grad=False)
        loss = lora_mod.make_lora_loss(loss_fn, base_params, lora_cfg)
        return cls(
            cfg, mesh, optimizer=optimizer, rules=rules, seed=seed,
            loss_fn=loss, accum_steps=accum_steps,
            init_fn=lambda key: lora_mod.init(key, base_params, lora_cfg))

    # ------------------------------------------------------ resilience
    def enable_checkpointing(self, directory, store_key: Optional[str] = None,
                             every: int = 0) -> "Trainer":
        """Arm this trainer for preemption: a :class:`CheckpointManager`
        under ``directory``, optional periodic saves every ``every``
        steps (async — Orbax writes in the background), and an
        *emergency checkpoint* registered with the preemption handler:
        on SIGTERM the state saves with ``wait=True`` and (when
        ``store_key`` is set) delta-pushes to the data store, so the
        restarted gang resumes at the step the preemption interrupted.
        Returns self (chainable)."""
        from kubetorch_tpu.resilience.preemption import (
            register_emergency_checkpoint,
        )
        from kubetorch_tpu.training.checkpoint import CheckpointManager

        self.checkpoint = CheckpointManager(directory)
        self._store_key = store_key
        self._ckpt_every = int(every)
        register_emergency_checkpoint(self.emergency_checkpoint,
                                      name="trainer")
        return self

    def resume(self) -> int:
        """Restore the newest checkpoint (if any) onto the current mesh
        and return the resumed step (0 = fresh). Prefers the local
        checkpoint directory; when it is empty — a replacement pod on a
        fresh node, the directory died with the preempted pod — and a
        ``store_key`` is armed, restores the store's emergency copy that
        the preempted generation pushed. The restore leg records the
        ``restart.restore`` recovery span."""
        if self.checkpoint is None:
            raise RuntimeError("call enable_checkpointing() first")
        from kubetorch_tpu.observability import tracing

        latest = self.checkpoint.latest_step()
        t0, wall0 = time.perf_counter(), time.time()
        if latest is not None:
            # the local dir survived: the emergency path writes the
            # blocking local save at the same step it pushes, so a
            # surviving dir is never behind the store copy
            with jax.set_mesh(self.mesh):
                self.state = self.checkpoint.restore(self.state)
            step, source = int(latest), "local"
        else:
            store_step = self._restore_from_store()
            if store_step is None:
                return 0
            step, source = store_step, "store"
        tracing.record_span(
            "restart.restore", time.perf_counter() - t0, start=wall0,
            attrs={"step": step, "source": source})
        self._step_count = step
        return step

    def _restore_from_store(self) -> Optional[int]:
        """Fetch ``<store_key>/emergency`` (the preempted generation's
        delta push) and place it onto this trainer's mesh. Returns the
        resumed step, or None when no store copy is reachable."""
        if not self._store_key:
            return None
        import numpy as np

        from kubetorch_tpu.data_store.device_transfer import get_arrays

        try:
            fetched = get_arrays(
                f"{self._store_key}/emergency",
                template={"step": np.asarray(0), "state": self.state})
        except Exception:  # noqa: BLE001 — no copy / store down: fresh
            return None

        def _placement(cur):
            sharding = cur.sharding
            if isinstance(sharding, NamedSharding):
                return sharding
            # uncommitted init leftovers (optax step counts): replicate
            # on the mesh — committing them to their incidental single
            # device would conflict with the mesh-sharded params in the
            # next jitted step
            return NamedSharding(self.mesh, PartitionSpec())

        with jax.set_mesh(self.mesh):
            self.state = jax.tree.map(
                lambda cur, new: jax.device_put(new, _placement(cur)),
                self.state, fetched["state"])
        return int(np.asarray(fetched["step"]))

    def save_checkpoint(self, wait: bool = False) -> int:
        if self.checkpoint is None:
            raise RuntimeError("call enable_checkpointing() first")
        self.checkpoint.save(self._step_count, self.state, wait=wait)
        return self._step_count

    def emergency_checkpoint(self) -> dict:
        """The preemption-path save: blocking (must land inside the
        SIGTERM grace window) + delta store push. Registered by
        :meth:`enable_checkpointing`; callable directly in tests."""
        if self.checkpoint is None:
            raise RuntimeError("call enable_checkpointing() first")
        from kubetorch_tpu.training.checkpoint import emergency_save

        return emergency_save(self.checkpoint, self.state,
                              self._step_count, store_key=self._store_key)

    def step(self, batch: Dict[str, jax.Array]):
        with jax.set_mesh(self.mesh):
            self.state, metrics = self._step(self.state, batch)
        self._step_count += 1
        if self._coll_stats is not None:
            from kubetorch_tpu.observability.prometheus import (
                record_collective,
            )

            record_collective({"dcn_bytes": self._coll_stats.wire_bytes,
                               "dcn_raw_bytes": self._coll_stats.raw_bytes})
        if (self.checkpoint is not None and self._ckpt_every
                and self._step_count % self._ckpt_every == 0):
            # async save: Orbax writes in the background; the emergency
            # path and explicit save_checkpoint(wait=True) block instead
            self.checkpoint.save(self._step_count, self.state)
        return metrics

    def benchmark(self, batch: Dict[str, jax.Array], n_steps: int = 10,
                  warmup: int = 2) -> Dict[str, float]:
        """Steady-state step time + tokens/sec (excludes compile). The
        timed region ends when the last step's loss — which depends on the
        whole step chain — is ready."""
        for _ in range(warmup):
            metrics = self.step(batch)
        if warmup:
            jax.block_until_ready(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(n_steps):
            metrics = self.step(batch)
        loss = float(jax.block_until_ready(metrics["loss"]))
        dt = (time.perf_counter() - t0) / n_steps
        tokens = int(batch["inputs"].shape[0] * batch["inputs"].shape[1])
        return {
            "step_time_s": dt,
            "tokens_per_sec": tokens / dt,
            "loss": loss,
        }
