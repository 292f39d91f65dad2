"""Pipeline parallelism (GPipe schedule) over the ``pp`` mesh axis.

Stages hold contiguous layer groups (params' leading ``stage`` dim sharded
over pp); microbatches stream through a skewed scan of ``n_micro + pp - 1``
ticks; activations hop stage→stage with ``ppermute`` (point-to-point ICI, the
cheapest collective — why pp is the outermost mesh axis and the one to place
across DCN for multi-slice). Differentiable end-to-end: the schedule is a
``lax.scan`` and gradients flow back through the reversed ppermutes.

Params enter the shard_map in their **at-rest sharding** (``param_specs``):
the stage dim on pp, weight dims on fsdp. The body all-gathers the fsdp
dims explicitly before running the stage — ZeRO-3 semantics, whose autodiff
transpose reduce-scatters the weight grads back over fsdp. Handing XLA a
replicated in_spec instead forces it to replicate-then-repartition every
weight on entry (the "[SPMD] Involuntary full rematerialization" failure
mode of round 1).

The whole schedule compiles to ONE XLA program — there is no per-stage
runtime actor (contrast: the reference's distributed path fans out HTTP calls
per worker; SURVEY.md §2.7 has no pipeline support at all).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _spec_axes(spec) -> Tuple[str, ...]:
    """All mesh axis names a PartitionSpec mentions."""
    out = []
    for entry in spec:
        if entry is None:
            continue
        out.extend(entry if isinstance(entry, tuple) else (entry,))
    return tuple(out)


def _gather_local(a: jax.Array, spec) -> jax.Array:
    """All-gather every sharded non-stage dim of a local param slice.

    ``a`` is the body-local slice with the stage dim already dropped, so
    array dim ``i`` corresponds to ``spec[i + 1]``. tiled all_gather
    transposes to psum_scatter — gradients come back reduce-scattered over
    the same axes (ZeRO grad flow for free).
    """
    for entry_idx, axes in enumerate(spec):
        if entry_idx == 0 or axes is None:
            continue
        # Minor axis first: undoing an (a, b) a-major tiling by gathering
        # a then b would interleave the blocks in permuted order.
        for ax in reversed(axes if isinstance(axes, tuple) else (axes,)):
            a = jax.lax.all_gather(a, ax, axis=entry_idx - 1, tiled=True)
    return a


def _pipeline_body(params, x, *, axis_name: str, n_micro: int,
                   stage_fn: Callable, mesh_axes: tuple = (),
                   param_specs=None):
    """Inside shard_map. ``params`` leaves: [1(stage), ...] local slice (weight
    dims possibly still fsdp-sharded); ``x``: [B_local, ...] this shard's
    batch rows."""
    pp = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    local_params = jax.tree.map(lambda a: a[0], params)
    if param_specs is not None:
        local_params = jax.tree.map(_gather_local, local_params, param_specs)
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    micro = x.shape[0] // n_micro
    xs = x.reshape((n_micro, micro) + x.shape[1:])
    mb_shape = xs.shape[1:]

    def tick(carry, t):
        inflight, outputs = carry
        # stage 0 consumes microbatch t (clamped; masked later)
        x_t = jax.lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False)
        inp = jnp.where(stage == 0, x_t, inflight)
        y = stage_fn(local_params, inp)
        # last stage writes output for microbatch t - (pp - 1)
        out_idx = t - (pp - 1)
        write = jnp.logical_and(stage == pp - 1, out_idx >= 0)
        updated = jax.lax.dynamic_update_index_in_dim(
            outputs, y.astype(outputs.dtype),
            jnp.clip(out_idx, 0, n_micro - 1), axis=0)
        outputs = jnp.where(write, updated, outputs)
        inflight = jax.lax.ppermute(y, axis_name, perm)
        return (inflight, outputs), None

    inflight0 = jnp.zeros(mb_shape, xs.dtype)
    outputs0 = jnp.zeros((n_micro,) + mb_shape, xs.dtype)
    # VMA typing: carries become device-varying (over pp and any batch/
    # weight-sharded axes) inside the scan.
    if mesh_axes:
        inflight0, outputs0 = jax.lax.pcast(
            (inflight0, outputs0), mesh_axes, to="varying")
    (_, outputs), _ = jax.lax.scan(
        tick, (inflight0, outputs0), jnp.arange(n_micro + pp - 1))
    # outputs live on the last stage only; replicate via psum.
    outputs = jnp.where(stage == pp - 1, outputs, 0)
    outputs = jax.lax.psum(outputs, axis_name)
    return outputs.reshape((x.shape[0],) + outputs.shape[2:])


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,          # pytree, leaves [pp, ...] (stage leading dim)
    x: jax.Array,               # [B, ...] global activations
    mesh: Mesh,
    n_microbatches: int,
    axis_name: str = "pp",
    param_specs: Any = None,    # pytree of P, leaf[0] must be the stage axis
    batch_axes: Optional[Tuple[str, ...]] = None,
) -> jax.Array:
    """Run ``x`` through pp stages of ``stage_fn`` with GPipe microbatching.

    ``stage_fn(params_for_stage, h) -> h`` must preserve activation shape.

    ``param_specs`` (optional) gives each stacked-param leaf's at-rest
    PartitionSpec — entry 0 names the stage axis, later entries the weight
    sharding (fsdp etc.). The shard_map consumes the params exactly as laid
    out and the body gathers the weight dims itself; without it, params are
    taken stage-sharded and otherwise replicated (the caller pays the
    gather outside, fine for small models/tests).

    ``batch_axes`` shards the batch dim of ``x`` (e.g. ``("dp", "fsdp")``) so
    every data-parallel group pipelines its own rows; default replicates
    ``x``. Batch must divide ``n_microbatches × prod(batch_axes sizes)``.
    """
    B = x.shape[0]
    dp_total = math.prod(mesh.shape[a] for a in (batch_axes or ()))
    if B % (n_microbatches * dp_total):
        raise ValueError(
            f"batch {B} not divisible by n_microbatches {n_microbatches} "
            f"× batch-sharding {dp_total}")

    if param_specs is None:
        param_specs_in = jax.tree.map(
            lambda a: P(axis_name, *([None] * (a.ndim - 1))), stage_params)
        gather_specs = None
    else:
        param_specs_in = param_specs
        gather_specs = param_specs

    x_spec = (P(tuple(batch_axes), *([None] * (x.ndim - 1)))
              if batch_axes else P())
    axes_used = {axis_name, *(batch_axes or ())}
    for spec in jax.tree.leaves(
            param_specs_in, is_leaf=lambda s: isinstance(s, P)):
        axes_used.update(_spec_axes(spec))
    body = functools.partial(
        _pipeline_body, axis_name=axis_name, n_micro=n_microbatches,
        stage_fn=stage_fn, mesh_axes=tuple(sorted(axes_used)),
        param_specs=gather_specs)
    return shard_map(
        body, mesh=mesh,
        in_specs=(param_specs_in, x_spec),
        out_specs=x_spec,
    )(stage_params, x)
