"""Hierarchical quantized collectives: int8 over DCN, f32 over ICI.

Multi-slice meshes put ``dcn`` first in AXIS_ORDER so the slowest links
carry the least traffic (mesh.py) — but the *bytes* on those links are
still full-precision: XLA lowers the gradient allreduce the sharding
annotations imply in the params' dtype end to end. EQuARX ("Efficient
Quantized AllReduce in XLA", PAPERS.md) shows the cross-slice hop is the
only one worth compressing: quantize ONLY the dcn leg to int8 with
per-block f32 scales and stochastic rounding, keep every in-slice (ICI)
reduction full-precision, and training quality holds while DCN bytes
drop ~4× (per-block scale overhead is 4/block).

The schedule here is a ring over ``dcn`` (``ppermute`` reduce-scatter +
all-gather), not a log-depth tree: a ring re-quantizes each partial sum
exactly once per hop with *stochastically rounded* blocks, so the
quantization noise stays zero-mean instead of compounding through
log(n) biased roundings. Two invariants matter:

- every rank consumes the DEQUANTIZED bytes of its own reduced chunk
  too (the owner does not keep its f32 copy) — the summed vector is
  bit-identical across slices and the replicas never drift;
- the per-hop rounding keys fold in the rank, every ICI coordinate and
  the hop index, so noise is decorrelated across devices and hops while
  staying deterministic for a given ``seed`` (the trainer passes the
  step counter).

The trainer (``training/trainer.py``) engages this behind
``KT_COLL_DCN_CODEC=int8`` on ``dcn>1`` meshes by computing per-slice
gradients (``vmap`` over a dcn-split batch — in-slice dp/fsdp/tp
reductions stay XLA-automatic and full-precision) and ring-summing the
stacked result here. ``dcn=1`` meshes and the default ``f32`` codec
never reach this module: the train step traces exactly the pre-existing
graph.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from kubetorch_tpu.config import env_int, env_str

DCN_AXIS = "dcn"


def dcn_codec() -> str:
    """``KT_COLL_DCN_CODEC``: 'f32' (XLA's own allreduce, the default)
    or 'int8' (the quantized ring below)."""
    codec = (env_str("KT_COLL_DCN_CODEC") or "f32").lower()
    if codec not in ("f32", "int8"):
        raise ValueError(
            f"KT_COLL_DCN_CODEC={codec!r}: expected 'f32' or 'int8'")
    return codec


def dcn_block() -> int:
    """``KT_COLL_BLOCK``: elements per f32 scale in the int8 ring."""
    return max(1, int(env_int("KT_COLL_BLOCK")))


@dataclasses.dataclass(frozen=True)
class DcnWireStats:
    """Static per-step byte accounting for one dcn ring allreduce.

    Byte counts are exact, not sampled: the ring's schedule is static
    (2·(n-1) chunk sends per device), so wire bytes follow from shapes
    alone. ``raw_bytes`` is what the same schedule moves in f32 — the
    baseline the ≥2× reduction is asserted against."""
    dcn: int            # ring size (devices per hop chain)
    ici: int            # in-slice devices per dcn rank
    payload_elems: int  # padded f32 elements synced per step
    wire_bytes: int     # bytes over dcn per step, summed over the mesh
    raw_bytes: int      # bytes an f32 ring would move

    @property
    def reduction(self) -> float:
        return self.raw_bytes / max(1, self.wire_bytes)


def dcn_wire_stats(n_elems: int, n_dcn: int, ici: int, block: int,
                   codec: str = "int8") -> DcnWireStats:
    """Bytes-on-wire for ring-allreducing ``n_elems`` f32 elements over
    a ``dcn=n_dcn`` axis with ``ici`` in-slice devices per rank."""
    if n_dcn <= 1:
        return DcnWireStats(n_dcn, ici, 0, 0, 0)
    quantum = n_dcn * ici * max(1, block)
    padded = -(-n_elems // quantum) * quantum
    chunk = padded // (n_dcn * ici)     # elems per ring chunk per device
    hops = 2 * (n_dcn - 1)              # reduce-scatter + all-gather
    f32_chunk = chunk * 4
    int8_chunk = chunk + (chunk // max(1, block)) * 4   # q + scales
    per_dev = int8_chunk if codec == "int8" else f32_chunk
    devices = n_dcn * ici
    return DcnWireStats(
        dcn=n_dcn, ici=ici, payload_elems=padded,
        wire_bytes=hops * per_dev * devices,
        raw_bytes=hops * f32_chunk * devices)


def dcn_ring_allreduce(stacked, mesh: Mesh, *, block: int = 256,
                       seed=None) -> Tuple[object, DcnWireStats]:
    """Sum a pytree of per-slice leaves (leading axis = ``dcn``) over
    the dcn axis through the quantized ring. Returns ``(summed_tree,
    stats)`` where each output leaf drops the leading axis and keeps
    its input dtype; the accumulator is f32 throughout.

    ``seed``: scalar folded into the stochastic-rounding keys (pass the
    training step so re-quantization noise is fresh every step but the
    computation stays deterministic). ``dcn=1`` meshes reduce to a
    no-op squeeze — the identity the tests pin."""
    from kubetorch_tpu.models.quant import block_dequantize, block_quantize

    n_dcn = int(mesh.shape.get(DCN_AXIS, 1))
    leaves, treedef = jax.tree.flatten(stacked)
    if not leaves:
        return stacked, dcn_wire_stats(0, n_dcn, 1, block)
    dtypes = [x.dtype for x in leaves]
    shapes = [x.shape for x in leaves]
    if n_dcn <= 1:
        out = [x.sum(axis=0).astype(dt) for x, dt in zip(leaves, dtypes)]
        return treedef.unflatten(out), dcn_wire_stats(0, n_dcn, 1, block)

    other = tuple(a for a in mesh.axis_names if a != DCN_AXIS)
    ici = 1
    for a in other:
        ici *= int(mesh.shape[a])
    vec = jnp.concatenate(
        [x.reshape(n_dcn, -1).astype(jnp.float32) for x in leaves], axis=1)
    n_elems = vec.shape[1]
    stats = dcn_wire_stats(n_elems, n_dcn, ici, block)
    pad = stats.payload_elems - n_elems
    if pad:
        vec = jnp.pad(vec, ((0, 0), (0, pad)))
    chunk = stats.payload_elems // (n_dcn * ici)
    seed_arr = jnp.asarray(0 if seed is None else seed).astype(jnp.uint32)
    perm = [(j, (j + 1) % n_dcn) for j in range(n_dcn)]

    def body(x, s):
        # x: [1, payload/ici] — this device's slab, chunked for the ring
        chunks = x[0].reshape(n_dcn, chunk)
        idx = jax.lax.axis_index(DCN_AXIS)
        key = jax.random.fold_in(jax.random.PRNGKey(s), idx)
        for a in other:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
        # reduce-scatter: n-1 hops; the partial sum re-quantizes once
        # per hop (stochastic — zero-mean noise), moves as (q, scale),
        # and accumulates in f32.
        send = jnp.take(chunks, idx % n_dcn, axis=0)
        for hop in range(n_dcn - 1):
            q, scale = block_quantize(send, block,
                                      key=jax.random.fold_in(key, hop))
            q = jax.lax.ppermute(q, DCN_AXIS, perm)
            scale = jax.lax.ppermute(scale, DCN_AXIS, perm)
            send = block_dequantize(q, scale, block) \
                + jnp.take(chunks, (idx - 1 - hop) % n_dcn, axis=0)
        # all-gather: the owner quantizes its reduced chunk ONCE and the
        # (q, scale) pair circulates; every rank — owner included —
        # consumes the dequantized bytes so the result replicates
        # bit-identically across slices (params must never drift).
        q, scale = block_quantize(send, block,
                                  key=jax.random.fold_in(key, n_dcn))
        out = jnp.zeros_like(chunks)
        out = out.at[(idx + 1) % n_dcn].set(
            block_dequantize(q, scale, block))
        for hop in range(n_dcn - 1):
            q = jax.lax.ppermute(q, DCN_AXIS, perm)
            scale = jax.lax.ppermute(scale, DCN_AXIS, perm)
            out = out.at[(idx - hop) % n_dcn].set(
                block_dequantize(q, scale, block))
        return out.reshape(-1)

    spec_other = other if other else None
    ring = shard_map(body, mesh=mesh,
                     in_specs=(P(DCN_AXIS, spec_other), P()),
                     out_specs=P(spec_other), check_vma=False)
    summed = ring(vec, seed_arr)[:n_elems]
    out, off = [], 0
    for shape, dt in zip(shapes, dtypes):
        size = 1
        for d in shape[1:]:
            size *= d
        out.append(summed[off:off + size].reshape(shape[1:]).astype(dt))
        off += size
    return treedef.unflatten(out), stats


def make_dcn_synced_grads(compute_grads, mesh: Mesh, *,
                          block: Optional[int] = None):
    """Wrap a ``compute_grads(params, batch) -> ((loss, aux), grads)``
    into the explicit two-level sync: per-slice gradients via ``vmap``
    over a dcn-split batch (XLA keeps the in-slice dp/fsdp/tp
    reductions automatic and full-precision; no cross-slice reduction
    exists because the vmapped slices are independent), then the
    quantized ring sums the stacked result over ``dcn``.

    Returns ``synced(params, batch, seed) -> ((loss, aux), grads)``.
    Losses/aux/grads combine token-weighted (``aux["tokens"]``, weight
    1.0 without one) — exactly the microbatch-accumulation math in
    ``make_train_step``, so the combined loss matches the full-batch
    mean even with ragged masks."""
    n_dcn = int(mesh.shape.get(DCN_AXIS, 1))
    block = dcn_block() if block is None else block

    def synced(params, batch, seed):
        B = jax.tree.leaves(batch)[0].shape[0]
        if B % n_dcn:
            raise ValueError(
                f"batch dim {B} not divisible by dcn={n_dcn}")
        micro = jax.tree.map(
            lambda x: x.reshape((n_dcn, B // n_dcn) + x.shape[1:]), batch)
        (loss_s, aux_s), g_s = jax.vmap(
            compute_grads, in_axes=(None, 0))(params, micro)
        w = aux_s.get("tokens", jnp.ones((n_dcn,), jnp.float32)) \
            if isinstance(aux_s, dict) else jnp.ones((n_dcn,), jnp.float32)
        # token-weighting promotes bf16 grads to f32 — exactly the
        # precision the ring wants; cast back to the per-slice grad
        # dtype at the end or apply_updates would promote the params.
        g_w = jax.tree.map(
            lambda g: g * w.reshape((n_dcn,) + (1,) * (g.ndim - 1)), g_s)
        g_sum, _ = dcn_ring_allreduce(g_w, mesh, block=block, seed=seed)
        inv = 1.0 / w.sum()
        aux = jax.tree.map(lambda a: (a * w).sum() * inv, aux_s)
        if isinstance(aux, dict) and "tokens" in aux:
            aux["tokens"] = w.sum()  # a count, not an average
        grads = jax.tree.map(
            lambda g, orig: (g * inv).astype(orig.dtype), g_sum, g_s)
        return ((loss_s * w).sum() * inv, aux), grads

    return synced
