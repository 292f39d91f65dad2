"""Device-mesh construction for TPU slices.

TPU-native counterpart of the reference's world bootstrap (reference:
``serving/spmd/pytorch_process.py:19`` sets RANK/WORLD_SIZE for NCCL;
``serving/spmd/jax_process.py:8`` sets JAX coordinator env vars). Here the
parallel layout is a first-class object: a :class:`MeshSpec` names six axes

    pp    pipeline stages      (slowest — crosses DCN between slices if needed)
    dp    pure data parallel   (gradients all-reduced)
    fsdp  data parallel w/ sharded params/optimizer (ZeRO-3 style)
    sp    sequence/context parallel (ring attention rides this axis)
    ep    expert parallel (MoE experts sharded)
    tp    tensor parallel      (innermost — fastest-varying, rides ICI)

and materializes a ``jax.sharding.Mesh``. Axis order is chosen so that the
highest-bandwidth-demand axis (tp) maps to the fastest-varying physical ICI
dimension, and pp (lowest demand, point-to-point only) is outermost — the
layout recipe from the public scaling-book guidance.

Multi-slice: the ``dcn`` axis (outermost of all) spans TPU slices over the
data-center network. Only gradient all-reduces ride it (pure data
parallelism — the lowest-bandwidth collective in the step), matching the
megascale deployment contract in ``provisioning/manifests.py`` (one JobSet
replicated job per slice). On real hardware ``build()`` uses
``mesh_utils.create_hybrid_device_mesh`` so ICI axes never straddle a
slice boundary.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

# Outermost → innermost. dcn crosses slices (DCN, lowest bandwidth);
# tp last so it lands on the fastest ICI ring.
AXIS_ORDER: tuple = ("dcn", "pp", "dp", "fsdp", "sp", "ep", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative parallel layout. ``-1`` on one axis means "fill the rest".

    Example::

        MeshSpec(fsdp=-1, tp=4).build()   # v5e-64: fsdp=16, tp=4
    """

    dcn: int = 1
    pp: int = 1
    dp: int = 1
    fsdp: int = 1
    sp: int = 1
    ep: int = 1
    tp: int = 1

    def sizes(self, n_devices: int) -> dict:
        sizes = {ax: getattr(self, ax) for ax in AXIS_ORDER}
        fills = [ax for ax, s in sizes.items() if s == -1]
        if len(fills) > 1:
            raise ValueError(f"only one axis may be -1, got {fills}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if fills:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}")
            sizes[fills[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh spec {sizes} wants {fixed} devices, have {n_devices}")
        return sizes

    def build(self, devices: Optional[Sequence] = None) -> Mesh:
        """Materialize a ``jax.sharding.Mesh`` over ``devices`` (default: all).

        Uses ``mesh_utils.create_device_mesh`` on real TPU backends so the
        logical mesh respects the physical ICI torus; falls back to a plain
        reshape for CPU/virtual device farms.
        """
        devices = list(devices if devices is not None else jax.devices())
        sizes = self.sizes(len(devices))
        shape = tuple(sizes[ax] for ax in AXIS_ORDER)
        try:
            if sizes["dcn"] > 1:
                # Hybrid mesh: ICI axes laid out within each slice, the
                # dcn axis across slices (requires device slice_index —
                # real multi-slice TPU; virtual farms take the fallback).
                ici = tuple(1 if ax == "dcn" else sizes[ax]
                            for ax in AXIS_ORDER)
                dcn = tuple(sizes["dcn"] if ax == "dcn" else 1
                            for ax in AXIS_ORDER)
                dev_array = mesh_utils.create_hybrid_device_mesh(
                    ici, dcn, devices=devices)
            else:
                dev_array = mesh_utils.create_device_mesh(
                    shape, devices=devices)
        except Exception:
            dev_array = np.asarray(devices).reshape(shape)
        return Mesh(dev_array, AXIS_ORDER)

    def describe(self, n_devices: int) -> str:
        sizes = self.sizes(n_devices)
        active = ", ".join(f"{ax}={s}" for ax, s in sizes.items() if s > 1)
        return active or "single-device"


def best_spec_for(
    n_devices: int,
    *,
    want_tp: int = 0,
    want_pp: int = 0,
    want_sp: int = 0,
    want_ep: int = 0,
) -> MeshSpec:
    """Pick a reasonable spec for ``n_devices``: honor requested axes when they
    divide the device count, put the remainder on fsdp.

    Used by the multichip dry-run and the default trainer when the user gives
    no explicit layout.
    """

    def usable(k: int, remaining: int) -> int:
        return k if k > 1 and remaining % k == 0 else 1

    remaining = n_devices
    pp = usable(want_pp, remaining); remaining //= pp
    tp = usable(want_tp, remaining); remaining //= tp
    sp = usable(want_sp, remaining); remaining //= sp
    ep = usable(want_ep, remaining); remaining //= ep
    return MeshSpec(pp=pp, tp=tp, sp=sp, ep=ep, fsdp=remaining)


def local_mesh(spec: Optional[MeshSpec] = None) -> Mesh:
    """Mesh over this process's addressable devices (single-host path)."""
    devs = jax.local_devices()
    spec = spec or MeshSpec(fsdp=-1)
    return spec.build(devs)
