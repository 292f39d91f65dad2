"""Per-framework distributed env bootstrap.

The JAX path is primary (reference had it as an afterthought:
``serving/spmd/jax_process.py:8`` sets JAX_COORDINATOR_ADDRESS / PROCESS_ID /
NUM_PROCESSES; torch at ``spmd/pytorch_process.py:19`` sets
MASTER_ADDR/PORT). Ranks are assigned ICI-topology-aware when TPU slice
metadata is present: workers of one slice are ordered by
``TPU_WORKER_HOSTNAMES``/``TPU_WORKER_ID`` so the jax.distributed process ids
match the physical slice order instead of arbitrary DNS order.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from kubetorch_tpu.config import env_int, env_str


class FrameworkProcess:
    """Computes per-rank env for one framework; subclass per framework."""

    name = "none"
    # Default coordinator port; override per framework.
    port = 29500

    def __init__(self, num_procs: int = 1, **opts):
        self.num_procs = num_procs
        self.opts = opts

    @classmethod
    def auto_num_procs(cls) -> int:
        """Processes per pod. On TPU hosts: one process per host (all local
        chips belong to it) — contrast GPUs' one-proc-per-device."""
        return 1

    def rank_env(
        self, *, node_rank: int, local_rank: int, num_nodes: int,
        pod_ips: List[str],
    ) -> Dict[str, str]:
        world_size = num_nodes * self.num_procs
        rank = node_rank * self.num_procs + local_rank
        env = {
            "RANK": str(rank),
            "WORLD_SIZE": str(world_size),
            "LOCAL_RANK": str(local_rank),
            "NODE_RANK": str(node_rank),
            "POD_IPS": ",".join(pod_ips),
        }
        env.update(self.framework_env(
            rank=rank, world_size=world_size, local_rank=local_rank,
            node_rank=node_rank, pod_ips=pod_ips))
        return env

    def framework_env(self, **kw) -> Dict[str, str]:
        return {}

    def cleanup_env(self) -> List[str]:
        """Env vars to clear when the supervisor tears down."""
        return []


class JaxProcess(FrameworkProcess):
    """jax.distributed bootstrap over ICI/DCN.

    Sets the env contract ``jax.distributed.initialize()`` reads, so user code
    needs only a bare ``jax.distributed.initialize()`` (or none at all for
    single-host). Slice-aware: on GKE TPU pods, ``TPU_WORKER_ID`` (set by the
    TPU device plugin) overrides DNS-order node ranks, and MEGASCALE_* vars
    pass through for multi-slice jobs.
    """

    name = "jax"

    @property
    def port(self) -> int:
        # jax.distributed default coordinator port; override when several
        # independent quorums share a network namespace (local backend,
        # tests, sidecar jobs on one host).
        return env_int("KT_JAX_COORD_PORT")

    def framework_env(self, *, rank, world_size, local_rank, node_rank,
                      pod_ips) -> Dict[str, str]:
        coordinator = pod_ips[0].split(":")[0] if pod_ips else "127.0.0.1"
        process_id = node_rank * self.num_procs + local_rank
        env: Dict[str, str] = {}
        tpu_worker_id = os.environ.get("TPU_WORKER_ID")
        if tpu_worker_id is not None and self.num_procs == 1:
            process_id = int(tpu_worker_id)
            slice_id = os.environ.get("MEGASCALE_SLICE_ID")
            num_slices = int(os.environ.get("MEGASCALE_NUM_SLICES") or 1)
            if slice_id is not None and num_slices > 1:
                # TPU_WORKER_ID restarts at 0 per slice; globalize it so
                # jax process ids are unique across the DCN mesh.
                hosts_per_slice = world_size // num_slices
                process_id = (int(slice_id) * hosts_per_slice
                              + int(tpu_worker_id))
                hostnames = self._slice_hostnames(slice_id, hosts_per_slice)
                if hostnames:
                    env["TPU_WORKER_HOSTNAMES"] = ",".join(hostnames)
            # With slice-derived process ids, pod_ips[0] (the HTTP-routed
            # pod, rotated to node_rank 0) is NOT necessarily process 0 —
            # jax.distributed requires the coordinator to BE process 0, so
            # point it at slice-0/worker-0's stable DNS name.
            if slice_id is not None and num_slices > 1:
                coord = os.environ.get("MEGASCALE_COORDINATOR_ADDRESS", "")
            else:
                coord = (os.environ.get("TPU_WORKER_HOSTNAMES", "")
                         or env.get("TPU_WORKER_HOSTNAMES", ""))
            if coord:
                coordinator = coord.split(",")[0].split(":")[0]
        env.update({
            "JAX_COORDINATOR_ADDRESS": f"{coordinator}:{self.port}",
            "JAX_NUM_PROCESSES": str(world_size),
            "JAX_PROCESS_ID": str(process_id),
        })
        # Multi-slice (megascale) pass-through.
        for key, value in os.environ.items():
            if key.startswith("MEGASCALE_"):
                env.setdefault(key, value)
        visible = os.environ.get("TPU_VISIBLE_CHIPS")
        if self.num_procs > 1 and visible:
            # Several jax processes in a pod the launcher confined to its
            # own chips (LocalBackend) split them evenly through libtpu's
            # per-process contract; left alone, the first to initialise
            # opens them all and the rest fail or hang.
            from kubetorch_tpu.resources.compute.topology import chip_env

            ports = [self.port + 1 + i for i in range(self.num_procs)]
            env.update(chip_env([int(c) for c in visible.split(",")],
                                ports, task=local_rank))
        return env

    @staticmethod
    def _slice_hostnames(slice_id: str,
                         hosts_per_slice: int) -> Optional[List[str]]:
        """Expand this slice's TPU_WORKER_HOSTNAMES from the provisioning
        pattern (multi-slice: each slice's list differs, so it cannot be a
        static env var — manifests.py sets the pattern instead)."""
        pattern = env_str("KT_TPU_HOSTNAME_PATTERN")
        if not pattern:
            return None
        hosts = env_int("KT_TPU_HOSTS_PER_SLICE") or hosts_per_slice
        return [pattern.format(slice=int(slice_id), host=i)
                for i in range(hosts)]

    def cleanup_env(self) -> List[str]:
        return ["JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID"]


class PyTorchProcess(FrameworkProcess):
    """torch.distributed bootstrap (CPU/GPU parity path)."""

    name = "pytorch"
    port = 29500

    @classmethod
    def auto_num_procs(cls) -> int:
        try:
            import torch

            if torch.cuda.is_available():
                return torch.cuda.device_count()
        except ImportError:
            pass
        return 1

    def framework_env(self, *, rank, world_size, local_rank, node_rank,
                      pod_ips) -> Dict[str, str]:
        master = pod_ips[0].split(":")[0] if pod_ips else "127.0.0.1"
        return {
            "MASTER_ADDR": master,
            "MASTER_PORT": str(self.port),
        }

    def cleanup_env(self) -> List[str]:
        return ["MASTER_ADDR", "MASTER_PORT"]


class TensorFlowProcess(FrameworkProcess):
    name = "tensorflow"
    port = 2222

    def framework_env(self, *, rank, world_size, local_rank, node_rank,
                      pod_ips) -> Dict[str, str]:
        import json

        hosts = [f"{ip.split(':')[0]}:{self.port}" for ip in pod_ips]
        tf_config = {
            "cluster": {"worker": hosts},
            "task": {"type": "worker", "index": rank},
        }
        return {"TF_CONFIG": json.dumps(tf_config)}

    def cleanup_env(self) -> List[str]:
        return ["TF_CONFIG"]


FRAMEWORKS = {
    "jax": JaxProcess,
    "pytorch": PyTorchProcess,
    "tensorflow": TensorFlowProcess,
    "spmd": FrameworkProcess,  # bare RANK/WORLD_SIZE contract only
    "actor": FrameworkProcess,  # single-controller mode: POD_IPS is the mesh
    "monarch": FrameworkProcess,  # reference-name alias for "actor"
}


def framework_class(name: Optional[str]) -> type:
    if not name or name == "none":
        return FrameworkProcess
    try:
        return FRAMEWORKS[name]
    except KeyError:
        raise ValueError(
            f"unknown distributed framework {name!r}; "
            f"options: {sorted(FRAMEWORKS)}")
