"""Make bare ``jax.distributed.initialize()`` work off the kubetorch env
contract.

The launcher injects ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
``JAX_PROCESS_ID`` per worker (``serving/frameworks.py`` JaxProcess — the
TPU-first analogue of the reference's ``serving/spmd/jax_process.py:8``;
which chips a process may open is libtpu's ``TPU_VISIBLE_CHIPS`` contract,
``resources/compute/topology.py::chip_env``). JAX only reads the
coordinator address from env; process count/id must come from a
registered ``ClusterEnv``. This module registers one keyed on
exactly those variables, so user code inside a ``.distribute("jax")``
workload needs no arguments — the same UX torch users get from
``MASTER_ADDR``/``RANK`` env in ``dist.init_process_group``.

Importing the module performs the registration (JAX auto-detects
``ClusterEnv`` subclasses on definition). ``initialize()`` passes the same
contract as explicit arguments.
"""

from __future__ import annotations

import os

__all__ = ["initialize", "register"]

_REGISTERED = False


def register() -> None:
    """Define + auto-register the ClusterEnv subclass (idempotent)."""
    global _REGISTERED
    if _REGISTERED:
        return
    from jax._src import clusters

    class KubetorchCluster(clusters.ClusterEnv):
        """Bootstraps from the env the kubetorch launcher injects."""

        name = "kubetorch"

        @classmethod
        def is_env_present(cls) -> bool:
            return all(v in os.environ for v in (
                "JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                "JAX_PROCESS_ID"))

        @classmethod
        def get_coordinator_address(cls, timeout_secs=None,
                                    override_coordinator_port=None) -> str:
            addr = os.environ["JAX_COORDINATOR_ADDRESS"]
            if override_coordinator_port:
                addr = f"{addr.rsplit(':', 1)[0]}:{override_coordinator_port}"
            return addr

        @classmethod
        def get_process_count(cls) -> int:
            return int(os.environ["JAX_NUM_PROCESSES"])

        @classmethod
        def get_process_id(cls) -> int:
            return int(os.environ["JAX_PROCESS_ID"])

    # First in line: JAX takes the first detector whose environment is
    # present, and its own come first. On a TPU host the chip variables
    # the launcher sets (TPU_PROCESS_ADDRESSES) look like GKE to JAX's
    # detector, which then asks a metadata server for the process count —
    # the launcher's explicit contract must win over that sniffing.
    types = clusters.ClusterEnv._cluster_types
    types.remove(KubetorchCluster)
    types.insert(0, KubetorchCluster)
    _REGISTERED = True


def initialize(**kwargs) -> None:
    """Explicit ``jax.distributed.initialize`` from the kubetorch env
    contract; idempotent. Use when you want initialization independent of
    JAX's cluster auto-detection."""
    import jax

    if jax.distributed.is_initialized():
        return
    args = dict(
        coordinator_address=os.environ.get("JAX_COORDINATOR_ADDRESS"),
        num_processes=_int_env("JAX_NUM_PROCESSES"),
        process_id=_int_env("JAX_PROCESS_ID"),
    )
    args.update(kwargs)
    jax.distributed.initialize(**args)


def _int_env(name: str):
    value = os.environ.get(name)
    return int(value) if value is not None else None
