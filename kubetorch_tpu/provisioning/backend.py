"""Deployment backends: where pods actually run.

``LocalBackend`` runs each "pod" as a local subprocess serving the same pod
server on 127.0.0.1 ports — the moral equivalent of the reference's
``LOCAL_IPS`` test mode (``distributed/utils.py:55``) promoted to a
first-class backend so the entire control path (deploy → ready → call →
distribute → teardown) runs identically with or without a cluster.

``K8sBackend`` (provisioning/k8s_backend.py) renders manifests and applies
them via the controller. Both implement the same interface, keeping the
``ControllerClient`` seam from SURVEY.md §7 stage-1.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from kubetorch_tpu.config import env_path, env_str, get_config
from kubetorch_tpu.exceptions import ServiceTimeoutError, StartupError
from kubetorch_tpu.resources.compute.topology import chip_env
from kubetorch_tpu.serving import http_client

_LOCAL_ROOT = env_path("KT_LOCAL_STATE")


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


class ServiceRecord(dict):
    """Persisted service state (the local 'pool registry' row)."""

    @property
    def urls(self) -> List[str]:
        return [f"http://127.0.0.1:{p['port']}" for p in self["pods"]]


class LocalBackend:
    name = "local"

    # ------------------------------------------------------------------
    def _service_dir(self, service_name: str) -> Path:
        return _LOCAL_ROOT / service_name

    def _record_path(self, service_name: str) -> Path:
        return self._service_dir(service_name) / "service.json"

    def lookup(self, service_name: str) -> Optional[ServiceRecord]:
        path = self._record_path(service_name)
        if not path.exists():
            return None
        record = ServiceRecord(json.loads(path.read_text()))
        return record

    def list_services(self) -> List[ServiceRecord]:
        if not _LOCAL_ROOT.exists():
            return []
        out = []
        for path in sorted(_LOCAL_ROOT.glob("*/service.json")):
            try:
                out.append(ServiceRecord(json.loads(path.read_text())))
            # ktlint: disable=KT004 -- a corrupt record must not hide the rest
            except Exception:
                continue
        return out

    # ------------------------------------------------------------------
    def launch(
        self,
        service_name: str,
        *,
        module_env: Dict[str, str],
        compute_dict: Dict[str, Any],
        module_meta: Dict[str, Any],
        num_pods: int = 1,
        launch_timeout: int = 300,
        launch_id: str = "",
    ) -> ServiceRecord:
        """Start (or replace) ``num_pods`` pod-server subprocesses."""
        existing = self.lookup(service_name)
        if existing:
            self.teardown(service_name, quiet=True)

        service_dir = self._service_dir(service_name)
        service_dir.mkdir(parents=True, exist_ok=True)
        ports = [free_port() for _ in range(num_pods)]
        local_ips = ",".join(f"127.0.0.1:{p}" for p in ports)

        base_env = _pod_base_env(compute_dict)

        # TPU-slice env emulation: a GKE TPU pod gets TPU_WORKER_ID from
        # the device plugin and MEGASCALE_SLICE_ID from its JobSet job
        # index (manifests.py:262). Local "pods" mirror that contract so
        # the slice-aware rank derivation in serving/frameworks.py —
        # including multi-slice TPU_WORKER_ID globalization — is testable
        # end-to-end without a cluster.
        from kubetorch_tpu.resources.compute.compute import Compute

        compute_obj = Compute.from_dict(compute_dict)
        # Only a distributed gang is a slice group (num_pods = workers ×
        # hosts, divisible by construction); independent serving replicas
        # must NOT get MEGASCALE identities — libtpu would try to join
        # them into one multi-slice job.
        tpu_spec = (compute_obj.tpu_spec
                    if compute_obj.distributed is not None else None)
        hosts_per_slice = tpu_spec.num_hosts if tpu_spec else 1
        n_slices = max(1, num_pods // hosts_per_slice) if tpu_spec else 1

        chips_per_pod = (compute_obj.tpu_spec.chips_per_pod
                         if compute_obj.tpu_spec else 0)
        taken = self._chips_taken() if chips_per_pod else set()
        pods = []
        for index, port in enumerate(ports):
            extra = {}
            if tpu_spec is not None:
                # Assign the computed identity EXPLICITLY: setdefault
                # would let a TPU_WORKER_ID inherited from the client's
                # own environment give every pod the same identity. An
                # explicit module_env (user override) still wins.
                extra["TPU_WORKER_ID"] = str(index % hosts_per_slice)
                if n_slices > 1:
                    extra.update({
                        "MEGASCALE_SLICE_ID": str(index // hosts_per_slice),
                        "MEGASCALE_NUM_SLICES": str(n_slices),
                        "MEGASCALE_COORDINATOR_ADDRESS": "127.0.0.1",
                    })
            pods.append(_spawn_pod(
                service_name, service_dir, index, port,
                _claim_chips(taken, chips_per_pod),
                base_env={**base_env, **extra}, module_env=module_env,
                launch_id=launch_id, local_ips=local_ips))

        record = ServiceRecord({
            "service_name": service_name,
            "backend": "local",
            "created_at": time.time(),
            "launch_id": launch_id,
            "pods": pods,
            "module_env": module_env,
            "module_meta": module_meta,
            "compute": compute_dict,
            "username": get_config().username,
            # the controller URL the pods inherited from THIS process's
            # env: a gang restart runs inside the controller (whose env
            # has no KT_CONTROLLER_URL) — without re-injecting it the
            # replacement pods come back headless: no registration, no
            # heartbeats, invisible to the liveness tracker that just
            # restarted them
            "controller_url": (env_str("KT_CONTROLLER_URL")
                               or get_config().controller_url),
        })
        self._record_path(service_name).write_text(json.dumps(record, indent=2))
        # Parity with the k8s backend: when a controller is configured,
        # the pool exists there too — pods register into it (instead of
        # parking as "waiting") and push their setup status, and
        # controller features (push-reload, TTL, pod views) see local
        # services. Best-effort: a missing controller never blocks local.
        try:
            from kubetorch_tpu.controller.client import ControllerClient

            controller = ControllerClient.maybe()
            if controller is not None:
                controller.register_pool(
                    service_name, module_meta, compute=compute_dict,
                    launch_id=launch_id, broadcast=False)
        # ktlint: disable=KT004 -- a missing controller never blocks local
        except Exception:
            pass
        self._wait_ready(record, launch_timeout, launch_id)
        return record

    # ------------------------------------------------------------------
    def _wait_ready(self, record: ServiceRecord, timeout: int,
                    launch_id: str):
        """Poll /ready on every pod; on failure surface the pod log tail
        (the local analog of the reference's pod-event extraction,
        ``service_manager.py:682``)."""
        deadline = time.time() + timeout
        pending = {p["port"]: p for p in record["pods"]}
        delay = 0.05  # tight at first — cold dispatch latency is a
        while pending and time.time() < deadline:  # headline metric
            for port, pod in list(pending.items()):
                if not _pid_alive(pod["pid"]):
                    raise ServiceTimeoutError(
                        f"pod {pod['index']} of {record['service_name']} "
                        f"exited during launch\n{_log_tail(pod['log'])}")
                ok, fatal = http_client.ready_state(
                    f"http://127.0.0.1:{port}", launch_id)
                if ok:
                    del pending[port]
                elif fatal:
                    # terminal setup failure (bad import, dead App
                    # subprocess): fail the launch now, not at timeout
                    raise StartupError(
                        f"pod {pod['index']} of {record['service_name']} "
                        f"failed setup: {fatal}\n{_log_tail(pod['log'])}")
            if pending:
                time.sleep(delay)
                delay = min(delay * 1.5, 0.3)
        if pending:
            pod = next(iter(pending.values()))
            raise ServiceTimeoutError(
                f"{len(pending)} pod(s) of {record['service_name']} not "
                f"ready after {timeout}s\n{_log_tail(pod['log'])}")

    # ------------------------------------------------------------------
    def service_url(self, service_name: str) -> str:
        record = self.lookup(service_name)
        if record is None:
            raise KeyError(f"no local service {service_name!r}")
        return record.urls[0]

    def pod_urls(self, service_name: str) -> List[str]:
        record = self.lookup(service_name)
        if record is None:
            raise KeyError(f"no local service {service_name!r}")
        return record.urls

    def reload(self, service_name: str, metadata: Dict[str, Any]):
        """Push new metadata to every pod (controller push-reload analog)."""
        for url in self.pod_urls(service_name):
            resp = http_client.sync_client().post(
                f"{url}/_reload", json=metadata, timeout=300.0)
            if resp.status_code != 200:
                from kubetorch_tpu.exceptions import rehydrate_exception

                raise rehydrate_exception(resp.json())

    def restart(self, service_name: str,
                compute_dict: Optional[Dict[str, Any]] = None,
                timeout: int = 120) -> Dict[str, Any]:
        """Gang-atomic restart: relaunch the whole subprocess set from
        the persisted service record (same env/meta/compute — ``launch``
        tears the old generation down first). The resilience layer calls
        this when liveness declares the gang dead; workers resume via
        ``resume_or_init`` + streaming restore on their own."""
        record = self.lookup(service_name)
        if record is None:
            raise KeyError(f"no local service {service_name!r}")
        module_env = dict(record.get("module_env") or {})
        controller_url = (record.get("controller_url")
                          or env_str("KT_CONTROLLER_URL"))
        if controller_url:
            # module_env overlays the launcher's env, so the replacement
            # pods re-register and heartbeat even though the restart runs
            # inside the controller process (no KT_CONTROLLER_URL there)
            module_env.setdefault("KT_CONTROLLER_URL", controller_url)
        new = self.launch(
            service_name,
            module_env=module_env,
            compute_dict=compute_dict or record.get("compute") or {},
            module_meta=record.get("module_meta") or {},
            num_pods=len(record.get("pods") or []) or 1,
            launch_timeout=timeout,
            launch_id=record.get("launch_id", ""),
        )
        return {"restarted": len(new.get("pods") or [])}

    def scale(self, service_name: str, replicas: int,
              launch_timeout: int = 120) -> Dict[str, Any]:
        """Resize a service IN PLACE: spawn additional pod-server
        subprocesses past the current set, or reap the highest-index
        pods down to ``replicas``. Unlike ``launch``/``restart`` the
        surviving pods are untouched — the fleet scaler's actuation
        must not replace a serving replica set to grow it.

        ``scale(0)`` reaps every pod but KEEPS the service record: the
        scale-from-zero path relaunches from it. Distributed gangs
        refuse — a gang's size is its topology; use restart."""
        record = self.lookup(service_name)
        if record is None:
            raise KeyError(f"no local service {service_name!r}")
        from kubetorch_tpu.resources.compute.compute import Compute

        compute_dict = record.get("compute") or {}
        if Compute.from_dict(compute_dict).distributed is not None:
            raise ValueError(
                f"{service_name} is a distributed gang — its size is its "
                f"topology; scale via a redeploy, not the replica knob")
        replicas = max(0, int(replicas))
        pods = list(record.get("pods") or [])
        current = len(pods)
        if replicas == current:
            return {"replicas": current}
        if replicas < current:
            for pod in pods[replicas:]:
                _kill_tree(pod["pid"])
            record["pods"] = pods[:replicas]
            self._record_path(service_name).write_text(
                json.dumps(record, indent=2))
            return {"replicas": replicas, "reaped": current - replicas}

        service_dir = self._service_dir(service_name)
        service_dir.mkdir(parents=True, exist_ok=True)
        module_env = dict(record.get("module_env") or {})
        controller_url = (record.get("controller_url")
                          or env_str("KT_CONTROLLER_URL"))
        if controller_url:
            # same re-injection as restart(): the scaler runs inside the
            # controller, whose own env has no KT_CONTROLLER_URL
            module_env.setdefault("KT_CONTROLLER_URL", controller_url)
        base_env = _pod_base_env(compute_dict)
        tpu_spec = Compute.from_dict(compute_dict).tpu_spec
        chips_per_pod = tpu_spec.chips_per_pod if tpu_spec else 0
        taken = self._chips_taken() if chips_per_pod else set()
        next_index = max((p["index"] for p in pods), default=-1) + 1
        launch_id = record.get("launch_id", "")
        new_ports = [free_port() for _ in range(replicas - current)]
        local_ips = ",".join(
            f"127.0.0.1:{p['port']}" for p in pods
        ) or ",".join(f"127.0.0.1:{p}" for p in new_ports)
        new_pods = []
        for offset, port in enumerate(new_ports):
            new_pods.append(_spawn_pod(
                service_name, service_dir, next_index + offset, port,
                _claim_chips(taken, chips_per_pod),
                base_env=base_env, module_env=module_env,
                launch_id=launch_id, local_ips=local_ips))
        record["pods"] = pods + new_pods
        self._record_path(service_name).write_text(
            json.dumps(record, indent=2))
        self._wait_ready(
            ServiceRecord({"service_name": service_name, "pods": new_pods}),
            launch_timeout, launch_id)
        return {"replicas": replicas, "launched": len(new_pods)}

    def _chips_taken(self) -> set:
        """Chip ids held by the live pods of every local service (a
        relaunch has torn its own down by now; a scale-up keeps its own).
        A chip belongs to one process at a time, so a new pod is given ids
        no live pod was given; an id the host does not have makes libtpu
        fail the pod's start-up, which surfaces as the launch's error."""
        taken: set = set()
        for record in self.list_services():
            for pod in record.get("pods") or []:
                if _pid_alive(pod["pid"]):
                    taken.update(pod.get("chips") or [])
        return taken

    def teardown(self, service_name: str, quiet: bool = False) -> bool:
        record = self.lookup(service_name)
        if record is None:
            if quiet:
                return False
            raise KeyError(f"no local service {service_name!r}")
        for pod in record["pods"]:
            _kill_tree(pod["pid"])
        shutil.rmtree(self._service_dir(service_name), ignore_errors=True)
        return True

    def logs(self, service_name: str, pod_index: Optional[int] = None,
             tail: int = 200) -> str:
        record = self.lookup(service_name)
        if record is None:
            raise KeyError(f"no local service {service_name!r}")
        chunks = []
        for pod in record["pods"]:
            if pod_index is not None and pod["index"] != pod_index:
                continue
            chunks.append(f"=== pod {pod['index']} ===\n"
                          f"{_log_tail(pod['log'], tail)}")
        return "\n".join(chunks)

    def is_up(self, service_name: str) -> bool:
        record = self.lookup(service_name)
        if record is None:
            return False
        return all(_pid_alive(p["pid"]) for p in record["pods"])


def _pid_alive(pid: int) -> bool:
    return _alive(pid, group=False)


def _pod_base_env(compute_dict: Dict[str, Any]) -> Dict[str, str]:
    """The client's environment as every pod of a service inherits it:
    this package importable even when the client was launched from
    elsewhere, and the platform pinned to what the compute asked for.

    A ``tpus=`` pod must come up on the chip or not at all: with the
    platform named, JAX raises at start-up when it is missing, and that
    surfaces as the launch's ``StartupError`` instead of a worker quietly
    computing on the CPU. Every other pod is pinned to the CPU so none can
    reach for a chip another process holds. The compute's own ``env``
    overlays this (an emulated slice in a test says ``JAX_PLATFORMS=cpu``
    itself)."""
    pkg_root = str(Path(__file__).resolve().parents[2])
    python_path = os.environ.get("PYTHONPATH", "")
    if pkg_root not in python_path.split(os.pathsep):
        python_path = (f"{pkg_root}{os.pathsep}{python_path}"
                       if python_path else pkg_root)
    return {**os.environ, "PYTHONPATH": python_path,
            "JAX_PLATFORMS": "tpu" if compute_dict.get("tpus") else "cpu"}


def _claim_chips(taken: set, count: int) -> List[int]:
    """The ``count`` lowest chip ids not in ``taken`` (marked taken)."""
    chips: List[int] = []
    candidate = 0
    while len(chips) < count:
        if candidate not in taken:
            chips.append(candidate)
            taken.add(candidate)
        candidate += 1
    return chips


def _spawn_pod(service_name: str, service_dir: Path, index: int, port: int,
               chips: List[int], *, base_env: Dict[str, str],
               module_env: Dict[str, str], launch_id: str,
               local_ips: str) -> Dict[str, Any]:
    """Start one pod-server subprocess, confined to ``chips`` when it has
    any; returns its service-record row."""
    env = {
        **base_env,
        **(chip_env(chips, [free_port()]) if chips else {}),
        **module_env,
        "PYTHONPATH": base_env["PYTHONPATH"],
        "KT_SERVICE_NAME": service_name,
        "KT_SERVER_PORT": str(port),
        "KT_REPLICA_INDEX": str(index),
        "KT_POD_NAME": f"{service_name}-{index}",
        "KT_LAUNCH_ID": launch_id,
        "LOCAL_IPS": local_ips,
    }
    log_path = service_dir / f"pod-{index}.log"
    with open(log_path, "ab") as log_file:
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.serving.server",
             "--host", "127.0.0.1", "--port", str(port)],
            env=env, stdout=log_file, stderr=subprocess.STDOUT,
            start_new_session=True)
    return {"pid": proc.pid, "port": port, "index": index,
            "log": str(log_path), "chips": chips}


def _alive(pid: int, group: bool) -> bool:
    """Whether ``pid`` — or, with ``group``, any process of the group it
    leads — still runs. The dead do not count: a zombie answers signal 0
    until its parent collects it (an orphaned worker waits on init for
    that), but it holds nothing, the chip included."""
    try:
        os.waitpid(pid, os.WNOHANG)     # collect our own dead pod server
    except ChildProcessError:
        pass
    try:
        (os.killpg if group else os.kill)(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    try:
        entries = ([e for e in os.listdir("/proc") if e.isdigit()]
                   if group else [str(pid)])
    except OSError:
        return True                     # no /proc to tell the dead apart
    for entry in entries:
        try:
            # "pid (comm) state ppid pgrp ..."; comm may hold anything
            state, _, pgrp = Path(f"/proc/{entry}/stat").read_text(
                ).rsplit(")", 1)[1].split()[:3]
        except (OSError, ValueError, IndexError):
            continue                    # exited while we looked
        if state not in "ZX" and (not group or int(pgrp) == pid):
            return True
    return False


def _gone(pid: int, timeout: float, group: bool) -> bool:
    deadline = time.time() + timeout
    while _alive(pid, group):
        if time.time() >= deadline:
            return False
        time.sleep(0.02)
    return True


def _kill_tree(pid: int):
    """Stop a pod and return once every process of it is gone — the worker
    among them, which is the one that holds the chip.

    SIGTERM goes to the pod server alone first: its SIGTERM sequence (drain,
    emergency checkpoint, report) asks the workers to save, and a worker
    signalled at the same moment is a corpse the server then waits on for
    its whole budget. The rest of its process group (it leads a session)
    follows, and SIGKILL for whatever is left."""
    try:
        os.kill(pid, signal.SIGTERM)
        _gone(pid, 3.0, group=False)
    except (ProcessLookupError, PermissionError):
        pass                # the server is gone already; its group may not be
    for sig, wait in ((signal.SIGTERM, 2.0), (signal.SIGKILL, 5.0)):
        try:
            os.killpg(pid, sig)
        except (ProcessLookupError, PermissionError):
            return
        if _gone(pid, wait, group=True):
            return


def _log_tail(log_path: str, lines: int = 60) -> str:
    try:
        content = Path(log_path).read_text(errors="replace").splitlines()
        return "\n".join(content[-lines:])
    except OSError:
        return "(no log available)"


_backends: Dict[str, Any] = {}


def get_backend(name: Optional[str] = None):
    name = name or get_config().backend
    if name not in _backends:
        if name == "local":
            _backends[name] = LocalBackend()
        elif name == "k8s":
            from kubetorch_tpu.provisioning.k8s_backend import K8sBackend

            _backends[name] = K8sBackend()
        else:
            raise ValueError(f"unknown backend {name!r}")
    return _backends[name]
