"""Pure manifest builders: Compute → K8s objects.

Reference: ``provisioning/utils.py`` (``build_deployment_manifest:431``,
``build_knative_manifest:489``) + the RESOURCE_CONFIGS kind table
(``:301-384``). TPU-first differences:

- multi-host TPU slices render as a **JobSet** (stable per-host identity +
  gang semantics — the ``jobset``/``tpu-slice`` kind SURVEY.md §7 hard-part 6
  calls for) with one pod per TPU VM host, ``TPU_WORKER_HOSTNAMES`` injected,
  and a headless service for slice DNS;
- Kueue gang admission sizes the gang to whole slices
  (``kueue.x-k8s.io/queue-name`` label + ``suspend`` semantics);
- everything is data-in/data-out: no cluster client here, so all builders are
  unit-testable without K8s.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from kubetorch_tpu.config import env_str
from kubetorch_tpu.resources.compute.compute import (
    KUEUE_QUEUE_LABEL,
    Compute,
)

SERVER_PORT = 32300
DEFAULT_SERVER_CMD = ["python", "-m", "kubetorch_tpu.serving.server"]


# --------------------------------------------------------------------------
# kind table (reference: RESOURCE_CONFIGS, provisioning/utils.py:301)
# --------------------------------------------------------------------------
RESOURCE_CONFIGS: Dict[str, Dict[str, Any]] = {
    "deployment": {
        "api_version": "apps/v1",
        "kind": "Deployment",
        "plural": "deployments",
        "pod_template_path": ("spec", "template"),
        "replica_path": ("spec", "replicas"),
        "routing": "service",
    },
    "jobset": {
        "api_version": "jobset.x-k8s.io/v1alpha2",
        "kind": "JobSet",
        "plural": "jobsets",
        "pod_template_path": (
            "spec", "replicatedJobs", 0, "template", "spec", "template"),
        "replica_path": (
            "spec", "replicatedJobs", 0, "template", "spec", "parallelism"),
        "routing": "headless",
    },
    "knative": {
        "api_version": "serving.knative.dev/v1",
        "kind": "Service",
        "plural": "services",
        "pod_template_path": ("spec", "template"),
        "replica_path": None,
        "routing": "knative",
    },
    "raycluster": {
        "api_version": "ray.io/v1",
        "kind": "RayCluster",
        "plural": "rayclusters",
        "pod_template_path": ("spec", "headGroupSpec", "template"),
        "replica_path": ("spec", "workerGroupSpecs", 0, "replicas"),
        "routing": "head",
    },
    # Kubeflow training-operator CRDs (reference SUPPORTED_TRAINING_JOBS,
    # provisioning/utils.py:423). Kinds are data, not code: the TPU-first
    # path is jobset, but BYO Kubeflow workloads route the same way.
    "pytorchjob": {
        "api_version": "kubeflow.org/v1",
        "kind": "PyTorchJob",
        "plural": "pytorchjobs",
        "pod_template_path": (
            "spec", "pytorchReplicaSpecs", "Worker", "template"),
        "replica_path": (
            "spec", "pytorchReplicaSpecs", "Worker", "replicas"),
        "routing": "headless",
    },
    "tfjob": {
        "api_version": "kubeflow.org/v1",
        "kind": "TFJob",
        "plural": "tfjobs",
        "pod_template_path": (
            "spec", "tfReplicaSpecs", "Worker", "template"),
        "replica_path": ("spec", "tfReplicaSpecs", "Worker", "replicas"),
        "routing": "headless",
    },
    "xgboostjob": {
        "api_version": "kubeflow.org/v1",
        "kind": "XGBoostJob",
        "plural": "xgboostjobs",
        "pod_template_path": (
            "spec", "xgbReplicaSpecs", "Worker", "template"),
        "replica_path": ("spec", "xgbReplicaSpecs", "Worker", "replicas"),
        "routing": "headless",
    },
    "mxjob": {
        "api_version": "kubeflow.org/v1",
        "kind": "MXJob",
        "plural": "mxjobs",
        "pod_template_path": (
            "spec", "mxReplicaSpecs", "Worker", "template"),
        "replica_path": ("spec", "mxReplicaSpecs", "Worker", "replicas"),
        "routing": "headless",
    },
    "selector": {  # BYO pods: route only, create nothing
        "api_version": None,
        "kind": None,
        "plural": None,
        "pod_template_path": None,
        "replica_path": None,
        "routing": "service",
    },
}


def navigate_path(obj: Any, path: tuple, default: Any = None) -> Any:
    """Walk a mixed dict/list path (reference: compute/utils.py:18)."""
    for part in path:
        try:
            obj = obj[part]
        except (KeyError, IndexError, TypeError):
            return default
    return obj


# --------------------------------------------------------------------------
# pod template
# --------------------------------------------------------------------------

def build_pod_template(
    service_name: str,
    compute: Compute,
    env: Optional[Dict[str, str]] = None,
    command: Optional[List[str]] = None,
) -> Dict[str, Any]:
    """The shared pod spec every kind embeds."""
    env = {**compute.env, **(env or {})}
    env.setdefault("KT_SERVICE_NAME", service_name)
    env.setdefault("KT_SERVER_PORT", str(SERVER_PORT))
    env.setdefault("JAX_COMPILATION_CACHE_DIR", env_str("KT_JAX_CACHE_DIR"))
    env_list = [{"name": k, "value": str(v)} for k, v in sorted(env.items())]
    # Downward-API-free pod identity (reference: http_server.py:146-185
    # derives identity without it; we inject the cheap fields anyway).
    env_list += [
        {"name": "KT_POD_NAME", "valueFrom": {
            "fieldRef": {"fieldPath": "metadata.name"}}},
        {"name": "KT_POD_IP", "valueFrom": {
            "fieldRef": {"fieldPath": "status.podIP"}}},
    ]
    for secret in compute.secrets:
        env_list += secret.pod_env()

    container: Dict[str, Any] = {
        "name": "kubetorch",
        "image": compute.image.image_id,
        "command": command or DEFAULT_SERVER_CMD,
        "ports": [{"containerPort": SERVER_PORT, "name": "kt-server"}],
        "env": env_list,
        "resources": compute.pod_resources(),
        "readinessProbe": {
            "httpGet": {"path": "/ready", "port": SERVER_PORT},
            "initialDelaySeconds": 2, "periodSeconds": 3,
        },
    }
    mounts = [v.pod_mount() for v in compute.volumes]
    mounts += [m for m in (s.pod_mount() for s in compute.secrets) if m]
    if mounts:
        container["volumeMounts"] = mounts

    spec: Dict[str, Any] = {"containers": [container]}
    selectors = compute.all_node_selectors()
    if selectors:
        spec["nodeSelector"] = selectors
    if compute.tolerations:
        spec["tolerations"] = compute.tolerations
    if compute.tpu_spec:
        spec.setdefault("tolerations", []).append({
            "key": "google.com/tpu", "operator": "Exists",
            "effect": "NoSchedule"})
    if compute.priority_class:
        spec["priorityClassName"] = compute.priority_class
    if compute.service_account:
        spec["serviceAccountName"] = compute.service_account
    pod_volumes = [v.pod_volume() for v in compute.volumes]
    pod_volumes += [v for v in (s.pod_volume() for s in compute.secrets) if v]
    if pod_volumes:
        spec["volumes"] = pod_volumes

    return {
        "metadata": {
            "labels": compute.workload_labels(service_name),
            "annotations": compute.workload_annotations(),
        },
        "spec": spec,
    }


# --------------------------------------------------------------------------
# kind builders
# --------------------------------------------------------------------------

def build_deployment_manifest(
    service_name: str, compute: Compute,
    env: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    template = build_pod_template(service_name, compute, env)
    return {
        "apiVersion": "apps/v1",
        "kind": "Deployment",
        "metadata": {
            "name": service_name,
            "namespace": compute.namespace,
            "labels": compute.workload_labels(service_name),
            "annotations": compute.workload_annotations(),
        },
        "spec": {
            "replicas": compute.num_pods,
            "selector": {"matchLabels": {
                "kubetorch.com/service": service_name}},
            "template": template,
        },
    }


def build_jobset_manifest(
    service_name: str, compute: Compute,
    env: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    """Multi-host TPU slice: one JobSet, ``workers`` replicated jobs (one per
    slice), each with parallelism = hosts-per-slice and TPU gang env."""
    tpu = compute.tpu_spec
    workers = compute.distributed.workers if compute.distributed else 1
    hosts = tpu.num_hosts if tpu else 1
    env = dict(env or {})
    if tpu:
        slice0 = tpu.worker_hostnames(service_name, compute.namespace,
                                      slice_index=0)
        if workers > 1:
            # Multi-slice (megascale): each replicated job is one slice;
            # libtpu's DCN mesh spans slices via the MEGASCALE contract.
            # TPU_WORKER_HOSTNAMES must list THIS slice's hosts, which vary
            # per job — the pod server expands the pattern with its
            # MEGASCALE_SLICE_ID at startup (serving/frameworks.py).
            env.setdefault(
                "KT_TPU_HOSTNAME_PATTERN",
                tpu.worker_hostnames(service_name, compute.namespace,
                                     slice_index=0)[0].replace(
                    f"-0-0.", "-{slice}-{host}.", 1))
            env.setdefault("KT_TPU_HOSTS_PER_SLICE", str(hosts))
            env.setdefault("MEGASCALE_NUM_SLICES", str(workers))
            env.setdefault("MEGASCALE_COORDINATOR_ADDRESS",
                           f"{slice0[0]}:8081")
        else:
            env.setdefault("TPU_WORKER_HOSTNAMES", ",".join(slice0))
    template = build_pod_template(service_name, compute, env)
    template["spec"]["subdomain"] = f"{service_name}-headless"
    if tpu and workers > 1:
        # slice id comes from the JobSet job index, resolved per pod via
        # the downward API (annotation set by the JobSet controller).
        template["spec"]["containers"][0]["env"].append({
            "name": "MEGASCALE_SLICE_ID",
            "valueFrom": {"fieldRef": {"fieldPath":
                "metadata.annotations['jobset.sigs.k8s.io/job-index']"}},
        })
    job_spec: Dict[str, Any] = {
        # Indexed completion + JobSet DNS (below) give each pod the stable
        # hostname the TPU_WORKER_HOSTNAMES contract resolves.
        "parallelism": hosts,
        "completions": hosts,
        "completionMode": "Indexed",
        "backoffLimit": 0,
        "template": template,
    }
    manifest: Dict[str, Any] = {
        "apiVersion": "jobset.x-k8s.io/v1alpha2",
        "kind": "JobSet",
        "metadata": {
            "name": service_name,
            "namespace": compute.namespace,
            "labels": compute.workload_labels(service_name),
            "annotations": compute.workload_annotations(),
        },
        "spec": {
            "network": {
                "enableDNSHostnames": True,
                "subdomain": f"{service_name}-headless",
            },
            "replicatedJobs": [{
                "name": "workers",
                "replicas": workers,
                "template": {"spec": job_spec},
            }],
        },
    }
    if compute.queue_name:
        # Kueue admits the whole JobSet as one gang sized in slices.
        manifest["metadata"]["labels"][KUEUE_QUEUE_LABEL] = compute.queue_name
        manifest["spec"]["suspend"] = True
    return manifest


def build_knative_manifest(
    service_name: str, compute: Compute,
    env: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    template = build_pod_template(service_name, compute, env)
    annotations = dict(template["metadata"].get("annotations") or {})
    if compute.autoscaling is not None:
        annotations.update(compute.autoscaling.to_annotations())
    template["metadata"]["annotations"] = annotations
    if (compute.autoscaling is not None
            and compute.autoscaling.container_concurrency):
        template["spec"]["containerConcurrency"] = (
            compute.autoscaling.container_concurrency)
    return {
        "apiVersion": "serving.knative.dev/v1",
        "kind": "Service",
        "metadata": {
            "name": service_name,
            "namespace": compute.namespace,
            "labels": compute.workload_labels(service_name),
        },
        "spec": {"template": template},
    }


def build_service_manifest(
    service_name: str, compute: Compute, headless: bool = False,
    selector: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    name = f"{service_name}-headless" if headless else service_name
    spec: Dict[str, Any] = {
        "selector": selector or {"kubetorch.com/service": service_name},
        "ports": [{"name": "kt-server", "port": SERVER_PORT,
                   "targetPort": SERVER_PORT}],
    }
    if headless:
        spec["clusterIP"] = "None"
        spec["publishNotReadyAddresses"] = True  # quorum sees starting pods
    return {
        "apiVersion": "v1",
        "kind": "Service",
        "metadata": {
            "name": name,
            "namespace": compute.namespace,
            "labels": compute.workload_labels(service_name),
        },
        "spec": spec,
    }


def preprocess_byo_manifest(
    service_name: str, compute: Compute,
    env: Optional[Dict[str, str]] = None,
) -> Dict[str, Any]:
    """Layer kubetorch identity onto a user-supplied manifest (reference:
    ``ServiceManager`` manifest preprocessing + ``from_manifest:271``):
    stamp labels so routing/teardown find it, and merge ``KT_*`` env into
    the pod template so the pod server can register itself. The user's
    command/image are left untouched."""
    import copy as _copy

    manifest = _copy.deepcopy(compute.manifest or {})
    kind = (manifest.get("kind") or "").lower()
    config = next(
        (c for c in RESOURCE_CONFIGS.values()
         if (c.get("kind") or "").lower() == kind), None)
    meta = manifest.setdefault("metadata", {})
    # the workload must be addressable by service_name (teardown/lookup
    # delete by name), so the manifest's own name is overridden.
    meta["name"] = service_name
    meta.setdefault("namespace", compute.namespace)
    meta.setdefault("labels", {}).update(
        compute.workload_labels(service_name))
    meta.setdefault("annotations", {}).update(
        compute.workload_annotations())

    template = (navigate_path(manifest, config["pod_template_path"])
                if config and config.get("pod_template_path") else None)
    if isinstance(template, dict):
        tmeta = template.setdefault("metadata", {})
        tmeta.setdefault("labels", {}).update(
            compute.workload_labels(service_name))
        merged = {**compute.env, **(env or {})}
        merged.setdefault("KT_SERVICE_NAME", service_name)
        merged.setdefault("KT_SERVER_PORT", str(SERVER_PORT))
        containers = navigate_path(template, ("spec", "containers"),
                                   default=[])
        for container in containers:
            existing = {e.get("name") for e in container.get("env", [])}
            container.setdefault("env", []).extend(
                {"name": k, "value": str(v)}
                for k, v in sorted(merged.items()) if k not in existing)
    return manifest


def build_workload_record(
    service_name: str, compute: "Compute",
    module_meta: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """The declarative KubetorchWorkload record (reference CRD:
    kubetorchworkloads.kubetorch.com/v1alpha1 — selector + serviceConfig +
    module). Applied best-effort alongside the workload so ``kubectl get
    ktw`` shows what kubetorch deployed."""
    meta = module_meta or {}
    return {
        "apiVersion": "kubetorch.com/v1alpha1",
        "kind": "KubetorchWorkload",
        "metadata": {
            "name": service_name,
            "namespace": compute.namespace,
            "labels": compute.workload_labels(service_name),
        },
        "spec": {
            "selector": {"kubetorch.com/service": service_name},
            "serviceConfig": {
                "port": SERVER_PORT,
                "deploymentMode": compute.deployment_mode,
                "replicas": compute.num_pods,
            },
            "module": {
                "type": meta.get("callable_type", "fn"),
                "dispatch": (compute.distributed.type
                             if compute.distributed else "local"),
                "pointers": {
                    "import_path": meta.get("import_path", ""),
                    "name": meta.get("name", ""),
                },
            },
        },
    }


LAUNCH_ID_LABEL = "kubetorch.com/launch-id"


def _stamp_launch_id(manifest: Dict[str, Any], launch_id: str):
    """Stamp the deploy generation into every pod/job template's labels.

    Launch waiters filter pods by this label: under one service label a
    terminating previous-generation pod can stay Ready (and WS-connected
    with a stale setup_error) well into a redeploy — counting it toward
    readiness would declare the new launch healthy before its own pods
    even pulled images."""
    if not launch_id:
        return

    def walk(node):
        if isinstance(node, dict):
            template = node.get("template")
            if isinstance(template, dict) and "spec" in template:
                meta = template.setdefault("metadata", {})
                meta.setdefault("labels", {})[LAUNCH_ID_LABEL] = launch_id
            for value in node.values():
                walk(value)
        elif isinstance(node, list):
            for item in node:
                walk(item)

    walk(manifest)


def build_manifests(
    service_name: str, compute: Compute,
    env: Optional[Dict[str, str]] = None,
) -> List[Dict[str, Any]]:
    """Everything to apply for this Compute, in order."""
    mode = compute.deployment_mode
    out: List[Dict[str, Any]] = []
    for volume in compute.volumes:
        out.append(volume.to_pvc_manifest(compute.namespace))
    for secret in compute.secrets:
        out.append(secret.to_manifest(compute.namespace))
    if mode == "deployment":
        out.append(build_deployment_manifest(service_name, compute, env))
    elif mode == "jobset":
        out.append(build_jobset_manifest(service_name, compute, env))
    elif mode == "knative":
        out.append(build_knative_manifest(service_name, compute, env))
    elif mode == "manifest":
        out.append(preprocess_byo_manifest(service_name, compute, env))
    elif mode == "selector":
        # BYO pods: create nothing but the routing Service below.
        pass
    else:
        raise ValueError(f"unknown deployment mode {mode!r}")
    # Knative's reconciler owns the routing Service (both the native knative
    # mode and a BYO Knative Service manifest) — creating our own would fight
    # it for the name.
    byo_is_knative = (
        mode == "manifest"
        and "knative" in (compute.manifest or {}).get("apiVersion", ""))
    if mode != "knative" and not byo_is_knative:
        out.append(build_service_manifest(
            service_name, compute, selector=compute.selector))
        if compute.distributed is not None or (
                compute.tpu_spec and compute.tpu_spec.multi_host):
            out.append(build_service_manifest(
                service_name, compute, headless=True,
                selector=compute.selector))
    launch_id = (env or {}).get("KT_LAUNCH_ID", "")
    for manifest in out:
        _stamp_launch_id(manifest, launch_id)
    return out
