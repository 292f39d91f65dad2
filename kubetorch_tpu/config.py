"""Layered configuration: env vars > local file cache > cluster ConfigMap.

Reference: ``python_client/kubetorch/config.py:29-230`` (KubetorchConfig) with
the same precedence rules. Env vars are ``KT_*``; the file cache lives at
``~/.ktpu/config`` (YAML); the cluster layer is fetched lazily from the
controller (ConfigMap-backed) and merged lowest-precedence.
"""

from __future__ import annotations

import getpass
import json
import os
import threading
from dataclasses import dataclass, field, fields
from pathlib import Path
from typing import Any, Dict, Iterator, Optional

import yaml

_CONFIG_PATH = Path(os.environ.get("KT_CONFIG_PATH", "~/.ktpu/config")).expanduser()

_ENV_MAP = {
    "username": "KT_USERNAME",
    "namespace": "KT_NAMESPACE",
    "install_namespace": "KT_INSTALL_NAMESPACE",
    "install_url": "KT_INSTALL_URL",
    "prefix_username": "KT_PREFIX_USERNAME",
    "stream_logs": "KT_STREAM_LOGS",
    "stream_metrics": "KT_STREAM_METRICS",
    "backend": "KT_BACKEND",
    "serialization": "KT_SERIALIZATION",
    "launch_timeout": "KT_LAUNCH_TIMEOUT",
    "inactivity_ttl": "KT_INACTIVITY_TTL",
    "log_level": "KT_LOG_LEVEL",
    "store_url": "KT_STORE_URL",
    "controller_url": "KT_CONTROLLER_URL",
}

_BOOLS = {"prefix_username", "stream_logs", "stream_metrics"}
_INTS = {"launch_timeout"}


def _coerce(name: str, value: Any) -> Any:
    if value is None:
        return None
    if name in _BOOLS and isinstance(value, str):
        return value.strip().lower() in ("1", "true", "yes", "on")
    if name in _INTS and isinstance(value, str):
        return int(value)
    return value


@dataclass
class KubetorchConfig:
    username: str = field(default_factory=lambda: os.environ.get("USER") or getpass.getuser())
    namespace: str = "default"
    install_namespace: str = "kubetorch"
    install_url: Optional[str] = None
    prefix_username: bool = True
    stream_logs: bool = True
    stream_metrics: bool = False
    # "local" runs pods as subprocesses on this machine (tests / laptops with
    # no cluster); "k8s" applies manifests through the controller.
    backend: str = "local"
    serialization: str = "json"   # default wire format; "pickle" must be allowed
    allowed_serialization: tuple = ("json", "pickle")
    launch_timeout: int = 600
    inactivity_ttl: Optional[str] = None
    log_level: str = "INFO"
    store_url: Optional[str] = None
    controller_url: Optional[str] = None

    def refresh(self) -> None:
        """Re-apply the precedence stack: file cache, then env vars on top."""
        file_cfg: Dict[str, Any] = {}
        if _CONFIG_PATH.exists():
            try:
                file_cfg = yaml.safe_load(_CONFIG_PATH.read_text()) or {}
            except Exception:
                file_cfg = {}
        for f in fields(self):
            if f.name in file_cfg:
                setattr(self, f.name, _coerce(f.name, file_cfg[f.name]))
        for name, env in _ENV_MAP.items():
            if env in os.environ:
                setattr(self, name, _coerce(name, os.environ[env]))

    def merge_cluster(self, cluster_cfg: Dict[str, Any]) -> None:
        """Merge cluster-level defaults at the *lowest* precedence."""
        file_cfg: Dict[str, Any] = {}
        if _CONFIG_PATH.exists():
            try:
                file_cfg = yaml.safe_load(_CONFIG_PATH.read_text()) or {}
            except Exception:
                file_cfg = {}
        for key, value in (cluster_cfg or {}).items():
            known = {f.name for f in fields(self)}
            if key in known and key not in file_cfg and _ENV_MAP.get(key) not in os.environ:
                setattr(self, key, _coerce(key, value))

    def save(self, **updates: Any) -> None:
        """Persist values to the local file cache."""
        current: Dict[str, Any] = {}
        if _CONFIG_PATH.exists():
            try:
                current = yaml.safe_load(_CONFIG_PATH.read_text()) or {}
            except Exception:
                current = {}
        current.update(updates)
        _CONFIG_PATH.parent.mkdir(parents=True, exist_ok=True)
        _CONFIG_PATH.write_text(yaml.safe_dump(current))
        self.refresh()

    def as_dict(self) -> Dict[str, Any]:
        return {f.name: getattr(self, f.name) for f in fields(self)}


_config: Optional[KubetorchConfig] = None
_lock = threading.Lock()


def get_config() -> KubetorchConfig:
    global _config
    with _lock:
        if _config is None:
            _config = KubetorchConfig()
            _config.refresh()
        return _config


def configure(**updates: Any) -> KubetorchConfig:
    """Set config values for this process (not persisted)."""
    cfg = get_config()
    for key, value in updates.items():
        if not hasattr(cfg, key):
            raise AttributeError(f"unknown config key: {key}")
        setattr(cfg, key, value)
    return cfg


# ---------------------------------------------------------------------------
# Typed KT_* knob registry
#
# Every ``KT_*`` environment variable the project reads is declared here —
# name, type, default, and a doc string — and read through the ``env_*``
# accessors below. This is the single source the generated
# ``docs/configuration.md`` table and the KT003 lint rule
# (``kubetorch_tpu/analysis``) are built from: ad-hoc ``os.environ`` reads
# of ``KT_*`` names anywhere else in the package are a lint error.
#
# Semantics shared by all accessors:
#   - an UNSET or EMPTY-STRING variable means "use the declared default"
#     (matching the historical ``os.environ.get(k) or default`` idiom);
#   - a set-but-malformed value raises :class:`ConfigError` naming the
#     variable, instead of an opaque ``ValueError`` from deep inside a
#     heartbeat loop or an import;
#   - reading an UNDECLARED name raises :class:`ConfigError` — declare the
#     knob first, that is the point of the registry.
# ---------------------------------------------------------------------------


class ConfigError(Exception):
    """A ``KT_*`` environment variable is undeclared or holds a value that
    cannot be parsed as its declared type."""


@dataclass(frozen=True)
class Knob:
    name: str
    type: str          # "str" | "int" | "float" | "bool" | "json"
    default: Any
    doc: str
    section: str = "general"


KNOBS: Dict[str, Knob] = {}


def _knob(name: str, type_: str, default: Any, doc: str,
          section: str = "general") -> None:
    KNOBS[name] = Knob(name=name, type=type_, default=default, doc=doc,
                       section=section)


# --- client -----------------------------------------------------------------
_knob("KT_CONFIG_PATH", "str", "~/.ktpu/config",
      "Path of the local YAML config cache layered under env vars.", "client")
_knob("KT_USERNAME", "str", None,
      "Username used to prefix service names (defaults to $USER).", "client")
_knob("KT_NAMESPACE", "str", "default",
      "Kubernetes namespace for deploys and controller queries.", "client")
_knob("KT_INSTALL_NAMESPACE", "str", "kubetorch",
      "Namespace the kubetorch control plane is installed in.", "client")
_knob("KT_INSTALL_URL", "str", None,
      "Override URL for the control-plane install manifest.", "client")
_knob("KT_PREFIX_USERNAME", "bool", True,
      "Prefix service names with the username (root-greet).", "client")
_knob("KT_STREAM_LOGS", "bool", True,
      "Stream pod logs back to the client during calls.", "client")
_knob("KT_STREAM_METRICS", "bool", False,
      "Stream pod metrics back to the client during calls.", "client")
_knob("KT_BACKEND", "str", "local",
      "Provisioning backend: 'local' (subprocess pods) or 'k8s'.", "client")
_knob("KT_SERIALIZATION", "str", "json",
      "Default wire format for call payloads ('json' or 'pickle').", "client")
_knob("KT_LAUNCH_TIMEOUT", "int", 600,
      "Seconds to wait for a deployed service to become ready.", "client")
_knob("KT_INACTIVITY_TTL", "str", None,
      "Idle TTL after which a service is scaled down (e.g. '2h').", "client")
_knob("KT_LOG_LEVEL", "str", "INFO",
      "Client-side log level.", "client")
_knob("KT_STORE_URL", "str", None,
      "Base URL of the data store server (weight sync, code sync).", "client")
_knob("KT_CONTROLLER_URL", "str", None,
      "Base URL of the controller (registry, log sink, liveness).", "client")
_knob("KT_CONTROLLER_TOKEN", "str", None,
      "Bearer token sent to the controller when auth is enabled.", "client")
_knob("KT_RETRY_ATTEMPTS", "int", 3,
      "Max attempts for retryable transport errors (retry.py).", "client")
_knob("KT_CODE_SYNC", "str", "auto",
      "Code-sync mode for deploys: auto, store, rsync, or off.", "client")
_knob("KT_RUN_ID", "str", None,
      "Ambient run id propagated to subprocess runs (runs/api.py).", "client")

# --- pod identity / bootstrap (set by provisioning, read by the pod) --------
_knob("KT_SERVICE_NAME", "str", "", "Service this pod belongs to.", "pod")
_knob("KT_POD_NAME", "str", None,
      "Pod name; falls back to the hostname when unset.", "pod")
_knob("KT_POD_IP", "str", None,
      "Pod IP used for registration and distributed rendezvous.", "pod")
_knob("KT_REPLICA_INDEX", "int", 0, "Replica index within the gang.", "pod")
_knob("KT_SERVER_PORT", "int", 32300, "Pod HTTP server port.", "pod")
_knob("KT_LAUNCH_ID", "str", "",
      "Launch generation id; stale-pod reports are fenced on it.", "pod")
_knob("KT_CLS_OR_FN_NAME", "str", "",
      "Name of the deployed callable (class or function).", "pod")
_knob("KT_CALLABLE_TYPE", "str", "fn",
      "Kind of deployed callable: fn, cls, or app.", "pod")
_knob("KT_CALLABLE_NAME", "str", "",
      "Instance name for the deployed callable.", "pod")
_knob("KT_ROOT_PATH", "str", "",
      "Client project root the synced code tree is relative to.", "pod")
_knob("KT_IMPORT_PATH", "str", "",
      "Module path to import the callable from.", "pod")
_knob("KT_NUM_PROCS", "int", 1, "Worker processes per pod.", "pod")
_knob("KT_FRAMEWORK", "str", None,
      "Distributed framework to initialize: jax, ray, or unset.", "pod")
_knob("KT_INIT_ARGS", "json", None,
      "JSON [args, kwargs] used to construct a deployed class.", "pod")
_knob("KT_DISTRIBUTED", "json", None,
      "JSON distributed topology spec (workers, framework).", "pod")
_knob("KT_ALLOWED_SERIALIZATION", "str", None,
      "Comma-separated wire formats the pod accepts.", "pod")
_knob("KT_APP_CMD", "str", None,
      "Shell command for app pods (uvicorn, etc.).", "pod")
_knob("KT_APP_PORT", "int", 0, "Port the app command listens on.", "pod")
_knob("KT_APP_HEALTH_PATH", "str", "",
      "HTTP path polled for app readiness.", "pod")
_knob("KT_APP_HEALTH_INTERVAL", "float", 0.5,
      "Seconds between app readiness polls.", "pod")
_knob("KT_CODE_KEY", "str", None,
      "Store key of the synced code tarball.", "pod")
_knob("KT_CODE_DEST", "str", "~/.ktpu/code",
      "Directory synced code trees are unpacked into.", "pod")

# --- serving ----------------------------------------------------------------
_knob("KT_CHANNEL_DEPTH", "int", 2,
      "Default pipeline depth (calls in flight) per CallChannel.", "serving")
_knob("KT_WORKER_THREADS", "int", 8,
      "Threads per worker process for concurrent calls.", "serving")
_knob("KT_PROXY_TIMEOUT", "float", 600.0,
      "Client HTTP timeout for proxied calls (seconds).", "serving")
_knob("KT_METRICS_INTERVAL", "float", 15.0,
      "Seconds between pod metrics pushes to the controller.", "serving")
_knob("KT_DEBUG_PORT", "int", 5678,
      "Base port for the remote debugger (plus LOCAL_RANK).", "serving")
_knob("KT_JAX_COORD_PORT", "int", 8476,
      "Port of the JAX distributed coordinator.", "serving")
_knob("KT_JAX_CACHE_DIR", "str", "/tmp/kt-jax-cache",
      "JAX compilation cache dir the K8s manifests inject into pods as "
      "JAX_COMPILATION_CACHE_DIR (mount a volume there to survive pod "
      "reschedules). Local processes use compile_cache_dir() instead.",
      "serving")
_knob("KT_TPU_HOSTNAME_PATTERN", "str", None,
      "Format string for TPU worker hostnames ({slice}, {host}).", "serving")
_knob("KT_TPU_HOSTS_PER_SLICE", "int", None,
      "Hosts per TPU slice; inferred from topology when unset.", "serving")
_knob("KT_TREE_MINIMUM", "int", 100,
      "Gang size at which SPMD supervisor switches to tree fanout.", "serving")
_knob("KT_FANOUT", "int", 50,
      "Branching factor of the SPMD supervisor tree.", "serving")
_knob("KT_ACTOR_HOSTS", "str", "",
      "Comma-separated host list for actor meshes.", "serving")

# --- serving reliability (exactly-once replay / deadlines / admission) ------
_knob("KT_RESULT_RETAIN", "int", 256,
      "Completed channel-call results retained per channel session for "
      "idempotent replay after a reconnect (ring; oldest evicted).",
      "serving-reliability")
_knob("KT_RESULT_RETAIN_BYTES", "int", 64 << 20,
      "Byte backstop on one session's retention ring — oldest retained "
      "results are evicted past it (count bound notwithstanding).",
      "serving-reliability")
_knob("KT_RESULT_RETAIN_S", "float", 300.0,
      "Seconds a detached channel session (its retention ring and any "
      "still-running calls) survives before the server expires it.",
      "serving-reliability")
_knob("KT_REPLAY_ATTEMPTS", "int", 3,
      "Client reconnect+replay attempts per call before a disconnect "
      "surfaces as ChannelInterrupted.", "serving-reliability")
_knob("KT_MAX_QUEUE_DEPTH", "int", 256,
      "Admission bound on calls queued+executing per pod; excess is shed "
      "with 429 + Retry-After (0 disables).", "serving-reliability")
_knob("KT_MAX_QUEUE_DELAY_S", "float", 30.0,
      "Shed when the estimated queue delay exceeds this; also caps the "
      "computed Retry-After.", "serving-reliability")
_knob("KT_CB_FAILURES", "int", 5,
      "Consecutive transport failures that open the client circuit "
      "breaker for an endpoint (0 disables).", "serving-reliability")
_knob("KT_CB_RESET_S", "float", 10.0,
      "Seconds an open circuit breaker waits before half-opening to let "
      "one probe call through.", "serving-reliability")

# --- serving engine (server-resident continuous-batching decode loop) -------
_knob("KT_ENGINE_PREFILL_CHUNK", "int", 64,
      "Tokens per interleaved prefill chunk: prompts longer than this "
      "prefill into the live grid one chunk per decode step instead of "
      "one monolithic admission, so long prompts never stall token "
      "emission.", "engine")
_knob("KT_ENGINE_ADMIT_ROWS", "int", 0,
      "Max rows admitted into the live batch per engine tick "
      "(0 = every free row).", "engine")
_knob("KT_ENGINE_MAX_WAITING", "int", 512,
      "Hard cap on generation requests queued ahead of admission; past "
      "it new programs are shed typed (ServerOverloaded / 429) "
      "(0 disables).", "engine")
_knob("KT_ENGINE_POLL_S", "float", 0.02,
      "Idle wait of the engine driver thread between work checks.",
      "engine")
_knob("KT_ENGINE_STALL_S", "float", 120.0,
      "Seconds a generation stream waits for the next engine frame "
      "before its rows are evicted and the stream fails typed.",
      "engine")

# --- engine KV manager (paged KV blocks, prefix cache, session offload) -----
_knob("KT_KV_BLOCK_TOKENS", "int", 16,
      "Tokens per KV block in the engine's HBM ledger — the accounting "
      "(and session-export leaf) granularity for rows, shared prefixes, "
      "and admission costs.", "engine-kv")
_knob("KT_KV_HBM_BUDGET", "int", 0,
      "Engine HBM budget in KV blocks shared by row planes and cached "
      "prefix blocks; past it cold prefixes LRU-evict and new programs "
      "shed typed (0 = 2x the decode grid's block count).", "engine-kv")
_knob("KT_KV_PREFIX_SPLIT", "str", "off",
      "Automatic prefix-sharing split rule applied to every submitted "
      "prompt: 'off', 'len:N' (first N tokens are the shared prefix), or "
      "'token:ID' (split after the last occurrence of token ID, e.g. a "
      "system-prompt terminator).", "engine-kv")
_knob("KT_KV_OFFLOAD_CODEC", "str", "auto",
      "Wire codec for parked-session KV offload. 'auto' = raw (exact "
      "resume for every grid; int8 grids' (q, scale) pairs are already "
      "half-size). 'int8' halves a bf16 grid's wire bytes at the cost "
      "of token-exact resume; zlib/zstd compress losslessly.",
      "engine-kv")
_knob("KT_KV_SESSION_PREFIX", "str", "kv/sessions",
      "Store key prefix parked-session KV blobs are published under.",
      "engine-kv")
_knob("KT_KV_SESSION_DELTA", "bool", True,
      "Delta-manifest publish for session KV re-parks: a grown cache "
      "ships only its new blocks (per-block leaves + PR-3 delta).",
      "engine-kv")

# --- disaggregated prefill/decode (phase tiers + KV handoff) ----------------
_knob("KT_DISAGG_PHASE", "str", "mixed",
      "Serving tier this pod's DecodeEngine runs as: 'prefill' (admit/"
      "prefill only; every program must carry handoff= and its row is "
      "exported to the decode tier), 'decode' (imports exported rows "
      "and streams; still runs suffix prefills so prefix-cache hits "
      "stay tier-local), or 'mixed' (monolithic).", "engine-disagg")
_knob("KT_HANDOFF_PREFIX", "str", "kv/handoffs",
      "Store key prefix exported handoff rows are published under.",
      "engine-disagg")
_knob("KT_HANDOFF_CODEC", "str", "auto",
      "Wire codec for prefill→decode row handoff. 'auto' branches on "
      "the grid: int8 KV grids ship their (q, scale) pairs raw "
      "(bit-exact at half size); bf16/f32 grids take the int8 wire "
      "codec (~2-4x fewer bytes). 'raw' forces exactness everywhere; "
      "zlib/zstd compress losslessly.", "engine-disagg")
_knob("KT_HANDOFF_TIMEOUT_S", "float", 30.0,
      "Seconds the decode-side import polls for the prefill pod's "
      "export to land before falling back to monolithic same-pod "
      "decode (the program still carries its prompt).", "engine-disagg")
_knob("KT_HANDOFF_POLL_S", "float", 0.01,
      "Decode-side poll interval while waiting for an in-flight "
      "handoff export.", "engine-disagg")

# --- multi-tenant LoRA serving (device-resident adapter pool) ---------------
_knob("KT_LORA_SLOTS", "int", 0,
      "Fixed adapter-axis width of the serving engine's stacked LoRA "
      "tree (0 = off: the axis is exactly the ctor adapters). A fixed "
      "width is what lets the AdapterPool hot-load/evict named "
      "adapters into slots without recompiling any serving "
      "executable; the per-row gather select's cost is flat in this.",
      "engine-lora")
_knob("KT_LORA_LOAD_EMA_ALPHA", "float", 0.3,
      "Weight of one measured adapter load (store fetch + device "
      "write) in the pool's load-time EMA — the Retry-After a "
      "residency-miss shed quotes while the cold adapter loads.",
      "engine-lora")
_knob("KT_LORA_LOAD_S", "float", 0.2,
      "Seed estimate for the adapter load-time EMA before any load "
      "has been measured (the first cold miss's Retry-After).",
      "engine-lora")

# --- speculative scheduling (per-row adaptive lookahead in the engine) ------
_knob("KT_SPEC_K_MAX", "int", 8,
      "Maximum per-row speculative lookahead (verify-forward width: 1 "
      "carried token + k-1 prompt-lookup drafts). Each row's k adapts "
      "between 1 and this via its acceptance EMA; the default for "
      "RollingGenerator(spec_k=None).", "engine-spec")
_knob("KT_SPEC_NGRAM", "int", 3,
      "N-gram length of the prompt-lookup draft matcher (the last N "
      "context tokens are matched against earlier occurrences).",
      "engine-spec")
_knob("KT_SPEC_EMA_ALPHA", "float", 0.25,
      "Weight of one verify round's acceptance in the per-row EMA that "
      "drives k adaptation (higher = faster regime tracking, noisier).",
      "engine-spec")
_knob("KT_SPEC_OCCUPANCY_THROTTLE", "float", 0.85,
      "Row occupancy at/above which the engine driver caps every row's "
      "lookahead at 1 (compute-bound regime: verify width stops being "
      "free); below it the cap lifts and high-accept rows regrow "
      "toward KT_SPEC_K_MAX.", "engine-spec")

# --- concurrency sanitizer (kubetorch_tpu/analysis/san.py, `ktpu san`) ------
_knob("KT_SAN", "bool", False,
      "Enable the runtime concurrency sanitizer: instrument lock "
      "factories to record per-thread acquisition order, detect "
      "event-loop stalls, and dump a per-process report at exit.",
      "sanitizer")
_knob("KT_SAN_DIR", "str", None,
      "Directory the sanitizer dumps per-process reports "
      "(san-<pid>.json) into; subprocess pods inherit it so one test "
      "session's reports land together. Unset = no dump.", "sanitizer")
_knob("KT_SAN_STALL_MS", "float", 100.0,
      "Event-loop stall threshold: any asyncio callback running longer "
      "than this is recorded as a stall in the sanitizer report.",
      "sanitizer")
_knob("KT_SAN_MAX_EDGES", "int", 20000,
      "Cap on distinct lock-order edges the runtime records (runaway "
      "guard; far above any real lock population).", "sanitizer")
_knob("KT_SAN_LEAKS", "bool", True,
      "Thread-leak guard in the test suite: assert no non-daemon "
      "threads survive a test module (0 = off).", "sanitizer")

# --- distributed ------------------------------------------------------------
_knob("KT_POD_IPS", "str", None,
      "Comma-separated pod IPs for the gang (rendezvous).", "distributed")
_knob("KT_POD_IPS_FILE", "str", None,
      "File containing one pod IP per line (preferred over "
      "KT_POD_IPS when both are set).", "distributed")

# --- controller -------------------------------------------------------------
_knob("KT_CONTROLLER_PORT", "int", 32320,
      "Controller listen port.", "controller")
_knob("KT_CONTROLLER_DB", "str", "~/.ktpu/controller.db",
      "SQLite path backing the controller registry.", "controller")
_knob("KT_REAPER_INTERVAL", "float", 15.0,
      "Seconds between controller TTL-reaper sweeps.", "controller")
_knob("KT_AUTH_VALIDATE_URL", "str", None,
      "External token-validation endpoint for controller auth.", "controller")
_knob("KT_AUTH_CACHE_TTL", "float", 60.0,
      "Seconds a validated token is cached by the controller.", "controller")
_knob("KT_AUTO_RESTART", "bool", True,
      "Gang-restart dead/preempted services automatically.", "controller")

# --- observability ----------------------------------------------------------
_knob("KT_OBS_DIR", "str", None,
      "Directory for controller log/metric persistence "
      "(defaults next to the --db path).", "observability")
_knob("KT_LOG_RETAIN_MB", "float", 256.0,
      "Log-sink size cap before old segments are dropped.", "observability")
_knob("KT_LOG_RETAIN_HOURS", "float", 72.0,
      "Log-sink age cap in hours.", "observability")
_knob("KT_LOG_MAX_PENDING", "int", 512,
      "Max queued log batches before the sink sheds load.", "observability")
_knob("KT_LOG_SINK_URL", "str", None,
      "Log-sink URL override (defaults to the controller).", "observability")
_knob("KT_DISABLE_LOG_STREAMING", "bool", False,
      "Disable pod->sink log streaming entirely.", "observability")
_knob("KT_REQUEST_ID", "str", None,
      "Ambient request id for log lines outside a call context.",
      "observability")
_knob("KT_TRACE_DISABLE", "bool", False,
      "Disable span recording entirely.", "observability")
_knob("KT_TRACE_RING", "int", 4096,
      "Capacity of the in-process span ring buffer.", "observability")
_knob("KT_TRACE_SLOW_MS", "float", None,
      "Auto-push call trees slower than this to the controller.",
      "observability")
_knob("KT_TRACE_PROC", "str", "client",
      "Process label stamped on spans (client/server/worker).",
      "observability")
_knob("KT_PUSH_TIMEOUT", "float", 5.0,
      "Bound on background pushes to the controller (trace slow-push, "
      "heartbeat POST fallback) so a hung controller cannot delay the "
      "SIGTERM drain.", "observability")
_knob("KT_FLIGHT_RING", "int", 2048,
      "Capacity of the engine flight recorder's per-tick ring buffer "
      "(one record per driver tick).", "observability")
_knob("KT_FLIGHT_DIR", "str", None,
      "Directory the flight recorder dumps per-process rings "
      "(flight-<pid>.json) into on preemption/teardown, next to the "
      "sanitizer reports; subprocess pods inherit it. Unset = no dump.",
      "observability")
_knob("KT_FLIGHT_DISABLE", "bool", False,
      "Disable the engine flight recorder entirely.", "observability")

# --- fleet telemetry plane (controller-resident time series) ----------------
_knob("KT_TELEMETRY_EVERY", "int", 1,
      "Piggyback a metric delta frame on every Nth liveness heartbeat "
      "(1 = every beat; 0 disables telemetry emission entirely).",
      "fleet")
_knob("KT_TELEMETRY_FULL_EVERY", "int", 20,
      "Every Nth telemetry frame is a full snapshot instead of a "
      "changed-keys delta, so a restarted controller converges without "
      "waiting for every counter to move.", "fleet")
_knob("KT_FLEET_RAW_S", "float", 120.0,
      "Seconds of raw (per-frame) samples the controller's fleet store "
      "retains per (service, pod, metric) before only downsampled "
      "tiers remain.", "fleet")
_knob("KT_FLEET_MID_S", "float", 900.0,
      "Retention of the 10 s downsampled tier.", "fleet")
_knob("KT_FLEET_RETAIN_S", "float", 3600.0,
      "Retention of the 1 m downsampled tier — the fleet store's total "
      "lookback for range queries and slow SLO windows.", "fleet")
_knob("KT_FLEET_STALE_S", "float", 30.0,
      "A pod whose last telemetry frame is older than this is marked "
      "stale: excluded from fleet gauge rollups and flagged in "
      "/metrics/fleet and the dashboard.", "fleet")

# --- SLO burn-rate engine ---------------------------------------------------
_knob("KT_SLO", "json", None,
      "Declarative SLO objectives as a JSON list, e.g. "
      '[{"service": "svc", "name": "ttft", "kind": "latency", '
      '"metric": "engine_ttft_seconds", "threshold_ms": 500, '
      '"objective": 0.99}]; evaluated by the controller\'s burn-rate '
      "loop (see docs/observability.md).", "slo")
_knob("KT_SLO_FAST_S", "float", 300.0,
      "Fast burn-rate window (Google-SRE multi-window: the trigger).",
      "slo")
_knob("KT_SLO_SLOW_S", "float", 3600.0,
      "Slow burn-rate window (the confirmation; clipped to available "
      "history on a young controller).", "slo")
_knob("KT_SLO_BURN", "float", 14.4,
      "Default burn-rate threshold: breach when BOTH windows exceed it "
      "(14.4 = a 30-day budget gone in 2 days); objectives may "
      "override per-entry with 'burn_threshold'.", "slo")

# --- data store -------------------------------------------------------------
_knob("KT_STORE_PORT", "int", 32310,
      "Store server listen port.", "data-store")
_knob("KT_STORE_ROOT", "str", "~/.ktpu/store_server",
      "Filesystem root of the store server.", "data-store")
_knob("KT_LOCAL_STORE", "str", "~/.ktpu/store",
      "Root of the local (no-server) store backend.", "data-store")
_knob("KT_STREAM_CHUNK_BYTES", "int", 4 << 20,
      "Chunk size for streaming puts/gets (min 64 KiB).", "data-store")
_knob("KT_WIRE_CODEC", "str", "raw",
      "Default wire codec for put_arrays: raw, zlib, zstd, or int8.",
      "data-store")
_knob("KT_WIRE_DELTA", "bool", False,
      "Publish byte-level delta patches when a base exists.", "data-store")
_knob("KT_RESTORE_CACHE", "str", "~/.ktpu/restore_cache",
      "Directory full fetches are teed into as delta bases.", "data-store")
_knob("KT_PEER_CACHE", "str", "~/.ktpu/peer_cache",
      "Directory of the broadcast peer cache.", "data-store")

# --- collectives ------------------------------------------------------------
_knob("KT_COLL_DCN_CODEC", "str", "f32",
      "Cross-slice (dcn) gradient allreduce codec: f32 keeps XLA's "
      "implicit full-precision allreduce; int8 routes the dcn hop "
      "through the block-quantized ring (parallel/collectives.py).",
      "collectives")
_knob("KT_COLL_BLOCK", "int", 256,
      "Elements per float32 scale in the int8 dcn ring (wire overhead "
      "is 4/block bytes per element).", "collectives")

# --- resilience -------------------------------------------------------------
_knob("KT_HEARTBEAT_S", "float", 5.0,
      "Pod liveness heartbeat interval (min 0.01).", "resilience")
_knob("KT_DEAD_AFTER_MISSES", "int", 2,
      "Missed beats before a suspect pod is declared dead.", "resilience")
_knob("KT_TERM_GRACE", "float", 2.0,
      "Total SIGTERM grace budget in seconds.", "resilience")
_knob("KT_DRAIN_TIMEOUT", "float", None,
      "In-flight drain budget; defaults to 40% of KT_TERM_GRACE.",
      "resilience")
_knob("KT_MAX_RESTARTS", "int", 3,
      "Restart budget per service before giving up.", "resilience")
_knob("KT_RESTART_BACKOFF_S", "float", 1.0,
      "Base of the exponential restart backoff.", "resilience")
_knob("KT_RESTART_RESET_S", "float", 300.0,
      "Healthy seconds after which the restart budget resets.", "resilience")
_knob("KT_CHAOS", "str", "",
      "Chaos-injection spec, e.g. 'seed=7,kill-worker=0.1'; kinds: "
      "kill-worker, drop-connection, inject-latency, corrupt-heartbeat, "
      "partition, slow-pod, controller-kill, ws-flap, handoff-drop, "
      "scale-storm, pod-lag.", "resilience")
_knob("KT_REJOIN_GRACE_S", "float", None,
      "Rejoin quarantine after a controller restart that restored "
      "durable state: for this many seconds the resilience sweep "
      "observes but never declares dead and never gang-restarts "
      "(default 2.5 heartbeat intervals; 0 disables).", "resilience")
_knob("KT_WS_RECONNECT_MAX_S", "float", 30.0,
      "Cap of the pod's controller-WebSocket reconnect backoff "
      "(full-jitter exponential from 1 s).", "resilience")

# --- fleet autoscaler (controller-side scale loop, provisioning/scaler.py) --
_knob("KT_SCALE_ENABLE", "bool", False,
      "Run the controller-side fleet scaler: per service (and disagg "
      "tier) compute desired replicas from fleet-rolled queue depth, "
      "row occupancy, KV pressure, and SLO burn, and actuate through "
      "the provisioning backend. Off = AutoscalingConfig stays "
      "annotation-only (the pre-ISSUE-20 behavior).", "scaler")
_knob("KT_SCALE_TARGET_OCCUPANCY", "float", 0.75,
      "Row-occupancy setpoint the scaler sizes the fleet for: desired "
      "= ceil(demand rows / (rows per pod x this)). Lower = more "
      "headroom per replica.", "scaler")
_knob("KT_SCALE_HYSTERESIS", "float", 0.1,
      "Deadband around the occupancy setpoint: the scaler only acts "
      "when measured occupancy leaves [target*(1-h), target*(1+h)], so "
      "load noise near the setpoint never flaps the fleet.", "scaler")
_knob("KT_SCALE_COOLDOWN_S", "float", 60.0,
      "Seconds after any actuated scale decision during which further "
      "scale-DOWNs (and direction reversals) for that service are "
      "suppressed. Persisted durably: a restarted controller keeps "
      "honoring an in-flight cooldown.", "scaler")
_knob("KT_SCALE_COLD_START_BUDGET_S", "float", 30.0,
      "Per-service cold-start-to-first-token budget: after a scale-up, "
      "further scale-ups are suppressed until the new replicas report "
      "in or this budget elapses (prevents over-provisioning while "
      "pods are still provisioning+restoring); also the Retry-After a "
      "scale-from-zero parked route quotes.", "scaler")
_knob("KT_SCALE_EVAL_WINDOW_S", "float", 30.0,
      "Fleet-rollup window the scaler reads its signals (queue depth, "
      "occupancy, KV pressure, shed rate) over.", "scaler")

# --- provisioning -----------------------------------------------------------
_knob("KT_LOCAL_STATE", "str", "~/.ktpu/local",
      "State root of the local (subprocess) backend.", "provisioning")
_knob("KT_READY_POLL", "float", 2.0,
      "Seconds between pod-readiness polls in the K8s backend.",
      "provisioning")
_knob("KT_IMAGE_REGISTRY", "str", "ghcr.io/kubetorch-tpu",
      "Container registry for built images.", "provisioning")
_knob("KT_IMAGE_TAG", "str", "latest",
      "Default image tag.", "provisioning")

# --- kernels ----------------------------------------------------------------
_knob("KT_QMM_DECODE", "bool", False,
      "Enable the fused quantized-matmul decode path.", "kernels")


def _raw(name: str) -> Optional[str]:
    """Registered-knob env read; unset and empty both mean 'default'."""
    knob = KNOBS.get(name)
    if knob is None:
        raise ConfigError(
            f"{name} is not a registered KT_* knob; declare it in "
            f"kubetorch_tpu/config.py before reading it")
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return None
    return raw


def env_str(name: str) -> Optional[str]:
    raw = _raw(name)
    return KNOBS[name].default if raw is None else raw


def env_int(name: str) -> Optional[int]:
    raw = _raw(name)
    if raw is None:
        return KNOBS[name].default
    try:
        return int(raw.strip())
    except ValueError:
        raise ConfigError(
            f"{name}={raw!r} is not a valid integer "
            f"(default: {KNOBS[name].default!r})") from None


def env_float(name: str) -> Optional[float]:
    raw = _raw(name)
    if raw is None:
        return KNOBS[name].default
    try:
        return float(raw.strip())
    except ValueError:
        raise ConfigError(
            f"{name}={raw!r} is not a valid number "
            f"(default: {KNOBS[name].default!r})") from None


_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def env_bool(name: str) -> Optional[bool]:
    raw = _raw(name)
    if raw is None:
        return KNOBS[name].default
    low = raw.strip().lower()
    if low in _TRUTHY:
        return True
    if low in _FALSY:
        return False
    raise ConfigError(
        f"{name}={raw!r} is not a valid boolean "
        f"(use one of {_TRUTHY + _FALSY})")


def env_json(name: str) -> Any:
    raw = _raw(name)
    if raw is None:
        return KNOBS[name].default
    try:
        return json.loads(raw)
    except json.JSONDecodeError as exc:
        raise ConfigError(f"{name} is not valid JSON: {exc}") from None


def env_path(name: str) -> Optional[Path]:
    """``env_str`` + ``Path(...).expanduser()`` (None stays None)."""
    value = env_str(name)
    return None if value is None else Path(value).expanduser()


def env_set(name: str) -> bool:
    """True when the (registered) variable is set to a non-empty value."""
    return _raw(name) is not None


_ACCESSORS = {"str": env_str, "int": env_int, "float": env_float,
              "bool": env_bool, "json": env_json}


def env_value(name: str) -> Any:
    """Read a knob with the accessor matching its declared type."""
    return _ACCESSORS[KNOBS[name].type](name)


def compile_cache_dir() -> str:
    """Where this process keeps JAX's persistent compilation cache.

    ``$JAX_COMPILATION_CACHE_DIR`` when the environment sets it — nothing
    in code overrides that — otherwise ``<checkout>/.jax_cache``. Never a
    temp name, pid or time: the directory is part of the cache's key, so
    one that moves never hits. Every process that compiles exports it
    BEFORE importing jax::

        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              compile_cache_dir())
    """
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(Path(__file__).resolve().parents[1] / ".jax_cache"))


def iter_knobs() -> Iterator[Knob]:
    """All declared knobs, sorted by (section, name) — docgen order."""
    return iter(sorted(KNOBS.values(), key=lambda k: (k.section, k.name)))
