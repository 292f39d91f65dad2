"""Metric registry: every metric family the project exports, declared once.

PRs 1-11 each added a metric family to ``observability/prometheus.py``
and a hand-maintained table to ``docs/observability.md``; the two have
drifted (names renamed in code but not in the doc, new counters never
documented). This registry is the single source of truth — name, type,
help text, group — and three consumers read it:

- ``prometheus.render`` emits ``# HELP`` exposition lines from it;
- ``ktpu metrics --gen-docs`` regenerates the metric tables in
  ``docs/observability.md`` between ``<!-- metrics:<group> -->`` markers
  (prose around the markers is hand-written and untouched);
- ``tests/test_fleetstore.py`` has a drift test mirroring the
  configuration.md one: a registry edit without regenerating fails CI.

Names are registered WITHOUT the ``kubetorch_`` exposition prefix
(``render`` adds it) and histogram families under their BASE name
(``engine_ttft_seconds``, not ``..._bucket``).
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterator, List, Optional

GENERATED_MARKER_FMT = "<!-- metrics:{group} -->"
GENERATED_END_FMT = "<!-- /metrics:{group} -->"


@dataclass(frozen=True)
class Metric:
    name: str          # family name without the kubetorch_ prefix
    type: str          # "counter" | "gauge" | "histogram"
    help: str          # one-line HELP text (exposition + doc table)
    group: str         # doc-table group key


METRICS: Dict[str, Metric] = {}


def _m(name: str, type_: str, help_: str, group: str) -> None:
    METRICS[name] = Metric(name=name, type=type_, help=help_, group=group)


# --- data-plane restore (PR 1) ----------------------------------------------
_m("data_store_restore_bytes_streamed_total", "counter",
   "Bytes fetched across all weight-sync restores.", "restore")
_m("data_store_restore_leaves_placed_total", "counter",
   "Leaves device_put via the placement pipeline.", "restore")
_m("data_store_restore_count_total", "counter",
   "Restores completed.", "restore")
_m("data_store_restore_last_wall_seconds", "gauge",
   "Last restore wall clock.", "restore")
_m("data_store_restore_last_fetch_seconds", "gauge",
   "Last restore time blocked on the wire.", "restore")
_m("data_store_restore_last_place_seconds", "gauge",
   "Last restore host-to-device transfer time.", "restore")
_m("data_store_restore_last_overlap_ratio", "gauge",
   "Fraction of placement hidden under the fetch (1.0 = fully "
   "pipelined).", "restore")
_m("data_store_restore_last_streaming", "gauge",
   "1 if the last restore streamed, 0 if it took the blocking "
   "fallback.", "restore")

# --- wire codec / delta publish (PR 3) --------------------------------------
_m("data_store_wire_tx_bytes_total", "counter",
   "Bytes actually published (encoded + delta).", "wire")
_m("data_store_wire_tx_raw_bytes_total", "counter",
   "Bytes a raw full publish would have shipped — the gap is wire "
   "saved.", "wire")
_m("data_store_wire_rx_bytes_total", "counter",
   "Bytes actually fetched.", "wire")
_m("data_store_wire_rx_raw_bytes_total", "counter",
   "Decoded size of fetched blobs.", "wire")
_m("data_store_wire_codec_encode_seconds_total", "counter",
   "Publish-side codec CPU time.", "wire")
_m("data_store_wire_codec_decode_seconds_total", "counter",
   "Fetch-side codec CPU time (stream decode).", "wire")
_m("data_store_wire_dequant_seconds_total", "counter",
   "On-device int8 dequant time in the placement pipeline.", "wire")
_m("data_store_wire_delta_publishes_total", "counter",
   "Publishes that shipped a patch instead of the full blob.", "wire")
_m("data_store_wire_delta_publish_fallbacks_total", "counter",
   "Patches refused (base drift) leading to a full publish.", "wire")
_m("data_store_wire_delta_leaves_skipped_total", "counter",
   "Unchanged leaves never re-sent.", "wire")
_m("data_store_wire_delta_fetch_hits_total", "counter",
   "Fetches satisfied by patch + local splice.", "wire")
_m("data_store_wire_delta_fetch_misses_total", "counter",
   "Delta-enabled fetches that fell back to a full fetch.", "wire")

# --- serving call path (PR 2) -----------------------------------------------
_m("serving_call_client_ser_seconds", "histogram",
   "Client-side serialize time per call.", "serving")
_m("serving_call_wire_seconds", "histogram",
   "Wall minus in-server time (transport + client loop).", "serving")
_m("serving_call_server_queue_seconds", "histogram",
   "Receipt to dispatch (FIFO wait behind earlier channel calls).",
   "serving")
_m("serving_call_worker_dispatch_seconds", "histogram",
   "MP-queue transit + worker loop scheduling.", "serving")
_m("serving_call_device_seconds", "histogram",
   "User-callable wall time in the worker (device time for engines).",
   "serving")
_m("serving_channel_connects_total", "counter",
   "Channel connections accepted/opened.", "serving")
_m("serving_channel_reconnects_total", "counter",
   "Client re-dials after a dropped channel.", "serving")
_m("serving_channel_calls_total", "counter",
   "Calls executed over channels.", "serving")
_m("serving_channel_errors_total", "counter",
   "Channel calls that ended in an error frame (garbled envelopes "
   "included — a misbehaving client must be visible).", "serving")
_m("serving_channel_inflight", "gauge",
   "Channel calls currently in flight on this pod.", "serving")
_m("serving_worker_calls_total", "counter",
   "Calls executed, summed across worker processes.", "serving")
_m("serving_worker_exec_seconds_total", "counter",
   "Total user-callable wall time across workers.", "serving")
_m("serving_worker_dispatch_seconds_total", "counter",
   "Total dispatch transit across workers.", "serving")
_m("controller_push_errors_total", "counter",
   "Pod-to-controller metrics pushes that failed.", "serving")
_m("heartbeat_send_errors_total", "counter",
   "Heartbeat POSTs that failed (the next beat retries).", "serving")

# --- call reliability (PR 8) ------------------------------------------------
_m("replay_hits_total", "counter",
   "Replayed calls answered entirely from the retention ring "
   "(already executed).", "reliability")
_m("replay_attaches_total", "counter",
   "Reconnects re-attached to a still-running execution.", "reliability")
_m("replay_fresh_total", "counter",
   "Replayed calls whose original submission never arrived — executed "
   "fresh (still exactly once).", "reliability")
_m("replay_expired_total", "counter",
   "Replays refused because the retained result was evicted "
   "(KT_RESULT_RETAIN).", "reliability")
_m("replay_frames_resent_total", "counter",
   "Stream frames re-delivered from the resume cursor.", "reliability")
_m("replay_requeues_total", "counter",
   "Queued-but-never-written calls re-sent verbatim after a drop "
   "(client side).", "reliability")
_m("admission_shed_total", "counter",
   "Calls shed with 429 + computed Retry-After.", "reliability")
_m("admission_deadline_rejected_total", "counter",
   "Expired calls rejected at a queue head instead of executed.",
   "reliability")
_m("admission_last_retry_after_seconds", "gauge",
   "Most recent computed Retry-After.", "reliability")
_m("admission_queue_depth", "gauge",
   "Queued+executing calls at the last admission decision.", "reliability")

# --- serving engine + paged KV (PRs 9-10) -----------------------------------
_m("engine_generations_total", "counter",
   "Generation programs executed (replays answered from retention "
   "don't count).", "engine")
_m("engine_steps_total", "counter",
   "Decode chunks dispatched by the engine loop.", "engine")
_m("engine_tokens_total", "counter",
   "Tokens emitted across all rows.", "engine")
_m("engine_admitted_rows_total", "counter",
   "Rows admitted into the live batch (per-row, never batch swaps).",
   "engine")
_m("engine_first_tokens_at_admit_total", "counter",
   "Rows whose first token left as a frame of its own when their "
   "admission ended, behind the dispatch of the decode chunk that "
   "follows it (over engine_admitted_rows_total: 1 where every "
   "admission draws its row's token).", "engine")
_m("engine_prefill_chunks_total", "counter",
   "Chunked-prefill dispatches interleaved between decode chunks.",
   "engine")
_m("engine_evictions_total", "counter",
   "Rows evicted (deadline / abandonment) before finishing.", "engine")
_m("engine_sheds_total", "counter",
   "Generation programs shed typed (ServerOverloaded + Retry-After).",
   "engine")
_m("engine_tick_errors_total", "counter",
   "Engine-loop ticks that raised (streams failed typed, loop "
   "survived).", "engine")
_m("engine_device_seconds_total", "counter",
   "Host wall from a decode chunk's dispatch to the end of its "
   "blocking read (tick phases decode_dispatch .. decode_sync, the "
   "first tokens' read and routing between them), summed; NOT device "
   "time: the device also works off what admission queued.", "engine")
_m("engine_queue_depth", "gauge",
   "Programs queued ahead of admission.", "engine")
_m("engine_active_rows", "gauge", "Rows decoding.", "engine")
_m("engine_free_rows", "gauge", "Rows free for admission.", "engine")
_m("engine_prefilling_rows", "gauge",
   "Rows mid-chunked-prefill.", "engine")
_m("engine_ttft_seconds", "histogram",
   "Time from generate()'s entry (before the scheduler lock) to the "
   "row's first frame; = lock wait + queue wait + admit-to-first. "
   "Buckets carry trace exemplars for the slowest calls.", "engine")
_m("engine_lock_wait_seconds", "histogram",
   "generate()'s entry to the request queued in the generator: the "
   "wait for the scheduler lock the driver holds through a tick (and "
   "a session restore's or handoff import's store fetch).", "engine")
_m("engine_queue_wait_seconds", "histogram",
   "Queued in the generator until the tick that admits the row "
   "starts its admission (0 for restored / imported rows).", "engine")
_m("engine_admit_to_first_seconds", "histogram",
   "Start of the row's admission to its first frame: the prefill, the "
   "read of the token it drew (behind the next decode chunk's "
   "dispatch) and routing; with the whole decode chunk too for a row "
   "that carries no such token (a restored or imported one).", "engine")
_m("kv_blocks_used", "gauge",
   "KV blocks held by row reservations + cached prefixes.", "engine")
_m("kv_blocks_free", "gauge",
   "Headroom under KT_KV_HBM_BUDGET (only published when a budget is "
   "set).", "engine")
_m("prefix_hits_total", "counter",
   "Prompts whose content-hashed prefix reused a registered device "
   "block (prefilled the suffix only).", "engine")
_m("prefix_misses_total", "counter",
   "Prefixes prefilled + registered for the first time.", "engine")
_m("prefix_evictions_total", "counter",
   "Cold (refcount-0) prefixes LRU-evicted under the HBM budget.",
   "engine")
_m("kv_offloads_total", "counter",
   "Session rows parked to the store (explicit park + deadline parks).",
   "engine")
_m("kv_restores_total", "counter",
   "Parked sessions restored into a free row (no re-prefill).", "engine")
_m("kv_offload_bytes_total", "counter",
   "Wire bytes published by session parks (delta manifests make "
   "re-parks cheap).", "engine")
_m("kv_restore_bytes_total", "counter",
   "Bytes restored through the streaming path.", "engine")
_m("engine_spec_rounds_total", "counter",
   "Speculative verify rounds dispatched (per active row; each round "
   "replaces one plain decode step).", "engine")
_m("engine_spec_emitted_total", "counter",
   "Tokens landed by verify rounds (carried tokens + accepted "
   "drafts); emitted/rounds is tokens-per-pass.", "engine")
_m("engine_spec_drafted_total", "counter",
   "Draft positions offered to verification (per-row lookahead minus "
   "the carried token, summed over rounds).", "engine")
_m("engine_spec_verify_waste_total", "counter",
   "Draft positions verified but rejected — the FLOPs the per-row "
   "adaptive lookahead exists to stop spending.", "engine")
_m("engine_spec_accept_rate", "gauge",
   "Cumulative draft acceptance (accepted / drafted) on this engine.",
   "engine")
_m("engine_spec_k_cap", "gauge",
   "Effective per-row lookahead ceiling: spec_k in the latency "
   "regime, 1 while the occupancy throttle "
   "(KT_SPEC_OCCUPANCY_THROTTLE) holds the batch to plain decode.",
   "engine")
_m("engine_spec_k", "histogram",
   "Per-row adaptive lookahead distribution, sampled once per driver "
   "tick per live row (buckets at the k values themselves).", "engine")
_m("handoff_exports_total", "counter",
   "Prefilled rows exported to the decode tier (disaggregated "
   "prefill/decode handoff; published in the background so the wire "
   "time overlaps the next program's prefill).", "engine")
_m("handoff_imports_total", "counter",
   "Exported rows imported into a free row on this (decode-tier) "
   "engine and streamed without re-prefill.", "engine")
_m("handoff_bytes_total", "counter",
   "Wire bytes published by handoff exports (int8 grids ship (q, "
   "scale) raw; bf16 grids take the int8 wire codec).", "engine")
_m("handoff_seconds_total", "counter",
   "Summed handoff export wall time (device slice + publish) — "
   "handoff latency over imports is the per-row handoff cost.",
   "engine")
_m("engine_phase", "gauge",
   "Serving tier this engine runs as: 0 = prefill, 1 = decode, 2 = "
   "mixed (KT_DISAGG_PHASE) — the controller's phase-routing key.",
   "engine")
_m("engine_row_eta_seconds", "gauge",
   "Earliest expected row-free time (0 with a free row; else queue "
   "depth x the row-free EMA, repriced by live speculation state) — "
   "the decode-tier routing currency.", "engine")
_m("engine_mfu", "gauge",
   "Model FLOPs utilization over the last gauge window: compiled-"
   "executable FLOPs (cost_analysis) over the host's dispatch wall "
   "(engine_device_seconds_total + prefill dispatch, not device "
   "time) x peak FLOP/s. Only published when the chip's peaks are "
   "known.", "engine")
_m("engine_mbu", "gauge",
   "HBM-bandwidth utilization over the last gauge window: executable "
   "bytes-accessed over the host's dispatch wall (as engine_mfu) x "
   "peak HBM bytes/s. Only published when the chip's peaks are "
   "known.", "engine")
_m("hbm_used_bytes", "gauge",
   "Accelerator memory in use, summed over this engine's local "
   "devices (absent on CPU-only pods — absent, not zero).", "engine")
_m("hbm_limit_bytes", "gauge",
   "Accelerator memory capacity, summed over local devices (absent "
   "on CPU-only pods).", "engine")

# --- multi-tenant LoRA adapter pool (this PR) -------------------------------
_m("engine_adapter_loads_total", "counter",
   "Named adapters installed into a device slot (background fetch + "
   "one dynamic-slice write at the driver-tick boundary).", "adapter")
_m("engine_adapter_load_seconds_total", "counter",
   "Summed adapter load wall time (fetch + device apply) — feeds the "
   "Retry-After EMA residency-miss sheds quote.", "adapter")
_m("engine_adapter_evictions_total", "counter",
   "Cold (refcount-0) adapters LRU-evicted from their slot to make "
   "room; the engine drops that adapter's prefix-cache entries with "
   "it.", "adapter")
_m("engine_adapter_resident", "gauge",
   "Named adapters currently resident across the KT_LORA_SLOTS device "
   "slots.", "adapter")

# --- quantized collectives + delta broadcast (this PR) ----------------------
_m("coll_dcn_bytes_total", "counter",
   "Bytes crossing the dcn links for quantized gradient allreduces "
   "(int8 payloads + per-block f32 scales, both ring phases).",
   "collectives")
_m("coll_dcn_raw_bytes_total", "counter",
   "Bytes the same ring schedule would have moved in f32 — the gap "
   "over coll_dcn_bytes_total is DCN wire saved.", "collectives")
_m("coll_dcn_quant_seconds_total", "counter",
   "Device time spent block-quantizing ring payloads (benchmarked "
   "kernel time; the compression's compute cost).", "collectives")
_m("coll_dcn_dequant_seconds_total", "counter",
   "Device time spent dequantizing received ring payloads into the "
   "f32 accumulator.", "collectives")
_m("bcast_delta_leaves_skipped_total", "counter",
   "Unchanged leaves the delta-aware broadcast spliced from the local "
   "peer-cache base instead of fetching.", "collectives")
_m("bcast_delta_bytes_saved_total", "counter",
   "Bytes the delta-aware broadcast avoided moving (full blob size "
   "minus patch size, per spliced fetch).", "collectives")

# --- resilience (PR 5) ------------------------------------------------------
_m("resilience_heartbeats_total", "counter",
   "Liveness beats accepted (WS + HTTP).", "resilience")
_m("resilience_heartbeats_corrupt_total", "counter",
   "Beats rejected for missing identity (chaos or a real serialization "
   "bug).", "resilience")
_m("resilience_suspect_transitions_total", "counter",
   "Pods aged alive to suspect (one missed beat).", "resilience")
_m("resilience_dead_transitions_total", "counter",
   "Pods declared dead (KT_DEAD_AFTER_MISSES missed).", "resilience")
_m("resilience_preemptions_total", "counter",
   "Explicit SIGTERM-drain reports.", "resilience")
_m("resilience_emergency_checkpoints_total", "counter",
   "Emergency-checkpoint callbacks that completed.", "resilience")
_m("resilience_gang_restarts_total", "counter",
   "Gang-atomic restarts that provisioned successfully.", "resilience")
_m("resilience_gang_restart_failures_total", "counter",
   "Restart attempts that failed (crash-looping gang = a dashboard "
   "line).", "resilience")
_m("resilience_last_detect_seconds", "gauge",
   "Last heartbeat to dead verdict, most recent detection.", "resilience")
_m("resilience_last_restart_seconds", "gauge",
   "Wall time of the most recent successful gang restart.", "resilience")
_m("ws_reconnects_total", "counter",
   "Pod controller-WebSocket re-dials after a drop (ws-flap chaos, "
   "controller restarts; full-jitter backoff capped at "
   "KT_WS_RECONNECT_MAX_S).", "resilience")
_m("controller_rejoins_total", "counter",
   "Controller starts that restored durable crash-safety state "
   "(persisted in the controller DB — survives the restarts it "
   "counts).", "resilience")
_m("controller_rejoin_grace_remaining_s", "gauge",
   "Seconds left in the rejoin quarantine (sweep observes, never "
   "declares dead or restarts); 0 outside the window.", "resilience")

# --- tracing (PR 4) ---------------------------------------------------------
_m("trace_spans_total", "counter",
   "Spans recorded, summed across pod + worker processes.", "trace")
_m("trace_spans_dropped_total", "counter",
   "Spans evicted from a full ring.", "trace")
_m("trace_slow_pushes_total", "counter",
   "Slow-call trees auto-pushed to the controller.", "trace")
_m("trace_ring_spans", "gauge",
   "Spans currently buffered in the reporting process.", "trace")

# --- concurrency sanitizer (PR 11) ------------------------------------------
_m("san_locks_tracked_total", "counter",
   "Lock classes created by repo code and instrumented.", "san")
_m("san_edges_total", "counter",
   "Distinct lock-order edges observed (A held while B acquired).", "san")
_m("san_cycles_total", "counter",
   "Lock-order cycles found by a session/CLI check.", "san")
_m("san_stalls_total", "counter",
   "Event-loop callbacks that ran longer than KT_SAN_STALL_MS.", "san")
_m("san_thread_leaks_total", "counter",
   "Non-daemon threads caught by the test-suite leak guard.", "san")

# --- fleet telemetry plane (this PR): pod side ------------------------------
_m("telemetry_frames_sent_total", "counter",
   "Metric delta frames piggybacked on heartbeats (WS) or posted "
   "(/telemetry fallback).", "telemetry")
_m("telemetry_full_frames_total", "counter",
   "Frames that carried a full snapshot instead of a delta "
   "(first frame, reconnect, or KT_TELEMETRY_FULL_EVERY cadence).",
   "telemetry")
_m("telemetry_send_errors_total", "counter",
   "Telemetry POST fallbacks that failed (frames stay in the bounded "
   "backlog and retry next beat).", "telemetry")
_m("telemetry_frame_keys_last", "gauge",
   "Metric keys carried by the most recent frame (delta size).",
   "telemetry")
_m("telemetry_backlog_dropped_total", "counter",
   "Outage-backlog delta frames superseded by a full snapshot at POST "
   "flush when the controller asks for resync (stale deltas against a "
   "restarted controller's empty store would mis-splice reset "
   "offsets), plus frames shed past the outage cap.", "telemetry")

# --- fleet telemetry plane: controller side ---------------------------------
_m("fleet_frames_total", "counter",
   "Telemetry frames ingested (WS heartbeat piggyback + POST "
   "/telemetry).", "fleet")
_m("fleet_samples_total", "counter",
   "Individual (service, pod, metric) samples ingested.", "fleet")
_m("fleet_resets_total", "counter",
   "Counter resets detected (a restarted pod's counters stepped "
   "down; rollups splice, never go negative).", "fleet")
_m("fleet_pods", "gauge",
   "Pods with telemetry in the store, per service.", "fleet")
_m("fleet_stale_pods", "gauge",
   "Pods whose last frame is older than KT_FLEET_STALE_S, per "
   "service (excluded from gauge rollups).", "fleet")

# --- SLO burn-rate engine (this PR) -----------------------------------------
_m("slo_burn_rate", "gauge",
   "Fast-window (KT_SLO_FAST_S) error-budget burn rate per objective; "
   "1.0 consumes exactly the budget over a full period.", "slo")
_m("slo_burn_rate_slow", "gauge",
   "Slow-window (KT_SLO_SLOW_S) burn rate — the confirmation window "
   "of the multi-window policy.", "slo")
_m("slo_error_budget_remaining", "gauge",
   "Fraction of the error budget left over the slow window "
   "(clamped to [0, 1]).", "slo")
_m("slo_breached", "gauge",
   "1 while the objective is in breach (both windows over the burn "
   "threshold), else 0.", "slo")
_m("slo_breach_total", "counter",
   "Breach transitions since the controller started.", "slo")
_m("slo_eval_ms", "gauge",
   "Wall milliseconds of the most recent SLO evaluation sweep.", "slo")

# --- fleet scaler (ISSUE 20): the closed autoscaling loop -------------------
_m("scaler_decisions_total", "counter",
   "Actuated scale decisions (every one is also a durable "
   "scale_decisions row).", "scaler")
_m("scaler_scale_ups_total", "counter",
   "Decisions that grew a service's replica count.", "scaler")
_m("scaler_scale_downs_total", "counter",
   "Decisions that shrank a service's replica count.", "scaler")
_m("scaler_flaps_total", "counter",
   "ACTUATED direction reversals inside the cooldown window (only a "
   "manual override can cause one; the flap guard blocks auto "
   "decisions).", "scaler")
_m("scaler_blocked_total", "counter",
   "Decisions withheld by a guard: rejoin quarantine, restart "
   "backoff, scale-down cooldown, flap guard, or an open cold-start "
   "settle window.", "scaler")
_m("scaler_reconciles_total", "counter",
   "Idempotent backend re-issues of a recorded desired count after "
   "the fleet drifted (no new decision row).", "scaler")
_m("scaler_cold_starts_total", "counter",
   "Scale-ups that settled (actual reached target).", "scaler")
_m("scaler_cold_starts_over_budget_total", "counter",
   "Scale-ups that settled past — or never settled inside — "
   "KT_SCALE_COLD_START_BUDGET_S.", "scaler")
_m("scaler_overrides_active", "gauge",
   "Services pinned by a durable manual override "
   "(`ktpu scale <svc> <n>`).", "scaler")
_m("scaler_desired_replicas", "gauge",
   "The scaler's recorded desired replica count, per service.",
   "scaler")
_m("scaler_actual_replicas", "gauge",
   "Observed live replicas (non-stale telemetry pods, or the "
   "backend's count), per service.", "scaler")
_m("scaler_cooldown_remaining_s", "gauge",
   "Seconds left in the per-service scale-down cooldown (0 when "
   "closed).", "scaler")
_m("scaler_cold_start_seconds", "gauge",
   "Wall seconds the most recent scale-up took to settle, per "
   "service.", "scaler")

# --- fleet router (ISSUE 20): controller-side route selection ---------------
_m("router_routes_total", "counter",
   "Routes handed out by POST /route/generate, labeled by mode "
   "(monolithic | disagg | decode-only).", "router")
_m("router_parked_total", "counter",
   "Programs parked behind a scale-from-zero capacity ask (202 + "
   "Retry-After) instead of erroring.", "router")
_m("router_unroutable_total", "counter",
   "Route misses with no live candidate pods (503, or a park on "
   "autoscaled services).", "router")
_m("router_backpressure_skips_total", "counter",
   "Candidate pods deprioritized because their admission gate was "
   "shedding during the rollup window.", "router")


# keep the doc groups in a stable, narrative-matching order
GROUP_ORDER = ("restore", "wire", "collectives", "serving", "reliability",
               "engine", "adapter", "resilience", "san", "trace",
               "telemetry", "fleet", "slo", "scaler", "router")

_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def lookup(name: str, prefix: str = "kubetorch_") -> Optional[Metric]:
    """Registry entry for an exposition family name. Accepts prefixed
    (``kubetorch_engine_tokens_total``) and raw names; histogram
    component families (``_bucket``/``_sum``/``_count``) resolve to
    their base when the base is a registered histogram."""
    if name.startswith(prefix):
        name = name[len(prefix):]
    met = METRICS.get(name)
    if met is not None:
        return met
    for suffix in _HIST_SUFFIXES:
        if name.endswith(suffix):
            base = METRICS.get(name[:-len(suffix)])
            if base is not None and base.type == "histogram":
                return base
    return None


def iter_metrics(group: Optional[str] = None) -> Iterator[Metric]:
    mets = sorted(METRICS.values(), key=lambda m: m.name)
    for met in mets:
        if group is None or met.group == group:
            yield met


# ------------------------------------------------------------------ docgen
def render_group_table(group: str) -> str:
    """One markdown table for a doc group, marker-bracketed."""
    lines = [GENERATED_MARKER_FMT.format(group=group),
             "| metric | type | meaning |",
             "| --- | --- | --- |"]
    for met in iter_metrics(group):
        lines.append(
            f"| `kubetorch_{met.name}` | {met.type} | {met.help} |")
    lines.append(GENERATED_END_FMT.format(group=group))
    return "\n".join(lines)


def splice_metric_tables(text: str) -> str:
    """Replace every ``<!-- metrics:<group> -->`` ... ``<!-- /metrics:
    <group> -->`` region in a document with the freshly rendered table.
    Unknown groups raise (a typo'd marker silently keeping a stale
    table is the drift this exists to kill)."""
    def _sub(match: "re.Match[str]") -> str:
        group = match.group(1)
        if group not in GROUP_ORDER:
            raise ValueError(f"unknown metric group in doc marker: "
                             f"{group!r} (known: {GROUP_ORDER})")
        return render_group_table(group)

    pattern = re.compile(
        r"<!-- metrics:([a-z0-9_-]+) -->.*?<!-- /metrics:\1 -->",
        re.DOTALL)
    return pattern.sub(_sub, text)


def write_metric_docs(path: Optional[Path] = None) -> Path:
    """Regenerate the metric tables inside ``docs/observability.md``
    (``ktpu metrics --gen-docs``). Only marker-bracketed regions change;
    the surrounding prose is the doc author's."""
    if path is None:
        from kubetorch_tpu.analysis.engine import _find_root

        path = _find_root() / "docs" / "observability.md"
    path = Path(path)
    path.write_text(splice_metric_tables(path.read_text()))
    return path


def doc_groups_in(text: str) -> List[str]:
    """Marker groups present in a document (drift-test helper)."""
    return re.findall(r"<!-- metrics:([a-z0-9_-]+) -->", text)
