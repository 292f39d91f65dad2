"""One run of a serving cell, from the client's side. Never imports JAX:
the worker of the pod holds the chip, then the reference's child does.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from benchmark import families, loadgen, manifest, report, stats
from benchmark.report import CellFailure, note, run_child


def live_positions(records, t0, t1, samples=64):
    """Time-average over [t0, t1] of the positions the active rows hold
    (prompt + tokens so far), and of the number of active rows, from the
    client's own record of frames."""
    pos = rows = 0.0
    for t in np.linspace(t0, t1, samples):
        for r in records:
            if r.frames and r.frames[0][0] <= t and (
                    not r.done or t <= r.frames[-1][0]):
                sofar = sum(n for ft, n in r.frames if ft <= t)
                pos += r.prompt_len + sofar
                rows += 1
    return {"positions": pos / samples, "rows": rows / samples}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))
            and isinstance(before.get(k), (int, float))}


def pick_sample(records, k, seed):
    """The longest finished request and ``k - 1`` more drawn from the seed."""
    done = [r for r in records if r.done and len(r.tokens) == r.out_len]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.prompt_len + r.out_len), r.id))
    rest = done[1:]
    rng = np.random.default_rng([seed, 32452843])
    idx = rng.permutation(len(rest))[:max(0, k - 1)]
    return [done[0]] + [rest[i] for i in sorted(idx)]


def run(cell: dict, bench: dict, seed: int, seconds: float, trace: bool,
        t_start: float, rehearsal: bool = False, control: bool = False,
        server_cls=None) -> dict:
    traffic, config = cell["traffic_json"], cell["config_json"]
    dep = traffic["deployment"]
    d = families.load(config, "serve").dims(config)
    names = report.reported(bench, cell)
    from kubetorch_tpu.config import compile_cache_dir

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    state = tempfile.mkdtemp(prefix="kt-bench-")
    os.environ["KT_LOCAL_STATE"] = os.path.join(state, "local")
    os.environ["KT_BACKEND"] = "local"
    import kubetorch_tpu as kt
    from kubetorch_tpu.serving.engine import program

    if server_cls is None:
        from benchmark.server import BenchServer as server_cls
    plan = loadgen.build(traffic, seconds)
    pod_env = {**dep.get("env", {}),
               "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    if rehearsal:
        pod_env.update(report.rehearsal_env())
    remote = kt.cls(server_cls, init_kwargs={
        "config_file": cell["config_file"], "deployment": dep,
        "seed": seed, "warm": traffic["warm"]}, name="bench-serve")
    ctx = {"seconds": seconds, "deployment": dep, "config": config,
           "dims": d, "cell": cell["name"]}
    try:
        t0 = time.perf_counter()
        try:
            remote.to(kt.Compute(tpus=f"v5e-{cell['chips']}", env=pod_env,
                                 launch_timeout=1000))
        except Exception as exc:  # noqa: BLE001 — no chip, no result
            raise CellFailure(f"launch failed: {type(exc).__name__}: {exc}")
        ctx["launch_ready_s"] = time.perf_counter() - t0
        with remote.channel(depth=dep["channel_depth"]) as chan:
            warm = chan.call(method="setup_report", timeout=60)
            dev = chan.call(method="device_report", timeout=60)
            if not rehearsal and (dev["platform"] != "tpu"
                                  or dev["count"] != cell["chips"]):
                raise CellFailure(
                    f"worker has {dev['count']} {dev['platform']} "
                    f"device(s); the cell asks for {cell['chips']} TPU")
            ctx["peaks"] = manifest.read("peaks.json").get(dev["kind"])
            if ctx["peaks"] is None and not rehearsal:
                raise CellFailure(
                    f"no peaks for device kind {dev['kind']!r}")
            note("warm", {**warm, "launch_ready_s": ctx["launch_ready_s"]})

            def submit(prompt, n):
                return chan.submit(program(prompt, max_new_tokens=n),
                                   method="generate", stream=True,
                                   concurrent=True, timeout=120)

            snaps = {}

            def on_window(_):
                snaps["open"] = chan.call(method="snapshot", timeout=60)
                snaps["t_open"] = time.perf_counter()
                if not trace:
                    return
                spec = traffic["trace"]
                start = min(spec["start_s"], 0.3 * seconds)
                length = min(spec["seconds"], 0.4 * seconds)
                time.sleep(max(0.0, start))
                snaps["trace0"] = chan.call(
                    os.path.join(state, "trace"), method="trace_start",
                    timeout=120)
                snaps["trace_t0"] = time.perf_counter() - snaps["t_open"]
                time.sleep(length)
                snaps["trace_t1"] = time.perf_counter() - snaps["t_open"]
                snaps["trace1"] = chan.call(method="trace_stop", timeout=300)

            side = []

            def opened(t_rel):
                # the snapshot and the trace must not hold the generator
                side.append(threading.Thread(
                    target=on_window, args=(t_rel,), daemon=True))
                side[0].start()

            records, t_close = loadgen.drive(
                plan, submit, seed, d["V"], seconds,
                float(traffic.get("drain_s", 0.0)), on_window=opened)
            for th in side:
                th.join(timeout=600)
            # errors from here on are streams cut by our own teardown
            errored = {r.id for r in records if r.error is not None}
            snaps["close"] = chan.call(method="snapshot", timeout=60)
            dev = chan.call(method="device_report", timeout=60)
            if trace and "trace1" in snaps:
                ctx["trace"] = chan.call(
                    [f"s32[{dep['steps_per_call']},{dep['max_slots']}]"],
                    method="trace_reduce", timeout=600)
    except BaseException:
        try:
            print(remote.logs(tail=40), file=sys.stderr, flush=True)
        except Exception:  # noqa: BLE001 — the run's own error matters
            pass
        raise
    finally:
        remote.teardown()
    setup_s = snaps["t_open"] - t_start
    # ------------------------------------------------------------ metrics
    ctx.update(records=records,
               compile_before=snaps["open"]["compile"],
               stats_delta=delta(snaps["close"]["stats"],
                                 snaps["open"]["stats"]),
               mean_prompt_len=(sum(r.prompt_len ** 2 for r in records)
                                / max(1, sum(r.prompt_len for r in records))))
    if "trace" in ctx:
        ctx["trace_stats_delta"] = delta(snaps["trace1"]["stats"],
                                         snaps["trace0"]["stats"])
        ctx["trace_live"] = live_positions(
            records, snaps["trace_t0"], snaps["trace_t1"])
    missing_ms = 1e3 * (seconds + float(traffic.get("drain_s", 0.0)))
    ttft = stats.ttft_ms(records, seconds, missing_ms)
    gaps = stats.token_gaps(records, seconds)
    tokens_done, reqs_done = stats.completed_tokens(records, seconds)
    values = {"setup_s": setup_s,
              "ttft_p90_ms": ttft["value"],
              "tok_gap_p99_ms": stats.weighted_percentile(gaps, 99.0),
              "serve_tok_s": stats.window_tokens(records, seconds) / seconds}
    in_window = [r for r in records if r.due is not None
                 and 0 <= r.due < seconds]
    failed = [r for r in in_window if r.id in errored
              or (plan["loop"] == "open" and not r.frames)]
    compiles = delta(snaps["close"]["compile"], snaps["open"]["compile"])
    note("window", {
        "requests_due": len(in_window), "failed": len(failed),
        "errors": sorted({r.error for r in records
                          if r.id in errored})[:3],
        "finished": sum(r.done for r in records),
        "completed_in_window": reqs_done, "tokens_completed": tokens_done,
        "tokens_in_window": stats.window_tokens(records, seconds),
        "ttft": ttft, "gap_frames": len(gaps),
        "gap_tokens": sum(n for _, n in gaps),
        "gap_p50_ms": stats.weighted_percentile(gaps, 50.0),
        "gap_candidates_ms": {
            f"p{q}": stats.weighted_percentile(gaps, q)
            for q in (75, 90, 95, 99)},
        "late_p90_ms": manifest.reader("loadgen_late_p90_ms")(ctx),
        "late_max_ms": max((1e3 * (r.sent - r.due) for r in in_window
                            if r.sent is not None), default=None),
        "silence_max": stats.longest_silence(records, seconds),
        "stats_delta": {k: ctx["stats_delta"].get(k) for k in (
            "steps", "tokens", "prefill_chunks", "admitted_rows",
            "prefill_tokens_executed")},
        "backlog_at_close": {k: snaps["close"]["stats"].get(k) for k in (
            "queued", "active_rows", "prefilling_rows")},
        "compiles_in_window": compiles, "t_close": t_close,
        "memory": dev["memory"]})
    # ------------------------------------------------------------ correct
    checks = report.Checks()
    limit = checks.limit
    limit("compiles_in_window",
          compiles["cache_hits"] + compiles["cache_misses"], 0)
    limit("compile_seconds_in_window", compiles["backend_compile_s"], 0.0)
    limit("failed_requests", len(failed), 0)
    wrong = [r.id for r in records if r.done and (
        len(r.tokens) != r.out_len
        or not all(0 <= t < d["V"] for t in r.tokens))]
    limit("streams_of_wrong_length_or_vocabulary", len(wrong), 0)
    sample = pick_sample(records, traffic["correct"]["sample"], seed)
    limit("sampled_finished_requests", len(sample), 1, ok=len(sample) >= 1)
    scored = None
    if sample and not cell.get("skip_reference"):
        job = {"config_file": cell["config_file"], "seed": seed,
               "buckets": traffic["correct"]["reference_buckets"],
               "need_platform": None if rehearsal else "tpu",
               "controls": cell.get("controls", []) if control else [],
               "requests": [{"id": r.id,
                             "prompt": loadgen.prompt_tokens(
                                 seed, r.id, r.prompt_len, d["V"]),
                             "served": r.tokens} for r in sample]}
        scored = run_child("benchmark.reference.score_serve", job,
                           rehearsal, 900, state)
        note("reference", {k: v for k, v in scored.items()
                           if k != "requests"})
        limit("served_token_gap_max_logits", scored["gap_max"],
              cell["correct"]["gap_max_limit"])
        limit("served_token_gap_mean_logits", scored["gap_mean"],
              cell["correct"]["gap_mean_limit"])
    shutil.rmtree(state, ignore_errors=True)
    # --------------------------------------------------------------- line
    peak = max((m.get("peak_bytes_in_use") or 0) for m in dev["memory"])
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    line = {"correct": checks.correct, "attempted": len(in_window),
            "failed": len(failed),
            "metrics": report.metrics_of(names, trace, values, ctx,
                                         rehearsal),
            "device": device}
    if trace:
        report.attach_trace(line, ctx.get("trace"), rehearsal, {
            "live": ctx.get("trace_live"),
            "trace_stats_delta": {k: (ctx.get("trace_stats_delta") or {}
                                      ).get(k) for k in (
                "steps", "tokens", "prefill_chunks", "admitted_rows",
                "prefill_tokens_executed")}})
    if rehearsal:
        line["rehearsal"] = True
    if scored is not None:
        line["reference"] = {k: v for k, v in scored.items()
                             if k != "requests"}
    line["compared"] = checks.compared
    return line
