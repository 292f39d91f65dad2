"""Serving bench: Llama-3-8B int8 through the continuous-batching engine.

VERDICT r3 #1: the 5.7k tok/s headline was the *static* ``Generator`` — a
batch-blocking decoder no serving system would run. This bench runs the
flagship through :class:`~kubetorch_tpu.models.rolling.RollingGenerator`
(the engine under ``RollingService``) and reports:

- ``rolling_tok_s``: steady-state decode throughput at full occupancy —
  chunks timed back-to-back on one executable, directly comparable to the
  static scan number (same B, P, N).
- ``ttft_ms`` / request-latency p50/p99 under a Poisson arrival load at
  ~80% of measured capacity, wall-clock-true on this host.

The steady-state window times decode chunks only (the same discipline the
static bench uses); the Poisson phase additionally reports
``swap_overhead_ms`` — the measured excess of a post-admission chunk over
the steady median.
"""

from __future__ import annotations

import sys
import time
from typing import Optional

from kubetorch_tpu.observability import devstats


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _pct(xs, p):
    xs = sorted(xs)
    if not xs:
        return 0.0
    i = min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))
    return xs[i]


def bench_8b_rolling(B: int = 112, P: int = 128, N: int = 128,
                     steps_per_call: int = 16,
                     poisson_requests: int = 96,
                     static_tok_s: Optional[float] = None,
                     seed: int = 0,
                     kv_dtype: str = "bf16") -> dict:
    """Build the 8B int8 engine and run both phases at the first slot
    count on the ladder that fits. Only an out-of-memory error steps down
    (the rung taken is in the result as ``batch``); any other failure, or
    no rung fitting, raises."""
    import jax
    import numpy as np

    from kubetorch_tpu.models import LlamaConfig, quant
    from kubetorch_tpu.models.rolling import RollingGenerator

    cfg = LlamaConfig.llama3_8b(max_seq_len=1024)
    params = quant.init_quantized(jax.random.key(0), cfg, fuse=True)
    jax.block_until_ready(params)

    rng = np.random.default_rng(seed)
    # (slots, decode length, chunk pair): the 112-slot rung shrinks both
    # the budget and the differencing pair so the cache grid (P+N+2·spc
    # rows) and the 2·spc chunk buffers stay inside HBM beside the 9.1 GB
    # int8 tree; smaller rungs keep the full length for comparability and
    # record it in the result as decode_len.
    rungs = ((112, 96, (8, 16)),
             (96, N, (steps_per_call, 2 * steps_per_call)),
             (64, N, (steps_per_call, 2 * steps_per_call)))
    if kv_dtype == "int8":
        # the quantized grid halves cache residency — the same headroom
        # that moved the static Generator's ceiling 112 → 192
        rungs = ((192, 96, (8, 16)), (160, 96, (8, 16))) + rungs
    ladder = [(b, n, pair) for b, n, pair in rungs if b <= B]
    for b, n, pair in ladder:
        try:
            out = _run_phases(params, cfg, b, P, n, pair,
                              poisson_requests, rng, kv_dtype)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"# 8b rolling B={b} out of memory; stepping down",
                  file=sys.stderr)
            continue
        if static_tok_s:
            out["vs_static"] = round(out["rolling_tok_s"]
                                     / static_tok_s, 4)
        return out
    raise RuntimeError(f"no slot count of {ladder} fits the chip")


def _run_phases(params, cfg, B, P, N, chunk_pair, n_poisson, rng,
                kv_dtype="bf16"):
    import jax
    import numpy as np

    from kubetorch_tpu.models.rolling import RollingGenerator

    # The load phase must outlive its own transient: occupancy on a
    # B-slot engine builds one admission wave at a time, so a request
    # count small relative to B measures ramp-up/drain edges, not steady
    # state (r5: 64 requests on 192 slots never got past ~30% occupancy
    # and the consistency check kept failing on edge effects).
    n_poisson = max(n_poisson, 3 * B)
    steps_per_call, spc2 = chunk_pair
    max_len = P + N + spc2
    eng = RollingGenerator(params, cfg, max_slots=B, max_len=max_len,
                           steps_per_call=steps_per_call, admit_width=16,
                           seed=0, kv_dtype=kv_dtype)

    def prompt():
        return rng.integers(1, cfg.vocab_size, P).tolist()

    def timed_chunks(n_new, spc):
        """Fill every slot, run decode chunks back to back, return the
        per-chunk wall times (first chunk — compile/swap — excluded)."""
        eng.steps_per_call = spc
        for _ in range(B):
            eng.submit(prompt(), max_new_tokens=n_new, temperature=0.8)
        t0 = time.perf_counter()
        while eng._queue:                   # admission prefills
            eng.step()
        admit = time.perf_counter() - t0
        times = []
        while eng.pending:
            t0 = time.perf_counter()
            eng.step()
            times.append(time.perf_counter() - t0)
        return admit, times[1:-1] if len(times) > 2 else times

    # ---- phase 1: steady-state decode, dispatch tax differenced --------
    # One step() is one jit dispatch plus one host sync. Timing the same
    # engine at chunk sizes K and 2K and differencing cancels the fixed
    # per-chunk cost: device-ms/step = (t_2K − t_K) / K.
    admit_s, times_k = timed_chunks(N, steps_per_call)
    _, times_2k = timed_chunks(N, spc2)
    med_k, med_2k = _median(times_k), _median(times_2k)
    diff = (med_2k - med_k) / (spc2 - steps_per_call)
    if diff * steps_per_call < 0.05 * med_k:
        # Differencing drowned in dispatch jitter (med_2k barely above
        # med_k): a clamped value would report absurd tok/s as real.
        raise RuntimeError(
            f"chunk differencing invalid: med_{steps_per_call}="
            f"{med_k * 1e3:.0f}ms med_{spc2}={med_2k * 1e3:.0f}ms "
            f"(samples {len(times_k)}/{len(times_2k)})")
    per_step_device = diff
    dispatch_ms = max(0.0, med_k - steps_per_call * per_step_device)
    rolling_tok_s = B / per_step_device
    eng.steps_per_call = steps_per_call

    # MBU, compiler truth first: the engine's devstats table captured
    # cost_analysis() bytes for exactly the decode executable whose wall
    # phase 1 just differenced. The classic hand-rolled roofline (int8
    # weight stream minus embedding + KV at average fill) is demoted to
    # an explicit proxy fallback for backends whose cost_analysis
    # reports no byte counts, and is labeled as such in the output.
    peaks = eng.devstats_peaks()
    if peaks is None:
        raise RuntimeError("no peaks known for this process's device_kind")
    peak_bw = peaks[1]
    costs = getattr(eng, "_devstats", None)
    entry = (costs.per_key_costs().get(("decode", steps_per_call))
             if costs is not None else None)
    mbu_key = "mbu"
    if entry is not None and entry[1] > 0:
        mbu = devstats.mbu_from_bytes(
            entry[1] / steps_per_call, per_step_device, peak_bw)
    else:
        nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
        emb = params["embedding"].nbytes
        kv = sum(x.nbytes for x in jax.tree.leaves(
            {"k": eng.cache["k"], "v": eng.cache["v"]}))
        avg_fill = (P + N / 2) / max_len
        mbu = devstats.mbu_from_bytes(
            devstats.analytic_decode_bytes(nbytes, emb, kv, avg_fill),
            per_step_device, peak_bw)
        mbu_key = "mbu_proxy"

    out = {
        "batch": B,
        "kv_dtype": kv_dtype,
        "decode_len": N,
        "rolling_tok_s": round(rolling_tok_s, 1),
        "ms_per_step_device": round(per_step_device * 1e3, 2),
        "dispatch_tax_ms_per_chunk": round(dispatch_ms * 1e3, 1),
        "chunk_ms_median": round(med_k * 1e3, 1),
        "rolling_tok_s_tunnel_wall": round(
            B * steps_per_call / med_k, 1),
        "steps_per_call": steps_per_call,
        "admit_s": round(admit_s, 2),
        mbu_key: round(mbu, 4),
    }

    # ---- phase 2: Poisson arrivals → TTFT + request latency ------------
    # λ sized to the decode-only rate ignores that every admission wave
    # pays a prefill dispatch, and the queue melts down. Calibrate λ
    # against ADMISSION-INCLUSIVE capacity measured on this host: a short
    # churn phase (staggered budgets, continuous slot reuse, admission
    # waves interleaved with decode) whose delivered tok/s is what this
    # host can actually absorb.
    lens = rng.integers(N // 4, N + 1, n_poisson)
    cal_n = max(2 * B, 32)
    cal_lens = rng.integers(N // 4, N + 1, cal_n)
    t0 = time.perf_counter()
    cal_done = 0
    next_cal = 0
    while cal_done < cal_n:
        # keep the engine SATURATED: top the queue up to the free-slot
        # count each step (submit() only enqueues — admission happens in
        # step() — so gating on an empty queue would trickle one request
        # per chunk and calibrate against a near-idle engine)
        while (next_cal < cal_n
               and len(eng._queue) < max(1, len(eng._free))):
            eng.submit(prompt(), max_new_tokens=int(cal_lens[next_cal]),
                       temperature=0.8)
            next_cal += 1
        cal_done += sum(d for _, _, d in eng.step())
    churn_tok_s = float(np.sum(cal_lens)) / (time.perf_counter() - t0)
    out["churn_tok_s_host"] = round(churn_tok_s, 1)

    def run_poisson(lam):
        gaps = rng.exponential(1.0 / lam, n_poisson)
        arrive_at = np.cumsum(gaps)
        t_start = time.perf_counter()
        submit_t: dict = {}
        first_tok_t: dict = {}
        done_t: dict = {}
        next_i = 0
        post_admit = []                   # chunk time right after admission
        steady = []                       # chunk time with no admission
        while len(done_t) < n_poisson:
            now = time.perf_counter() - t_start
            while next_i < n_poisson and arrive_at[next_i] <= now:
                rid = eng.submit(prompt(),
                                 max_new_tokens=int(lens[next_i]),
                                 temperature=0.8)
                submit_t[rid] = time.perf_counter()
                next_i += 1
            if not eng.pending:
                if next_i < n_poisson:    # idle gap: sleep to next arrival
                    time.sleep(max(0.0, arrive_at[next_i]
                                   - (time.perf_counter() - t_start)))
                continue
            admitted = bool(eng._queue) and bool(eng._free)
            t0 = time.perf_counter()
            events = eng.step()
            dt = time.perf_counter() - t0
            (post_admit if admitted else steady).append(dt)
            tnow = time.perf_counter()
            for rid, toks, done in events:
                if toks and rid not in first_tok_t:
                    first_tok_t[rid] = tnow
                if done:
                    done_t[rid] = tnow
        ttft = [(first_tok_t[r] - submit_t[r]) * 1e3 for r in first_tok_t]
        lat = [(done_t[r] - submit_t[r]) * 1e3 for r in done_t]
        wall = max(done_t.values()) - t_start
        return ttft, lat, wall, post_admit, steady

    # Two-pass λ calibration: the churn phase measures SATURATED
    # capacity, where big admission waves amortize the per-wave
    # dispatch+swap cost; open-loop arrivals spread admissions out and
    # absorb less. Pass 1 offers 0.8× churn; if the engine can't keep
    # up (delivered < 0.75× offered), the measured delivered rate IS
    # the open-loop capacity — pass 2 re-offers 80% of that, and the
    # consistency flag is judged on the final pass.
    lam = 0.8 * churn_tok_s / float(np.mean(lens))
    total_toks = int(np.sum(lens))
    passes = 0
    while True:
        ttft, lat, wall, post_admit, steady = run_poisson(lam)
        offered = lam * float(np.mean(lens))
        delivered = total_toks / wall
        # one-sided: only UNDER-delivery is queueing collapse (the wall
        # ends at the last completion, so a fast drain of bunched
        # arrivals can legitimately deliver above the offered rate)
        consistent = delivered >= 0.75 * offered
        passes += 1
        if consistent or passes >= 2:
            break
        lam = 0.8 * delivered / float(np.mean(lens))
    out.update({
        "poisson_requests": n_poisson,
        "poisson_offered_tok_s": round(offered, 1),
        "poisson_tok_s": round(delivered, 1),
        "poisson_valid": bool(consistent),
        "poisson_calibration_passes": passes,
        "ttft_ms_p50": round(_pct(ttft, 50), 1),
        "ttft_ms_p99": round(_pct(ttft, 99), 1),
        "latency_ms_p50": round(_pct(lat, 50), 1),
        "latency_ms_p99": round(_pct(lat, 99), 1),
    })
    if not consistent:
        out["poisson_invalid_reason"] = (
            f"delivered {delivered:.0f} tok/s vs offered {offered:.0f} "
            f"(queueing collapse — raw latencies describe the queue)")
    if post_admit and steady:
        # the per-admission excess of a chunk that follows an admission
        # over the steady chunk median
        out["swap_overhead_ms"] = round(
            (_median(post_admit) - _median(steady)) * 1e3, 1)
        out["admit_chunks"] = len(post_admit)
    return out


def bench_rolling_spec(params, cfg, slots: int = 16, k: int = 8,
                       kv_dtype: str = "int8", P: int = 112,
                       N: int = 384, seed: int = 0) -> dict:
    """Speculative continuous batching vs plain rolling at LOW occupancy
    (VERDICT r4 #1 done-bar: 8–16 occupied slots — the latency-sensitive
    regime where decode is weight-bound and accepted drafts are nearly
    free; at 192 slots decode is compute-roofline-bound and plain chunks
    win).

    Traffic: looping continuations (greedy rollouts re-fed as prompts —
    the honest analogue of extractive/code-edit traffic, same
    construction as the static speculative bench). Timing: per-chunk
    device cost differenced over two chunk sizes exactly like phase 1;
    the speculative rate pairs the differenced per-ROUND device cost
    with the acceptance-measured tokens/round, and the acceptance bound
    is reported beside the wall-derived numbers (acceptance is a count and
    the stable quantity of the two).
    """
    import numpy as np

    from kubetorch_tpu.models.generate import Generator
    from kubetorch_tpu.models.rolling import RollingGenerator

    rng = np.random.default_rng(seed)
    seeds_ = rng.integers(1, cfg.vocab_size, (slots, 16)).tolist()
    gen = Generator(params, cfg)
    warm = gen.generate(seeds_, max_new_tokens=P - 16, temperature=0.0)
    prompts = [s + w for s, w in zip(seeds_, warm)]
    del gen

    def drain(spec_k, spc, spc_pair_max):
        # max_len from the LARGER chunk size of the differencing pair:
        # both engines in a pair must share the grid size, or the
        # subtraction attributes the bigger engine's extra KV-read cost
        # to per-step device time (phase 1 differences one engine at
        # fixed max_len for the same reason)
        eng = RollingGenerator(
            params, cfg, max_slots=slots, admit_width=slots,
            max_len=2 * P + N + 2 * spc_pair_max * max(spec_k, 1),
            steps_per_call=spc, kv_dtype=kv_dtype, spec_k=spec_k)
        for p in prompts:
            eng.submit(p, max_new_tokens=N)
        while eng._queue:
            eng.step()
        times = []
        while eng.pending:
            t0 = time.perf_counter()
            eng.step()
            times.append(time.perf_counter() - t0)
        stats = dict(eng.spec_stats) if spec_k else {}
        return (_median(times[1:-1] if len(times) > 2 else times), stats)

    # plain rolling: device ms/step via (4K − K)/3K differencing. The
    # WIDE pair matters at this low-occupancy scale, where a step is a
    # few ms of device time: 8-vs-32 puts enough of it between the
    # medians to stand clear of per-chunk jitter.
    med_k, _ = drain(0, 8, 32)
    med_2k, _ = drain(0, 32, 32)
    step_dev = (med_2k - med_k) / 24
    if step_dev <= 0:
        raise RuntimeError(
            f"plain differencing invalid: {med_k * 1e3:.0f} / "
            f"{med_2k * 1e3:.0f} ms")
    plain_tok_s = slots / step_dev

    # speculative: device ms/ROUND via the same differencing; tokens per
    # round from the engine's acceptance accounting
    med_r, st_r = drain(k, 4, 16)
    med_2r, st_2r = drain(k, 16, 16)
    round_dev = (med_2r - med_r) / 12
    if round_dev <= 0:
        raise RuntimeError(
            f"spec differencing invalid: {med_r * 1e3:.0f} / "
            f"{med_2r * 1e3:.0f} ms")
    emitted = st_r["emitted"] + st_2r["emitted"]
    rounds = st_r["rounds"] + st_2r["rounds"]
    tokens_per_pass = emitted / max(rounds, 1)
    spec_tok_s = slots * tokens_per_pass / round_dev
    return {
        "slots": slots, "k": k, "kv_dtype": kv_dtype,
        "plain_tok_s": round(plain_tok_s, 1),
        "spec_tok_s": round(spec_tok_s, 1),
        "speedup": round(spec_tok_s / plain_tok_s, 2),
        "tokens_per_pass": round(tokens_per_pass, 2),
        "ms_per_step_device": round(step_dev * 1e3, 2),
        "ms_per_round_device": round(round_dev * 1e3, 2),
        "speedup_acceptance_bound": round(
            tokens_per_pass * step_dev / round_dev, 2),
    }


# ---------------------------------------------------------------------
# Call-tunnel phase: the persistent pipelined call channel vs per-call
# POST (ISSUE 2). BENCH_r05 measured ~103 ms of fixed cost per call on
# the staging path — connection + headers + two serialize/deserialize
# hops — which is most of the gap between rolling decode on-device
# (6,850 tok/s) and through the tunnel (4,168 tok/s). This phase
# measures that tax directly against a real pod server + worker
# subprocess serving a decode-chunk simulator whose ``step()`` costs a
# configurable device time and returns a [steps, batch] token block, so
# the tunnel numbers compose with phase 1's measured device time:
#
# - ``serving_post_ms_p50``      one chunk via POST (the old path)
# - ``serving_chan_ms_p50``      one chunk via the channel at depth 1
#   (must reproduce, not regress, the POST-era behavior)
# - ``serving_chunk_ms_pipelined`` effective per-chunk wall at depth ≥ 2
#   (client ships chunk N+1 while N is on device — the dispatch tax
#   hides under device time)
# - the per-call latency decomposition (client serialize / wire /
#   server queue / worker dispatch / device), medians over the depth-1
#   channel calls, mirroring the Prometheus histograms.

_DECODE_SIM = '''\
"""Decode-chunk simulator served by the call-tunnel bench (written to a
temp dir; the pod worker imports it by path)."""
import time


class DecodeSim:
    def __init__(self, device_ms=3.0, batch=8, steps=16):
        self.device_ms = float(device_ms)
        self.block = [[(i * steps + j) % 32000 for i in range(batch)]
                      for j in range(steps)]

    def step(self, i=0):
        time.sleep(self.device_ms / 1000.0)
        return {"events": self.block, "i": i, "pending": 1}

    def ping(self):
        return "pong"
'''


class _PodServer:
    """A throwaway pod-server subprocess serving a bench callable (the
    same shape bench_dataplane uses for its store server). Defaults to
    DecodeSim; the engine phase points it at EngineHost."""

    def __init__(self, root: str, device_ms: float, batch: int,
                 steps: int, name: str = "DecodeSim",
                 import_path: str = "decode_sim",
                 init_kwargs: Optional[dict] = None,
                 extra_env: Optional[dict] = None):
        import json as _json
        import os
        import subprocess

        from kubetorch_tpu.bench_dataplane import _free_port
        from kubetorch_tpu.serving import http_client

        self.port = _free_port()
        env = {
            **os.environ,
            "KT_SERVICE_NAME": "bench-decode",
            "KT_CLS_OR_FN_NAME": name,
            "KT_CALLABLE_NAME": name,
            "KT_CALLABLE_TYPE": "cls",
            "KT_ROOT_PATH": root,
            "KT_IMPORT_PATH": import_path,
            "KT_NUM_PROCS": "1",
            "KT_ALLOWED_SERIALIZATION": "json,pickle",
            "KT_INIT_ARGS": _json.dumps({"kwargs": init_kwargs if
                                         init_kwargs is not None else {
                "device_ms": device_ms, "batch": batch, "steps": steps}}),
            **(extra_env or {}),
            # host-only callables (DecodeSim, SimRollingEngine) under a
            # parent that may hold the chip: never reach for it
            "JAX_PLATFORMS": "cpu",
        }
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.serving.server",
             "--host", "127.0.0.1", "--port", str(self.port)],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
        self.url = f"http://127.0.0.1:{self.port}"
        deadline = time.time() + 60
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise RuntimeError("bench pod server died during startup")
            if http_client.is_ready(self.url, timeout=2.0):
                return
            time.sleep(0.1)
        raise RuntimeError("bench pod server never became ready")

    def stop(self):
        self.proc.terminate()
        try:
            self.proc.wait(5)
        except Exception:
            self.proc.kill()


def bench_call_channel(device_ms: float = 3.0, batch: int = 8,
                       steps_per_call: int = 16, n_chunks: int = 40,
                       depth: int = 2, reps: int = 3,
                       dryrun: bool = False) -> dict:
    """Measure the call tunnel: POST vs channel vs pipelined channel at
    ``depth`` against a pod server whose chunk costs ``device_ms`` on
    "device". Phases are INTERLEAVED per rep (post, chan, pipelined,
    post, ...) and the reported per-chunk number is the median of
    per-rep means — on a shared host a phase-ordered run would charge
    whichever phase ran during a load spike (first dryruns measured the
    pipelined phase 2× slower than depth-1 purely from ordering).
    ``dryrun`` shrinks sizes to the CI smoke shape."""
    import os
    import shutil
    import tempfile

    from kubetorch_tpu.observability import tracing
    from kubetorch_tpu.serving import http_client
    from kubetorch_tpu.serving.channel import CallChannel

    if dryrun:
        device_ms, batch, steps_per_call = 3.0, 8, 16
        n_chunks, depth, reps = 20, 2, 3
    trace_seq0 = tracing.recorder.seq
    root = tempfile.mkdtemp(prefix="kt-bench-chan-")
    with open(os.path.join(root, "decode_sim.py"), "w") as f:
        f.write(_DECODE_SIM)
    server = _PodServer(root, device_ms, batch, steps_per_call)
    out = {
        "serving_pipeline_depth": depth,
        "serving_device_ms_cfg": device_ms,
        "serving_chunk_tokens": batch * steps_per_call,
    }

    def run_post():
        walls = []
        for i in range(n_chunks):
            t0 = time.perf_counter()
            http_client.call_method(server.url, "DecodeSim",
                                    method="step", args=(i,))
            walls.append(time.perf_counter() - t0)
        return _median(walls) * 1e3

    stages: dict = {"client_ser": [], "wire": [], "server_queue": [],
                    "worker_dispatch": [], "device": []}

    def run_chan(d):
        """One channel pass at depth ``d``; per-chunk ms = wall / n (at
        depth 1 that's also the per-call median discipline, and the
        per-call stage decomposition is collected from these calls)."""
        with CallChannel(server.url, "DecodeSim", depth=d) as chan:
            chan.call(method="ping")     # connection + import warm
            calls = []
            t0 = time.perf_counter()
            for i in range(n_chunks):
                calls.append(chan.submit(i, method="step"))
            results = [c.result() for c in calls]
            wall = time.perf_counter() - t0
            assert [r["i"] for r in results] == list(range(n_chunks)), \
                "pipelined responses arrived out of order"
            if d == 1:
                for call in calls:
                    t = call.timings
                    for key in stages:
                        if key in t:
                            stages[key].append(t[key])
        return wall / n_chunks * 1e3

    try:
        # warm: worker import + keep-alive connection, off the clock
        for _ in range(3):
            http_client.call_method(server.url, "DecodeSim",
                                    method="ping")
        post, chan1, piped = [], [], []
        for _ in range(max(1, reps)):
            post.append(run_post())
            chan1.append(run_chan(1))
            piped.append(run_chan(depth))
        out["serving_post_ms_p50"] = round(_median(post), 2)
        out["serving_chan_ms_p50"] = round(_median(chan1), 2)
        out["serving_chunk_ms_pipelined"] = round(_median(piped), 2)
        out["serving_chunk_ms_pipelined_spread"] = [
            round(min(piped), 2), round(max(piped), 2)]
        for key, xs in stages.items():
            if xs:
                out[f"serving_{key}_ms"] = round(_median(xs), 3)
    finally:
        server.stop()
        shutil.rmtree(root, ignore_errors=True)

    # derived: per-chunk tax above device time, and tok/s through each
    # tunnel flavor for a batch*steps_per_call chunk
    toks = batch * steps_per_call
    for flavor, key in (("post", "serving_post_ms_p50"),
                        ("chan", "serving_chan_ms_p50"),
                        ("pipelined", "serving_chunk_ms_pipelined")):
        ms = out[key]
        out[f"serving_dispatch_tax_ms_{flavor}"] = round(
            max(0.0, ms - device_ms), 2)
        out[f"serving_tok_s_{flavor}"] = round(toks / (ms / 1e3), 1)
    out["serving_pipeline_speedup"] = round(
        out["serving_post_ms_p50"] / out["serving_chunk_ms_pipelined"], 3)
    # tracing cost accounting (always-on spans ride every call above):
    # client-side spans recorded during the bench, and the measured
    # per-span overhead — the smoke test asserts a pipelined chunk pays
    # <5% of its wall to tracing (see tests/test_serving_smoke.py)
    out["trace_span_count"] = tracing.recorder.seq - trace_seq0
    out["trace_overhead_us_per_span"] = round(
        tracing.measure_overhead_us(), 3)
    return out


# ---------------------------------------------------------------------
# Engine phase (ISSUE 10): the SERVER-RESIDENT generation loop vs the
# client-driven chunk loop. BENCH_r05's two headline serving gaps —
# 144 ms/chunk dispatch tax (client drives every chunk) and 182 ms
# admission-swap overhead with 561 ms TTFT p50 (admission swaps whole
# batches) — both disappear when the loop lives where the batch lives:
# the client submits ONE generation program as a streamed channel call
# and serving/engine.py runs rolling steps back-to-back, admitting
# per-row and interleaving chunked prefill between decode chunks.
#
# Keys (asserted by tests/test_serving_smoke.py):
# - engine_tok_s_tunnel_wall     delivered tok/s through the tunnel with
#                                the engine loop server-side
# - engine_device_tok_s          the same window's device-side rate
# - engine_tunnel_ratio          tunnel/device — the acceptance number
#                                (full run asserts >= 0.9 vs BENCH_r05's
#                                0.61)
# - engine_dispatch_ms_per_chunk amortized fixed cost per decode chunk
#                                (wall minus device over the chunk count)
# - engine_ttft_ms_p50/p99       Poisson-phase first-token latency with
#                                per-row admission
# - engine_poisson_goodput_ratio delivered / offered under open-loop load
# - engine_prefill_interleave_ok scheduler invariant: decode never
#                                stalled while a long prompt prefilled
# - engine_admit_to_first_token_chunks  ticks from admission to first
#                                token for a chunked-prefill prompt
#
# The pod hosts DecodeEngine over the host-only SimRollingEngine (the
# scheduler cannot tell it from the real thing), so the phase runs on
# CPU CI; the full bench re-runs it with step_ms set to phase 1's
# differenced device time, composing device truth with loop overhead.

_ENGINE_HOST = '''\
"""Engine host served by the engine bench (written to a temp dir; the
pod worker imports it by path): DecodeEngine over SimRollingEngine."""
from kubetorch_tpu.serving.engine import DecodeEngine, SimRollingEngine


class EngineHost:
    def __init__(self, max_slots=8, steps_per_call=16, step_ms=20.0,
                 prefill_chunk=32):
        self.engine = DecodeEngine(SimRollingEngine(
            max_slots=int(max_slots), steps_per_call=int(steps_per_call),
            prefill_chunk=int(prefill_chunk),
            step_s=float(step_ms) / 1e3))

    def generate(self, program):
        yield from self.engine.generate(program)

    def stats(self):
        return self.engine.stats()

    def ping(self):
        return "pong"
'''


def _bench_engine_scheduler() -> dict:
    """In-process scheduler invariants (no pod, no model): chunked
    prefill must interleave — the live stream keeps emitting while a
    long prompt fills — and admit-to-first-token must be bounded by the
    prompt's chunk count."""
    import threading

    from kubetorch_tpu.serving.engine import DecodeEngine, SimRollingEngine

    out: dict = {}
    long_p = list(range(10, 74))                    # 64 tokens = 8 chunks
    eng = DecodeEngine(
        SimRollingEngine(max_slots=4, steps_per_call=8, prefill_chunk=8,
                         step_s=0.002), poll_s=0.001)
    stamps: dict = {"short": [], "long": []}

    def drain(name, prog):
        for f in eng.generate(prog):
            stamps[name].append(time.perf_counter())

    import contextvars

    try:
        ts = threading.Thread(
            target=contextvars.copy_context().run, args=(
                drain, "short",
                {"prompt": [1, 2, 3], "max_new_tokens": 400}))
        ts.start()
        wait_deadline = time.time() + 30
        while not stamps["short"]:
            if time.time() > wait_deadline or not ts.is_alive():
                raise RuntimeError(
                    "engine scheduler bench: the short stream never "
                    "produced a frame (engine loop broken?)")
            time.sleep(0.001)
        t_submit = time.perf_counter()
        tl = threading.Thread(
            target=contextvars.copy_context().run, args=(
                drain, "long",
                {"prompt": long_p, "max_new_tokens": 16}))
        tl.start()
        ts.join(60)
        tl.join(60)
        t_first_long = stamps["long"][0]
        short_during = [t for t in stamps["short"]
                        if t_submit <= t < t_first_long]
        out["engine_prefill_interleave_ok"] = float(
            len(short_during) >= 3)
    finally:
        eng.close()

    # admit-to-first-token in TICKS, hand-driven (wall-free — CI-safe):
    # a 64-token prompt at chunk 8 needs 8 prefill ticks + its first
    # decode tick, decode running the whole way
    sim = SimRollingEngine(max_slots=2, steps_per_call=4,
                           prefill_chunk=8, step_s=0.0)
    bg = sim.submit([1], max_new_tokens=10 ** 6)
    sim.step()
    r_long = sim.submit(long_p, max_new_tokens=8)
    ticks = 0
    while ticks < 100:
        ticks += 1
        events = sim.step()
        assert any(r == bg and toks for r, toks, _ in events), \
            "decode stalled during chunked prefill"
        if any(r == r_long and toks for r, toks, _ in events):
            break
    sim.evict(bg)
    out["engine_admit_to_first_token_chunks"] = ticks

    # satellite (ISSUE 19): flight-append overhead. The recorder rides
    # every driver tick, so its append must cost well under 1% of one.
    # Denominator: the mean wall of the live engine's WORKING ticks
    # above (idle polls append too but carry no device time — dividing
    # by them would flatter nothing and measure the poll loop instead);
    # fallback when the ring is disabled in this environment: the sim's
    # configured 2 ms chunk.
    from kubetorch_tpu.observability import flight as _flight

    tick_s = 0.002
    rec = _flight.get_recorder()
    if rec is not None:
        walls = [r["tick_s"] for r in rec.snapshot(limit=512)
                 if r.get("decode_tokens") and r.get("tick_s")]
        if walls:
            tick_s = sum(walls) / len(walls)
    bench_rec = _flight.FlightRecorder(capacity=1024)
    sample = (time.time(), time.perf_counter(), 0.002, 0.002, 1e-4,
              1.0, 1.0, 8.0, 32.0, 1.0, 1.0, 0.0, 0.0, 0.0, 0.0, 2.0,
              4.0, 100.0, 0.5, 0.5, ("trace",))
    n_app = 20000
    t0 = time.perf_counter()
    for _ in range(n_app):
        bench_rec.append(*sample)
    per_append = (time.perf_counter() - t0) / n_app
    out["flight_overhead_pct"] = round(per_append / tick_s * 100, 4)
    return out


def bench_engine(step_ms: float = 20.0, batch: int = 8,
                 steps_per_call: int = 16, n_tokens: int = 320,
                 poisson_programs: int = 24, load: float = 0.6,
                 dryrun: bool = False) -> dict:
    """Measure the server-resident engine loop end-to-end: a real pod
    server + worker hosting DecodeEngine, driven by generation programs
    over the channel. ``step_ms`` is the simulated per-decode-chunk
    device time (the full bench passes phase 1's differenced number);
    ``load`` the Poisson phase's offered fraction of device capacity."""
    import os
    import random
    import shutil
    import tempfile
    import threading

    from kubetorch_tpu.serving.channel import CallChannel
    from kubetorch_tpu.serving.engine import SimRollingEngine

    if dryrun:
        step_ms, batch, steps_per_call = 20.0, 8, 16
        n_tokens, poisson_programs, load = 320, 24, 0.6
    out = _bench_engine_scheduler()
    out["engine_step_ms_cfg"] = step_ms
    out["engine_chunk_tokens"] = batch * steps_per_call

    root = tempfile.mkdtemp(prefix="kt-bench-engine-")
    with open(os.path.join(root, "engine_host.py"), "w") as f:
        f.write(_ENGINE_HOST)
    server = _PodServer(
        root, step_ms, batch, steps_per_call, name="EngineHost",
        import_path="engine_host",
        init_kwargs={"max_slots": batch, "steps_per_call": steps_per_call,
                     "step_ms": step_ms, "prefill_chunk": 32},
        extra_env={"KT_WORKER_THREADS": str(max(32, 2 * batch)),
                   "KT_ENGINE_POLL_S": "0.002"})
    try:
        # ---- tunnel wall: fill every row, one program per row --------
        with CallChannel(server.url, "EngineHost", depth=batch) as chan:
            chan.call(method="ping")       # connect + import, off-clock
            st0 = chan.call(method="stats")
            prompts = [[i + 1, i + 2, i + 3] for i in range(batch)]
            calls = []
            t0 = time.perf_counter()
            for p in prompts:
                calls.append(chan.submit(
                    {"prompt": p, "max_new_tokens": n_tokens},
                    method="generate", stream=True, concurrent=True,
                    timeout=120.0))
            total = 0
            for call, p in zip(calls, prompts):
                toks = [t for f in call.result(timeout=300)
                        for t in f["tokens"]]
                assert toks == SimRollingEngine.expected_tokens(
                    p, n_tokens), "engine stream tokens diverged"
                total += len(toks)
            wall = time.perf_counter() - t0
            st1 = chan.call(method="stats")
        steps = max(1, st1["steps"] - st0["steps"])
        dev_s = max(1e-9, st1["device_s"] - st0["device_s"])
        out["engine_tok_s_tunnel_wall"] = round(total / wall, 1)
        out["engine_device_tok_s"] = round(total / dev_s, 1)
        out["engine_tunnel_ratio"] = round(
            out["engine_tok_s_tunnel_wall"]
            / out["engine_device_tok_s"], 4)
        out["engine_dispatch_ms_per_chunk"] = round(
            max(0.0, wall - dev_s) / steps * 1e3, 2)
        if not dryrun and out["engine_tunnel_ratio"] < 0.9:
            # the acceptance bar: with the loop server-side the tunnel
            # rate sits within 10% of device-side (BENCH_r05's
            # client-driven loop managed 61%)
            raise RuntimeError(
                f"engine tunnel ratio {out['engine_tunnel_ratio']} "
                f"below the 0.9 acceptance floor")

        # ---- Poisson arrivals: per-row admission TTFT + goodput ------
        rnd = random.Random(0)
        lens = [rnd.randrange(2 * steps_per_call, 8 * steps_per_call + 1)
                for _ in range(poisson_programs)]
        capacity = batch * steps_per_call / (step_ms / 1e3)
        offered = load * capacity
        lam_req = offered / (sum(lens) / len(lens))
        arrive, acc = [], 0.0
        for _ in lens:
            acc += rnd.expovariate(lam_req)
            arrive.append(acc)
        results: list = []
        threads = []
        with CallChannel(server.url, "EngineHost", depth=batch) as chan:
            chan.call(method="ping")
            t_start = time.perf_counter()
            for i, n_i in enumerate(lens):
                lag = arrive[i] - (time.perf_counter() - t_start)
                if lag > 0:
                    time.sleep(lag)
                call = chan.submit(
                    {"prompt": [i + 1, 7], "max_new_tokens": n_i},
                    method="generate", stream=True, concurrent=True,
                    timeout=120.0)
                t_sub = time.perf_counter()

                def drain_one(call=call, t_sub=t_sub):
                    first = None
                    count = 0
                    for frame in call:
                        if first is None and frame["tokens"]:
                            first = time.perf_counter()
                        count += len(frame["tokens"])
                    results.append((t_sub, first, time.perf_counter(),
                                    count))

                import contextvars as _cv

                th = threading.Thread(
                    target=_cv.copy_context().run, args=(drain_one,),
                    daemon=True)
                th.start()
                threads.append(th)
            for th in threads:
                th.join(300)
        assert len(results) == poisson_programs, \
            f"{len(results)}/{poisson_programs} programs completed"
        ttft = [(first - t_sub) * 1e3 for t_sub, first, _, _ in results
                if first is not None]
        done_wall = max(t_done for _, _, t_done, _ in results) - t_start
        delivered = sum(c for _, _, _, c in results) / done_wall
        out.update({
            "engine_poisson_programs": poisson_programs,
            "engine_poisson_offered_tok_s": round(offered, 1),
            "engine_poisson_tok_s": round(delivered, 1),
            "engine_poisson_goodput_ratio": round(delivered / offered, 4),
            "engine_ttft_ms_p50": round(_pct(ttft, 50), 1),
            "engine_ttft_ms_p99": round(_pct(ttft, 99), 1),
        })
    finally:
        server.stop()
        shutil.rmtree(root, ignore_errors=True)
    return out


# ---------------------------------------------------------------------
# Paged-KV / prefix-cache phase (ISSUE 11): the two multi-tenant wins
# the Gemma-on-TPU serving paper attributes real throughput to —
# prefix sharing (N same-system-prompt programs prefill the prefix
# once) and idle-session KV park/restore (a returning user resumes
# mid-conversation at ~one decode chunk instead of a full prefill).
# Runs the DecodeEngine scheduler in-process over SimRollingEngine
# (dryrun-capable: pure CPU, wall-free arithmetic where possible).
#
# Keys (asserted by tests/test_serving_smoke.py):
# - prefix_prefill_tokens_saved_ratio   1 − executed/naive prefill
#     tokens across an N-way shared-prefix run; the acceptance floor is
#     ≥ 0.5·(N−1)/N (perfect sharing approaches (N−1)/N as suffix→0)
# - prefix_kv_hits/misses               cache behavior (N−1 hits, 1 miss)
# - kv_resume_ttft_ms/_chunks           park→resume first-token latency,
#     in ms and in units of one decode chunk — the "≈ one decode chunk"
#     acceptance number
# - kv_unparked_ttft_ms                 the same prompt's cold TTFT (it
#     pays the full chunked prefill) — the contrast that makes the
#     resume number meaningful


def bench_prefix_kv(n_programs: int = 8, prefix_len: int = 64,
                    suffix_len: int = 8, max_new: int = 32,
                    step_ms: float = 4.0, park_step_ms: float = 20.0,
                    dryrun: bool = False) -> dict:
    import tempfile
    import threading

    from kubetorch_tpu.data_store import client as client_mod
    from kubetorch_tpu.serving.engine import (
        DecodeEngine,
        SimRollingEngine,
    )

    if dryrun:
        n_programs, prefix_len, suffix_len = 8, 64, 8
        max_new, step_ms, park_step_ms = 32, 4.0, 20.0
    out: dict = {"prefix_kv_programs": n_programs}

    # ---- phase 1: N-way shared prefix --------------------------------
    sim = SimRollingEngine(max_slots=n_programs, steps_per_call=8,
                           step_s=step_ms / 1e3)
    eng = DecodeEngine(sim, poll_s=0.002,
                       prefix_split=f"len:{prefix_len}",
                       kv_block_tokens=16)
    prefix = list(range(100, 100 + prefix_len))
    results: dict = {}

    def drain(i):
        suffix = [1000 + i] * suffix_len
        frames = list(eng.generate({"prompt": prefix + suffix,
                                    "max_new_tokens": max_new}))
        results[i] = [t for f in frames for t in f["tokens"]]

    import contextvars as _cv

    try:
        threads = [threading.Thread(
            target=_cv.copy_context().run, args=(drain, i))
            for i in range(n_programs)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        for i in range(n_programs):
            expect = SimRollingEngine.expected_tokens(
                prefix + [1000 + i] * suffix_len, max_new)
            assert results.get(i) == expect, \
                f"shared-prefix stream {i} diverged"
        st = eng.stats()
    finally:
        eng.close()
    naive = st["prefill_tokens_naive"]
    executed = st["prefill_tokens_executed"]
    saved = 1.0 - executed / naive
    misses = st["prefixes"]       # each distinct prefix registered once
    out.update({
        "prefix_prefill_tokens_naive": naive,
        "prefix_prefill_tokens_executed": executed,
        "prefix_prefill_tokens_saved_ratio": round(saved, 4),
        "prefix_kv_hits": n_programs - misses,
        "prefix_kv_misses": misses,
    })
    floor = 0.5 * (n_programs - 1) / n_programs
    assert saved >= floor, (
        f"prefill tokens saved {saved:.3f} below the "
        f"0.5*(N-1)/N = {floor:.3f} acceptance floor — prefix sharing "
        f"is not actually sharing")

    # ---- phase 2: park → resume TTFT ---------------------------------
    # The store is process-default; point the local backend at a temp
    # root for the bench's session blobs and restore it after.
    tmp = tempfile.mkdtemp(prefix="kt-bench-kv-")
    saved_root = client_mod._LOCAL_STORE
    saved_default = client_mod.DataStoreClient._default
    client_mod._LOCAL_STORE = __import__("pathlib").Path(tmp)
    client_mod.DataStoreClient._default = None
    prompt = list(range(7, 71))                 # 64 tokens = 8 chunks
    sim2 = SimRollingEngine(max_slots=2, steps_per_call=8,
                            prefill_chunk=8, step_s=park_step_ms / 1e3)
    eng2 = DecodeEngine(sim2, poll_s=0.002)
    try:
        # cold TTFT: the same prompt pays its full chunked prefill
        t0 = time.perf_counter()
        for f in eng2.generate({"prompt": prompt, "max_new_tokens": 8}):
            if f["tokens"]:
                out["kv_unparked_ttft_ms"] = round(
                    (time.perf_counter() - t0) * 1e3, 1)
                break

        got: list = []
        parked = threading.Event()

        def run_session():
            for f in eng2.generate({"prompt": prompt,
                                    "max_new_tokens": 512,
                                    "session_id": "bench-sess"}):
                if f.get("parked"):
                    parked.set()
                    return
                got.extend(f["tokens"])

        th = threading.Thread(target=_cv.copy_context().run,
                              args=(run_session,))
        th.start()
        deadline = time.time() + 30
        while len(got) < 8 and time.time() < deadline:
            time.sleep(0.002)
        t0 = time.perf_counter()
        n_parked = eng2.park("bench-sess")
        out["kv_park_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        th.join(10)
        assert n_parked == 1 and parked.is_set(), "park never landed"

        t0 = time.perf_counter()
        ttft = None
        rest: list = []
        for f in eng2.generate({"prompt": prompt, "max_new_tokens": 512,
                                "session_id": "bench-sess"}):
            if f["tokens"] and ttft is None:
                ttft = time.perf_counter() - t0
            rest.extend(f["tokens"])
            if len(rest) >= 16:
                break
        expect = SimRollingEngine.expected_tokens(
            prompt, len(got) + len(rest))
        assert got + rest == expect, "resumed stream diverged"
        out["kv_resume_ttft_ms"] = round(ttft * 1e3, 1)
        out["kv_resume_ttft_chunks"] = round(ttft * 1e3 / park_step_ms, 2)
        # the acceptance contrast: a resume costs ~one decode chunk, not
        # the prompt's 8-chunk prefill
        assert out["kv_resume_ttft_ms"] < 0.5 * out["kv_unparked_ttft_ms"], (
            out["kv_resume_ttft_ms"], out["kv_unparked_ttft_ms"])
    finally:
        eng2.close()
        client_mod._LOCAL_STORE = saved_root
        client_mod.DataStoreClient._default = saved_default
        import shutil as _sh

        _sh.rmtree(tmp, ignore_errors=True)
    return out


# ---------------------------------------------------------------------
# Speculative scheduling phase (ISSUE 14): draft/verify as a scheduler
# citizen — per-row adaptive lookahead inside the continuous-batching
# engine. Paired Poisson runs (IDENTICAL seeded arrivals) with
# speculation off and on over a mixed workload: half the programs are
# "extractive" rows whose drafts land (scripted accept 0.9 — the
# code-editing / RAG-quoting regime), half adversarial-random (accept
# 0.0). The numbers the smoke test guards:
#
# - spec_tok_s_{on,off} + spec_goodput_ratio   delivered tok/s at the
#     same offered load — speculation must BEAT plain decode
# - spec_ttft_ms_p99_{on,off}                  ...at equal TTFT p99
#     (admission is untouched; spec only frees rows faster)
# - spec_accept_rate                           drafts landed / offered
# - spec_k_p50/p99                             per-row lookahead at
#     completion, across all programs
# - spec_k_high_accept_p50 / spec_k_adversarial_p50   the adaptation
#     acceptance: high-accept rows hold k > 2, adversarial rows settle
#     at k = 1 (verify FLOPs stop where drafts don't land)


def bench_engine_spec(n_programs: int = 16, step_ms: float = 10.0,
                      batch: int = 8, steps_per_call: int = 8,
                      spec_k: int = 6, max_new: int = 64,
                      load: float = 1.4, dryrun: bool = False) -> dict:
    import random

    from kubetorch_tpu.serving.engine import SimRollingEngine

    if dryrun:
        n_programs, step_ms, batch = 16, 10.0, 8
        steps_per_call, spec_k, max_new, load = 8, 6, 64, 1.4

    # even first token = extractive row, odd = adversarial-random
    def accept(prompt):
        return 0.9 if prompt and prompt[0] % 2 == 0 else 0.0

    prompts = [[100 + i, 7] for i in range(n_programs)]
    rnd = random.Random(11)
    capacity = batch * steps_per_call / (step_ms / 1e3)   # plain tok/s
    lam = load * capacity / max_new
    arrive, acc_t = [], 0.0
    for _ in prompts:
        acc_t += rnd.expovariate(lam)
        arrive.append(acc_t * 1e3)              # ms, virtual

    def run_phase(k):
        # VIRTUAL-TIME Poisson phase (the PR-8 goodput-model pattern):
        # hand-driven ticks over the row-granular scheduler surface,
        # one decode chunk = step_ms of clock — deterministic on any
        # host, no sleeps, no thread-scheduling noise. The arrivals
        # run ABOVE plain capacity so the scheduler, not the arrival
        # process, is the bottleneck — that is where speculation's
        # extra tokens per chunk become goodput. (The occupancy
        # throttle is out of scope here: the sim's chunk cost is
        # constant in verify width — the weight-bound regime — so a
        # cap would only model a penalty the sim doesn't charge; the
        # throttle's behavior is unit-tested.)
        sim = SimRollingEngine(
            max_slots=batch, steps_per_call=steps_per_call,
            step_s=0.0, spec_k=k, spec_accept=accept)
        sub_at: dict = {}
        first: dict = {}
        done_at: dict = {}
        by_rid: dict = {}
        clock, i = 0.0, 0
        while len(done_at) < n_programs:
            while i < n_programs and arrive[i] <= clock:
                rid = sim.submit(prompts[i], max_new_tokens=max_new)
                sub_at[rid] = arrive[i]
                by_rid[rid] = prompts[i]
                i += 1
            if not sim.pending:
                clock = arrive[i]          # idle: jump to next arrival
                continue
            events = sim.step()
            clock += step_ms               # one decode chunk of device
            for rid, toks, done in events:
                if toks and rid not in first:
                    first[rid] = clock
                if done:
                    done_at[rid] = clock
        total = n_programs * max_new
        wall_ms = max(done_at.values()) - min(sub_at.values())
        ttft = [first[rid] - sub_at[rid] for rid in first]
        return {
            "tok_s": total / (wall_ms / 1e3),
            "ttft_p99": _pct(ttft, 99),
            "stats": dict(sim.spec_stats),
            "final_k": [(by_rid[rid], sim.spec_k_done.get(rid))
                        for rid in done_at],
        }

    off = run_phase(0)
    on = run_phase(spec_k)
    ks = [k for _, k in on["final_k"] if k is not None]
    high = [k for (p, k) in on["final_k"]
            if k is not None and p[0] % 2 == 0]
    adv = [k for (p, k) in on["final_k"]
           if k is not None and p[0] % 2 == 1]
    out = {
        "spec_programs": n_programs,
        "spec_k_max_cfg": spec_k,
        "spec_tok_s_off": round(off["tok_s"], 1),
        "spec_tok_s_on": round(on["tok_s"], 1),
        "spec_goodput_ratio": round(on["tok_s"] / off["tok_s"], 4),
        "spec_ttft_ms_p99_off": round(off["ttft_p99"], 1),
        "spec_ttft_ms_p99_on": round(on["ttft_p99"], 1),
        "spec_accept_rate": round(
            on["stats"].get("accept_rate", 0.0), 4),
        "spec_k_p50": _pct(ks, 50),
        "spec_k_p99": _pct(ks, 99),
        "spec_k_high_accept_p50": _pct(high, 50),
        "spec_k_adversarial_p50": _pct(adv, 50),
    }
    # the ISSUE 14 acceptance shape, asserted here so a full bench run
    # fails loudly too (the smoke test re-asserts on dryrun output):
    # speculation must beat plain decode WITHOUT costing TTFT (at the
    # overloaded operating point it strictly improves it — rows free
    # faster, the queue drains sooner), and the per-row k must
    # converge BOTH directions
    assert out["spec_tok_s_on"] >= out["spec_tok_s_off"], out
    assert (out["spec_ttft_ms_p99_on"]
            <= 1.25 * out["spec_ttft_ms_p99_off"] + 25.0), out
    assert out["spec_k_high_accept_p50"] > 2, out
    assert out["spec_k_adversarial_p50"] <= 1.0, out
    return out


def bench_telemetry(frames: int = 200, n_metrics: int = 80,
                    n_hists: int = 3, n_objectives: int = 4,
                    dryrun: bool = False) -> dict:
    """Fleet telemetry plane cost: what the heartbeat piggyback adds to
    a beat tick (frame build on the pod + ingest at the controller),
    and what one SLO evaluation sweep costs — both CI-guarded
    (``tests/test_serving_smoke.py``): the piggyback must stay <3 % of
    a heartbeat tick, or telemetry is taxing liveness.

    Dryrun and full runs share the shape (pure CPU, in-process
    FleetStore + SLOEngine at a representative pod profile: ~80 flat
    metrics + 3 histogram families x 13 buckets, two replicas)."""
    import time as _time

    from kubetorch_tpu.config import env_float as _env_float
    from kubetorch_tpu.observability.fleetstore import (
        FleetStore,
        build_frame,
    )
    from kubetorch_tpu.observability.slo import Objective, SLOEngine

    if dryrun:
        frames = min(frames, 120)
    heartbeat_s = _env_float("KT_HEARTBEAT_S")
    store = FleetStore()
    objectives = [
        Objective(service="bench", name=f"slo{i}", kind="latency",
                  metric="h0", threshold_ms=250.0, objective=0.99)
        for i in range(max(1, n_objectives - 1))
    ] + [Objective(service="bench", name="shed", kind="ratio",
                   bad="engine_sheds_bench_total",
                   total="engine_calls_bench_total", objective=0.98)]
    slo = SLOEngine(store, objectives=objectives)

    # representative pod metric surface: counters climb monotonically,
    # gauges wander, histograms accumulate
    les = [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
           1.0, 2.5, 10.0, 30.0]

    def pod_state(step, pod_seed):
        metrics = {}
        for i in range(n_metrics):
            name = (f"engine_m{i}_total" if i % 2 == 0
                    else f"engine_g{i}")
            metrics[name] = (step * (i + 1) if i % 2 == 0
                             else (step + pod_seed) % 17)
        metrics["engine_sheds_bench_total"] = step
        metrics["engine_calls_bench_total"] = step * 50
        hists = {}
        for j in range(n_hists):
            count = step * 10.0
            buckets = [count * min(1.0, (k + 1) / len(les))
                       for k in range(len(les))]
            hists[f"h{j}"] = {"le": les, "buckets": buckets,
                              "sum": count * 0.05, "count": count}
        return metrics, hists

    import json as _json

    build_s = 0.0
    ingest_s = 0.0
    bytes_total = 0
    last_sent = [{}, {}]
    for step in range(1, frames + 1):
        for pod in (0, 1):
            metrics, hists = pod_state(step, pod)
            t0 = _time.perf_counter()
            frame = build_frame(metrics, hists,
                                last_sent=last_sent[pod],
                                full=(step == 1))
            build_s += _time.perf_counter() - t0
            bytes_total += len(_json.dumps(frame))
            t0 = _time.perf_counter()
            store.ingest("bench", f"pod-{pod}", frame)
            ingest_s += _time.perf_counter() - t0
    n = frames * 2
    t0 = _time.perf_counter()
    slo.evaluate()
    eval_1 = (_time.perf_counter() - t0) * 1e3
    t0 = _time.perf_counter()
    reps = 5
    for _ in range(reps):
        slo.evaluate()
    eval_ms = ((_time.perf_counter() - t0) * 1e3) / reps
    per_frame_s = (build_s + ingest_s) / n
    out = {
        "telemetry_frames": n,
        "telemetry_frame_bytes_avg": round(bytes_total / n, 1),
        "telemetry_build_us_per_frame": round(build_s / n * 1e6, 2),
        "telemetry_ingest_us_per_frame": round(ingest_s / n * 1e6, 2),
        # the acceptance number: pod-side build + controller-side
        # ingest of ONE frame, as a percentage of one heartbeat tick
        "telemetry_ingest_overhead_pct": round(
            per_frame_s / heartbeat_s * 100.0, 4),
        "slo_eval_ms": round(eval_ms, 3),
        "slo_eval_first_ms": round(eval_1, 3),
        "slo_objectives": len(objectives),
    }
    assert out["telemetry_ingest_overhead_pct"] < 3.0, (
        f"telemetry piggyback costs "
        f"{out['telemetry_ingest_overhead_pct']}% of a heartbeat tick "
        f"(bound: 3%)")
    return out


# ---------------------------------------------------------------------
# Multi-tenant LoRA phase (ISSUE 16): device-resident adapter pool with
# O(1) per-row gather select. The numbers the smoke test guards:
#
# - lora_tok_s_ratio_8_adapters   delivered tok/s with 8 concurrent
#     tenants (one adapter per program) vs the same offered load on ONE
#     adapter — the scheduler's per-tenant surcharge (name resolution,
#     refcounting, per-adapter telemetry) must stay under 10%
# - lora_cold_load_hidden_ratio   decode wall time undisturbed vs with
#     a cold-adapter load storm mid-stream — background fetches +
#     driver-tick installs must not stall live rows
# - lora_select_overhead_pct      jax micro-bench of the gather select:
#     compiled cost at a 1-slot vs KT_LORA_SLOTS-wide adapter axis.
#     The gather reads each row's OWN rank-r factors, so the cost is
#     FLAT in the slot count (the one-hot einsum it replaced streamed
#     every slot's factors through the matmul, growing linearly)


def bench_lora(n_adapters: int = 8, programs: int = 8,
               max_new: int = 64, step_ms: float = 3.0,
               load_ms: float = 40.0, dryrun: bool = False) -> dict:
    import threading

    from kubetorch_tpu.exceptions import ServerOverloaded
    from kubetorch_tpu.serving.adapterpool import AdapterPool
    from kubetorch_tpu.serving.engine import (
        DecodeEngine,
        SimRollingEngine,
    )

    if dryrun:
        n_adapters, programs, max_new = 8, 8, 64
        step_ms, load_ms = 3.0, 40.0
    out: dict = {"lora_adapters": n_adapters,
                 "lora_slots_cfg": n_adapters}

    # ---- phase 1+2: engine throughput under the pool -----------------
    sim = SimRollingEngine(max_slots=programs, adapter_slots=n_adapters,
                           steps_per_call=8, step_s=step_ms / 1e3)

    def loader(name):
        time.sleep(load_ms / 1e3)
        return {"adapter": name}

    pool = AdapterPool(n_adapters, loader, sim.load_adapter_slot,
                       load_ema_alpha=0.5, load_seed_s=load_ms / 1e3)
    eng = DecodeEngine(sim, poll_s=0.002, adapter_pool=pool)

    def until_resident(fn, timeout=30.0):
        deadline = time.time() + timeout
        while True:
            try:
                return fn()
            except ServerOverloaded:
                if time.time() > deadline:
                    raise
                time.sleep(0.005)

    import contextvars as _cv

    def run_phase(names):
        """All ``programs`` rows concurrently, program i on
        names[i % len(names)] — identical offered load across phases,
        only the tenant fan-out differs."""
        results: dict = {}

        def drain(i):
            prompt = [100 + i, 7, 3]
            frames = until_resident(lambda: list(eng.generate(
                {"prompt": prompt, "max_new_tokens": max_new,
                 "adapter": names[i % len(names)]})))
            results[i] = [t for f in frames for t in f["tokens"]]

        t0 = time.perf_counter()
        threads = [threading.Thread(
            target=_cv.copy_context().run, args=(drain, i))
            for i in range(programs)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        wall = time.perf_counter() - t0
        for i in range(programs):
            expect = SimRollingEngine.expected_tokens([100 + i, 7, 3],
                                                      max_new)
            assert results.get(i) == expect, f"lora stream {i} diverged"
        return programs * max_new / wall

    try:
        names = [f"tenant-{i}" for i in range(n_adapters)]
        # warm every tenant resident first: phase 1 measures the STEADY
        # state surcharge, not cold-load latency (phase 2 measures that)
        for nm in names:
            until_resident(lambda nm=nm: list(eng.generate(
                {"prompt": [1], "max_new_tokens": 1, "adapter": nm})))
        # best-of-2 per phase: the phases are symmetric, so scheduler
        # jitter (CI neighbors) is the only difference between runs
        tok_s_single = max(run_phase(names[:1]) for _ in range(2))
        tok_s_multi = max(run_phase(names) for _ in range(2))
        ratio = tok_s_multi / tok_s_single
        out.update({
            "lora_tok_s_single": round(tok_s_single, 1),
            "lora_tok_s_8_adapters": round(tok_s_multi, 1),
            "lora_tok_s_ratio_8_adapters": round(ratio, 4),
        })

        # ---- cold loads hidden behind decode -------------------------
        long_new = max_new * 3
        cold_prompt = [9, 9, 9]
        expect = SimRollingEngine.expected_tokens(cold_prompt, long_new)

        def long_decode(disturb):
            got: list = []
            fired = False
            t0 = time.perf_counter()
            for f in eng.generate({"prompt": cold_prompt,
                                   "max_new_tokens": long_new,
                                   "adapter": "tenant-0"}):
                got.extend(f["tokens"])
                if disturb and not fired and got:
                    fired = True
                    # cold-adapter storm mid-stream: each sheds typed
                    # (load_ms fetch runs in the background) and LRU-
                    # evicts a cold resident at its driver-tick install
                    for nm in ("cold-a", "cold-b", "cold-c"):
                        try:
                            list(eng.generate(
                                {"prompt": [1], "max_new_tokens": 1,
                                 "adapter": nm}))
                        except ServerOverloaded:
                            pass
            wall = time.perf_counter() - t0
            assert got == expect, "cold-load phase stream diverged"
            return wall

        base_wall = min(long_decode(False) for _ in range(2))
        storm_wall = long_decode(True)
        out["lora_cold_load_hidden_ratio"] = round(
            base_wall / storm_wall, 4)
        # the storm's fetches must actually have happened for the
        # number to mean anything
        assert pool.loads >= n_adapters + 1, pool.stats()
    finally:
        eng.close()

    # ---- phase 3: gather-select cost, flat in the slot count ---------
    import jax
    import jax.numpy as jnp

    B, K, r, N = 8, 64, 8, 64

    def select(h, a, b, slots):
        # mirrors llama._lora_apply: per-row gather of rank-r factors
        sel = jnp.maximum(slots, 0)
        ag = jnp.take(a, sel, axis=0).astype(jnp.float32)
        bg = jnp.take(b, sel, axis=0).astype(jnp.float32)
        z = jnp.einsum("btk,bkr->btr", h.astype(jnp.float32), ag)
        d = jnp.einsum("btr,brn->btn", z, bg)
        return jnp.where((slots >= 0)[:, None, None], d, 0.0)

    def measure(n_slots):
        h = jnp.ones((B, 1, K), jnp.float32)
        a = jnp.ones((n_slots, K, r), jnp.float32)
        b = jnp.ones((n_slots, r, N), jnp.float32)
        slots = jnp.arange(B, dtype=jnp.int32) % n_slots
        fn = jax.jit(select)
        compiled = fn.lower(h, a, b, slots).compile()
        cost = None
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            cost = float(ca.get("flops", 0.0)) or None
        except Exception:
            cost = None
        if cost is not None:
            return cost, "flops"
        fn(h, a, b, slots).block_until_ready()     # warm
        t0 = time.perf_counter()
        reps = 200
        for _ in range(reps):
            fn(h, a, b, slots).block_until_ready()
        return (time.perf_counter() - t0) / reps, "seconds"

    one, unit = measure(1)
    wide, _ = measure(n_adapters)
    overhead = (wide - one) / one * 100.0
    out.update({
        "lora_select_cost_unit": unit,
        "lora_select_cost_1_slot": round(one, 9),
        "lora_select_cost_8_slots": round(wide, 9),
        "lora_select_overhead_pct": round(overhead, 3),
    })
    # FLAT: widening the adapter axis 1 → n must not grow the select's
    # compiled FLOPs at all (exact with cost_analysis); the timing
    # fallback gets CI headroom but still catches an O(n_slots) select
    bound = 1.0 if unit == "flops" else 30.0
    assert overhead < bound, (
        f"gather select cost grew {overhead:.1f}% ({unit}) from 1 to "
        f"{n_adapters} adapter slots — the select is scaling with pool "
        f"occupancy again (one-hot regression)")
    return out


def bench_disagg(n_programs: int = 64, step_ms: float = 3.0,
                 prefill_ms: float = 12.0, prompt_tokens: int = 64,
                 prefill_chunk: int = 32, max_new: int = 384,
                 batch: int = 4, steps_per_call: int = 8,
                 handoff_chunks: float = 2.0, load: float = 1.0,
                 ttft_slo_ms: float = 250.0,
                 dryrun: bool = False) -> dict:
    """Disaggregated prefill/decode vs equal-chip monolithic, in
    VIRTUAL time (the bench_engine_spec pattern: hand-driven ticks,
    seeded arrivals, deterministic on any host).

    Two chips per side. Monolithic: two mixed pods, join-least-pending
    routing, each tick pays its prefill chunks (compute-bound: charged
    per prefilling row) PLUS one decode chunk (bandwidth-bound: flat
    ``step_ms`` regardless of occupancy — the batched-step shape the
    sim pins). Disagg: one prefill pod that exports each row the tick
    its prefill lands (real ``export_row`` state dicts — the same tree
    the store ships) and frees the slot, one decode pod that imports
    off the wire and never pays a prefill charge. The handoff costs
    ``handoff_chunks`` decode chunks of wire latency and is fully
    overlapped with the prefill pod's next rows (measured, not
    assumed: the overlap ratio below is busy-interval arithmetic).
    The decode pod hosts no prefill activations, so its freed HBM
    carries 2x the KV row pool — the memory-budget specialization
    that lets the decode tier consolidate the fleet's decode into
    one full-batch bandwidth-bound loop.

    Goodput is SLO-attainment goodput (the DistServe definition):
    tokens from requests that met BOTH the TTFT SLO and the p95
    inter-chunk-gap SLO, per second of wall. That is the number the
    tentpole moves — interleaved prefill inflates the monolithic
    fleet's inter-token gaps (a 4x-cost prefill chunk stalls the whole
    decode batch) and its slot hold times (rows decode 5x slower, the
    queue spirals), while the decode tier's cadence stays one chunk
    per ``step_ms``.
    """
    import collections
    import random

    from kubetorch_tpu.serving.engine import SimRollingEngine

    if dryrun:
        n_programs, step_ms, prefill_ms = 64, 3.0, 12.0
        prompt_tokens, prefill_chunk, max_new = 64, 32, 384
        batch, steps_per_call, handoff_chunks = 4, 8, 2.0
        load, ttft_slo_ms = 1.0, 250.0
    assert prompt_tokens > prefill_chunk, "prompts must need prefill"

    handoff_ms = handoff_chunks * step_ms
    tpot_slo_ms = 2.0 * step_ms + 0.5      # p95 inter-chunk gap bound
    pf_chunks = -(-prompt_tokens // prefill_chunk)
    pf_req_ms = pf_chunks * prefill_ms
    lam = load / pf_req_ms                 # overload the prefill tier
    rnd = random.Random(17)
    arrive, prompts, t_acc = [], [], 0.0
    for i in range(n_programs):
        t_acc += rnd.expovariate(lam)
        arrive.append(t_acc)
        prompts.append([200 + i] + [7] * (prompt_tokens - 1))

    def tree_bytes(tree):
        if isinstance(tree, dict):
            return sum(tree_bytes(v) for v in tree.values())
        return int(getattr(tree, "nbytes", 0))

    class Pod:
        def __init__(self, slots=batch):
            self.eng = SimRollingEngine(
                max_slots=slots, steps_per_call=steps_per_call,
                prefill_chunk=prefill_chunk, step_s=0.0)
            self.clock = 0.0
            self.busy = []                 # device-busy (t0, t1) spans
            self.rid2idx = {}
            self.decode_ticks = 0
            self.decode_tokens = 0

    class Trace:
        def __init__(self):
            self.chunk_t = collections.defaultdict(list)
            self.got = collections.defaultdict(list)
            self.done_at = {}

        def record(self, pod, events):
            pod.decode_ticks += 1
            for rid, toks, done in events:
                idx = pod.rid2idx[rid]
                if toks:
                    self.got[idx].extend(toks)
                    self.chunk_t[idx].append(pod.clock)
                    pod.decode_tokens += len(toks)
                if done:
                    self.done_at[idx] = pod.clock

        def summarize(self):
            for idx in range(n_programs):
                expect = SimRollingEngine.expected_tokens(
                    prompts[idx], max_new)
                assert self.got[idx] == expect, \
                    f"stream {idx} diverged from the monolithic truth"
            ttft = [self.chunk_t[i][0] - arrive[i]
                    for i in range(n_programs)]
            wall_ms = max(self.done_at.values()) - arrive[0]
            ok_tok = 0
            for idx in range(n_programs):
                ct = self.chunk_t[idx]
                gaps = [b - a for a, b in zip(ct, ct[1:])]
                if (ttft[idx] <= ttft_slo_ms
                        and _pct(gaps, 95) <= tpot_slo_ms):
                    ok_tok += max_new
            return {"ttft_p99": _pct(ttft, 99), "wall_ms": wall_ms,
                    "tok_s": n_programs * max_new / (wall_ms / 1e3),
                    "goodput": ok_tok / (wall_ms / 1e3)}

    def mixed_tick(pod, trace):
        t0 = pod.clock
        # chunked prefill runs ONE request at a time (the real
        # engine's dispatch shape): run-to-completion FIFO, not a
        # co-prefill batch that finishes every row late
        if not pod.eng.prefilling_rows:
            pod.eng.admit(max_rows=1)
        n_pf = pod.eng.prefilling_rows
        if n_pf:
            pod.eng.prefill_step()
            pod.clock += prefill_ms * n_pf
        if pod.eng.active_rows:
            events = pod.eng.decode_step()
            pod.clock += step_ms
            trace.record(pod, events)
        if pod.clock > t0:
            pod.busy.append((t0, pod.clock))

    def run_monolithic():
        pods, trace, i = [Pod(), Pod()], Trace(), 0
        while len(trace.done_at) < n_programs:
            working = [p for p in pods if p.eng.pending]
            front = min((p.clock for p in working), default=None)
            while i < n_programs and (front is None
                                      or arrive[i] <= front):
                p = min(pods, key=lambda q: (q.eng.pending, q.clock))
                p.clock = max(p.clock, arrive[i])
                p.rid2idx[p.eng.submit(
                    prompts[i], max_new_tokens=max_new)] = i
                i += 1
                working = [q for q in pods if q.eng.pending]
                front = min(q.clock for q in working)
            mixed_tick(min(working, key=lambda q: q.clock), trace)
        return trace.summarize()

    def run_disagg():
        # same chip, different memory budget: a decode-only pod hosts
        # no prefill activations, so the freed HBM doubles its KV row
        # pool — the consolidation that makes the decode tier's batch
        # (and its bandwidth utilization) worth specializing for
        pf, dc, trace, i = Pod(), Pod(slots=2 * batch), Trace(), 0
        handoffs = collections.deque()     # (ready_ms, idx, state)
        exports = []                       # (t_export, wire_bytes)
        while len(trace.done_at) < n_programs:
            # the prefill pod is an independent device: an idle pod
            # starts the next arrival at the arrival's own timestamp,
            # not at whatever the decode pod is doing
            while i < n_programs and (arrive[i] <= pf.clock
                                      or not pf.eng.pending):
                if not pf.eng.pending:
                    pf.clock = max(pf.clock, arrive[i])
                pf.rid2idx[pf.eng.submit(
                    prompts[i], max_new_tokens=max_new)] = i
                i += 1
            # an idle decode pod waits for the wire, not for prefill
            if (not dc.eng.active_rows and handoffs
                    and handoffs[0][0] > dc.clock):
                dc.clock = handoffs[0][0]
            can_pf = bool(pf.eng.pending)
            can_dc = bool(dc.eng.active_rows) or (
                handoffs and handoffs[0][0] <= dc.clock
                and dc.eng.free_rows)
            if can_pf and (not can_dc or pf.clock <= dc.clock):
                t0 = pf.clock
                if not pf.eng.prefilling_rows:
                    pf.eng.admit(max_rows=1)
                n_pf = pf.eng.prefilling_rows
                activated = []
                if n_pf:
                    activated = pf.eng.prefill_step()
                    pf.clock += prefill_ms * n_pf
                for rid in activated:
                    # export the finished row and free the slot NOW —
                    # the publish overlaps the next rows' prefill
                    idx = pf.rid2idx.pop(rid)
                    state = pf.eng.export_row(rid, block_tokens=16)
                    pf.eng.evict(rid)
                    handoffs.append(
                        (pf.clock + handoff_ms, idx, state))
                    exports.append((pf.clock, tree_bytes(state)))
                if pf.clock > t0:
                    pf.busy.append((t0, pf.clock))
            elif can_dc:
                t0 = dc.clock
                while (handoffs and handoffs[0][0] <= dc.clock
                       and dc.eng.free_rows):
                    _, idx, state = handoffs.popleft()
                    dc.rid2idx[dc.eng.import_row(
                        state, block_tokens=16)] = idx
                if dc.eng.active_rows:
                    events = dc.eng.decode_step()
                    dc.clock += step_ms
                    trace.record(dc, events)
                if dc.clock > t0:
                    dc.busy.append((t0, dc.clock))
            elif i < n_programs:
                pf.clock = max(pf.clock, arrive[i])
            else:
                raise AssertionError("disagg sim stalled")
        # overlap: wire time covered by prefill-pod device activity
        olap = total = 0.0
        for t_e, _ in exports:
            total += handoff_ms
            for b0, b1 in pf.busy:
                if b1 <= t_e:
                    continue
                if b0 >= t_e + handoff_ms:
                    break
                olap += min(b1, t_e + handoff_ms) - max(b0, t_e)
        out = trace.summarize()
        out["overlap"] = olap / total if total else 0.0
        out["bytes"] = _median([b for _, b in exports])
        out["mbu"] = devstats.decode_mbu_proxy(
            dc.decode_tokens, dc.decode_ticks, batch, steps_per_call)
        return out

    mono = run_monolithic()
    dis = run_disagg()
    out = {
        "disagg_programs": n_programs,
        "disagg_handoff_chunks": round(handoff_chunks, 2),
        "disagg_handoff_bytes_p50": dis["bytes"],
        "disagg_handoff_overlap_ratio": round(dis["overlap"], 4),
        "disagg_ttft_p99_ms": round(dis["ttft_p99"], 1),
        "disagg_ttft_p99_ms_mono": round(mono["ttft_p99"], 1),
        "disagg_ttft_p99_ms_vs_monolithic": round(
            dis["ttft_p99"] / mono["ttft_p99"], 4),
        "disagg_tok_s": round(dis["tok_s"], 1),
        "disagg_tok_s_mono": round(mono["tok_s"], 1),
        "disagg_goodput_tok_s": round(dis["goodput"], 1),
        "disagg_goodput_tok_s_mono": round(mono["goodput"], 1),
        "disagg_goodput_ratio": round(
            dis["goodput"] / max(mono["goodput"], 1.0), 4),
        "disagg_decode_mbu_proxy": round(dis["mbu"], 4),
    }
    # the ISSUE 17 acceptance shape, asserted here so a full bench run
    # fails loudly too (the smoke test re-asserts on dryrun output):
    # at equal chip count the disaggregated fleet must win BOTH tails —
    # SLO goodput AND TTFT p99 — with the handoff under a few decode
    # chunks and genuinely overlapped with the next rows' prefill
    assert out["disagg_goodput_ratio"] > 1.0, out
    assert out["disagg_ttft_p99_ms_vs_monolithic"] < 1.0, out
    assert out["disagg_handoff_chunks"] <= 3.0, out
    assert out["disagg_handoff_overlap_ratio"] >= 0.5, out
    return out


def run(dryrun: bool = False, static_tok_s: float = 5673.0) -> dict:
    """Full serving bench. ``dryrun`` (CI smoke) runs only the
    call-tunnel phase at toy sizes — the model phases need a chip-scale
    engine. A full run drives the tunnel phase at the measured rolling
    config (device_ms = the differenced per-chunk device time) so
    ``rolling_tok_s_tunnel_wall_pipelined`` composes phase-1 device
    truth with the measured channel overhead."""
    if dryrun:
        out = bench_call_channel(dryrun=True)
        out.update(bench_engine(dryrun=True))
        out.update(bench_prefix_kv(dryrun=True))
        out.update(bench_engine_spec(dryrun=True))
        out.update(bench_telemetry(dryrun=True))
        out.update(bench_lora(dryrun=True))
        out.update(bench_disagg(dryrun=True))
        return out
    out = bench_8b_rolling(static_tok_s=static_tok_s) or {}
    if out:
        chan = bench_call_channel(
            device_ms=out["ms_per_step_device"] * out["steps_per_call"],
            batch=out["batch"], steps_per_call=out["steps_per_call"],
            n_chunks=40, depth=2)
        out.update(chan)
        # tunnel-wall rate with the pipelined channel on (depth 2); the
        # in-process number (phase 1's med_k) stays as
        # rolling_tok_s_tunnel_wall for cross-round comparability
        out["rolling_tok_s_tunnel_wall_pipelined"] = \
            chan["serving_tok_s_pipelined"]
        # engine phase at phase 1's measured per-chunk device time: the
        # server-resident loop's tunnel rate composes device truth with
        # loop overhead — and asserts the 10% acceptance bar
        out.update(bench_engine(
            step_ms=out["ms_per_step_device"] * out["steps_per_call"],
            batch=min(out["batch"], 16),
            steps_per_call=out["steps_per_call"]))
        # paged-KV phase at the measured per-chunk device time: the
        # prefix-sharing and park/resume numbers compose with phase 1's
        # device truth the same way the engine phase does
        out.update(bench_prefix_kv(
            step_ms=out["ms_per_step_device"] * out["steps_per_call"],
            park_step_ms=out["ms_per_step_device"]
            * out["steps_per_call"]))
        # speculative-scheduling phase at the measured per-chunk device
        # time (the scripted-accept model isolates the SCHEDULER's
        # contribution; bench_rolling_spec measures the device-side
        # acceptance bound of the real model)
        out.update(bench_engine_spec(
            step_ms=out["ms_per_step_device"] * out["steps_per_call"]))
        # fleet telemetry plane cost at full-frame count
        out.update(bench_telemetry())
        # multi-tenant LoRA phase at the measured per-chunk device time:
        # the per-tenant surcharge and cold-load shadowing compose with
        # phase 1's device truth like the other engine phases
        out.update(bench_lora(
            step_ms=out["ms_per_step_device"] * out["steps_per_call"]))
        # disaggregation phase at the measured per-chunk device time
        # (prefill chunks charged at the compute-bound 4x multiple)
        step = out["ms_per_step_device"] * out["steps_per_call"]
        out.update(bench_disagg(step_ms=step, prefill_ms=4.0 * step))
    return out


if __name__ == "__main__":
    import argparse
    import json

    parser = argparse.ArgumentParser(description="kubetorch_tpu serving bench")
    parser.add_argument(
        "--dryrun", action="store_true",
        help="CI smoke: call-tunnel phase only, toy sizes, no model")
    args = parser.parse_args()
    print(json.dumps(run(dryrun=args.dryrun), indent=2))
