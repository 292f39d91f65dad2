"""Headline bench: Llama training + Llama-3-8B serving on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "device", "mfu",
"extra"}. The headline metric is the 0.8B train number; "extra" carries
the north-star rows (BASELINE.md targets #3/#5): Llama-3-8B int8
weight-only decode throughput, and the largest-fitting train config
(~1.5B) with MFU.

Runs on an accelerator only: with no chip it exits non-zero, a failed
phase fails the run, and the peaks come from the ``device_kind`` table in
``observability/devstats.py`` (an unknown kind is an error).
"""

from __future__ import annotations

import json
import os
import sys


def _peaks():
    """(peak FLOP/s, peak HBM bytes/s) of the device this run is on."""
    import jax

    from kubetorch_tpu.observability import devstats

    kind = jax.devices()[0].device_kind
    peaks = devstats.peaks_for_kind(kind)
    if peaks is None:
        raise RuntimeError(f"no peaks known for device_kind {kind!r}")
    return peaks


def _train_flops_per_token(cfg, seq: int) -> float:
    """Matmul model-flops per token, fwd+bwd.

    6·N_matmul for the dense/attention/unembed matmuls (untied embedding
    *lookups* are excluded — counting the [V,E] table twice would flatter
    MFU by ~7% at 128k vocab) plus causal attention's 6·L·S·H·D.
    """
    from kubetorch_tpu.models import llama

    n = llama.num_params(cfg)
    if not cfg.tie_embeddings:
        n -= cfg.vocab_size * cfg.embed_dim
    attn = 6 * cfg.n_layers * seq * cfg.n_heads * cfg.head_dim
    return 6 * n + attn


def _bench_train(cfg, batch, seq, steps, n_dev):
    import jax
    import numpy as np
    import optax

    from kubetorch_tpu.parallel import MeshSpec
    from kubetorch_tpu.training import Trainer

    mesh = MeshSpec(fsdp=-1).build()
    trainer = Trainer(cfg, mesh, optimizer=optax.adamw(1e-4))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
    data = {
        "inputs": jax.numpy.asarray(toks[:, :-1], jax.numpy.int32),
        "targets": jax.numpy.asarray(toks[:, 1:], jax.numpy.int32),
    }
    result = trainer.benchmark(data, n_steps=steps, warmup=2)
    result["tokens_per_sec_per_chip"] = result["tokens_per_sec"] / n_dev
    result["mfu"] = (result["tokens_per_sec_per_chip"]
                     * _train_flops_per_token(cfg, seq) / _peaks()[0])
    result["params"] = trainer.state["params"]
    return result


def _bench_decode(params, cfg, B=8, P=128, N=64):
    """KV-cache generation throughput incl. prefill (stderr detail)."""
    import time

    import numpy as np

    from kubetorch_tpu.models import Generator

    gen = Generator(params, cfg)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, P)).tolist()
    gen.generate(prompts, max_new_tokens=N, temperature=0.8)   # compile
    t0 = time.perf_counter()
    gen.generate(prompts, max_new_tokens=N, temperature=0.8)
    return B * N / (time.perf_counter() - t0)


def _bench_speculative(params, cfg, B=8, k=8):
    """Speculative (prompt-lookup) vs plain greedy decode, steady-state
    per-step costs differenced over two generation lengths so fixed
    per-call costs (prefill, dispatch) cancel."""
    import time

    from kubetorch_tpu.models.generate import Generator
    from kubetorch_tpu.models.speculative import SpeculativeGenerator

    import numpy as np

    gen = Generator(params, cfg)
    seeds = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, 16)).tolist()
    # a looping continuation: greedy rollouts of tiny/random-ish models
    # cycle, giving the n-gram draft something honest to match — the
    # realistic analogue is extractive/code-edit traffic
    warm = gen.generate(seeds, max_new_tokens=96, temperature=0.0)
    prompts = [p + w[:96] for p, w in zip(seeds, warm)]

    def best_of(f, reps=3):
        f()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            best = min(best, time.perf_counter() - t0)
        return best

    tg = [best_of(lambda n=n: gen.generate(
        prompts, max_new_tokens=n, temperature=0.0)) for n in (64, 128)]
    plain_step = (tg[1] - tg[0]) / 64

    spec = SpeculativeGenerator(params, cfg, k=k, ngram=3)
    stats = {}

    def runspec(n):
        _, stats[n] = spec.generate(prompts, max_new_tokens=n,
                                    return_stats=True)

    ts = [best_of(lambda n=n: runspec(n)) for n in (64, 128)]
    rounds = stats[128]["rounds"] - stats[64]["rounds"]
    spec_tok_s = 64 * B / (ts[1] - ts[0])
    return {
        "plain_tok_s": round(B / plain_step, 1),
        "spec_tok_s": round(spec_tok_s, 1),
        "speedup": round(spec_tok_s * plain_step / B, 2),
        "tokens_per_pass": round(64 * B / max(rounds, 1) / B, 2),
        "k": k,
    }


def _bench_weight_sync(cfg):
    """Device→store→device throughput for the full param tree."""
    import time

    import jax

    from kubetorch_tpu.bench_dataplane import _Store
    from kubetorch_tpu.data_store import device_transfer as dt
    from kubetorch_tpu.data_store.client import DataStoreClient
    from kubetorch_tpu.models import llama

    import tempfile
    from pathlib import Path

    params = jax.jit(lambda k: llama.init(k, cfg))(jax.random.key(1))
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))

    # RAM-backed store root when available: this stage measures the
    # framework's pack/wire/unpack path — on a ~100 MB/s VM disk the
    # number otherwise degenerates into a page-cache lottery (0.1-0.8 GB/s
    # run to run for identical code)
    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    tmp = Path(tempfile.mkdtemp(prefix="ktpu-wsync-", dir=base))
    store = _Store(tmp / "root")
    old_env = os.environ.get("KT_STORE_URL")
    os.environ["KT_STORE_URL"] = store.url
    DataStoreClient._default = None
    try:
        import numpy as np

        # Decompose the device→host hop. Model: t(call) = fixed +
        # bytes/wire_bw. Two distinct-size probes solve for both terms;
        # medians of 3. Probes are DEVICE-COMPUTED and fetched ONCE each
        # (distinct arrays per rep): a fetched array caches its host copy,
        # so a re-fetch measures nothing.
        mk = jax.jit(lambda k, n: jax.random.uniform(k, (n,)),
                     static_argnames="n")

        def fetch_time(nelem, keys):
            ts = []
            for k in keys:
                arr = mk(jax.random.key(k), n=nelem)
                jax.block_until_ready(arr)
                t0 = time.perf_counter()
                np.asarray(jax.device_get(arr))
                ts.append(time.perf_counter() - t0)
            return sorted(ts)[len(ts) // 2]

        fetch_time((1 << 20) // 4, [99])           # warm the path
        t_small = fetch_time((1 << 20) // 4, [7, 17, 27])
        t_big = fetch_time((16 << 20) // 4, [8, 18, 28])
        # validity guard, same discipline as every other differencing
        # path: a jitter-inverted pair (t_big <= t_small) must not be
        # reported as a >10 GB/s wire + zero fixed cost
        probe_valid = t_big > 1.05 * t_small
        if probe_valid:
            wire_bps = (16 - 1) * (1 << 20) / (t_big - t_small)
            fixed_s = max(0.0, t_small - (1 << 20) / wire_bps)
        else:
            wire_bps = float("nan")
            fixed_s = float("nan")

        leaves = jax.tree.leaves(params)
        n_leaves = len(leaves)
        # per-leaf staging (the r4 path): n_leaves × fixed + bytes/wire
        t0 = time.perf_counter()
        jax.tree.map(np.asarray, params)
        per_leaf_s = time.perf_counter() - t0
        # chunked staging (device_transfer.device_get_chunked — what
        # put_arrays now uses): O(total/chunk) calls
        t0 = time.perf_counter()
        host_leaves = dt.device_get_chunked(leaves)
        chunked_s = time.perf_counter() - t0
        host = jax.tree.unflatten(jax.tree.structure(params), host_leaves)
        decomp = (f"per-call fixed {fixed_s * 1e3:.0f} ms, small-probe "
                  f"wire {wire_bps / 1e6:.0f} MB/s" if probe_valid else
                  "probe differencing invalid this run (t_big <= "
                  "t_small) — fixed/wire unreported")
        note = (
            f"decomposition: {decomp}; per-leaf "
            f"staging ({n_leaves} fetches) {per_leaf_s:.1f}s vs chunked "
            f"(O(total/256MB) fetches) {chunked_s:.1f}s = "
            f"{per_leaf_s / max(chunked_s, 1e-9):.1f}×")

        # best-of-2: on a 1-CPU host the client and store processes share
        # a core and single-shot timings swing ±3×
        put_s = get_s = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            dt.put_arrays("bench/weights", host)
            put_s = min(put_s, time.perf_counter() - t0)
            t0 = time.perf_counter()
            fetched = dt.get_arrays("bench/weights", template=host)
            get_s = min(get_s, time.perf_counter() - t0)
            del fetched
        return {"param_gb": round(nbytes / 1e9, 2),
                "device_stage_GBps": round(nbytes / 1e9 / chunked_s, 3),
                "device_stage_per_leaf_GBps": round(
                    nbytes / 1e9 / per_leaf_s, 3),
                "stage_fixed_ms_per_call": (round(fixed_s * 1e3, 1)
                                            if probe_valid else None),
                "stage_wire_MBps": (round(wire_bps / 1e6, 1)
                                    if probe_valid else None),
                "stage_n_leaves": n_leaves,
                "store_publish_GBps": round(nbytes / 1e9 / put_s, 2),
                "store_fetch_GBps": round(nbytes / 1e9 / get_s, 2),
                "note": note}
    finally:
        if old_env is None:
            os.environ.pop("KT_STORE_URL", None)
        else:
            os.environ["KT_STORE_URL"] = old_env
        DataStoreClient._default = None
        store.close()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


def _bench_8b_decode(P=128, N=128):
    """Llama-3-8B int8 weight-only decode, steady-state (north star #5).

    Weights are random int8 initialized directly on device (a bf16 8B tree
    is 16 GB and cannot be staged on the chip; values don't affect
    throughput). Timed region: the second call of the compiled decode
    scan, closed by ``block_until_ready``.

    Two variants ride one ladder: **int8 KV cache** (r4 — per-vector
    scales halve the cache stream AND residency, so the batch ceiling
    moves 112 → 192 and tok/s moves 5.65k → 6.6k) as the headline, and
    the bf16-KV B=112 config as the cross-round continuity row.
    """
    import time

    import jax
    import numpy as np

    from kubetorch_tpu.models import Generator, LlamaConfig, quant

    cfg = LlamaConfig.llama3_8b(max_seq_len=1024)
    params = quant.init_quantized(jax.random.key(0), cfg, fuse=True)
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    hbm_bw = _peaks()[1]

    def run_one(b, kv_dtype):
        gen = Generator(params, cfg, kv_dtype=kv_dtype)
        prompts = np.random.default_rng(0).integers(
            1, cfg.vocab_size, (b, P))
        lens = np.full((b,), P, np.int32)
        first_logits, cache = gen._prefill(
            params, jax.numpy.asarray(prompts), jax.numpy.asarray(lens),
            None, max_len=P + N)
        win0 = jax.numpy.asarray(np.full((b, 64), -1, np.int32))
        kw = dict(n_steps=N, temperature=0.8, top_k=None, top_p=None,
                  eos_id=None, pad_id=0, repetition_penalty=1.0)
        args = (params, cache, first_logits, jax.numpy.asarray(lens))
        out, _ = gen._decode(*args, jax.random.key(0), win0, None, **kw)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out, _ = gen._decode(*args, jax.random.key(1), win0, None, **kw)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        emb_bytes = params["embedding"].nbytes
        kv_bytes = sum(x.nbytes for x in jax.tree.leaves(cache))
        avg_fill = (P + N / 2) / (P + N)
        bytes_per_step = (nbytes - emb_bytes) + kv_bytes * avg_fill
        return {"tok_s": b * N / dt, "batch": b, "kv_dtype": kv_dtype,
                "ms_per_step": dt / N * 1e3, "param_gb": nbytes / 1e9,
                "mbu": bytes_per_step / (dt / N) / hbm_bw}

    def ladder(configs):
        """First batch on the ladder that fits. Only an out-of-memory
        error steps down, and the rung it took is in the result; any
        other failure, or no rung fitting, fails the run."""
        for b, kv in configs:
            try:
                return run_one(b, kv)
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                print(f"# 8b decode B={b}/{kv} out of memory; stepping "
                      f"down", file=sys.stderr)
        raise RuntimeError(f"no batch of {configs} fits the chip")

    best = ladder([(192, "int8"), (160, "int8"), (128, "int8"),
                   (96, "int8")])
    # continuity row: the bf16-KV config every prior round reported
    bf16 = ladder([(112, "bf16"), (96, "bf16"), (64, "bf16")])
    best["bf16_kv"] = {k: round(v, 2) if isinstance(v, float) else v
                       for k, v in bf16.items() if k != "param_gb"}
    return best


def _bench_tpu():
    from kubetorch_tpu.config import compile_cache_dir

    # before jax is imported; never overrides the variable when set
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    import jax

    from kubetorch_tpu.models import LlamaConfig

    device = jax.devices()[0]
    if device.platform == "cpu":
        sys.exit("bench.py measures an accelerator and JAX found none; "
                 "a CPU number under a device metric's name helps nobody")
    _peaks()    # an unknown device_kind fails here, before any phase runs
    n_dev = len(jax.devices())

    extra = {}
    # Data plane (store throughput, delta code-sync, broadcast fan-out):
    # localhost protocol numbers — host measurements, not device ones.
    from kubetorch_tpu.bench_dataplane import run as dp_run

    extra["dataplane"] = dp_run()

    # Headline: ~0.8B-param Llama (tied embeddings), fp32-master-free Adam.
    cfg = LlamaConfig(
        vocab_size=32768, embed_dim=2048, n_layers=12, n_heads=16,
        n_kv_heads=8, head_dim=128, mlp_dim=8192, tie_embeddings=True,
        remat=True, remat_policy="dots", dtype="bfloat16",
        param_dtype="bfloat16")
    result = _bench_train(cfg, batch=4, seq=2048, steps=10, n_dev=n_dev)
    params = result.pop("params")
    result["generate_tok_s"] = _bench_decode(params, cfg)
    # Speculative decoding (prompt-lookup drafts, greedy-exact): the
    # small-batch latency lever the wide-batch rows can't touch.
    extra["speculative"] = _bench_speculative(params, cfg)

    # Speculative CONTINUOUS BATCHING at low occupancy (VERDICT r4 #1):
    # 16 slots, int8 grid, looping-continuation traffic — same model as
    # the static spec row above. (The 8B tree can't host this bench in
    # this environment: a random-init 128k-vocab model's greedy
    # continuation never cycles, so prompt-lookup has nothing to match —
    # measured: static AND rolling spec both degrade to 1.0 tokens/pass
    # there. With trained weights the trigger is the traffic, not the
    # model size.)
    from kubetorch_tpu.bench_serving import bench_rolling_spec

    extra["rolling_spec_16slot"] = bench_rolling_spec(
        params, cfg, slots=16, k=8, kv_dtype="int8", P=112, N=384)
    del params

    # Largest-fitting single-chip train config (north star #3 proxy at
    # 1 chip): ~1.5B incl. 128k-vocab untied embeddings, B=4 S=2048 under
    # dots_no_mlp (larger optimizer amortization beats the mlp recompute;
    # grad accumulation OOMs: the f32 grad accumulator can't sit beside
    # adam state).
    big = LlamaConfig.llama3_1b(remat=True, remat_policy="dots_no_mlp",
                                xent_chunk=4096)
    r = _bench_train(big, batch=4, seq=2048, steps=8, n_dev=n_dev)
    r.pop("params")
    extra["llama_1.5b_train_tok_s_per_chip"] = round(
        r["tokens_per_sec_per_chip"], 1)
    extra["llama_1.5b_train_mfu"] = round(r["mfu"], 4)

    # Weight-sync transfer path: device → store → device round trip of
    # the 0.8B bf16 tree through a local store server — the RL
    # weight-publish/fetch primitive.
    extra["weight_sync"] = _bench_weight_sync(cfg)

    # North star #5: Llama-3-8B int8 decode.
    dec = _bench_8b_decode()
    extra["llama3_8b_int8_decode_tok_s"] = round(dec["tok_s"], 1)
    extra["llama3_8b_decode_batch"] = dec["batch"]
    extra["llama3_8b_decode_kv_dtype"] = dec["kv_dtype"]
    extra["llama3_8b_decode_ms_per_step"] = round(dec["ms_per_step"], 2)
    extra["llama3_8b_decode_mbu"] = round(dec["mbu"], 4)
    extra["llama3_8b_param_gb"] = round(dec["param_gb"], 2)
    extra["llama3_8b_decode_bf16_kv"] = dec["bf16_kv"]

    # The serving product: the same 8B model through the continuous-
    # batching engine (RollingGenerator), plus TTFT / request latency
    # under a Poisson load (the static scan above is a ceiling no
    # serving system runs). The engine runs the int8 grid, so the honest
    # vs_static denominator is the int8 static scan.
    from kubetorch_tpu.bench_serving import bench_8b_rolling

    roll = bench_8b_rolling(B=192, kv_dtype="int8", poisson_requests=64,
                            static_tok_s=dec["tok_s"])
    extra["llama3_8b_rolling"] = roll

    # Call-tunnel phase (ISSUE 2): the per-call dispatch tax through the
    # serving path — POST vs persistent channel vs pipelined channel at
    # depth 2 — against a pod-server subprocess whose simulated chunk
    # costs the rolling phase's measured per-chunk device time, so
    # serving_tok_s_pipelined composes that device time with the call
    # path's own cost (reported as rolling_tok_s_tunnel_wall_pipelined).
    from kubetorch_tpu.bench_serving import bench_call_channel

    chan = bench_call_channel(
        device_ms=roll["ms_per_step_device"] * roll["steps_per_call"],
        batch=roll["batch"], steps_per_call=roll["steps_per_call"])
    chan["rolling_tok_s_tunnel_wall_pipelined"] = \
        chan["serving_tok_s_pipelined"]
    extra["serving_call_tunnel"] = chan

    return ("llama_0.8b_train_tokens_per_sec_per_chip",
            result["tokens_per_sec_per_chip"], result, extra,
            {"platform": device.platform, "kind": device.device_kind,
             "count": n_dev})


def main():
    metric, value, detail, extra, device = _bench_tpu()
    out = {
        "metric": metric,
        "value": round(value, 2),
        "unit": "tokens/s/chip",
        "device": device,
        "mfu": round(detail["mfu"], 4),
        "extra": extra,
    }
    print(json.dumps(out))
    print(f"# detail: step_time={detail['step_time_s'] * 1e3:.1f}ms "
          f"loss={detail['loss']:.3f} "
          f"generate={detail['generate_tok_s']:.0f}tok/s", file=sys.stderr)


if __name__ == "__main__":
    main()
