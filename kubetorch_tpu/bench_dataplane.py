"""Data-plane microbenchmarks on the HOST: store throughput, delta
code-sync, broadcast-tree fan-out, streamed restore, wire codecs.

A host measurement and nothing else: everything here is CPU/localhost,
and what it times is protocol overhead (delta manifests, rolling-join
tree, HTTP framing, codec passes), not the NIC and never the chip. No
cell of ``BENCHMARK.json`` covers these paths; a number from here is not
a device number and is not written under the name of one. Run directly
(``python -m kubetorch_tpu.bench_dataplane [--dryrun]``).

The reference's comparable pitch is rsync-delta code sync + NCCL/fs
broadcast (``data_store/rsync_client.py``, ``pod_data_server.py``); it
ships no numbers for either (BASELINE.md), so these rows establish the
targets.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


REPS = 5


def _spread(samples, key: str, out: Dict[str, float], scale=1.0,
            invert=False):
    """Record median + [min, max] for a repeated measurement
    (single-shot numbers make regressions unfalsifiable on a shared
    host). ``invert``: samples are durations but the reported
    metric is a rate (min duration → max rate)."""
    xs = sorted(samples)
    med = xs[len(xs) // 2]
    lo, hi = xs[0], xs[-1]
    if invert:
        out[key] = round(scale / med, 1)
        out[f"{key}_spread"] = [round(scale / hi, 1), round(scale / lo, 1)]
    else:
        out[key] = round(med * scale, 1)
        out[f"{key}_spread"] = [round(lo * scale, 1), round(hi * scale, 1)]


def _timed(f) -> float:
    t0 = time.perf_counter()
    f()
    return time.perf_counter() - t0


class _Store:
    """A throwaway store-server subprocess."""

    def __init__(self, root: Path):
        import httpx

        self.port = _free_port()
        self.proc = subprocess.Popen(
            [sys.executable, "-m",
             "kubetorch_tpu.data_store.store_server",
             "--host", "127.0.0.1", "--port", str(self.port),
             "--root", str(root)],
            # host-only child of a parent that may hold the chip
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
        self.url = f"http://127.0.0.1:{self.port}"
        for _ in range(100):
            try:
                if httpx.get(f"{self.url}/health",
                             timeout=2.0).status_code == 200:
                    return
            except httpx.HTTPError:
                pass
            time.sleep(0.1)
        self.close()  # don't leak the subprocess on startup failure
        raise RuntimeError("store server did not start")

    def stats(self) -> Dict:
        import httpx

        return httpx.get(f"{self.url}/stats", timeout=5.0).json()

    def close(self):
        self.proc.terminate()
        self.proc.wait(5)


def bench_blob_throughput(store: "_Store", mb: int = 32,
                          reps: int = REPS) -> Dict[str, float]:
    from kubetorch_tpu.data_store.http_store import HttpStoreBackend

    be = HttpStoreBackend(store.url)
    blob = os.urandom(mb * 1024 * 1024)
    puts, gets = [], []
    got = None
    for _ in range(reps):
        puts.append(_timed(lambda: be.put_blob("bench/blob.bin", blob)))

        def _get():
            nonlocal got
            got = be.get_blob("bench/blob.bin")

        gets.append(_timed(_get))
    assert got == blob
    out: Dict[str, float] = {}
    _spread(puts, "blob_put_MBps", out, scale=mb, invert=True)
    _spread(gets, "blob_get_MBps", out, scale=mb, invert=True)
    return out


def _make_repo_tree(root: Path, n_files: int = 300):
    """A code-repo-shaped tree: many small files, a few larger ones."""
    rng = __import__("random").Random(0)
    for i in range(n_files):
        sub = root / f"pkg{i % 12}"
        sub.mkdir(parents=True, exist_ok=True)
        size = 2_000 if i % 20 else 80_000
        (sub / f"mod{i}.py").write_bytes(
            bytes(rng.getrandbits(8) for _ in range(size)))


def bench_code_sync(store: "_Store", n_files: int = 300,
                    reps: int = REPS) -> Dict[str, float]:
    """Cold upload of a ~300-file tree vs warm re-sync after a one-file
    edit — the delta property that makes the deploy loop fast."""
    from kubetorch_tpu.data_store.http_store import HttpStoreBackend

    be = HttpStoreBackend(store.url)
    cold, warm, pull_cold, pull_warm = [], [], [], []
    with tempfile.TemporaryDirectory() as td:
        src = Path(td) / "proj"
        src.mkdir()
        _make_repo_tree(src, n_files=n_files)
        for i in range(reps):
            # cold: a fresh store key per rep (the delta protocol would
            # make a same-key re-upload warm by design)
            cold.append(_timed(
                lambda i=i: be.put_path(f"bench/proj{i}", src)))
            (src / "pkg0" / f"mod{i}.py").write_bytes(b"EDITED = 1\n")
            warm.append(_timed(
                lambda i=i: be.put_path(f"bench/proj{i}", src)))
        # download direction: cold clone vs no-op re-pull
        with tempfile.TemporaryDirectory() as dd:
            for i in range(reps):
                pull_cold.append(_timed(
                    lambda i=i: be.get_path("bench/proj0",
                                            Path(dd) / f"clone{i}")))
                pull_warm.append(_timed(
                    lambda i=i: be.get_path("bench/proj0",
                                            Path(dd) / f"clone{i}")))
    out: Dict[str, float] = {}
    _spread(cold, "codesync_cold_ms", out, scale=1e3)
    _spread(warm, "codesync_warm_ms", out, scale=1e3)
    _spread(pull_cold, "codepull_cold_ms", out, scale=1e3)
    _spread(pull_warm, "codepull_warm_ms", out, scale=1e3)
    return out


def bench_broadcast(store: "_Store", world: int = 8,
                    mb: int = 16, reps: int = REPS) -> Dict[str, float]:
    """8 peers fetching the same blob: rolling-join broadcast tree
    (fanout 2) vs everyone hammering the store directly. The ratio that
    matters is store egress — the tree keeps it O(fanout), direct is
    O(world)."""
    from kubetorch_tpu.data_store.http_store import HttpStoreBackend
    from kubetorch_tpu.data_store.types import BroadcastWindow

    be = HttpStoreBackend(store.url)
    payload = os.urandom(mb * 1024 * 1024)
    be.put_blob("bench/bcast.bin", payload)

    def fan_out(fetch) -> float:
        errors = []

        def worker(i):
            try:
                fetch(HttpStoreBackend(store.url), i)
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        # ktlint: disable=KT002 -- bench load generator: no ambient ctx
        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(world)]  # daemon: a hung fetch must not
        #                                    block interpreter shutdown
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        if any(t.is_alive() for t in threads):
            raise TimeoutError(
                "broadcast fan-out worker hung past 120s — refusing to "
                "report a fabricated wall time")
        if errors:
            raise errors[0]
        return (time.perf_counter() - t0) * 1e3

    direct_times, direct_egresses = [], []
    for _ in range(reps):
        out0 = store.stats()["bytes_out"]
        direct_times.append(
            fan_out(lambda b, i: b.get_blob("bench/bcast.bin")))
        direct_egresses.append(store.stats()["bytes_out"] - out0)
    direct_egress = sorted(direct_egresses)[len(direct_egresses) // 2]

    # per-worker cache roots: each worker simulates its own pod — a shared
    # root would let the O_EXCL fetch-dedup collapse the tree into one
    # download + 7 local cache hits and measure nothing network-shaped
    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    cache_base = Path(tempfile.mkdtemp(prefix="ktpu-bcast-cache-", dir=base))

    def bcast_fetch(key, expect, rep):
        def fetch(b, i):
            window = BroadcastWindow(
                world_size=world, fanout=2, timeout=120,
                cache_root=str(cache_base / f"rep{rep}-peer{i}"))
            got = b.get_blob(key, broadcast=window)
            if len(got) != expect:
                raise AssertionError(f"peer {i}: {len(got)} bytes")
        return fetch

    # warmup: spin up the 8 peer servers + connections on a small key so
    # the measured run sees steady-state (production peers are long-lived)
    be.put_blob("bench/bcast-warm.bin", os.urandom(1 << 20))
    fan_out(bcast_fetch("bench/bcast-warm.bin", 1 << 20, rep="w"))

    bcast_times, bcast_egresses = [], []
    for rep in range(reps):
        # fresh KEY + cache roots per rep: with a reused key the next
        # rep's peers find the previous rep's still-warm peer caches and
        # the store sees zero egress — measuring nothing network-shaped
        key = f"bench/bcast-r{rep}.bin"
        be.put_blob(key, payload)
        out0 = store.stats()["bytes_out"]
        bcast_times.append(fan_out(bcast_fetch(key, len(payload), rep)))
        bcast_egresses.append(store.stats()["bytes_out"] - out0)
    bcast_egress = sorted(bcast_egresses)[len(bcast_egresses) // 2]

    # Relay-tax isolation: same 2 peers, same bytes —
    # once with the adaptive direct policy (world ≤ direct_below → both
    # pull from the store), once with the tree forced (fanout 1: rank 1
    # relays through rank 0). The delta is the pure per-hop relay cost on
    # this host, separated from fan-out effects.
    def two_peer(key, direct: bool) -> float:
        be.put_blob(key, payload)
        errors = []

        def worker(i):
            try:
                window = BroadcastWindow(
                    world_size=2, timeout=120,
                    fanout=(2 if direct else 1),
                    direct_below=(4 if direct else 0),
                    cache_root=str(cache_base / f"tp{int(direct)}-{i}"))
                got = HttpStoreBackend(store.url).get_blob(
                    key, broadcast=window)
                if len(got) != len(payload):
                    raise AssertionError(f"2peer {i}: {len(got)} bytes")
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        # ktlint: disable=KT002 -- bench load generator: no ambient ctx
        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(2)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        if any(t.is_alive() for t in threads):
            raise TimeoutError(
                "2-peer fetch hung past 120s — refusing to report a "
                "fabricated wall time")
        if errors:
            raise errors[0]
        return (time.perf_counter() - t0) * 1e3

    two_direct = [two_peer(f"bench/bcast-2d{r}.bin", direct=True)
                  for r in range(reps)]
    two_relay = [two_peer(f"bench/bcast-2r{r}.bin", direct=False)
                 for r in range(reps)]
    shutil.rmtree(cache_base, ignore_errors=True)
    out: Dict[str, float] = {}
    _spread(direct_times, "bcast_direct_ms", out)
    _spread(bcast_times, "bcast_tree_ms", out)
    out["bcast_direct_egress_mb"] = round(direct_egress / 1e6, 1)
    out["bcast_tree_egress_mb"] = round(bcast_egress / 1e6, 1)
    out["bcast_egress_ratio"] = round(
        direct_egress / max(1, bcast_egress), 2)
    _spread(two_direct, "bcast_2peer_direct_ms", out)
    _spread(two_relay, "bcast_2peer_relay_ms", out)
    out["bcast_relay_tax_ms"] = round(
        out["bcast_2peer_relay_ms"] - out["bcast_2peer_direct_ms"], 1)
    return out


def _restore_tree(total_mb: float = 64.0, n_leaves: int = 64):
    """A param-tree-shaped pytree of host arrays: many leaves, mixed
    dtypes, a few dominating large ones (like a real transformer stack)."""
    import numpy as np

    rng = np.random.default_rng(0)
    total = int(total_mb * (1 << 20))
    big = total // 2
    tree = {"layers": {}, "head": {}}
    n_emb = max(64, (big // 4) // 64 * 64)  # float32 elems, 64-col rows
    tree["head"]["emb"] = rng.random(n_emb).astype(
        np.float32).reshape(-1, 64)
    left = total - tree["head"]["emb"].nbytes
    per = max(1024, left // max(1, n_leaves - 1))
    for i in range(n_leaves - 1):
        dt = (np.float32, np.int8, np.float16)[i % 3]
        n = max(64, per // np.dtype(dt).itemsize)
        tree["layers"][f"w{i}"] = (rng.integers(-5, 5, n).astype(dt)
                                   if dt is np.int8
                                   else rng.random(n).astype(dt))
    return tree


def bench_restore(store: "_Store", total_mb: float = 64.0,
                  reps: int = REPS) -> Dict[str, float]:
    """The weight-sync restore decomposition: raw fetch wire rate, the
    blocking fetch-then-place path, and the streaming pipelined path
    (get_blob_stream → iter_unpack → batched device_put), with the
    fetch/placement overlap ratio. The streamed path should sit within
    ~1.3× of raw fetch time — placement hidden under the wire — where the
    blocking path pays fetch + place serially."""
    import jax

    from kubetorch_tpu.data_store.client import DataStoreClient
    from kubetorch_tpu.data_store.device_transfer import (
        get_arrays,
        last_restore_stats,
        put_arrays,
    )
    from kubetorch_tpu.data_store.http_store import HttpStoreBackend

    tree = _restore_tree(total_mb)
    total_bytes = sum(a.nbytes for a in jax.tree.leaves(tree))
    prev_url, prev_default = (os.environ.get("KT_STORE_URL"),  # ktlint: disable=KT003 -- save/restore of raw env state, not a config read
                              DataStoreClient._default)
    os.environ["KT_STORE_URL"] = store.url
    DataStoreClient._default = None
    try:
        put_arrays("bench/restore-tree", tree)
        be = HttpStoreBackend(store.url)
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])

        fetches = [_timed(lambda: be.get_blob("bench/restore-tree"))
                   for _ in range(reps)]
        blocking, streamed, overlaps, place_s = [], [], [], []
        for _ in range(reps):
            blocking.append(_timed(lambda: get_arrays(
                "bench/restore-tree", template=tree, shardings=sharding,
                streaming=False)))
            # batch ≈ total/8: ~8 pipelined placement batches regardless
            # of workload size, so fetch/place overlap is visible even at
            # dryrun sizes (the default 64 MB batch targets multi-GB
            # weight trees)
            streamed.append(_timed(lambda: get_arrays(
                "bench/restore-tree", template=tree, shardings=sharding,
                streaming=True, chunk_bytes=max(1 << 20, total_bytes // 16),
                batch_bytes=max(1 << 20, total_bytes // 8))))
            stats = last_restore_stats()
            overlaps.append(stats.get("overlap_ratio", 0.0))
            place_s.append(max(1e-9, stats.get("place_s", 0.0)))
    finally:
        if prev_url is None:
            os.environ.pop("KT_STORE_URL", None)
        else:
            os.environ["KT_STORE_URL"] = prev_url
        DataStoreClient._default = prev_default
    out: Dict[str, float] = {}
    gb = total_bytes / 1e9
    _spread(fetches, "restore_fetch_GBps", out, scale=gb, invert=True)
    _spread(blocking, "restore_blocking_ms", out, scale=1e3)
    _spread(streamed, "restore_streamed_ms", out, scale=1e3)
    out["restore_place_GBps"] = round(
        gb / (sorted(place_s)[len(place_s) // 2]), 2)
    out["restore_overlap_ratio"] = round(
        sorted(overlaps)[len(overlaps) // 2], 3)
    out["restore_speedup"] = round(
        out["restore_blocking_ms"] / max(1e-9, out["restore_streamed_ms"]),
        2)
    # streamed wall vs the raw wire floor (target: ≤ ~1.3×)
    out["restore_vs_wire_ratio"] = round(
        (out["restore_streamed_ms"] / 1e3)
        / max(1e-9, sorted(fetches)[len(fetches) // 2]), 2)
    return out


def _weight_sync_tree(total_mb: float, lora_frac: float = 0.005):
    """A weight-sync-shaped float32 tree: a big frozen backbone (the bulk
    of the bytes) plus small LoRA-style adapter leaves (~0.5%) — the
    blob the codec/delta layer exists for."""
    import numpy as np

    rng = np.random.default_rng(0)
    total = int(total_mb * (1 << 20))
    lora_bytes = max(8192, int(total * lora_frac))
    backbone = total - lora_bytes
    tree = {"backbone": {}, "lora": {}}
    n_bb = 8
    for i in range(n_bb):
        rows = max(1, backbone // n_bb // 4 // 64)
        tree["backbone"][f"w{i}"] = rng.standard_normal(
            (rows, 64)).astype(np.float32)
    for i in range(4):
        rows = max(1, lora_bytes // 4 // 4 // 64)
        tree["lora"][f"a{i}"] = rng.standard_normal(
            (rows, 64)).astype(np.float32)
    return tree


def bench_codec(store: "_Store", total_mb: float = 64.0,
                reps: int = REPS) -> Dict[str, float]:
    """Wire-bytes decomposition of the quantized delta codec on the
    weight-sync blob: raw vs int8 wire bytes (the ≥2× reduction), codec
    encode/decode rates, and the delta publish/fetch path — a LoRA-only
    update must ship <1% of the full blob's bytes in BOTH directions,
    with the delta counters proving unchanged leaves were skipped."""
    import jax
    import numpy as np

    from kubetorch_tpu.data_store.client import DataStoreClient
    from kubetorch_tpu.data_store.device_transfer import (
        get_arrays,
        last_publish_stats,
        last_restore_stats,
        put_arrays,
    )

    tree = _weight_sync_tree(total_mb)
    raw_bytes = sum(a.nbytes for a in jax.tree.leaves(tree))
    raw_mb = raw_bytes / 1e6
    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    cache_dir = tempfile.mkdtemp(prefix="ktpu-restore-cache-", dir=base)
    prev_env = {k: os.environ.get(k)
                for k in ("KT_STORE_URL", "KT_RESTORE_CACHE")}
    prev_default = DataStoreClient._default
    os.environ["KT_STORE_URL"] = store.url
    os.environ["KT_RESTORE_CACHE"] = cache_dir
    DataStoreClient._default = None
    out: Dict[str, float] = {}
    try:
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])

        # raw vs int8 wire bytes on the same blob
        put_arrays("bench/codec-raw", tree, codec="raw")
        get_arrays("bench/codec-raw", template=tree, shardings=sharding,
                   streaming=True)
        wire_raw = last_restore_stats()["wire_bytes"]
        encode, decode, streamed = [], [], []
        for _ in range(reps):
            put_arrays("bench/codec-int8", tree, codec="int8")
            encode.append(max(1e-9, last_publish_stats()["encode_s"]))
            streamed.append(_timed(lambda: get_arrays(
                "bench/codec-int8", template=tree, shardings=sharding,
                streaming=True)))
            decode.append(max(1e-9,
                              last_restore_stats()["codec_decode_s"]))
        stats = last_restore_stats()
        wire_int8 = stats["wire_bytes"]
        out["restore_wire_bytes_raw_mb"] = round(wire_raw / 1e6, 2)
        out["restore_wire_bytes_int8_mb"] = round(wire_int8 / 1e6, 2)
        out["restore_wire_reduction_int8"] = round(
            wire_raw / max(1, wire_int8), 2)
        _spread(streamed, "restore_int8_streamed_ms", out, scale=1e3)
        out["codec_int8_encode_MBps"] = round(
            raw_mb / sorted(encode)[len(encode) // 2], 1)
        out["codec_int8_decode_MBps"] = round(
            raw_mb / sorted(decode)[len(decode) // 2], 1)
        out["codec_int8_dequant_ms"] = round(
            stats.get("dequant_s", 0.0) * 1e3, 2)

        # delta publish/fetch: full round, then LoRA-only updates
        put_arrays("bench/codec-delta", tree, codec="int8", delta=True)
        out["delta_publish_full_mb"] = round(
            last_publish_stats()["wire_bytes"] / 1e6, 2)
        get_arrays("bench/codec-delta", template=tree, shardings=sharding,
                   delta=True)  # populates the restore cache (miss)
        upd_pub, upd_fetch, skipped = [], [], []
        rng = np.random.default_rng(1)
        for _ in range(reps):
            for name in tree["lora"]:
                tree["lora"][name] = (
                    tree["lora"][name]
                    + rng.standard_normal(1).astype(np.float32))
            put_arrays("bench/codec-delta", tree, codec="int8",
                       delta=True)
            pub = last_publish_stats()
            upd_pub.append(pub["wire_bytes"])
            skipped.append(pub["leaves_skipped"])
            get_arrays("bench/codec-delta", template=tree,
                       shardings=sharding, delta=True)
            fs = last_restore_stats()
            if fs.get("delta_hit") != 1.0:
                raise AssertionError(
                    "delta fetch missed with a warm restore cache")
            upd_fetch.append(fs["wire_bytes"])
        out["delta_publish_update_mb"] = round(
            sorted(upd_pub)[len(upd_pub) // 2] / 1e6, 3)
        out["delta_publish_update_pct"] = round(
            100.0 * sorted(upd_pub)[len(upd_pub) // 2] / raw_bytes, 2)
        out["delta_publish_leaves_skipped"] = sorted(
            skipped)[len(skipped) // 2]
        out["delta_fetch_wire_mb"] = round(
            sorted(upd_fetch)[len(upd_fetch) // 2] / 1e6, 3)
        out["delta_fetch_hit"] = 1.0
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        DataStoreClient._default = prev_default
        shutil.rmtree(cache_dir, ignore_errors=True)
    return out


def bench_collectives(store: "_Store", steps: int = 20,
                      n_grad_elems: int = 1 << 22,
                      reps: int = REPS) -> Dict[str, float]:
    """The PR-18 train-plane wire diet, measured end to end: the int8
    dcn ring's bytes-on-wire reduction vs the f32 schedule (floor >= 2x,
    smoke-asserted), the f32-vs-int8 ``Trainer.step`` loss-trajectory
    delta on a dcn=2 mesh, the block-quantize/dequantize kernel rates
    that bound the ring's compute tax, and the delta-aware broadcast's
    patch bytes vs the full blob. The mesh parts need >= 2 (even) jax
    devices — CI's virtual 8-CPU mesh or real hardware; on a 1-device
    host only the kernel + broadcast rows are emitted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubetorch_tpu.models.quant import block_dequantize, block_quantize
    from kubetorch_tpu.observability.prometheus import record_collective
    from kubetorch_tpu.parallel import MeshSpec
    from kubetorch_tpu.parallel import collectives as coll

    out: Dict[str, float] = {}
    block = coll.dcn_block()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(n_grad_elems), jnp.float32)
    mb = x.nbytes / 1e6

    # codec kernel rates (jitted, sync'd) — the compute the ring spends
    # to earn its wire reduction; fed into the live counters so the
    # quant/dequant seconds totals are exercised the same way the
    # trainer feeds the byte counters
    qfn = jax.jit(lambda v: block_quantize(v, block))
    q, s = jax.block_until_ready(qfn(x))
    dfn = jax.jit(lambda q, s: block_dequantize(q, s, block))
    jax.block_until_ready(dfn(q, s))
    quant = [_timed(lambda: jax.block_until_ready(qfn(x)))
             for _ in range(reps)]
    dequant = [_timed(lambda: jax.block_until_ready(dfn(q, s)))
               for _ in range(reps)]
    _spread(quant, "coll_quant_MBps", out, scale=mb, invert=True)
    _spread(dequant, "coll_dequant_MBps", out, scale=mb, invert=True)
    record_collective({"quant_s": sum(quant), "dequant_s": sum(dequant)})

    ndev = jax.device_count()
    if ndev >= 2 and ndev % 2 == 0:
        mesh = MeshSpec(dcn=2, fsdp=ndev // 2).build()
        stacked = {"g": x.reshape(2, -1)}
        summed, stats = coll.dcn_ring_allreduce(stacked, mesh,
                                                block=block, seed=1)
        want = np.asarray(x.reshape(2, -1).sum(axis=0))
        got = np.asarray(summed["g"])
        out["coll_ring_rel_err"] = round(
            float(np.abs(got - want).max() / np.abs(want).max()), 5)
        out["coll_dcn_wire_reduction"] = round(stats.reduction, 2)
        record_collective({"dcn_bytes": stats.wire_bytes,
                           "dcn_raw_bytes": stats.raw_bytes})

        # f32 vs int8 loss trajectories through the real Trainer — the
        # quantized ring must be invisible in training quality. Uses the
        # same tiny config as tests/test_collectives.py so CI shares the
        # persistent XLA compile cache.
        import optax

        from kubetorch_tpu.models import LlamaConfig
        from kubetorch_tpu.training.trainer import Trainer

        cfg = LlamaConfig(vocab_size=512, embed_dim=64, n_layers=2,
                          n_heads=4, n_kv_heads=4, head_dim=16,
                          mlp_dim=128)
        brng = np.random.default_rng(0)
        B, S = 8, 32
        batches = []
        for _ in range(steps):
            toks = brng.integers(0, cfg.vocab_size, (B, S + 1))
            batches.append(
                {"inputs": jnp.asarray(toks[:, :-1], jnp.int32),
                 "targets": jnp.asarray(toks[:, 1:], jnp.int32)})
        prev_codec = os.environ.get("KT_COLL_DCN_CODEC")  # ktlint: disable=KT003 -- save/restore of raw env state, not a config read
        losses = {}
        try:
            for codec in ("f32", "int8"):
                os.environ["KT_COLL_DCN_CODEC"] = codec  # ktlint: disable=KT003 -- bench toggles the knob per run
                tmesh = MeshSpec(dcn=2, fsdp=ndev // 2).build()
                tr = Trainer(cfg, tmesh, optimizer=optax.adamw(1e-3),
                             seed=0)
                losses[codec] = np.asarray(
                    [float(jax.device_get(tr.step(b)["loss"]))
                     for b in batches])
        finally:
            if prev_codec is None:
                os.environ.pop("KT_COLL_DCN_CODEC", None)  # ktlint: disable=KT003
            else:
                os.environ["KT_COLL_DCN_CODEC"] = prev_codec  # ktlint: disable=KT003
        out["coll_loss_equiv_delta"] = round(
            float(np.abs(losses["f32"] - losses["int8"]).max()), 5)
        out["coll_loss_equiv_steps"] = steps
    return out


def bench_delta_broadcast(store: "_Store",
                          tree_elems: int = 65536) -> Dict[str, float]:
    """Changed-leaf broadcast: re-fetch a re-put 6-leaf tree with one
    changed leaf and measure store egress for the patch vs the full
    blob — the delta fetch must ship a fraction of the bytes."""
    import numpy as np

    from kubetorch_tpu.data_store import device_transfer as dt
    from kubetorch_tpu.data_store.client import DataStoreClient
    from kubetorch_tpu.data_store.http_store import HttpStoreBackend
    from kubetorch_tpu.data_store.types import BroadcastWindow

    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    cache = Path(tempfile.mkdtemp(prefix="ktpu-delta-bcast-", dir=base))
    prev_env = {k: os.environ.get(k)  # ktlint: disable=KT003 -- save/restore of raw env state, not a config read
                for k in ("KT_STORE_URL", "KT_WIRE_DELTA")}
    prev_default = DataStoreClient._default
    os.environ["KT_STORE_URL"] = store.url
    os.environ["KT_WIRE_DELTA"] = "1"
    DataStoreClient._default = None
    out: Dict[str, float] = {}
    try:
        tree = {f"w{i}": np.random.default_rng(i)
                .standard_normal(tree_elems).astype(np.float32)
                for i in range(6)}
        dt.put_arrays("bench/coll-delta", tree)
        backend = HttpStoreBackend(store.url)

        def fetch():
            window = BroadcastWindow(world_size=1, fanout=1, timeout=60,
                                     serve=False, cache_root=str(cache))
            return bytes(backend.get_blob("bench/coll-delta",
                                          broadcast=window))

        out0 = store.stats()["bytes_out"]
        full = fetch()
        out["bcast_delta_full_mb"] = round(
            (store.stats()["bytes_out"] - out0) / 1e6, 3)

        tree["w3"] = tree["w3"] + 1.0  # one changed leaf of six
        dt.put_arrays("bench/coll-delta", tree)
        out0 = store.stats()["bytes_out"]
        patched = fetch()
        out["bcast_delta_wire_mb"] = round(
            (store.stats()["bytes_out"] - out0) / 1e6, 3)
        if patched == full:
            raise AssertionError("delta re-fetch returned stale bytes")
        if patched != bytes(backend.get_blob("bench/coll-delta")):
            raise AssertionError("spliced bytes differ from store blob")
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        DataStoreClient._default = prev_default
        shutil.rmtree(cache, ignore_errors=True)
    return out


def run(dryrun: bool = False) -> Dict[str, float]:
    """Full data-plane bench; ``dryrun=True`` is the CI smoke shape — the
    same code paths (including the streaming pipelined restore) at toy
    sizes and 1 rep, emitting the same metric KEYS so a key that vanishes
    (a silently-dropped measurement) fails the smoke test."""
    from kubetorch_tpu.observability import tracing

    # RAM-backed when available: measure the data plane, not the VM disk
    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    tmp = Path(tempfile.mkdtemp(prefix="ktpu-dpbench-", dir=base))
    store = None
    reps = 1 if dryrun else REPS
    trace_seq0 = tracing.recorder.seq
    try:
        store = _Store(tmp / "root")
        out: Dict[str, float] = {}
        out.update(bench_blob_throughput(store, mb=(2 if dryrun else 32),
                                         reps=reps))
        out.update(bench_code_sync(store, n_files=(40 if dryrun else 300),
                                   reps=reps))
        out.update(bench_broadcast(store, world=(3 if dryrun else 8),
                                   mb=(1 if dryrun else 16), reps=reps))
        out.update(bench_restore(store, total_mb=(8 if dryrun else 64),
                                 reps=reps))
        out.update(bench_codec(store, total_mb=(8 if dryrun else 64),
                               reps=reps))
        out.update(bench_collectives(
            store, steps=(6 if dryrun else 20),
            n_grad_elems=(1 << 20 if dryrun else 1 << 22), reps=reps))
        out.update(bench_delta_broadcast(
            store, tree_elems=(4096 if dryrun else 65536)))
    finally:
        if store is not None:
            store.close()
        shutil.rmtree(tmp, ignore_errors=True)
    # tracing cost accounting: spans the restore/publish paths recorded
    # during the bench plus the measured per-span overhead (the smoke
    # test key-guards both — a silently un-instrumented dataplane would
    # otherwise look identical to a healthy one)
    out["trace_span_count"] = tracing.recorder.seq - trace_seq0
    out["trace_overhead_us_per_span"] = round(
        tracing.measure_overhead_us(), 3)
    return out


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(
        description="kubetorch_tpu data-plane microbenchmarks")
    parser.add_argument(
        "--dryrun", action="store_true",
        help="CI smoke: same code paths at toy sizes / 1 rep (stable "
             "metric keys, throwaway values)")
    args = parser.parse_args()
    if args.dryrun:
        # keep the smoke off any accelerator: the restore bench imports
        # jax, and the point here is the protocol, not the chip
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps(run(dryrun=args.dryrun), indent=2))
