"""ISSUE 17: disaggregated prefill/decode — ship KV, not recompute.

Five layers:

1. **Wire validation**: ``handoff=`` (prefill side) vs ``handoff_id=``
   (decode side) program fields — mutually exclusive, session-free,
   single-prompt, store-key-safe ids.
2. **Geometry guard**: export/import across engines with different
   grid geometry refuses typed, naming BOTH geometries and the exact
   mismatching axis (block size, max_len, lora_slots) — one regression
   test per axis.
3. **Engine-level handoff over the real store**: a prefill-phase
   :class:`DecodeEngine` exports the finished row (zero tokens emitted
   locally, sentinel only after the publish is durable), a decode-phase
   engine imports it and streams byte-identical with NO re-prefill
   (execution count 1); the same-pod relay is the degenerate case, a
   missing blob falls back to monolithic same-pod decode, and the
   decode tier still serves prefix-cache hits tier-local.
4. **Chaos** ``KT_CHAOS=handoff-drop``: the paired decode pod dies
   mid-handoff (seeded, typed-retryable); the import re-routes to a
   second decode pod — the blob is still in the store — and the stream
   is byte-identical.
5. **Controller brokering** (subprocess): ``POST /route/generate``
   phase-aware routing off the fleet rollup's ``engine_phase`` /
   ``engine_row_eta_seconds`` / ``engine_queue_depth`` by-pod gauges —
   prefix hits stay tier-local, stale/excluded pods never route, the
   handoff id is minted once and echoed on re-routes.

The REAL :class:`RollingGenerator` legs (tiny CPU model) pin the
cross-pod handoff token-identical to a monolithic run on both grids:
the int8 grid ships its (q, scale) pairs raw (bit-exact), the bf16
grid takes the int8 wire codec.
"""

import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from kubetorch_tpu.exceptions import (
    KubetorchError,
    KVGeometryMismatch,
    ServerOverloaded,
)
from kubetorch_tpu.observability import prometheus as prom
from kubetorch_tpu.resilience import chaos
from kubetorch_tpu.serving import kvpool
from kubetorch_tpu.serving.engine import (
    DecodeEngine,
    GenerationProgram,
    SimRollingEngine,
    program,
)


@pytest.fixture()
def local_store(tmp_path, monkeypatch):
    """Point the default (local) store at a temp dir — the same
    redirection test_store uses, plus a cleared client singleton so the
    backend is rebuilt against the new root."""
    from kubetorch_tpu.data_store import client as client_mod

    root = tmp_path / "store"
    monkeypatch.setenv("KT_LOCAL_STORE", str(root))
    monkeypatch.setattr(client_mod, "_LOCAL_STORE", root)
    monkeypatch.setattr(client_mod.DataStoreClient, "_default", None)
    yield root


@pytest.fixture(autouse=True)
def _no_leftover_chaos():
    yield
    chaos.install(None)


def _wait(cond, timeout=10.0, what="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


# ------------------------------------------------------ wire validation
@pytest.mark.level("unit")
def test_handoff_wire_validation():
    ok = program([1, 2, 3], max_new_tokens=4, handoff={"id": "h-abc"})
    assert ok["handoff"] == {"id": "h-abc"}
    ok = program([1, 2, 3], max_new_tokens=4, handoff_id="h-abc")
    assert ok["handoff_id"] == "h-abc"
    prog = GenerationProgram.from_wire(
        {"prompt": [1], "max_new_tokens": 2,
         "handoff": {"id": "h-1", "store_url": "http://dc:7100"}})
    assert prog.handoff["store_url"] == "http://dc:7100"
    with pytest.raises(ValueError, match="not both"):
        GenerationProgram.from_wire(
            {"prompt": [1], "max_new_tokens": 2,
             "handoff": {"id": "h-1"}, "handoff_id": "h-1"})
    # a handoff row's lifecycle is a one-shot relay, never a session
    for extra in ({"handoff": {"id": "h-1"}}, {"handoff_id": "h-1"}):
        with pytest.raises(ValueError, match="session_id"):
            GenerationProgram.from_wire(
                {"prompt": [1], "max_new_tokens": 2,
                 "session_id": "s-1", **extra})
    with pytest.raises(ValueError, match="exactly one prompt"):
        GenerationProgram.from_wire(
            {"prompts": [[1], [2]], "max_new_tokens": 2,
             "handoff": {"id": "h-1"}})
    with pytest.raises(ValueError, match="dict"):
        GenerationProgram.from_wire(
            {"prompt": [1], "max_new_tokens": 2, "handoff": "h-1"})
    with pytest.raises(ValueError, match="must match"):
        GenerationProgram.from_wire(
            {"prompt": [1], "max_new_tokens": 2,
             "handoff": {"id": "no spaces!"}})
    with pytest.raises(ValueError, match="store_url"):
        GenerationProgram.from_wire(
            {"prompt": [1], "max_new_tokens": 2,
             "handoff": {"id": "h-1", "store_url": ""}})


# ------------------------------------------------------- geometry guard
def _active_export(sim, prompt, n=8, block_tokens=16):
    rid = sim.submit(prompt, max_new_tokens=n)
    sim.admit()
    return rid, sim.export_row(rid, block_tokens=block_tokens)


@pytest.mark.level("unit")
@pytest.mark.parametrize("axis,imp_kw,imp_bt", [
    ("block_tokens", {}, 32),
    ("max_len", {"max_len": 512}, 16),
    ("lora_slots", {"adapter_slots": 4}, 16),
])
def test_geometry_mismatch_refuses_typed_per_axis(axis, imp_kw, imp_bt):
    """Cross-tier heterogeneity: every geometry axis mismatch refuses
    typed, and the error names BOTH geometries — the operator reads
    which fleet tier is misconfigured straight off the message."""
    exporter = SimRollingEngine(max_slots=1, max_len=256)
    _rid, state = _active_export(exporter, [1, 2, 3], block_tokens=16)
    kw = {"max_len": 256, **imp_kw}
    importer = SimRollingEngine(max_slots=1, **kw)
    with pytest.raises(KVGeometryMismatch) as err:
        importer.import_row(state, block_tokens=imp_bt)
    assert err.value.axis == axis
    assert err.value.exported == {"block_tokens": 16, "max_len": 256,
                                  "lora_slots": 0}
    assert err.value.importer["block_tokens"] == imp_bt
    assert err.value.importer["max_len"] == kw["max_len"]
    assert err.value.importer["lora_slots"] == kw.get("adapter_slots", 0)
    msg = str(err.value)
    # BOTH geometries in the message, plus the mismatching axis
    assert "block_tokens=16" in msg and f"{axis} mismatch" in msg
    assert "exported geometry" in msg and "importing engine" in msg
    # the importer did not burn a row on the refused splice
    assert importer.free_rows == 1


@pytest.mark.level("unit")
def test_geometry_match_imports_and_continues():
    prompt = [4, 7, 11]
    n = 12
    exporter = SimRollingEngine(max_slots=1, max_len=256,
                                steps_per_call=4)
    rid, _ = _active_export(exporter, prompt, n=n)
    first = []
    for r, toks, _done in exporter.decode_step():
        assert r == rid
        first.extend(toks)
    state = exporter.export_row(rid, block_tokens=16)
    exporter.evict(rid)
    importer = SimRollingEngine(max_slots=1, max_len=256,
                                steps_per_call=4)
    rid_b = importer.import_row(state, block_tokens=16)
    rest = []
    while importer.pending:
        for r, toks, _done in importer.decode_step():
            assert r == rid_b
            rest.extend(toks)
    assert first + rest == SimRollingEngine.expected_tokens(prompt, n)


# -------------------------------------- engine-level cross-pod handoff
def _sim_engine(phase, **sim_kw):
    sim_kw.setdefault("max_slots", 2)
    sim_kw.setdefault("steps_per_call", 4)
    sim_kw.setdefault("step_s", 0.001)
    sim = SimRollingEngine(**sim_kw)
    return DecodeEngine(sim, poll_s=0.002, phase=phase), sim


@pytest.mark.level("unit")
def test_cross_pod_handoff_stream_identical_no_reprefill(local_store):
    """The tentpole, engine to engine: prefill pod exports (zero tokens
    emitted locally, sentinel after the publish lands), decode pod
    imports and streams byte-identical — the prompt prefills exactly
    once, on the prefill tier."""
    m0 = prom.engine_metrics()
    pf, sim_pf = _sim_engine("prefill", prefill_chunk=8)
    dc, sim_dc = _sim_engine("decode")
    prompt = list(range(1, 25))           # 24 tokens = 3 prefill chunks
    n = 40
    hid = "h-xpod-1"
    try:
        frames_a = list(pf.generate(
            {"prompt": prompt, "max_new_tokens": n,
             "handoff": {"id": hid}, "tag": "relay"}))
        assert all(f["tokens"] == [] for f in frames_a)
        assert frames_a[-1]["handoff"] is True
        assert frames_a[-1]["handoff_id"] == hid
        assert not frames_a[-1]["done"]
        st_pf = pf.stats()
        assert st_pf["phase"] == "prefill"
        assert st_pf["handoff_exports"] == 1
        assert sim_pf.prefill_tokens == len(prompt)
        assert sim_pf.free_rows == 2      # export freed the row

        frames_b = list(dc.generate(
            {"prompt": prompt, "max_new_tokens": n,
             "handoff_id": hid, "tag": "relay"}))
        toks = [t for f in frames_b for t in f["tokens"]]
        assert toks == SimRollingEngine.expected_tokens(prompt, n)
        assert frames_b[-1]["done"]
        st_dc = dc.stats()
        assert st_dc["phase"] == "decode"
        assert st_dc["handoff_imports"] == 1
        # execution count 1: the decode pod never re-ran the prefill
        assert sim_dc.prefill_tokens == 0
        # the blob is a one-shot relay buffer: dropped after the splice
        _wait(lambda: kvpool.restore_handoff(hid) is None,
              what="handoff blob drop")
        # process-level telemetry moved (merged into /metrics + fleet)
        m1 = prom.engine_metrics()
        assert m1["handoff_exports_total"] - m0.get(
            "handoff_exports_total", 0) == 1
        assert m1["handoff_imports_total"] - m0.get(
            "handoff_imports_total", 0) == 1
        assert m1["handoff_bytes_total"] > m0.get(
            "handoff_bytes_total", 0)
        assert m1["handoff_seconds_total"] > m0.get(
            "handoff_seconds_total", 0)
    finally:
        pf.close()
        dc.close()


@pytest.mark.level("unit")
def test_prefill_phase_rejects_plain_programs():
    pf, _sim = _sim_engine("prefill")
    try:
        assert prom.engine_metrics()["engine_phase"] == 0.0
        with pytest.raises(ValueError, match="prefill-tier"):
            list(pf.generate({"prompt": [1], "max_new_tokens": 2}))
    finally:
        pf.close()


@pytest.mark.level("unit")
def test_same_pod_handoff_is_degenerate_park(local_store):
    """park/resume's one-shot cousin on a single mixed pod: export out,
    import back in, stream identical — the monolithic fallback path."""
    eng, sim = _sim_engine("mixed")
    prompt = [9, 8, 7]
    n = 16
    hid = "h-same-pod"
    try:
        frames = list(eng.generate(
            {"prompt": prompt, "max_new_tokens": n,
             "handoff": {"id": hid}}))
        assert frames[-1]["handoff_id"] == hid
        assert all(f["tokens"] == [] for f in frames)
        frames = list(eng.generate(
            {"prompt": prompt, "max_new_tokens": n, "handoff_id": hid}))
        toks = [t for f in frames for t in f["tokens"]]
        assert toks == SimRollingEngine.expected_tokens(prompt, n)
        st = eng.stats()
        assert st["handoff_exports"] == 1 and st["handoff_imports"] == 1
        assert sim.prefill_tokens == len(prompt)   # prefilled ONCE
    finally:
        eng.close()


@pytest.mark.level("unit")
def test_missing_handoff_falls_back_to_monolithic(local_store,
                                                  monkeypatch):
    """A lost/never-published export must not hang the decode pod: the
    poll times out and the program falls back to a same-pod prefill —
    nothing is lost but the recompute."""
    monkeypatch.setenv("KT_HANDOFF_TIMEOUT_S", "0.05")
    dc, sim = _sim_engine("decode")
    prompt = [2, 4, 6, 8]
    n = 12
    try:
        frames = list(dc.generate(
            {"prompt": prompt, "max_new_tokens": n,
             "handoff_id": "h-never-published"}))
        toks = [t for f in frames for t in f["tokens"]]
        assert toks == SimRollingEngine.expected_tokens(prompt, n)
        assert sim.prefill_tokens == len(prompt)   # local fallback
        assert dc.stats()["handoff_imports"] == 0
    finally:
        dc.close()


@pytest.mark.level("unit")
def test_decode_tier_serves_prefix_hits_tier_local(local_store):
    """Routing invariant's engine half: a decode-phase pod still runs
    suffix prefills, so a full-prefix hit is served tier-local instead
    of bouncing through the prefill tier."""
    dc, sim = _sim_engine("decode")
    try:
        shared = [11, 12, 13, 14]
        pid = dc.register_prefix(shared)
        fill0 = sim.prefill_tokens
        frames = list(dc.generate(
            {"prompt": [15], "max_new_tokens": 8, "prefix_id": pid}))
        toks = [t for f in frames for t in f["tokens"]]
        assert toks == SimRollingEngine.expected_tokens(shared + [15], 8)
        # only the 1-token suffix prefilled — the hit stayed tier-local
        assert sim.prefill_tokens - fill0 == 1
    finally:
        dc.close()


# ------------------------------------------------- chaos: handoff-drop
@pytest.mark.level("unit")
def test_chaos_handoff_drop_reroutes_byte_identical(local_store,
                                                    monkeypatch):
    """Seeded mid-handoff decode-pod drop: the first paired pod raises
    typed-retryable from the import await, the re-route to a SECOND
    decode pod succeeds off the still-durable blob, and the stream is
    byte-identical — execution count stays 1."""
    monkeypatch.setenv("KT_CHAOS", "handoff-drop,max=1")
    pf, sim_pf = _sim_engine("prefill")
    dc1, sim_dc1 = _sim_engine("decode")
    dc2, sim_dc2 = _sim_engine("decode")
    prompt = [3, 1, 4, 1, 5]
    n = 24
    hid = "h-chaos-1"
    try:
        frames = list(pf.generate(
            {"prompt": prompt, "max_new_tokens": n,
             "handoff": {"id": hid}}))
        assert frames[-1]["handoff_id"] == hid
        prog = {"prompt": prompt, "max_new_tokens": n,
                "handoff_id": hid}
        with pytest.raises(ServerOverloaded, match="re-route") as err:
            list(dc1.generate(prog))
        assert err.value.retry_after == 0.0
        assert chaos.active().events == [(chaos.HANDOFF_DROP, hid)]
        # the blob survived the drop — that's what makes re-route safe
        assert kvpool.restore_handoff(hid) is not None
        frames = list(dc2.generate(prog))
        toks = [t for f in frames for t in f["tokens"]]
        assert toks == SimRollingEngine.expected_tokens(prompt, n)
        assert sim_dc1.prefill_tokens == 0
        assert sim_dc2.prefill_tokens == 0     # still no re-prefill
        assert dc2.stats()["handoff_imports"] == 1
    finally:
        pf.close()
        dc1.close()
        dc2.close()


# --------------------------------------- controller phase-aware routing
def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def controller():
    import httpx

    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.controller.server",
         "--host", "127.0.0.1", "--port", str(port), "--db", ":memory:",
         "--reaper-interval", "1.0"],
        env={**os.environ, "KT_CONTROLLER_DB": ":memory:"},
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    url = f"http://127.0.0.1:{port}"
    try:
        for _ in range(200):
            if proc.poll() is not None:
                raise RuntimeError(
                    f"controller exited rc={proc.returncode} early")
            try:
                if httpx.get(f"{url}/health",
                             timeout=2.0).status_code == 200:
                    break
            except httpx.HTTPError:
                pass
            time.sleep(0.2)
        else:
            raise RuntimeError(f"{url}/health never answered 200")
    except RuntimeError:
        proc.kill()
        raise
    yield url
    proc.terminate()
    proc.wait(5)


@pytest.fixture
def client(controller):
    from kubetorch_tpu.controller.client import ControllerClient

    return ControllerClient(controller)


def _pod_frame(client, svc, pod, phase, eta=0.0, queue=0.0, age_s=0.0):
    client.push_telemetry(svc, pod, [{
        "ts": time.time() - age_s,
        "m": {"engine_phase": float(phase),
              "engine_row_eta_seconds": float(eta),
              "engine_queue_depth": float(queue)}}])


@pytest.mark.level("minimal")
def test_route_generate_phase_aware(client):
    svc = "disagg-svc"
    _pod_frame(client, svc, "p-pf", phase=0, queue=1.0)
    _pod_frame(client, svc, "p-pf2", phase=0, queue=3.0)
    _pod_frame(client, svc, "p-dc", phase=1, eta=0.5)
    _pod_frame(client, svc, "p-dc2", phase=1, eta=0.0)
    _pod_frame(client, svc, "p-mx", phase=2, eta=0.1)
    # a STALE decode pod with the best ETA must never route
    _pod_frame(client, svc, "p-dead", phase=1, eta=0.0, age_s=3600.0)

    # prefill AND decode tier live → disagg pairing: prefill by
    # shallowest queue, decode by earliest row-free ETA
    r = client.route_generate(svc)
    assert r["mode"] == "disagg"
    assert r["prefill"] == "p-pf" and r["decode"] == "p-dc2"
    assert r["handoff_id"].startswith("h-")

    # full-prefix hit: the KV already lives tier-local on the decode
    # pod — skip the prefill tier entirely
    r = client.route_generate(svc, prefix_hit=True)
    assert r["mode"] == "decode-only" and r["decode"] == "p-dc2"

    # re-route after a drop: excluded pod never routes, the echoed
    # handoff id never changes (prefill and decode agreed on the key)
    r = client.route_generate(svc, exclude=["p-dc2"],
                              handoff_id="h-keep-me")
    assert r["mode"] == "disagg" and r["decode"] == "p-dc"
    assert r["handoff_id"] == "h-keep-me"

    # decode tier wiped out → monolithic fallback to the mixed pod
    # (a mixed pod can import the still-durable blob)
    r = client.route_generate(svc, exclude=["p-dc", "p-dc2"])
    assert r["mode"] == "monolithic" and r["pod"] == "p-mx"

    # nothing routable → typed 503, not a silent default
    with pytest.raises(KubetorchError, match="no routable pods"):
        client.route_generate(
            svc, exclude=["p-pf", "p-pf2", "p-dc", "p-dc2", "p-mx"])

    with pytest.raises(KubetorchError, match="route needs service"):
        client.route_generate("")


# ------------------------------------- the real rolling engine (jax)
@pytest.fixture(scope="module")
def model():
    import jax

    from kubetorch_tpu.models import LlamaConfig, llama

    cfg = LlamaConfig(vocab_size=256, embed_dim=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, head_dim=16, mlp_dim=128,
                      remat=False, dtype="float32",
                      param_dtype="float32", max_seq_len=128)
    return llama.init(jax.random.key(0), cfg), cfg


def _rolling(model, **kw):
    from kubetorch_tpu.models.rolling import RollingGenerator

    params, cfg = model
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("steps_per_call", 4)
    return RollingGenerator(params, cfg, **kw)


def _mono_stream(model, kv_dtype, prompt, n):
    mono = DecodeEngine(_rolling(model, kv_dtype=kv_dtype),
                        poll_s=0.002)
    try:
        frames = list(mono.generate(
            {"prompt": prompt, "max_new_tokens": n}))
        return [t for f in frames for t in f["tokens"]]
    finally:
        mono.close()


def _prefill_export(model, kv_dtype, prompt, n, hid):
    """Run the prefill-tier half on the real engine: zero tokens
    emitted locally, sentinel after the publish lands. Returns the
    publish's wire stats (valid because the sentinel orders after the
    durable publish)."""
    from kubetorch_tpu.data_store.device_transfer import last_publish_stats

    pf = DecodeEngine(_rolling(model, kv_dtype=kv_dtype),
                      poll_s=0.002, phase="prefill")
    try:
        frames = list(pf.generate(
            {"prompt": prompt, "max_new_tokens": n,
             "handoff": {"id": hid}}))
        assert all(f["tokens"] == [] for f in frames)
        assert frames[-1]["handoff_id"] == hid
        return dict(last_publish_stats())
    finally:
        pf.close()


def _decode_import(model, kv_dtype, prompt, n, hid):
    dc = DecodeEngine(_rolling(model, kv_dtype=kv_dtype),
                      poll_s=0.002, phase="decode")
    try:
        frames = list(dc.generate(
            {"prompt": prompt, "max_new_tokens": n,
             "handoff_id": hid}))
        assert dc.stats()["handoff_imports"] == 1
        return [t for f in frames for t in f["tokens"]]
    finally:
        dc.close()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.level("minimal")
def test_real_cross_pod_handoff_token_identical(model, local_store,
                                                monkeypatch, kv_dtype):
    """The acceptance bar on the REAL engine: prefill on pod A, ship
    the row through the store, decode on pod B — greedy stream
    token-identical to an uninterrupted monolithic run, on both grids.
    The int8 grid's (q, scale) pairs ride the wire raw under the
    default ``auto`` codec (bit-exact handoff at half size); the bf16
    grid's exactness path is ``KT_HANDOFF_CODEC=raw`` (the default
    int8 wire codec trades exactness for bytes — covered separately
    below)."""
    if kv_dtype == "bf16":
        monkeypatch.setenv("KT_HANDOFF_CODEC", "raw")
    prompt = [5, 9, 13, 2]
    n = 24
    hid = f"h-real-{kv_dtype}"
    expected = _mono_stream(model, kv_dtype, prompt, n)
    assert len(expected) == n
    _prefill_export(model, kv_dtype, prompt, n, hid)

    # the published blob's KV leaves kept the grid's storage dtype:
    # int8 planes stay int8 on the wire (raw codec — no double-quant)
    blob = kvpool.restore_handoff(hid)
    assert blob is not None
    if kv_dtype == "int8":
        assert set(blob["kv"]) == {"k", "v", "ks", "vs"}
        blk = np.asarray(next(iter(blob["kv"]["k"].values())))
        assert blk.dtype == np.int8

    toks = _decode_import(model, kv_dtype, prompt, n, hid)
    assert toks == expected, (kv_dtype, toks, expected)


@pytest.mark.level("minimal")
def test_prefill_tier_ships_its_row_with_no_first_frame(model, local_store,
                                                        monkeypatch):
    """ISSUE 38: a prefill-tier pod decodes nothing, so the token its
    admission drew is never read there: the handoff row ships with zero
    tokens emitted locally and ``first_tokens_at_admit`` stays 0 beside
    ``admitted``; the decode tier, which imports the row, sends the first
    frame of its first chunk whole (the row carries logits, no drawn
    token) and the stream is the monolithic one's, whose first frame holds
    the first token alone."""
    monkeypatch.setenv("KT_HANDOFF_CODEC", "raw")
    prompt, n, hid = [5, 9, 13, 2], 10, "h-first-frame"
    mono = DecodeEngine(_rolling(model), poll_s=0.002)
    try:
        whole = list(mono.generate({"prompt": prompt, "max_new_tokens": n}))
    finally:
        mono.close()
    assert [len(f["tokens"]) for f in whole] == [1, 3, 4, 2]
    assert mono.stats()["first_tokens_at_admit"] == 1
    pf = DecodeEngine(_rolling(model), poll_s=0.002, phase="prefill")
    try:
        frames = list(pf.generate({"prompt": prompt, "max_new_tokens": n,
                                   "handoff": {"id": hid}}))
    finally:
        pf.close()
    assert [f["tokens"] for f in frames] == [[]] and frames[0]["handoff"]
    stats = pf.stats()
    assert stats["admitted"] == 1 and stats["first_tokens_at_admit"] == 0
    assert stats["tick_first_sync_n"] == 0 and stats["free_rows"] == 2
    assert not pf.engine._first_pending
    dc = DecodeEngine(_rolling(model), poll_s=0.002, phase="decode")
    try:
        moved = list(dc.generate({"prompt": prompt, "max_new_tokens": n,
                                  "handoff_id": hid}))
    finally:
        dc.close()
    assert [len(f["tokens"]) for f in moved] == [4, 4, 2]
    assert [t for f in moved for t in f["tokens"]] == [
        t for f in whole for t in f["tokens"]]
    assert dc.stats()["first_tokens_at_admit"] == 0 == dc.stats()["admitted"]


@pytest.mark.level("minimal")
def test_real_bf16_handoff_int8_wire_codec(model, local_store,
                                           monkeypatch):
    """The bf16 grid's DEFAULT handoff codec is the int8 wire codec:
    the quantized blob ships far fewer bytes than raw, the decode pod
    still streams a full generation off it with no re-prefill, and the
    first decode chunk matches the monolithic run (the prefilled
    context survived the wire). Full-stream argmax identity is NOT the
    int8 codec's contract — ``KT_HANDOFF_CODEC=raw`` is (covered
    above); on this deliberately tiny random-init model the greedy
    margins are far narrower than any trained checkpoint's, so a late
    token may drift where a real model's would not."""
    prompt = [5, 9, 13, 2]
    n = 24
    expected = _mono_stream(model, "bf16", prompt, n)

    monkeypatch.setenv("KT_HANDOFF_CODEC", "raw")
    raw_stats = _prefill_export(model, "bf16", prompt, n, "h-wire-raw")
    monkeypatch.delenv("KT_HANDOFF_CODEC")
    q_stats = _prefill_export(model, "bf16", prompt, n, "h-wire-int8")
    assert 0 < q_stats["wire_bytes"] < 0.6 * raw_stats["wire_bytes"], (
        q_stats, raw_stats)

    toks = _decode_import(model, "bf16", prompt, n, "h-wire-int8")
    assert len(toks) == n
    assert toks[:4] == expected[:4], (toks, expected)
