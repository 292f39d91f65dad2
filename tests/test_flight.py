"""Flight recorder + device-truth utilization plane (ISSUE 19): the
ring wraps without losing seq continuity and snapshots safely while a
live engine appends; every driver tick leaves a record whose trace ids
join against PR-4 spans; the Perfetto export is valid trace_event JSON;
the MFU/MBU gauges reconcile (±10%) against the devstats totals when
re-weighted by each tick's differenced device time; and the sim engine
exposes the same devstats surface as the real one."""

import json
import os
import threading
import time

import pytest

from kubetorch_tpu.observability import devstats, flight, tracing

# one appender-supplied row (everything after ``seq``): zeros with an
# empty trace-id tuple in the last slot
_ZEROS = tuple([0.0] * (len(flight.FIELDS) - 2)) + ((),)


# ------------------------------------------------------------- ring
@pytest.mark.level("unit")
class TestRing:
    def test_wraparound_keeps_newest_with_seq_continuity(self):
        rec = flight.FlightRecorder(capacity=16)
        for _ in range(40):
            rec.append(*_ZEROS)
        snap = rec.snapshot()
        assert [r["seq"] for r in snap] == list(range(24, 40))
        assert rec.seq == 40
        assert all(set(r) == set(flight.FIELDS) for r in snap)

    def test_since_seq_and_limit(self):
        rec = flight.FlightRecorder(capacity=16)
        for _ in range(10):
            rec.append(*_ZEROS)
        assert [r["seq"] for r in rec.snapshot(since_seq=4)] == [
            5, 6, 7, 8, 9]
        assert [r["seq"] for r in rec.snapshot(limit=3)] == [7, 8, 9]

    def test_append_arity_enforced(self):
        rec = flight.FlightRecorder(capacity=16)
        with pytest.raises(ValueError):
            rec.append(1.0, 2.0)

    def test_incremental_ships_each_record_once(self):
        flight.reset()
        try:
            rec = flight.get_recorder()
            assert rec is not None
            for _ in range(3):
                rec.append(*_ZEROS)
            first = flight.incremental()
            assert [r["seq"] for r in first] == [0, 1, 2]
            assert flight.incremental() is None
            rec.append(*_ZEROS)
            assert [r["seq"] for r in flight.incremental()] == [3]
        finally:
            flight.reset()

    def test_merge_procs_dedupes_overlapping_increments(self):
        a1 = [{"seq": 0, "decode_tokens": 1}, {"seq": 1, "decode_tokens": 2}]
        a2 = [{"seq": 1, "decode_tokens": 2}, {"seq": 2, "decode_tokens": 3}]
        merged = flight.merge_procs([("pod/9", a1), ("pod/9", a2)])
        assert [r["seq"] for r in merged["pod/9"]] == [0, 1, 2]


# ------------------------------------------------------ live engine
def _drain(eng, prompt, n):
    return [t for f in eng.generate({"prompt": prompt,
                                     "max_new_tokens": n})
            for t in f["tokens"]]


@pytest.mark.level("unit")
class TestEngineFlight:
    def test_live_engine_records_and_concurrent_snapshot(self):
        """The driver tick appends one record per tick while a second
        thread snapshots the ring — no tearing, full schema, sane
        host/device decomposition, and the submitting span's trace id
        lands in the records covering the program's lifetime."""
        from kubetorch_tpu.serving.engine import (
            DecodeEngine,
            SimRollingEngine,
        )

        flight.reset()
        eng = DecodeEngine(
            SimRollingEngine(max_slots=4, steps_per_call=8,
                             step_s=0.001), poll_s=0.001)
        rec = flight.get_recorder()
        stop = threading.Event()
        errors = []

        def poll():
            while not stop.is_set():
                try:
                    for r in rec.snapshot(limit=64):
                        assert set(r) == set(flight.FIELDS)
                except Exception as e:  # noqa: BLE001 - collected for the assert below
                    errors.append(e)
                time.sleep(0.0005)

        th = threading.Thread(target=poll)
        th.start()
        try:
            with tracing.span("flight-live") as sp:
                tid = sp.span["trace_id"]
                toks = _drain(eng, [1, 2, 3], 48)
        finally:
            stop.set()
            th.join(10)
            eng.close()
        assert not errors, errors
        assert len(toks) == 48
        snap = rec.snapshot()
        assert snap, "no flight records from a live engine"
        working = [r for r in snap if r["decode_tokens"]]
        assert working, "no working tick recorded"
        assert sum(r["decode_tokens"] for r in working) >= 48
        for r in snap:
            assert r["tick_s"] >= r["device_s"] >= 0.0
            assert r["host_s"] >= 0.0
        assert any(tid in (r["trace_ids"] or ()) for r in snap), (
            "submitting span's trace id never reached the flight ring")
        flight.reset()

    def test_mfu_mbu_gauges_reconcile_with_devstats(self):
        """Re-weighting each tick's published MFU/MBU by that tick's
        differenced device wall must recover the devstats totals:
        sum(util_i * device_s_i * peak) == flops/bytes_total (±10% for
        publish-boundary windows). This catches either side drifting —
        a wall counted twice, a dispatch missed, a stale gauge."""
        from kubetorch_tpu.serving.engine import (
            DecodeEngine,
            SimRollingEngine,
        )

        flight.reset()
        sim = SimRollingEngine(max_slots=4, steps_per_call=8,
                               step_s=0.002)
        eng = DecodeEngine(sim, poll_s=0.001)
        try:
            toks = _drain(eng, [1, 2, 3], 64)
            st = eng.stats()
        finally:
            eng.close()
        assert len(toks) == 64
        assert 0.0 < st["mfu"] <= 1.0
        assert 0.0 < st["mbu"] <= 1.0
        snap = sim.devstats_snapshot()
        peak_flops, peak_bw = sim.devstats_peaks()
        records = flight.get_recorder().snapshot()
        flops_rebuilt = sum(
            r["mfu"] * r["device_s"] * peak_flops
            for r in records if r["mfu"] and r["device_s"])
        bytes_rebuilt = sum(
            r["mbu"] * r["device_s"] * peak_bw
            for r in records if r["mbu"] and r["device_s"])
        assert flops_rebuilt == pytest.approx(
            snap["flops_total"], rel=0.1)
        assert bytes_rebuilt == pytest.approx(
            snap["bytes_total"], rel=0.1)
        assert st["devstats_dispatches"] == snap["dispatches_total"]
        flight.reset()

    def test_preemption_dump_holds_the_ticks_just_run(self, tmp_path,
                                                      monkeypatch):
        """What a terminating pod leaves behind: ``maybe_dump`` writes
        this process's ring into ``KT_FLIGHT_DIR`` as
        ``flight-<pid>.json``, and the file parses back to the driver
        ticks the engine ran; without the directory it writes nothing."""
        from kubetorch_tpu.serving.engine import (
            DecodeEngine,
            SimRollingEngine,
        )

        flight.reset()
        eng = DecodeEngine(
            SimRollingEngine(max_slots=2, steps_per_call=8, step_s=0.0),
            poll_s=0.001)
        try:
            assert len(_drain(eng, [1, 2, 3], 32)) == 32
        finally:
            eng.close()
        monkeypatch.delenv("KT_FLIGHT_DIR", raising=False)
        assert flight.maybe_dump() is None
        monkeypatch.setenv("KT_FLIGHT_DIR", str(tmp_path))
        path = flight.maybe_dump()
        assert path is not None
        assert path.name == f"flight-{os.getpid()}.json"
        report = json.loads(path.read_text())
        assert report["pid"] == os.getpid()
        assert sum(r["decode_tokens"] for r in report["records"]) == 32
        flight.reset()


# --------------------------------------------------------- perfetto
@pytest.mark.level("unit")
class TestPerfetto:
    def test_export_valid_and_trace_ids_join_pr4_spans(self):
        """The merged export is JSON-serializable trace_event data:
        counter tracks for every COUNTER_TRACKS series, one instant per
        tick, None gauge samples skipped (absent, not zero) — and the
        tick's trace_ids resolve against tracing spans exported into
        the same file."""
        with tracing.span("flight-join") as sp:
            tid = sp.span["trace_id"]
        row = dict.fromkeys(flight.FIELDS, 0.0)
        row.update(seq=0, t_wall=time.time(), decode_tokens=8.0,
                   mfu=None, mbu=0.5, trace_ids=(tid,))
        spans = tracing.recorder.snapshot(trace_id=tid)
        extra = tracing.to_trace_events(spans)["traceEvents"]
        out = flight.to_perfetto({"pod-0/123": [row]}, extra_events=extra)
        parsed = json.loads(json.dumps(out))
        events = parsed["traceEvents"]
        counters = [e for e in events if e["ph"] == "C"]
        names = {e["name"] for e in counters}
        assert "mfu" not in names, "None sample must be skipped"
        assert {"mbu", "decode_tokens"} <= names
        ticks = [e for e in events if e["ph"] == "i"]
        assert len(ticks) == 1
        assert ticks[0]["args"]["trace_ids"] == [tid]
        span_ids = {e["args"].get("trace_id") for e in events
                    if e["ph"] == "X"}
        assert tid in span_ids, (
            "flight tick's trace id has no matching span event")

    def test_counter_tracks_cover_every_working_field(self):
        for track in flight.COUNTER_TRACKS:
            assert track in flight.FIELDS


# ------------------------------------------------- devstats surface
@pytest.mark.level("unit")
class TestDevstats:
    def test_peaks_table_and_unknown_kinds(self):
        assert devstats.peaks_for_kind("TPU v5e") == (197e12, 819e9)
        assert devstats.peaks_for_kind("TPU v4") == (275e12, 1228e9)
        assert devstats.peaks_for_kind("cpu") is None
        assert devstats.peaks_for_kind("") is None

    def test_utilization_clamps_and_gates(self):
        assert devstats.utilization(1e12, 1e9, 0.0, (1e12, 1e9)) is None
        assert devstats.utilization(1e12, 1e9, 1.0, None) is None
        mfu, mbu = devstats.utilization(5e11, 5e8, 1.0, (1e12, 1e9))
        assert (mfu, mbu) == (0.5, 0.5)
        mfu, mbu = devstats.utilization(9e12, 9e9, 1.0, (1e12, 1e9))
        assert (mfu, mbu) == (1.0, 1.0)

    def test_analytic_twin_matches_executable_surface(self):
        ana = devstats.AnalyticCosts()
        ana.count(2.0e9, 1.0e9)
        real = devstats.ExecutableCosts()
        assert set(ana.snapshot()) == set(real.snapshot())

    def test_executable_capture_forced_on_cpu(self):
        """force_capture exercises the real lower().compile()
        cost_analysis path without an accelerator (the default skips
        capture when no peaks are known — no gauge could ever publish,
        so the extra compile would buy nothing)."""
        import jax
        import jax.numpy as jnp

        costs = devstats.ExecutableCosts(force_capture=True)
        fn = jax.jit(lambda x: (x * 2.0).sum())
        x = jnp.ones((64, 64), jnp.float32)
        costs.call("toy", 64, fn, x)
        costs.call("toy", 64, fn, x)
        snap = costs.snapshot()
        assert snap["dispatches_total"] == 2.0
        assert snap["captured_executables"] == 1.0
        assert snap["flops_total"] > 0
        assert snap["bytes_total"] > 0
        flops, bytes_ = costs.per_key_costs()[("toy", 64)]
        assert snap["flops_total"] == 2 * flops
        assert snap["bytes_total"] == 2 * bytes_

    def test_capture_skipped_without_peaks(self):
        """The default accumulator on a peak-less host counts
        dispatches but records zero-cost entries without compiling."""
        import jax
        import jax.numpy as jnp

        costs = devstats.ExecutableCosts()
        fn = jax.jit(lambda x: x + 1)
        costs.call("toy", 1, fn, jnp.ones((4,)))
        snap = costs.snapshot()
        assert snap["dispatches_total"] == 1.0
        if devstats.device_peaks() is None:
            assert snap["captured_executables"] == 0.0
            assert snap["flops_total"] == 0.0


@pytest.mark.level("minimal")
def test_real_engine_devstats_surface_parity():
    """The REAL engine (tiny CPU llama) exposes the same devstats
    surface the sim does — snapshot keys identical, dispatches counted
    per jit call — so the utilization plane needs no isinstance
    branches. (CPU cost_analysis availability varies by jaxlib; the
    dispatch counting must not depend on it.)"""
    import jax

    from kubetorch_tpu.models import LlamaConfig, llama
    from kubetorch_tpu.models.rolling import RollingGenerator
    from kubetorch_tpu.serving.engine import SimRollingEngine

    cfg = LlamaConfig(vocab_size=256, embed_dim=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, head_dim=16, mlp_dim=128,
                      remat=False, dtype="float32",
                      param_dtype="float32", max_seq_len=128)
    params = llama.init(jax.random.key(0), cfg)
    eng = RollingGenerator(params, cfg, max_slots=2, max_len=96,
                           steps_per_call=4)
    eng.submit([5, 9, 13, 2], max_new_tokens=8)
    for _ in range(6):
        if not eng.pending:
            break
        eng.step()
    snap = eng.devstats_snapshot()
    sim_snap = SimRollingEngine(max_slots=2).devstats_snapshot()
    assert set(snap) == set(sim_snap)
    assert snap["dispatches_total"] >= 2  # at least prefill + decode
    # peaks: both surfaces answer; CPU answers None (absent-not-zero)
    assert eng.devstats_peaks() is None or len(eng.devstats_peaks()) == 2
    assert SimRollingEngine(max_slots=2).devstats_peaks() == (100e12, 1e12)
