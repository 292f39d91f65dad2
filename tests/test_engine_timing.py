"""ISSUE 24: the serving engine times its own requests and ticks.

One set of ``perf_counter`` stamps in ``DecodeEngine`` is read three
ways, and each way is held here on the CPU: flat counters in
``stats()`` (tick phases, request lifecycle), spans in the ``tracing``
recorder (``engine.admit`` / ``engine.prefill`` / ``engine.step`` with
their old attrs, ``engine.slow_tick`` with the phase split), and
``kt.tick.<phase>`` host events inside a ``jax.profiler`` trace, on the
clock of the device events. The generator's executables carry their
implementations' names.
"""

import glob
import os
import threading
import time

import jax
import pytest

from kubetorch_tpu.models import LlamaConfig, llama
from kubetorch_tpu.models.rolling import RollingGenerator
from kubetorch_tpu.observability import prometheus as prom
from kubetorch_tpu.observability import tracing
from kubetorch_tpu.serving.engine import (
    _LIFE_HISTS,
    _TICK_PHASES,
    DecodeEngine,
    SimRollingEngine,
    program,
)

LOCK, QUEUE, FIRST, TTFT = _LIFE_HISTS


def _toy_generator(**kw):
    cfg = LlamaConfig(vocab_size=256, embed_dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, head_dim=16, mlp_dim=128, remat=False,
                      dtype="float32", param_dtype="float32",
                      max_seq_len=128)
    params = llama.init(jax.random.key(0), cfg)
    return RollingGenerator(params, cfg, max_slots=2, max_len=96,
                            steps_per_call=4, **kw)


def _build(kind):
    if kind == "sim":
        return SimRollingEngine(max_slots=2, steps_per_call=4,
                                step_s=0.002)
    if kind == "sim-chunked":
        return SimRollingEngine(max_slots=2, steps_per_call=4,
                                step_s=0.002, prefill_chunk=4)
    return _toy_generator()


def _run(engine, prompts, n_new=8):
    """Each prompt as its own program, all at once; -> the streams."""
    out = [None] * len(prompts)

    def one(i):
        out[i] = [f for f in engine.generate(
            program(prompts[i], max_new_tokens=n_new))]

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert all(o is not None and o[-1]["done"] for o in out), out
    return out


def _timing(stats):
    return {k: v for k, v in stats.items()
            if k.startswith("tick_") or k in ("ticks", "slow_ticks")
            or k.endswith(("_seconds_sum", "_seconds_count"))}


# ------------------------------------------------- (a) counters in stats()
@pytest.mark.level("minimal")
@pytest.mark.parametrize("kind", ["sim", "sim-chunked", "rolling"])
def test_stats_carry_tick_phases_and_lifecycle(kind):
    """Every phase and every lifecycle pair is in ``stats()``, monotone
    from run to run, ``count`` equal to the programs run, and the three
    parts of a request's time to first token add up to it."""
    engine = DecodeEngine(_build(kind))
    try:
        before = _timing(engine.stats())
        for phase in _TICK_PHASES:
            assert f"tick_{phase}_s" in before and f"tick_{phase}_n" in before
        assert {"ticks", "slow_ticks"} <= set(before)
        prompts = [[1, 2, 3, 4, 5, 6, 7, 8, 9], [4, 5], [7, 7, 7]]
        _run(engine, prompts)
        mid = _timing(engine.stats())
        _run(engine, prompts[:2])
        after = _timing(engine.stats())
    finally:
        engine.close()
    for a, b in ((before, mid), (mid, after)):
        assert set(a) == set(b)
        assert all(b[k] >= a[k] for k in a), (a, b)
    for name in _LIFE_HISTS:
        assert before[f"{name}_count"] == 0
        assert mid[f"{name}_count"] == 3
        assert after[f"{name}_count"] == 5
    parts = sum(after[f"{n}_sum"] for n in (LOCK, QUEUE, FIRST))
    assert abs(parts - after[f"{TTFT}_sum"]) < 5 * 1e-6
    assert after[f"{TTFT}_sum"] > 0
    # a tick that decodes runs each of these once, and the generator's
    # own share of route beside the engine's
    ticks = after["ticks"]
    assert ticks >= 2
    for phase in ("decode_dispatch", "decode_sync", "publish"):
        assert after[f"tick_{phase}_n"] >= ticks
        assert after[f"tick_{phase}_s"] > 0
    assert after["tick_route_n"] >= 2 * ticks
    assert 1 <= after["tick_admit_n"] <= 5
    assert after["tick_handover_s"] > 0
    if kind == "sim-chunked":
        assert after["tick_prefill_n"] >= 2      # the 9-token prompt
    # the sim's device time is its sleep: it lands in the blocking read
    if kind != "rolling":
        assert after["tick_decode_sync_s"] >= 0.002 * ticks * 0.9
        assert after["tick_decode_dispatch_s"] < after["tick_decode_sync_s"]


@pytest.mark.level("minimal")
@pytest.mark.parametrize("kind", ["sim", "rolling"])
def test_lifecycle_parts_sum_per_request(kind, monkeypatch):
    """lock wait + queue wait + admit-to-first == ttft for EVERY request
    to a microsecond, as observed into the histogram family with the
    submitting call's trace id as exemplar."""
    seen = []
    real = prom.record_hist

    def spy(name, value, buckets=None, trace_id=None):
        seen.append((name, value, trace_id))
        return real(name, value, buckets=buckets, trace_id=trace_id)

    monkeypatch.setattr(prom, "record_hist", spy)
    engine = DecodeEngine(_build(kind))
    try:
        with tracing.span("client.call") as sp:
            trace_id = sp.context[0]
            assert list(engine.generate(program(
                [1, 2, 3], max_new_tokens=8)))[-1]["done"]
        _run(engine, [[3, 2, 1], [5, 6]])
    finally:
        engine.close()
    by = {n: [v for name, v, _ in seen if name == n] for n in _LIFE_HISTS}
    assert [len(v) for v in by.values()] == [3, 3, 3, 3]
    for lock, queue, first, ttft in zip(*(by[n] for n in _LIFE_HISTS)):
        assert min(lock, queue, first) >= 0
        assert abs(lock + queue + first - ttft) < 1e-6
    # the first program ran under a span: each of its four observations
    # carries that trace id; _run's bare threads have none
    assert [t for _, _, t in seen[:4]] == [trace_id] * 4
    assert {t for _, _, t in seen[4:]} == {None}


@pytest.mark.level("minimal")
def test_restored_row_stamps_the_same_record(tmp_path, monkeypatch):
    """A parked session resumed by a second program is admitted by its
    import: it observes the lifecycle too, with no queue wait."""
    monkeypatch.setenv("KT_LOCAL_STATE", str(tmp_path))
    sim = SimRollingEngine(max_slots=2, steps_per_call=2, step_s=0.005)
    engine = DecodeEngine(sim)
    try:
        frames = []
        gen = engine.generate(program([1, 2, 3], max_new_tokens=64,
                                      session_id="s-timing"))
        frames.append(next(gen))
        assert engine.park("s-timing") == 1
        frames.extend(gen)
        assert frames[-1].get("parked")
        q0 = engine.stats()[f"{QUEUE}_sum"]
        out = [f for f in engine.generate(program(
            [1, 2, 3], max_new_tokens=64, session_id="s-timing"))]
        assert out[-1]["done"]
        st = engine.stats()
    finally:
        engine.close()
    assert st["restores"] == 1
    assert st[f"{TTFT}_count"] == 2
    assert st[f"{QUEUE}_sum"] - q0 < 1e-3      # the import IS the admission


# ------------------------------------------------------ (b) a slow tick
class _SleepsOnce(SimRollingEngine):
    """A generator whose blocking read stalls once."""

    stall_s = 0.0

    def decode_step(self):
        if self.stall_s and self._rows:
            self.step_s, self.stall_s, keep = self.stall_s, 0.0, self.step_s
            try:
                return super().decode_step()
            finally:
                self.step_s = keep
        return super().decode_step()


@pytest.mark.level("minimal")
def test_slow_tick_counted_and_named():
    tracing.recorder.clear()
    sim = _SleepsOnce(max_slots=2, steps_per_call=1, step_s=0.01)
    engine = DecodeEngine(sim)
    try:
        _run(engine, [[1, 2, 3]], n_new=16)          # the median settles
        slow0 = engine.stats()["slow_ticks"]
        sim.stall_s = 0.4
        _run(engine, [[4, 5, 6]], n_new=8)
        slow1 = engine.stats()["slow_ticks"]
    finally:
        engine.close()
    spans = [s for s in tracing.recorder.snapshot()
             if s["name"] == "engine.slow_tick"]
    # every slow tick leaves exactly one span (a loaded CI host may add
    # one of its own; the stalled one is told by its split)
    assert slow1 == len(spans) and slow1 - slow0 >= 1
    stalled = [s for s in spans
               if s["attrs"].get("decode_sync", 0.0) >= 0.39]
    assert len(stalled) == 1
    attrs = stalled[0]["attrs"]
    # its number, and its class: the stall is in the program's first chunk,
    # in the tick that admitted it (the sim names no bucket)
    assert attrs["class"] == "admit"
    split = {k: v for k, v in attrs.items() if k not in ("tick", "class")}
    assert max(split, key=split.get) == "decode_sync"
    assert set(split) <= set(_TICK_PHASES)
    assert stalled[0]["dur"] >= 0.39
    assert abs(sum(v for k, v in split.items() if k != "idle")
               - stalled[0]["dur"]) < 0.005


# ----------------------------------- (c) phases in the profiler's trace
@pytest.mark.level("minimal")
def test_phases_are_host_events_of_the_profilers_trace(tmp_path):
    """Under ``jax.profiler`` the phases lie in a host plane of the SAME
    ``.xplane.pb`` as the backend's execution events, each inside its
    ``kt.tick`` step."""
    from jax.profiler import ProfileData

    engine = DecodeEngine(_toy_generator())
    try:
        _run(engine, [[1, 2, 3]], n_new=4)           # compile outside
        jax.profiler.start_trace(str(tmp_path))
        try:
            _run(engine, [[1, 2, 3, 4], [9, 8]], n_new=8)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.close()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    host = planes["/host:CPU"]
    driver, executed = [], 0
    for line in host.lines:
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats)) for e in line.events]
        if any(name == "kt.tick" for name, *_ in events):
            driver.append(events)
        # the CPU backend's execution events live in this plane too
        executed += sum("_decode_impl" in name or "_prefill_impl" in name
                        for name, *_ in events)
    assert len(driver) == 1, "all ticks on the one driver thread's line"
    assert executed >= 1
    events = driver[0]
    ticks = [(s, e) for name, s, e, _ in events if name == "kt.tick"]
    steps = [st["step_num"] for name, _, _, st in events
             if name == "kt.tick"]
    assert len(ticks) >= 2 and steps == sorted(set(steps))
    for phase in ("admit", "decode_dispatch", "decode_sync", "route",
                  "publish"):
        inside = [(s, e) for name, s, e, _ in events
                  if name == f"kt.tick.{phase}"]
        assert inside, phase
        for s, e in inside:
            assert any(t0 <= s and e <= t1 for t0, t1 in ticks), phase
    # handover is between ticks, never inside one
    for name, s, e, _ in events:
        if name == "kt.tick.handover":
            assert not any(t0 < s and e < t1 for t0, t1 in ticks)
    marks = {name: [st.get("rid") for n, _, _, st in events if n == name]
             for name in ("kt.req.admit", "kt.req.first_frame")}
    assert len(marks["kt.req.admit"]) == 2
    assert sorted(marks["kt.req.admit"]) == sorted(
        marks["kt.req.first_frame"])


# ------------- (c') the first frame leaves before the chunk's read ends
def _first_frame_engine(decoder):
    from test_rolling import _toy_engine

    return DecodeEngine(_toy_engine(decoder, max_slots=2))


@pytest.mark.level("minimal")
@pytest.mark.parametrize("decoder", ["dense", "latent", "hybrid"])
def test_first_frame_is_marked_before_the_chunks_read_ends(decoder,
                                                           tmp_path):
    """ISSUE 38: in the tick that admits a row, the read of the token its
    admission drew (``kt.tick.first_sync``) follows the decode chunk's
    dispatch and precedes the chunk's own read, and the row's
    ``kt.req.first_frame`` mark lies between the two reads: the frame has
    left when the driver starts to wait for the chunk."""
    from jax.profiler import ProfileData

    engine = _first_frame_engine(decoder)
    try:
        _run(engine, [[1, 2, 3]], n_new=4)           # compile outside
        jax.profiler.start_trace(str(tmp_path))
        try:
            _run(engine, [[1, 2, 3, 4], [9, 8]], n_new=8)
        finally:
            jax.profiler.stop_trace()
        stats = engine.stats()
    finally:
        engine.close()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    (events,) = [ev for ev in (
        [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        for line in planes["/host:CPU"].lines)
        if any(name == "kt.tick" for name, *_ in ev)]
    ticks = [(s, e) for name, s, e in events if name == "kt.tick"]
    marks = [s for name, s, _ in events if name == "kt.req.first_frame"]
    assert len(marks) == 2

    def inside(tick, name):
        return [(s, e) for n, s, e in events
                if n == name and tick[0] <= s and e <= tick[1]]

    for mark in marks:
        (tick,) = [t for t in ticks if t[0] <= mark <= t[1]]
        (dispatch,) = inside(tick, "kt.tick.decode_dispatch")
        (first,) = inside(tick, "kt.tick.first_sync")
        (sync,) = inside(tick, "kt.tick.decode_sync")
        assert inside(tick, "kt.tick.admit")
        # never between the two dispatches, always ahead of the chunk's read
        assert dispatch[1] <= first[0] and first[1] <= mark <= sync[0]
    assert stats["first_tokens_at_admit"] == stats["admitted"] == 3
    assert stats["admitted_rows"] == 3
    assert stats["tick_first_sync_n"] >= 2


@pytest.mark.level("minimal")
@pytest.mark.parametrize("decoder", ["dense", "latent", "hybrid"])
def test_first_frame_holds_the_first_token_alone(decoder):
    """The stream of a program: one token, then what is left of the first
    chunk, then whole chunks; a request of one token finishes on its first
    frame and its row is free when the stream ends; and the tokens are those
    of the generator driven by hand."""
    from test_rolling import _toy_engine

    by_hand = _toy_engine(decoder, max_slots=2)
    rids = [by_hand.submit(p, max_new_tokens=n)
            for p, n in (([1, 2, 3, 4], 10), ([9, 8], 1))]
    want = by_hand.run()
    engine = _first_frame_engine(decoder)
    try:
        long, short = _run(engine, [[1, 2, 3, 4]], n_new=10)[0], _run(
            engine, [[9, 8]], n_new=1)[0]
    finally:
        engine.close()
    # read once the driver is gone: the short stream ended on its first
    # frame, in the middle of the tick that admitted it
    stats = engine.stats()
    assert [len(f["tokens"]) for f in long] == [1, 3, 4, 2]
    assert [f["done"] for f in long] == [False, False, False, True]
    assert [t for f in long for t in f["tokens"]] == want[rids[0]]
    assert [(f["tokens"], f["done"]) for f in short] == [
        (want[rids[1]], True)]
    assert stats["free_rows"] == 2 and stats["pending"] == 0
    assert stats["tokens"] == 11
    assert stats["first_tokens_at_admit"] == stats["admitted"] == 2
    # both ticks that admitted dispatched a chunk, and count as steps
    assert stats["ticks"] == stats["tick_decode_dispatch_n"] == 4


# ------------------------------------------ (d) executables carry names
@pytest.mark.level("minimal")
def test_jitted_attributes_are_named_for_their_implementations():
    gen = _toy_generator(spec_k=2)
    jitted = {"_prefill": "_prefill_impl", "_decode": "_decode_impl",
              "_prefix_fill": "_prefix_fill_impl",
              "_prefill_px": "_prefill_px_impl",
              "_prefill_ext": "_prefill_extend_impl",
              "_decode_sp": "_decode_spec_impl",
              "_ctx_admit": "_ctx_admit_impl"}
    for attr, impl in jitted.items():
        assert getattr(gen, attr).__name__ == impl, attr
    # what the trace's ``XLA Modules`` line prints is the module's name
    import jax.numpy as jnp

    ctx = jnp.zeros((2, 8), jnp.int32)
    text = gen._ctx_admit.lower(ctx, ctx[:1], jnp.zeros(
        (1,), jnp.int32)).as_text()
    assert "module @jit__ctx_admit_impl" in text
    plain = _toy_generator()
    plain.submit([1, 2, 3], max_new_tokens=2)
    plain.admit()
    lowered = plain._decode.lower(
        plain.params, plain.cache, plain._logits, plain._dpos,
        plain._dactive, plain._dnt, plain._dnt_valid,
        jnp.asarray(plain._temps),
        jnp.asarray(plain._penalties), jnp.asarray(plain._win),
        plain._draw_key(), None, top_k=plain.top_k, top_p=plain.top_p,
        n_steps=plain.steps_per_call).as_text()
    assert "module @jit__decode_impl" in lowered
    assert "unknown" not in lowered.splitlines()[0]


# ----------------------------- (f) the three spans, with their old attrs
@pytest.mark.level("minimal")
def test_engine_spans_keep_their_names_and_attrs():
    tracing.recorder.clear()
    engine = DecodeEngine(SimRollingEngine(
        max_slots=2, steps_per_call=4, step_s=0.001, prefill_chunk=4))
    try:
        _run(engine, [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10], [4, 5]])
    finally:
        engine.close()
    spans = {}
    for s in tracing.recorder.snapshot():
        spans.setdefault(s["name"], []).append(s)
    assert {"engine.admit", "engine.prefill", "engine.step"} <= set(spans)
    assert sum(s["attrs"]["rows"] for s in spans["engine.admit"]) == 2
    assert all(set(s["attrs"]) == {"rows"} for s in spans["engine.prefill"])
    for s in spans["engine.step"]:
        assert set(s["attrs"]) == {"rows", "tokens"}
        assert 1 <= s["attrs"]["rows"] <= 2 and s["attrs"]["tokens"] >= 1
        assert s["dur"] >= 0.001 * 0.9
    assert sum(s["attrs"]["tokens"] for s in spans["engine.step"]) == 16
    assert "engine.slow_tick" not in spans or len(
        spans["engine.slow_tick"]) <= 2


@pytest.mark.level("unit")
def test_nested_phase_time_is_exclusive():
    """A wait booked inside a phase is the wait's, not the phase's."""
    from kubetorch_tpu.serving.engine import _TickTimer

    timer = _TickTimer()
    with timer("evict"):
        time.sleep(0.01)
        with timer("evict_sync"):
            time.sleep(0.03)
    st = timer.stats()
    assert 0.03 <= st["tick_evict_sync_s"] < 0.05
    assert 0.01 <= st["tick_evict_s"] < 0.03
    assert st["tick_evict_n"] == st["tick_evict_sync_n"] == 1
    assert timer.open is None


# ---------------- (f) the second decoder's counters, held to a hand count
@pytest.mark.level("minimal")
def test_expert_counters_equal_a_hand_count():
    """``moe_*`` ride ``RollingGenerator.stats()`` into
    ``DecodeEngine.stats()``: pairs computed = tokens x top_k x expert
    layers (prompt tokens counted at admission, one a live row a decode
    step, the steps a finished row idles to its chunk's end included);
    touched <= slots = expert layers x steps x experts; cache positions
    fetched >= positions live."""
    from kubetorch_tpu.models import LatentMoEConfig, latent_moe

    cfg = LatentMoEConfig.tiny()
    params = latent_moe.init(jax.random.key(0), cfg)
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=96,
                           steps_per_call=4)
    eng = DecodeEngine(gen, poll_s=0.002)
    try:
        prompts, n_new = [[3, 1, 4, 1, 5, 9, 2], [2, 7, 1, 8]], 8
        _run(eng, prompts, n_new=n_new)
        s = eng.stats()
    finally:
        eng.close()
    layers, steps = cfg.n_moe_layers, s["steps"] * 4
    # every row decodes n_new tokens = 2 chunks of 4: both rows live in
    # every step they were dispatched in
    decoded = 2 * n_new
    assert s["moe_assignments"] == (
        sum(len(p) for p in prompts) + decoded) * cfg.top_k * layers
    assert s["moe_expert_slots"] == layers * steps * cfg.n_experts
    assert 0 < s["moe_experts_touched"] <= s["moe_expert_slots"]
    # a step's rows choose top_k experts each: at most rows x top_k touched
    assert s["moe_experts_touched"] <= decoded * cfg.top_k * layers
    assert s["moe_group_max"] >= layers * steps    # some expert, every step
    assert s["decode_kv_positions_read"] >= s["decode_kv_positions_live"] > 0
    assert s["kv_position_bytes"] == cfg.n_layers * 128 * 4
