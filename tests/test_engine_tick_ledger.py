"""ISSUE 37: the tick accounts for itself.

The generator says what it dispatched where it dispatches
(``RollingGenerator._dispatch`` -> the engine's ``dispatched`` hook; the sim
calls the same hook from its modelled admit / prefill / decode), so
``_TickTimer`` files every tick under one class by what it held and sums,
by phase, the host seconds in which the device had nothing to do. Held
here on the CPU: counts and identities, and sleeps where a time is wanted;
never a device number.
"""

import glob
import os
import time

import jax
import pytest

from kubetorch_tpu.serving.engine import (
    _CLASS_FIELDS,
    _IDLE,
    _NOT_STARVED,
    _TICK_PHASES,
    DecodeEngine,
    SimRollingEngine,
    _TickTimer,
)
from test_engine_timing import _build, _run, _toy_generator, program

STARVED = [name for i, name in enumerate(_TICK_PHASES)
           if i not in _NOT_STARVED]


def _classes(stats):
    """{class: {field: value}} out of the flat ``tick_class_*`` keys."""
    out = {}
    for key, value in stats.items():
        if key.startswith("tick_class_"):
            name, field = key[len("tick_class_"):].split("_", 1)
            out.setdefault(name, {})[field] = value
    return out


# --------------------------------- every key is there from the construction
@pytest.mark.level("minimal")
@pytest.mark.parametrize("kind, buckets", [
    ("sim", [16, 32, 64, 128, 256, 512, 1024, 2048]),   # max_len 2048
    ("sim-chunked", [16, 32, 64, 128, 256, 512, 1024, 2048]),
    ("rolling", [16, 32, 64, 128]),     # max_len 96: a 70-token prompt's
])
def test_every_class_and_starved_key_is_born_at_zero(kind, buckets):
    """``serve_cell.delta`` keeps a key only if both snapshots hold it: a
    class born inside the window would vanish from the window's delta."""
    engine = DecodeEngine(_build(kind))
    try:
        stats = engine.stats()
    finally:
        engine.close()
    classes = _classes(stats)
    assert set(classes) == {"empty", "plain", "chunk", "admit"} | {
        f"b{b}" for b in buckets}
    for name, fields in classes.items():
        assert set(fields) == set(_CLASS_FIELDS), name
        assert all(v == 0 for v in fields.values()), (name, fields)
    assert STARVED == ["evict", "admit", "prefill", "handoff",
                       "decode_dispatch", "route", "publish", "handover"]
    for phase in STARVED:
        assert stats[f"tick_starved_{phase}_s"] == 0.0
    assert stats["tick_starved_s"] == 0.0
    for phase in ("evict_sync", "handoff_sync", "first_sync", "decode_sync",
                  "idle"):
        assert f"tick_starved_{phase}_s" not in stats


# --------------------------- every tick is in one class, with all its wall
@pytest.mark.level("minimal")
@pytest.mark.parametrize("kind", ["sim", "sim-chunked", "rolling"])
def test_classes_partition_the_ticks_and_their_wall(kind):
    engine = DecodeEngine(_build(kind))
    timer = engine._timer
    t_built = timer._t_end
    try:
        _run(engine, [[1, 2, 3, 4, 5, 6, 7, 8, 9], [4, 5], [7, 7, 7]])
        time.sleep(0.02)                            # an idle stretch between
        _run(engine, [[3, 1, 4], [1, 5]])
    finally:
        engine.close()
    stats = engine.stats()
    classes = _classes(stats)
    assert sum(c["n"] for c in classes.values()) == timer.started >= 4
    # a tick's wall is the time since the last tick's end, idle taken out
    total = timer._t_end - t_built - timer._base[_IDLE]
    assert abs(sum(c["wall_s"] for c in classes.values()) - total) < 1e-6
    assert sum(c["tokens"] for c in classes.values()) == stats["tokens"] == 40
    # every wait for the device, the read of the admissions' first tokens
    # (behind the chunk's dispatch) among them
    sync = sum(stats[f"tick_{p}_s"] for p in (
        "decode_sync", "first_sync", "evict_sync", "handoff_sync"))
    assert abs(sum(c["sync_s"] for c in classes.values()) - sync) < 1e-6
    if kind == "rolling":
        assert stats["tick_first_sync_n"] >= 2       # ticks that admitted
        assert stats["first_tokens_at_admit"] == stats["admitted"] == 5
    else:
        assert stats["tick_first_sync_n"] == 0       # a sim draws none
    # starved seconds: by phase and by class, one sum (the stretch after
    # the last tick's blocking read is in no tick yet)
    by_phase = sum(stats[f"tick_starved_{p}_s"] for p in STARVED)
    assert abs(by_phase - stats["tick_starved_s"]) < 1e-9
    by_class = sum(c["starved_s"] for c in classes.values())
    assert 0 < by_class <= stats["tick_starved_s"] + 1e-9
    for c in classes.values():
        assert 0 <= c["starved_s"] <= c["wall_s"] + 1e-9
        assert 0 <= c["sync_s"] <= c["wall_s"] + 1e-9


# --------------------------------------------- which class a tick is filed in
@pytest.mark.level("minimal")
@pytest.mark.parametrize("prompt_len, held, ticks", [
    (3, "b16", 1),          # one bucketed admission a program
    (40, "b64", 1),
    (60, "chunk", 2),       # over prefill_chunk 48: two chunk dispatches
])
def test_rolling_generator_files_ticks_by_bucket_and_chunk(prompt_len, held,
                                                           ticks):
    engine = DecodeEngine(_toy_generator(prefill_chunk=48))
    try:
        _run(engine, [list(range(1, prompt_len + 1))], n_new=8)
    finally:
        engine.close()
    classes = _classes(engine.stats())
    assert classes[held]["n"] == ticks
    # the row's first chunk of 4 tokens is decoded in the tick that made it
    # decode-active; the second in a plain tick
    assert classes[held]["tokens"] == 4
    assert classes["plain"]["n"] == 1 and classes["plain"]["tokens"] == 4
    others = set(classes) - {held, "plain", "empty"}
    assert all(classes[name]["n"] == 0 for name in others)


@pytest.mark.level("minimal")
@pytest.mark.parametrize("chunk, held", [(None, "admit"), (4, "chunk")])
def test_sim_files_ticks_through_the_same_hook(chunk, held):
    """The sim names no bucket: its one-shot admissions are ``admit``."""
    engine = DecodeEngine(SimRollingEngine(
        max_slots=2, steps_per_call=4, step_s=0.001, prefill_chunk=chunk))
    try:
        _run(engine, [[1, 2, 3, 4, 5, 6]], n_new=8)
    finally:
        engine.close()
    classes = _classes(engine.stats())
    assert classes[held]["n"] == (1 if chunk is None else 2)
    assert classes["plain"]["n"] == 1
    assert all(c["n"] == 0 for name, c in classes.items()
               if name not in (held, "plain", "empty"))


# ------------------------------------- the starved seconds, phase by phase
def _tick(timer, admit_s=None, dispatch_s=0.0, route_s=0.0):
    """One hand-driven tick in the order ``_tick_locked`` runs it."""
    with timer:
        with timer("evict"):
            pass
        if admit_s is not None:
            with timer("admit"):
                time.sleep(admit_s)
                timer.dispatched("prefill", (1, 64))
                time.sleep(admit_s)         # after the dispatch: not starved
        with timer("decode_dispatch"):
            time.sleep(dispatch_s)
            timer.dispatched("decode", 4)
        with timer("decode_sync"):
            time.sleep(0.002)
        with timer("route"):
            time.sleep(route_s)
    with timer("idle"):
        time.sleep(0.01)
    with timer("handover"):
        pass


@pytest.mark.level("unit")
@pytest.mark.parametrize("admits", [False, True])
def test_starved_seconds_end_at_the_dispatch(admits):
    timer = _TickTimer(64)
    _tick(timer)                                       # ends with a dry queue
    before = timer.stats()
    _tick(timer, admit_s=0.01 if admits else None, dispatch_s=0.02,
          route_s=0.03)
    after = timer.stats()
    grew = {p: after[f"tick_starved_{p}_s"] - before[f"tick_starved_{p}_s"]
            for p in STARVED}
    took = {p: after[f"tick_{p}_s"] - before[f"tick_{p}_s"] for p in STARVED}
    if admits:
        # the prefill's dispatch ended the stretch half way through admit
        # (the sleep after it is not counted), and the decode chunk was
        # dispatched onto a busy device
        assert 0.01 <= grew["admit"] <= took["admit"] - 0.01
        assert grew["decode_dispatch"] == 0.0
        held = "tick_class_b64"
    else:
        assert grew["admit"] == 0.0
        assert 0.02 <= grew["decode_dispatch"] <= took["decode_dispatch"]
        held = "tick_class_plain"
    assert 0.03 <= grew["route"] <= took["route"] + 1e-9
    # neither the wait for the device nor the wait for work is starving:
    # no phase is starved for longer than it took
    assert all(grew[p] <= took[p] + 1e-9 for p in STARVED)
    assert abs(sum(grew.values())
               - (after["tick_starved_s"] - before["tick_starved_s"])) < 1e-9
    # the tick's class carries what lay between the last tick's end and
    # its own, as its wall does (the handover after it is the next tick's)
    assert after[f"{held}_n"] - before[f"{held}_n"] == 1
    assert after[f"{held}_sync_s"] - before[f"{held}_sync_s"] >= 0.002
    assert abs(after[f"{held}_starved_s"] - before[f"{held}_starved_s"]
               - sum(grew.values())) < 0.005


class _SlowRoute(SimRollingEngine):
    """A sim whose bookkeeping after the blocking read takes ``route_s``."""

    route_s = 0.02

    def _emit_events(self):
        time.sleep(self.route_s)
        return super()._emit_events()


@pytest.mark.level("minimal")
def test_route_after_the_read_is_starved_and_dispatch_after_admit_is_not():
    sim = _SlowRoute(max_slots=2, steps_per_call=4, step_s=0.005)
    engine = DecodeEngine(sim)
    try:
        # one program of one chunk: its only tick admits, so the decode
        # chunk is dispatched behind the admission and counts nothing
        _run(engine, [[1, 2, 3]], n_new=4)
        first = engine.stats()
        assert first["tick_class_admit_n"] == 1
        assert first["tick_class_plain_n"] == 0
        assert first["tick_starved_decode_dispatch_s"] == 0.0
        assert first["tick_starved_admit_s"] > 0.0
        assert first["tick_starved_route_s"] >= sim.route_s
        # three chunks: two plain ticks, whose dispatch ends the stretch
        _run(engine, [[4, 5, 6]], n_new=12)
        after = engine.stats()
    finally:
        engine.close()
    assert after["tick_class_plain_n"] == 2
    assert after["tick_starved_decode_dispatch_s"] > 0.0
    routed = after["tick_starved_route_s"] - first["tick_starved_route_s"]
    assert 3 * sim.route_s <= routed <= (
        after["tick_route_s"] - first["tick_route_s"] + 1e-9)
    # the modelled device time is a wait, never starved
    assert after["tick_decode_sync_s"] >= 4 * 0.005 * 0.9
    assert after["tick_starved_s"] < (after["tick_route_s"]
                                      + after["tick_handover_s"]
                                      + after["tick_publish_s"]
                                      + after["tick_evict_s"]
                                      + after["tick_admit_s"]
                                      + after["tick_handoff_s"]
                                      + after["tick_decode_dispatch_s"]
                                      + 1e-6)


# ------------------- the read that returns with the chunk still queued
@pytest.mark.level("unit")
def test_first_read_is_a_wait_that_leaves_the_device_busy():
    """ISSUE 38: ``first_sync`` returns when the admission ends, with the
    decode chunk queued behind it. Its seconds are a wait for the device
    (the class's ``sync_s``), it does not start a dry stretch, and the
    routing of the first frames after it is host time under a busy device:
    no starved second comes from either, however long the chunk runs."""
    timer = _TickTimer(64)
    _tick(timer)                                       # ends with a dry queue
    before = timer.stats()
    with timer:
        with timer("admit"):
            timer.dispatched("prefill", (1, 64))
        with timer("decode_dispatch"):
            timer.dispatched("decode", 4)
        with timer("first_sync"):
            time.sleep(0.01)                           # the prefill's wall
        assert timer.dry_t is None
        with timer("route"):
            time.sleep(0.02)                           # the first frames
        assert timer.dry_t is None
        with timer("decode_sync"):
            time.sleep(0.03)                           # the chunk's wall
        assert timer.dry_t is not None
        with timer("route"):
            time.sleep(0.005)                          # the chunk's frames
    after = timer.stats()
    grew = {p: after[f"tick_starved_{p}_s"] - before[f"tick_starved_{p}_s"]
            for p in STARVED}
    assert after["tick_route_s"] - before["tick_route_s"] >= 0.025
    assert 0.005 <= grew["route"] < 0.015              # the late route only
    assert grew["decode_dispatch"] == 0.0
    # the dry stretch from the last tick's read to the prefill's dispatch
    # is all that is left: well under the chunk's wall
    assert sum(grew.values()) - grew["route"] < 0.02
    assert (after["tick_class_b64_sync_s"]
            - before["tick_class_b64_sync_s"]) >= 0.04
    assert after["tick_first_sync_n"] - before["tick_first_sync_n"] == 1


class _FirstFrames(SimRollingEngine):
    """A sim whose admissions draw a first token as the generator's do: read
    behind the chunk's dispatch (``first_s``: what is left of the prefill),
    routed through the engine's hook (``route_s`` of bookkeeping first),
    and only then the wait for the chunk (``step_s``). (Its chunk still adds
    ``steps_per_call`` tokens to a fresh row, where the generator's adds
    one fewer: the accounting under test does not care.)"""

    first_s = 0.01
    route_s = 0.02
    first_frames = None

    def admit(self, max_rows=None):
        rids = super().admit(max_rows)
        self._fresh = [self._rows[rid] for rid in rids]
        return rids

    def decode_step(self):
        if not self._rows:
            return []
        with self.tick_phase("decode_dispatch"):
            self.dispatched("decode", self.steps_per_call)
        fresh, self._fresh = getattr(self, "_fresh", []), []
        if fresh:
            with self.tick_phase("first_sync"):
                time.sleep(self.first_s)
            with self.tick_phase("route"):
                time.sleep(self.route_s)
                events = []
                for req in fresh:
                    req["emitted"] = 1
                    events.append((req["rid"], self.expected_tokens(
                        req["prompt"], 1), req["n"] == 1))
                    if req["n"] == 1:
                        self._free.append(req["slot"])
                        del self._rows[req["rid"]]
                self.first_frames(events)
        with self.tick_phase("decode_sync"):
            time.sleep(self.step_s)
        with self.tick_phase("route"):
            return self._emit_events()


@pytest.mark.level("minimal")
def test_first_frames_leave_mid_tick_and_the_tick_stays_whole():
    """Through the engine: the first frame arrives a chunk's wall before the
    second, the starved seconds do not grow by the chunk's wall or by the
    routing under the busy device, and the classes still hold every tick,
    every token and every wait of the window."""
    sim = _FirstFrames(max_slots=2, steps_per_call=4, step_s=0.05)
    engine = DecodeEngine(sim)
    stamps = []
    try:
        for frame in engine.generate(program([1, 2, 3], max_new_tokens=8)):
            stamps.append((time.perf_counter(), frame["tokens"]))
        one = _run(engine, [[4, 5]], n_new=1)[0]
    finally:
        engine.close()
    stats = engine.stats()
    assert [len(toks) for _, toks in stamps] == [1, 4, 3]
    assert [t for _, toks in stamps for t in toks] == (
        SimRollingEngine.expected_tokens([1, 2, 3], 8))
    assert stamps[1][0] - stamps[0][0] >= sim.step_s * 0.9
    assert [(f["tokens"], f["done"]) for f in one] == [
        (SimRollingEngine.expected_tokens([4, 5], 1), True)]
    classes = _classes(stats)
    assert stats["tick_class_admit_n"] == 2 and stats["ticks"] == 3
    assert sum(c["tokens"] for c in classes.values()) == stats["tokens"] == 9
    sync = sum(stats[f"tick_{p}_s"] for p in ("decode_sync", "first_sync"))
    assert abs(sum(c["sync_s"] for c in classes.values()) - sync) < 1e-6
    assert stats["tick_first_sync_s"] >= 2 * sim.first_s
    assert stats["tick_route_s"] >= 2 * sim.route_s
    # three chunks' walls and two early routings were waited and worked
    # through: none of it is the device starving
    assert stats["tick_starved_route_s"] < 2 * sim.route_s
    assert stats["tick_starved_s"] < sim.step_s
    assert stats["engine_admit_to_first_seconds_count"] == 2
    assert stats["engine_admit_to_first_seconds_sum"] < 2 * sim.step_s


# --------------------------------------- the dispatch in the profiler's trace
@pytest.mark.level("minimal")
def test_dispatches_are_host_events_that_name_their_bucket(tmp_path):
    """Under ``jax.profiler`` every executable the generator runs lies in a
    ``kt.dispatch`` event of the driver thread's line, with its ``kind``
    and ``key``, inside the phase that dispatched it."""
    from jax.profiler import ProfileData

    engine = DecodeEngine(_toy_generator())
    try:
        _run(engine, [[1, 2, 3]], n_new=4)           # compile outside
        jax.profiler.start_trace(str(tmp_path))
        try:
            _run(engine, [[1, 2, 3, 4]], n_new=8)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.close()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    (driver,) = [
        [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
         for e in line.events]
        for line in planes["/host:CPU"].lines
        if any(e.name == "kt.tick" for e in line.events)]
    spans = {name: [(s, e) for n, s, e, _ in driver if n == name]
             for name in ("kt.tick.admit", "kt.tick.decode_dispatch")}
    dispatches = [(st["kind"], str(st["key"]), s, e)
                  for n, s, e, st in driver if n == "kt.dispatch"]
    kinds = [(kind, key) for kind, key, _, _ in dispatches]
    assert kinds.count(("prefill", "(1, 16)")) == 1
    assert kinds.count(("decode", "4")) == 2
    for kind, _, s, e in dispatches:
        phase = ("kt.tick.admit" if kind == "prefill"
                 else "kt.tick.decode_dispatch")
        assert any(t0 <= s and e <= t1 for t0, t1 in spans[phase]), kind
