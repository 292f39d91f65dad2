"""ISSUE 10: server-resident continuous-batching decode engine.

Three layers:

1. **Scheduler invariants** (in-process, CPU, no pod): the
   :class:`DecodeEngine` loop over :class:`SimRollingEngine` — decode
   never stalls while a long prompt prefills in chunks, admit-to-first-
   token is bounded by the chunk count, deadline eviction frees the
   row, and overload sheds typed (``ServerOverloaded`` + retry_after).
2. **Generation programs over the wire** (real pod server + worker):
   one streamed channel call runs the whole generation server-side; a
   mid-stream partition (chaos kind ``partition``) resumes the token
   stream byte-identical via PR-8 replay with a server-asserted
   execution count of exactly 1.
3. **Control frames**: ``chan.control("stats")`` answers queue depth /
   engine occupancy out-of-band — no worker hop, no FIFO wait behind
   the live stream.
"""

import os
import threading
import time
from pathlib import Path

import pytest

import kubetorch_tpu as kt
from kubetorch_tpu.exceptions import DeadlineExceeded, ServerOverloaded
from kubetorch_tpu.resilience import chaos
from kubetorch_tpu.resources.callables.cls import Cls
from kubetorch_tpu.serving.engine import (
    DecodeEngine,
    GenerationProgram,
    SimRollingEngine,
)

ASSETS = Path(__file__).parent / "assets" / "summer"


@pytest.fixture(autouse=True, scope="module")
def _local_state(tmp_path_factory):
    state = tmp_path_factory.mktemp("ktlocal-engine")
    os.environ["KT_LOCAL_STATE"] = str(state)
    import kubetorch_tpu.provisioning.backend as backend

    backend._LOCAL_ROOT = state
    yield
    for record in backend.LocalBackend().list_services():
        backend.LocalBackend().teardown(record["service_name"], quiet=True)


@pytest.fixture(autouse=True)
def _no_leftover_chaos():
    yield
    chaos.install(None)


# ------------------------------------------------- scheduler invariants
@pytest.mark.level("unit")
def test_program_validation():
    with pytest.raises(ValueError):
        GenerationProgram.from_wire([1, 2, 3])
    with pytest.raises(ValueError):
        GenerationProgram.from_wire({"max_new_tokens": 4})
    with pytest.raises(ValueError):
        GenerationProgram.from_wire({"prompt": []})
    with pytest.raises(ValueError):
        GenerationProgram.from_wire({"prompt": [1], "deadline_s": -1})
    prog = GenerationProgram.from_wire(
        {"prompts": [[1, 2], [3]], "max_new_tokens": 7, "tag": "x"})
    assert prog.prompts == [[1, 2], [3]] and prog.tag == "x"
    assert prog.submit_kwargs()["max_new_tokens"] == 7


@pytest.mark.level("unit")
def test_engine_stream_byte_identical_and_seq_gapless():
    eng = DecodeEngine(SimRollingEngine(max_slots=4, steps_per_call=8,
                                        step_s=0.001), poll_s=0.005)
    try:
        prompt = list(range(1, 9))
        frames = list(eng.generate(
            {"prompt": prompt, "max_new_tokens": 40, "tag": "one"}))
        toks = [t for f in frames for t in f["tokens"]]
        assert toks == SimRollingEngine.expected_tokens(prompt, 40)
        assert [f["seq"] for f in frames] == list(range(len(frames)))
        assert frames[-1]["done"] and not frames[0]["done"]
        assert eng.exec_count("one") == 1
        assert eng.stats()["free_rows"] == 4
    finally:
        eng.close()


@pytest.mark.level("unit")
def test_no_decode_stall_during_chunked_prefill():
    """The headline scheduler invariant: while a long prompt prefills
    chunk by chunk, the already-decoding stream KEEPS emitting — chunked
    prefill interleaves between decode chunks instead of stalling them."""
    sim = SimRollingEngine(max_slots=2, steps_per_call=4,
                           prefill_chunk=8, step_s=0.004)
    eng = DecodeEngine(sim, poll_s=0.002)
    try:
        short = [1, 2, 3]
        long_p = list(range(10, 74))          # 64 tokens = 8 chunks
        stamps: dict = {"short": [], "long": []}

        def run(name, prog):
            for f in eng.generate(prog):
                stamps[name].append((time.perf_counter(), f))

        t_s = threading.Thread(target=run, args=(
            "short", {"prompt": short, "max_new_tokens": 120}))
        t_s.start()
        wait_deadline = time.time() + 20
        while not stamps["short"]:           # short is live and emitting
            assert time.time() < wait_deadline and t_s.is_alive(), \
                "short stream never produced a frame"
            time.sleep(0.002)
        t_l = threading.Thread(target=run, args=(
            "long", {"prompt": long_p, "max_new_tokens": 16}))
        t_l.start()
        t_s.join(30)
        t_l.join(30)
        assert stamps["short"][-1][1]["done"]
        assert stamps["long"][-1][1]["done"]
        long_toks = [t for _, f in stamps["long"] for t in f["tokens"]]
        assert long_toks == SimRollingEngine.expected_tokens(long_p, 16)
        # no stall: during the long prompt's prefill window (submit →
        # its first frame), the short stream kept producing chunks
        t_first_long = stamps["long"][0][0]
        short_during = [t for t, _ in stamps["short"]
                        if t < t_first_long]
        assert len(short_during) >= 3, (
            f"short stream produced only {len(short_during)} chunks "
            f"while the long prompt prefilled — decode stalled")
        # admit-to-first-token bounded: a tick with a prefilling row
        # runs exactly one chunk, so the ticks between the long prompt's
        # admission and its first decode chunk are its 8 chunks (the
        # short prompt fits the admission itself and adds none): first
        # token within 9 ticks of admission
        st = eng.stats()
        assert st["prefill_chunks"] == 8
    finally:
        eng.close()


@pytest.mark.level("unit")
def test_partial_program_submit_failure_releases_rows():
    """A multi-prompt program whose LATER prompt fails validation must
    release the earlier prompts' rows — they would otherwise stream
    into a sink nobody reads for their whole token budget."""

    class Picky(SimRollingEngine):
        def submit(self, prompt, **kw):
            if prompt == [666]:
                raise ValueError("bad prompt")
            return super().submit(prompt, **kw)

    eng = DecodeEngine(Picky(max_slots=4, steps_per_call=4,
                             step_s=0.001), poll_s=0.002)
    try:
        with pytest.raises(ValueError):
            next(eng.generate({"prompts": [[1, 2], [666]],
                               "max_new_tokens": 8}))
        assert eng.stats()["pending"] == 0
        assert eng.stats()["free_rows"] == 4
        frames = list(eng.generate({"prompt": [1, 2],
                                    "max_new_tokens": 8}))
        assert frames[-1]["done"]            # engine still serves
    finally:
        eng.close()


@pytest.mark.level("unit")
def test_abandoned_stream_evicts_rows():
    """Closing the generate() generator mid-stream (what the worker
    does when the client abandons the call or the wire deadline
    passes) must evict the program's rows — an abandoned program must
    not burn device chunks to its token budget."""
    eng = DecodeEngine(SimRollingEngine(max_slots=2, steps_per_call=1,
                                        step_s=0.005), poll_s=0.002)
    try:
        gen = eng.generate({"prompt": [1, 2], "max_new_tokens": 100000})
        assert next(gen)["tokens"]            # the row is live
        gen.close()                           # GeneratorExit at yield
        deadline = time.time() + 5
        while eng.stats()["free_rows"] != 2 and time.time() < deadline:
            time.sleep(0.01)
        assert eng.stats()["free_rows"] == 2, "abandoned row never freed"
        assert eng.stats()["pending"] == 0
    finally:
        eng.close()


@pytest.mark.level("unit")
def test_deadline_evicts_row_and_frees_it():
    eng = DecodeEngine(SimRollingEngine(max_slots=2, steps_per_call=1,
                                        step_s=0.01), poll_s=0.002)
    try:
        got = []
        with pytest.raises(DeadlineExceeded):
            for f in eng.generate({"prompt": [5, 5], "deadline_s": 0.08,
                                   "max_new_tokens": 100000}):
                got.append(f)
        assert got, "frames before the deadline must still deliver"
        deadline = time.time() + 5
        while eng.stats()["free_rows"] != 2 and time.time() < deadline:
            time.sleep(0.01)
        assert eng.stats()["free_rows"] == 2, "evicted row never freed"
    finally:
        eng.close()


@pytest.mark.level("unit")
def test_overload_sheds_typed_with_retry_after():
    sim = SimRollingEngine(max_slots=1, steps_per_call=1, step_s=0.05)
    eng = DecodeEngine(sim, poll_s=0.002, max_waiting=2)
    try:
        def run(k):
            try:
                list(eng.generate({"prompt": [k], "max_new_tokens": 400}))
            # the teardown close() fails still-queued streams typed;
            # either way the thread must exit quietly
            except Exception:  # noqa: BLE001
                pass

        threads = [threading.Thread(target=run, args=(k,), daemon=True)
                   for k in range(1, 4)]
        # the one slot is taken BEFORE the two that queue are sent: three
        # sent at once race the first admission, and the third is then the
        # one that is shed (the backlog shows for a tick and is gone)
        threads[0].start()
        deadline = time.time() + 5
        while sim.active_rows < 1 and time.time() < deadline:
            time.sleep(0.005)
        assert sim.active_rows == 1, "the first request was never admitted"
        for t in threads[1:]:
            t.start()
        while sim.queued < 2 and time.time() < deadline:
            time.sleep(0.005)
        assert sim.queued >= 2, "backlog never built"
        with pytest.raises(ServerOverloaded) as err:
            list(eng.generate({"prompt": [99], "max_new_tokens": 4}))
        assert err.value.retry_after and err.value.retry_after >= 0.05
    finally:
        eng.close()


# --------------------------------------------------- wire-level (e2e)
@pytest.fixture(scope="module")
def enginehost(_local_state):
    remote = Cls(root_path=str(ASSETS), import_path="summer",
                 callable_name="EngineHost", name="enginehost")
    remote.to(kt.Compute(cpus="0.1"))
    yield remote
    remote.teardown()


@pytest.mark.level("minimal")
def test_generation_program_survives_partition_byte_identical(enginehost):
    """Acceptance: ONE streamed channel call runs the whole generation
    server-side; two injected mid-stream partitions cost nothing — the
    token stream resumes byte-identical from the ack cursor (PR-8
    replay) and the program executed exactly once."""
    prompt = [3, 1, 4, 1, 5]
    n = 240                                     # 30 chunks of 8
    expected = SimRollingEngine.expected_tokens(prompt, n)
    with enginehost.channel(depth=2) as chan:
        base = list(chan.submit(
            {"prompt": [9, 9], "max_new_tokens": 16, "tag": "base"},
            method="generate", stream=True, concurrent=True,
        ).result(timeout=60))
        assert [t for f in base for t in f["tokens"]] == \
            SimRollingEngine.expected_tokens([9, 9], 16)
        policy = chaos.ChaosPolicy(seed=7, partition=1.0, max_events=2)
        chaos.install(policy)
        stream = chan.submit(
            {"prompt": prompt, "max_new_tokens": n, "tag": "hot"},
            kwargs={"delay_ms": 5.0}, method="generate", stream=True,
            concurrent=True)
        frames = list(stream.result(timeout=120))
        chaos.install(None)
        assert len(policy.events) == 2, policy.events
        assert [e[0] for e in policy.events] == ["partition", "partition"]
        # byte-identical: exact tokens, gapless engine seqs, no dup
        assert [t for f in frames for t in f["tokens"]] == expected
        assert [f["seq"] for f in frames] == list(range(len(frames)))
        assert chan.connects == 3, chan.connects
        # exactly once: the program ran a single time server-side
        assert chan.call("hot", method="exec_count") == 1
        assert chan.call("base", method="exec_count") == 1


@pytest.mark.level("minimal")
def test_control_frame_answers_out_of_band(enginehost):
    """``chan.control`` answers from pod/session state + the last
    worker-piggybacked engine snapshot — even while a stream is live on
    the same channel (it would deadlock if it queued in the FIFO)."""
    with enginehost.channel(depth=2) as chan:
        # a completed generation piggybacks the engine_* snapshot onto
        # the pod's metrics dict
        list(chan.submit({"prompt": [2, 7], "max_new_tokens": 16},
                         method="generate", stream=True,
                         concurrent=True).result(timeout=60))
        info = chan.control("stats")
        assert info["op"] == "stats"
        assert "pod_queue_depth" in info and "session_queue_depth" in info
        assert info["engine"]["engine_generations_total"] >= 1
        assert info["engine"]["engine_steps_total"] >= 1
        # out-of-band: answered while a slow stream holds the session
        slow = chan.submit(
            {"prompt": [1, 1, 1], "max_new_tokens": 80},
            kwargs={"delay_ms": 30.0}, method="generate", stream=True,
            concurrent=True)
        t0 = time.perf_counter()
        info2 = chan.control("stats")
        ctl_s = time.perf_counter() - t0
        assert info2["pod_queue_depth"] >= 1
        assert ctl_s < 5.0
        assert list(slow.result(timeout=120))[-1]["done"]


@pytest.mark.level("minimal")
def test_prefix_id_round_trips_over_wire(enginehost):
    """Satellite (ISSUE 11): the client can REGISTER a prefix and
    submit against it — ``register_prefix`` over the channel returns
    the id, ``program(prefix_id=...)`` carries it, and the stream
    equals the full-prompt ground truth."""
    from kubetorch_tpu.serving.engine import program

    with enginehost.channel(depth=2) as chan:
        prefix = list(range(40, 56))
        pid = chan.call(prefix, method="register_prefix")
        assert isinstance(pid, int)
        frames = list(chan.submit(
            program([7, 8], prefix_id=pid, max_new_tokens=16),
            method="generate", stream=True, concurrent=True,
        ).result(timeout=60))
        toks = [t for f in frames for t in f["tokens"]]
        assert toks == SimRollingEngine.expected_tokens(prefix + [7, 8], 16)


@pytest.mark.level("minimal")
def test_session_park_resume_over_wire(enginehost):
    """ISSUE 11 acceptance at the wire level: a session program parks
    mid-stream (explicit ``park`` call — answered while the stream is
    live, ``concurrent=True``), its stream ends with a ``parked``
    frame, and a resubmit with the same ``session_id`` continues the
    token stream exactly where it stopped — no re-prefill."""
    import uuid

    from kubetorch_tpu.serving.engine import program

    sid = f"wire-{uuid.uuid4().hex[:8]}"
    prompt = [5, 6]
    n = 400
    with enginehost.channel(depth=2) as chan:
        stream = chan.submit(
            program(prompt, session_id=sid, max_new_tokens=n),
            kwargs={"delay_ms": 5.0}, method="generate", stream=True,
            concurrent=True, timeout=60.0)
        got, saw_parked = [], False
        parked_rows = None
        for frame in stream:
            if frame.get("parked"):
                saw_parked = True
                assert frame["session_id"] == sid
                break
            got.extend(frame["tokens"])
            if parked_rows is None and len(got) >= 8:
                parked_rows = chan.call(sid, method="park")
        assert parked_rows == 1
        assert saw_parked and 0 < len(got) < n
        st_before = chan.call(method="stats")
        frames = list(chan.submit(
            program(prompt, session_id=sid, max_new_tokens=n),
            method="generate", stream=True, concurrent=True,
        ).result(timeout=120))
        rest = [t for f in frames for t in f["tokens"]]
        assert frames[-1]["done"]
        assert got + rest == SimRollingEngine.expected_tokens(prompt, n)
        st = chan.call(method="stats")
        assert st["restores"] == st_before["restores"] + 1
        # resume never re-ran the prompt prefill
        assert st["prefill_tokens_executed"] == \
            st_before["prefill_tokens_executed"]


@pytest.mark.level("minimal")
def test_control_stats_surface_kv_metrics(enginehost):
    """Satellite observability: the kv_/prefix_ counters ride the
    worker piggyback into the pod snapshot and come back on the
    out-of-band control frame."""
    with enginehost.channel(depth=2) as chan:
        list(chan.submit({"prompt": [4, 2], "max_new_tokens": 16},
                         method="generate", stream=True,
                         concurrent=True).result(timeout=60))
        info = chan.control("stats")
        assert "kv_blocks_used" in info["engine"], sorted(info["engine"])


# --------------------------------------------- speculative (ISSUE 14)
@pytest.fixture(scope="module")
def spechost(_local_state):
    """EngineHost over a SPECULATIVE sim engine with automatic prefix
    sharing on — the composition the PR-10 gate used to forbid. The
    sim's emission stays a pure function of (full prompt, index), so
    every stream below is byte-asserted against the spec-OFF ground
    truth by construction."""
    remote = Cls(root_path=str(ASSETS), import_path="summer",
                 callable_name="EngineHost", name="spechost",
                 init_args={"args": [], "kwargs": {
                     "spec_k": 4, "spec_accept": 0.8,
                     "prefix_split": "len:16", "prefill_chunk": 16,
                     "step_ms": 2.0}})
    remote.to(kt.Compute(cpus="0.1"))
    yield remote
    remote.teardown()


@pytest.mark.level("minimal")
def test_spec_prefix_hit_stream_byte_identical_with_partition(spechost):
    """ISSUE 14 acceptance over a real pod: the full path — admission →
    chunked prefill → prefix HIT → adaptive spec decode → stream —
    emits byte-identical to a spec-off engine under greedy, including a
    mid-stream partition resume (PR-8 replay, exec-count 1). Also pins
    the removed ``engine.py`` spec×prefix-sharing gate: the second
    program's prefix must HIT the cache registered by the first."""
    prefix = list(range(200, 216))               # len:16 split point
    suffix_a = [61] * 24                         # > prefill_chunk head
    suffix_b = [62] * 24
    with spechost.channel(depth=2) as chan:
        first = list(chan.submit(
            {"prompt": prefix + suffix_a, "max_new_tokens": 64,
             "tag": "pfx-a"},
            method="generate", stream=True, concurrent=True,
        ).result(timeout=60))
        assert [t for f in first for t in f["tokens"]] == \
            SimRollingEngine.expected_tokens(prefix + suffix_a, 64)
        st0 = chan.call(method="stats")
        policy = chaos.ChaosPolicy(seed=5, partition=1.0, max_events=1)
        chaos.install(policy)
        stream = chan.submit(
            {"prompt": prefix + suffix_b, "max_new_tokens": 160,
             "tag": "pfx-b"},
            kwargs={"delay_ms": 5.0}, method="generate", stream=True,
            concurrent=True)
        frames = list(stream.result(timeout=120))
        chaos.install(None)
        assert [e[0] for e in policy.events] == ["partition"]
        assert [t for f in frames for t in f["tokens"]] == \
            SimRollingEngine.expected_tokens(prefix + suffix_b, 160)
        assert [f["seq"] for f in frames] == list(range(len(frames)))
        assert chan.call("pfx-b", method="exec_count") == 1
        st = chan.call(method="stats")
        # the second program's prefix HIT (no second prefix prefill),
        # and the engine actually speculated
        assert st["prefixes"] == 1
        assert st["prefill_tokens_executed"] - \
            st0["prefill_tokens_executed"] == len(suffix_b)
        assert st["spec_rounds"] > 0
        assert st["spec_tokens_per_pass"] > 1.0


@pytest.mark.level("minimal")
def test_spec_session_park_resume_over_wire(spechost):
    """ISSUE 14 × PR 10: a SPECULATIVE session parks mid-stream and a
    resubmit resumes its stream exactly — the acceptance EMA + draft
    lookahead ride the store blob (spec composes with park/resume)."""
    import uuid

    from kubetorch_tpu.serving.engine import program

    sid = f"spec-{uuid.uuid4().hex[:8]}"
    prompt = [71, 72]
    n = 600
    with spechost.channel(depth=2) as chan:
        stream = chan.submit(
            program(prompt, session_id=sid, max_new_tokens=n),
            kwargs={"delay_ms": 5.0}, method="generate", stream=True,
            concurrent=True, timeout=60.0)
        got, saw_parked = [], False
        parked_rows = None
        for frame in stream:
            if frame.get("parked"):
                saw_parked = True
                break
            got.extend(frame["tokens"])
            if parked_rows is None and len(got) >= 8:
                parked_rows = chan.call(sid, method="park")
        assert parked_rows == 1 and saw_parked and 0 < len(got) < n
        st_before = chan.call(method="stats")
        frames = list(chan.submit(
            program(prompt, session_id=sid, max_new_tokens=n),
            method="generate", stream=True, concurrent=True,
        ).result(timeout=120))
        rest = [t for f in frames for t in f["tokens"]]
        assert frames[-1]["done"]
        assert got + rest == SimRollingEngine.expected_tokens(prompt, n)
        st = chan.call(method="stats")
        assert st["restores"] == st_before["restores"] + 1
        assert st["prefill_tokens_executed"] == \
            st_before["prefill_tokens_executed"]


@pytest.mark.level("minimal")
def test_program_deadline_rejected_typed_over_wire(enginehost):
    """A program deadline evicts the row server-side mid-stream and the
    client sees the typed DeadlineExceeded after the frames that made
    it out — never a silent truncation."""
    with enginehost.channel(depth=2) as chan:
        stream = chan.submit(
            {"prompt": [8, 8], "max_new_tokens": 100000,
             "deadline_s": 0.4},
            kwargs={"delay_ms": 10.0}, method="generate", stream=True,
            concurrent=True, timeout=30.0)
        got = []
        with pytest.raises(DeadlineExceeded):
            # iterate the handle directly: items delivered before the
            # deadline arrive, then the typed refusal raises (result()
            # would raise at the error terminal without yielding)
            for frame in stream:
                got.append(frame)
        assert got, "pre-deadline frames must still arrive"
