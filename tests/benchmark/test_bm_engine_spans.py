"""The readers of the serving engine's own timing
(``benchmark/readers/engine_spans.py``) on hand-made contexts: exact
arithmetic where the counters are there, ``None`` where they are not (the
parent of the PR that added them, a rehearsal), and the manifest with the
six entries."""

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import loadgen, manifest  # noqa: E402
from benchmark.readers import engine_spans  # noqa: E402

NEW = ("engine_queue_wait_ms", "engine_admit_to_first_ms",
       "ttft_outside_engine_ms", "tick_host_ms", "admit_host_ms",
       "tick_publish_ms")


def _record(sent, first, due=None):
    r = loadgen.Record({"id": 0, "prompt_len": 4, "out_len": 1})
    r.due, r.sent = (sent if due is None else due), sent
    r.frames = [] if first is None else [(first, 1)]
    return r


def _ctx():
    delta = {
        "ticks": 200, "steps": 200,
        "engine_queue_wait_seconds_sum": 80.0,
        "engine_queue_wait_seconds_count": 160,
        "engine_admit_to_first_seconds_sum": 48.0,
        "engine_admit_to_first_seconds_count": 160,
        "engine_lock_wait_seconds_sum": 16.0,
        "engine_lock_wait_seconds_count": 160,
        "engine_ttft_seconds_sum": 144.0,
        "engine_ttft_seconds_count": 160,
        "tick_evict_s": 0.02, "tick_handoff_s": 0.01,
        "tick_decode_dispatch_s": 0.5, "tick_route_s": 0.3,
        "tick_publish_s": 0.1, "tick_handover_s": 0.07,
        "tick_decode_sync_s": 38.0, "tick_idle_s": 3.0,
        "tick_admit_s": 1.5, "tick_admit_n": 150,
        "tick_prefill_s": 0.0, "tick_prefill_n": 0,
    }
    records = [_record(1.0, 2.0), _record(2.0, 2.9),
               _record(-0.5, 0.4),            # sent in the ramp, seen inside
               _record(49.5, 50.2),           # first frame after the close
               _record(3.0, None),            # never answered
               _record(-3.0, -1.0)]           # all of it before the window
    return {"seconds": 50.0, "stats_delta": delta, "records": records}


@pytest.mark.parametrize("name, want", [
    ("engine_queue_wait_ms", 500.0),
    ("engine_admit_to_first_ms", 300.0),
    # client: (1000 + 900 + 900) / 3 ms; engine: 144 / 160 s
    ("ttft_outside_engine_ms", 2800.0 / 3 - 900.0),
    # (0.02 + 0.01 + 0.5 + 0.3 + 0.1 + 0.07) s / 200 ticks
    ("tick_host_ms", 5.0),
    ("admit_host_ms", 10.0),
    ("tick_publish_ms", 0.5),
])
def test_reader_arithmetic(name, want):
    reader = manifest.reader(name)
    assert reader is getattr(engine_spans, name)
    assert reader(_ctx()) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_without_the_counters(name):
    """The parent commit's ``stats()`` has none of these keys, and a window
    may hold no request or no tick: nothing to read, and no error."""
    reader = manifest.reader(name)
    bare = {"seconds": 50.0, "records": _ctx()["records"],
            "stats_delta": {"steps": 200, "tokens": 9000}}
    assert reader(bare) is None
    assert reader({"seconds": 50.0}) is None
    zero = _ctx()
    zero["stats_delta"] = {k: 0 for k in zero["stats_delta"]}
    zero["records"] = []
    assert reader(zero) is None


def test_tick_host_needs_every_phase():
    ctx = _ctx()
    del ctx["stats_delta"]["tick_handover_s"]
    assert engine_spans.tick_host_ms(ctx) is None


def test_manifest_holds_the_six_in_the_chat_cell_only():
    bench = manifest.benchmark_json()
    assert manifest.check(bench) == []
    by_name = {m["name"]: m for m in bench["per_layer"]}
    moves = {"engine_queue_wait_ms": "ttft_p90_ms",
             "engine_admit_to_first_ms": "ttft_p90_ms",
             "ttft_outside_engine_ms": "ttft_p90_ms",
             "tick_host_ms": "tok_gap_p99_ms",
             "admit_host_ms": "tok_gap_p99_ms",
             "tick_publish_ms": "tok_gap_p99_ms"}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == ["mistral7b-chat-steady"]
        assert (m["source"], m["unit"], m["better"]) == (
            "program_counter", "ms", "lower")
        assert m["moves"] == moves[name]
    _, per = manifest.reported(bench, "mistral7b-chat-steady")
    assert set(NEW) <= set(per)
    _, per_train = manifest.reported(bench, "mistral7b-train-1chip")
    assert not set(NEW) & set(per_train)
