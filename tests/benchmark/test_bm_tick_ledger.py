"""The readers of the tick's own account (``benchmark/readers/tick_ledger.py``,
PR 37) on hand-made contexts: exact arithmetic where the counters are there,
``None`` where there is nothing to read (the parent of the PR that added
them, a window with no plain tick, a run with no trace); the manifest with
the five entries; and two ``stats()`` snapshots of an engine built as
``BenchServer`` builds it, which keep every class key through
``serve_cell.delta``."""

import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "tests")]

from benchmark import manifest, serve_cell  # noqa: E402
from benchmark.readers import engine_spans, tick_ledger  # noqa: E402
from test_engine_timing import _run, _toy_generator  # noqa: E402

NEW = {"tail_tick_ms": "tok_gap_p99_ms", "admit_stall_ms": "tok_gap_p99_ms",
       "tick_starved_ms": "tok_gap_p99_ms",
       "starved_over_idle": "tok_gap_p99_ms",
       "engine_lock_wait_ms": "ttft_p90_ms"}
SERVING = ["mistral7b-chat-steady", "kanana2-docs-steady",
           "olmohybrid-rag-steady"]
FIELDS = ("n", "wall_s", "sync_s", "starved_s", "tokens")


def _class(name, n=0, wall_s=0.0, sync_s=0.0, starved_s=0.0, tokens=0):
    values = dict(n=n, wall_s=wall_s, sync_s=sync_s, starved_s=starved_s,
                  tokens=tokens)
    return {f"tick_class_{name}_{f}": values[f] for f in FIELDS}


def _ctx():
    delta = {
        **_class("empty", n=3, wall_s=0.003),
        **_class("plain", n=100, wall_s=10.0, sync_s=9.2, starved_s=0.5,
                 tokens=8000),
        **_class("chunk"), **_class("admit"),
        **_class("b16", n=20, wall_s=2.4, starved_s=0.16, tokens=1700),
        **_class("b512", n=30, wall_s=4.8, starved_s=0.24, tokens=2500),
        # the largest bucket WITH ticks; b2048 and b4096 are known and idle
        **_class("b1024", n=10, wall_s=2.8, sync_s=2.7, starved_s=0.1,
                 tokens=900),
        **_class("b2048"), **_class("b4096"),
        "tick_starved_s": 1.0, "tick_starved_route_s": 0.4,
        "engine_lock_wait_seconds_sum": 16.0,
        "engine_lock_wait_seconds_count": 160,
    }
    return {"seconds": 50.0, "stats_delta": delta,
            "trace_stats_delta": {"tick_starved_s": 0.27},
            "trace": {"window_s": 4.0, "busy_s": 3.7}}


@pytest.mark.parametrize("name, want", [
    ("tail_tick_ms", 280.0),            # b1024: 2.8 s / 10 ticks
    ("admit_stall_ms", 180.0),          # less plain: 10.0 s / 100 ticks
    ("tick_starved_ms", 6.25),          # 1.0 s / (100 + 20 + 30 + 10) ticks
    ("starved_over_idle", 0.9),         # 0.27 s / (4.0 - 3.7) s
    ("engine_lock_wait_ms", 100.0),
])
def test_reader_arithmetic(name, want):
    reader = manifest.reader(name)
    assert reader is getattr(tick_ledger, name)
    assert reader(_ctx()) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_finds_nothing_without_the_counters(name):
    """The parent commit's ``stats()`` has no class and no starved key, a
    window may hold no tick, and an untraced run has no trace: nothing to
    read, and no error."""
    reader = manifest.reader(name)
    assert reader({"seconds": 50.0}) is None
    assert reader({"seconds": 50.0, "stats_delta": {"steps": 200},
                   "trace_stats_delta": {"steps": 16},
                   "trace": {"window_s": 4.0, "busy_s": 3.7}}) is None
    zero = _ctx()
    zero["stats_delta"] = {k: 0 for k in zero["stats_delta"]}
    zero["trace_stats_delta"] = {}
    assert reader(zero) is None


@pytest.mark.parametrize("name, drop", [
    ("admit_stall_ms", "plain"),        # no plain tick in the window
    ("admit_stall_ms", "b"),            # no bucketed admission in it
    ("tail_tick_ms", "b"),
    ("starved_over_idle", "trace"),     # an untraced run
    ("starved_over_idle", "busy"),      # a device busy through its span
])
def test_reader_finds_nothing_where_its_part_is_missing(name, drop):
    ctx = _ctx()
    d = ctx["stats_delta"]
    if drop == "plain":
        d.update(_class("plain"))
    elif drop == "b":
        for key in list(d):
            if key.startswith("tick_class_b"):
                d[key] = 0
    elif drop == "trace":
        del ctx["trace"], ctx["trace_stats_delta"]
    else:
        ctx["trace"]["busy_s"] = 4.0
    assert getattr(tick_ledger, name)(ctx) is None


def test_manifest_holds_the_five_in_the_three_serving_cells():
    bench = manifest.benchmark_json()
    assert manifest.check(bench) == []
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW)
    for name, moves in NEW.items():
        entry, body = by_name[name], manifest.read(f"metrics/{name}.json")
        assert entry["workloads"] == SERVING
        assert entry["source"] == body["source"] == "program_counter"
        assert entry["moves"] == body["moves"] == moves
        assert body["reader"] == f"tick_ledger:{name}"
    for cell in SERVING:
        assert set(NEW) <= set(manifest.reported(bench, cell)[1])
    assert not set(NEW) & set(
        manifest.reported(bench, "mistral7b-train-1chip")[1])


def test_check_manifest_passes_from_the_command_line():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--check-manifest"],
        cwd=str(REPO), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.strip().splitlines()[-1] == "manifest: ok"


# ------------------------------------ through a real engine's two snapshots
def test_every_class_key_survives_the_windows_delta():
    """``BenchServer`` warms its buckets through the generator BEFORE the
    engine exists, and ``serve_cell.delta`` keeps a key only if it is
    numeric in both snapshots: the classes are born with the engine, so
    the window's first ``b64`` tick is in the delta."""
    from kubetorch_tpu.serving.engine import DecodeEngine

    generator = _toy_generator()        # 2 slots x 96, 4 steps a call
    for length in (3, 40):                          # buckets 16 and 64
        generator.submit([1] * length, max_new_tokens=1)
        generator.run()
    engine = DecodeEngine(generator)
    try:
        opened = engine.stats()
        _run(engine, [[5] * 40], n_new=8)
        _run(engine, [[7, 8, 9]], n_new=12)
        closed = engine.stats()
    finally:
        engine.close()
    d = serve_cell.delta(closed, opened)
    names = {"empty", "plain", "chunk", "admit", "b16", "b32", "b64", "b128"}
    assert {f"tick_class_{c}_{f}" for c in names for f in FIELDS} <= set(d)
    assert d["tick_class_b64_n"] == d["tick_class_b16_n"] == 1
    assert d["tick_class_plain_n"] == 3
    window_ticks = sum(v for k, v in d.items()
                       if k.startswith("tick_class_") and k.endswith("_n"))
    assert window_ticks - d["tick_class_empty_n"] == d["ticks"] == 5
    ctx = {"seconds": 1.0, "stats_delta": d}
    # the largest bucket with ticks, held to its own class's mean
    assert tick_ledger.tail_tick_ms(ctx) == pytest.approx(
        1e3 * d["tick_class_b64_wall_s"])
    assert tick_ledger.admit_stall_ms(ctx) == pytest.approx(
        tick_ledger.tail_tick_ms(ctx)
        - 1e3 * d["tick_class_plain_wall_s"] / 3)
    assert tick_ledger.tick_starved_ms(ctx) == pytest.approx(
        1e3 * d["tick_starved_s"] / 5) and d["tick_starved_s"] > 0


def test_lock_queue_and_admission_are_the_engines_ttft_on_the_sim():
    """With the lock wait read, the three parts of a request's way to its
    first token inside the engine are ``engine_ttft_seconds``' mean."""
    from kubetorch_tpu.serving.engine import DecodeEngine, SimRollingEngine

    engine = DecodeEngine(SimRollingEngine(max_slots=2, steps_per_call=4,
                                           step_s=0.002))
    try:
        opened = engine.stats()
        _run(engine, [[1, 2, 3], [4, 5], [6, 7, 8, 9]], n_new=8)
        closed = engine.stats()
    finally:
        engine.close()
    ctx = {"seconds": 1.0, "stats_delta": serve_cell.delta(closed, opened)}
    d = ctx["stats_delta"]
    assert d["engine_ttft_seconds_count"] == 3
    parts = (tick_ledger.engine_lock_wait_ms(ctx)
             + engine_spans.engine_queue_wait_ms(ctx)
             + engine_spans.engine_admit_to_first_ms(ctx))
    assert parts == pytest.approx(
        1e3 * d["engine_ttft_seconds_sum"] / 3, abs=1e-2)
    assert tick_ledger.engine_lock_wait_ms(ctx) >= 0
