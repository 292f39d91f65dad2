"""The harness end to end on the CPU at toy sizes: one serving and one
training cell through the same calls a chip run makes. Each prints a
contract-shaped last line that says ``rehearsal`` and carries no device
metric; the reference passes the sound program, fails the lower-precision
control, and fails a timed path that is broken underneath the harness."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
DEVICE_METRICS = {"device_idle.steady", "device_idle.train",
                  "decode_step_dev_ms", "trainer_mfu"}


def run(args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)
    return proc


def last_line(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def shape(line):
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert not DEVICE_METRICS & set(line["metrics"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None


@pytest.fixture(scope="module")
def serve_run():
    return run(["benchmark/run.py", "--workload", "rehearsal-serve",
                "--seed", str(2**31 + 3), "--seconds", "5", "--trace", "1",
                "--rehearsal", "1", "--control", "1"])


def test_serving_rehearsal_prints_a_contract_line(serve_run):
    line = last_line(serve_run)
    shape(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    assert {"loadgen_late_p90_ms", "launch_ready_s", "compile_s",
            "rows_per_step"} <= set(line["metrics"])
    assert "# check compiles_in_window: 0 (limit 0) ok" in serve_run.stdout


def test_serving_reference_passes_sound_and_fails_the_control(serve_run):
    ref = last_line(serve_run)["reference"]
    limit = json.loads((REPO / "benchmark/cells/rehearsal-serve.json"
                        ).read_text())["correct"]
    assert ref["served_tokens"] >= 20
    # the widest gap holds int4 weights; the mean gap holds fp8 operands too
    assert (ref["gap_max"] <= limit["gap_max_limit"]
            < ref["control_w4_gap_max"])
    assert (ref["gap_mean"] <= limit["gap_mean_limit"]
            < min(ref["control_fp8_gap_mean"], ref["control_w4_gap_mean"]))


def test_serving_timed_path_broken_is_not_correct():
    proc = run([str(HERE / "bm_drive_broken.py"), "serve"])
    line = last_line(proc)
    shape(line)
    assert line["correct"] is False
    failed = [ln for ln in proc.stdout.splitlines() if "FAILED" in ln]
    assert any("served_token_gap_max_logits" in ln for ln in failed)
    assert any("served_token_gap_mean_logits" in ln for ln in failed)


def test_end_to_end_metrics_without_a_trace():
    proc = run(["benchmark/run.py", "--workload", "rehearsal-serve",
                "--seed", "9", "--seconds", "4", "--trace", "0",
                "--rehearsal", "1"])
    line = last_line(proc)
    shape(line)
    assert set(line["metrics"]) == {"ttft_p90_ms", "tok_gap_p99_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_no_chip_no_line():
    proc = run(["benchmark/run.py", "--workload", "mistral7b-train-1chip",
                "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert not proc.stdout.strip().startswith("{")
    assert '"correct"' not in proc.stdout
