"""BENCHMARK.json against the files it is assembled from, and the rule that
refused PR 22: a per-layer metric may be reported only in cells that report
the end-to-end metric it moves."""

import copy
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import manifest  # noqa: E402


@pytest.fixture(scope="module")
def bench():
    return manifest.benchmark_json()


def test_manifest_agrees_with_its_files(bench):
    assert manifest.check(bench) == []


def test_check_manifest_command_passes():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--check-manifest"], cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.strip().endswith("manifest: ok")


def test_every_per_layer_metric_moves_a_metric_its_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        target = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in target.get("workloads", cells), (m["name"], cell)


def test_the_fault_of_pr22_is_caught(bench):
    bad = copy.deepcopy(bench)
    late = next(m for m in bad["per_layer"]
                if m["name"] == "loadgen_late_p90_ms")
    late["workloads"] = late["workloads"] + ["mistral7b-train-1chip"]
    errors = manifest.check(bad)
    assert any("loadgen_late_p90_ms is reported on workload "
               "mistral7b-train-1chip" in e and "ttft_p90_ms" in e
               for e in errors), errors


@pytest.mark.parametrize("mutate, needle", [
    (lambda b: b["per_layer"][0].update(unit="tokens per second"), "unit"),
    (lambda b: b["per_layer"][0].update(unit="x" * 17), "unit"),
    (lambda b: b["workloads"][0].update(name="has space"), "name"),
    (lambda b: b["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda b: b["end_to_end"][0].update(source="program_span"),
     "host_clock"),
    (lambda b: [w.update(chips=4) for w in b["workloads"][:2]],
     "four chips"),
    (lambda b: b["workloads"][0].update(why="changed"), "why differs"),
    (lambda b: b["configs"][0].update(reduced=["hidden_size"]),
     "reduced differs"),
    (lambda b: b["per_layer"][0].update(moves="nothing"), "moves"),
    (lambda b: b["per_layer"].append(dict(b["per_layer"][0], name="ghost")),
     "no benchmark/metrics/ghost.json"),
])
def test_faults_are_named(bench, mutate, needle):
    bad = copy.deepcopy(bench)
    mutate(bad)
    errors = manifest.check(bad)
    assert any(needle in e for e in errors), errors


def test_contract_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        assert not set(c["reduced"]) & {
            "hidden_size", "intermediate_size", "head_dim",
            "num_attention_heads", "num_key_value_heads"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert len(json.dumps(bench)) < 64 * 1024
    for part in bench["command"]:
        assert not part.startswith("/") and ".." not in part


def test_every_file_under_paths_is_named_from_name_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in bench["paths"]:
        for path in (REPO / root).rglob("*"):
            if "__pycache__" in path.parts:
                continue
            assert ok.match(str(path.relative_to(REPO))), path


def test_published_widths_are_uncut(bench):
    for c in bench["configs"]:
        body = json.loads((REPO / c["file"]).read_text())
        assert (body["hidden_size"], body["intermediate_size"],
                body["num_attention_heads"], body["num_key_value_heads"],
                body["head_dim"], body["vocab_size"]) == (
                    4096, 14336, 32, 8, 128, 32768)
        for key in ("source", "assumed", "reduced", "stands_for"):
            assert body[key] or key == "reduced"
