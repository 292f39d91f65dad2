"""Examples must keep running in smoke mode (BASELINE config harnesses)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

EXAMPLES = Path(__file__).resolve().parents[1] / "examples"
REPO = EXAMPLES.parent


def _run_smoke(name: str, tmp_path, timeout=300):
    env = {
        **os.environ,
        "PYTHONPATH": str(REPO),
        "KT_LOCAL_STATE": str(tmp_path / "state"),
        "KT_STORE_ROOT": str(tmp_path / "store"),
    }
    proc = subprocess.run(
        [sys.executable, str(EXAMPLES / name), "--smoke"],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_hello_world_smoke(tmp_path):
    result = _run_smoke("hello_world.py", tmp_path)
    assert result["example"] == "hello_world"
    assert result["cold_start_s"] > 0
    assert result["warm_dispatch_p50_ms"] < 1000


def test_fault_tolerance_smoke(tmp_path):
    result = _run_smoke("fault_tolerance_dynamic_world.py", tmp_path)
    assert result["world"] == 2
    assert result["ranks"] == [0, 1]


@pytest.mark.level("release")
def test_llama_serve_smoke(tmp_path):
    result = _run_smoke("llama_serve.py", tmp_path)
    # two programs streamed through DecodeEngine over the channel, the
    # second one prefilled in chunks
    assert [len(s) for s in result["streamed"]] == [8, 8]
    # every streamed token IS the static Generator's argmax (CPU)
    assert result["static_gap_max"] == 0.0
    assert result["platform"] == "cpu" and result["model_params"] > 0


@pytest.mark.level("release")
def test_vit_dp_kueue_smoke(tmp_path):
    result = _run_smoke("vit_dp_kueue.py", tmp_path)
    assert result["devices"] == 8
    assert result["images_per_sec"] > 0


@pytest.mark.level("release")
def test_tpu_matmul_smoke(tmp_path):
    result = _run_smoke("tpu_matmul.py", tmp_path)
    assert result["tflops"] > 0


@pytest.mark.level("release")
def test_llama_fsdp_smoke(tmp_path):
    result = _run_smoke("llama_fsdp_pretrain.py", tmp_path)
    assert result["devices"] == 8
    assert result["tokens_per_sec"] > 0


@pytest.mark.level("release")
def test_long_context_ring_smoke(tmp_path):
    result = _run_smoke("long_context_ring.py", tmp_path)
    assert result["ring_attention"] is True
    assert result["mesh"]["sp"] == 4


@pytest.mark.level("release")
def test_grpo_elastic_smoke(tmp_path):
    result = _run_smoke("grpo_elastic.py", tmp_path)
    assert result["trainer"]["published"] == 2
    assert result["sampler"]["sampled"] == 4


@pytest.mark.level("minimal")
def test_actor_rollout_smoke(tmp_path):
    result = _run_smoke("actor_rollout.py", tmp_path)
    assert result["smoke"] is True
    assert len(result["rollout"]) == 6
    assert result["rollouts_served"] == 1
