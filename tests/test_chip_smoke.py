"""``chip_smoke.py``'s contract, rehearsed on the CPU; the compile-cache
rule; and the per-pod chip environment the local backend builds."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import kubetorch_tpu as kt
from kubetorch_tpu import config
from kubetorch_tpu.resources.callables.fn import Fn
from kubetorch_tpu.resources.compute.topology import chip_env

REPO = Path(__file__).resolve().parents[1]
ASSETS = Path(__file__).parent / "assets" / "summer"


@pytest.fixture(autouse=True, scope="module")
def _local_state(tmp_path_factory):
    state = tmp_path_factory.mktemp("ktlocal-chipsmoke")
    os.environ["KT_LOCAL_STATE"] = str(state)
    import kubetorch_tpu.provisioning.backend as backend

    backend._LOCAL_ROOT = state
    yield
    for record in backend.LocalBackend().list_services():
        backend.LocalBackend().teardown(record["service_name"], quiet=True)


def _smoke(tmp_path, *args):
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=300, cwd=str(tmp_path))


# ------------------------------------------------------------ chip_smoke
@pytest.mark.level("minimal")
def test_chip_smoke_rehearsal_runs_both_phases(tmp_path):
    proc = _smoke(tmp_path, "--size", "tiny")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    reports = {line.split(":", 1)[0][2:]: json.loads(line.split(":", 1)[1])
               for line in lines[:-1] if line.startswith("# ")}
    serve, train, parent = (reports["serve"], reports["train"],
                            reports["parent"])
    # the engine ran in the pod's worker and the trainer in a child of
    # its own — neither in the parent, which stayed off JAX
    assert len({serve["worker_pid"], train["pid"], parent["pid"]}) == 3
    assert parent["imported_jax"] is False
    assert serve["prefill_chunks"] > 0 and serve["requests"] == 7
    # on the CPU the engine is token-identical to the static Generator
    assert serve["static_gap_max_std"] == 0.0 and serve["static_exact"] == 36
    assert len(train["losses"]) == 3
    assert set(parent["walls"]) == {"serve", "train"}


@pytest.mark.level("minimal")
def test_chip_smoke_without_a_chip_fails_with_no_result(tmp_path):
    """Full size here, where JAX finds no accelerator: the tpus= pod must
    not come up on the CPU, and the script prints no result."""
    proc = _smoke(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "StartupError" in proc.stderr


# --------------------------------------------------------- compile cache
def test_compile_cache_dir_follows_the_variable_and_never_moves(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert config.compile_cache_dir() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert config.compile_cache_dir() == str(REPO / ".jax_cache")
    assert config.compile_cache_dir() == config.compile_cache_dir()
    monkeypatch.setenv("KT_JAX_CACHE_DIR", "/ktfs/cache/jax")   # K8s only
    assert config.compile_cache_dir() == str(REPO / ".jax_cache")


@pytest.mark.level("minimal")
def test_plain_worker_gets_the_cache(monkeypatch):
    """A plain ``kt.fn`` worker (no .distribute) compiles too: with the
    variable unset it gets the helper's directory."""
    remote = Fn(root_path=str(ASSETS), import_path="summer",
                callable_name="env_values", name="cache-env")
    key = "JAX_COMPILATION_CACHE_DIR"
    monkeypatch.delenv(key, raising=False)
    try:
        remote.to(kt.Compute(cpus="0.1"))
        assert remote([key])[key] == str(REPO / ".jax_cache")
    finally:
        remote.teardown()


# -------------------------------------------------------------- chip env
def test_chip_env_one_process_per_share():
    one = chip_env([2], [9001])
    assert one["TPU_VISIBLE_CHIPS"] == "2"
    assert one["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert one["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert one["TPU_PROCESS_ADDRESSES"] == "localhost:9001"
    host = chip_env([0, 1, 2, 3], [9002])
    assert host["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert host["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"
    # four processes of one pod split its four chips, one each
    group = [chip_env([4, 5, 6, 7], [9010, 9011, 9012, 9013], task=i)
             for i in range(4)]
    assert [g["TPU_VISIBLE_CHIPS"] for g in group] == ["4", "5", "6", "7"]
    assert {g["TPU_PROCESS_BOUNDS"] for g in group} == {"2,2,1"}
    assert [g["CLOUD_TPU_TASK_ID"] for g in group] == ["0", "1", "2", "3"]
    assert len({g["TPU_PROCESS_ADDRESSES"] for g in group}) == 1
    with pytest.raises(ValueError):
        chip_env([0, 1], [9020, 9021])      # no verified two-process layout


def test_local_replicas_get_disjoint_chips(monkeypatch):
    """The environment ``LocalBackend`` hands each pod server: two v5e-1
    replicas, a second service beside them, then a scale-up — every pod
    confined to a chip no other live pod was given and told to come up on
    the TPU, unless the compute's own env says otherwise."""
    import kubetorch_tpu.provisioning.backend as backend

    spawned = []

    class FakePod:
        pid = 2 ** 22 + 1               # above pid_max: signals reach no one

        def __init__(self, cmd, env, **kwargs):
            spawned.append(env)

    monkeypatch.setattr(backend.subprocess, "Popen", FakePod)
    monkeypatch.setattr(backend, "_pid_alive", lambda pid: True)
    monkeypatch.setattr(backend.LocalBackend, "_wait_ready",
                        lambda *a, **k: None)
    local = backend.LocalBackend()

    def launch(name, num_pods, env=None):
        compute = kt.Compute(tpus="v5e-1", replicas=num_pods, env=env)
        local.launch(name, module_env=dict(compute.env),
                     compute_dict=compute.to_dict(), module_meta={},
                     num_pods=num_pods)
        return spawned[-num_pods:]

    a = launch("chips-a", 2)
    assert [e["TPU_VISIBLE_CHIPS"] for e in a] == ["0", "1"]
    assert all(e["JAX_PLATFORMS"] == "tpu"
               and e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in a)
    assert len({e["TPU_PROCESS_PORT"] for e in a}) == 2
    # a second service takes the next free chip; an emulated slice says
    # JAX_PLATFORMS=cpu itself and is heard
    (b,) = launch("chips-b", 1, env={"JAX_PLATFORMS": "cpu"})
    assert (b["TPU_VISIBLE_CHIPS"], b["JAX_PLATFORMS"]) == ("2", "cpu")
    local.scale("chips-a", 3)
    assert spawned[-1]["TPU_VISIBLE_CHIPS"] == "3"
    assert [p["chips"] for p in local.lookup("chips-a")["pods"]] == [
        [0], [1], [3]]
    # a pod that asked for no TPU is pinned to the CPU and confined to
    # nothing
    local.launch("cpu-only", module_env={}, compute_dict=kt.Compute(
        cpus="1").to_dict(), module_meta={})
    assert spawned[-1]["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in spawned[-1]
    for name in ("chips-a", "chips-b", "cpu-only"):
        local.teardown(name)
