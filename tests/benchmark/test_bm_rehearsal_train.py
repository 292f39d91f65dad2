"""The training harness end to end on the CPU at toy sizes (see
``test_bm_rehearsal.py``): reference, control, broken step."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from test_bm_rehearsal import last_line, run, shape  # noqa: E402


@pytest.fixture(scope="module")
def train_run():
    return run(["benchmark/run.py", "--workload", "rehearsal-train",
                "--seed", str(2**31 + 5), "--seconds", "2", "--trace", "1",
                "--rehearsal", "1", "--control", "1"])


def test_training_rehearsal_prints_a_contract_line(train_run):
    line = last_line(train_run)
    shape(line)
    assert line["correct"] is True and line["attempted"] >= 3
    assert set(line["metrics"]) == {"compile_s"}
    assert "# check compiles_in_window: 0 (limit 0) ok" in train_run.stdout


def test_training_reference_passes_sound_and_fails_the_control(train_run):
    line = last_line(train_run)
    lim = json.loads((REPO / "benchmark/cells/rehearsal-train.json"
                      ).read_text())["correct"]
    ref, low = line["reference"], line["controls"]["fp8"]
    assert ref["loss_gap"] <= lim["loss_gap_limit"]
    assert ref["loss0_gap"] <= lim["loss0_gap_limit"]
    assert ref["grad_norm_gap"][0] <= lim["grad_norm_gap_limit"]
    assert ref["grad_sample_gap"][0] <= lim["grad_sample_gap_limit"]
    assert ref["delta_norm_gap"][0] <= lim["delta_norm_gap_limit"]
    # the number the lower precision has to fail: the first gradient read
    # at seeded positions (a norm moves only to second order)
    assert low["grad_sample_gap"][0] > 3 * lim["grad_sample_gap_limit"]


def test_training_step_that_returns_its_state_unchanged_is_not_correct():
    proc = run([str(HERE / "bm_drive_broken.py"), "train"])
    line = last_line(proc)
    shape(line)
    assert line["correct"] is False
    assert "parameter_change_norm_gap_worst_leaf" in proc.stdout
