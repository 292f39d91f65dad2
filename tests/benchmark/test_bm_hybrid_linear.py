"""The hybrid_linear family through the harness's door, from new files only:
the manifest takes its configuration, cell and metrics; a rehearsal on the
CPU prints a contract line, passes sound, fails the family's controls and
fails a decode chunk that does not carry the recurrent state; its least-work
counts follow the live rows and positions."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO), str(HERE)]

from benchmark import families, manifest  # noqa: E402
from benchmark.opcounts import hybrid_linear as ops  # noqa: E402
from benchmark.readers import hybrid_linear as readers  # noqa: E402
from test_bm_rehearsal import last_line, run, shape  # noqa: E402

CELL, TOY = "olmohybrid-rag-steady", "rehearsal-hybrid-linear-serve"
NEW_METRICS = ("decode_state_rows_over_live", "prefill_scan_over_prompt")
# file and reader kept, not in the manifest: the trace summary keeps ten
# operation names and the kernel has a call site a bucket (PERF.md section 7)
KEPT_OUT = ("gated_delta_prefill_roofline",)
ARCHITECTURES = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_manifest_takes_the_new_entries():
    bench = manifest.benchmark_json()
    assert manifest.check(bench) == []
    assert CELL in [w["name"] for w in bench["workloads"]]
    e2e, per = manifest.reported(bench, CELL)
    assert e2e == ["ttft_p90_ms", "tok_gap_p99_ms", "setup_s"]
    assert set(NEW_METRICS) <= set(per)
    assert {"decode_hbm_roofline", "decode_kv_read_over_live",
            "decode_step_dev_ms", "prefill_chunk_dev_ms",
            "device_idle.steady"} <= set(per)
    # every serving metric the two older serving cells both report
    both = {m["name"] for m in bench["per_layer"]
            if {"mistral7b-chat-steady", "kanana2-docs-steady"}
            <= set(m.get("workloads", []))}
    assert both <= set(per)
    assert not set(KEPT_OUT) & {m["name"] for m in bench["per_layer"]}
    for old in ("mistral7b-chat-steady", "mistral7b-train-1chip",
                "kanana2-docs-steady"):
        assert not set(NEW_METRICS) & set(manifest.reported(bench, old)[1])


PUBLISHED = {"hidden_size": 3840, "num_attention_heads": 30,
             "num_key_value_heads": 30, "intermediate_size": 11008,
             "linear_num_key_heads": 30, "linear_num_value_heads": 30,
             "linear_key_head_dim": 96, "linear_value_head_dim": 192,
             "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
             "vocab_size": 100352, "rms_norm_eps": 1e-06,
             "attention_bias": False, "tie_word_embeddings": False,
             "hidden_act": "silu", "model_type": "olmo_hybrid",
             "rope_parameters": {"rope_theta": None}}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_width(key):
    config = manifest.cell(CELL)["config_json"]
    assert config[key] == PUBLISHED[key]


def test_configuration_is_the_catalog_row_cut_in_depth_and_positions_only():
    config = manifest.cell(CELL)["config_json"]
    assert sorted(config["reduced"]) == ["max_position_embeddings",
                                         "num_hidden_layers"]
    assert (config["num_hidden_layers"],
            config["max_position_embeddings"]) == (16, 4096)
    assert config["published"] == {"num_hidden_layers": 32,
                                   "max_position_embeddings": 65536}
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert config["layer_types"] == period * 8        # the list whole
    assert config["assumed"] and config["assumed_why"]
    assert "two sixteen-layer pipeline stages" in config["stands_for"]
    assert (config["state_dtype"], config["kv_dtype"],
            config["chips"]) == ("float32", "bf16", 1)
    if ARCHITECTURES.is_file():
        row = next(json.loads(line) for line in ARCHITECTURES.open()
                   if '"Olmo-Hybrid-7B"' in line)
        assert config["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items()
                         if config.get(k) != v)
        assert differs == sorted(config["reduced"])
    family = families.load(config, "serve")
    d = family.dims(config)
    assert family.layer_kinds(d) == tuple(period * 4)      # the layers run
    assert d["D"] == 128
    assert set(manifest.cell(CELL)["controls"]) <= set(family.controls())
    # ISSUE 31's arithmetic: 88.75 M a linear mixer, 4 x 14.75 M a full
    # one, 126.81 M a SwiGLU; 61440 B of K/V a position, 26.5 MB of state
    assert ops.linear_params(d) == 88_750_080
    assert ops.full_params(d) == 4 * 14_745_600
    assert ops.mlp_params(d) == 126_812_160
    assert ops.kv_bytes_per_position(d) == 61440
    assert ops.state_bytes_per_row(d) == 12 * 30 * 96 * 192 * 4


def test_cell_is_the_traffic_and_deployment_the_issue_gives():
    traffic = manifest.cell(CELL)["traffic_json"]
    dep = traffic["deployment"]
    assert (dep["max_slots"], dep["max_len"], dep["steps_per_call"],
            dep["prefill_chunk"], dep["admit_rows"]) == (16, 4096, 8, 4096, 1)
    chat = manifest.read("traffic/chat-steady.json")["deployment"]
    assert dep["env"] == chat["env"]
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 1200,
                                     "sigma": 0.6, "min": 256, "max": 3584}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 128,
                                     "sigma": 0.7, "min": 16, "max": 448}
    assert (traffic["loop"], traffic["sampling"], traffic["ramp_s"],
            traffic["drain_s"]) == ("open", "greedy", 6.0, 10.0)
    assert traffic["arrivals"]["gaps"] == {"dist": "exponential"}
    assert traffic["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * traffic["arrivals"]["knee_per_s"])
    assert traffic["trace"] == {"start_s": 12.0, "seconds": 4.0}
    assert traffic["correct"] == {"sample": 4, "reference_buckets": [
        1024, 2048, 4096]}
    # every bucket a prompt of the mix can take is warmed
    from kubetorch_tpu.models.rolling import _bucket

    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    reach = {_bucket(n) for n in range(lo, hi + 1, 64)} | {_bucket(hi)}
    assert {_bucket(n) for _, n in traffic["warm"]} == reach == {
        256, 512, 1024, 2048, 4096}


def _ctx(positions, rows):
    config = manifest.cell(CELL)["config_json"]
    return {"dims": families.load(config).dims(config), "config": config,
            "trace_live": {"positions": positions, "rows": rows}}


def test_decode_step_bytes_count_the_live_rows_and_positions():
    family = families.load(manifest.cell(CELL)["config_json"], "serve")
    d = _ctx(0, 0)["dims"]
    none = family.decode_step_bytes(_ctx(0.0, 0.0))
    assert none == ops.weight_bytes(d) == 7_430_553_600
    deep = family.decode_step_bytes(_ctx(15000.0, 0.0))
    assert deep - none == 15000 * 61440
    busy = family.decode_step_bytes(_ctx(15000.0, 10.0))
    assert busy - deep == 10 * 2 * ops.state_bytes_per_row(d)  # read + write
    assert family.decode_step_bytes(
        {**_ctx(1.0, 1.0), "trace_live": None}) is None


def test_new_readers_return_none_where_the_program_has_nothing():
    """The parent of this PR, or another family's cell: no counter, no
    kernel in the trace's list."""
    bare = {"stats_delta": {"steps": 5}, "trace_stats_delta": {"steps": 5},
            "trace": {"device_ops": [["%fusion.1", 0.5]]},
            "deployment": {"steps_per_call": 8}}
    for name in NEW_METRICS + KEPT_OUT:
        assert manifest.reader(name)(bare) is None, name
    assert readers.decode_state_rows_over_live(
        {"stats_delta": {"decode_state_rows_live": 80,
                         "decode_state_rows_touched": 128}}) == 1.6
    assert readers.prefill_scan_over_prompt(
        {"stats_delta": {"linear_scan_positions": 2048,
                         "linear_scan_prompt_tokens": 1280}}) == 1.6


def test_kernel_roofline_reads_the_trace_by_kernel_name():
    ctx = _ctx(0.0, 0.0)
    peaks = manifest.read("peaks.json")["TPU v5 lite"]
    d = ctx["dims"]
    tokens = 5000
    # bytes bind: 30 heads x ((96 + 96 + 192 + 192) x 2 + 8) B a token
    # against 6 x 30 x 96 x 192 flops
    per_token = max(6 * 30 * 96 * 192 / peaks["bf16_flops"],
                    30 * 1160 / peaks["hbm_bytes_per_s"])
    assert per_token == 30 * 1160 / peaks["hbm_bytes_per_s"]
    least = 12 * tokens * per_token
    assert ops.gated_delta_least_seconds(d, peaks, tokens) == pytest.approx(
        least)
    ctx.update(peaks=peaks,
               trace_stats_delta={"linear_scan_prompt_tokens": tokens},
               trace={"device_ops": [["%gated_delta_prefill.3", least],
                                     ["%gated_delta_prefill.9", 3 * least],
                                     ["%fusion.1", 1.0]]})
    assert readers.gated_delta_prefill_roofline(ctx) == pytest.approx(25.0)


# ------------------------------------------------------- the rehearsal
@pytest.fixture(scope="module")
def toy_run():
    return run(["benchmark/run.py", "--workload", TOY, "--seed",
                str(2**31 + 5), "--seconds", "5", "--trace", "1",
                "--rehearsal", "1", "--control", "1"])


def test_family_prints_a_contract_line(toy_run):
    line = last_line(toy_run)
    shape(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    assert {"loadgen_late_p90_ms", "launch_ready_s", "compile_s",
            "rows_per_step", "decode_kv_read_over_live"} | set(
                NEW_METRICS) <= set(line["metrics"])
    # every row of the grid is carried, a bucket is a power of two
    assert line["metrics"]["decode_state_rows_over_live"]["value"] >= 1.0
    assert line["metrics"]["prefill_scan_over_prompt"]["value"] >= 1.0
    config = manifest.read(f"configs/{TOY}.json")
    assert config["family"] == "hybrid_linear"
    assert families.load(config, "serve").layer_kinds(
        families.load(config).dims(config)) == (
            ("linear_attention",) * 3 + ("full_attention",)
            + ("linear_attention",) * 2)
    # the mix takes both admissions: bucketed and chunked
    assert "\"prefill_chunks\": 0" not in toy_run.stdout


def test_family_passes_sound_and_fails_its_controls(toy_run):
    ref = last_line(toy_run)["reference"]
    limit = manifest.read(f"cells/{TOY}.json")["correct"]
    assert ref["served_tokens"] >= 100
    for control in ("fp8", "state_bf16"):
        assert ref["gap_max"] <= limit["gap_max_limit"] < \
            ref[f"control_{control}_gap_max"]
        assert ref["gap_mean"] <= limit["gap_mean_limit"] < \
            ref[f"control_{control}_gap_mean"]


def test_state_not_carried_across_a_chunk_is_not_correct():
    proc = run([str(HERE / "bm_drive_broken_state.py")])
    line = last_line(proc)
    shape(line)
    assert line["correct"] is False and line["failed"] == 0
    failed = [ln for ln in proc.stdout.splitlines() if "FAILED" in ln]
    assert any("served_token_gap_max_logits" in ln for ln in failed)
    assert any("served_token_gap_mean_logits" in ln for ln in failed)
