"""The latent_moe family through the harness's door, from new files only:
the manifest takes its configuration, cell and metrics; a rehearsal on the
CPU prints a contract line, passes sound and fails the family's controls;
its least-work counts follow what a step touched."""

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO), str(HERE)]

from benchmark import families, manifest  # noqa: E402
from benchmark.opcounts import latent_moe as ops  # noqa: E402
from benchmark.readers import latent_moe as readers  # noqa: E402
from test_bm_rehearsal import last_line, run, shape  # noqa: E402

CELL, TOY = "kanana2-docs-steady", "rehearsal-latent-moe-serve"
NEW_METRICS = ("moe_experts_touched_share", "decode_kv_read_over_live")
# files and readers kept, not in the manifest: the trace summary keeps ten
# operation names and no per-kernel totals, so a run can lack them (PERF.md
# section 7)
KEPT_OUT = ("moe_grouped_roofline", "mla_decode_roofline")


def test_manifest_takes_the_new_entries():
    bench = manifest.benchmark_json()
    assert manifest.check(bench) == []
    assert CELL in [w["name"] for w in bench["workloads"]]
    e2e, per = manifest.reported(bench, CELL)
    assert e2e == ["ttft_p90_ms", "tok_gap_p99_ms", "setup_s"]
    assert set(NEW_METRICS) <= set(per) and "decode_hbm_roofline" in per
    assert not set(KEPT_OUT) & {m["name"] for m in bench["per_layer"]}
    # the older cells report none of the new metrics
    for old in ("mistral7b-chat-steady", "mistral7b-train-1chip"):
        assert not set(NEW_METRICS) & set(manifest.reported(bench, old)[1])


def test_configuration_holds_the_published_widths():
    config = manifest.cell(CELL)["config_json"]
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "kv_lora_rank": 512, "q_lora_rank": None,
                 "intermediate_size": 6144, "moe_intermediate_size": 768,
                 "n_routed_experts": 128, "num_experts_per_tok": 6,
                 "n_shared_experts": 2, "first_k_dense_replace": 1,
                 "scoring_func": "sigmoid", "norm_topk_prob": True,
                 "routed_scaling_factor": 2.448, "vocab_size": 128256,
                 "rope_theta": 1000000, "rms_norm_eps": 1e-06}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 8
    assert sorted(config["reduced"]) == ["max_position_embeddings",
                                         "num_hidden_layers"]
    family = families.load(config, "serve")
    d = family.dims(config)
    assert family.layer_kinds(d) == ("dense",) + ("moe",) * 7
    assert set(manifest.cell(CELL)["controls"]) <= set(family.controls())
    # ISSUE 27's arithmetic: 36.05 M a layer outside the routed experts,
    # 4.72 M an expert, 5.07 G parameters held
    assert ops.attn_params(d) + ops.shared_params(d) + d["E"] * d["X"] \
        == 36_044_800
    assert ops.expert_params(d) == 4_718_592
    traffic = manifest.cell(CELL)["traffic_json"]
    dep = traffic["deployment"]
    assert (dep["max_slots"], dep["max_len"], dep["steps_per_call"],
            dep["prefill_chunk"], dep["admit_rows"]) == (32, 8192, 8, 8192, 1)
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 3000,
                                     "sigma": 0.6, "min": 1024, "max": 7168}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 160,
                                     "sigma": 0.7, "min": 16, "max": 512}
    assert traffic["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * traffic["arrivals"]["knee_per_s"])


def _ctx(touched, slots, positions):
    config = manifest.cell(CELL)["config_json"]
    return {"dims": families.load(config).dims(config), "config": config,
            "trace_live": {"positions": positions, "rows": 14.0},
            "trace_stats_delta": {"moe_experts_touched": touched,
                                  "moe_expert_slots": slots}}


def test_decode_step_bytes_count_what_was_touched_and_nothing_else():
    family = families.load(manifest.cell(CELL)["config_json"], "serve")
    step = 7 * 128                                  # slots of ONE step
    d = _ctx(0, step, 0)["dims"]
    none = family.decode_step_bytes(_ctx(0, 10 * step, 0.0))
    assert none == ops.fixed_weight_bytes(d)        # no expert, no position
    half = family.decode_step_bytes(_ctx(10 * 448, 10 * step, 0.0))
    full = family.decode_step_bytes(_ctx(10 * 896, 10 * step, 0.0))
    assert half - none == 448 * 2 * ops.expert_params(d)
    assert full - none == 896 * 2 * ops.expert_params(d)    # every expert
    deep = family.decode_step_bytes(_ctx(10 * 448, 10 * step, 56000.0))
    assert deep - half == 56000 * 8 * (512 + 64) * 2
    # nothing to read: the reader then leaves the metric out
    assert family.decode_step_bytes(
        {**_ctx(1, step, 1.0), "trace_live": None}) is None
    assert family.decode_step_bytes(
        {**_ctx(1, step, 1.0), "trace_stats_delta": {}}) is None


def test_new_readers_return_none_where_the_program_has_nothing():
    """The parent of this PR, or another family's cell: no counter, no
    kernel in the trace's list."""
    bare = {"stats_delta": {"steps": 5}, "trace_stats_delta": {"steps": 5},
            "trace": {"device_ops": [["%fusion.1", 0.5]]},
            "deployment": {"steps_per_call": 8}}
    for name in NEW_METRICS + KEPT_OUT:
        assert manifest.reader(name)(bare) is None, name
    assert readers.decode_kv_read_over_live(
        {"stats_delta": {"decode_kv_positions_live": 100,
                         "decode_kv_positions_read": 512}}) == 5.12
    assert readers.moe_experts_touched_share(
        {"stats_delta": {"moe_experts_touched": 448,
                         "moe_expert_slots": 896}}) == 50.0


def test_kernel_rooflines_read_the_trace_by_kernel_name():
    ctx = _ctx(4480, 8960, 56000.0)      # a span of 10 steps
    peaks = manifest.read("peaks.json")["TPU v5 lite"]
    ctx.update(peaks=peaks, deployment={"steps_per_call": 8})
    ctx["trace_stats_delta"].update(decode_kv_positions_live=7000)
    d = ctx["dims"]
    bytes_ = 4480 * 2 * ops.expert_params(d)
    secs = 2 * bytes_ / peaks["hbm_bytes_per_s"]
    ctx["trace"] = {"device_ops": [
        ["%moe_grouped_matmul.3", secs / 2], ["%moe_grouped_matmul.7",
                                              secs / 2],
        ["%latent_decode_attention.2", 1.0]]}
    assert readers.moe_grouped_roofline(ctx) == pytest.approx(50.0)
    least = 8 * 576 * 2 * 7000 * 8 / peaks["hbm_bytes_per_s"]
    assert readers.mla_decode_roofline(ctx) == pytest.approx(100.0 * least)


# ------------------------------------------------------- the rehearsal
@pytest.fixture(scope="module")
def toy_run():
    return run(["benchmark/run.py", "--workload", TOY, "--seed", "5",
                "--seconds", "5", "--trace", "1", "--rehearsal", "1",
                "--control", "1"])


def test_family_prints_a_contract_line(toy_run):
    line = last_line(toy_run)
    shape(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    assert {"loadgen_late_p90_ms", "launch_ready_s", "compile_s",
            "rows_per_step", "decode_kv_read_over_live"} <= set(
                line["metrics"])
    config = manifest.read(f"configs/{TOY}.json")
    assert config["family"] == "latent_moe"
    assert families.load(config, "serve").layer_kinds(
        families.load(config).dims(config)) == ("dense", "moe", "moe")


def test_family_passes_sound_and_fails_its_controls(toy_run):
    ref = last_line(toy_run)["reference"]
    limit = manifest.read(f"cells/{TOY}.json")["correct"]
    assert ref["served_tokens"] >= 100
    for control in ("fp8", "fp8_experts"):
        assert ref["gap_max"] <= limit["gap_max_limit"] < \
            ref[f"control_{control}_gap_max"]
        assert ref["gap_mean"] <= limit["gap_mean_limit"] < \
            ref[f"control_{control}_gap_mean"]
