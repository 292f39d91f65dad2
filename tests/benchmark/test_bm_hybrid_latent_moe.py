"""The hybrid_latent_moe family through the harness's door, from new files
only: the manifest takes its configuration, cell and metrics; the
configuration is the catalog row cut in depth, experts held, vocabulary and
positions, and no width; a rehearsal on the CPU prints a contract line,
passes sound, fails the family's controls, fails a decode chunk that does not
carry the recurrent state and fails a program that ignores its share of the
experts; its least-work counts follow the live rows, positions and touched
experts; its readers find nothing in a program that has nothing."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO), str(HERE)]

from benchmark import families, manifest  # noqa: E402
from benchmark.opcounts import hybrid_latent_moe as ops  # noqa: E402
from benchmark.readers import hybrid_latent_moe as readers  # noqa: E402
from test_bm_rehearsal import last_line, run, shape  # noqa: E402

CELL, TOY = "ling3-reason-steady", "rehearsal-hybrid-latent-moe-serve"
NEW_METRICS = ("kda_prefill_roofline", "kda_step_roofline",
               "moe_held_over_routed")
SHARED = ("moe_experts_touched_share", "decode_kv_read_over_live",
          "decode_state_rows_over_live", "prefill_scan_over_prompt",
          "decode_hbm_roofline")
ARCHITECTURES = Path("/opt/skills/guides/model-configs/architectures.jsonl")
REDUCED = {"num_hidden_layers": (42, 7), "first_k_dense_replace": (2, 1),
           "num_experts": (512, 128), "vocab_size": (157184, 39296),
           "max_position_embeddings": (262144, 32768)}


def test_manifest_takes_the_new_entries():
    bench = manifest.benchmark_json()
    assert manifest.check(bench) == []
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert bench["configs"][-1]["name"] == "ling-3.0-flash-bf16-serve"
    e2e, per = manifest.reported(bench, CELL)
    assert e2e == ["ttft_p90_ms", "tok_gap_p99_ms", "setup_s"]
    assert set(SHARED) <= set(per)
    # every serving metric the five older serving cells all report
    five = {"mistral7b-chat-steady", "kanana2-docs-steady",
            "olmohybrid-rag-steady", "smallthinker-mixed-steady",
            "keye2-longctx-steady"}
    assert {m["name"] for m in bench["per_layer"]
            if five <= set(m.get("workloads", []))} <= set(per)
    listed = {m["name"] for m in bench["per_layer"]}
    for name in NEW_METRICS:
        # a file and a reader each; in the manifest, for this cell alone
        manifest.read(f"metrics/{name}.json")
        manifest.reader(name)
        if name in listed:
            assert name in per
            assert next(m for m in bench["per_layer"]
                        if m["name"] == name)["workloads"] == [CELL]
    assert "moe_held_over_routed" in per
    for old in sorted(five) + ["mistral7b-train-1chip"]:
        assert not set(NEW_METRICS) & set(manifest.reported(bench, old)[1])


def _catalog_row():
    if not ARCHITECTURES.is_file():
        pytest.skip("no catalog beside the model-configs guide here")
    return next(json.loads(line) for line in ARCHITECTURES.open()
                if '"name": "Ling-3.0-flash"' in line)


def test_configuration_is_the_catalog_row_and_no_width_is_cut():
    config = manifest.cell(CELL)["config_json"]
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key, (published, run_) in REDUCED.items():
        assert config["published"][key] == published and config[key] == run_
    row = _catalog_row()
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k) != v)
    assert differs == sorted(REDUCED)
    # the floors: a whole period after the dense layers, >= 8 experts held,
    # >= an eighth of the vocabulary
    assert config["layers_run"] == [1, 6, 7, 8, 9, 10, 11]
    assert config["experts_held"] == [0, 128]
    assert config["vocab_size"] * 8 >= row["config"]["vocab_size"]
    for key in ("assumed", "assumed_why", "departures", "stands_for"):
        assert config[key], key
    assert "four chips share each layer" in config["stands_for"]
    for name in config["assumed"]:
        assert name in config, name
    assert (config["kv_dtype"], config["chips"], config["family"]) == (
        "bf16", 1, "hybrid_latent_moe")


@pytest.mark.parametrize("key,value", sorted({
    "hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128,
    "kv_lora_rank": 512, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
    "v_head_dim": 128, "moe_intermediate_size": 768,
    "moe_shared_expert_intermediate_size": 768, "intermediate_size": 6144,
    "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "layer_group_size": 6, "routed_scaling_factor": 2.5,
    "rope_theta": 6000000}.items()))
def test_configuration_holds_the_published_width(key, value):
    assert manifest.cell(CELL)["config_json"][key] == value


def test_family_reads_the_configuration_as_the_issue_sized_it():
    config = manifest.cell(CELL)["config_json"]
    family = families.load(config, "serve")
    d = family.dims(config)
    assert family.layer_kinds(d) == ("kda_dense",) + ("kda_moe",) * 5 + (
        "mla_moe",)
    assert (d["Xr"], d["X"], d["first"], d["G"], d["Gk"], d["Kx"]) == (
        512, 128, 0, 8, 4, 8)
    assert set(manifest.cell(CELL)["controls"]) <= set(family.controls())
    # ISSUE 47's arithmetic: a KDA mixer 63.0 M, the MLA mixer 32.0 M, an
    # expert 5.90 M; 12.6 MB of state a row, 1280 B of latent a position
    assert ops.kda_params(d) == 63_045_632
    assert ops.mla_params(d) == 31_965_184
    assert ops.expert_params(d) == 5_898_240
    assert ops.state_bytes_per_row(d) == 6 * 32 * 128 * 128 * 4
    assert ops.latent_bytes_per_position(d) == 1152
    # a swiglu limit in a layer run is refused by key, as the program's
    # configuration object refuses it
    clamped = {**config, "layers_run": [1, 36, 37, 38, 39, 40, 41]}
    with pytest.raises(ValueError, match="swiglu_limit"):
        family.program_config(clamped, "serve", {"max_len": 64})


def test_cell_is_the_traffic_and_deployment_the_issue_gives():
    traffic = manifest.cell(CELL)["traffic_json"]
    dep = traffic["deployment"]
    assert (dep["max_slots"], dep["max_len"], dep["steps_per_call"],
            dep["prefill_chunk"], dep["admit_rows"]) == (32, 32768, 8, 32768,
                                                         1)
    keye = manifest.read("traffic/longctx-steady.json")
    assert dep["env"] == keye["deployment"]["env"]
    assert traffic["trace"] == keye["trace"]
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                     "sigma": 1.0, "min": 256, "max": 30720}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 0.6, "min": 64, "max": 1536}
    assert (traffic["loop"], traffic["sampling"], traffic["ramp_s"],
            traffic["drain_s"]) == ("open", "greedy", 6.0, 10.0)
    assert traffic["arrivals"]["rate_per_s"] == pytest.approx(
        0.7 * traffic["arrivals"]["knee_per_s"])
    from kubetorch_tpu.models.rolling import _bucket

    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    reach = {_bucket(n) for n in range(lo, hi + 1, 64)} | {_bucket(hi)}
    assert {_bucket(n) for _, n in traffic["warm"]} == reach == {
        256, 512, 1024, 2048, 4096, 8192, 16384, 32768}


def _ctx(positions, rows, touched=0.0, steps=8):
    config = manifest.cell(CELL)["config_json"]
    d = families.load(config).dims(config)
    return {"dims": d, "config": config,
            "trace_live": {"positions": positions, "rows": rows},
            "trace_stats_delta": {
                "moe_expert_slots": steps * d["X"] * 6,
                "moe_experts_touched": steps * touched}}


def test_decode_step_bytes_count_rows_positions_and_touched_experts():
    family = families.load(manifest.cell(CELL)["config_json"], "serve")
    d = _ctx(0, 0)["dims"]
    none = family.decode_step_bytes(_ctx(0.0, 0.0))
    assert none == ops.fixed_weight_bytes(d) == 1_218_281_472
    deep = family.decode_step_bytes(_ctx(40000.0, 0.0))
    assert deep - none == 40000 * 1152
    busy = family.decode_step_bytes(_ctx(40000.0, 20.0))
    assert busy - deep == 20 * 2 * ops.state_bytes_per_row(d)
    routed = family.decode_step_bytes(_ctx(40000.0, 20.0, touched=300.0))
    assert routed - busy == 300 * 2 * ops.expert_params(d)
    assert family.decode_step_bytes(
        {**_ctx(1.0, 1.0), "trace_live": None}) is None


def test_new_readers_return_none_where_the_program_has_nothing():
    """The parent of this PR, or another family's cell: no counter, no
    kernel in the trace's list."""
    bare = {"stats_delta": {"steps": 5}, "trace_stats_delta": {"steps": 5},
            "trace": {"device_ops": [["%fusion.1", 0.5]]},
            "deployment": {"steps_per_call": 8}}
    for name in NEW_METRICS:
        assert manifest.reader(name)(bare) is None, name
        assert manifest.reader(name)({}) is None, name
    assert readers.moe_held_over_routed(
        {"stats_delta": {"moe_assignments_step": 4800,
                         "moe_assignments_held": 1200,
                         "moe_assignments": 99999}}) == 0.25


def test_kernel_rooflines_read_the_trace_by_kernel_name():
    ctx = _ctx(0.0, 0.0)
    peaks = manifest.read("peaks.json")["TPU v5 lite"]
    d = ctx["dims"]
    tokens, row_steps = 9000, 160
    # bytes bind the scan: 32 heads x ((128 x 4) x 2 + 128 x 4 + 4) B a
    # token against 6 x 32 x 128 x 128 flops
    per_token = 32 * 1540 / peaks["hbm_bytes_per_s"]
    assert per_token > 6 * 32 * 128 * 128 / peaks["bf16_flops"]
    scan = 6 * tokens * per_token
    assert ops.kda_prefill_least_seconds(d, peaks, tokens) == pytest.approx(
        scan)
    step = row_steps * 2 * 6 * 32 * 128 * 128 * 4 / peaks["hbm_bytes_per_s"]
    assert ops.kda_step_least_seconds(d, peaks, row_steps) == pytest.approx(
        step)
    ctx.update(peaks=peaks,
               trace_stats_delta={"linear_scan_prompt_tokens": tokens,
                                  "decode_state_rows_live": row_steps},
               trace={"device_ops": [["%kda_prefill.3", scan],
                                     ["%kda_prefill.9", 3 * scan],
                                     ["%kda_step.2", 2 * step],
                                     ["%fusion.1", 1.0]]})
    assert readers.kda_prefill_roofline(ctx) == pytest.approx(25.0)
    assert readers.kda_step_roofline(ctx) == pytest.approx(50.0)


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
            "rows_per_step", "decode_kv_read_over_live",
            "decode_state_rows_over_live", "prefill_scan_over_prompt",
            "moe_held_over_routed"} <= set(line["metrics"])
    # every expert is held in the toy, every row of the grid is carried
    assert line["metrics"]["moe_held_over_routed"]["value"] == 1.0
    assert line["metrics"]["decode_state_rows_over_live"]["value"] >= 1.0
    config = manifest.read(f"configs/{TOY}.json")
    assert config["family"] == "hybrid_latent_moe"
    assert families.load(config, "serve").layer_kinds(
        families.load(config).dims(config)) == (
            "kda_dense", "kda_moe", "kda_moe", "mla_moe", "kda_moe")
    # the mix takes both admissions: bucketed and chunked
    assert "\"prefill_chunks\": 0" not in toy_run.stdout


@pytest.mark.parametrize("control", ["fp8", "scalar_decay", "ungrouped"])
def test_family_passes_sound_and_fails_its_controls(toy_run, control):
    ref = last_line(toy_run)["reference"]
    limit = manifest.read(f"cells/{TOY}.json")["correct"]
    assert ref["served_tokens"] >= 100
    assert ref["gap_max"] <= limit["gap_max_limit"] < \
        ref[f"control_{control}_gap_max"]
    assert ref["gap_mean"] <= limit["gap_mean_limit"] < \
        ref[f"control_{control}_gap_mean"]


@pytest.mark.parametrize("broken", ["state", "share"])
def test_a_broken_server_is_not_correct(broken):
    """The state not carried across a decode chunk; the share of the experts
    ignored (which the SOUND program, serving the same share, passes)."""
    proc = run([str(HERE / "bm_drive_broken_kda.py"), broken])
    line = last_line(proc)
    shape(line)
    assert line["correct"] is False and line["failed"] == 0
    failed = [ln for ln in proc.stdout.splitlines() if "FAILED" in ln]
    assert any("served_token_gap_max_logits" in ln for ln in failed)
    assert any("served_token_gap_mean_logits" in ln for ln in failed)


def test_the_sound_program_serves_a_share_correctly():
    line = last_line(run([str(HERE / "bm_drive_broken_kda.py"),
                          "share_sound"]))
    shape(line)
    assert line["correct"] is True and line["failed"] == 0


def test_the_parent_refuses_the_cells_family_at_once(tmp_path):
    """What the driver's first try of the new cell on the parent meets: the
    family's ``dims`` raises where the program has no such decoder."""
    from benchmark.families import hybrid_latent_moe as family

    config = manifest.cell(CELL)["config_json"]
    gone = family.PROGRAM_FILE
    try:
        family.PROGRAM_FILE = tmp_path / "no_such_decoder.py"
        with pytest.raises(LookupError, match="hybrid_latent_moe.py"):
            family.dims(config)
    finally:
        family.PROGRAM_FILE = gone
