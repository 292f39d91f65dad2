"""The window_moe family through the harness's door, from new files only:
the manifest takes its configuration, cell and metrics; the configuration
restates the published widths key by key; a rehearsal on the CPU prints a
contract line, passes sound, fails both of the family's controls and fails
two broken window paths; its least-work counts follow what a step touched."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO), str(HERE)]

from benchmark import families, manifest  # noqa: E402
from benchmark.opcounts import window_moe as ops  # noqa: E402
from benchmark.readers import window_moe as readers  # noqa: E402
from test_bm_rehearsal import last_line, run, shape  # noqa: E402

CELL, TOY = "smallthinker-mixed-steady", "rehearsal-window-moe-serve"
NEW_METRICS = ("decode_window_read_over_live",
               "prefill_window_blocks_over_band")
# file and reader kept, not in the manifest: the trace summary keeps ten
# operation names (PERF.md section 5 has their readings)
KEPT_OUT = ("window_prefill_roofline", "ragged_decode_roofline")
ARCHITECTURES = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PERIOD = [0, 1, 1, 1]


def test_manifest_takes_the_new_entries():
    bench = manifest.benchmark_json()
    assert manifest.check(bench) == []
    assert CELL in [w["name"] for w in bench["workloads"]]
    e2e, per = manifest.reported(bench, CELL)
    assert e2e == ["ttft_p90_ms", "tok_gap_p99_ms", "setup_s"]
    assert set(NEW_METRICS) <= set(per)
    assert {"decode_hbm_roofline", "decode_kv_read_over_live",
            "moe_experts_touched_share", "decode_step_dev_ms",
            "prefill_chunk_dev_ms", "device_idle.steady"} <= set(per)
    # every serving metric the three older serving cells all report
    older = {"mistral7b-chat-steady", "kanana2-docs-steady",
             "olmohybrid-rag-steady"}
    shared = {m["name"] for m in bench["per_layer"]
              if older <= set(m.get("workloads", []))}
    assert shared <= set(per)
    assert not set(KEPT_OUT) & {m["name"] for m in bench["per_layer"]}
    for old in sorted(older) + ["mistral7b-train-1chip"]:
        assert not set(NEW_METRICS) & set(manifest.reported(bench, old)[1])


PUBLISHED = {"hidden_size": 2560, "num_attention_heads": 28,
             "num_key_value_heads": 4, "head_dim": 128,
             "moe_ffn_hidden_size": 768, "moe_num_primary_experts": 64,
             "moe_num_active_primary_experts": 6,
             "moe_primary_router_apply_softmax": True,
             "norm_topk_prob": True, "sliding_window_size": 4096,
             "sliding_window_layout": PERIOD * 13,
             "rope_layout": PERIOD * 13, "rope_theta": 1500000,
             "rope_scaling": None, "rms_norm_eps": 1e-06,
             "vocab_size": 151936, "max_position_embeddings": 16384,
             "tie_word_embeddings": False,
             "model_name": "smallthinker_21b_instruct"}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_width(key):
    config = manifest.cell(CELL)["config_json"]
    assert config[key] == PUBLISHED[key]


def test_configuration_is_the_catalog_row_cut_in_depth_only():
    config = manifest.cell(CELL)["config_json"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8
    assert config["published"] == {"num_hidden_layers": 52}
    assert config["assumed"] and config["assumed_why"]
    assert config["router_input"] == "layer_input"
    assert "first of seven pipeline stages" in config["stands_for"]
    assert (config["kv_dtype"], config["weights_dtype"],
            config["chips"]) == ("bf16", "bfloat16", 1)
    if ARCHITECTURES.is_file():
        row = next(json.loads(line) for line in ARCHITECTURES.open()
                   if '"SmallThinker-21BA3B-Instruct"' in line)
        assert config["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items()
                         if config.get(k) != v)
        assert differs == config["reduced"]
    family = families.load(config, "serve")
    d = family.dims(config)
    # two whole periods: one NoPE full layer, three RoPE window layers
    assert family.layer_kinds(d) == (
        ("full_attention",) + ("window_attention",) * 3) * 2
    assert set(manifest.cell(CELL)["controls"]) <= set(family.controls())
    # ISSUE 40's arithmetic: 20.97 M of attention a layer, 5.898 M an
    # expert, 398.6 M a layer; 2048 B of K/V a position a layer
    assert ops.attn_params(d) == 20_971_520
    assert ops.expert_params(d) == 5_898_240
    assert (ops.attn_params(d) + d["E"] * d["X"]
            + d["X"] * ops.expert_params(d)) == 398_622_720
    assert ops.kv_bytes_per_position(d) == 2048
    assert (ops.layers(d, ops.FULL), ops.layers(d, ops.WINDOW)) == (2, 6)


def test_a_layout_that_rotates_other_layers_than_the_window_is_refused():
    config = dict(manifest.cell(CELL)["config_json"])
    config["rope_layout"] = [1] * 52
    with pytest.raises(ValueError, match="rope_layout"):
        families.load(config).dims(config)


def test_cell_is_the_traffic_and_deployment_the_issue_gives():
    traffic = manifest.cell(CELL)["traffic_json"]
    dep = traffic["deployment"]
    assert (dep["max_len"], dep["steps_per_call"], dep["prefill_chunk"],
            dep["admit_rows"]) == (16384, 8, 16384, 1)
    assert dep["max_slots"] in (32, 24, 16)
    chat = manifest.read("traffic/chat-steady.json")["deployment"]
    assert dep["env"] == chat["env"]
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 3000,
                                     "sigma": 1.0, "min": 256, "max": 15360}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 192,
                                     "sigma": 0.7, "min": 16, "max": 640}
    assert (traffic["loop"], traffic["sampling"], traffic["ramp_s"],
            traffic["drain_s"]) == ("open", "greedy", 6.0, 10.0)
    assert traffic["arrivals"]["gaps"] == {"dist": "exponential"}
    share = (traffic["arrivals"]["rate_per_s"]
             / traffic["arrivals"]["knee_per_s"])
    assert 0.7 - 1e-9 <= share <= 0.8 + 1e-9
    assert traffic["trace"] == {"start_s": 12.0, "seconds": 4.0}
    assert traffic["correct"]["reference_buckets"][-1] == 16384
    # every bucket a prompt of the mix can take is warmed
    from kubetorch_tpu.models.rolling import _bucket

    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    reach = {_bucket(n) for n in range(lo, hi + 1, 64)} | {_bucket(hi)}
    assert {_bucket(n) for _, n in traffic["warm"]} == reach == {
        256, 512, 1024, 2048, 4096, 8192, 16384}
    assert all(rows == 1 for rows, _ in traffic["warm"])


def _ctx(**delta):
    config = manifest.cell(CELL)["config_json"]
    return {"dims": families.load(config).dims(config), "config": config,
            "deployment": {"steps_per_call": 8},
            "trace_live": {"positions": 60000.0, "rows": 12.0},
            "trace_stats_delta": delta}


def test_decode_step_bytes_count_what_the_step_touched():
    family = families.load(manifest.cell(CELL)["config_json"], "serve")
    d = _ctx()["dims"]
    fixed = ops.fixed_weight_bytes(d)
    # attention of 8 layers and the head in bf16, the routers in float32
    assert fixed == 2 * (8 * 20_971_520 + 2560 * 151936) + 4 * 8 * 2560 * 64
    # 40 decode steps: 330 experts touched a step over the 8 layers; the
    # rows' positions are 60000, of which the windows hold 60%
    ctx = _ctx(moe_expert_slots=40 * 8 * 64, moe_experts_touched=40 * 330,
               decode_kv_positions_live=100, decode_window_positions_live=60)
    got = family.decode_step_bytes(ctx)
    assert got == pytest.approx(
        fixed + 330 * 2 * 5_898_240
        + 2048 * (2 * 60000.0 + 6 * 36000.0))
    assert family.decode_step_bytes(_ctx()) is None
    assert family.decode_step_bytes({**ctx, "trace_live": None}) is None


def test_prefill_flops_count_the_band():
    d = _ctx()["dims"]
    assert ops.band_pairs(100, 4096) == 100 * 101 / 2
    assert ops.band_pairs(16384, 4096) == 4096 * 4097 / 2 + 12288 * 4096
    # ISSUE 40's reckoning of the 16384 call: 14.8 TFLOP of products, 3.9
    # of full attention, 5.0 of banded (11.5 if the band were not skipped)
    n = 16384
    tri = n * (n + 1) / 2
    whole = ops.prefill_flops(d, n, tri, ops.band_pairs(n, d["W"]))
    products = ops.prefill_flops(d, n, 0, 0)
    assert products == pytest.approx(14.8e12, rel=0.02)
    assert ops.prefill_flops(d, 0, tri, 0) == pytest.approx(3.85e12, rel=0.02)
    assert ops.prefill_flops(d, 0, 0, ops.band_pairs(n, d["W"])) == \
        pytest.approx(4.98e12, rel=0.02)
    assert ops.prefill_flops(d, 0, 0, tri) == pytest.approx(11.5e12, rel=0.02)
    assert whole == pytest.approx(23.6e12, rel=0.02)


def test_new_readers_return_none_where_the_program_has_nothing():
    """The parent of this PR, or another family's cell: no counter, no
    kernel in the trace's list."""
    bare = {"stats_delta": {"steps": 5}, "trace_stats_delta": {"steps": 5},
            "trace": {"device_ops": [["%fusion.1", 0.5]]},
            "deployment": {"steps_per_call": 8}}
    for name in NEW_METRICS + KEPT_OUT:
        assert manifest.reader(name)(bare) is None, name
    assert readers.decode_window_read_over_live(
        {"stats_delta": {"decode_window_positions_live": 1000,
                         "decode_window_positions_read": 1100}}) == 1.1
    assert readers.prefill_window_blocks_over_band(
        {"stats_delta": {"prefill_window_key_blocks": 70,
                         "prefill_window_key_blocks_band": 70}}) == 1.0


def test_kernel_rooflines_read_the_trace_by_kernel_name():
    peaks = manifest.read("peaks.json")["TPU v5 lite"]
    ctx = _ctx(decode_kv_positions_live=50000,
               decode_window_positions_live=30000)
    d = ctx["dims"]
    least = 2048 * 8 * (2 * 50000 + 6 * 30000) / peaks["hbm_bytes_per_s"]
    assert ops.ragged_decode_least_seconds(
        d, peaks, 8 * 50000, 8 * 30000) == pytest.approx(least)
    ctx.update(peaks=peaks, trace={"device_ops": [
        ["%ragged_decode_attention.3", least],
        ["%ragged_decode_attention.5", 3 * least], ["%fusion.1", 1.0]]})
    assert readers.ragged_decode_roofline(ctx) == pytest.approx(25.0)

    class Rec:
        def __init__(self, n):
            self.prompt_len = n

    records = [Rec(1000), Rec(6000), Rec(12000)]
    pairs = ops.band_pairs(6000, 4096) + ops.band_pairs(12000, 4096)
    least = 4.0 * 28 * 128 * 6 * pairs / peaks["bf16_flops"]
    ctx.update(records=records,
               trace_stats_delta={"prefill_tokens_executed": 19000},
               trace={"device_ops": [["%admit_window_attention.2",
                                      2 * least]]})
    assert readers.window_prefill_roofline(ctx) == pytest.approx(50.0)


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
    # the einsum pair streams every slot's ring; a toy bucket is one block
    assert line["metrics"]["decode_window_read_over_live"]["value"] >= 1.0
    assert line["metrics"]["prefill_window_blocks_over_band"]["value"] == 1.0
    config = manifest.read(f"configs/{TOY}.json")
    assert config["family"] == "window_moe"
    assert families.load(config, "serve").layer_kinds(
        families.load(config).dims(config)) == (
            ("full_attention",) + ("window_attention",) * 3
            + ("full_attention",))
    # every scored row is longer than the window: its rings have wrapped
    assert config["sliding_window_size"] == 16
    traffic = manifest.read(f"traffic/{TOY.replace('serve', 'open')}.json")
    assert traffic["output_len"]["min"] > 2 * config["sliding_window_size"]


def test_family_passes_sound_and_fails_its_controls(toy_run):
    ref = last_line(toy_run)["reference"]
    limit = manifest.read(f"cells/{TOY}.json")["correct"]
    assert ref["served_tokens"] >= 100
    for control in ("fp8", "fp8_experts"):
        assert ref["gap_max"] <= limit["gap_max_limit"] < \
            ref[f"control_{control}_gap_max"]
        assert ref["gap_mean"] <= limit["gap_mean_limit"] < \
            ref[f"control_{control}_gap_mean"]


@pytest.mark.parametrize("broken", ["window_ignored", "ring_not_wrapped"])
def test_a_broken_window_path_is_not_correct(broken):
    proc = run([str(HERE / "bm_drive_broken_window.py"), broken])
    line = last_line(proc)
    shape(line)
    assert line["correct"] is False and line["failed"] == 0
    failed = [ln for ln in proc.stdout.splitlines() if "FAILED" in ln]
    assert any("served_token_gap_max_logits" in ln for ln in failed)
    assert any("served_token_gap_mean_logits" in ln for ln in failed)
