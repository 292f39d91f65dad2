"""The indexed_moe family through the harness's door, from new files only:
the manifest takes its configuration, cell and metrics; the configuration
restates the published widths key by key; a rehearsal on the CPU prints a
contract line, passes sound, fails both of the family's controls (a lower
precision and a wrong CHOICE) and fails two broken choice paths; its
least-work counts follow what a step touched and chose."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO), str(HERE)]

from benchmark import families, manifest  # noqa: E402
from benchmark.opcounts import indexed_moe as ops  # noqa: E402
from benchmark.readers import indexed_moe as readers  # noqa: E402
from test_bm_rehearsal import last_line, run, shape  # noqa: E402

CELL, TOY = "keye2-longctx-steady", "rehearsal-indexed-moe-serve"
NEW_METRICS = ("decode_sparse_read_over_chosen",
               "prefill_index_pairs_over_needed")
ROOFLINES = ("index_select_roofline", "admit_indexed_attention_roofline",
             "indexed_decode_attention_roofline")
ARCHITECTURES = Path("/opt/skills/guides/model-configs/architectures.jsonl")


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
    # every serving metric the four older serving cells all report
    older = {"mistral7b-chat-steady", "kanana2-docs-steady",
             "olmohybrid-rag-steady", "smallthinker-mixed-steady"}
    shared = {m["name"] for m in bench["per_layer"]
              if older <= set(m.get("workloads", []))}
    assert shared <= set(per)
    # a roofline is in the manifest only where its reader finds its kernel
    # in a traced run of the cell (PERF.md section 5); its files are kept
    # and tested either way
    for name in ROOFLINES:
        assert manifest.read(f"metrics/{name}.json")["unit"] == "%"
        assert manifest.reader(name)
    # the admission's attention is the first of the summary's operation
    # names in every traced run; the other two kernels are not among its ten
    assert set(ROOFLINES) & {m["name"] for m in bench["per_layer"]} == {
        "admit_indexed_attention_roofline"}
    assert "admit_indexed_attention_roofline" in per
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS + ROOFLINES:
            assert m["workloads"] == [CELL]
    for old in sorted(older) + ["mistral7b-train-1chip"]:
        assert not set(NEW_METRICS + ROOFLINES) & set(
            manifest.reported(bench, old)[1])


PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_configuration_holds_the_published_width(key):
    config = manifest.cell(CELL)["config_json"]
    assert config[key] == PUBLISHED[key]


def test_configuration_is_the_catalog_row_cut_in_depth_and_positions():
    config = manifest.cell(CELL)["config_json"]
    assert config["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    assert (config["num_hidden_layers"],
            config["max_position_embeddings"]) == (4, 32768)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "max_position_embeddings": 262144}
    assert config["assumed"] == [
        "qk_norm", "indexer_k_norm", "indexer_rope_dims",
        "chunk_sizes_are_tiling", "rope_halves", "text_only_positions"]
    assert all(key in config for key in config["assumed"])
    assert config["assumed_why"] and len(config["departures"]) == 2
    assert "first of twelve four-layer pipeline stages" in config[
        "stands_for"]
    assert (config["kv_dtype"], config["weights_dtype"],
            config["chips"]) == ("bf16", "bfloat16", 1)
    if ARCHITECTURES.is_file():
        row = next(json.loads(line) for line in ARCHITECTURES.open()
                   if '"Keye-VL-2.0-30B-A3B"' in line)
        assert config["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items()
                         if config.get(k) != v)
        assert differs == sorted(config["reduced"])
        assert row["config"] == {**PUBLISHED, "num_hidden_layers": 48,
                                 "max_position_embeddings": 262144}
    family = families.load(config, "serve")
    d = family.dims(config)
    assert family.layer_kinds(d) == ("indexed_attention",) * 4
    assert set(manifest.cell(CELL)["controls"]) == {"fp8", "last_k"}
    assert set(family.controls()) == {"fp8", "last_k"}
    # ISSUE 42's arithmetic: 18.87 M of attention, 2.13 M of index
    # projections (+ 32768 float32 index weights and the router's 262144),
    # 4.72 M an expert, 625.3 M a layer; 2048 B of K/V and 128 B of index
    # key a position a layer
    assert ops.attn_params(d) == 18_874_368
    assert ops.index_params(d) == 2_228_224
    assert ops.expert_params(d) == 4_718_592
    layer = (ops.attn_params(d) + ops.index_params(d)
             + d["E"] * (d["X"] + d["Hi"]) + d["X"] * ops.expert_params(d))
    assert layer == 625_377_280
    assert 4 * layer + 2 * d["E"] * d["V"] == pytest.approx(3.124e9, rel=1e-3)
    assert ops.kv_bytes_per_position(d) == 2048
    assert ops.index_bytes_per_position(d) == 128
    cfg = family.program_config(config, "serve", {"max_len": 32768})
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk, cfg.n_experts,
            cfg.top_k, cfg.n_layers) == (16, 64, 2048, 128, 8, 4)


def test_what_the_family_does_not_carry_is_refused():
    config = dict(manifest.cell(CELL)["config_json"])
    family = families.load(config)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        family.dims({**config, "norm_topk_prob": False})
    with pytest.raises(ValueError, match="ONE index key"):
        family.dims({**config, "sa_config": {
            **config["sa_config"], "indexer_num_kv_heads": 2}})
    with pytest.raises(KeyError, match="sa_config.topk"):
        family.dims({**config, "sa_config": {
            k: v for k, v in config["sa_config"].items() if k != "topk"}})
    with pytest.raises(ValueError, match="32769 positions"):
        family.program_config(config, "serve", {"max_len": 32769})
    with pytest.raises(NotImplementedError, match="no training path"):
        family.program_config(config, "train")


def test_cell_is_the_traffic_and_deployment_the_issue_gives():
    traffic = manifest.cell(CELL)["traffic_json"]
    dep = traffic["deployment"]
    assert (dep["max_slots"], dep["max_len"], dep["steps_per_call"],
            dep["prefill_chunk"], dep["admit_rows"]) == (16, 32768, 8,
                                                         32768, 1)
    mixed = manifest.read("traffic/mixed-steady.json")["deployment"]
    assert dep["env"] == mixed["env"]
    assert traffic["prompt_len"]["dist"] == "lognormal"
    assert traffic["prompt_len"]["median"] in (10000, 8000)
    assert {k: v for k, v in traffic["prompt_len"].items()
            if k not in ("dist", "median")} == {
        "sigma": 0.6, "min": 2048, "max": 30720}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 160,
                                     "sigma": 0.7, "min": 16, "max": 512}
    assert (traffic["loop"], traffic["sampling"], traffic["ramp_s"],
            traffic["drain_s"]) == ("open", "greedy", 6.0, 10.0)
    assert traffic["arrivals"]["gaps"] == {"dist": "exponential"}
    assert traffic["shape_seed"] not in (
        manifest.read(f"traffic/{name}.json")["shape_seed"]
        for name in ("chat-steady", "docs-steady", "rag-steady",
                     "mixed-steady"))
    share = (traffic["arrivals"]["rate_per_s"]
             / traffic["arrivals"]["knee_per_s"])
    assert 0.6 - 1e-9 <= share <= 0.8 + 1e-9
    assert traffic["correct"]["reference_buckets"][-1] == 32768
    # every prompt passes topk: every admission scores and chooses
    topk = manifest.cell(CELL)["config_json"]["sa_config"]["topk"]
    assert traffic["prompt_len"]["min"] >= topk
    # every bucket a prompt of the mix can take is warmed
    from kubetorch_tpu.models.rolling import _bucket

    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    reach = {_bucket(n) for n in range(lo, hi + 1, 64)} | {_bucket(hi)}
    assert {_bucket(n) for _, n in traffic["warm"]} == reach == {
        2048, 4096, 8192, 16384, 32768}
    assert all(rows == 1 for rows, _ in traffic["warm"])
    assert hi + traffic["output_len"]["max"] <= dep["max_len"]


def _ctx(**delta):
    config = manifest.cell(CELL)["config_json"]
    return {"dims": families.load(config).dims(config), "config": config,
            "deployment": {"steps_per_call": 8},
            "trace_live": {"positions": 60000.0, "rows": 5.0},
            "trace_stats_delta": delta}


def test_decode_step_bytes_count_what_the_step_touched_and_chose():
    family = families.load(manifest.cell(CELL)["config_json"], "serve")
    d = _ctx()["dims"]
    fixed = ops.fixed_weight_bytes(d)
    # attention and index of 4 layers and the head in bf16, the routers and
    # the index's weight projections in float32
    assert fixed == (2 * (4 * (18_874_368 + 2_228_224) + 2048 * 151936)
                     + 4 * 4 * 2048 * (128 + 16))
    # 40 decode steps: 140 experts touched a step over the 4 layers; five
    # rows hold 60000 positions and choose 2048 each in each layer
    ctx = _ctx(moe_expert_slots=40 * 4 * 128, moe_experts_touched=40 * 140,
               decode_sparse_positions_chosen=40 * 4 * 5 * 2048)
    got = family.decode_step_bytes(ctx)
    assert got == pytest.approx(
        fixed + 140 * 2 * 4_718_592 + 4 * 128 * 60000.0
        + 2048 * 4 * 5 * 2048)
    # far under what a read of the rows whole would be
    assert got < fixed + 140 * 2 * 4_718_592 + 4 * 2176 * 60000.0
    assert family.decode_step_bytes(_ctx()) is None
    assert family.decode_step_bytes({**ctx, "trace_live": None}) is None


def test_prefill_flops_count_the_chosen_and_the_scored_pairs():
    d = _ctx()["dims"]
    assert ops.chosen_pairs(100, 2048) == 100 * 101 / 2
    assert ops.chosen_pairs(32768, 2048) == (2048 * 2049 / 2
                                             + 30720 * 2048)
    assert ops.index_pairs(2048, 2048) == 0
    assert ops.index_pairs(32768, 2048) == (32768 * 32769
                                            - 2048 * 2049) / 2
    # ISSUE 42's reckoning of the 32768 call: ~15 TFLOP of products, 4.4
    # of index; the attention a query NEEDS is its 2048 chosen (4.3 TFLOP),
    # an eighth of the dense causal 35
    n = 32768
    products = ops.prefill_flops(d, n, 0, 0)
    assert products == pytest.approx(15.4e12, rel=0.02)
    assert ops.prefill_flops(d, 0, 0, ops.index_pairs(n, 2048)) == \
        pytest.approx(4.38e12, rel=0.02)
    assert ops.prefill_flops(d, 0, ops.chosen_pairs(n, 2048), 0) == \
        pytest.approx(4.26e12, rel=0.02)
    assert ops.prefill_flops(d, 0, n * (n + 1) / 2, 0) == \
        pytest.approx(35.2e12, rel=0.02)


def test_new_readers_return_none_where_the_program_has_nothing():
    """The parent of this PR, or another family's cell: no counter, no
    kernel in the trace's list."""
    bare = {"stats_delta": {"steps": 5}, "trace_stats_delta": {"steps": 5},
            "trace": {"device_ops": [["%fusion.1", 0.5]]},
            "deployment": {"steps_per_call": 8}}
    for name in NEW_METRICS + ROOFLINES:
        assert manifest.reader(name)(bare) is None, name
        assert manifest.reader(name)({}) is None, name
    assert readers.decode_sparse_read_over_chosen(
        {"stats_delta": {"decode_sparse_positions_chosen": 2048,
                         "decode_sparse_positions_read": 12288}}) == 6.0
    assert readers.prefill_index_pairs_over_needed(
        {"stats_delta": {"prefill_index_pairs_needed": 1000,
                         "prefill_index_pairs_scored": 1100}}) == 1.1


def test_kernel_rooflines_read_the_trace_by_kernel_name():
    peaks = manifest.read("peaks.json")["TPU v5 lite"]
    chosen = 40 * 4 * 5 * 2048
    ctx = _ctx(decode_sparse_positions_chosen=chosen)
    d = ctx["dims"]
    least = 2048 * chosen / peaks["hbm_bytes_per_s"]
    assert ops.indexed_decode_least_seconds(d, peaks, chosen) == \
        pytest.approx(least)
    ctx.update(peaks=peaks, trace={"device_ops": [
        ["%indexed_decode_attention.3", least],
        ["%indexed_decode_attention.5", 3 * least], ["%fusion.1", 1.0]]})
    assert readers.indexed_decode_attention_roofline(ctx) == \
        pytest.approx(25.0)

    class Rec:
        def __init__(self, n):
            self.prompt_len = n

    records = [Rec(3000), Rec(6000), Rec(12000)]
    scored = sum(ops.index_pairs(r.prompt_len, 2048) for r in records)
    attended = sum(ops.chosen_pairs(r.prompt_len, 2048) for r in records)
    select = 4 * 2.0 * 16 * 64 * scored / peaks["bf16_flops"]
    attend = 4 * 4.0 * 32 * 128 * attended / peaks["bf16_flops"]
    ctx.update(records=records,
               trace_stats_delta={"prefill_tokens_executed": 21000},
               trace={"device_ops": [
                   ["%index_select.2", 4 * select],
                   ["%admit_indexed_attention.4", 10 * attend]]})
    assert readers.index_select_roofline(ctx) == pytest.approx(25.0)
    assert readers.admit_indexed_attention_roofline(ctx) == \
        pytest.approx(10.0)
    # half the span's prompt tokens: half the pairs at the same mix
    ctx["trace_stats_delta"] = {"prefill_tokens_executed": 10500}
    assert readers.index_select_roofline(ctx) == pytest.approx(12.5)


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
    # the einsum pair streams every slot's plane; the toy's admissions are
    # one block of (128, 512) for a few hundred needed pairs
    assert line["metrics"]["decode_sparse_read_over_chosen"]["value"] > 1.0
    assert line["metrics"]["prefill_index_pairs_over_needed"]["value"] > 1.0
    config = manifest.read(f"configs/{TOY}.json")
    assert config["family"] == "indexed_moe"
    # every scored row grows well past topk
    assert config["sa_config"]["topk"] == 16
    traffic = manifest.read(f"traffic/{TOY.replace('serve', 'open')}.json")
    assert traffic["output_len"]["min"] > 2 * config["sa_config"]["topk"]


def test_family_passes_sound_and_fails_its_controls(toy_run):
    ref = last_line(toy_run)["reference"]
    limit = manifest.read(f"cells/{TOY}.json")["correct"]
    assert ref["served_tokens"] >= 100
    for control in ("fp8", "last_k"):
        assert ref["gap_max"] <= limit["gap_max_limit"] < \
            ref[f"control_{control}_gap_max"]
        assert ref["gap_mean"] <= limit["gap_mean_limit"] < \
            ref[f"control_{control}_gap_mean"]


@pytest.mark.parametrize("broken", ["most_recent", "index_key_not_merged"])
def test_a_broken_choice_is_not_correct(broken):
    proc = run([str(HERE / "bm_drive_broken_choice.py"), broken])
    line = last_line(proc)
    shape(line)
    assert line["correct"] is False and line["failed"] == 0
    failed = [ln for ln in proc.stdout.splitlines() if "FAILED" in ln]
    assert any("served_token_gap_max_logits" in ln for ln in failed)
    assert any("served_token_gap_mean_logits" in ln for ln in failed)
