"""The yardstick's arithmetic: percentiles with missing answers, the load
generator's seeded schedule and lateness, operation and byte counts against
hand-worked numbers for Mistral-7B-v0.3, interval sums of the trace."""

import json
import math
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import loadgen, manifest, stats, trace  # noqa: E402
from benchmark.opcounts import llama_dense as ops  # noqa: E402
from benchmark.weights_dims import dims_of  # noqa: E402


# ------------------------------------------------------------ percentiles
@pytest.mark.parametrize("values, q, missing, want", [
    (list(range(1, 11)), 90, 0, 9),
    (list(range(1, 11)), 95, 0, 10),
    (list(range(1, 11)), 50, 0, 5),
    (list(range(1, 10)), 90, 1, 9),          # the miss is the worst
    (list(range(1, 9)), 90, 2, math.inf),    # the 9th of 10 is a miss
    ([], 90, 0, None),
    ([5.0], 90, 0, 5.0),
])
def test_percentile_counts_missing_as_worst(values, q, missing, want):
    assert stats.percentile(values, q, missing) == want


def test_samples_beyond():
    assert stats.samples_beyond(150, 90) == 15
    assert stats.samples_beyond(100, 95) == 5
    assert stats.samples_beyond(0, 90) == 0


def test_weighted_percentile_is_token_weighted():
    # 8 tokens at 10 ms, 1 token at 100 ms: the 9th of 9 tokens
    pairs = [(10.0, 8), (100.0, 1)]
    assert stats.weighted_percentile(pairs, 50) == 10.0
    assert stats.weighted_percentile(pairs, 88) == 10.0
    assert stats.weighted_percentile(pairs, 95) == 100.0
    assert stats.weighted_percentile([], 95) is None


def _record(due, frames, done=True, out_len=None, error=None):
    r = loadgen.Record({"id": 0, "prompt_len": 4,
                        "out_len": out_len or sum(n for _, n in frames)})
    r.due, r.sent, r.frames, r.done, r.error = due, due + 0.002, frames, \
        done, error
    r.tokens = [1] * sum(n for _, n in frames)
    return r


def test_ttft_is_from_the_due_time_and_missing_is_a_miss():
    recs = [_record(1.0, [(1.5, 1), (1.9, 8)]),
            _record(2.0, [(2.25, 1)]),
            _record(3.0, [], done=False),          # never answered
            _record(-1.0, [(0.2, 1)]),             # due in the ramp
            _record(11.0, [(11.1, 1)])]            # due after the window
    out = stats.ttft_ms(recs, seconds=10.0, missing_ms=99999.0, q=90)
    assert out["n"] == 3 and out["missing"] == 1
    assert out["value"] == 99999.0
    assert out["p50"] == pytest.approx(500.0)


def test_token_gaps_divide_by_frame_size_and_keep_the_window():
    recs = [_record(0.0, [(0.5, 1), (0.9, 8), (1.3, 8), (12.0, 8)])]
    pairs = stats.token_gaps(recs, seconds=10.0)
    assert pairs == [(pytest.approx(50.0), 8), (pytest.approx(50.0), 8)]


def test_completed_tokens_counts_requests_finished_inside():
    recs = [_record(0.0, [(0.5, 1), (3.0, 7)]),
            _record(0.0, [(0.5, 1), (11.0, 7)]),     # finished after
            _record(0.0, [(0.5, 1)], done=False)]
    assert stats.completed_tokens(recs, 10.0) == (8, 1)


def test_longest_silence_names_a_stall_and_when_it_began():
    recs = [_record(0.0, [(0.5, 1), (0.7, 8), (0.9, 8), (3.1, 8)]),
            _record(0.0, [(0.6, 1), (0.8, 8), (3.2, 8), (11.0, 8)])]
    out = stats.longest_silence(recs, seconds=10.0)
    assert out["ms"] == pytest.approx(2200.0) and out["at_s"] == 0.9
    assert stats.longest_silence(recs[:1], seconds=0.6) is None


# ---------------------------------------------------------- load generator
@pytest.fixture(scope="module")
def chat():
    return manifest.read("traffic/chat-steady.json")


def test_every_seed_replays_the_files_one_draw(chat):
    a = loadgen.build(chat, 40.0)
    assert a == loadgen.build(chat, 40.0)
    assert a != loadgen.build(dict(chat, shape_seed=1), 40.0)
    n = round(chat["arrivals"]["rate_per_s"] * (40.0 + chat["ramp_s"]))
    assert len(a["requests"]) == n
    due = [r["due_s"] for r in a["requests"]]
    assert due == sorted(due) and due[0] > -chat["ramp_s"]
    assert due[-1] == pytest.approx(40.0)          # the offered load is fixed
    lens = [r["prompt_len"] for r in a["requests"]]
    assert min(lens) >= 16 and max(lens) <= 1536
    assert all(8 <= r["out_len"] <= 448 for r in a["requests"])
    # what the seed draws: the token ids of each request
    rid, n_tok = a["requests"][0]["id"], a["requests"][0]["prompt_len"]
    assert loadgen.prompt_tokens(1, rid, n_tok, 32768) != \
        loadgen.prompt_tokens(2**31 + 11, rid, n_tok, 32768)


@pytest.mark.parametrize("bad", [
    {"arrivals": {"rate_per_s": 3.0, "gaps": {"dist": "gamma", "cv": 3}}},
    {"prompt_len": {"dist": "zipf"}},
    {"loop": "half-open"},
])
def test_a_traffic_file_the_generator_does_not_know_is_refused(chat, bad):
    with pytest.raises(ValueError):
        loadgen.build({**chat, **bad}, 40.0)


def test_window_tokens_counts_every_frame_inside():
    recs = [_record(0.0, [(0.5, 1), (3.0, 7)]),
            _record(0.0, [(9.5, 1), (11.0, 7)]),
            _record(-2.0, [(-0.5, 1), (0.2, 8)], done=False)]
    assert stats.window_tokens(recs, 10.0) == 1 + 7 + 1 + 8


def test_prompt_tokens_are_seeded_and_in_vocabulary():
    a = loadgen.prompt_tokens(3, 5, 100, 32768)
    assert a == loadgen.prompt_tokens(3, 5, 100, 32768)
    assert a != loadgen.prompt_tokens(4, 5, 100, 32768)
    assert all(1 <= t < 32768 for t in a)


def test_drive_open_loop_sends_at_due_times_and_reports_lateness():
    traffic = {"loop": "open", "shape_seed": 1, "ramp_s": 0.2,
               "arrivals": {"rate_per_s": 20.0},
               "prompt_len": {"dist": "fixed", "value": 3},
               "output_len": {"dist": "fixed", "value": 2}}
    plan = loadgen.build(traffic, 1.0)
    opened = []

    def submit(prompt, n):
        yield {"tokens": [1], "done": False}
        yield {"tokens": [2], "done": True}

    records, t_close = loadgen.drive(plan, submit, 1, 100, 1.0, 0.5,
                                     on_window=opened.append)
    assert len(opened) == 1 and abs(opened[0]) < 0.05
    assert t_close == pytest.approx(1.0, abs=0.05)
    assert all(r.done and r.tokens == [1, 2] for r in records)
    late = [r.sent - r.due for r in records]
    assert all(0 <= x < 0.05 for x in late)
    from benchmark.readers import client
    p90 = client.loadgen_late_p90_ms({"records": records, "seconds": 1.0})
    assert 0 <= p90 < 50


def test_drive_closed_loop_keeps_one_request_per_client_in_flight():
    traffic = {"loop": "closed", "shape_seed": 1, "ramp_s": 0.0,
               "clients": 3, "requests_per_client": 50,
               "prompt_len": {"dist": "fixed", "value": 3},
               "output_len": {"dist": "uniform", "min": 1, "max": 3}}
    plan = loadgen.build(traffic, 0.5)
    inflight, worst = [0], [0]

    def submit(prompt, n):
        import time
        inflight[0] += 1
        worst[0] = max(worst[0], inflight[0])
        time.sleep(0.01)
        yield {"tokens": list(range(n)), "done": True}
        inflight[0] -= 1

    records, _ = loadgen.drive(plan, submit, 1, 100, 0.5, 0.0)
    assert worst[0] <= 3
    done = [r for r in records if r.done]
    assert 30 < len(done) < 150 and all(r.error is None for r in records)


# ----------------------------------------------------------- op counts
@pytest.fixture(scope="module")
def d32():
    return dims_of(manifest.read("configs/mistral-7b-v0.3-int8-serve.json"))


def test_mistral_7b_parameter_count(d32):
    assert ops.layer_matmul_params(d32) == 218_103_808
    assert ops.matmul_params(d32) == 7_113_539_584
    assert ops.total_params(d32) == 7_248_023_552      # the published 7.25 B


def test_serving_bytes(d32):
    assert ops.kv_bytes_per_position(d32, "int8") == 67_584
    assert ops.kv_bytes_per_position(d32, "bf16") == 131_072
    assert ops.serving_weight_bytes(d32) == 7_250_509_824
    assert ops.decode_step_bytes(d32, "int8", 10_000) == (
        7_250_509_824 + 675_840_000)


def test_flops(d32):
    assert ops.prefill_flops(d32, 1000, 1_000_000) == pytest.approx(
        1.4227079168e13 + 2.62144e11)
    d4 = dict(d32, L=4)
    assert ops.train_flops_per_token(d4, 4096) == 6_442_450_944


def test_peaks_table_has_the_v5e_and_no_default():
    peaks = manifest.read("peaks.json")
    assert peaks["TPU v5 lite"]["bf16_flops"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks and peaks.get("TPU v9") is None


# ------------------------------------------------------------- intervals
def test_union_and_subtract():
    assert trace.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert trace.subtract([(0, 10)], [(2, 4), (6, 12)]) == 4
    assert trace.subtract([(0, 4), (8, 10)], []) == 6
    assert trace.subtract([(0, 4)], [(0, 4)]) == 0


def test_summarise_synthetic_planes():
    ms = 1_000_000
    dec = "jit__unknown(11)"
    planes = {"/device:TPU:0": {
        "XLA Modules": [[dec, 0, 8 * ms], ["jit__threefry_split(7)", 9 * ms,
                                            1000],
                        [dec, 10 * ms, 8 * ms],
                        ["jit__unknown(22)", 20 * ms, 30 * ms],
                        [dec, 51 * ms, 8 * ms]],
        "XLA Ops": [["%while.1 = (s32[8,32]{1,0}) while(...)", 0, 8 * ms],
                    ["%fusion.1 = f32[32]{0} fusion(...)", 0, 8 * ms],
                    ["%fusion.1 = f32[32]{0} fusion(...)", 10 * ms, 8 * ms],
                    ["%fusion.2 = bf16[32,128,4096]{2,1,0} fusion(...)",
                     20 * ms, 30 * ms],
                    ["all-gather.3", 45 * ms, 10 * ms],
                    ["%fusion.1 = f32[32]{0} fusion(...)", 56 * ms, 3 * ms]]}}
    out = trace.summarise(planes, marks=["s32[8,32]"])
    assert out["devices"] == 1
    # the window is the trace's own: first event's start to the last's end
    assert out["window_s"] == pytest.approx(0.059)
    assert out["busy_s"] == pytest.approx(0.054)
    assert out["modules"]["decode:" + dec]["calls"] == 3
    assert out["modules"]["prefill:jit__unknown(22)"]["kind"] == "prefill"
    assert out["modules"]["other:jit__threefry_split(7)"]["kind"] == "other"
    # after a decode: the next device work of any kind, helper or not
    assert out["gap_after_ms"]["decode"] == [pytest.approx(1.0),
                                             pytest.approx(2.0)]
    gaps = dict(out["idle_gaps"])
    assert gaps["between decode ticks"] == pytest.approx(0.002 - 1e-6)
    assert gaps["decode->prefill"] == pytest.approx(0.002)
    assert gaps["prefill->decode"] == pytest.approx(0.001)
    assert out["collective_s"] == pytest.approx(0.010)
    assert out["collective_exposed_s"] == pytest.approx(0.005)
    assert out["device_ops"][0] == ["executable:prefill",
                                    pytest.approx(0.030)]
    assert out["device_ops"][2][0] == "%fusion.2"      # loops left out
    from benchmark.readers import device
    ctx = {"trace": out, "deployment": {"steps_per_call": 8}}
    assert device.device_idle(ctx) == pytest.approx(100 * 5 / 59)
    assert device.tick_gap_p50_ms(ctx) == pytest.approx(1.5)
    assert device.decode_step_dev_ms(ctx) == pytest.approx(1.0)
    assert device.prefill_chunk_dev_ms(ctx) == pytest.approx(30.0)
    assert device.device_idle({"trace": {"devices": 0}}) is None


@pytest.mark.parametrize("shift", [0, 7_000_000_000])
def test_window_and_busy_are_on_the_traces_own_clock(shift):
    ms = 1_000_000
    ops = lambda spans: [["%fusion.1", shift + a * ms, (b - a) * ms]
                         for a, b in spans]
    planes = {"/device:TPU:0": {"XLA Ops": ops([(0, 10)])},
              "/device:TPU:1": {"XLA Ops": ops([(0, 4), (6, 10)])},
              "/host:CPU": {"XLA Ops": ops([(0, 500)])}}     # not a device
    out = trace.summarise(planes)
    assert out["devices"] == 2
    assert out["window_s"] == pytest.approx(0.010)
    assert out["busy_s_per_device"] == [pytest.approx(0.010),
                                        pytest.approx(0.008)]
    assert out["busy_s"] == pytest.approx(0.009)       # the mean, uncapped
    from benchmark.readers import device
    assert device.device_idle({"trace": out}) == pytest.approx(10.0)
    # a later event stretches the window; nothing clips it to a host stamp
    planes["/device:TPU:0"]["XLA Ops"] += ops([(19, 20)])
    assert trace.summarise(planes)["window_s"] == pytest.approx(0.020)
    assert trace.summarise({"/host:CPU": planes["/host:CPU"]}) == {
        "devices": 0, "window_s": 0.0}


def test_summarise_the_recorded_chip_trace():
    """0.95 s of the v5e running chat-steady at 32 x 2048, chunk 128
    (chip run, PR 23): a chunked prefill, a decode chunk of 8 steps, a
    width-1 admission, the next chunked prefill."""
    import gzip

    with gzip.open(REPO / "benchmark/fixtures/chat_steady_head.json.gz",
                   "rt") as f:
        fx = json.load(f)
    out = trace.summarise(fx["planes"], fx["meta"]["marks"])
    kinds = sorted((m["kind"], m["calls"]) for m in out["modules"].values())
    assert kinds == [("decode", 1), ("other", 1), ("other", 1),
                     ("prefill", 1), ("prefill", 2)]
    decode = next(m for m in out["modules"].values()
                  if m["kind"] == "decode")
    assert decode["total_s"] == pytest.approx(0.197194982)
    assert 0.9 < out["window_s"] <= 1.0 + 0.7      # the head kept + its tail
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["gap_after_ms"]["decode"] == [pytest.approx(8.11465)]
    assert dict(out["idle_gaps"])["decode->prefill"] == pytest.approx(
        0.00811465)
    assert dict(out["idle_gaps"])["prefill->decode"] == pytest.approx(
        1.486e-6 + 1.573e-6 + 2.509e-6, rel=1e-3)
    assert out["device_ops"][0][0] == "executable:prefill"
    assert out["device_ops"][1] == ["executable:decode",
                                    pytest.approx(0.197194982)]
    assert all(name.startswith(("%", "executable:"))
               and len(name) <= 64 for name, _ in out["device_ops"])
    # without the mark nothing can be called a decode
    blind = trace.summarise(fx["planes"])
    assert not [m for m in blind["modules"].values()
                if m["kind"] == "decode"]
    from benchmark.readers import device
    ctx = {"trace": out, "deployment": {"steps_per_call": 8}}
    assert device.decode_step_dev_ms(ctx) == pytest.approx(24.649, abs=1e-3)
    assert device.tick_gap_p50_ms(ctx) == pytest.approx(8.11465)
