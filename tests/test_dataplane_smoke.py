"""Tier-1-safe data-plane smoke: ``bench_dataplane.run(dryrun=True)`` runs
every bench — including the streaming pipelined restore — at toy sizes on
CPU, and this test fails if any metric KEY disappears (a silently-dropped
measurement is how a perf regression hides)."""

import pytest

# The bench's stable contract. Values are environment-dependent; keys are
# not. Adding keys is fine; losing one fails here.
EXPECTED_KEYS = {
    "blob_put_MBps",
    "blob_get_MBps",
    "codesync_cold_ms",
    "codesync_warm_ms",
    "codepull_cold_ms",
    "codepull_warm_ms",
    "bcast_direct_ms",
    "bcast_tree_ms",
    "bcast_direct_egress_mb",
    "bcast_tree_egress_mb",
    "bcast_egress_ratio",
    "bcast_2peer_direct_ms",
    "bcast_2peer_relay_ms",
    "bcast_relay_tax_ms",
    # streaming pipelined restore decomposition
    "restore_fetch_GBps",
    "restore_blocking_ms",
    "restore_streamed_ms",
    "restore_place_GBps",
    "restore_overlap_ratio",
    "restore_speedup",
    "restore_vs_wire_ratio",
    # quantized delta wire codec decomposition
    "restore_wire_bytes_raw_mb",
    "restore_wire_bytes_int8_mb",
    "restore_wire_reduction_int8",
    "restore_int8_streamed_ms",
    "codec_int8_encode_MBps",
    "codec_int8_decode_MBps",
    "codec_int8_dequant_ms",
    "delta_publish_full_mb",
    "delta_publish_update_mb",
    "delta_publish_update_pct",
    "delta_publish_leaves_skipped",
    "delta_fetch_wire_mb",
    "delta_fetch_hit",
    # quantized dcn collectives + delta-aware broadcast (train plane)
    "coll_quant_MBps",
    "coll_dequant_MBps",
    "coll_ring_rel_err",
    "coll_dcn_wire_reduction",
    "coll_loss_equiv_delta",
    "coll_loss_equiv_steps",
    "bcast_delta_full_mb",
    "bcast_delta_wire_mb",
    # distributed tracing instruments the restore/publish paths above
    "trace_span_count",
    "trace_overhead_us_per_span",
}


@pytest.mark.level("minimal")
def test_dataplane_dryrun_metric_keys():
    from kubetorch_tpu import bench_dataplane

    out = bench_dataplane.run(dryrun=True)
    missing = EXPECTED_KEYS - set(out)
    assert not missing, (
        f"dataplane bench dropped metric keys: {sorted(missing)} — a "
        f"measurement went silent; restore it (or update EXPECTED_KEYS "
        f"if the rename is deliberate)")
    # sanity: the restore decomposition carries real measurements
    assert out["restore_streamed_ms"] > 0
    assert out["restore_blocking_ms"] > 0
    assert 0.0 <= out["restore_overlap_ratio"] <= 1.0
    # codec/delta acceptance floors hold even at dryrun sizes: the int8
    # codec must at least halve the weight-sync wire bytes, and a
    # LoRA-only delta update must ship <1% of the full blob
    assert out["restore_wire_reduction_int8"] >= 2.0
    assert out["delta_publish_update_pct"] < 1.0
    assert out["delta_publish_leaves_skipped"] > 0
    assert out["delta_fetch_hit"] == 1.0
    # train-plane collectives floors: the int8 dcn ring must at least
    # halve bytes-on-wire vs the f32 schedule, train indistinguishably
    # from f32 (loss-trajectory bound), and the delta broadcast must
    # ship a strict fraction of the full blob for a 1-of-6-leaf change
    assert out["coll_dcn_wire_reduction"] >= 2.0
    assert out["coll_loss_equiv_delta"] < 0.05
    assert out["coll_quant_MBps"] > 0 and out["coll_dequant_MBps"] > 0
    assert 0 < out["bcast_delta_wire_mb"] < 0.5 * out["bcast_delta_full_mb"]
    # the dataplane paths must actually record spans (fetch/decode/
    # device_put per restore, put/get per publish) at a sane per-span
    # cost — a silently un-instrumented path would zero the count
    assert out["trace_span_count"] >= 4
    assert 0 < out["trace_overhead_us_per_span"] < 1000
