"""One run of a training cell, from outside: the child holds the chips for
the program, then the reference's child holds one. Never imports JAX.
"""

from __future__ import annotations

import os
import shutil
import statistics
import tempfile

from benchmark import families, manifest, report
from benchmark.report import CellFailure, note, run_child


def worst_leaf_gap(prog: dict, ref: dict):
    """The widest gap between the program's norm and the reference's, leaf
    by leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero)."""
    median = statistics.median(ref.values())
    worst, where = 0.0, None
    for leaf, r in ref.items():
        gap = abs(prog[leaf] - r) / max(r, median)
        if gap >= worst:
            worst, where = gap, leaf
    return worst, where


def worst_sample_gap(prog: dict, ref: dict):
    """The first gradient read at the same seeded positions on both sides:
    the distance between the two samples against the reference's, by the
    worst leaf (the median leaf's scale where a leaf is all but zero). A
    norm hardly moves under zero-mean rounding (second order); this does."""
    def norm(xs):
        return sum(x * x for x in xs) ** 0.5

    median = statistics.median(norm(v) for v in ref.values())
    worst, where = 0.0, None
    for leaf, r in ref.items():
        gap = norm([a - b for a, b in zip(prog[leaf], r)]) / max(
            norm(r), median)
        if gap >= worst:
            worst, where = gap, leaf
    return worst, where


def compare(child: dict, ref: dict) -> dict:
    """The numbers ``correct`` is decided on."""
    out = {"loss0_gap": abs(child["loss"][0] - ref["loss"][0])
           / abs(ref["loss"][0]),
           "loss_gap": max(abs(a - b) / abs(b) for a, b in
                           zip(child["loss"], ref["loss"])),
           "grad_norm_gap": worst_leaf_gap(child["grad_norm"],
                                           ref["grad_norm"]),
           "grad_sample_gap": worst_sample_gap(child["grad_sample"],
                                               ref["grad_sample"])}
    if ref.get("delta_norm"):
        out["delta_norm_gap"] = worst_leaf_gap(child["delta_norm"],
                                               ref["delta_norm"])
    return out


def run(cell: dict, bench: dict, seed: int, seconds: float, trace: bool,
        t_start_epoch: float, rehearsal: bool = False, control: bool = False,
        child_module: str = "benchmark.train_child") -> dict:
    traffic, config = cell["traffic_json"], cell["config_json"]
    d = families.load(config, "train").dims(config)
    names = report.reported(bench, cell)
    from kubetorch_tpu.config import compile_cache_dir

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache_dir())
    state = tempfile.mkdtemp(prefix="kt-bench-")
    job = {"config_file": cell["config_file"], "seed": seed,
           "seconds": seconds, "chips": cell["chips"],
           "batches": traffic["batches"], "trace": traffic["trace"],
           "trace_on": bool(trace), "trace_dir": os.path.join(state, "trace"),
           "rehearsal": rehearsal}
    try:
        child = run_child(child_module, job, rehearsal, 1150, state,
                           devices=cell["chips"])
        if "refused" in child:
            raise CellFailure(f"no chips for the cell: {child['refused']}")
        setup_s = child["t_open_epoch"] - t_start_epoch
        chips = child["count"]
        rate = child["steps"] * child["tokens_per_step"] / (
            child["elapsed_s"] * chips)
        note("train", {k: child[k] for k in (
            "build_s", "first_step_s", "steps", "elapsed_s", "step_ms_p50",
            "loss", "window_loss_last", "compile_before",
            "compile_in_window", "memory", "n_params")})
        # -------------------------------------------------------- correct
        checks = report.Checks()
        limit = checks.limit

        cw = child["compile_in_window"]
        limit("compiles_in_window", cw["cache_hits"] + cw["cache_misses"], 0)
        limit("compile_seconds_in_window", cw["backend_compile_s"], 0.0)
        limit("non_finite_losses", int(not child["window_losses_finite"]), 0)
        ref_job = {"config_file": cell["config_file"], "seed": seed,
                   "rows": child["rows"], "seq": child["seq"],
                   "need_platform": None if rehearsal else "tpu"}
        ref = run_child("benchmark.reference.score_train", ref_job,
                        rehearsal, 900, state)
        gaps = compare(child, ref)
        note("reference", {"loss": ref["loss"], "seconds": ref["seconds"],
                           "program_loss": child["loss"], **gaps})
        lim = cell["correct"]
        limit("first_loss_gap_relative", gaps["loss0_gap"],
              lim["loss0_gap_limit"])
        # the later losses are always printed (``# reference``: ``loss_gap``)
        # and compared where the cell holds a limit for them: after two
        # updates a bfloat16 run's third loss lies 6e-5 to 5e-3 from the
        # float32 reference's by the seed, and an fp8 control no further
        # (PERF.md section 2), so only a float32 cell states one
        if "loss_gap_limit" in lim:
            limit("loss_gap_relative_worst_of_three", gaps["loss_gap"],
                  lim["loss_gap_limit"])
        limit(f"first_gradient_norm_gap_worst_leaf[{gaps['grad_norm_gap'][1]}]",
              gaps["grad_norm_gap"][0], lim["grad_norm_gap_limit"])
        limit(f"first_gradient_sample_distance_worst_leaf"
              f"[{gaps['grad_sample_gap'][1]}]",
              gaps["grad_sample_gap"][0], lim["grad_sample_gap_limit"])
        limit(f"parameter_change_norm_gap_worst_leaf"
              f"[{gaps['delta_norm_gap'][1]}]",
              gaps["delta_norm_gap"][0], lim["delta_norm_gap_limit"])
        controls = {}
        if control:
            for lower in cell.get("controls", []):
                low = run_child(
                    "benchmark.reference.score_train",
                    {**ref_job, "lower": lower, "steps": 1}, rehearsal,
                    900, state)
                controls[lower] = compare(
                    low, {**ref, "loss": ref["loss"][:1],
                          "delta_norm": None})
                controls[lower].pop("delta_norm_gap", None)
                note(f"control {lower}", controls[lower])
    finally:
        shutil.rmtree(state, ignore_errors=True)
    # --------------------------------------------------------------- line
    peak = max((m.get("peak_bytes_in_use") or 0) for m in child["memory"])
    device = {"platform": child["platform"], "kind": child["kind"],
              "count": chips, "memory_peak_bytes": peak}
    values = {"setup_s": setup_s, "train_tok_s_chip": rate}
    ctx = {}
    if trace:
        peaks = manifest.read("peaks.json").get(child["kind"])
        if peaks is None and not rehearsal:
            raise CellFailure(f"no peaks for device kind {child['kind']!r}")
        ctx = {"seconds": seconds, "config": config, "dims": d,
               "cell": cell["name"], "peaks": peaks, "seq": child["seq"],
               "train_tok_s_chip": rate, "trace": child.get("trace"),
               "compile_before": child["compile_before"]}
    line = {"correct": checks.correct, "attempted": child["steps"],
            "failed": 0,
            "metrics": report.metrics_of(names, trace, values, ctx,
                                         rehearsal),
            "device": device}
    if trace:
        t = child.get("trace") or {}
        report.attach_trace(line, t, rehearsal, {
            "traced_steps": child.get("traced_steps"),
            "collective_s": t.get("collective_s"),
            "collective_exposed_s": t.get("collective_exposed_s")})
    if rehearsal:
        line["rehearsal"] = True
    line["reference"] = {k: (list(v) if isinstance(v, tuple) else v)
                         for k, v in gaps.items()}
    if controls:
        line["controls"] = controls
    line["compared"] = checks.compared
    return line
