"""Readers of the benchmark's own clock: the load generator, the launch."""

from benchmark import stats


def loadgen_late_p90_ms(ctx):
    """How late the generator sent: actual send minus due time."""
    late = [(r.sent - r.due) * 1e3 for r in ctx.get("records", [])
            if r.sent is not None and r.due is not None
            and 0 <= r.due < ctx["seconds"]]
    return stats.percentile(late, 90.0) if late else None


def launch_ready_s(ctx):
    """Wall of ``.to(kt.Compute(...))`` until the pod answers ready."""
    return ctx.get("launch_ready_s")
