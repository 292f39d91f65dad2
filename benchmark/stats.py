"""Percentile arithmetic of the end-to-end metrics. No JAX here."""

from __future__ import annotations

import math


def percentile(values, q: float, missing: int = 0, missing_value=math.inf):
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values`` plus
    ``missing`` samples that count as worse than every finite one: a
    request that failed, was shed or never answered misses any limit."""
    n = len(values) + missing
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    ordered = sorted(values)
    return ordered[rank - 1] if rank <= len(ordered) else missing_value


def samples_beyond(n: int, q: float) -> int:
    """How many samples lie strictly beyond the nearest-rank percentile."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def weighted_percentile(pairs, q: float):
    """``q``-th percentile of values weighted by integer weights:
    ``pairs`` is ``[(value, weight), ...]``; each value counts ``weight``
    times (a frame of 8 tokens is 8 token gaps)."""
    pairs = sorted(p for p in pairs if p[1] > 0)
    total = sum(w for _, w in pairs)
    if not total:
        return None
    need = q / 100.0 * total
    seen = 0
    for value, w in pairs:
        seen += w
        if seen >= need:
            return value
    return pairs[-1][0]


def ttft_ms(records, seconds: float, missing_ms: float, q: float = 90.0):
    """Time to first token, open loop: first frame's arrival minus the time
    the request was DUE, over requests due inside the window."""
    due = [r for r in records if r.due is not None and 0 <= r.due < seconds]
    got = [(r.frames[0][0] - r.due) * 1e3 for r in due if r.frames]
    return {"value": percentile(got, q, len(due) - len(got), missing_ms),
            "n": len(due), "missing": len(due) - len(got),
            "beyond": samples_beyond(len(due), q),
            "p50": percentile(got, 50.0, len(due) - len(got), missing_ms)}


def token_gaps(records, seconds: float):
    """(gap per token in ms, tokens) for every frame after a request's
    first that arrived inside the window."""
    pairs = []
    for r in records:
        for (t_prev, _), (t, n) in zip(r.frames, r.frames[1:]):
            if 0 <= t < seconds:
                pairs.append(((t - t_prev) / n * 1e3, n))
    return pairs


def longest_silence(records, seconds: float):
    """The longest stretch of the window in which no frame reached any
    client, and when it began: a tick is ~0.2 s, so a silence of seconds is
    a stall of the serving side (engine, worker or channel), and with the
    generator's own worst lateness beside it says which side of the wire
    paused. Diagnostic only (a ``#`` line); no metric reads it."""
    times = sorted(t for r in records for t, _ in r.frames
                   if 0 <= t < seconds)
    if len(times) < 2:
        return None
    gap, at = max((b - a, a) for a, b in zip(times, times[1:]))
    return {"ms": gap * 1e3, "at_s": at}


def completed_tokens(records, seconds: float):
    """Output tokens of requests whose last frame arrived in the window."""
    done = [r for r in records if r.done and r.frames
            and 0 <= r.frames[-1][0] < seconds]
    return sum(len(r.tokens) for r in done), len(done)


def window_tokens(records, seconds: float) -> int:
    """Output tokens that arrived at the clients inside the window,
    whichever request they belong to: all the work of the window. (Counting
    only requests that FINISHED inside it moves +-5% with one request across
    the edge when a window holds twenty: chip runs, PR 23.)"""
    return sum(n for r in records for t, n in r.frames if 0 <= t < seconds)
