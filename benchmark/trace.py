"""From a profiler trace (``.xplane.pb``) to intervals and sums.

``load`` turns the file into plain lists (plane -> line -> events of
``[name, start_ns, duration_ns]``) with nothing but JAX's own reader;
``summarise`` reduces those lists and is what the tests drive on the
recorded fixture. Readers under ``benchmark/readers`` take their numbers
from the summary. Device planes are ``/device:TPU:<n>``; the line
``XLA Modules`` holds one event per executable run, ``XLA Ops`` one per
operation (names as the trace prints them, recorded in PERF.md).
"""

from __future__ import annotations

import glob
import os
import re

MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
# Executable kinds. The trainer's step has a name (``jit_train_step``). The
# engine's executables do not: ``models/rolling.py`` jits ``functools.partial``
# objects, so every one of them is ``jit__unknown(<program id>)`` in the trace
# (chip run, PR 23). They are told apart by a mark the harness derives from
# the deployment's geometry: the decode executable alone holds the sampled
# tokens ``s32[steps_per_call,max_slots]``; an unnamed executable without
# the mark is a prefill (admission, chunk or splice). Names for all of them
# are the ``tracing`` issue's to add.
NAMED = (("train_step", "train"), ("_prefill_extend_impl", "prefill"),
         ("_prefill_impl", "prefill"), ("_decode_impl", "decode"))
UNNAMED = "jit__unknown"


def find_xplane(directory: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return hits[-1]


def load(path: str) -> dict:
    """{plane name: {line name: [[event name, start_ns, dur_ns], ...]}} for
    the device planes' module and operation lines."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {}
        for line in plane.lines:
            if line.name not in (MODULES_LINE, OPS_LINE):
                continue
            lines.setdefault(line.name, []).extend(
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events)
        planes[plane.name] = lines
    return planes


def kind_of(module_name: str, marked: bool = False) -> str:
    for needle, kind in NAMED:
        if needle in module_name:
            return kind
    if base_name(module_name) == UNNAMED:
        return "decode" if marked else "prefill"
    return "other"


def short(op_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``%fusion.12``."""
    return op_name.split(" = ", 1)[0][:64]


def marked_programs(mods, ops, marks) -> set:
    """Module names (with their program id) one of whose operations
    carries one of ``marks`` in its text."""
    import bisect

    if not marks:
        return set()
    ops = sorted(ops, key=lambda ev: ev[1])
    starts = [ev[1] for ev in ops]
    found, seen = set(), set()
    for name, s, d in mods:
        if name in seen or base_name(name) != UNNAMED:
            continue
        seen.add(name)
        lo, hi = bisect.bisect_left(starts, s), bisect.bisect_right(
            starts, s + d)
        if any(m in ops[i][0] for i in range(lo, hi) for m in marks):
            found.add(name)
    return found


def base_name(name: str) -> str:
    """``jit__decode_impl(123456)`` -> ``jit__decode_impl``."""
    return re.sub(r"\(\d+\)$", "", name)


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def subtract(a, b):
    """Total length of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _label(before: str, after: str) -> str:
    if before == after == "decode":
        return "between decode ticks"
    if before == after == "train":
        return "between train steps"
    if not before or not after:
        return "unattributed"
    return f"{before}->{after}"


def summarise(planes: dict, marks=()) -> dict:
    """The numbers readers take. Times in seconds unless named ``_ms``.
    ``window_s`` is the trace's own: the first device event's start to the
    last one's end, on the clock the events are stamped with (a host stamp
    around ``start_trace``/``stop_trace`` is another clock and leaves out
    what the device still ran while the profiler stopped). ``busy_s`` is
    the union of operation intervals, averaged over the device planes, so it
    cannot pass the window unless the events are wrong; modules, gaps and
    operations are those of the first device."""
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p)),
                     key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
    events = [(s, s + d) for p in devices for evs in planes[p].values()
              for _, s, d in evs]
    if not events:
        return {"devices": 0, "window_s": 0.0}
    window_s = (max(e for _, e in events) - min(s for s, _ in events)) / 1e9
    busy, exposed, coll = [], [], []
    for p in devices:
        ops = planes[p].get(OPS_LINE) or planes[p].get(MODULES_LINE) or []
        spans = [(s, s + d) for _, s, d in ops]
        busy.append(sum(e - s for s, e in union(spans)) / 1e9)
        c = [(s, s + d) for n, s, d in ops if COLLECTIVE.match(n)]
        rest = [(s, s + d) for n, s, d in ops if not COLLECTIVE.match(n)]
        coll.append(sum(e - s for s, e in union(c)) / 1e9)
        exposed.append(subtract(c, rest) / 1e9)
    first = planes[devices[0]]
    mods = sorted(first.get(MODULES_LINE, []), key=lambda ev: ev[1])
    marked = marked_programs(mods, first.get(OPS_LINE, []), marks)
    kind = {name: kind_of(name, name in marked) for name, _, _ in mods}
    modules = {}
    for name, _, d in mods:
        m = modules.setdefault(f"{kind[name]}:{name}", {
            "kind": kind[name], "calls": 0, "total_s": 0.0, "ms": []})
        m["calls"] += 1
        m["total_s"] += d / 1e9
        m["ms"].append(d / 1e6)
    # What follows each executable: the gap to the next device work of any
    # kind. Idle time is labelled by the executables around it; the tiny
    # helper programs between them (an rng split, an unstack: microseconds)
    # belong to the gap they sit in.
    follow = {}
    gaps = {}
    for (n0, s0, d0), (_, s1, _) in zip(mods, mods[1:]):
        if kind[n0] != "other":
            follow.setdefault(kind[n0], []).append(
                max(0, s1 - (s0 + d0)) / 1e6)
    main = [ev for ev in mods if kind[ev[0]] != "other"]
    helper_ns = [(s, d) for n, s, d in mods if kind[n] == "other"]
    for (n0, s0, d0), (n1, s1, _) in zip(main, main[1:]):
        between = sum(d for s, d in helper_ns if s0 + d0 <= s < s1)
        gap = max(0, s1 - (s0 + d0) - between) / 1e9
        label = _label(kind[n0], kind[n1])
        gaps[label] = gaps.get(label, 0.0) + gap
    # operations by name; loops and calls hold their bodies' operations,
    # which are events of their own, so they are left out of the list
    ops = {}
    for name, _, d in first.get(OPS_LINE, []):
        name = short(name)
        if not name.startswith(("%while", "%conditional", "%call")):
            ops[name] = ops.get(name, 0.0) + d / 1e9
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    by_kind = {}
    for m in modules.values():
        by_kind[m["kind"]] = by_kind.get(m["kind"], 0.0) + m["total_s"]
    executables = sorted(([f"executable:{k}", v] for k, v in by_kind.items()
                          if k != "other"), key=lambda kv: -kv[1])
    return {
        "devices": len(devices), "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "collective_s": sum(coll) / len(coll),
        "collective_exposed_s": sum(exposed) / len(exposed),
        "modules": modules,
        "gap_after_ms": follow,
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:10],
        "device_ops": (executables[:3]
                       + [[n, s] for n, s in top[:10 - len(executables[:3])]]),
    }


def reduce_dir(directory: str, marks=()) -> dict:
    """Reduce the newest trace under ``directory``."""
    path = find_xplane(directory)
    planes = load(path)
    out = summarise(planes, marks)
    out["xplane_bytes"] = os.path.getsize(path)
    return out
