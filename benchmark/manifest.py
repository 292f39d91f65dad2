"""``BENCHMARK.json`` against the files it is assembled from. No JAX here.

Every configuration, traffic mix, cell and metric is a file of its own,
found by the name ``BENCHMARK.json`` gives, so a later PR adds files and
entries and edits none. ``check()`` lists every disagreement, and every
per-layer metric reported in a cell that does not report the end-to-end
metric it moves: the rule that refused PR 22 before a single run.
"""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def read(rel: str) -> dict:
    with open(ROOT / rel) as f:
        return json.load(f)


def benchmark_json() -> dict:
    with open(REPO / "BENCHMARK.json") as f:
        return json.load(f)


def cell(name: str) -> dict:
    """A cell with its configuration and traffic read in. Works for cells
    that are not (yet) in ``BENCHMARK.json`` too: rehearsal cells, and cells
    kept for a later PR."""
    if not NAME.match(name):
        raise ValueError(f"not a cell name: {name!r}")
    out = read(f"cells/{name}.json")
    out["config_file"] = str(ROOT / "configs" / f"{out['config']}.json")
    out["config_json"] = read(f"configs/{out['config']}.json")
    out["traffic_json"] = read(f"traffic/{out['traffic']}.json")
    return out


def reported(manifest: dict, cell_name: str):
    """(end-to-end names, per-layer names) the cell has to report."""
    e2e = reported_e2e(manifest, cell_name)
    per = []
    for m in manifest["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            cells = [w["name"] for w in manifest["workloads"]
                     if m["moves"] in reported_e2e(manifest, w["name"])]
        if cell_name in cells:
            per.append(m["name"])
    return e2e, per


def reported_e2e(manifest: dict, cell_name: str):
    return [m["name"] for m in manifest["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric_name: str):
    """The function that reads a per-layer metric: ``<file>:<function>``
    under ``benchmark/readers``."""
    spec = read(f"metrics/{metric_name}.json")["reader"]
    module, func = spec.split(":")
    return getattr(importlib.import_module(f"benchmark.readers.{module}"),
                   func)


def check(manifest: dict = None) -> list:
    """Every fault found, as text; empty when the manifest is sound."""
    errs = []
    try:
        m = manifest if manifest is not None else benchmark_json()
    except (OSError, ValueError) as exc:
        return [f"BENCHMARK.json: {exc}"]

    def bad(msg):
        errs.append(msg)

    cells = {w["name"]: w for w in m["workloads"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    names = ([w for w in cells] + [c["name"] for c in m["configs"]]
             + list(e2e) + [x["name"] for x in m["per_layer"]])
    for n in names:
        if not NAME.match(n):
            bad(f"name {n!r} uses characters outside letters, digits, _ . -")
    for group in ("workloads", "configs"):
        seen = [x["name"] for x in m[group]]
        if len(seen) != len(set(seen)):
            bad(f"{group}: a name appears twice")
    metric_names = list(e2e) + [x["name"] for x in m["per_layer"]]
    if len(metric_names) != len(set(metric_names)):
        bad("two metrics share a name")
    if "setup_s" not in e2e:
        bad("end_to_end lacks setup_s")
    for x in list(e2e.values()) + m["per_layer"]:
        if not UNIT.match(x["unit"]):
            bad(f"{x['name']}: unit {x['unit']!r} not 1-16 of letters, "
                f"digits, _ / % . -")
        if x["better"] not in ("lower", "higher"):
            bad(f"{x['name']}: better is {x['better']!r}")
        if x["source"] not in SOURCES:
            bad(f"{x['name']}: source {x['source']!r}")
    for x in e2e.values():
        if x["source"] not in ("host_clock", "device_trace"):
            bad(f"{x['name']}: an end-to-end metric reads host_clock or "
                f"device_trace only")
        if not 0 < x["bound"] <= 0.1:
            bad(f"{x['name']}: bound {x['bound']} outside (0, 0.1]")
    # configurations and cells against their files
    from benchmark import families

    configs = {c["name"]: c for c in m["configs"]}
    resolved = {}       # config -> its file's body, where the family loads
    for c in m["configs"]:
        path = REPO / c["file"]
        if not path.is_file():
            bad(f"config {c['name']}: no file {c['file']}")
            continue
        body = json.loads(path.read_text())
        if body.get("source") != c["source"]:
            bad(f"config {c['name']}: source differs from its file")
        if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
            bad(f"config {c['name']}: reduced differs from its file")
        for key in ("assumed", "reduced", "stands_for", "source"):
            if key not in body:
                bad(f"config {c['name']}: file lacks {key!r}")
        if not any(w["config"] == c["name"] for w in m["workloads"]):
            bad(f"config {c['name']}: used by no cell")
        try:
            families.load(body)
            resolved[c["name"]] = body
        except LookupError as exc:
            bad(f"config {c['name']}: {exc}")
    pairs = set()
    for w in m["workloads"]:
        if (w["config"], w["traffic"]) in pairs:
            bad(f"cell {w['name']}: its (config, traffic) appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["config"] not in configs:
            bad(f"cell {w['name']}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            bad(f"cell {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad(f"cell {w['name']}: why must be 1-200 characters, one line")
        try:
            body = read(f"cells/{w['name']}.json")
        except OSError:
            bad(f"cell {w['name']}: no benchmark/cells/{w['name']}.json")
            continue
        for key in ("config", "traffic", "chips", "why"):
            if body.get(key) != w[key]:
                bad(f"cell {w['name']}: {key} differs from its file")
        if not (ROOT / "traffic" / f"{w['traffic']}.json").is_file():
            bad(f"cell {w['name']}: no traffic file {w['traffic']}.json")
        if w["config"] not in resolved:
            continue
        try:
            known = families.load(resolved[w["config"]],
                                  body.get("kind")).controls()
        except LookupError as exc:
            bad(f"cell {w['name']}: {exc}, which a {body.get('kind')} cell "
                f"calls")
            continue
        for lower in body.get("controls", []):
            if lower not in known:
                bad(f"cell {w['name']}: control {lower!r} is not one of its "
                    f"family's {list(known)}")
    four = sum(w["chips"] == 4 for w in m["workloads"])
    if four > max(1, len(cells) // 4):
        bad(f"{four} of {len(cells)} cells ask for four chips "
            f"(at most 25%, and one always may)")
    # metrics against their files, and the moves rule
    for x in list(e2e.values()) + m["per_layer"]:
        try:
            body = read(f"metrics/{x['name']}.json")
        except OSError:
            bad(f"metric {x['name']}: no benchmark/metrics/{x['name']}.json")
            continue
        for key in ("unit", "better", "source", "layer", "moves"):
            if key in x and body.get(key) != x[key]:
                bad(f"metric {x['name']}: {key} differs from its file")
        for w in x.get("workloads", []):
            if w not in cells:
                bad(f"metric {x['name']}: unknown cell {w!r}")
    for x in m["per_layer"]:
        if x["moves"] not in e2e:
            bad(f"{x['name']}: moves {x['moves']!r}, not an end-to-end "
                f"metric")
            continue
        try:
            reader(x["name"])
        except Exception as exc:  # noqa: BLE001 — any fault is a finding
            bad(f"metric {x['name']}: reader not found ({exc})")
        for w in x.get("workloads") or []:
            if w in cells and x["moves"] not in reported_e2e(m, w):
                bad(f"per_layer metric {x['name']} is reported on workload "
                    f"{w}, where {x['moves']}, which it should move, is not")
    for w in cells:
        e, p = reported(m, w)
        if "setup_s" not in e or len(e) < 2:
            bad(f"cell {w}: reports {e}; needs setup_s and one more")
        if not p:
            bad(f"cell {w}: reports no per-layer metric")
    return errs
