"""What the two kinds of cell share when they turn a run into its lines:
the children they start, the ``#`` notes, the checks of ``correct``, and the
result line's metrics, device and breakdown. No JAX here.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark import manifest


class CellFailure(Exception):
    """The run cannot give a result line (no chip, a launch that failed)."""


def note(tag, payload):
    print(f"# {tag}: {json.dumps(payload, sort_keys=True, default=str)}",
          flush=True)


def rehearsal_env(devices: int = 1) -> dict:
    return {"JAX_PLATFORMS": "cpu", "XLA_FLAGS":
            f"--xla_force_host_platform_device_count={devices}"}


def child_env(rehearsal: bool, devices: int = 1) -> dict:
    env = dict(os.environ)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(manifest.REPO)] + [p for p in env.get(
            "PYTHONPATH", "").split(os.pathsep) if p])
    if rehearsal:
        env.update(rehearsal_env(devices))
    return env


def run_child(module: str, job: dict, rehearsal: bool, timeout: float,
              workdir: str, devices: int = 1) -> dict:
    """Run ``python -m <module> <job file>`` and return its last line."""
    path = os.path.join(workdir, f"{module.rsplit('.', 1)[-1]}-job.json")
    with open(path, "w") as f:
        json.dump(job, f)
    proc = subprocess.Popen(
        [sys.executable, "-m", module, path], cwd=str(manifest.REPO),
        env=child_env(rehearsal, devices), stdout=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except BaseException:
        try:
            os.killpg(proc.pid, 9)
        except ProcessLookupError:
            pass
        proc.wait()
        raise
    if proc.returncode != 0:
        raise CellFailure(f"{module} exited {proc.returncode}")
    return json.loads(out.strip().splitlines()[-1])


class Checks:
    """The numbers ``correct`` is decided on, each printed beside its limit
    as it is read. ``compared`` keeps every one in the order read (a name
    may come twice: both stay) for the result line's last key and the run's
    last lines on standard error (``run.py``)."""

    def __init__(self):
        self.compared = []

    def limit(self, name, value, bound, ok=None):
        ok = (value <= bound) if ok is None else ok
        self.compared.append({"name": name, "value": value, "limit": bound,
                              "ok": bool(ok)})
        print(f"# check {name}: {value} (limit {bound}) "
              f"{'ok' if ok else 'FAILED'}", flush=True)

    @property
    def correct(self) -> bool:
        return all(c["ok"] for c in self.compared)


def reported(bench: dict, cell: dict):
    """(end-to-end, per-layer) names of the cell: the manifest's, or the
    cell file's own for a rehearsal cell that is in no manifest."""
    if cell["name"] in [w["name"] for w in bench["workloads"]]:
        return manifest.reported(bench, cell["name"])
    return cell.get("rehearsal_e2e", []), cell.get("rehearsal_per", [])


def metrics_of(names, trace_on: bool, values: dict, ctx: dict,
               rehearsal: bool) -> dict:
    """The line's ``metrics``: the end-to-end values, or with a trace what
    each per-layer reader finds (a reader that finds nothing is left out;
    a rehearsal reports nothing that needs the device)."""
    e2e_names, per_names = names
    out = {}
    for name in (per_names if trace_on else e2e_names):
        spec = manifest.read(f"metrics/{name}.json")
        if not trace_on:
            out[name] = {"value": values[name], "unit": spec["unit"]}
            continue
        if rehearsal and (spec["source"] == "device_trace"
                          or spec["unit"] == "%"):
            continue        # a time or a share of the device: chip only
        value = manifest.reader(name)(ctx)
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out


def attach_trace(line: dict, summary, rehearsal: bool, extra: dict):
    """``busy_s``, ``window_s`` and the breakdown of a traced run, and the
    executables' names and times on a ``# trace`` line."""
    if not summary or not summary.get("devices") or rehearsal:
        return
    line["device"]["busy_s"] = summary["busy_s"]
    line["device"]["window_s"] = summary["window_s"]
    line["breakdown"] = {"device_ops": summary["device_ops"],
                         "idle_gaps": summary["idle_gaps"]}
    note("trace", {
        "modules": {k: {kk: vv for kk, vv in v.items() if kk != "ms"}
                    for k, v in summary["modules"].items()},
        "xplane_bytes": summary.get("xplane_bytes"),
        "busy_s_per_device": summary["busy_s_per_device"], **extra})
