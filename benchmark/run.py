"""Run one cell of the benchmark and print its result as the last line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python benchmark/run.py --check-manifest

A new process each time: it loads, warms the cell's own shapes (set-up),
measures for ``--seconds``, checks what the timed path produced against the
float32 reference, and prints one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device``, ``breakdown`` when traced, and last
``compared``: every number ``correct`` was decided on beside its limit, which
are also the run's last lines on standard error. Everything else goes on
earlier ``#`` lines. This process never imports JAX: the pod's
worker (serving) or a child (training) holds the chips. No chip, or fewer
than the cell asks for: exit code 3 and no result line.

``--rehearsal 1`` drives the same calls on the CPU (tests use it at tiny
sizes); its line says ``"rehearsal": true`` and carries no device metric.
``--control 1`` adds the lower-precision controls to the reference's pass:
the readings the limits in ``benchmark/cells`` were set from.
"""

from __future__ import annotations

import time

T_START, T_START_EPOCH = time.perf_counter(), time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    parser.add_argument("--control", type=int, choices=(0, 1), default=0)
    parser.add_argument("--check-manifest", action="store_true")
    parser.add_argument("--override", action="append", default=[],
                        metavar="PATH=JSON", help="sweeps only: set a key "
                        "of the traffic file, e.g. arrivals.rate_per_s=2.5; "
                        "the line then says so and is no measurement")
    parser.add_argument("--reference", type=int, choices=(0, 1), default=1,
                        help="sweeps only: 0 skips the reference's pass")
    args = parser.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmark import manifest

    if args.check_manifest:
        errors = manifest.check()
        for e in errors:
            print(f"manifest: {e}")
        print(f"manifest: {'ok' if not errors else f'{len(errors)} fault(s)'}")
        return 1 if errors else 0
    if not args.workload:
        parser.error("--workload is required")
    bench = manifest.benchmark_json()
    errors = manifest.check(bench)
    if errors:
        print("\n".join(f"manifest: {e}" for e in errors), file=sys.stderr)
        return 2
    cell = manifest.cell(args.workload)
    seconds = (args.seconds if args.seconds is not None
               else float(bench["run_seconds"]))
    for item in args.override:
        path, _, value = item.partition("=")
        node = cell["traffic_json"]
        *parents, leaf = path.split(".")
        for key in parents:
            node = node[key]
        node[leaf] = json.loads(value)
    cell["skip_reference"] = not args.reference
    from benchmark import report, serve_cell, train_cell

    try:
        if cell["kind"] == "serve":
            line = serve_cell.run(cell, bench, args.seed, seconds,
                                  bool(args.trace), T_START,
                                  rehearsal=bool(args.rehearsal),
                                  control=bool(args.control))
        elif cell["kind"] == "train":
            line = train_cell.run(cell, bench, args.seed, seconds,
                                  bool(args.trace), T_START_EPOCH,
                                  rehearsal=bool(args.rehearsal),
                                  control=bool(args.control))
        else:
            raise report.CellFailure(f"unknown kind {cell['kind']!r}")
    except report.CellFailure as exc:
        print(f"# FAILED: {exc}", file=sys.stderr, flush=True)
        return 3
    if "jax" in sys.modules:
        print("# FAILED: the parent imported JAX", file=sys.stderr)
        return 4
    if args.override or not args.reference:
        line["sweep"] = {"override": args.override,
                         "reference": bool(args.reference)}
    compared = line["compared"] = line.pop("compared")      # the last key
    print(json.dumps(line), flush=True)
    for c in compared:
        print(f"compared {c['name']}: {c['value']} (limit {c['limit']}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
