"""Drive one rehearsal run with the timed path broken; print the line.

    python bm_drive_broken.py serve|train [cell]
"""

import json
import sys
import time
from pathlib import Path

T0, T0_EPOCH = time.perf_counter(), time.time()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

from benchmark import manifest, serve_cell, train_cell  # noqa: E402


def main(kind, cell=None):
    bench = manifest.benchmark_json()
    if kind == "serve":
        from bm_broken_server import BrokenServer

        line = serve_cell.run(manifest.cell(cell or "rehearsal-serve"),
                              bench, 5, 5.0, False, T0, rehearsal=True,
                              server_cls=BrokenServer)
    else:
        line = train_cell.run(manifest.cell(cell or "rehearsal-train"),
                              bench, 5, 2.0, False, T0_EPOCH, rehearsal=True,
                              child_module="tests.benchmark.bm_broken_child")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:3])
