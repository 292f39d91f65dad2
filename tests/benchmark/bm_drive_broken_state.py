"""Drive one rehearsal run of the hybrid_linear family with the recurrent
state not carried across a decode chunk; print the line.

    python bm_drive_broken_state.py
"""

import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

from benchmark import manifest, serve_cell  # noqa: E402


def main():
    from bm_broken_state_server import BrokenStateServer

    line = serve_cell.run(manifest.cell("rehearsal-hybrid-linear-serve"),
                          manifest.benchmark_json(), 5, 5.0, False, T0,
                          rehearsal=True, server_cls=BrokenStateServer)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
