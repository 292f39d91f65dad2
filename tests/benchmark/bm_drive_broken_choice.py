"""Drive one rehearsal run of the indexed_moe family with its choice broken
(``bm_broken_choice_server.py``: ``most_recent`` or
``index_key_not_merged``); print the line.

    python bm_drive_broken_choice.py <break>
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
    from bm_broken_choice_server import SERVERS

    line = serve_cell.run(manifest.cell("rehearsal-indexed-moe-serve"),
                          manifest.benchmark_json(), 5, 5.0, False, T0,
                          rehearsal=True, server_cls=SERVERS[sys.argv[1]])
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
