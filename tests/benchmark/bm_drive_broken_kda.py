"""Drive one rehearsal run of the hybrid_latent_moe family with a broken
server and print the line.

    python bm_drive_broken_kda.py state|share|share_sound

``state``: the recurrent state not carried across a decode chunk, on the toy
as it is. ``share``: the toy with experts 8-15 of its 16 held (a
configuration file written beside the run), served by a program that ignores
the share; ``share_sound``: the same share served by the sound program.
"""

import json
import sys
import tempfile
import time
from pathlib import Path

T0 = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

from benchmark import manifest, serve_cell  # noqa: E402


def main():
    import bm_broken_kda_server as servers

    cell = manifest.cell("rehearsal-hybrid-latent-moe-serve")
    server = servers.BrokenStateServer
    if sys.argv[1].startswith("share"):
        server = (servers.BrokenShareServer if sys.argv[1] == "share"
                  else servers.BenchServer)
        config = {**cell["config_json"], "num_experts": 8,
                  "experts_held": [8, 8], "reduced": ["num_experts"],
                  "published": {"num_experts": 16}}
        path = Path(tempfile.mkdtemp(prefix="kt-bm-share-")) / "share.json"
        path.write_text(json.dumps(config))
        cell.update(config_json=config, config_file=str(path))
    line = serve_cell.run(cell, manifest.benchmark_json(), 5, 5.0, False, T0,
                          rehearsal=True, server_cls=server)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
