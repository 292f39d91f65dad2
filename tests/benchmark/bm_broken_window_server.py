"""Served classes whose window layers are broken, one way each:
``window_ignored`` builds the program with a window as long as the grid (a
window layer then sees every earlier position); ``ring_not_wrapped`` lands a
decode chunk's columns in the rings at the depth itself instead of modulo
the span, so past the first wrap the rings keep what the admission left."""

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.server import BenchServer  # noqa: E402


class WindowIgnoredServer(BenchServer):
    def __init__(self, *args, **kwargs):
        from benchmark.families import window_moe as family

        sound = family.program_config

        def config(config, path, deployment=None):
            cfg = sound(config, path, deployment)
            return dataclasses.replace(cfg, window=deployment["max_len"])

        family.program_config = config
        super().__init__(*args, **kwargs)


class RingNotWrappedServer(BenchServer):
    def __init__(self, *args, **kwargs):
        from kubetorch_tpu.ops import grid_write

        grid_write.write_columns_ring = grid_write.write_columns
        super().__init__(*args, **kwargs)


SERVERS = {"window_ignored": WindowIgnoredServer,
           "ring_not_wrapped": RingNotWrappedServer}
