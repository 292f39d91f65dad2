"""A served class whose decode chunk does not carry the recurrent state:
the merge keeps the GRID's row-state leaves and drops the chunk's, so every
decode chunk starts again from what the admission left."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.server import BenchServer  # noqa: E402


class BrokenStateServer(BenchServer):
    def __init__(self, *args, **kwargs):
        from kubetorch_tpu.models import hybrid_linear

        sound = hybrid_linear.merge_chunk_into_grid

        def merge(cache, chunk, start, count):
            new = sound(cache, chunk, start, count)
            return {**new, **{n: cache[n] for n in hybrid_linear.ROW_LEAVES}}

        hybrid_linear.HybridLinearDecoder.merge_chunk_into_grid = \
            staticmethod(merge)
        super().__init__(*args, **kwargs)
