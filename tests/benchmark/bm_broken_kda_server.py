"""Two served classes of the hybrid_latent_moe family that are broken
underneath the harness. ``BrokenStateServer``: the decode chunk does not
carry the recurrent state (the merge keeps the GRID's row-state leaves and
drops the chunk's, so every decode chunk starts again from what the admission
left). ``BrokenShareServer``: the expert layer forgets that it holds a share
(a pair whose expert is absent is computed by the held expert its number
wraps to, instead of by nobody)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.server import BenchServer  # noqa: E402


class BrokenStateServer(BenchServer):
    def __init__(self, *args, **kwargs):
        from kubetorch_tpu.models import hybrid_latent_moe as model

        sound = model.merge_chunk_into_grid

        def merge(cache, chunk, start, count):
            new = sound(cache, chunk, start, count)
            return {**new, **{n: cache[n] for n in model.ROW_LEAVES}}

        model.HybridLatentMoEDecoder.merge_chunk_into_grid = \
            staticmethod(merge)
        super().__init__(*args, **kwargs)


class BrokenShareServer(BenchServer):
    def __init__(self, *args, **kwargs):
        import types

        from kubetorch_tpu.models import experts
        from kubetorch_tpu.models import hybrid_latent_moe as model

        def wrapped(m, valid, chosen, weights, stack, i, cfg, act,
                    held_bytes, held_first=None):
            return experts.experts(m, valid, chosen % cfg.n_experts, weights,
                                   stack, i, cfg, act, held_bytes,
                                   held_first=0)

        model.experts = types.SimpleNamespace(
            **{**vars(experts), "experts": wrapped})
        super().__init__(*args, **kwargs)
