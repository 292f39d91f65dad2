"""Served classes whose learned choice is broken, one way each:
``most_recent`` orders positions by their index instead of their index
score, so every query attends the most recent ``topk`` positions (the
program doing what the reference's ``last_k`` control does);
``index_key_not_merged`` lands a decode chunk's K and V but not its index
keys, so the positions a row generated score as zero keys and are chosen by
accident or not at all."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.server import BenchServer  # noqa: E402


class MostRecentServer(BenchServer):
    def __init__(self, *args, **kwargs):
        import jax.numpy as jnp

        from kubetorch_tpu.ops import indexed_attention

        def by_position(scores, valid):
            at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
            return jnp.where(valid, jnp.broadcast_to(at, scores.shape),
                             indexed_attention._INT_MIN)

        indexed_attention.order_keys = by_position
        super().__init__(*args, **kwargs)


class IndexKeyNotMergedServer(BenchServer):
    def __init__(self, *args, **kwargs):
        import jax.numpy as jnp

        from kubetorch_tpu.models import indexed_moe

        sound = indexed_moe.merge_chunk_into_grid

        def merge(cache, chunk, start, count):
            return sound(cache, {**chunk, "ik": jnp.zeros_like(chunk["ik"])},
                         start, count)

        indexed_moe.IndexedMoEDecoder.merge_chunk_into_grid = staticmethod(
            merge)
        super().__init__(*args, **kwargs)


SERVERS = {"most_recent": MostRecentServer,
           "index_key_not_merged": IndexKeyNotMergedServer}
