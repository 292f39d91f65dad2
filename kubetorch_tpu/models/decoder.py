"""What the serving engine needs of a decoder: the program half of "one
engine, several decoders".

``RollingGenerator`` (and through it ``DecodeEngine``) owns admission, the
slot grid, the decode chunk, eviction, export / import and the counters; a
*decoder* owns the layers and the cache they read. The generator reaches a
decoder only through the functions below, found from the configuration
object it was built with (``decoder_for(cfg)``):

- ``layer_kinds(cfg)``: one label a layer (``("dense",) * L``; a leading
  dense layer before expert layers is ``("dense", "moe", ...)``). Layers of
  one kind have one shape.
- ``cache_leaves(cfg, quantized)``: kind -> the leaves a layer of that kind
  keeps a position (``CacheLeaf``: name, per-position shape, dtype). A leaf
  is one array ``[L, B, M, *shape]`` stacked over the layers (every layer of
  both decoders here keeps the same leaves), so the generator addresses a
  row as ``leaf[:, slot, :depth]`` whatever the leaf holds.
- ``init_cache(cfg, batch, max_len, dtype=None, quantized=False)``,
  ``init_cache_like(cfg, cache, batch, max_len)`` (a private cache of the
  grid's own leaves and dtypes, for a bucketed prefill) and
  ``init_chunk(cfg, cache, batch, cols)`` (the few columns a decode chunk or
  a prefill chunk writes before the merge).
- ``forward_cached(...)``: ``models/llama.py::forward_cached``'s contract
  (prefill into a private cache, or chunk mode over the read-only grid) with
  a third result, the step's counters (``{}`` where the decoder has none).
  A caller states what its mask is where that lets a decoder choose an
  implementation: ``grid_depth`` (a plain prefix mask, as a length) and
  ``causal_lens`` (causal from position 0 under a length: a prompt's own
  prefill); a decoder may ignore either.
- ``prefill_flash_engages(cfg, p_pad)``: whether a bucketed prefill of
  ``p_pad`` positions that passes ``causal_lens`` attends through the
  blocked flash kernel instead of the einsum pair over its private cache:
  what ``prefill_flash_positions`` counts.
- ``merge_chunk_into_grid(cache, chunk, start, count)``: chunk columns
  ``[0, count[b])`` of row ``b`` land at positions ``start[b] + col`` of
  every layer and leaf, and nothing else of the grid is read or written
  (``ops/grid_write.py``: a loop over the landing rows of slice updates, not
  a scatter and not a select over whole planes); a column at or past
  ``count[b]`` or a position ``>= M`` never lands.
- ``ragged_block(cfg, max_len, cache, spec)``: the key block of the decode
  attention that reads a row only to its depth, or None where every step
  streams the whole grid: what ``decode_kv_positions_read`` counts.
- ``counters``: names of the per-step counters ``forward_cached`` returns
  in chunk mode, summed over a decode chunk on the device and fetched with
  the chunk's tokens; ``prefill_counters(cfg, prompt_tokens)`` what a
  prefill of that many prompt tokens adds to them, counted on the host.
- ``check_serving(cfg, **features)``: raises for a serving feature the
  decoder does not carry, naming the feature.

``models/llama.py`` is the first instance (``LlamaDecoder`` only names its
functions; its executables are the ones they were), ``models/latent_moe.py``
the second.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax.numpy as jnp


class CacheLeaf(NamedTuple):
    name: str
    shape: Tuple[int, ...]      # of one position of one layer
    dtype: Any


def grid_dims(cache: Dict[str, Any]) -> Tuple[int, int, int]:
    """``(layers, rows, positions)`` of a cache: every leaf is
    ``[L, B, M, ...]``."""
    return next(iter(cache.values())).shape[:3]


def position_bytes(model, cfg, quantized: bool = False) -> int:
    """Bytes one position holds over all layers."""
    leaves = model.cache_leaves(cfg, quantized)
    return sum(math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
               for kind in model.layer_kinds(cfg) for leaf in leaves[kind])


class LlamaDecoder:
    """The dense GQA decoder (``models/llama.py``): K and V planes of
    ``n_kv_heads x head_dim`` a position, int8 with a scale a head vector
    where the grid is quantised."""

    counters: Tuple[str, ...] = ()

    @staticmethod
    def layer_kinds(cfg) -> Tuple[str, ...]:
        return ("dense",) * cfg.n_layers

    @staticmethod
    def cache_leaves(cfg, quantized: bool = False):
        vec = (cfg.n_kv_heads, cfg.head_dim)
        if quantized:
            leaves = (CacheLeaf("k", vec, jnp.int8),
                      CacheLeaf("v", vec, jnp.int8),
                      CacheLeaf("ks", vec[:1], jnp.float32),
                      CacheLeaf("vs", vec[:1], jnp.float32))
        else:
            leaves = (CacheLeaf("k", vec, cfg.compute_dtype),
                      CacheLeaf("v", vec, cfg.compute_dtype))
        return {"dense": leaves}

    @staticmethod
    def init_cache(cfg, batch, max_len, dtype=None, quantized=False):
        from kubetorch_tpu.models import llama

        return llama.init_cache(cfg, batch, max_len, dtype=dtype,
                                quantized=quantized)

    @staticmethod
    def init_cache_like(cfg, cache, batch, max_len):
        from kubetorch_tpu.models import llama

        quantized = "ks" in cache
        return llama.init_cache(
            cfg, batch, max_len,
            dtype=None if quantized else cache["k"].dtype,
            quantized=quantized)

    @staticmethod
    def init_chunk(cfg, cache, batch, cols):
        # the chunk of an int8 grid stays bf16 and quantises at the merge
        L = cache["k"].shape[0]
        Hkv, D = cache["k"].shape[3:]
        cdt = jnp.bfloat16 if "ks" in cache else cache["k"].dtype
        return {"k": jnp.zeros((L, batch, cols, Hkv, D), cdt),
                "v": jnp.zeros((L, batch, cols, Hkv, D), cdt)}

    @staticmethod
    def forward_cached(*args, **kwargs):
        from kubetorch_tpu.models import llama

        logits, cache = llama.forward_cached(*args, **kwargs)
        return logits, cache, {}

    @staticmethod
    def merge_chunk_into_grid(cache, chunk, start, count):
        from kubetorch_tpu.models import llama

        return llama.merge_chunk_into_grid(cache, chunk, start, count)

    @staticmethod
    def ragged_block(cfg, max_len, cache, spec: bool) -> Optional[int]:
        from kubetorch_tpu.ops import decode_attention

        if spec or not decode_attention.engages(
                1, max_len, cfg.n_kv_heads, cfg.head_dim, cache["k"].dtype):
            return None
        return decode_attention.block_for(max_len)

    @staticmethod
    def prefill_flash_engages(cfg, p_pad: int) -> bool:
        from kubetorch_tpu.ops import flash_attention

        return flash_attention.prefill_engages(
            p_pad, p_pad, 0, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)

    @staticmethod
    def prefill_counters(cfg, prompt_tokens: int) -> Dict[str, int]:
        return {}

    @staticmethod
    def check_serving(cfg, **features) -> None:
        """Carries every serving feature the generator has."""


def decoder_for(cfg):
    """The decoder of a configuration object, by its type."""
    from kubetorch_tpu.models.configs import LatentMoEConfig, LlamaConfig

    if isinstance(cfg, LlamaConfig):
        return LlamaDecoder
    if isinstance(cfg, LatentMoEConfig):
        from kubetorch_tpu.models.latent_moe import LatentMoEDecoder

        return LatentMoEDecoder
    raise TypeError(f"no decoder for a configuration of type "
                    f"{type(cfg).__name__}")
