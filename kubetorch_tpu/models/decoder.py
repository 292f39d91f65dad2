"""What the serving engine needs of a decoder: the program half of "one
engine, several decoders".

``RollingGenerator`` (and through it ``DecodeEngine``) owns admission, the
slot grid, the decode chunk, eviction, export / import and the counters; a
*decoder* owns the layers and the cache they read. The generator reaches a
decoder only through the functions below, found from the configuration
object it was built with (``decoder_for(cfg)``):

- ``layer_kinds(cfg)``: one label a layer (``("dense",) * L``; a leading
  dense layer before expert layers is ``("dense", "moe", ...)``; three
  linear-attention layers to one full-attention layer is ``("linear_attention",
  "linear_attention", "linear_attention", "full_attention", ...)``). Layers
  of one kind have one shape and keep the same leaves.
- ``cache_leaves(cfg, quantized)``: kind -> the leaves a layer of that kind
  keeps (``CacheLeaf``: name, shape, dtype, positional). Leaves are of two
  sorts. A **positional** leaf holds ``shape`` a POSITION: one array ``[L,
  B, M, *shape]`` that the generator addresses as ``leaf[:, slot, :depth]``
  whatever it holds (keys and values, a latent). A **row-state** leaf
  (``positional=False``) holds ``shape`` a ROW whatever the row's depth:
  one array ``[L, B, *shape]``, addressed as ``leaf[:, slot]`` (a recurrent
  state, a convolution's tail); it is what a row IS after its last real
  token, so it is spliced whole at admission, carried through a decode
  chunk (the grid is read-only there, the state is not), held for a row
  that is not active, exported and imported whole, and zeroed when a row is
  freed. A leaf is stacked over the layers of the kinds that list it, in
  layer order, so ``L`` is a leaf's own: all layers where every kind lists
  the leaf (both decoders before the third), the full-attention layers for
  the hybrid's ``k`` and ``v``, the linear ones for its ``state``. The
  cache is one flat dict of both sorts; ``row_leaves(model, cfg)`` names the
  second. A positional leaf has a **span** of its own (``CacheLeaf.span``):
  None is the grid's ``max_len`` (position ``p`` at ``leaf[:, slot, p]``); a
  number is a RING of that many positions (``[L, B, span, *shape]``,
  position ``p`` at ``leaf[:, slot, p % span]``: a row at depth ``d`` holds
  its last ``min(d, span)`` positions there, what a window layer can still
  see). A ring is what the row holds of those layers, so it is exported and
  imported whole beside the depth it belongs to; a freed row's ring needs
  no clearing (a ring is read to ``min(depth, span)`` as a plane is read
  to its depth). ``ring_leaves(model, cfg)`` names the rings with their
  spans, ``off_grid_leaves`` every leaf that does not lie along ``max_len``.
- ``init_cache(cfg, batch, max_len, dtype=None, quantized=False)``,
  ``init_cache_like(cfg, cache, batch, max_len)`` (a private cache of the
  grid's own leaves and dtypes, for a bucketed prefill) and
  ``init_chunk(cfg, cache, batch, cols)`` (the few columns a decode chunk or
  a prefill chunk writes before the merge; a decoder with row-state leaves
  puts the grid's own there too: the chunk is what a chunk-mode forward may
  write, and ``merge_chunk_into_grid`` takes them back whole).
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
- ``state_rows_touched(cfg, rows, live)``: rows whose row-state leaves one
  decode step over ``rows`` rows, ``live`` decoding, reads and writes (0: no
  such leaf; ``live`` where the step skips an idle row, ``rows`` where it
  holds it in place): what ``decode_state_rows_touched`` counts.
  ``scan_positions(cfg, rows, length)``: positions a recurrent layer's scan
  walks for a prefill of ``rows`` rows padded to ``length`` (0 where no
  layer scans): what ``linear_scan_positions`` counts.
- ``check_serving(cfg, **features)``: raises for a serving feature the
  decoder does not carry, naming the feature.

**What has a default.** A decoder's class inherits ``Decoder`` below and
overrides only where it differs: ``counters = ()``, ``prefill_counters`` ->
``{}``, ``state_rows_touched`` and ``scan_positions`` -> 0 (no row-state
leaf, no scan), ``init_chunk`` -> zeros for the chunk's columns of every leaf
(all positional), and ``check_serving`` from the class's ``label`` and
``refused`` (feature -> the words of the refusal; nothing refused where
``refused`` is empty). Everything else a decoder gives itself.

- a decoder with rings also gives ``window_key_blocks(cfg, p_pad)``: the
  key blocks its window layers' admission attention visits for one row of
  a ``p_pad`` bucket, and those the band touches, a layer (what
  ``prefill_window_key_blocks`` / ``_band`` count).

- a decoder with routed experts (``moe_rows_multiplied`` among its
  ``counters``) also gives ``expert_admission(cfg, lens, p_pad)``: what its
  expert layers make of one bucketed admission of rows ``lens`` long, from
  shapes alone (``experts.admission_plan``: tokens a pass, the row tile,
  the work lists' row tiles and those of them that hold only padding; what
  ``moe_piece_tokens_b<p_pad>``, ``moe_tile_rows_b<p_pad>``,
  ``moe_admission_tiles`` and ``moe_padding_tiles_skipped`` say).

``models/llama.py`` is the first instance (``LlamaDecoder`` only names its
functions; its executables are the ones they were), ``models/latent_moe.py``
the second, ``models/hybrid_linear.py`` the third and the first with
row-state leaves, ``models/window_moe.py`` the fourth and the first with
rings, ``models/indexed_moe.py`` the fifth and the first whose layer keeps a
leaf (``ik``, the index's key) that attention itself never reads,
``models/hybrid_latent_moe.py`` the sixth and the first with row-state
leaves beside a LATENT plane (and the first to hold a share of its experts).

**What a decoder is written with, and the import rule.** A decoder is a
file of its own equations. What two of them share lives in one of three
places and in no decoder's name: here, what every decoder is written with
(``scan_runs`` over a layer pattern, ``layer_at`` a stack, the stream's two
ends ``embed`` / ``unembed``, ``refusal`` and the defaults of ``Decoder``);
``models/experts.py``, the routed expert layer; ``ops/``, what asks only
shapes (the cached attentions of ``ops/cached_attention.py``, the kernels and
their tile rules). A decoder module imports ``models/configs.py``, this
module, ``models/experts.py``, ``ops/`` and ``parallel/``, and NEVER another
decoder, at module level or inside a function; ``ops/`` imports nothing from
``models/`` (tests/test_decoder_interface.py holds both). This module names
the decoders only where it finds one (``decoder_for``, ``LlamaDecoder``'s
functions), inside the function that does.
"""

from __future__ import annotations

import math
from typing import Any, Dict, FrozenSet, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from kubetorch_tpu.ops.norms import rms_norm


class CacheLeaf(NamedTuple):
    name: str
    shape: Tuple[int, ...]      # of one position (or one row) of one layer
    dtype: Any
    positional: bool = True     # False: a row-state leaf, no position axis
    span: Optional[int] = None  # positional: None = the grid's max_len; n =
    #                             a ring of n positions, p held at p % n


def row_leaves(model, cfg) -> FrozenSet[str]:
    """Names of the cache's row-state leaves (``[L, B, *shape]``); every
    other leaf is positional (``[L, B, M, *shape]``)."""
    return frozenset(leaf.name
                     for leaves in model.cache_leaves(cfg).values()
                     for leaf in leaves if not leaf.positional)


def ring_leaves(model, cfg) -> Dict[str, int]:
    """name -> span of the positional leaves held as a ring (``[L, B, span,
    ...]``, position ``p`` at ``p % span``); {} for a decoder whose every
    positional leaf lies along the grid's ``max_len``."""
    return {leaf.name: leaf.span
            for leaves in model.cache_leaves(cfg).values()
            for leaf in leaves if leaf.positional and leaf.span is not None}


def off_grid_leaves(model, cfg) -> FrozenSet[str]:
    """Names of the leaves that do not lie along the grid's ``max_len``:
    the row-state leaves and the rings (what ``grid_dims`` leaves out)."""
    return row_leaves(model, cfg) | frozenset(ring_leaves(model, cfg))


def grid_dims(cache: Dict[str, Any], rows=()) -> Tuple[int, int]:
    """``(rows, positions)`` of a cache's grid: ``B`` and ``M`` of its
    positional leaves (``[L, B, M, ...]``, each with its own ``L``), which
    agree; ``rows`` names the leaves with no ``M`` or one of their own (the
    row-state leaves and the rings: ``off_grid_leaves``)."""
    dims = {leaf.shape[1:3] for name, leaf in cache.items()
            if name not in rows}
    if len(dims) != 1:
        raise ValueError(f"positional leaves disagree on (rows, positions): "
                         f"{sorted(dims)}")
    return dims.pop()


def _leaf_bytes(model, cfg, quantized: bool, positional: bool,
                ring: bool = False) -> int:
    leaves = model.cache_leaves(cfg, quantized)
    return sum(math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
               for kind in model.layer_kinds(cfg) for leaf in leaves[kind]
               if leaf.positional == positional
               and (leaf.span is not None) == ring)


def position_bytes(model, cfg, quantized: bool = False) -> int:
    """Bytes one position holds over the layers that keep every position:
    the positional leaves along ``max_len`` of each layer's kind."""
    return _leaf_bytes(model, cfg, quantized, True)


def ring_position_bytes(model, cfg, quantized: bool = False) -> int:
    """Bytes one position holds over the layers that keep a ring: a row
    holds ``min(depth, span)`` of them (0 for a decoder with no ring)."""
    return _leaf_bytes(model, cfg, quantized, True, ring=True)


def row_bytes(model, cfg, quantized: bool = False) -> int:
    """Bytes a row holds whatever its depth: the row-state leaves of each
    layer's kind, over the layers (0 for a decoder that keeps none)."""
    return _leaf_bytes(model, cfg, quantized, False)


# ---------------------------------------- what a decoder is written with
def _runs(kinds: Tuple[str, ...]) -> List[Tuple[str, int]]:
    runs: List[Tuple[str, int]] = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    return runs


def scan_runs(layer_types: Tuple[str, ...], carry, step):
    """Run ``step(carry, kind, first, j) -> carry`` over the layers in
    order, ``first + j`` the layer's index among the layers of its kind (the
    run's first and the place in the run, handed in apart: the sum is the
    caller's to form where it reads it). The layer pattern is
    cut into runs of one kind, the shortest repeating unit of runs is the
    body of one ``lax.scan`` over its repeats and a run of several layers is
    a ``lax.scan`` inside it, so each kind's layer is compiled once a place
    in the unit, not once a layer."""
    runs = _runs(layer_types)
    unit = next(n for n in range(1, len(runs) + 1)
                if len(runs) % n == 0
                and runs == runs[:n] * (len(runs) // n))
    per_unit = {kind: sum(c for k, c in runs[:unit] if k == kind)
                for kind in dict.fromkeys(layer_types)}

    def one_unit(carry, r):
        first = {kind: r * per_unit[kind] for kind in per_unit}
        for kind, count in runs[:unit]:
            def one(carry, j, kind=kind, at=first[kind]):
                return step(carry, kind, at, j), None

            if count == 1:
                carry, _ = one(carry, jnp.int32(0))
            else:
                carry, _ = jax.lax.scan(
                    one, carry, jnp.arange(count, dtype=jnp.int32))
            first[kind] = first[kind] + count
        return carry, None

    repeats = len(runs) // unit
    if repeats == 1:
        return one_unit(carry, jnp.int32(0))[0]
    return jax.lax.scan(one_unit, carry,
                        jnp.arange(repeats, dtype=jnp.int32))[0]


def layer_at(stack, i):
    """Layer ``i`` of a leaf stacked over layers (``[L, ...]`` -> ``[...]``),
    ``i`` traced: the stacks are scanned by index, never sliced by the
    scan."""
    return jax.lax.dynamic_index_in_dim(stack, i, 0, False)


def embed(params, tokens):
    """tokens [B,T] -> the residual stream [B,T,E], float32 whatever the
    compute dtype: every product rounds its operands to the compute dtype,
    but the stream itself (and a router's input read from it) does not take
    a rounding a layer. A bf16 stream moves a router's scores by ~1e-2,
    which flips one near-tied choice in ten at 128 experts top 6 (chip run,
    PR 27)."""
    return params["embedding"][tokens].astype(jnp.float32)


def unembed(x, params, cfg, unembed_positions=None):
    """The stream's other end: x [B,T,E] -> logits [B,T,V] float32 through
    the final norm and an untied head in the compute dtype; with
    ``unembed_positions`` [B], at that one position a row ([B,1,V])."""
    if unembed_positions is not None:
        x = jnp.take_along_axis(x, unembed_positions[:, None, None], axis=1)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps).astype(
        cfg.compute_dtype)
    return jnp.einsum("bse,ev->bsv", x, params["lm_head"].astype(
        cfg.compute_dtype)).astype(jnp.float32)


def refusal(label: str, refused: Dict[str, str], *names: str):
    """The error for serving features a decoder does not carry: ``label``
    names the decoder and its module, ``refused[name]`` the feature."""
    return NotImplementedError(
        f"{label} does not carry " + "; ".join(refused[n] for n in names))


class Decoder:
    """The interface's defaults (the module docstring says which members
    have one); a decoder's class inherits this and names its own functions
    beside them."""

    counters: Tuple[str, ...] = ()
    # ``check_serving``: the decoder as a refusal names it, and what
    # RollingGenerator can be asked for that it does not carry
    label: str = ""
    refused: Dict[str, str] = {}

    @staticmethod
    def init_chunk(cfg, cache, batch, cols):
        return {name: jnp.zeros((leaf.shape[0], batch, cols)
                                + leaf.shape[3:], leaf.dtype)
                for name, leaf in cache.items()}

    @staticmethod
    def prefill_counters(cfg, prompt_tokens: int) -> Dict[str, int]:
        return {}

    @staticmethod
    def state_rows_touched(cfg, rows: int, live: int) -> int:
        return 0

    @staticmethod
    def scan_positions(cfg, rows: int, length: int) -> int:
        return 0

    @classmethod
    def check_serving(cls, cfg, kv_dtype: str = "bf16", **features) -> None:
        asked = [name for name, on in features.items()
                 if on and name in cls.refused]
        if kv_dtype != "bf16" and "kv_dtype" in cls.refused:
            asked.insert(0, "kv_dtype")
        if asked:
            raise refusal(cls.label, cls.refused, *asked)


class LlamaDecoder(Decoder):
    """The dense GQA decoder (``models/llama.py``): K and V planes of
    ``n_kv_heads x head_dim`` a position, int8 with a scale a head vector
    where the grid is quantised. It carries every serving feature the
    generator has (``refused`` is empty)."""

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


def decoder_for(cfg):
    """The decoder of a configuration object, by its type."""
    from kubetorch_tpu.models.configs import (HybridLatentMoEConfig,
                                              HybridLinearConfig,
                                              IndexedMoEConfig,
                                              LatentMoEConfig, LlamaConfig,
                                              WindowMoEConfig)

    if isinstance(cfg, LlamaConfig):
        return LlamaDecoder
    if isinstance(cfg, LatentMoEConfig):
        from kubetorch_tpu.models.latent_moe import LatentMoEDecoder

        return LatentMoEDecoder
    if isinstance(cfg, HybridLinearConfig):
        from kubetorch_tpu.models.hybrid_linear import HybridLinearDecoder

        return HybridLinearDecoder
    if isinstance(cfg, WindowMoEConfig):
        from kubetorch_tpu.models.window_moe import WindowMoEDecoder

        return WindowMoEDecoder
    if isinstance(cfg, IndexedMoEConfig):
        from kubetorch_tpu.models.indexed_moe import IndexedMoEDecoder

        return IndexedMoEDecoder
    if isinstance(cfg, HybridLatentMoEConfig):
        from kubetorch_tpu.models.hybrid_latent_moe import \
            HybridLatentMoEDecoder

        return HybridLatentMoEDecoder
    raise TypeError(f"no decoder for a configuration of type "
                    f"{type(cfg).__name__}")
