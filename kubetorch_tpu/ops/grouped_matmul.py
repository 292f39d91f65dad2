"""Grouped matrix product over uneven groups, for expert layers.

``lhs`` [m, k] holds rows sorted by group (rows past the groups' total
belong to none and come out zero); ``rhs_all`` [L, X, k, n] the STACKED
weights of X groups for L layers, of which ``layer`` is used; row ``r`` of
group ``g`` is multiplied by ``rhs_all[layer, g]``. The stack and the index
go in, never a sliced layer: an operand of a custom call is materialised, and
one layer of experts is a gigabyte.

The Pallas kernel walks a work list made from ``group_sizes``
(``plan_groups``): one item a (row tile, group) pair that overlap, in row
order, so a group with no row is in no item and **its weights are never
fetched**: a decode step of a few rows reads only the experts those rows
chose. An item multiplies its whole ``tile_m x k`` row tile by its group's
``k x tile_n`` block on the MXU and keeps the rows that belong to the group;
the output tile stays in VMEM across the items of one row tile. Rows past the
last group (an admission bucket's padding) are one more item a tile that
fetches nothing, neither weights nor rows, multiplies nothing and writes
zeros: **a tile with no row of any group costs no MXU pass**. The number of
items is a traced scalar: the grid is as long as the list. The row tile's
height follows the rows a group brings (``tiles_for``).

Elsewhere (CPU, a mesh) the same product is ``jax.lax.ragged_dot`` on the
sliced layer, which is also the kernel's oracle in the tests
(``interpret=True`` runs the kernel on the CPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test hook, as ``decode_attention._FORCE_INTERPRET``.
_FORCE_INTERPRET = False
# the tall row tile and the rows a group must bring for it (``tiles_for``)
_TALL, _TALL_ROWS = 256, 2048


def engages(m: int, k: int, n: int) -> bool:
    """Whether the product takes the kernel: the TPU backend, everything on
    one device, and widths the MXU tiles divide."""
    if k % 128 or n % 128:
        return False
    mesh = jax.sharding.get_abstract_mesh()
    return jax.default_backend() == "tpu" and (mesh.empty or mesh.size == 1)


def runs_kernel(m: int, k: int, n: int) -> bool:
    """``engages``, or the tests' interpret mode: whether the product walks
    the work list (what the counters of row tiles describe)."""
    return _FORCE_INTERPRET or engages(m, k, n)


def vmem_bytes(tile_m: int, tile_n: int, k: int) -> int:
    """What a grid step holds in VMEM at bf16: the row tile, the weights
    block and the output tile, each double-buffered, and the float32
    product."""
    return (4 * (tile_m * k + k * tile_n + tile_m * tile_n)
            + 4 * tile_m * tile_n)


def tiles_for(m: int, X: int, k: int, n: int):
    """(tile_m, tile_n) from the call's static shapes. ``tile_m``: 128 rows
    fill the v5e's MXU and keep the rows an item multiplies for nothing
    (those of the tile's other groups: a group of ``r`` rows touches
    ``r / tile_m + 1`` tiles) few where groups are small, which is every
    decode step and every bucket that gives a group a few hundred rows;
    fewer rows than that make one tile, rounded to the bf16 sublane pack.
    Where the rows spread evenly would give a group ``_TALL_ROWS`` or more
    (a long admission in one pass) the tile is ``_TALL`` rows: one set of
    weights pushed into the MXU then meets that many rows. ``tile_n``: the
    largest multiple of 128 that divides ``n`` and keeps the step's VMEM
    (``vmem_bytes``) under half the 64 MB the call asks for: up to ``n``
    itself where the call has more than two row tiles (an admission: each
    row tile is then fetched once and the list walked once; a whole 2048 x
    1536 block beside a 256-row tile is 18 MB), up to 1024 where it has one
    or two (a decode step, whose one row tile stays put and whose narrower
    blocks start the stream sooner). Both from the chip (PERF.md section 6,
    PR 43: the whole width 4-10% ahead at every size; beside it 256 rows
    1.5-3% ahead of 128 at 2048 rows a group, level at 1024, 3-6% behind
    at 256; 512 behind everywhere)."""
    if m < 128:
        tile_m = -(-m // 16) * 16
    else:
        tile_m = _TALL if m // X >= _TALL_ROWS else 128
    widest = n if m > 2 * tile_m else min(n, 1024)
    fits = [t for t in range(widest - widest % 128, 0, -128) if n % t == 0]
    tile_n = next((t for t in fits if vmem_bytes(tile_m, t, k) <= 32 << 20),
                  fits[-1] if fits else n)
    return tile_m, tile_n


def _tiles_of(group_sizes, m: int, tile_m: int):
    """(sizes [X+1] with the rows past the last group as pseudo-group X,
    starts, ends, first row tile and row tiles touched of each)."""
    sizes = jnp.concatenate([
        group_sizes.astype(jnp.int32),
        (m - jnp.sum(group_sizes, dtype=jnp.int32))[None]])       # [X+1]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    t_first = starts // tile_m
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tile_m - t_first + 1, 0)
    return sizes, starts, ends, t_first, n_tiles


def rows_multiplied(group_sizes, m: int, k: int, n: int):
    """Rows the MXU multiplies for ``group_sizes`` in a call of ``m`` rows:
    the work list's items that hold a row x the tile's height where the
    kernel runs (``engages``; 1.0 x the groups' rows is the least), the
    groups' rows themselves under ``ragged_dot``. int32 scalar."""
    X = group_sizes.shape[0]
    if not runs_kernel(m, k, n):
        return jnp.sum(group_sizes, dtype=jnp.int32)
    tile_m, _ = tiles_for(m, X, k, n)
    n_tiles = _tiles_of(group_sizes, -(-m // tile_m) * tile_m, tile_m)[-1]
    return tile_m * jnp.sum(n_tiles[:X], dtype=jnp.int32)


def plan_groups(group_sizes, m: int, tile_m: int):
    """The kernel's work list: ``(tile, src, group, lo, hi, first,
    n_items)``, int32. Item ``i`` computes rows ``[lo[i], hi[i])`` (all
    inside row tile ``tile[i]``) with group ``group[i]``'s weights;
    ``first[i]`` marks a row tile's first item. The rows past the last group
    are a pseudo-group X whose items have an empty row range and fetch
    nothing: their weights block is the previous item's and their rows'
    block ``src[i]`` the last tile that holds a row (``src`` is ``tile``
    everywhere else). ``m`` is a multiple of ``tile_m``. At most
    ``m / tile_m + X`` items; entries at or past ``n_items`` repeat the
    last."""
    X = group_sizes.shape[0]
    sizes, starts, ends, t_first, n_tiles = _tiles_of(group_sizes, m, tile_m)
    item_ends = jnp.cumsum(n_tiles)
    total = item_ends[-1]
    cap = m // tile_m + X
    i = jnp.minimum(jnp.arange(cap, dtype=jnp.int32), total - 1)
    g = jnp.sum(i[:, None] >= item_ends[None, :], axis=1).astype(jnp.int32)
    g = jnp.minimum(g, X)
    tile = t_first[g] + (i - (item_ends[g] - n_tiles[g]))
    lo = jnp.maximum(starts[g], tile * tile_m)
    hi = jnp.minimum(ends[g], (tile + 1) * tile_m)
    tail = g == X
    hi = jnp.where(tail, lo, hi)
    # what a tail item's blocks point at: its predecessor's
    last_real = jnp.max(jnp.where(sizes[:X] > 0, jnp.arange(X), 0))
    group = jnp.where(tail, last_real, g).astype(jnp.int32)
    src = jnp.where(tail, jnp.maximum(starts[X] - 1, 0) // tile_m, tile)
    first = jnp.concatenate([jnp.ones((1,), bool), tile[1:] != tile[:-1]])
    return (tile.astype(jnp.int32), src.astype(jnp.int32), group,
            lo.astype(jnp.int32), hi.astype(jnp.int32),
            first.astype(jnp.int32), total.astype(jnp.int32)[None])


def _kernel(layer_ref, tile_ref, src_ref, group_ref, lo_ref, hi_ref,
            first_ref, lhs_ref, rhs_ref, out_ref, *, tile_m: int):
    i = pl.program_id(1)
    lo, hi, first = lo_ref[i], hi_ref[i], first_ref[i]

    @pl.when(hi > lo)
    def _rows():
        rows = tile_ref[i] * tile_m + jax.lax.broadcasted_iota(
            jnp.int32, (tile_m, 1), 0)
        mine = (rows >= lo) & (rows < hi)
        acc = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

        @pl.when(first == 1)
        def _first():
            out_ref[...] = jnp.where(mine, acc, jnp.zeros_like(acc))

        @pl.when(first == 0)
        def _later():
            out_ref[...] = jnp.where(mine, acc, out_ref[...])

    # a tile past the last group: no product, its zeros
    @pl.when((hi <= lo) & (first == 1))
    def _padding():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _grouped_matmul_pallas(lhs, rhs_all, layer, group_sizes, *,
                           tiles=None, interpret: bool = False):
    m0, k = lhs.shape
    X, n = rhs_all.shape[1], rhs_all.shape[-1]
    tile_m, tile_n = tiles or tiles_for(m0, X, k, n)
    m = -(-m0 // tile_m) * tile_m
    if m != m0:
        lhs = jnp.pad(lhs, ((0, m - m0), (0, 0)))
    tile, src, group, lo, hi, first, n_items = plan_groups(
        group_sizes, m, tile_m)
    out = pl.pallas_call(
        functools.partial(_kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(n // tile_n, n_items[0]),
            in_specs=[
                pl.BlockSpec((tile_m, k),
                             lambda j, i, ly, t, s, g, *_: (s[i], 0)),
                pl.BlockSpec((1, 1, k, tile_n),
                             lambda j, i, ly, t, s, g, *_: (ly[0], g[i], 0,
                                                            j)),
            ],
            out_specs=pl.BlockSpec((tile_m, tile_n),
                                   lambda j, i, ly, t, *_: (t[i], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="moe_grouped_matmul",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tile, src, group, lo, hi,
      first, lhs, rhs_all)
    return out[:m0]


def grouped_matmul(lhs, rhs_all, layer, group_sizes,
                   interpret: Optional[bool] = None):
    """``out[r] = lhs[r] @ rhs_all[layer, group_of(r)]`` for the rows of the
    groups (``group_sizes`` [X] int32, rows sorted by group), zero for rows
    past them. ``lhs`` [m, k]; ``rhs_all`` [L, X, k, n]; -> [m, n] in
    ``lhs``'s dtype (f32 accumulation)."""
    m, k = lhs.shape
    n = rhs_all.shape[-1]
    if interpret is None:
        interpret = _FORCE_INTERPRET
        if not runs_kernel(m, k, n):
            rhs = jax.lax.dynamic_index_in_dim(rhs_all, layer, 0, False)
            out = jax.lax.ragged_dot(
                lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
                preferred_element_type=jnp.float32)
            in_group = jnp.arange(m) < jnp.sum(group_sizes)
            return jnp.where(in_group[:, None], out, 0).astype(lhs.dtype)
    return _grouped_matmul_pallas(lhs, rhs_all.astype(lhs.dtype), layer,
                                  group_sizes, interpret=interpret)
