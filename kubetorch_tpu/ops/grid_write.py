"""The two cache writes of the serving grid: a chunk's columns land at each
row's own depth (``write_columns``, once a decode or prefill chunk), an
admission's rows land whole at their slots (``write_rows``), and nothing else
of the grid is touched.

A grid leaf is ``[L, B, M, ...]`` (layers, rows, positions), its chunk
``[L, B, K, ...]``. Row ``b`` takes columns ``[0, count[b])`` at positions
``start[b] + col``; ``start`` is ``B`` scalars, so this is a loop over the
rows that have something to land, each a ``dynamic_slice`` of the row's
``[L, 1, K, ...]`` window, a ``where`` between the chunk's columns and what is
there, and a ``dynamic_update_slice`` back on the loop-carried (donated) leaf,
which XLA updates in place. The bytes moved follow ``rows x K``, not
``B x M``. An admission's private cache is ``[L, N, M_own, ...]``: row ``n``
takes the span ``[0, M_own)`` of grid row ``slots[n]`` whole, so the same loop
needs no read of the grid and no ``where``, one ``dynamic_update_slice`` a
leaf; a row-state leaf (``[L, B, *shape]``, no position axis) lands the same
way.

Contiguous slice writes at a scalar offset are not the scatter that once
serialised here (a full-cache ``take_along_axis`` read ~1.8 s a step, a
batched-axis scatter ~7 s an admission: computed index maps, one element at a
time), and not the selects over whole planes that stood in for it: until
PR 28 a one-hot einsum over all ``M`` positions of every layer (4.4 GB read
and written a chunk to land ~19 x 8 positions), until PR 32 a gather + masked
select over every row's ``[0, M_own)`` (the whole grid at the largest bucket
to land one row); both are kept as the oracles in
``tests/test_grid_write.py``. Never ``vmap`` of an update or ``.at[].set``
with computed indices: those lower to that scatter again.

A RING leaf (``[L, B, span, ...]``, position ``p`` at slot ``p % span``: what
a window layer keeps) takes a chunk's columns modulo its span
(``write_columns_ring``): the same row loop with two windows a row, one at
the landing slot and one at the ring's front for the columns that wrapped.

Pure data movement on every backend and mesh (the window is cut along rows
and positions, which no serving mesh shards): what lands is bit for bit what
the chunk, or the private cache, held.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def write_columns(grid: Dict[str, jax.Array], cols: Dict[str, jax.Array],
                  start: jax.Array, count: jax.Array
                  ) -> Dict[str, jax.Array]:
    """``grid[name][:, b, start[b] + c] = cols[name][:, b, c]`` for
    ``c < count[b]``, every leaf in one loop over the rows.

    ``start`` (>= 0) and ``count`` are ``[B]`` int32. A column at or past
    ``count[b]`` never lands, a row with ``count == 0`` is not visited, and
    a position ``>= M`` is dropped: a window that would pass the end is cut
    at ``M - K`` and the chunk's columns shifted inside it, so no write moves
    back onto a neighbour."""
    names = tuple(grid)
    _, B, M = grid[names[0]].shape[:3]
    K = min(cols[names[0]].shape[2], M)
    # K empty columns in front: one slice then picks the row AND shifts its
    # columns right by however far the window was pulled back from the end
    padded = tuple(
        jnp.pad(cols[n][:, :, :K].astype(grid[n].dtype),
                ((0, 0), (0, 0), (K, 0)) + ((0, 0),) * (grid[n].ndim - 3))
        for n in names)
    # rows with something to land, first; the loop stops after them
    order = jnp.argsort(count <= 0, stable=True).astype(jnp.int32)
    zero = jnp.int32(0)

    def row(i, leaves):
        b = order[i]
        s, n = start[b], count[b]
        w = jnp.clip(s, 0, M - K)            # the window's first position
        back = s - w                         # > 0: pulled back from the end
        col = jnp.arange(K) - back           # the column a window slot takes
        lands = (col >= 0) & (col < n)
        shift = jnp.clip(back, 0, K)
        out = []
        for leaf, pad in zip(leaves, padded):
            tail = (zero,) * (leaf.ndim - 3)
            size = (leaf.shape[0], 1, K) + leaf.shape[3:]
            new = jax.lax.dynamic_slice(pad, (zero, b, K - shift) + tail,
                                        size)
            old = jax.lax.dynamic_slice(leaf, (zero, b, w) + tail, size)
            keep = lands.reshape((1, 1, K) + (1,) * (leaf.ndim - 3))
            out.append(jax.lax.dynamic_update_slice(
                leaf, jnp.where(keep, new, old), (zero, b, w) + tail))
        return tuple(out)

    leaves = jax.lax.fori_loop(
        0, jnp.sum(count > 0, dtype=jnp.int32), row,
        tuple(grid[n] for n in names))
    return dict(zip(names, leaves))


def write_columns_ring(grid: Dict[str, jax.Array],
                       cols: Dict[str, jax.Array], start: jax.Array,
                       count: jax.Array) -> Dict[str, jax.Array]:
    """``grid[name][:, b, (start[b] + c) % span] = cols[name][:, b, c]`` for
    ``c < count[b]``: ``write_columns`` over ring leaves (``span`` their
    position axis, at least the chunk's ``K`` columns). A row's columns
    land in a window at ``start % span`` (pulled back from the end as there)
    and, where they pass the end, in a second window at slot 0; a row that
    does not wrap rewrites that second window with what it held."""
    names = tuple(grid)
    _, B, M = grid[names[0]].shape[:3]
    K = cols[names[0]].shape[2]
    if K > M:
        raise ValueError(f"a chunk of {K} columns does not fit a ring of "
                         f"{M} positions")
    # K empty columns on both sides: one slice picks the row and shifts its
    # columns right (the window pulled back) or left (the wrapped part)
    padded = tuple(
        jnp.pad(cols[n].astype(grid[n].dtype),
                ((0, 0), (0, 0), (K, K)) + ((0, 0),) * (grid[n].ndim - 3))
        for n in names)
    order = jnp.argsort(count <= 0, stable=True).astype(jnp.int32)
    zero = jnp.int32(0)

    def row(i, leaves):
        b = order[i]
        s, n = start[b] % M, count[b]
        w = jnp.clip(s, 0, M - K)
        # (window's first slot, the column its first slot takes)
        windows = ((w, w - s), (zero, M - s))
        out = []
        for leaf, pad in zip(leaves, padded):
            tail = (zero,) * (leaf.ndim - 3)
            size = (leaf.shape[0], 1, K) + leaf.shape[3:]
            for at, first in windows:
                col = first + jnp.arange(K)
                # a column lands here iff it exists and this window's slot
                # is the one it wraps to
                lands = ((col >= 0) & (col < n)
                         & ((s + col) % M == at + jnp.arange(K)))
                new = jax.lax.dynamic_slice(
                    pad, (zero, b, K + jnp.clip(first, -K, K)) + tail, size)
                old = jax.lax.dynamic_slice(leaf, (zero, b, at) + tail, size)
                keep = lands.reshape((1, 1, K) + (1,) * (leaf.ndim - 3))
                leaf = jax.lax.dynamic_update_slice(
                    leaf, jnp.where(keep, new, old), (zero, b, at) + tail)
            out.append(leaf)
        return tuple(out)

    leaves = jax.lax.fori_loop(
        0, jnp.sum(count > 0, dtype=jnp.int32), row,
        tuple(grid[n] for n in names))
    return dict(zip(names, leaves))


def positions_written(count, cols: int) -> int:
    """Positions ``write_columns`` rewrites for these counts, on the host: a
    window of ``cols`` for every row that lands anything."""
    return int(np.count_nonzero(np.asarray(count) > 0)) * cols


def write_rows(grid: Dict[str, jax.Array], own: Dict[str, jax.Array],
               slots: jax.Array) -> Dict[str, jax.Array]:
    """``grid[name][:, slots[n]]`` takes ``own[name][:, n]`` from the front
    (positions ``[0, M_own)`` of a positional leaf, the whole of a row-state
    leaf), every leaf in one loop over the rows whose slot is in range.

    ``slots`` is ``[N]`` int32, distinct where in range. A dummy row
    (``slots[n] == B``, how an admission pads its width) is not visited, so
    nothing is clamped onto the last row."""
    names = tuple(grid)
    B = grid[names[0]].shape[1]
    valid = (slots >= 0) & (slots < B)
    # rows with a slot, first; the loop stops after them
    order = jnp.argsort(~valid, stable=True).astype(jnp.int32)

    def row(i, leaves):
        n = order[i]
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(
                leaf, jax.lax.dynamic_slice_in_dim(own[name], n, 1, axis=1),
                slots[n], axis=1)
            for leaf, name in zip(leaves, names))

    leaves = jax.lax.fori_loop(
        0, jnp.sum(valid, dtype=jnp.int32), row,
        tuple(grid[n] for n in names))
    return dict(zip(names, leaves))


def row_positions_written(slots, rows: int, span: int) -> int:
    """Positions ``write_rows`` rewrites for these slots on a grid of
    ``rows`` rows, on the host: a span for every slot in range."""
    slots = np.asarray(slots)
    return int(np.count_nonzero((slots >= 0) & (slots < rows))) * span
