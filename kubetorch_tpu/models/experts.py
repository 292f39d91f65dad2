"""The routed expert layer every expert decoder shares: dropless, the step's
(token, expert) pairs sorted by expert and the gate/up and down products
grouped over the uneven groups (``ops/grouped_matmul.py``, which reads only
the experts given a token). It is nobody's decoder: ``models/latent_moe.py``,
``models/window_moe.py`` and ``models/indexed_moe.py`` each ROUTE by their own
equation (a sigmoid with a selection bias, a softmax over the chosen, a
softmax over all renormalised) and hand the choice here with the gate's
activation; a configuration object gives ``top_k``, ``n_experts``,
``expert_mlp_dim``, ``embed_dim`` and ``compute_dtype``.

- ``routed_experts``: one pass over ``n`` tokens; ``admitted_experts`` an
  admission of any length, in one pass where memory lets it
  (``expert_piece`` / ``expert_pass_bytes``); ``experts`` the layer as a
  decoder's block calls it, on its stack and layer index.
- ``held_first`` on all three: the stack holds a SHARE of the experts the
  router chooses among (the local part of expert parallelism); pairs whose
  expert is absent are given to no expert. The exchange is not here.
- ``COUNTERS``: what a pass counts on the device; ``moe_assignments`` and
  ``expert_admission`` (``admission_plan``) what an admission adds, counted
  on the host from shapes and lengths.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubetorch_tpu.ops import grouped_matmul

COUNTERS = ("moe_assignments", "moe_experts_touched", "moe_expert_slots",
            "moe_group_max", "moe_rows_multiplied")
# the largest float32 copy of a call's gathered expert rows that
# ``routed_experts`` makes for its sum (a decode step's is 1-2 MB)
_SUM_COPY_BYTES = 16 << 20


def routed_experts(m, valid, chosen, weights, we_gu_all, we_down_all, li,
                   cfg, act=jax.nn.silu, held_first: Optional[int] = None):
    """Sum over each token's chosen experts, dropless. m [n,E]; ``valid``
    [n] bool (a padded or inactive position is given to no expert);
    ``we_*_all`` the STACKED expert weights [Lm,X,..] and ``li`` this
    layer's index in them. ``cfg`` gives ``top_k`` and ``n_experts``;
    ``act`` is the gate's activation (SiLU for the second and the fifth
    decoder, ReLU for the fourth). Returns (y [n,E] float32, counters).

    ``held_first`` is the LOCAL part of expert parallelism: the stack holds
    the ``n_experts`` consecutive experts from ``held_first`` of a router
    that chooses among more (``chosen`` names the router's); a pair whose
    expert is absent is given to no expert and adds nothing, whatever its
    weight (its term of the sum is its holder's), and the counters gain
    ``moe_assignments_held``, the pairs whose expert is here."""
    n, E = m.shape
    K, X = cfg.top_k, cfg.n_experts
    with jax.named_scope("moe_experts"):
        here = None
        if held_first is not None:
            chosen = chosen - held_first
            here = (chosen >= 0) & (chosen < X)

        def given():        # [n, K] or [n, 1]: the pairs given to an expert
            return valid[:, None] if here is None else valid[:, None] & here

        # pairs sorted by expert; pairs of no token sort past the last one
        e_flat = jnp.where(given(), chosen, X).reshape(-1)
        order = jnp.argsort(e_flat, stable=True)
        sizes = jnp.sum(e_flat[:, None] == jnp.arange(X)[None, :],
                        axis=0, dtype=jnp.int32)
        xs = m[order // K]                                       # [n*K, E]
        h = grouped_matmul.grouped_matmul(xs, we_gu_all, li, sizes)
        half = h.shape[-1] // 2
        a = (act(h[:, :half]) * h[:, half:]).astype(m.dtype)
        y = grouped_matmul.grouped_matmul(a, we_down_all, li, sizes)
        # back to token order, weighted: a gather by the inverse permutation
        # and the float32 sum over a token's K rows. Where the float32 copy
        # of the gathered rows would be large (an admission: 2 GB at 32768
        # tokens) the rows are gathered one choice at a time and summed as
        # they come; a decode step keeps the one gather, a quarter the ops
        inv = jnp.argsort(order).reshape(n, K)
        g = jnp.where(given(), weights, 0.0)
        if n * K * E * 4 <= _SUM_COPY_BYTES:
            out = jnp.einsum("nke,nk->ne", y[inv].astype(jnp.float32), g)
        else:
            out = jnp.zeros((n, E), jnp.float32)
            for c in range(K):
                out = out + y[inv[:, c]].astype(jnp.float32) * g[:, c, None]
    counters = {"moe_assignments": K * jnp.sum(valid, dtype=jnp.int32),
                "moe_experts_touched": jnp.sum(sizes > 0, dtype=jnp.int32),
                "moe_expert_slots": jnp.int32(X),
                "moe_group_max": jnp.max(sizes),
                "moe_rows_multiplied": grouped_matmul.rows_multiplied(
                    sizes, n * K, E, h.shape[-1])}
    if held_first is not None:
        counters["moe_assignments_held"] = jnp.sum(given(), dtype=jnp.int32)
    return out, counters


def expert_pass_bytes(tokens: int, cfg, E: int, itemsize: int) -> int:
    """What one pass of ``routed_experts`` over ``tokens`` tokens holds at
    its widest, from static shapes: the sorted pairs' rows beside their
    gate/up products ((E + 2 Mx) a pair) or the down products beside the
    rows gathered back (2 E a pair), whichever is more, and the float32
    sum."""
    pairs, Mx = tokens * cfg.top_k, cfg.expert_mlp_dim
    return pairs * max(E + 2 * Mx, 2 * E) * itemsize + tokens * E * 4


def expert_piece(n: int, cfg, E: int, itemsize: int, held_bytes: int) -> int:
    """Tokens of an ``n``-token admission the expert layer takes in one
    pass: all of them where that pass (``expert_pass_bytes``) holds no more
    than ``held_bytes``, what the caller's admission holds elsewhere at its
    peak; else the largest half, quarter, ... that does (never under the
    kernel's smallest row tile of pairs an expert, ``16 * n_experts /
    top_k`` tokens: below that a piece only re-reads the experts)."""
    piece = n
    floor = 16 * cfg.n_experts // cfg.top_k
    while (piece % 2 == 0 and piece // 2 >= floor
           and expert_pass_bytes(piece, cfg, E, itemsize) > held_bytes):
        piece //= 2
    return piece


def admitted_experts(m, valid, chosen, weights, we_gu_all, we_down_all, li,
                     cfg, act, held_bytes: int,
                     held_first: Optional[int] = None):
    """``routed_experts`` for an admission of any length: every fetch of an
    expert's weights should meet all the rows the admission has for it, so
    the tokens go through in ONE pass where memory lets them and in the
    largest pieces that fit where it does not (``expert_piece``), one after
    the other. A piece reads the experts it touches again. Pieces return no
    counters: an admission's are counted on the host
    (``prefill_counters``)."""
    n, E = m.shape
    piece = expert_piece(n, cfg, E, m.dtype.itemsize, held_bytes)

    def some(args):
        return routed_experts(*args, we_gu_all, we_down_all, li, cfg,
                              act=act, held_first=held_first)

    if piece == n:
        return some((m, valid, chosen, weights))
    y, _ = jax.lax.map(some, tuple(
        a.reshape((n // piece, piece) + a.shape[1:])
        for a in (m, valid, chosen, weights)))
    return y.reshape(n, E), {}


def admission_plan(cfg, E: int, lens, p_pad: int,
                   held_bytes: Optional[int], layers: int):
    """What the expert layers make of ONE bucketed admission, on the host
    and exact, from static shapes and the prompts' lengths (``lens``, a row
    each, padded to ``p_pad``): ``(piece, tile, tiles, skipped)`` = tokens a
    pass takes (``held_bytes`` None: all, the caller has no pieces), the
    row tile's height, the row tiles of the work lists (one list a layer
    serves both products) and those of them that hold no pair, the bucket's
    padding, which the kernel neither fetches nor multiplies; tile 0 and no
    tiles where the product is ``ragged_dot`` (the CPU, a mesh)."""
    n = len(lens) * p_pad
    it = jnp.dtype(cfg.compute_dtype).itemsize
    piece = n if held_bytes is None else expert_piece(n, cfg, E, it,
                                                      held_bytes)
    m, wide = piece * cfg.top_k, 2 * cfg.expert_mlp_dim
    if not grouped_matmul.runs_kernel(m, E, wide):
        return piece, 0, 0, 0
    tile = grouped_matmul.tiles_for(m, cfg.n_experts, E, wide)[0]
    real = (np.arange(p_pad)[None, :] < np.asarray(lens)[:, None]).reshape(
        n // piece, piece).sum(axis=1) * cfg.top_k
    tiles = -(-m // tile) * (n // piece)
    return (piece, tile, layers * tiles,
            layers * int(tiles - (-(-real // tile)).sum()))


def experts(m, valid, chosen, weights, stack, i, cfg, act, held_bytes: int,
            held_first: Optional[int] = None):
    """The expert layer of a decoder's block: m [n,E] in the compute dtype
    -> (sum over each token's chosen experts [n,E] float32, counters), the
    kind's stacked ``we_gu`` / ``we_down`` at layer ``i``, in one pass where
    that holds no more than ``held_bytes``, what the decoder's attention
    holds at its longest admission. ``held_first``: the stack holds a
    share of the router's experts (``routed_experts``)."""
    return admitted_experts(m, valid, chosen, weights, stack["we_gu"],
                            stack["we_down"], i, cfg, act, held_bytes,
                            held_first)


def expert_admission(cfg, lens, p_pad: int, held_bytes: Optional[int],
                     layers: int):
    """A decoder's ``expert_admission`` (``models/decoder.py``):
    ``admission_plan`` of ``layers`` expert layers at the stream's width."""
    return admission_plan(cfg, cfg.embed_dim, lens, p_pad, held_bytes,
                          layers)


def moe_assignments(cfg, prompt_tokens: int, layers: int) -> int:
    """Pairs a prefill of ``prompt_tokens`` gives ``layers`` expert layers:
    padding past a prompt's end is given to no expert, so a prefill computes
    exactly its prompt's pairs."""
    return prompt_tokens * cfg.top_k * layers
