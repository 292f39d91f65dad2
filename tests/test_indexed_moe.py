"""The fifth decoder (``models/indexed_moe.py``: a learned index scores every
earlier position, each query attends its best ``topk``, the index's key is a
third leaf of the grid, softmax-routed SwiGLU experts) against the plain
float32 reference of its architecture (``benchmark/families/indexed_moe.py``:
no cache, no kernel, no threshold search: ``lax.top_k`` over the causal
prefix), on seeded random weights at a toy size: hidden 64, 8 query heads
over 4 kv heads of 16, an index of 4 heads of 8 that keeps 16 positions, on
a grid of 96, 8 experts top 2, two layers.

Tolerances. The float32 comparisons hold LOGITS to 2e-4 (their deviation is
~1): two float32 implementations of the same sums differ by summation
order, ~2e-6 here; anything the architecture gets wrong moves logits by
1e-2 or more (``test_each_departure_fails``: float8 operands ~1.0, the most
recent ``topk`` positions for the chosen ones ~1.9, the choice ignored,
ties to the higher position, an unrotated or unnormed index key). A choice
can flip between two float32 implementations only where two index scores
(or two router scores) tie to ~1e-6; the tests that force ties make them
EXACT (zero index weights: every score is +0), where both sides follow the
same rule.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import indexed_moe as family
from kubetorch_tpu.models import IndexedMoEConfig, indexed_moe
from kubetorch_tpu.models.decoder import (decoder_for, grid_dims,
                                          off_grid_leaves, position_bytes,
                                          ring_leaves, row_leaves)
from kubetorch_tpu.models.rolling import RollingGenerator
from kubetorch_tpu.ops import (cached_attention, decode_attention,
                               indexed_attention)
from kubetorch_tpu.serving import kvpool
from kubetorch_tpu.serving.engine import DecodeEngine

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-indexed-moe-serve.json").read_text())
TOL = 2e-4
SEED = 11
TOPK = CONFIG["sa_config"]["topk"]           # 16
GRID = 96


@pytest.fixture(scope="module")
def toy():
    d = family.dims(CONFIG)
    cfg = family.program_config(CONFIG, "serve", {"max_len": GRID})
    params = family.serving_tree(SEED, d)
    return d, cfg, params


def reference_logits(d, tokens, lower=None, edit=None):
    """The reference's full forward over one sequence -> [T, V]; ``edit``
    changes a layer's plain matrices first."""
    with jax.default_matmul_precision("highest"):
        key = weights.root_key(SEED)
        glob = family.reference_globals(key, d, "serve")
        x = glob["embedding"][jnp.asarray(tokens)]
        positions = jnp.arange(len(tokens))
        for l, kind in enumerate(family.layer_kinds(d)):
            w = family.reference_layer(key, l, d, kind, "serve")
            if edit is not None:
                w = edit(w)
            x = family.block(x, w, positions, d, lower, kind)
        return np.asarray(family.head(x, glob["final_norm"],
                                      glob["lm_head"], d, lower))


def tokens_of(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], n)]


def no_index_weights(params):
    """The program's tree with the index's weight projection zeroed: every
    index score is +0, every choice a tie."""
    layers = dict(params["layers"])
    layers["wiw"] = jnp.zeros_like(layers["wiw"])
    return {**params, "layers": layers}


# ------------------------------------------------- (i) the layer itself
def test_uncached_forward_equals_the_reference(toy):
    d, cfg, params = toy
    toks = tokens_of(64)
    got = np.asarray(indexed_moe.forward(params, jnp.asarray([toks]), cfg))
    want = reference_logits(d, toks)
    assert want.std() > 0.5
    assert np.abs(got[0] - want).max() < TOL


def test_each_departure_fails(toy, monkeypatch):
    """What the tolerance has to catch, each on its own: both controls of
    the reference, and four wrong programs."""
    d, cfg, params = toy
    toks = tokens_of(64)
    want = reference_logits(d, toks)
    for lower in family.controls():
        assert np.abs(reference_logits(d, toks, lower) - want).max() > 1e-2

    def program():
        return np.asarray(indexed_moe.forward(
            params, jnp.asarray([toks]), cfg))[0]

    assert np.abs(program() - want).max() < TOL
    # the choice ignored: every query attends everything it may see
    with monkeypatch.context() as m:
        m.setattr(indexed_attention, "choice_mask",
                  lambda qi, ki, w, valid, k: valid)
        assert np.abs(program() - want).max() > 1e-2
    # the index key left unrotated
    with monkeypatch.context() as m:
        real = indexed_moe.apply_rope
        m.setattr(indexed_moe, "apply_rope",
                  lambda x, p, sin, cos: x if x.shape[-2:] == (
                      1, cfg.index_dim) else real(x, p, sin=sin, cos=cos))
        assert np.abs(program() - want).max() > 1e-2
    # the index key's LayerNorm taken for an RMSNorm
    with monkeypatch.context() as m:
        m.setattr(indexed_moe, "_layer_norm",
                  lambda x, w, b, eps: indexed_moe.rms_norm(x, w, eps))
        assert np.abs(program() - want).max() > 1e-2
    # one position too few chosen
    with monkeypatch.context() as m:
        real = indexed_attention.kth_choice
        m.setattr(indexed_attention, "kth_choice",
                  lambda keys, k: real(keys, k - 1))
        assert np.abs(program() - want).max() > 1e-2


def test_ties_go_to_the_lower_position(toy):
    """Zero index weights: every score is +0, so past ``topk`` every query
    attends positions 0 .. topk - 1 and nothing later, itself included. The
    reference follows ``lax.top_k``'s order by its own rule; the program's
    threshold search has to land on the same set."""
    d, cfg, params = toy
    toks = tokens_of(48, seed=3)

    def zero(w):
        return {**w, "wiw": jnp.zeros_like(w["wiw"])}

    want = reference_logits(d, toks, edit=zero)
    got = np.asarray(indexed_moe.forward(
        no_index_weights(params), jnp.asarray([toks]), cfg))[0]
    assert np.abs(got - want).max() < TOL
    # and it is another function than the learned choice and than last_k
    assert np.abs(want - reference_logits(d, toks)).max() > 1e-2
    assert np.abs(want - reference_logits(d, toks, "last_k",
                                          edit=zero)).max() > 1e-2


ROWS = {
    "random": lambda rng, n: rng.normal(size=n),
    "all_equal": lambda rng, n: np.full(n, 0.25),
    "many_zeros": lambda rng, n: np.where(rng.random(n) < 0.8, 0.0,
                                          rng.normal(size=n)),
    "negatives_only": lambda rng, n: -np.abs(rng.integers(0, 4, size=n)
                                              ).astype(np.float64) - 1.0,
    "few_values": lambda rng, n: rng.integers(-2, 3, size=n),
    "signed_zeros": lambda rng, n: np.where(rng.random(n) < 0.5, 0.0, -0.0),
}


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("n, k", [(70, 16), (33, 32), (16, 16), (9, 16),
                                  (200, 1)])
def test_the_threshold_choice_equals_lax_top_k(rows, n, k):
    """``kth_choice`` + ``chosen`` (32 + 1 + log2(N) compare-and-count
    passes) against the indices ``lax.top_k`` returns, on adversarial rows
    with a fifth of the positions invalid."""
    rng = np.random.default_rng(len(rows) * 1000 + n)
    x = np.stack([ROWS[rows](rng, n) for _ in range(5)]).astype(np.float32)
    # the program turns a zero of either sign into +0 before the choice
    x = np.where(x == 0, np.float32(0.0), x)
    valid = rng.random(x.shape) < 0.8
    keys = indexed_attention.order_keys(jnp.asarray(x), jnp.asarray(valid))
    v, p = indexed_attention.kth_choice(keys, k)
    got = np.asarray(indexed_attention.chosen(keys, v, p)) & valid
    for r in range(len(x)):
        want = np.zeros(n, bool)
        kk = min(k, int(valid[r].sum()))
        if kk:
            _, idx = jax.lax.top_k(
                jnp.where(jnp.asarray(valid[r]), x[r], -jnp.inf), kk)
            want[np.asarray(idx)] = True
        assert (got[r] == want).all(), (rows, r)


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("n, k", [(700, 128), (512, 512), (1100, 64)])
def test_index_choice_kernel_equals_the_plain_search(rows, n, k):
    """The decode step's threshold search in VMEM (interpreted) against
    ``kth_choice``: the same ``(v, p)`` to the bit, rows with fewer than
    ``k`` valid positions among them."""
    rng = np.random.default_rng(len(rows) + n)
    x = np.stack([ROWS[rows](rng, n) for _ in range(4)]).astype(np.float32)
    x = np.where(x == 0, np.float32(0.0), x)
    valid = rng.random(x.shape) < 0.8
    valid[3, k // 2:] = False                  # fewer than k to choose from
    keys = indexed_attention.order_keys(jnp.asarray(x), jnp.asarray(valid))
    want = indexed_attention.kth_choice(keys, k)
    got = indexed_attention.index_choice(keys, topk=k, interpret=True)
    assert (np.asarray(got[0]) == np.asarray(want[0])).all()
    assert (np.asarray(got[1]) == np.asarray(want[1])).all()
    assert int(got[1][3]) == -1


@pytest.mark.parametrize("rows", sorted(ROWS))
def test_the_references_choice_is_lax_top_ks_own_set(rows):
    """Departure (3) of the reference: the set read back from the
    ``topk``-th value is the set of ``lax.top_k``'s indices."""
    rng = np.random.default_rng(7)
    T = 40
    x = np.stack([ROWS[rows](rng, T) for _ in range(T)]).astype(np.float32)
    x = np.where(x == 0, np.float32(0.0), x)
    seen = np.tril(np.ones((T, T), bool))
    got = np.asarray(family.choice(jnp.asarray(x), jnp.asarray(seen), TOPK))
    for t in range(T):
        _, idx = jax.lax.top_k(jnp.asarray(x[t, :t + 1]), min(TOPK, t + 1))
        want = np.zeros(T, bool)
        want[np.asarray(idx)] = True
        assert (got[t] == want).all(), (rows, t)


def test_router_is_a_softmax_over_all_with_the_chosen_renormalised(toy):
    d, cfg, params = toy
    m = jax.random.normal(jax.random.key(1), (40, cfg.embed_dim))
    router = params["layers"]["router"][0]
    chosen, w = indexed_moe.route(m, router, cfg)
    p = np.asarray(jax.nn.softmax(np.asarray(m) @ np.asarray(router), -1))
    order = np.argsort(-p, -1)[:, :cfg.top_k]
    assert (np.asarray(chosen) == order).all()
    top = np.take_along_axis(p, order, -1)
    assert np.allclose(np.asarray(w), top / top.sum(-1, keepdims=True),
                       atol=1e-6)
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)


def test_long_admissions_go_through_the_experts_in_pieces(toy, monkeypatch):
    """The expert layer takes an admission in one pass where that holds no
    more than the longest admission's attention (``_held_bytes``) and in
    pieces where it would, by shapes alone (``experts.admitted_experts``):
    a generator laid out for 8 positions against one laid out for 4096, the
    same logits."""
    from kubetorch_tpu.models import experts

    d, cfg, params = toy
    T = 256
    toks = jnp.asarray([tokens_of(T, seed=2)])
    roomy = dataclasses.replace(cfg, max_seq_len=4096)
    small = dataclasses.replace(cfg, max_seq_len=8)
    E = cfg.embed_dim
    assert experts.expert_piece(T, roomy, E, 4,
                                indexed_moe._held_bytes(roomy)) == T
    assert experts.expert_piece(T, small, E, 4,
                                indexed_moe._held_bytes(small)) < T
    whole = np.asarray(indexed_moe.forward(params, toks, roomy))
    monkeypatch.setattr(indexed_moe, "_QUERY_BLOCK", 16)
    pieces = np.asarray(indexed_moe.forward(params, toks, small))
    assert np.abs(pieces - whole).max() < 1e-5
    # the cell's shapes: one pass at every bucket, the top one too
    real = IndexedMoEConfig()
    for bucket in (2048, 8192, 16384, 32768):
        assert experts.expert_piece(
            bucket, real, real.embed_dim, 2,
            indexed_moe._held_bytes(real)) == bucket


# ----------------------------------------------------- (ii) the cache
def test_the_cache_declares_three_positional_leaves(toy):
    d, cfg, params = toy
    model = decoder_for(cfg)
    assert model is indexed_moe.IndexedMoEDecoder
    leaves = model.cache_leaves(cfg)
    assert list(leaves) == [indexed_moe.KIND]
    assert [(l.name, l.shape, l.positional, l.span)
            for l in leaves[indexed_moe.KIND]] == [
        ("k", (4, 16), True, None), ("v", (4, 16), True, None),
        ("ik", (8,), True, None)]
    assert not row_leaves(model, cfg) and not ring_leaves(model, cfg)
    assert not off_grid_leaves(model, cfg)
    cache = model.init_cache(cfg, 3, GRID)
    assert cache["ik"].shape == (2, 3, GRID, 8)
    assert cache["k"].shape == cache["v"].shape == (2, 3, GRID, 4, 16)
    assert grid_dims(cache) == (3, GRID)
    # float32 here: (2 x 4 x 16 + 8) x 4 bytes a position a layer, 2 layers
    assert position_bytes(model, cfg) == 2 * (2 * 4 * 16 + 8) * 4
    chunk = model.init_chunk(cfg, cache, 3, 4)
    assert chunk["ik"].shape == (2, 3, 4, 8)


def test_what_the_fifth_decoder_does_not_carry_is_refused_by_name(toy):
    d, cfg, params = toy
    with pytest.raises(NotImplementedError, match="int8 K/V cache"):
        RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                         kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="speculative decode"):
        RollingGenerator(params, cfg, max_slots=2, max_len=GRID, spec_k=4)
    with pytest.raises(NotImplementedError, match="LoRA adapters"):
        indexed_moe.IndexedMoEDecoder.check_serving(cfg, adapters=True)
    with pytest.raises(NotImplementedError, match="mesh"):
        indexed_moe.IndexedMoEDecoder.check_serving(cfg, mesh=True)
    with pytest.raises(NotImplementedError, match="handoff"):
        indexed_moe.IndexedMoEDecoder.check_serving(cfg, handoff=True)
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID)
    with pytest.raises(NotImplementedError, match="prefix reuse"):
        gen.register_prefix(tokens_of(8))
    with pytest.raises(NotImplementedError, match="int8 K/V cache"):
        indexed_moe.init_cache(cfg, 1, 8, quantized=True)


# ----------------------------------- (iii) prefill, then decode, by logits
@pytest.mark.parametrize("n_prompt", [7, TOPK, TOPK + 1, 3 * TOPK])
def test_prefill_then_decode_through_the_cache_equals_the_reference(
        toy, n_prompt):
    """Through ``RollingGenerator``: a bucketed prefill (rows shorter than,
    equal to, one past and three times ``topk``), then one decode step a
    call over grid and chunk, the pending logits read after each: every one
    is the reference's full forward at that position. The row of 7 grows
    from under ``topk`` to over it."""
    d, cfg, params = toy
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                           steps_per_call=1)
    prompt = tokens_of(n_prompt, seed=5)
    steps = 14
    gen.submit(prompt, max_new_tokens=steps)
    gen.admit()
    seen, toks = [np.asarray(gen._logits[0])], []
    while gen.pending:
        for _, new, _ in gen.decode_step():
            toks += new
        seen.append(np.asarray(gen._logits[0]))
    assert len(toks) == steps
    want = reference_logits(d, prompt + toks)
    for i in range(steps):
        assert np.abs(seen[i] - want[n_prompt - 1 + i]).max() < TOL, i
    assert toks == [int(t) for t in
                    want[n_prompt - 1:n_prompt - 1 + steps].argmax(-1)]


def test_a_row_passes_topk_inside_one_decode_chunk(toy):
    """Depth 11 and chunks of 8 steps: the row holds 16 positions at the
    chunk's sixth step, so the steps of ONE chunk first choose everything
    and then choose; from there every choice spans grid positions AND the
    chunk's own columns. The logits after each chunk and every token are
    the reference's."""
    d, cfg, params = toy
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                           steps_per_call=8)
    prompt = tokens_of(11, seed=8)
    gen.submit(prompt, max_new_tokens=24)
    gen.admit()
    toks, seen = [], []
    while gen.pending:
        for _, new, _ in gen.decode_step():
            toks += new
        seen.append(np.asarray(gen._logits[0]))
    want = reference_logits(d, prompt + toks)
    assert toks == [int(t) for t in want[10:34].argmax(-1)]
    # after chunk c the pending logits predict position 11 + 8 (c + 1)
    for c in range(2):
        assert np.abs(seen[c] - want[10 + 8 * (c + 1)]).max() < TOL
    stats = gen.stats()
    # three chunks from depths 11, 19, 27, two layers
    assert stats["decode_kv_positions_live"] == 11 + 19 + 27
    chosen = sum(min(depth + 1, TOPK) for depth in range(11, 35))
    assert stats["decode_sparse_positions_chosen"] == 2 * chosen
    # the einsum pair reads every slot's whole plane, a layer a step
    assert stats["decode_sparse_positions_read"] == 2 * 24 * 2 * GRID
    assert stats["decode_index_positions_scored"] == 2 * 24 * 2 * (GRID + 8)


def test_chunk_columns_compete_with_grid_positions_in_one_choice():
    """``decode_choice`` over a grid part and a chunk part against one
    choice over the whole sequence, with ties across the seam."""
    rng = np.random.default_rng(3)
    B, M, C, Hi, Di, k = 3, 32, 4, 2, 4, 8
    depth = jnp.asarray([20, 5, 0], jnp.int32)
    col = 2                                    # the query sits at column 2
    qi = jnp.round(jnp.asarray(rng.normal(size=(B, 1, Hi, Di)), jnp.float32))
    w = jnp.round(jnp.asarray(rng.normal(size=(B, 1, Hi)), jnp.float32))
    whole = jnp.round(jnp.asarray(rng.normal(size=(B, M + C, Di)),
                                  jnp.float32))
    # a row's sequence: grid positions [0, depth) then the chunk's columns
    grid_ik = whole[:, :M]
    chunk_ik = jnp.stack([jax.lax.dynamic_slice_in_dim(
        whole[b], int(depth[b]), C) for b in range(B)])
    emask = jnp.broadcast_to((jnp.arange(C) <= col)[None, None, :],
                             (B, 1, C)) & (jnp.arange(B) != 2)[:, None, None]
    keys, echosen, v, p = indexed_attention.decode_choice(
        qi, w, grid_ik, chunk_ik, depth, emask, k)
    got_grid = np.asarray(indexed_attention.chosen(keys, v, p)[:, 0]
                          & (jnp.arange(M)[None] < depth[:, None]))
    for b in range(2):
        n = int(depth[b]) + col + 1
        want = np.asarray(indexed_attention.choice_mask(
            qi[b], whole[b, :n], w[b], jnp.ones((1, n), bool), k))[0]
        assert want.sum() == min(n, k)
        assert (got_grid[b, :int(depth[b])] == want[:int(depth[b])]).all()
        assert (np.asarray(echosen[b, 0, :col + 1])
                == want[int(depth[b]):]).all()
    assert not got_grid[2].any() and not np.asarray(echosen[2]).any()


def test_chunked_prefill_is_carried(toy):
    """A prompt longer than ``prefill_chunk`` goes through the chunk-mode
    forward (several query positions a row, each with its own choice over
    grid and chunk) and decodes the same tokens as the one-shot admission:
    chunked prefill is carried, in plain ``jnp``."""
    d, cfg, params = toy
    prompt = tokens_of(43, seed=6)
    out = []
    for chunk in (None, 8):
        gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                               steps_per_call=4, prefill_chunk=chunk)
        rid = gen.submit(prompt, max_new_tokens=9)
        out.append(gen.run()[rid])
    assert out[0] == out[1]
    want = reference_logits(d, prompt + out[0])
    assert out[0] == [int(t) for t in want[42:51].argmax(-1)]


def test_an_admission_of_two_rows_of_different_lengths(toy):
    """One bucketed admission of width 2: a row under ``topk`` beside one
    past it, each as it would be served alone."""
    d, cfg, params = toy
    prompts = [tokens_of(9, seed=30), tokens_of(29, seed=31)]
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                           steps_per_call=4)
    rids = [gen.submit(p, max_new_tokens=10) for p in prompts]
    gen.admit()
    assert gen.stats()["admitted"] == 2 and not gen.queued
    got = gen.run()
    for rid, prompt in zip(rids, prompts):
        want = reference_logits(d, prompt + got[rid])
        n = len(prompt)
        assert got[rid] == [int(t) for t in want[n - 1:n + 9].argmax(-1)]


def test_engine_serves_interleaved_requests_each_as_alone(toy):
    d, cfg, params = toy
    prompts = [tokens_of(n, seed=20 + i)
               for i, n in enumerate([9, 40, 17, 33, 12])]
    budgets = [14, 6, 20, 8, 12]
    alone = []
    for p, n in zip(prompts, budgets):
        gen = RollingGenerator(params, cfg, max_slots=1, max_len=GRID,
                               steps_per_call=4)
        rid = gen.submit(p, max_new_tokens=n)
        alone.append(gen.run()[rid])
    gen = RollingGenerator(params, cfg, max_slots=3, max_len=GRID,
                           steps_per_call=4)
    eng = DecodeEngine(gen, poll_s=0.002)
    try:
        from concurrent.futures import ThreadPoolExecutor

        def one(i):
            frames = eng.generate({"prompt": prompts[i],
                                   "max_new_tokens": budgets[i]})
            return [t for f in frames for t in f["tokens"]]

        with ThreadPoolExecutor(5) as pool:
            got = list(pool.map(one, range(len(prompts))))
        stats = eng.stats()
    finally:
        eng.close()
    assert got == alone
    for name in indexed_moe.INDEX_COUNTERS + indexed_moe.PREFILL_COUNTERS:
        assert stats[name] > 0, name
    assert stats["moe_expert_slots"] > 0
    assert (stats["prefill_index_pairs_scored"]
            >= stats["prefill_index_pairs_needed"])


@pytest.mark.parametrize("p_pad, prompt, plan", [
    # one pass, the tall tile: 262144 pairs in 1024 tiles of 256 a layer,
    # the prompt's 172000 pairs fill 672 of them
    (32768, 21500, (32768, 256, 4 * 1024, 4 * (1024 - 672))),
    # 1024 rows an expert and fewer: tile 128
    (16384, 10000, (16384, 128, 4 * 1024, 4 * (1024 - 625))),
    (8192, 7168, (8192, 128, 4 * 512, 4 * (512 - 448))),
    (2048, 2048, (2048, 128, 4 * 128, 0)),
])
def test_expert_admission_by_hand(p_pad, prompt, plan, monkeypatch):
    """What ``stats()`` says of an admission's expert layers, at the cell's
    widths, as where the kernel runs: tokens a pass, the row tile, the row
    tiles of 4 layers' work lists and those that hold only padding."""
    from kubetorch_tpu.models import experts
    from kubetorch_tpu.ops import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "_FORCE_INTERPRET", True)
    real = IndexedMoEConfig()
    assert indexed_moe.IndexedMoEDecoder.expert_admission(
        real, [prompt], p_pad) == plan
    # a generator laid out for 4096 positions would take 32768 in pieces
    small = dataclasses.replace(real, max_seq_len=4096)
    piece, tile, tiles, skipped = experts.admission_plan(
        small, small.embed_dim, [prompt], p_pad,
        indexed_moe._held_bytes(small), 4)
    if p_pad == 32768:
        assert (piece, tile) == (1024, 128)
        # 20 whole pieces, one of 1020 tokens (8160 pairs: all 64 of its
        # tiles hold one) and 11 of padding alone
        assert (tiles, skipped) == (4 * 32 * 64, 4 * 11 * 64)
    monkeypatch.setattr(grouped_matmul, "_FORCE_INTERPRET", False)
    assert indexed_moe.IndexedMoEDecoder.expert_admission(
        real, [prompt], p_pad) == (p_pad, 0, 0, 0)       # ``ragged_dot``


def test_prefill_counters_by_hand(toy):
    """A prompt of 40 with ``topk`` 16: queries 16 .. 39 need their ``t +
    1`` pairs scored; ``index_select`` at blocks of (128, 512) scores one
    block of 128 queries against one of 512 keys (block rounding shows);
    under ``topk`` nothing is needed and nothing scored."""
    d, cfg, params = toy
    got = indexed_moe.IndexedMoEDecoder.prefill_counters(cfg, 40)
    assert got["moe_assignments"] == 40 * 2 * 2
    assert got["prefill_index_pairs_needed"] == 2 * sum(range(17, 41))
    assert got["prefill_index_pairs_scored"] == 2 * 128 * 512
    short = indexed_moe.IndexedMoEDecoder.prefill_counters(cfg, TOPK)
    assert short["prefill_index_pairs_needed"] == 0
    assert short["prefill_index_pairs_scored"] == 0
    # at the published widths: a prompt of 10000 under topk 2048
    big = dataclasses.replace(cfg, index_topk=2048, n_layers=1)
    got = indexed_moe.IndexedMoEDecoder.prefill_counters(big, 10000)
    assert got["prefill_index_pairs_needed"] == (
        10000 * 10001 - 2048 * 2049) // 2
    blocks = [q0 for q0 in range(0, 10240, 128)
              if q0 + 128 > 2048 and q0 < 10000]
    assert got["prefill_index_pairs_scored"] == sum(
        128 * (q0 // 512 + 1) * 512 for q0 in blocks)
    assert indexed_attention.select_pairs(10000, 2048) == (
        got["prefill_index_pairs_scored"])


# ------------------------------------------- (iv) export, import and free
def test_export_and_import_carry_the_index_key(toy):
    d, cfg, params = toy
    prompt = tokens_of(27, seed=9)
    whole = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                             steps_per_call=4)
    rid = whole.submit(prompt, max_new_tokens=16)
    want = whole.run()[rid]

    a = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                         steps_per_call=4)
    rid = a.submit(prompt, max_new_tokens=16)
    a.admit()
    first = []
    for _ in range(2):
        for _, new, _ in a.decode_step():
            first += new
    state = a.export_row(rid, block_tokens=8)
    assert sorted(state["kv"]) == ["ik", "k", "v"]
    # blocks of 8 positions: the row's 27 + 8 reach into the fifth
    assert state["kv"]["ik"]["00000"].shape == (2, 8, 8)
    assert all(state["kv"]["ik"][f"{b:05d}"].any() for b in range(5))
    assert not state["kv"]["ik"]["00004"][:, 3:].any()      # past depth 35

    # into a used engine: the slot's previous occupant was longer
    b = RollingGenerator(params, cfg, max_slots=1, max_len=GRID,
                         steps_per_call=4)
    b.submit(tokens_of(58, seed=2), max_new_tokens=12)
    b.run()
    new_rid = b.import_row(state, block_tokens=8)
    assert first + b.run()[new_rid] == want

    # an index key of another width does not fit
    c = RollingGenerator(
        family.serving_tree(SEED, {**d, "Di": 4}),
        dataclasses.replace(cfg, index_dim=4), max_slots=1, max_len=GRID,
        steps_per_call=4)
    with pytest.raises(ValueError, match="does not fit"):
        c.import_row(state, block_tokens=8)


def test_a_freed_rows_stale_index_keys_are_never_chosen(toy):
    """A long row finishes; the next occupant of the slot is shorter, so the
    slot's ``ik`` (and K, V) past its depth are the old row's. A choice that
    looked past the depth would pick them (their scores are as large as
    any): the new row decodes as it would alone. The same after an
    eviction."""
    d, cfg, params = toy
    short = tokens_of(20, seed=3)
    alone = RollingGenerator(params, cfg, max_slots=1, max_len=GRID,
                             steps_per_call=4)
    rid = alone.submit(short, max_new_tokens=9)
    want = alone.run()[rid]
    gen = RollingGenerator(params, cfg, max_slots=1, max_len=GRID,
                           steps_per_call=4)
    gen.submit(tokens_of(60, seed=4), max_new_tokens=12)
    gen.run()
    assert np.asarray(gen.cache["ik"][:, 0, 40:70]).any()
    rid = gen.submit(short, max_new_tokens=9)
    assert gen.run()[rid] == want
    # evicted mid-decode, the slot reused
    old = gen.submit(tokens_of(62, seed=5), max_new_tokens=20)
    gen.admit()
    gen.decode_step()
    gen.evict(old)
    rid = gen.submit(short, max_new_tokens=9)
    assert gen.run()[rid] == want


# ------------------------------------------------- (v) the three kernels
def _index_case(rng, B, T, Hi, Di, ties):
    def r(*shape):
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        return jnp.round(x) if ties else x

    return r(B, T, Hi, Di), r(B, T, Di), r(B, T, Hi)


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("T, topk, bq, bk", [(512, 128, 128, 256),
                                             (512, 64, 64, 128),
                                             (1024, 256, 128, 512)])
def test_index_select_equals_the_plain_choice(T, topk, bq, bk, ties):
    """The admission's choice kernel (interpreted) against
    ``choice_mask``: whole rows, a row that ends mid-block, integer-valued
    operands that tie by the hundred."""
    rng = np.random.default_rng(T + topk)
    qi, ki, w = _index_case(rng, 2, T, 4, 8, ties)
    lens = jnp.asarray([T, T - 200], jnp.int32)
    mask = np.asarray(indexed_attention.index_select(
        qi, ki, w, lens, topk=topk, block_q=bq, block_k=bk, interpret=True))
    causal = np.tril(np.ones((T, T), bool))
    want = np.asarray(indexed_attention.choice_mask(
        qi, ki, w, jnp.broadcast_to(causal, (2, T, T)), topk))
    got = (mask != 0) & causal
    assert (got[0] == want[0]).all()
    n = T - 200
    assert (got[1][:n] == want[1][:n]).all()
    assert (got[0].sum(-1) == np.minimum(np.arange(T) + 1, topk)).all()
    # a query block past the row's end is not scored: the causal mask
    past = -(-n // bq) * bq
    assert (got[1][past:] == causal[past:]).all()


def test_admission_kernels_equal_the_plain_attention():
    """Both admission kernels (interpreted) against the choice and the
    einsum pair in plain ``jnp``, two rows of different lengths."""
    rng = np.random.default_rng(5)
    B, T, H, Hkv, D, topk = 2, 512, 8, 4, 128, 128
    qi, ki, w = _index_case(rng, B, T, 4, 8, False)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, h, D)), jnp.float32)
               for h in (H, Hkv, Hkv))
    lens = jnp.asarray([512, 300], jnp.int32)
    mask = indexed_attention.index_select(
        qi, ki, w, lens, topk=topk, block_q=128, block_k=256, interpret=True)
    got = indexed_attention.admit_indexed_attention(
        q, k, v, mask, block=256, interpret=True)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))
    keep = indexed_attention.choice_mask(qi, ki, w, causal, topk)
    want = cached_attention.cached_attn(q, k, v, keep)
    assert float(jnp.abs(got - want)[0].max()) < 1e-5
    assert float(jnp.abs(got - want)[1, :300].max()) < 1e-5
    assert bool(jnp.isfinite(got).all())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_indexed_decode_kernel_equals_the_masked_einsum(dtype):
    """The ragged kernel with the choice as a mask (interpreted): rows at
    depth 0, mid-block, a whole plane and inside the first block, keys of a
    few values so that every row's threshold ties."""
    rng = np.random.default_rng(9)
    L, B, M, H, Hkv, D, k = 2, 4, 1024, 8, 4, 128, 128
    depth = jnp.asarray([0, 700, 1024, 130], jnp.int32)
    k_all, v_all = (jnp.asarray(rng.normal(size=(L, B, M, Hkv, D)), dtype)
                    for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    valid = jnp.arange(M)[None] < depth[:, None]
    keys = jnp.where(valid, jnp.asarray(rng.integers(-5, 5, size=(B, M)),
                                        jnp.int32), indexed_attention._INT_MIN)
    v_thr, p_tie = indexed_attention.kth_choice(keys, k)
    acc, m, l = indexed_attention.indexed_decode_attention(
        q, k_all, v_all, jnp.int32(1), decode_attention.plan(depth, M),
        keys, v_thr, p_tie, interpret=True)
    keep = indexed_attention.chosen(keys, v_thr, p_tie) & valid
    assert (np.asarray(keep.sum(-1)) == [0, k, k, k]).all()
    want = cached_attention.cached_attn(
        q[:, None].astype(jnp.float32), k_all[1].astype(jnp.float32),
        v_all[1].astype(jnp.float32), keep[:, None, :])[:, 0]
    got = acc / jnp.maximum(l, 1e-30)[..., None]
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.abs(got - want)[1:].max()) < tol
    assert float(jnp.abs(acc[0]).max()) == 0 and float(l[0].max()) == 0


def test_engine_through_the_kernels_matches_the_plain_paths(monkeypatch):
    """A toy at head 128 with ``topk`` 128 on a grid of 1024, a prompt of
    1500 tokens' worth of blocks cut to 1024: the admission through
    ``index_select`` + ``admit_indexed_attention`` and the decode through
    ``indexed_decode_attention`` (all interpreted) serve the same tokens as
    plain ``jnp``, and the counters say what each read."""
    cfg = IndexedMoEConfig.tiny(head_dim=128, n_heads=4, n_kv_heads=2,
                                index_topk=128, max_seq_len=2048,
                                n_layers=1)
    params = indexed_moe.init(jax.random.key(0), cfg)
    prompt = tokens_of(900, seed=12)

    def run():
        gen = RollingGenerator(params, cfg, max_slots=2, max_len=2048,
                               steps_per_call=4)
        rid = gen.submit(prompt, max_new_tokens=8)
        return gen.run()[rid], gen.stats()

    plain, s0 = run()
    monkeypatch.setattr(indexed_attention, "_FORCE_INTERPRET", True)
    model = indexed_moe.IndexedMoEDecoder
    assert model.prefill_flash_engages(cfg, 1024)
    assert not model.prefill_flash_engages(cfg, 1000)
    kernel, s1 = run()
    assert kernel == plain
    # two chunks from depths 900 and 904: two 512-blocks a step a layer
    assert s1["decode_sparse_positions_read"] == 8 * 1024
    assert s0["decode_sparse_positions_read"] == 8 * 2 * 2048   # every slot
    assert s1["decode_sparse_positions_chosen"] == 8 * 128
    assert s1["decode_kv_positions_read"] == 2 * 1024
    assert s0["decode_kv_positions_read"] == 2 * 2 * 2048
    assert s1["prefill_flash_positions"] == 1024
    assert s0["prefill_flash_positions"] == 0


# ------------------------------------------------ (vi) pricing, isolation
def test_engine_prices_rows_with_the_index_key(toy):
    """``kvpool.priced_tokens`` and the engine's gauges carry the third
    leaf with no edit: a position costs K, V and the index key."""
    d, cfg, params = toy
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID)
    stats = gen.stats()
    assert stats["kv_position_bytes"] == 2 * (2 * 4 * 16 + 8) * 4
    assert stats["state_row_bytes"] == 0 and stats["window_positions"] == 0
    eng = DecodeEngine(gen, poll_s=0.002)
    try:
        # no ring, no row state: a row is priced by its depth alone
        assert eng._kv.window_tokens == 0
        assert eng._kv.row_cost(40) == kvpool.blocks_for(
            40, eng._kv.block_tokens)
    finally:
        eng.close()
    assert kvpool.priced_tokens(100) == 100
