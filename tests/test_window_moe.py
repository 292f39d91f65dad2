"""The fourth decoder (``models/window_moe.py``: window layers that keep a
ring beside NoPE full layers that keep every position, a router that reads
the layer's input, ReGLU experts) against the plain float32 reference of its
architecture (``benchmark/families/window_moe.py``: no cache, no ring, no
kernel, the mask built from positions, experts one at a time), on seeded
random weights at a toy size: hidden 64, 8 query heads over 4 kv heads of
16, window 16 on a grid of 64, 8 experts top 2, one period and one layer
(F W W W F).

Tolerances. The float32 comparisons hold LOGITS to 2e-4 (their deviation is
~1): two float32 implementations of the same sums differ by summation
order, ~1e-5 here; anything the architecture gets wrong (the window
ignored, a ring read unrotated or past the window, rope on a full layer or
none on a window layer, the router reading the normed stream, SiLU for
ReLU, weights not the softmax of the chosen, bf16 where float32 is stated)
moves logits by 1e-2 or more (``test_each_departure_fails``). A top-k choice
can flip between two implementations only where two scores tie to ~1e-6 in
float32: with 8 experts and a few hundred tokens the smallest gap between
the 2nd and 3rd score is ~1e-4 on these seeds, so no flip decides a float32
test.
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import window_moe as family
from kubetorch_tpu.exceptions import KVGeometryMismatch
from kubetorch_tpu.models import (WindowMoEConfig, experts, latent_moe,
                                  window_moe)
from kubetorch_tpu.models.decoder import (decoder_for, grid_dims,
                                          off_grid_leaves, position_bytes,
                                          ring_leaves, ring_position_bytes)
from kubetorch_tpu.models.rolling import RollingGenerator
from kubetorch_tpu.ops import decode_attention, flash_attention, grid_write
from kubetorch_tpu.serving import kvpool
from kubetorch_tpu.serving.engine import DecodeEngine

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-window-moe-serve.json").read_text())
TOL = 2e-4
SEED = 11
W = CONFIG["sliding_window_size"]            # 16
GRID = 64


@pytest.fixture(scope="module")
def toy():
    d = family.dims(CONFIG)
    cfg = family.program_config(CONFIG, "serve", {"max_len": GRID})
    params = family.serving_tree(SEED, d)
    return d, cfg, params


def reference_logits(d, tokens, lower=None):
    """The reference's full forward over one sequence -> [T, V]."""
    with jax.default_matmul_precision("highest"):
        key = weights.root_key(SEED)
        glob = family.reference_globals(key, d, "serve")
        x = glob["embedding"][jnp.asarray(tokens)]
        positions = jnp.arange(len(tokens))
        for l, kind in enumerate(family.layer_kinds(d)):
            w = family.reference_layer(key, l, d, kind, "serve")
            x = family.block(x, w, positions, d, lower, kind)
        return np.asarray(family.head(x, glob["final_norm"],
                                      glob["lm_head"], d, lower))


def tokens_of(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], n)]


# ------------------------------------------------- (i) the layer itself
def test_uncached_forward_equals_the_reference(toy):
    d, cfg, params = toy
    toks = tokens_of(56)                     # three windows and a half
    got = np.asarray(window_moe.forward(params, jnp.asarray([toks]), cfg))[0]
    want = reference_logits(d, toks)
    assert want.std() > 0.5
    assert np.abs(got - want).max() < TOL


def test_each_departure_fails(toy):
    """What the tolerance must refuse, each made in the program's own
    parameters or configuration: the logits then leave the reference's by
    far more than TOL."""
    d, cfg, params = toy
    toks = tokens_of(56)
    want = reference_logits(d, toks)

    def gap(p=params, c=cfg):
        return np.abs(np.asarray(
            window_moe.forward(p, jnp.asarray([toks]), c))[0] - want).max()

    def stacks_with(fn):
        return {**params, **{s: {**params[s], **fn(params[s])}
                             for s in ("full", "window")}}

    departures = {
        "the window ignored": gap(c=dataclasses.replace(cfg, window=GRID)),
        "a narrower window": gap(c=dataclasses.replace(cfg, window=W - 1)),
        "another rope base": gap(c=dataclasses.replace(cfg,
                                                        rope_theta=1e3)),
        "another expert count a token": gap(c=dataclasses.replace(
            cfg, top_k=1)),
        "no router": gap(stacks_with(
            lambda s: {"router": jnp.zeros_like(s["router"])})),
        "bfloat16 compute": gap(c=dataclasses.replace(cfg,
                                                      dtype="bfloat16")),
    }
    # kinds swapped: the stacks change length, so rebuild the tree's halves
    tree = {**params, "full": params["window"], "window": params["full"]}
    swapped = dataclasses.replace(cfg, layer_types=(
        window_moe.WINDOW, window_moe.FULL, window_moe.FULL,
        window_moe.FULL, window_moe.WINDOW))
    departures["rope and window on the other layers"] = gap(tree, swapped)
    assert all(v > 20 * TOL for v in departures.values()), departures


def test_router_reads_the_layers_input_and_weighs_by_softmax_of_chosen(toy):
    d, cfg, params = toy
    x = jax.random.normal(jax.random.key(5), (64, cfg.embed_dim))
    router = params["window"]["router"][1]
    chosen, w = window_moe.route(x, router, cfg)
    scores = np.asarray(x @ router)
    want = np.argsort(-scores, axis=-1)[:, :cfg.top_k]
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(want, -1))
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=-1)
    soft = np.exp(scores) / np.exp(scores).sum(-1, keepdims=True)
    renorm = np.take_along_axis(soft, np.asarray(chosen), axis=-1)
    renorm = renorm / renorm.sum(-1, keepdims=True)
    assert np.abs(np.asarray(w) - renorm).max() < 1e-6
    assert np.abs(np.asarray(w)
                  - np.exp(picked) / np.exp(picked).sum(-1, keepdims=True)
                  ).max() < 1e-6


def _experts(m, valid, chosen, w, stack, i, cfg):
    """The shared expert layer as ``window_moe._block`` calls it."""
    return experts.experts(m, valid, chosen, w, stack, i, cfg, jax.nn.relu,
                           window_moe._held_bytes(cfg))


def test_reglu_experts_equal_a_loop_over_tokens(toy):
    d, cfg, params = toy
    n = 50
    m = jax.random.normal(jax.random.key(4), (n, cfg.embed_dim))
    valid = jnp.arange(n) % 7 != 3                   # some rows are no token
    stack = params["window"]
    chosen, w = window_moe.route(m, stack["router"][2], cfg)
    got, counters = _experts(m, valid, chosen, w, stack, 2, cfg)
    want = np.zeros((n, cfg.embed_dim), np.float32)
    for t in range(n):
        if not bool(valid[t]):
            continue
        for e, g in zip(np.asarray(chosen[t]), np.asarray(w[t])):
            h = np.asarray(m[t]) @ np.asarray(stack["we_gu"][2, e])
            half = h.shape[0] // 2
            a = np.maximum(h[:half], 0.0) * h[half:]
            want[t] += g * (a @ np.asarray(stack["we_down"][2, e]))
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    assert int(counters["moe_assignments"]) == int(valid.sum()) * cfg.top_k
    assert int(counters["moe_expert_slots"]) == cfg.n_experts


def test_long_admissions_go_through_the_experts_in_pieces(toy, monkeypatch):
    """An admission whose one pass would hold more than the decoder's
    longest attention does (``_held_bytes``: here a generator laid out for
    8 positions) goes through ``experts.admitted_experts`` in pieces, by
    shapes alone: the same sum as one pass, and no counters."""
    d, cfg, params = toy
    n = 256
    m = jax.random.normal(jax.random.key(6), (n, cfg.embed_dim))
    valid = jnp.arange(n) < 200
    stack = params["full"]
    chosen, w = window_moe.route(m, stack["router"][0], cfg)
    roomy = dataclasses.replace(cfg, max_seq_len=4096)
    whole, counted = _experts(m, valid, chosen, w, stack, 0, roomy)
    assert int(counted["moe_assignments"]) == 200 * cfg.top_k
    small = dataclasses.replace(cfg, max_seq_len=8)
    held = window_moe._held_bytes(small)
    piece = experts.expert_piece(n, small, cfg.embed_dim, 4, held)
    assert piece < n and n % piece == 0
    assert experts.expert_pass_bytes(piece, small, cfg.embed_dim,
                                     4) <= held or piece == 64
    # the pieces sum a choice at a time, as an admission's size makes them
    monkeypatch.setattr(experts, "_SUM_COPY_BYTES", 0)
    pieces, counters = _experts(m, valid, chosen, w, stack, 0, small)
    assert counters == {}
    assert np.abs(np.asarray(whole) - np.asarray(pieces)).max() < 1e-5
    # the cell's shapes: one pass at every bucket, the top one too
    real = window_moe.WindowMoEConfig(max_seq_len=16384)
    for bucket in (512, 4096, 16384):
        assert experts.expert_piece(
            bucket, real, real.embed_dim, 2,
            window_moe._held_bytes(real)) == bucket


# -------------------------------------- (ii) the cache's leaves and rings
def test_the_cache_declares_full_leaves_and_rings(toy):
    d, cfg, params = toy
    model = decoder_for(cfg)
    assert model is window_moe.WindowMoEDecoder
    assert ring_leaves(model, cfg) == {"wk": W, "wv": W}
    assert off_grid_leaves(model, cfg) == {"wk", "wv"}
    cache = model.init_cache(cfg, 3, GRID)
    assert cache["k"].shape == (2, 3, GRID, 4, 16)
    assert cache["wk"].shape == (3, 3, W, 4, 16)
    assert grid_dims(cache, off_grid_leaves(model, cfg)) == (3, GRID)
    with pytest.raises(ValueError, match="disagree"):
        grid_dims(cache)
    # a private cache under the window keeps every position
    assert model.init_cache_like(cfg, cache, 1, 8)["wk"].shape[2] == 8
    per = 2 * 4 * 16 * 4                     # K and V, float32
    assert position_bytes(model, cfg) == 2 * per
    assert ring_position_bytes(model, cfg) == 3 * per
    # the other decoders have no ring
    from kubetorch_tpu.models import LatentMoEConfig, LlamaConfig

    for other in (LlamaConfig.tiny(), LatentMoEConfig.tiny()):
        assert ring_leaves(decoder_for(other), other) == {}
        assert ring_position_bytes(decoder_for(other), other) == 0


def test_what_the_fourth_decoder_does_not_carry_is_refused_by_name(toy):
    d, cfg, params = toy
    model = decoder_for(cfg)
    model.check_serving(cfg, kv_dtype="bf16", spec=False, adapters=False)
    for feature, word in (("spec", "speculative"), ("adapters", "LoRA"),
                          ("mesh", "mesh"), ("prefix", "prefix reuse"),
                          ("handoff", "handoff")):
        with pytest.raises(NotImplementedError, match=word):
            model.check_serving(cfg, **{feature: True})
    with pytest.raises(NotImplementedError, match="int8"):
        model.check_serving(cfg, kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="int8"):
        RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                         kv_dtype="int8")


# ---------------------------- (iii) prefill then decode through the engine
@pytest.mark.parametrize("n_prompt", [7, W, 3 * W])
def test_prefill_then_decode_through_the_cache_equals_the_reference(
        toy, n_prompt):
    """Through ``RollingGenerator``: a bucketed prefill (rows shorter than,
    equal to and three times the window: the last lands its last 16
    positions in ring order), then one decode step a call over ring and
    chunk, the pending logits read after each: every one is the reference's
    full forward at that position."""
    d, cfg, params = toy
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                           steps_per_call=1)
    prompt = tokens_of(n_prompt, seed=5)
    steps = 12
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


def test_a_decode_chunk_that_crosses_the_wrap(toy):
    """Depth 13 and a chunk of 8 steps: the chunk's columns land at slots
    13, 14, 15, 0, 1, ..: the ring wraps INSIDE the merge, and the steps of
    the next chunk read a ring whose oldest entries fall out one a step."""
    d, cfg, params = toy
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                           steps_per_call=8)
    prompt = tokens_of(13, seed=8)
    rid = gen.submit(prompt, max_new_tokens=24)
    got = gen.run()[rid]
    want = reference_logits(d, prompt + got)
    assert got == [int(t) for t in want[12:36].argmax(-1)]
    stats = gen.stats()
    # three chunks from depths 13, 21, 29: the rings hold min(depth, 16)
    assert stats["decode_window_positions_live"] == 13 + 16 + 16
    assert stats["decode_kv_positions_live"] == 13 + 21 + 29
    assert stats["window_positions"] == W


def test_chunked_prefill_fills_the_same_rings(toy):
    """A prompt longer than ``prefill_chunk`` goes through the chunk-mode
    forward (several query positions over ring and chunk, each with its own
    window) and decodes the same tokens as the one-shot admission."""
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


def test_a_chunk_wider_than_the_ring_is_refused(toy):
    d, cfg, params = toy
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                           steps_per_call=4, prefill_chunk=2 * W)
    gen.submit(tokens_of(40, seed=6), max_new_tokens=4)
    with pytest.raises(ValueError, match="wider than the ring"):
        gen.run()


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
    assert stats["decode_window_positions_live"] > 0
    assert stats["prefill_window_key_blocks_band"] > 0
    assert stats["moe_expert_slots"] > 0


# ------------------------------------------- (iv) export, import and free
def test_export_import_and_free_of_a_wrapped_row(toy):
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
    assert sorted(state["rings"]) == ["wk", "wv"]
    assert state["rings"]["wk"].shape == (3, W, 4, 16)
    assert sorted(state["kv"]) == ["k", "v"]
    # a row that has not wrapped exports its ring's tail zeroed
    short = a.submit(tokens_of(5, seed=1), max_new_tokens=8)
    a.admit()
    a.decode_step()
    tail = a.export_row(short, block_tokens=8)["rings"]["wk"]
    depth = int(a._depth[next(s for s, r in a._slots.items()
                              if r.rid == short)])
    assert depth < W and not tail[:, depth:].any() and tail[:, :depth].any()

    # into a used engine: the slot's previous occupant wrapped its ring too
    b = RollingGenerator(params, cfg, max_slots=1, max_len=GRID,
                         steps_per_call=4)
    b.submit(tokens_of(30, seed=2), max_new_tokens=12)
    b.run()
    new_rid = b.import_row(state, block_tokens=8)
    assert first + b.run()[new_rid] == want

    # a ring of another span is another geometry
    c = RollingGenerator(params, dataclasses.replace(cfg, window=8),
                         max_slots=1, max_len=GRID, steps_per_call=4)
    with pytest.raises(KVGeometryMismatch) as err:
        c.import_row(state, block_tokens=8)
    assert err.value.axis == "ring"


def test_a_freed_wrapped_row_leaves_nothing_to_its_successor(toy):
    """A row that wrapped its rings finishes; the next occupant of the slot,
    shorter than the window, reads only its own positions (a ring is read to
    ``min(depth, span)``): it decodes as it would alone."""
    d, cfg, params = toy
    short = tokens_of(6, seed=3)
    alone = RollingGenerator(params, cfg, max_slots=1, max_len=GRID,
                             steps_per_call=4)
    rid = alone.submit(short, max_new_tokens=7)
    want = alone.run()[rid]
    gen = RollingGenerator(params, cfg, max_slots=1, max_len=GRID,
                           steps_per_call=4)
    gen.submit(tokens_of(45, seed=4), max_new_tokens=12)
    gen.run()
    rid = gen.submit(short, max_new_tokens=7)
    assert gen.run()[rid] == want


# -------------------------------------------------- (v) the ring's writer
@pytest.mark.parametrize("cols", [1, 4, 8, 16])
def test_ring_writer_lands_columns_modulo_the_span(cols):
    L, B, M = 2, 5, 16
    rng = np.random.default_rng(cols)
    grid = rng.normal(size=(L, B, M, 3)).astype(np.float32)
    chunk = rng.normal(size=(L, B, cols, 3)).astype(np.float32)
    for trial in range(20):
        start = rng.integers(0, 100, size=B).astype(np.int32)
        count = rng.integers(0, cols + 1, size=B).astype(np.int32)
        want = grid.copy()
        for b in range(B):
            for c in range(count[b]):
                want[:, b, (start[b] + c) % M] = chunk[:, b, c]
        got = grid_write.write_columns_ring(
            {"wk": jnp.asarray(grid)}, {"wk": jnp.asarray(chunk)},
            jnp.asarray(start), jnp.asarray(count))["wk"]
        assert np.array_equal(np.asarray(got), want), (trial, start, count)
    with pytest.raises(ValueError, match="does not fit a ring"):
        grid_write.write_columns_ring(
            {"wk": jnp.zeros((1, 1, 4, 2))}, {"wk": jnp.zeros((1, 1, 8, 2))},
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32))


# --------------------- (vi) the two kernels (interpreted) and their oracles
def _ring_case(depths, step):
    """A ring leaf [L,B,span,Hkv,D] written to ``depths``, queries at
    ``depth + step``, and the einsum oracle over the live slots."""
    L, B, span, Hkv, G, D = 2, len(depths), 256, 4, 7, 128
    ks = jax.random.split(jax.random.key(7), 3)
    k = jax.random.normal(ks[0], (L, B, span, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[1], (L, B, span, Hkv, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, Hkv * G, D), jnp.float32)
    depth = jnp.asarray(depths, jnp.int32)
    held, there = window_moe.ring_positions(depth, span)
    live = there & (held > (depth + step)[:, None] - span)         # [B,span]
    s = jnp.einsum("bkgd,bmkd->bkgm", q.reshape(B, Hkv, G, D), k[1]) * (
        D ** -0.5)
    s = jnp.where(live[:, None, None, :], s, -1e30)
    want = jnp.einsum("bkgm,bmkd->bkgd", jax.nn.softmax(s, -1), v[1])
    return q, k, v, depth, live, want.reshape(B, Hkv * G, D)


@pytest.mark.parametrize("step", [0, 3, 7])
def test_ragged_kernel_reads_a_ring_less_what_left_the_window(step):
    """7 query heads a kv head, float32 queries, a ring of 256 in blocks of
    128: rows that have not reached the span, that fill it exactly, that
    wrapped once and many times; the entries a query ``step`` past the depth
    no longer sees are masked, and only the held blocks are visited."""
    depths = [0, 5, 130, 256, 257, 300, 1000, 255 - step]
    q, k, v, depth, live, want = _ring_case(depths, step)
    items = decode_attention.ring_plan(depth, step, 256, 128)
    acc, m, l = decode_attention.ragged_decode_attention(
        q, k, v, None, None, jnp.int32(1), items, interpret=True)
    got = acc / jnp.maximum(l, 1e-30)[..., None]
    rows = np.asarray([i for i, n in enumerate(depths) if n > 0])
    assert np.abs(np.asarray(got - want))[rows].max() < 1e-5
    assert float(l[0].max()) == 0.0 and float(m[0].max()) < -1e29
    # the blocks visited: ceil(min(depth, span) / 128) a row
    n_items = int(items[3][0])
    assert n_items == sum(-(-min(n, 256) // 128) for n in depths)
    # what the mask removed: the oldest step + 1 of a full ring
    gone = np.asarray(items[5])
    assert gone[6] == step + 1 and gone[1] == 0 and gone[0] == 0
    assert int(live[6].sum()) == 256 - step - 1


def test_plain_work_list_is_the_one_it_was():
    """A plane-reading call carries no ring entries: four scalars a list,
    as before this decoder."""
    depth = jnp.asarray([3, 200], jnp.int32)
    assert len(decode_attention.plan(depth, 256, 128)) == 4
    assert len(decode_attention.ring_plan(depth, 0, 256, 128)) == 6


@pytest.mark.parametrize("T, window", [(2048, 512), (1024, 300),
                                       (2048, 4096)])
def test_banded_flash_kernel_equals_the_masked_einsum(T, window):
    """GQA 7:1 at head 128; the band narrower than the bucket (blocks
    skipped), ragged against the blocks, and wider than the bucket (plain
    causal)."""
    B, Hkv, G, D = 1, 1, 7, 128
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, T, Hkv * G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    got = flash_attention.prefill_attention(q, k, v, window)
    t = jnp.arange(T)
    seen = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < window)
    s = jnp.einsum("btkgd,bskd->bkgts", q.reshape(B, T, Hkv, G, D), k) * (
        D ** -0.5)
    s = jnp.where(seen[None, None, None], s, -1e30)
    want = jnp.einsum("bkgts,bskd->btkgd", jax.nn.softmax(s, -1), v)
    assert np.abs(np.asarray(got) - np.asarray(
        want.reshape(B, T, Hkv * G, D))).max() < 2e-5


def test_banded_call_visits_the_bands_blocks_only():
    """The grid's key axis is the band's: at 16384 positions in blocks of
    1024 under a window of 4096, five key blocks a query block instead of
    sixteen, and the host's count agrees."""
    bq = flash_attention.auto_block_q(16384)
    bk = flash_attention.auto_block_k(16384)
    assert (bq, bk) == (1024, 1024)
    assert flash_attention.band_width(16, bq, bk, 4096) == 5
    visited, band = flash_attention.prefill_key_blocks(16384, 4096, True)
    # query block qi sees blocks max(0, qi - 4) .. qi
    assert visited == band == sum(min(qi, 4) + 1 for qi in range(16))
    unbanded, _ = flash_attention.prefill_key_blocks(16384, 4096, False)
    assert unbanded == 256
    # the first block of a query block's band, as the kernel computes it
    first = [int(flash_attention.band_first_block(jnp.int32(qi), bq, bk,
                                                  4096)) for qi in range(16)]
    assert first == [max(0, qi - 4) for qi in range(16)]
    # a bucket under the window: the triangle, one block
    assert flash_attention.prefill_key_blocks(512, 4096, False) == (1, 1)


def test_engine_through_both_kernels_matches_the_einsum_paths(monkeypatch):
    """A toy with window 128 on a grid of 1024 at head 128, driven through
    the ragged kernel (interpreted) on both kinds of leaf: the same tokens
    as the einsum pair, and the counters say what each read."""
    cfg = WindowMoEConfig.tiny(head_dim=128, n_heads=4, n_kv_heads=2,
                               window=128, max_seq_len=1024,
                               layer_types=("full_attention",
                                            "window_attention"))
    params = window_moe.init(jax.random.key(0), cfg)
    prompt = tokens_of(150, seed=12)

    def run():
        gen = RollingGenerator(params, cfg, max_slots=2, max_len=1024,
                               steps_per_call=4)
        rid = gen.submit(prompt, max_new_tokens=8)
        return gen.run()[rid], gen.stats()

    plain, s0 = run()
    monkeypatch.setattr(decode_attention, "_FORCE_INTERPRET", True)
    kernel, s1 = run()
    assert kernel == plain
    # two chunks from depths 150 and 154, the ring holding 128
    assert s0["decode_window_positions_read"] == 2 * 2 * 128   # every slot
    assert s1["decode_window_positions_read"] == 2 * 128
    assert s1["decode_window_positions_live"] == 2 * 128
    assert s1["decode_kv_positions_read"] == 2 * 512       # one 512-block
    assert s0["decode_kv_positions_read"] == 2 * 2 * 1024


# ------------------------------------------------ (vii) pricing by kind
def test_kvpool_prices_a_row_by_kind():
    """Full layers x depth + window layers x min(depth, window), in the
    currency of full positions: 2 full and 6 window layers of one width make
    a window position three full ones."""
    pool = kvpool.PagedKVPool(10_000, 16, window_tokens=4096,
                              window_weight=3.0)
    assert pool.row_cost(1000) == kvpool.blocks_for(1000 + 3000, 16)
    assert pool.row_cost(4096) == kvpool.blocks_for(4 * 4096, 16)
    assert pool.row_cost(16384) == kvpool.blocks_for(16384 + 3 * 4096, 16)
    plain = kvpool.PagedKVPool(10_000, 16)
    assert plain.row_cost(16384) == kvpool.blocks_for(16384, 16)
    used = pool.reserve_row(7, 16384)
    assert used == pool.row_cost(16384)
    assert pool.release_row(7) == used


def test_engine_prices_rows_from_the_generators_gauges(toy):
    d, cfg, params = toy
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=GRID,
                           steps_per_call=4)
    stats = gen.stats()
    assert stats["window_position_bytes"] == 3 * stats["kv_position_bytes"] // 2
    eng = DecodeEngine(gen, poll_s=0.002)
    try:
        assert eng._kv.window_tokens == W
        assert eng._kv.window_weight == pytest.approx(1.5)
        assert eng._kv.row_cost(40) == kvpool.blocks_for(
            40 + 24, eng._kv.block_tokens)
    finally:
        eng.close()


# ----------------------------- (viii) the shared expert layer stays shared
def _parents_routed_experts(m, valid, chosen, weights, we_gu_all, we_down_all,
                            li, cfg):
    """``experts.routed_experts`` with SiLU written in, as it stood
    before it took the gate's activation as an argument (its sum and its
    counters as PR 43 left them): the oracle of the test below."""
    from kubetorch_tpu.ops import grouped_matmul

    n, E = m.shape
    K, X = cfg.top_k, cfg.n_experts
    with jax.named_scope("moe_experts"):
        e_flat = jnp.where(valid[:, None], chosen, X).reshape(-1)
        order = jnp.argsort(e_flat, stable=True)
        sizes = jnp.sum(e_flat[:, None] == jnp.arange(X)[None, :],
                        axis=0, dtype=jnp.int32)
        xs = m[order // K]
        h = grouped_matmul.grouped_matmul(xs, we_gu_all, li, sizes)
        half = h.shape[-1] // 2
        a = (jax.nn.silu(h[:, :half]) * h[:, half:]).astype(m.dtype)
        y = grouped_matmul.grouped_matmul(a, we_down_all, li, sizes)
        inv = jnp.argsort(order).reshape(n, K)
        g = jnp.where(valid[:, None], weights, 0.0)
        out = jnp.einsum("nke,nk->ne", y[inv].astype(jnp.float32), g)
    counters = {"moe_assignments": K * jnp.sum(valid, dtype=jnp.int32),
                "moe_experts_touched": jnp.sum(sizes > 0, dtype=jnp.int32),
                "moe_expert_slots": jnp.int32(X),
                "moe_group_max": jnp.max(sizes),
                "moe_rows_multiplied": grouped_matmul.rows_multiplied(
                    sizes, n * K, E, h.shape[-1])}
    return out, counters


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_second_decoders_executables_lower_as_before(which, monkeypatch):
    """``routed_experts`` takes the gate's activation as an argument now
    (this decoder hands in ReLU): with the default, the second decoder's
    decode and admission executables lower to the same HLO text as with the
    function as it stood."""
    from kubetorch_tpu.models import LatentMoEConfig
    from kubetorch_tpu.parallel.sharding import ShardingRules

    cfg = LatentMoEConfig.tiny()
    params = latent_moe.init(jax.random.key(0), cfg)
    b, m = 3, 64
    cache = latent_moe.init_cache(cfg, b, m)
    state = (jnp.zeros((b, cfg.vocab_size)), jnp.zeros((b,), jnp.int32),
             jnp.zeros((b,), bool), jnp.zeros((b,), jnp.int32),
             jnp.zeros((b,), bool))

    def draw(n):
        return (jnp.zeros((n,)), jnp.ones((n,)),
                jnp.full((n, 8), -1, jnp.int32), jnp.zeros((2,), jnp.uint32))

    rules = ShardingRules.default()

    def lower():
        if which == "decode":
            return jax.jit(lambda *a: RollingGenerator._decode_impl(
                *a, None, top_k=None, top_p=None, n_steps=4, cfg=cfg,
                rules=rules)).lower(params, cache, *state, *draw(b)).as_text()
        return jax.jit(lambda *a: RollingGenerator._prefill_impl(
            *a, None, p_pad=32, top_k=None, top_p=None, cfg=cfg,
            rules=rules)).lower(
            params, cache, *state, jnp.zeros((1, 32), jnp.int32),
            jnp.ones((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            *draw(1)).as_text()

    now = lower()
    monkeypatch.setattr(experts, "routed_experts", _parents_routed_experts)
    assert lower() == now
