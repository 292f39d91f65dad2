"""The second decoder (``models/latent_moe.py``: latent attention, dropless
sigmoid-routed experts beside a shared one) against the plain float32
reference of its architecture (``benchmark/families/latent_moe.py``: never
absorbed, no cache, no kernel, experts one at a time), on seeded random
weights at a toy size: hidden 64, 4 heads of 16 | 8 | 16, latent 32, 8
experts top 2, one shared expert, 1 dense + 2 expert layers.

Tolerances. The float32 comparisons hold LOGITS to 2e-4 (their deviation is
~1): two float32 implementations of the same sums differ by summation
order, ~1e-5 here; anything the architecture gets wrong (a dropped token, an
unweighted or unscaled expert sum, a missing shared expert, the bias used
as a weight, rope on the wrong pairs, bf16 where float32 is stated) moves
logits by 1e-2 or more (``test_each_departure_fails``). A top-k choice can
flip between two implementations only where two scores tie to ~1e-6 in
float32: with 8 experts and a few hundred tokens the smallest gap between
the 2nd and 3rd score is ~1e-4 on these seeds, so no flip decides a
float32 test; the one bfloat16 test reads a MEDIAN, which a few flipped
tokens cannot move.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import latent_moe as family
from kubetorch_tpu.models import LatentMoEConfig, experts, latent_moe
from kubetorch_tpu.models.decoder import decoder_for, position_bytes
from kubetorch_tpu.models.rolling import RollingGenerator
from kubetorch_tpu.ops import grouped_matmul, latent_attention
from kubetorch_tpu.serving.engine import DecodeEngine

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-latent-moe-serve.json").read_text())
TOL = 2e-4
SEED = 11


@pytest.fixture(scope="module")
def toy():
    d = family.dims(CONFIG)
    cfg = family.program_config(CONFIG, "serve", {"max_len": 128})
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


# ------------------------------------------------------------- (i)
def test_uncached_forward_equals_the_reference(toy):
    d, cfg, params = toy
    toks = tokens_of(40)
    got = np.asarray(latent_moe.forward(params, jnp.asarray([toks]), cfg))[0]
    want = reference_logits(d, toks)
    assert want.std() > 0.5
    assert np.abs(got - want).max() < TOL


def test_each_departure_fails(toy):
    """What the tolerance must refuse, each made in the program's own
    parameters or configuration: the logits then leave the reference's by
    far more than TOL."""
    import dataclasses

    d, cfg, params = toy
    toks = tokens_of(40)
    want = reference_logits(d, toks)

    def gap(p=params, c=cfg):
        return np.abs(np.asarray(
            latent_moe.forward(p, jnp.asarray([toks]), c))[0] - want).max()

    def moe_with(**leaves):
        return {**params, "moe": {**params["moe"], **leaves}}

    moe = params["moe"]
    departures = {
        "unscaled expert sum": gap(c=dataclasses.replace(
            cfg, routed_scale=1.0)),
        "weights not renormalised": gap(c=dataclasses.replace(
            cfg, norm_topk=False)),
        "no shared expert": gap(moe_with(
            ws_down=jnp.zeros_like(moe["ws_down"]))),
        "another expert count a token": gap(c=dataclasses.replace(
            cfg, top_k=1)),
        "no selection bias": gap(moe_with(
            router_bias=jnp.zeros_like(moe["router_bias"]))),
        "rope on other dimensions": gap(c=dataclasses.replace(
            cfg, rope_theta=1e3)),
        "bfloat16 compute": gap(c=dataclasses.replace(cfg,
                                                      dtype="bfloat16")),
    }
    assert all(v > 20 * TOL for v in departures.values()), departures


def test_bfloat16_program_stays_near_the_float32_reference(toy):
    """bfloat16 compute over the same (float32-stored) weights: rounding
    moves logits by ~1e-2 and flips a near-tied expert choice now and then,
    which moves that token's logits by ~1e-1. The MEDIAN gap is held, which
    flips cannot decide; the float32 tests above hold everything else."""
    import dataclasses

    d, cfg, params = toy
    toks = tokens_of(96, seed=3)
    got = np.asarray(latent_moe.forward(
        params, jnp.asarray([toks]),
        dataclasses.replace(cfg, dtype="bfloat16")))[0]
    err = np.abs(got - reference_logits(d, toks))
    assert 1e-4 < np.median(err) < 3e-2, np.median(err)


# ------------------------------------------------------------ (ii)
def test_prefill_then_decode_through_the_cache_equals_the_reference(toy):
    """Through ``RollingGenerator``: a bucketed prefill (the EXPAND path
    into the latent cache), then one decode step a call (the ABSORBED path
    over grid and chunk), the pending logits read after each: every one is
    the reference's full forward at that position."""
    d, cfg, params = toy
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=128,
                           steps_per_call=1)
    prompt = tokens_of(21, seed=5)
    gen.submit(prompt, max_new_tokens=12)
    gen.admit()
    seen, toks = [np.asarray(gen._logits[0])], []
    while gen.pending:
        for _, new, _ in gen.decode_step():
            toks += new
        seen.append(np.asarray(gen._logits[0]))
    assert len(toks) == 12
    want = reference_logits(d, prompt + toks)
    for i in range(12):
        assert np.abs(seen[i] - want[len(prompt) - 1 + i]).max() < TOL, i
    assert toks == [int(t) for t in
                    want[len(prompt) - 1:len(prompt) + 11].argmax(-1)]


def test_chunked_prefill_fills_the_same_cache(toy):
    """A prompt longer than ``prefill_chunk`` goes through the chunk-mode
    forward (absorbed, several query positions) and decodes the same
    tokens as the one-shot admission."""
    d, cfg, params = toy
    prompt = tokens_of(37, seed=6)
    out = []
    for chunk in (None, 16):
        gen = RollingGenerator(params, cfg, max_slots=2, max_len=128,
                               steps_per_call=4, prefill_chunk=chunk)
        rid = gen.submit(prompt, max_new_tokens=9)
        out.append(gen.run()[rid])
    assert out[0] == out[1]
    want = reference_logits(d, prompt + out[0])
    assert out[0] == [int(t) for t in want[36:45].argmax(-1)]


# ----------------------------------------------------------- (iii)
def test_absorbed_attention_equals_expanded_on_one_layer(toy):
    d, cfg, params = toy
    layer = {k: v[0] for k, v in params["moe"].items()}
    T, B = 24, 2
    x = jax.random.normal(jax.random.key(1), (B, T, cfg.embed_dim))
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    sin, cos = latent_moe._angles(positions, cfg)
    qn, qr, c, kr = latent_moe._attn_inputs(x, layer, sin, cos, cfg)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool))[None],
                              (B, T, T))
    want = latent_moe._attn_expand(qn, qr, c, kr, layer, causal, cfg)
    # the last 4 positions as a chunk over a grid that holds the first 20
    G, K, M = 20, 4, 32
    packed = latent_moe._pack(c, kr, cfg, jnp.float32)
    grid = jnp.full((1, B, M, packed.shape[-1]), jnp.nan).at[0, :, :G].set(
        packed[:, :G])                    # NaN past the depth: never read
    gmask = jnp.broadcast_to(jnp.arange(M)[None, None, :] < G, (B, K, M))
    emask = jnp.broadcast_to(jnp.tril(jnp.ones((K, K), bool))[None],
                             (B, K, K))
    got = latent_moe._attn_absorbed(qn[:, G:], qr[:, G:], layer, 0, grid,
                                    packed[:, G:], gmask, emask, None, cfg)
    assert np.abs(np.asarray(got) - np.asarray(want[:, G:])).max() < 1e-5


def test_ragged_decode_kernel_equals_the_einsum_over_the_grid():
    B, H, r, dr, M, L = 3, 4, 128, 64, 256, 2
    W = r + 128
    ks = jax.random.split(jax.random.key(2), 2)
    q = jax.random.normal(ks[0], (B, H, W)).at[..., r + dr:].set(0)
    grid = jax.random.normal(ks[1], (L, B, M, W)).at[..., r + dr:].set(0)
    depth = jnp.asarray([200, 0, 129], jnp.int32)
    acc, m, l = latent_attention.ragged_decode_attention(
        q, grid, jnp.int32(1), latent_attention.plan(depth, M), r, 0.1,
        interpret=True)
    s = jnp.einsum("bhw,bmw->bhm", q, grid[1]) * 0.1
    s = jnp.where(jnp.arange(M)[None, None] < depth[:, None, None], s, -1e30)
    want = jnp.einsum("bhm,bmr->bhr", jax.nn.softmax(s, -1),
                      grid[1, ..., :r])
    got = acc / jnp.maximum(l, 1e-30)[..., None]
    live = np.asarray([0, 2])
    assert np.abs(np.asarray(got - want))[live].max() < 1e-5
    assert float(l[1].max()) == 0.0 and float(m[1].max()) < -1e29


def test_prefill_kernel_equals_causal_attention_with_unlike_head_sizes():
    B, T, H, dn, dr, dv = 1, 1024, 2, 128, 64, 128
    ks = jax.random.split(jax.random.key(3), 5)
    qn = jax.random.normal(ks[0], (B, T, H, dn))
    qr = jax.random.normal(ks[1], (B, T, H, dr))
    kn = jax.random.normal(ks[2], (B, T, H, dn))
    kr = jax.random.normal(ks[3], (B, T, dr))
    v = jax.random.normal(ks[4], (B, T, H, dv))
    got = latent_attention.prefill_attention(qn, qr, kn, kr, v, 0.07,
                                             interpret=True)
    s = (jnp.einsum("bthd,bshd->bhts", qn, kn)
         + jnp.einsum("bthd,bsd->bhts", qr, kr)) * 0.07
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e30)
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


# ------------------------------------------------------------ (iv)
def _loop(lhs, rhs, sizes):
    want = np.zeros((lhs.shape[0], rhs.shape[-1]), np.float32)
    at = 0
    for g, size in enumerate(sizes):
        want[at:at + size] = np.asarray(lhs[at:at + size]) @ np.asarray(
            rhs[g])
        at += size
    return want


# every height ``tiles_for`` can return: the call's rows rounded to the
# sublane pack (None: the rule's own for these few rows), 128, the tall tile
@pytest.mark.parametrize("tile_m", [None, 128, grouped_matmul._TALL])
@pytest.mark.parametrize("sizes, m", [
    ([0, 7, 0, 20, 3, 0, 11, 0], 50),       # rows past the groups: zeros
    ([0, 0, 300, 0, 0, 0, 0, 0], 300),      # one expert gets all
    ([100, 0, 0, 150, 0, 0, 0, 30], 300),   # groups across row tiles
    ([1, 1, 1, 1, 1, 1, 1, 1], 8),
])
def test_grouped_product_equals_the_loop(sizes, m, tile_m):
    X, k, n = 8, 64, 96
    lhs = jax.random.normal(jax.random.key(0), (m, k), jnp.float32)
    rhs = jax.random.normal(jax.random.key(1), (3, X, k, n), jnp.float32)
    want = _loop(lhs, rhs[1], sizes)
    sizes = jnp.asarray(sizes, jnp.int32)
    if tile_m is None:
        kernel = grouped_matmul.grouped_matmul(lhs, rhs, jnp.int32(1), sizes,
                                               interpret=True)
    else:
        kernel = grouped_matmul._grouped_matmul_pallas(
            lhs, rhs, jnp.int32(1), sizes, tiles=(tile_m, n), interpret=True)
    plain = grouped_matmul.grouped_matmul(lhs, rhs, jnp.int32(1), sizes)
    assert np.abs(np.asarray(kernel) - want).max() < 1e-4
    assert np.abs(np.asarray(plain) - want).max() < 1e-4


@pytest.mark.parametrize("tile_m", [16, 128, grouped_matmul._TALL])
@pytest.mark.parametrize("sizes", [
    [100, 0, 300, 0, 5, 0, 700, 19],        # the tail starts inside a tile
    [0, 0, 0, 0, 0, 0, 0, 1024],            # and on a tile's edge
    [0] * 8,                                # an all-padding piece
])
def test_a_tile_of_padding_is_not_multiplied(sizes, tile_m):
    """Rows past the last group are poisoned: a product over them would
    carry NaN into its tile. They come out zero, the groups' rows exact,
    and the work list gives the padding's tiles no rows and no fetch."""
    X, k, n, m = 8, 128, 256, 2048
    total = sum(sizes)
    lhs = jax.random.normal(jax.random.key(0), (m, k), jnp.float32)
    lhs = lhs.at[total:].set(jnp.nan)
    rhs = jax.random.normal(jax.random.key(1), (2, X, k, n), jnp.float32)
    got = np.asarray(grouped_matmul._grouped_matmul_pallas(
        lhs, rhs, jnp.int32(1), jnp.asarray(sizes, jnp.int32),
        tiles=(tile_m, 128), interpret=True))
    assert not np.isnan(got).any()
    assert (got[total:] == 0).all()
    assert np.allclose(got[:total], _loop(lhs, rhs[1], sizes)[:total],
                       atol=1e-4)
    tile, src, group, lo, hi, first, count = (np.asarray(a) for a in (
        grouped_matmul.plan_groups(jnp.asarray(sizes, jnp.int32), m, tile_m)))
    tail = hi[:count[0]] == lo[:count[0]]
    # a tail item points at blocks already there: the last tile with a row,
    # the last group's weights; every tile is still written once
    assert (src[:count[0]][tail] == max(total - 1, 0) // tile_m).all()
    assert (src[:count[0]][~tail] == tile[:count[0]][~tail]).all()
    assert first[:count[0]].sum() == m // tile_m
    assert int(grouped_matmul._tiles_of(
        jnp.asarray(sizes, jnp.int32), m, tile_m)[-1][:X].sum()) == (~tail).sum()


def test_work_list_names_no_group_without_a_row():
    sizes = jnp.asarray([0, 7, 0, 20, 3, 0, 11, 0], jnp.int32)
    tile, src, group, lo, hi, first, n = grouped_matmul.plan_groups(
        sizes, 64, 16)
    n = int(n[0])
    assert set(np.asarray(group[:n]).tolist()) <= {1, 3, 4, 6}
    rows = sum(int(h - l) for l, h in zip(lo[:n], hi[:n]))
    assert rows == 41 and int(first[:n].sum()) == 4      # 64 / 16 row tiles


def _uniform_sizes(tokens, real, K, X, seed=0):
    """Group sizes of ``real`` of ``tokens`` tokens routed evenly at random."""
    rng = np.random.default_rng(seed)
    return np.bincount(rng.integers(0, X, size=real * K), minlength=X)


# (cell's call, tokens, prompt, K, X, E, Mx, tile_m the rule gives, rows
# multiplied over pairs at most): the shapes the cells really call
@pytest.mark.parametrize("tokens, real, K, X, E, Mx, tile_m, most", [
    (16, 16, 8, 128, 2048, 768, 128, None),          # keye2 decode, m 128
    (32, 32, 6, 64, 2560, 768, 128, None),           # smallthinker, m 192
    (8192, 7168, 6, 128, 2048, 768, 128, 1.45),      # kanana2's tail tick
    (16384, 15360, 6, 64, 2560, 768, None, 1.25),    # smallthinker's
    (16384, 10000, 8, 128, 2048, 768, None, 1.45),   # keye2's median prompt
    (32768, 30720, 8, 128, 2048, 768, None, 1.25),   # keye2's tail tick
])
def test_tile_and_work_list_at_the_cells_shapes(tokens, real, K, X, E, Mx,
                                                tile_m, most):
    m = tokens * K
    got, tile_n = grouped_matmul.tiles_for(m, X, E, 2 * Mx)
    # an admission takes the weights' whole width, a decode step half
    wide = grouped_matmul.tiles_for(m, X, Mx, E)[1]
    assert (tile_n, wide) == ((2 * Mx, E) if tokens > 32
                              else (768, 1024 if E == 2048 else 640))
    # both products of a layer walk one work list
    assert grouped_matmul.tiles_for(m, X, Mx, E)[0] == got
    if tile_m is None:                    # a long admission in one pass
        assert got == (grouped_matmul._TALL
                       if m // X >= grouped_matmul._TALL_ROWS else 128)
    else:
        assert got == tile_m
    # VMEM: rows, weights and product double-buffered, the float32 product
    assert grouped_matmul.vmem_bytes(got, tile_n, E) < 24 << 20
    # a model eight times as wide gets a narrower block, not a refusal
    wide = grouped_matmul.tiles_for(m, X, 8 * E, 2 * Mx)[1]
    assert wide < tile_n and grouped_matmul.vmem_bytes(got, wide,
                                                       8 * E) <= 32 << 20
    if most is None:
        return
    sizes = _uniform_sizes(tokens, real, K, X)
    n_tiles = grouped_matmul._tiles_of(jnp.asarray(sizes, jnp.int32), m,
                                       got)[-1]
    ratio = got * int(n_tiles[:X].sum()) / (real * K)
    assert 1.0 <= ratio <= most, ratio
    # the bucket's padding is no item's rows
    assert got * int(n_tiles[:X].sum()) <= -(-real * K // got) * got + X * got


@pytest.mark.parametrize("summed", ["one_gather", "a_choice_at_a_time"])
def test_routed_experts_equal_a_loop_over_tokens(toy, summed, monkeypatch):
    """Both ways ``routed_experts`` sums a token's K rows: one gather and
    its float32 copy (a decode step), and a gather a choice with no such
    copy (an admission, where the copy would be gigabytes)."""
    d, cfg, params = toy
    if summed == "a_choice_at_a_time":
        monkeypatch.setattr(experts, "_SUM_COPY_BYTES", 0)
    n = 50
    m = jax.random.normal(jax.random.key(4), (n, cfg.embed_dim))
    valid = jnp.arange(n) % 7 != 3                   # some rows are no token
    moe = params["moe"]
    chosen, w = latent_moe.route(m, moe["router"][1], moe["router_bias"][1],
                                 cfg)
    got, counters = experts.routed_experts(
        m, valid, chosen, w, moe["we_gu"], moe["we_down"], 1, cfg)
    want = np.zeros((n, cfg.embed_dim), np.float32)
    for t in range(n):
        if not bool(valid[t]):
            continue
        for e, g in zip(np.asarray(chosen[t]), np.asarray(w[t])):
            h = np.asarray(m[t]) @ np.asarray(moe["we_gu"][1, e])
            half = h.shape[0] // 2
            a = h[:half] / (1 + np.exp(-h[:half])) * h[half:]
            want[t] += g * (a @ np.asarray(moe["we_down"][1, e]))
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    assert int(counters["moe_assignments"]) == int(valid.sum()) * cfg.top_k
    used = {int(e) for t in range(n) if bool(valid[t])
            for e in np.asarray(chosen[t])}
    assert int(counters["moe_experts_touched"]) == len(used)
    # under ``ragged_dot`` (here) the rows multiplied are the pairs'
    assert int(counters["moe_rows_multiplied"]) == int(valid.sum()) * cfg.top_k


# ------------------------------------------------------------- (v)
def test_selection_bias_chooses_and_does_not_weigh(toy):
    d, cfg, params = toy
    m = jax.random.normal(jax.random.key(5), (64, cfg.embed_dim))
    router = params["moe"]["router"][0]
    zero = jnp.zeros((cfg.n_experts,))
    push = zero.at[3].set(10.0)
    chosen0, w0 = latent_moe.route(m, router, zero, cfg)
    chosen1, w1 = latent_moe.route(m, router, push, cfg)
    assert bool(jnp.all(jnp.any(chosen1 == 3, axis=-1)))
    assert not bool(jnp.all(jnp.any(chosen0 == 3, axis=-1)))
    scores = jax.nn.sigmoid(m @ router)
    picked = jnp.take_along_axis(scores, chosen1, axis=-1)
    want = cfg.routed_scale * picked / picked.sum(-1, keepdims=True)
    assert np.abs(np.asarray(w1 - want)).max() < 1e-6
    assert np.abs(np.asarray(w1.sum(-1)) - cfg.routed_scale).max() < 1e-5


# ------------------------------------------------------------ (vi)
def test_engine_serves_interleaved_requests_each_as_alone(toy):
    d, cfg, params = toy
    prompts = [tokens_of(n, seed=20 + i)
               for i, n in enumerate([9, 40, 17, 33, 12])]
    budgets = [14, 6, 10, 8, 12]
    alone = []
    for p, n in zip(prompts, budgets):
        gen = RollingGenerator(params, cfg, max_slots=1, max_len=128,
                               steps_per_call=4)
        rid = gen.submit(p, max_new_tokens=n)
        alone.append(gen.run()[rid])
    gen = RollingGenerator(params, cfg, max_slots=3, max_len=128,
                           steps_per_call=4)
    eng = DecodeEngine(gen, poll_s=0.002)
    try:
        import threading

        got = [None] * len(prompts)

        def one(i):
            frames = list(eng.generate({"prompt": prompts[i],
                                        "max_new_tokens": budgets[i]}))
            got[i] = [t for f in frames for t in f["tokens"]]

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        stats = eng.stats()
    finally:
        eng.close()
    assert got == alone
    assert stats["moe_experts_touched"] <= stats["moe_expert_slots"]
    assert stats["kv_position_bytes"] == position_bytes(
        decoder_for(cfg), cfg)


# ------------------------------------------- the interface and what it refuses
def test_generator_names_what_the_decoder_does_not_carry(toy):
    d, cfg, params = toy

    def build(**kw):
        return RollingGenerator(params, cfg, max_slots=2, max_len=128, **kw)

    with pytest.raises(NotImplementedError, match="int8 latent cache"):
        build(kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="speculative decode"):
        build(spec_k=4)
    with pytest.raises(NotImplementedError, match="LoRA adapters"):
        build(adapters={"wq": {"a": jnp.zeros((3, 2, 64, 4)),
                               "b": jnp.zeros((3, 2, 4, 96))}},
              adapter_scale=1.0, lora_slots=0)
    gen = build()
    with pytest.raises(NotImplementedError, match="prefix reuse"):
        gen.register_prefix([1, 2, 3])
    with pytest.raises(NotImplementedError, match="handoff"):
        DecodeEngine(gen, phase="prefill")
    with pytest.raises(NotImplementedError, match="prefix reuse"):
        DecodeEngine(gen, prefix_split="len:4")


def test_family_refuses_group_limited_routing_by_key():
    with pytest.raises(ValueError, match="n_group"):
        family.dims({**CONFIG, "n_group": 4})
    with pytest.raises(ValueError, match="q_lora_rank"):
        family.dims({**CONFIG, "q_lora_rank": 1536})


def test_cache_is_described_by_the_interface(toy):
    d, cfg, params = toy
    model = decoder_for(cfg)
    assert model.layer_kinds(cfg) == ("dense", "moe", "moe")
    leaves = model.cache_leaves(cfg)
    assert [leaf.name for leaf in leaves["moe"]] == ["ckr"]
    cache = model.init_cache(cfg, 2, 64)
    assert cache["ckr"].shape == (3, 2, 64) + leaves["dense"][0].shape
    assert position_bytes(model, cfg) == sum(
        x[:, 0, 0].size * x.dtype.itemsize for x in cache.values())
    # the published widths: 512 + 64 numbers a position, stored at 640
    real = LatentMoEConfig()
    assert latent_moe.ckr_width(real) == 640
    assert position_bytes(decoder_for(real), real) == 8 * 640 * 2


def test_export_and_import_of_a_row_resume_the_stream(toy):
    d, cfg, params = toy
    prompt = tokens_of(19, seed=9)
    gen = RollingGenerator(params, cfg, max_slots=2, max_len=128,
                           steps_per_call=4)
    rid = gen.submit(prompt, max_new_tokens=16)
    whole = gen.run()[rid]
    a = RollingGenerator(params, cfg, max_slots=2, max_len=128,
                         steps_per_call=4)
    rid = a.submit(prompt, max_new_tokens=16)
    first = [t for r, toks, _ in a.step() if r == rid for t in toks]
    state = a.export_row(rid, block_tokens=16)
    assert set(state["kv"]) == {"ckr"}
    b = RollingGenerator(params, cfg, max_slots=2, max_len=128,
                         steps_per_call=4)
    rid2 = b.import_row(state, block_tokens=16)
    rest = b.run()[rid2]
    assert first + rest == whole


@pytest.mark.parametrize("engine", [dict(), dict(prefill_chunk=16)],
                         ids=["decode", "prefill_extend"])
def test_engine_stream_and_grid_equal_the_one_hot_selects(toy, engine,
                                                          monkeypatch):
    """PR 28: the merge as a row loop of slice updates gives the tokens and
    the latent grid, bit for bit, that the one-hot select it replaced gave
    (the select lives on as the oracle of ``tests/test_grid_write.py``), and
    counts what it wrote."""
    from test_grid_write import _select_latent

    d, cfg, params = toy
    prompts = [tokens_of(29, seed=12), tokens_of(7, seed=13)]

    def run():
        gen = RollingGenerator(params, cfg, max_slots=4, max_len=128,
                               steps_per_call=4, **engine)
        rids = [gen.submit(p, max_new_tokens=9) for p in prompts]
        out = gen.run()
        return ([out[r] for r in rids],
                np.asarray(gen.cache["ckr"].astype(jnp.float32)),
                gen.stats())

    toks, grid, stats = run()
    monkeypatch.setattr(latent_moe.LatentMoEDecoder, "merge_chunk_into_grid",
                        staticmethod(_select_latent))
    want_toks, want_grid, _ = run()
    assert toks == want_toks
    np.testing.assert_array_equal(grid, want_grid)
    assert 0 < stats["merge_positions_new"] <= stats[
        "merge_positions_written"]
    if not engine:
        assert stats["merge_positions_written"] == stats[
            "merge_positions_new"]


def test_engine_stream_is_the_same_through_the_kernels(toy, monkeypatch):
    """The decode path as the chip runs it, in interpret mode: the ragged
    latent kernel joined to the chunk by the log-sum-exp rule, and the
    grouped kernel for both expert products, give the stream the einsum
    and ``ragged_dot`` give, and count reads by key blocks."""
    d, cfg, params = toy
    prompt = tokens_of(29, seed=12)

    def stream():
        gen = RollingGenerator(params, cfg, max_slots=2, max_len=128,
                               steps_per_call=4)
        rid = gen.submit(prompt, max_new_tokens=8)
        return gen.run()[rid], gen.stats()

    plain, s_plain = stream()
    monkeypatch.setattr(latent_attention, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(grouped_matmul, "_FORCE_INTERPRET", True)
    jax.clear_caches()
    kernels, s_kernels = stream()
    jax.clear_caches()
    assert kernels == plain
    assert s_plain["decode_kv_positions_read"] == \
        s_plain["decode_kv_positions_grid"]
    assert s_kernels["decode_kv_positions_read"] == 2 * 4 * 128 // 4
    assert s_kernels["moe_assignments"] == s_plain["moe_assignments"]
    # rows the MXU multiplies, counted on the device over the decode steps:
    # the pairs themselves under ``ragged_dot``, whole row tiles (one 16-row
    # tile an item here: 2 slots x 2 pairs a step) through the kernel
    decoded = 2 * cfg.top_k * cfg.n_moe_layers         # pairs, a step's
    steps = (s_plain["moe_expert_slots"]
             // (cfg.n_experts * cfg.n_moe_layers))
    assert s_plain["moe_rows_multiplied"] <= steps * decoded
    assert s_kernels["moe_rows_multiplied"] % 16 == 0
    assert s_kernels["moe_rows_multiplied"] >= s_plain["moe_rows_multiplied"]
    # the admission's expert layers, from shapes and the prompt's length:
    # one pass of the 32 bucket; under ``ragged_dot`` no tile at all
    assert (s_plain["moe_piece_tokens_b32"], s_plain["moe_tile_rows_b32"],
            s_plain["moe_admission_tiles"]) == (32, 0, 0)
    tile = grouped_matmul.tiles_for(32 * cfg.top_k, cfg.n_experts,
                                    cfg.embed_dim,
                                    2 * cfg.expert_mlp_dim)[0]
    assert s_kernels["moe_tile_rows_b32"] == tile == 64
    assert s_kernels["moe_admission_tiles"] == cfg.n_moe_layers
    assert s_kernels["moe_padding_tiles_skipped"] == 0     # 29 of 32 tokens
