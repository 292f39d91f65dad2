"""The sixth decoder (``models/hybrid_latent_moe.py``: KDA layers whose state
a ROW keeps beside an MLA layer whose latent a POSITION keeps, group-limited
sigmoid-routed experts of which a share is held) against the plain float32
reference of its architecture (``benchmark/families/hybrid_latent_moe.py``:
the recurrence one token at a time, direct convolution, expanded attention,
the router as a loop of choices, no cache, no kernel), on seeded random
weights at a toy size: hidden 64, 4 heads, KDA 8 x 8, latent 32 + 8, 16
experts in 4 groups top 2 of the best 2 groups, layers D K K M K.

Tolerance. The float32 comparisons hold LOGITS to 5e-4 (their deviation is
~1): two float32 implementations of the same sums differ by summation order
and by the chunked scan's triangular solve and references, up to ~1e-4 over
150 tokens here; anything the architecture gets wrong (a scalar decay, a
bfloat16 state or compute, a state advanced by padding, a missing head gate,
an ungrouped router, an absent expert computed) moves logits by 1e-2 or more
(``test_each_departure_fails``).
"""

import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import hybrid_latent_moe as family
from kubetorch_tpu.exceptions import KVGeometryMismatch
from kubetorch_tpu.models import HybridLatentMoEConfig, hybrid_latent_moe
from kubetorch_tpu.models.decoder import (decoder_for, position_bytes,
                                          row_bytes, row_leaves)
from kubetorch_tpu.models.rolling import RollingGenerator
from kubetorch_tpu.ops import kda
from kubetorch_tpu.serving.engine import DecodeEngine

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-hybrid-latent-moe-serve.json").read_text())
TOL = 5e-4
SEED = 13


@pytest.fixture(scope="module")
def toy():
    d = family.dims(CONFIG)
    cfg = family.program_config(CONFIG, "serve", {"max_len": 128})
    params = family.serving_tree(SEED, d)
    return d, cfg, params


_REFERENCE, _BLOCKS = {}, {}


def _block(d, kind, lower):
    """The reference's layer, jitted a (share, kind, control)."""
    key_ = (d["first"], d["X"], kind, lower)
    if key_ not in _BLOCKS:
        _BLOCKS[key_] = jax.jit(lambda x, w, positions: family.block(
            x, w, positions, d, lower, kind))
    return _BLOCKS[key_]


def reference_logits(d, tokens, lower=None, seed=SEED):
    """The reference's full forward over one sequence -> [T, V]. The
    sequence is padded AFTER its tokens to a multiple of 64 (every operation
    is causal, so the padding changes no real position): three shapes to
    compile instead of one a length."""
    key_ = (tuple(tokens), lower, seed, d["first"], d["X"])
    if key_ not in _REFERENCE:
        n = len(tokens)
        padded = list(tokens) + [0] * (-n % 64)
        with jax.default_matmul_precision("highest"):
            key = weights.root_key(seed)
            glob = family.reference_globals(key, d, "serve")
            x = glob["embedding"][jnp.asarray(padded)]
            positions = jnp.arange(len(padded))
            for l, kind in enumerate(family.layer_kinds(d)):
                w = family.reference_layer(key, l, d, kind, "serve")
                x = _block(d, kind, lower)(x, w, positions)
            _REFERENCE[key_] = np.asarray(family.head(
                x, glob["final_norm"], glob["lm_head"], d, lower))[:n]
    return _REFERENCE[key_]


def tokens_of(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], n)]


def generator(toy, **kw):
    _, cfg, params = toy
    kw = {"max_slots": 4, "max_len": 128, "steps_per_call": 4, **kw}
    return RollingGenerator(params, cfg, **kw)


@pytest.fixture(params=[False, True], ids=["xla_step", "kernels"])
def kernels(request, monkeypatch):
    """The decode step and the scan as the CPU takes them (``kda.step`` over
    every row, the ``lax.scan`` over chunks) and as one TPU device does (the
    kernels ``kda_step`` on the stacked leaf and ``kda_prefill``,
    interpreted here)."""
    if request.param:
        monkeypatch.setattr(kda, "_FORCE_INTERPRET", True)
    return request.param


def slot_of(gen, rid):
    return next(s for s, r in gen._slots.items() if r.rid == rid)


# ------------------------------------------------------------- (i)
def test_uncached_forward_equals_the_reference(toy):
    d, cfg, params = toy
    assert family.layer_kinds(d) == ("kda_dense", "kda_moe", "kda_moe",
                                     "mla_moe", "kda_moe")
    toks = tokens_of(150)        # more than two chunks of the scan
    got = np.asarray(hybrid_latent_moe.forward(params, jnp.asarray([toks]),
                                               cfg))[0]
    want = reference_logits(d, toks)
    assert want.std() > 0.5
    assert np.abs(got - want).max() < TOL


def test_each_departure_fails(toy):
    """What the tolerance must refuse, each made in the program's own
    parameters or configuration, or by the reference's controls."""
    d, cfg, params = toy
    toks = tokens_of(48)
    want = reference_logits(d, toks)

    def gap(p=params, c=cfg):
        return np.abs(np.asarray(hybrid_latent_moe.forward(
            p, jnp.asarray([toks]), c))[0] - want).max()

    def with_(kind, **leaves):
        return {**params, kind: {**params[kind], **leaves}}

    moe, mla = params["kda_moe"], params["mla_moe"]
    got = np.asarray(hybrid_latent_moe.forward(
        params, jnp.asarray([toks]), cfg))[0]
    departures = {
        "a scalar decay (the control)": np.abs(
            got - reference_logits(d, toks, "scalar_decay")).max(),
        "an ungrouped router (the control)": np.abs(
            got - reference_logits(d, toks, "ungrouped")).max(),
        "no decay": gap(with_("kda_moe", dt_bias=jnp.full_like(
            moe["dt_bias"], -1e4))),
        "a convolution that sees one token": gap(with_(
            "kda_moe", conv_w=moe["conv_w"].at[:, :3].set(0))),
        "no output gate": gap(with_("kda_moe",
                                    wg=jnp.zeros_like(moe["wg"]))),
        "no head gate": gap(with_("mla_moe",
                                  wgate=jnp.zeros_like(mla["wgate"]))),
        "no selection bias": gap(with_("kda_moe", router_bias=jnp.zeros_like(
            moe["router_bias"]))),
        "another layer pattern": gap(c=dataclasses.replace(
            cfg, layer_types=("kda_dense", "kda_moe", "kda_moe", "kda_moe",
                              "mla_moe"))),
        "bfloat16 compute": gap(c=dataclasses.replace(cfg,
                                                      dtype="bfloat16")),
    }
    assert all(v > 20 * TOL for v in departures.values()), departures


def test_a_bfloat16_state_fails(toy, monkeypatch):
    """The state rounded to bfloat16 between tokens (what the tolerance of
    the float32 comparison must refuse)."""
    d, cfg, params = toy
    toks = tokens_of(48)
    step = kda.step

    def rounded(*args):
        o, s = step(*args)
        return o, jax.lax.reduce_precision(s, exponent_bits=8,
                                           mantissa_bits=7)

    monkeypatch.setattr(kda, "step", rounded)
    monkeypatch.setattr(
        kda, "prefill_scan",
        lambda q, k, v, a, b, s, kernel=None: kda.recurrence(q, k, v, a, b,
                                                             s))
    got = np.asarray(hybrid_latent_moe.forward(
        params, jnp.asarray([toks]), cfg))[0]
    assert np.abs(got - reference_logits(d, toks)).max() > 10 * TOL


def test_bfloat16_program_stays_near_the_float32_reference(toy):
    d, cfg, params = toy
    toks = tokens_of(96, seed=3)
    got = np.asarray(hybrid_latent_moe.forward(
        params, jnp.asarray([toks]),
        dataclasses.replace(cfg, dtype="bfloat16")))[0]
    err = np.abs(got - reference_logits(d, toks))
    assert 1e-4 < np.median(err) < 5e-2, np.median(err)


# ------------------------------------------------------------ (ii)
def test_prefill_then_decode_through_cache_and_state_equals_the_reference(
        toy, kernels):
    """Through ``RollingGenerator``: a bucketed prefill (the chunked scan
    into a private state, the latent into a private plane, both spliced into
    the grid), then one decode step a call (the one-token rule over the
    state in the chunk, absorbed attention over grid and chunk), the pending
    logits read after each: every one is the reference's full forward
    there. 60 + 12 tokens: the decode steps cross a chunk boundary of the
    scan's XLA form (64)."""
    d, _, _ = toy
    gen = generator(toy, max_slots=2, steps_per_call=1)
    prompt = tokens_of(60, seed=5)
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


def test_ragged_prompts_in_one_bucket_an_idle_row_and_a_mid_chunk_finish(
        toy, kernels):
    """Three prompts of 17, 25 and 31 tokens admitted in ONE padded call
    (bucket 32: the scan must stop each row's state at its own last real
    token, a padded position is given to no expert), a fourth row never used
    (held through every chunk), and output budgets that end inside a chunk
    of 4 steps."""
    d, _, _ = toy
    gen = generator(toy)
    prompts = [tokens_of(n, seed=n) for n in (17, 25, 31)]
    budgets = (6, 9, 3)                       # none a multiple of the chunk
    rids = [gen.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    gen.admit()
    assert gen.stats()["prefill_positions"] == 4 * 32      # one call
    assert not np.asarray(gen.cache["state"][:, 3]).any()
    out = {rid: [] for rid in rids}
    after = {}                    # rid -> (tokens so far, pending logits)
    while gen.pending:
        for rid, new, done in gen.decode_step():
            out[rid] += new
            if not done:
                after[rid] = (len(out[rid]),
                              np.asarray(gen._logits[slot_of(gen, rid)]))
    for rid, prompt, budget in zip(rids, prompts, budgets):
        assert len(out[rid]) == budget
        want = reference_logits(d, prompt + out[rid])
        assert out[rid] == [int(t) for t in want[
            len(prompt) - 1:len(prompt) - 1 + budget].argmax(-1)]
        if rid in after:
            n, logits = after[rid]
            assert np.abs(logits - want[len(prompt) - 1 + n]).max() < TOL
    # the row nobody used was carried through every chunk and never moved;
    # the rows that finished were zeroed when they were freed
    assert not np.asarray(gen.cache["state"]).any()
    assert not np.asarray(gen.cache["conv"]).any()
    s = gen.stats()
    if kernels:
        assert (s["decode_state_rows_touched"]
                == s["decode_state_rows_live"] > 0)
    else:
        assert (s["decode_state_rows_touched"]
                > s["decode_state_rows_live"] > 0)
    assert s["linear_scan_positions"] == 4 * 32
    assert s["linear_scan_prompt_tokens"] == 17 + 25 + 31
    # every expert is held in the toy: every pair of a decode step is here,
    # and every token chose a held group
    assert s["moe_assignments_held"] == s["moe_assignments_step"] > 0
    assert s["moe_groups_held_hits"] * 2 == s["moe_assignments_step"]
    assert s["moe_assignments"] == s["moe_assignments_step"] + (
        17 + 25 + 31) * 2 * 4


def test_chunked_prefill_carries_the_state_from_chunk_to_chunk(toy):
    """A prompt longer than ``prefill_chunk`` goes through the chunk-mode
    forward several positions at a time, state and convolution tail riding
    in the chunk, and decodes the reference's tokens."""
    d, _, _ = toy
    prompt = tokens_of(37, seed=6)
    out = []
    for chunk in (None, 16):
        gen = generator(toy, max_slots=2, prefill_chunk=chunk)
        rid = gen.submit(prompt, max_new_tokens=9)
        out.append(gen.run()[rid])
    assert out[0] == out[1]
    want = reference_logits(d, prompt + out[0])
    assert out[0] == [int(t) for t in want[36:45].argmax(-1)]


def test_a_long_bucket_in_segments_is_the_one_scan(toy, monkeypatch):
    """A bucket longer than ``_SEGMENT`` goes through the KDA mixer in
    segments that hand state and tail on: the same logits and the same
    state as the whole bucket at once, two rows of different lengths, one of
    them ending inside the second segment."""
    _, cfg, params = toy
    toks = jnp.asarray([tokens_of(64, seed=1), tokens_of(64, seed=2)])
    lens = jnp.asarray([64, 37])
    mask = (jnp.tril(jnp.ones((64, 64), bool))[None]
            & (jnp.arange(64)[None, None, :] < lens[:, None, None])
            & (jnp.arange(64)[None, :, None] < lens[:, None, None]))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))

    def run():
        own = hybrid_latent_moe.init_cache(cfg, 2, 64)
        return hybrid_latent_moe.forward_cached(params, toks, pos, own, 0,
                                                mask, cfg)

    whole, cache, _ = run()
    monkeypatch.setattr(hybrid_latent_moe, "_SEGMENT", 16)
    cut, cache_cut, _ = run()
    assert np.abs(np.asarray(whole - cut))[0].max() < TOL
    assert np.abs(np.asarray(whole - cut))[1, :37].max() < TOL
    for name in ("state", "conv"):
        assert np.abs(np.asarray(cache[name] - cache_cut[name])).max() < TOL
    # the latent of a padded position is read by nobody
    latent = np.abs(np.asarray(cache["ckr"] - cache_cut["ckr"]))
    assert latent[:, 0].max() < TOL and latent[:, 1, :37].max() < TOL


def test_a_freed_row_starts_the_next_sequence_from_zero(toy, kernels):
    """Evict mid-generation, then reuse the row through the CHUNKED path,
    which starts from whatever the row holds."""
    d, _, _ = toy
    gen = generator(toy, max_slots=1, prefill_chunk=16)
    rid = gen.submit(tokens_of(12, seed=1), max_new_tokens=40)
    gen.step()
    assert np.asarray(gen.cache["state"]).any()
    assert gen.evict(rid)
    assert not np.asarray(gen.cache["state"]).any()
    assert not np.asarray(gen.cache["conv"]).any()
    prompt = tokens_of(37, seed=6)
    rid = gen.submit(prompt, max_new_tokens=5)
    got = gen.run()[rid]
    want = reference_logits(d, prompt + got)
    assert got == [int(t) for t in want[36:41].argmax(-1)]


# ----------------------------------------------------------- (iii)
def test_export_then_import_then_continue_equals_uninterrupted(toy, kernels):
    d, _, _ = toy
    prompt = tokens_of(19, seed=9)
    gen = generator(toy, max_slots=2)
    rid = gen.submit(prompt, max_new_tokens=16)
    whole = gen.run()[rid]
    a = generator(toy, max_slots=2)
    rid = a.submit(prompt, max_new_tokens=16)
    first = []
    for _ in range(2):
        for _, new, _ in a.step():
            first += new
    state = a.export_row(rid)
    assert sorted(state["kv"]) == ["ckr"]
    assert sorted(state["row_state"]) == ["conv", "state"]
    assert state["row_state"]["state"].shape == a.cache["state"].shape[:1] \
        + a.cache["state"].shape[2:]
    assert state["row_state"]["state"].dtype == np.float32
    b = generator(toy, max_slots=2)
    b.submit(tokens_of(5, seed=2), max_new_tokens=30)   # row 0 is taken
    b.step()
    new_rid = b.import_row(state)
    rest = []
    while any(r.rid == new_rid for r in b._slots.values()):
        for r, new, _ in b.step():
            if r == new_rid:
                rest += new
    assert first + rest == whole
    want = reference_logits(d, prompt + whole)
    assert whole == [int(t) for t in want[18:34].argmax(-1)]


def test_import_refuses_a_row_without_its_state(toy):
    a = generator(toy, max_slots=2)
    rid = a.submit(tokens_of(9), max_new_tokens=8)
    a.step()
    state = a.export_row(rid)
    b = generator(toy, max_slots=2)
    stripped = {k: v for k, v in state.items() if k != "row_state"}
    with pytest.raises(KVGeometryMismatch, match="row-state"):
        b.import_row(stripped)
    assert b.free_rows == 2


# ------------------------------------------------------------ (iv)
def route_by_loop(s, bias, groups, keep, top_k, scale):
    """Group-limited routing as ISSUE 47 section 1 writes it, a token and a
    choice at a time (equal scores to the lower index)."""
    chosen, weights = [], []
    for row in np.asarray(s, np.float64):
        sb = row + np.asarray(bias, np.float64)
        size = len(row) // groups
        score = [sum(sorted(sb[g * size:(g + 1) * size])[-2:])
                 for g in range(groups)]
        kept = sorted(range(groups), key=lambda g: (-score[g], g))[:keep]
        among = [e for e in range(len(row)) if e // size in kept]
        best = sorted(among, key=lambda e: (-sb[e], e))[:top_k]
        total = sum(row[e] for e in best)
        chosen.append(best)
        weights.append([scale * row[e] / total for e in best])
    return np.asarray(chosen), np.asarray(weights)


def test_group_limited_routing_equals_the_loop(toy):
    """Against the loop: random tokens, a group whose BIAS wins it (its raw
    scores are the lowest), and exact ties between groups and between
    experts (the lower index goes first)."""
    _, cfg, _ = toy
    E, X = cfg.embed_dim, cfg.n_experts_routed
    rng = np.random.default_rng(0)
    router = jnp.asarray(rng.normal(size=(E, X)) * E ** -0.5, jnp.float32)
    m = jnp.asarray(rng.normal(size=(40, E)), jnp.float32)
    for bias in (rng.normal(size=X) * 0.05,
                 np.where(np.arange(X) // 4 == 3, 2.0, 0.0),
                 np.zeros(X)):
        bias = jnp.asarray(bias, jnp.float32)
        chosen, w, kept = hybrid_latent_moe.route(m, router, bias, cfg)
        s = jax.nn.sigmoid(jnp.matmul(m, router, precision="highest"))
        want_c, want_w = route_by_loop(s, bias, cfg.n_group, cfg.topk_group,
                                       cfg.top_k, cfg.routed_scale)
        assert (np.sort(np.asarray(chosen), 1) == np.sort(want_c, 1)).all()
        assert np.allclose(np.sort(np.asarray(w), 1), np.sort(want_w, 1),
                           atol=1e-6)
        assert (np.asarray(kept).sum(1) == cfg.topk_group).all()
    # ties: every score equal -> groups 0 and 1, experts 0 and 1
    chosen, w, kept = hybrid_latent_moe.route(
        jnp.zeros((3, E)), router, jnp.zeros(X), cfg)
    assert (np.asarray(chosen) == [0, 1]).all()
    assert (np.asarray(kept) == [True, True, False, False]).all()
    assert np.allclose(np.asarray(w), cfg.routed_scale / 2)


def test_the_bias_wins_a_group(toy):
    _, cfg, _ = toy
    E, X = cfg.embed_dim, cfg.n_experts_routed
    rng = np.random.default_rng(1)
    router = jnp.asarray(rng.normal(size=(E, X)) * E ** -0.5, jnp.float32)
    m = jnp.asarray(rng.normal(size=(20, E)), jnp.float32)
    bias = jnp.asarray(np.where(np.arange(X) // 4 == 3, 2.0, 0.0),
                       jnp.float32)
    chosen, w, kept = hybrid_latent_moe.route(m, router, bias, cfg)
    assert np.asarray(kept)[:, 3].all()
    assert (np.asarray(chosen) // 4 == 3).all()      # both from group 3
    # the bias chose; it does not weigh: weights from the raw scores
    s = jax.nn.sigmoid(jnp.matmul(m, router, precision="highest"))
    raw = np.take_along_axis(np.asarray(s), np.asarray(chosen), 1)
    assert np.allclose(np.asarray(w), cfg.routed_scale * raw
                       / raw.sum(1, keepdims=True), atol=1e-6)


def shares(config, n):
    """The toy's configuration cut into ``n`` shares of its experts."""
    X = config["num_experts"]
    return [{**config, "num_experts": X // n,
             "reduced": ["num_experts"], "published": {"num_experts": X},
             "experts_held": [i * X // n, X // n]} for i in range(n)]


def share_params(params, first, count):
    """The uncut toy's tree with the experts ``first .. first + count`` of
    every expert layer alone."""
    cut = lambda x: x[:, first:first + count]
    return {k: ({**v, "we_gu": cut(v["we_gu"]), "we_down": cut(v["we_down"])}
                if isinstance(v, dict) and "we_gu" in v else v)
            for k, v in params.items()}


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """The share ties to the model: one expert layer of the toy cut into
    four shares of 4 experts; the four routed parts, plus what every share
    computes alike (the shared expert, the stream) counted once, are the
    uncut layer, in the program and in the reference; a token none of whose
    kept groups is held gets exactly zero from the routed part and its
    shared expert's term all the same."""
    d, cfg, params = toy
    stack = params["kda_moe"]
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 24, cfg.embed_dim)), jnp.float32)
    valid = jnp.ones((1, 24), bool)
    whole, _ = hybrid_latent_moe._feed_forward(x, valid, stack, 1, "kda_moe",
                                               cfg)
    no_experts = dataclasses.replace(cfg, routed_scale=0.0)
    alike, _ = hybrid_latent_moe._feed_forward(x, valid, stack, 1, "kda_moe",
                                               no_experts)
    parts = []
    for conf in shares(CONFIG, 4):
        first, count = conf["experts_held"]
        c = family.program_config(conf, "serve", {"max_len": 128})
        assert c.n_experts == 4 and c.n_experts_routed == 16
        cut = share_params(params, first, count)["kda_moe"]
        y, _ = hybrid_latent_moe._feed_forward(x, valid, cut, 1, "kda_moe",
                                               c)
        parts.append(np.asarray(y - alike))
    routed = np.asarray(whole - alike)
    assert np.abs(routed).max() > 0.1
    assert np.abs(sum(parts) - routed).max() < 1e-5
    # a token keeps 2 of 4 groups and chooses 2 experts: in two or three of
    # the four shares none of its experts is held, and its routed part there
    # is exactly zero
    zero = np.sum([(np.abs(p[0]).max(-1) == 0) for p in parts], 0)
    assert ((zero == 2) | (zero == 3)).all() and (zero == 2).any()
    assert np.abs(np.asarray(alike - x)).max() > 0.01      # shared expert


def test_a_share_of_the_model_equals_the_reference_of_that_share(toy):
    """The whole toy with share 1 of 2 of its experts held (experts 8-15):
    program and reference leave the absent experts' terms out alike, so the
    logits agree; and they differ from the uncut model's."""
    d, cfg, params = toy
    conf = shares(CONFIG, 2)[1]
    ds = family.dims(conf)
    c = family.program_config(conf, "serve", {"max_len": 128})
    # the family draws a share's experts as experts 0 .. X of the seed
    p = family.serving_tree(SEED, ds)
    toks = tokens_of(40, seed=8)
    got = np.asarray(hybrid_latent_moe.forward(p, jnp.asarray([toks]), c))[0]
    want = reference_logits(ds, toks)
    assert np.abs(got - want).max() < TOL
    assert np.abs(got - reference_logits(d, toks)).max() > 20 * TOL


def test_a_nonzero_swiglu_limit_is_refused_by_key(toy):
    with pytest.raises(ValueError, match="swiglu_limit"):
        HybridLatentMoEConfig.tiny(swiglu_limits=(0, 0, 4.0, 0, 0))
    bad = {**CONFIG, "expert_swiglu_limit_list": [0, 0, 0, 4, 0]}
    with pytest.raises(ValueError, match="swiglu_limit"):
        family.program_config(bad, "serve", {"max_len": 64})
    with pytest.raises(ValueError, match="decay_lower_bound"):
        HybridLatentMoEConfig.tiny(decay_lower_bound=-8.0)
    with pytest.raises(ValueError, match="whole groups"):
        HybridLatentMoEConfig.tiny(experts_held=(2, 4))


def test_generator_names_what_the_decoder_does_not_carry(toy):
    _, cfg, params = toy

    def build(**kw):
        return RollingGenerator(params, cfg, max_slots=2, max_len=128, **kw)

    with pytest.raises(NotImplementedError, match="int8 latent plane"):
        build(kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="speculative decode"):
        build(spec_k=4)
    with pytest.raises(NotImplementedError, match="LoRA adapters"):
        build(adapters={"wq": {"a": jnp.zeros((3, 2, 64, 4)),
                               "b": jnp.zeros((3, 2, 4, 96))}},
              adapter_scale=1.0, lora_slots=0)
    mesh = jax.make_mesh((2,), ("tp",))
    with pytest.raises(NotImplementedError,
                       match="expert-parallel mesh"):
        build(mesh=mesh)
    gen = build()
    with pytest.raises(NotImplementedError, match="prefix reuse"):
        gen.register_prefix([1, 2, 3])
    with pytest.raises(NotImplementedError, match="prefix reuse"):
        DecodeEngine(gen, prefix_split="len:16")
    with pytest.raises(NotImplementedError,
                       match="disaggregated prefill/decode handoff"):
        DecodeEngine(gen, phase="prefill")
    decoder_for(cfg).check_serving(cfg)          # nothing asked: carried


def test_engine_serves_it_on_the_same_tick(toy):
    """``DecodeEngine(RollingGenerator(...))``: two streams side by side,
    each the reference's greedy tokens; the engine's stats carry the
    decoder's counters."""
    from kubetorch_tpu.serving.engine import program

    d, _, _ = toy
    eng = DecodeEngine(generator(toy))
    try:
        prompts = [tokens_of(9, seed=21), tokens_of(23, seed=22)]
        streams = [eng.generate(program(p, max_new_tokens=10))
                   for p in prompts]
        outs = [[t for frame in s for t in frame["tokens"]] for s in streams]
        stats = eng.stats()
    finally:
        eng.close()
    for prompt, out in zip(prompts, outs):
        want = reference_logits(d, prompt + out)
        assert out == [int(t) for t in want[
            len(prompt) - 1:len(prompt) + 9].argmax(-1)]
    for name in ("moe_assignments_held", "moe_groups_held_hits",
                 "decode_state_rows_live", "linear_scan_positions",
                 "decode_kv_positions_read", "state_row_bytes"):
        assert stats[name] > 0, name


def test_leaves_are_declared_by_kind_and_by_sort(toy):
    """Two row-state leaves over the four KDA layers of both kinds, one
    positional latent leaf over the one MLA layer; bytes a position and a
    row from the declaration alone."""
    _, cfg, _ = toy
    model = decoder_for(cfg)
    assert model is hybrid_latent_moe.HybridLatentMoEDecoder
    assert row_leaves(model, cfg) == {"state", "conv"}
    cache = model.init_cache(cfg, 3, 32)
    assert cache["ckr"].shape == (1, 3, 32, 128)
    assert cache["state"].shape == (4, 3, 4, 8, 8)
    assert cache["conv"].shape == (4, 3, 3, 4 * 24)
    assert position_bytes(model, cfg) == 128 * 4
    assert row_bytes(model, cfg) == 4 * (4 * 8 * 8 * 4 + 3 * 96 * 4)


# ------------------------------------------------------------- (v)
def lowered_hash(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# the three expert decoders' tiny forwards as the PARENT of PR 47 lowers them
# (jit(forward).lower(params, tokens [2, 24]).as_text(), sha256, 16 hex):
# ``models/experts.py`` gained ``held_first``, and a caller that passes
# nothing must lower to the text it lowered to before
_PARENT_TEXT = {
    "latent_moe": "b3870b4a8183ff43",
    "window_moe": "45963da9e887e25d",
    "indexed_moe": "feb31ae0037a54be",
}


@pytest.mark.parametrize("name", sorted(_PARENT_TEXT))
def test_the_other_expert_decoders_lower_to_the_parents_text(name):
    import importlib

    from kubetorch_tpu import models

    module = importlib.import_module(f"kubetorch_tpu.models.{name}")
    cfg = {"latent_moe": models.LatentMoEConfig,
           "window_moe": models.WindowMoEConfig,
           "indexed_moe": models.IndexedMoEConfig}[name].tiny()
    params = module.init(jax.random.key(0), cfg)
    toks = jnp.zeros((2, 24), jnp.int32)
    assert lowered_hash(lambda p, t: module.forward(p, t, cfg), params,
                        toks) == _PARENT_TEXT[name]


def test_kernels_in_interpret_mode_serve_the_same_tokens(toy, monkeypatch):
    """Every kernel the cell takes on one TPU device, interpreted: the scan,
    the step, the latent attention's ragged decode, the grouped product."""
    from kubetorch_tpu.ops import grouped_matmul

    def served():
        gen = generator(toy, max_slots=2)
        rids = [gen.submit(tokens_of(n, seed=n), max_new_tokens=7)
                for n in (11, 30)]
        out = gen.run()
        return [out[r] for r in rids]

    plain = served()
    monkeypatch.setattr(kda, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(grouped_matmul, "_FORCE_INTERPRET", True)
    assert served() == plain
