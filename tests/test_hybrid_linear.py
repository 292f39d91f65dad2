"""The third decoder (``models/hybrid_linear.py``: gated-delta linear
attention layers whose state a ROW keeps, beside full-attention layers whose
K/V a POSITION keeps) against the plain float32 reference of its
architecture (``benchmark/families/hybrid_linear.py``: the recurrence one
token at a time, direct convolution, full softmax, no cache, no kernel), on
seeded random weights at a toy size: hidden 64, 4 heads of 16, linear 4 x 8
keys and 4 x 16 values, conv 4, layers L L L F L L.

Tolerance. The float32 comparisons hold LOGITS to 5e-4 (their deviation is
~1): two float32 implementations of the same sums differ by summation order
and by the chunked scan's triangular solve, up to 2e-4 over 150 tokens here
(every block's output is normed, so a layer's rounding is not damped by a
small residual branch); anything the
architecture gets wrong (a state advanced by padding or by an idle row, a
convolution tail one token off, a decay on the wrong side of the update,
beta without its 2, a rotary embedding, bf16 where float32 is stated) moves
logits by 1e-2 or more (``test_each_departure_fails``).
"""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import hybrid_linear as family
from kubetorch_tpu.exceptions import KVGeometryMismatch
from kubetorch_tpu.models import HybridLinearConfig, hybrid_linear
from kubetorch_tpu.models.decoder import (decoder_for, grid_dims,
                                          position_bytes, row_bytes,
                                          row_leaves)
from kubetorch_tpu.models.rolling import RollingGenerator
from kubetorch_tpu.ops import decode_attention, gated_delta
from kubetorch_tpu.serving.engine import DecodeEngine

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads((REPO / "benchmark" / "configs"
                     / "rehearsal-hybrid-linear-serve.json").read_text())
TOL = 5e-4
SEED = 13


@pytest.fixture(scope="module")
def toy():
    d = family.dims(CONFIG)
    cfg = family.program_config(CONFIG, "serve", {"max_len": 128})
    params = family.serving_tree(SEED, d)
    return d, cfg, params


_REFERENCE = {}


def reference_logits(d, tokens, lower=None):
    """The reference's full forward over one sequence -> [T, V]."""
    key_ = (tuple(tokens), lower)
    if key_ not in _REFERENCE:
        with jax.default_matmul_precision("highest"):
            key = weights.root_key(SEED)
            glob = family.reference_globals(key, d, "serve")
            x = glob["embedding"][jnp.asarray(tokens)]
            positions = jnp.arange(len(tokens))
            for l, kind in enumerate(family.layer_kinds(d)):
                w = family.reference_layer(key, l, d, kind, "serve")
                x = family.block(x, w, positions, d, lower, kind)
            _REFERENCE[key_] = np.asarray(family.head(
                x, glob["final_norm"], glob["lm_head"], d, lower))
    return _REFERENCE[key_]


def tokens_of(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, CONFIG["vocab_size"], n)]


def generator(toy, **kw):
    _, cfg, params = toy
    kw = {"max_slots": 4, "max_len": 128, "steps_per_call": 4, **kw}
    return RollingGenerator(params, cfg, **kw)


@pytest.fixture(params=[False, True], ids=["xla_step", "step_kernel"])
def step_kernel(request, monkeypatch):
    """The linear layers' decode step as the CPU takes it (``gated_delta.
    step`` over every row) and as one TPU device does (the kernel over the
    stacked leaf, ``gated_delta.step_rows``, interpreted here)."""
    if request.param:
        monkeypatch.setattr(gated_delta, "_FORCE_INTERPRET", True)
    return request.param


def slot_of(gen, rid):
    return next(s for s, r in gen._slots.items() if r.rid == rid)


# ------------------------------------------------------------- (i)
def test_uncached_forward_equals_the_reference(toy):
    d, cfg, params = toy
    assert family.layer_kinds(d) == ("linear_attention",) * 3 + (
        "full_attention",) + ("linear_attention",) * 2
    toks = tokens_of(150)        # more than one chunk of the scan
    got = np.asarray(hybrid_linear.forward(params, jnp.asarray([toks]),
                                           cfg))[0]
    want = reference_logits(d, toks)
    assert want.std() > 0.5
    assert np.abs(got - want).max() < TOL


def test_each_departure_fails(toy):
    """What the tolerance must refuse, each made in the program's own
    parameters or configuration."""
    d, cfg, params = toy
    toks = tokens_of(40)
    want = reference_logits(d, toks)

    def gap(p=params, c=cfg):
        return np.abs(np.asarray(hybrid_linear.forward(
            p, jnp.asarray([toks]), c))[0] - want).max()

    def linear_with(**leaves):
        return {**params, "linear": {**params["linear"], **leaves}}

    lin = params["linear"]
    departures = {
        "beta without its 2": gap(c=dataclasses.replace(
            cfg, neg_eigval=False)),
        "no decay": gap(linear_with(
            a_log=jnp.full_like(lin["a_log"], -30.0))),
        "a convolution that sees one token": gap(linear_with(
            conv_w=lin["conv_w"].at[:, :3].set(0))),
        "no output gate": gap(linear_with(wg=jnp.zeros_like(lin["wg"]))),
        "another layer pattern": gap(c=dataclasses.replace(
            cfg, layer_types=cfg.layer_types[1:] + cfg.layer_types[:1])),
        "bfloat16 compute": gap(c=dataclasses.replace(cfg,
                                                      dtype="bfloat16")),
    }
    assert all(v > 20 * TOL for v in departures.values()), departures


def test_bfloat16_program_stays_near_the_float32_reference(toy):
    d, cfg, params = toy
    toks = tokens_of(96, seed=3)
    got = np.asarray(hybrid_linear.forward(
        params, jnp.asarray([toks]),
        dataclasses.replace(cfg, dtype="bfloat16")))[0]
    err = np.abs(got - reference_logits(d, toks))
    assert 1e-4 < np.median(err) < 5e-2, np.median(err)


# ------------------------------------------------------------ (ii)
def test_prefill_then_decode_through_cache_and_state_equals_the_reference(
        toy, step_kernel):
    """Through ``RollingGenerator``: a bucketed prefill (the chunked scan
    into a private state, K/V into a private cache, both spliced into the
    grid), then one decode step a call (the one-token rule over the state
    in the chunk, attention over grid and chunk), the pending logits read
    after each: every one is the reference's full forward there."""
    d, _, _ = toy
    gen = generator(toy, max_slots=2, steps_per_call=1)
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


def test_ragged_prompts_in_one_bucket_an_idle_row_and_a_mid_chunk_finish(
        toy, step_kernel):
    """Three prompts of 17, 25 and 31 tokens admitted in ONE padded call
    (bucket 32: the scan must stop each row's state at its own last real
    token), a fourth row never used (held through every chunk), and output
    budgets that end inside a chunk of 4 steps (the row is freed; the
    others' logits after the chunk are still the reference's)."""
    d, _, _ = toy
    gen = generator(toy)
    prompts = [tokens_of(n, seed=n) for n in (17, 25, 31)]
    budgets = (6, 9, 3)                       # none a multiple of the chunk
    rids = [gen.submit(p, max_new_tokens=m)
            for p, m in zip(prompts, budgets)]
    gen.admit()
    assert gen.stats()["prefill_positions"] == 4 * 32      # one call
    idle = gen.cache["state"][:, 3]
    assert not np.asarray(idle).any()
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
    # the XLA step carries every row of the grid, the kernel the live ones
    s = gen.stats()
    if step_kernel:
        assert (s["decode_state_rows_touched"]
                == s["decode_state_rows_live"] > 0)
    else:
        assert (s["decode_state_rows_touched"]
                > s["decode_state_rows_live"] > 0)
    assert s["linear_scan_positions"] == 4 * 32
    assert s["linear_scan_prompt_tokens"] == 17 + 25 + 31


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


def test_a_freed_row_starts_the_next_sequence_from_zero(toy, step_kernel):
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
def test_export_then_import_then_continue_equals_uninterrupted(
        toy, step_kernel):
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
    assert sorted(state["kv"]) == ["k", "v"]
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
    cut = {**state, "row_state": {
        **state["row_state"], "state": state["row_state"]["state"][:2]}}
    with pytest.raises(KVGeometryMismatch, match="row-state"):
        b.import_row(cut)
    assert b.free_rows == 2


# ------------------------------------------------------------ (iv)
def test_generator_names_what_the_decoder_does_not_carry(toy):
    _, cfg, params = toy

    def build(**kw):
        return RollingGenerator(params, cfg, max_slots=2, max_len=128, **kw)

    with pytest.raises(NotImplementedError, match="int8 K/V cache"):
        build(kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="speculative decode"):
        build(spec_k=4)
    with pytest.raises(NotImplementedError, match="LoRA adapters"):
        build(adapters={"wq": {"a": jnp.zeros((3, 2, 64, 4)),
                               "b": jnp.zeros((3, 2, 4, 96))}},
              adapter_scale=1.0, lora_slots=0)
    mesh = jax.make_mesh((2,), ("tp",))
    with pytest.raises(NotImplementedError, match="tensor-parallel mesh"):
        build(mesh=mesh)
    gen = build()
    with pytest.raises(NotImplementedError, match="prefix reuse"):
        gen.register_prefix([1, 2, 3])
    with pytest.raises(NotImplementedError, match="prefix reuse"):
        DecodeEngine(gen, prefix_split="len:16")
    with pytest.raises(NotImplementedError,
                       match="disaggregated prefill/decode handoff"):
        DecodeEngine(gen, phase="prefill")
    eng = DecodeEngine(build())
    try:
        from kubetorch_tpu.serving.engine import program

        with pytest.raises(NotImplementedError, match="handoff"):
            list(eng.generate(program([1, 2, 3], max_new_tokens=2,
                                      handoff={"id": "h-1"})))
    finally:
        eng.close()
    decoder_for(cfg).check_serving(cfg)          # nothing asked: carried


def test_engine_serves_it_on_the_same_tick(toy):
    """``DecodeEngine(RollingGenerator(...))``: two streams side by side
    equal the generator driven alone, and the engine's stats carry the new
    counters and the gauge."""
    _, cfg, params = toy
    from kubetorch_tpu.serving.engine import program

    prompts = [tokens_of(11, seed=1), tokens_of(23, seed=2)]
    alone = []
    for p in prompts:
        gen = generator(toy)
        rid = gen.submit(p, max_new_tokens=10)
        alone.append(gen.run()[rid])
    eng = DecodeEngine(generator(toy))
    try:
        import threading

        got = [None, None]

        def drive(i):
            got[i] = [t for frame in eng.generate(
                program(prompts[i], max_new_tokens=10))
                for t in frame.get("tokens", [])]

        threads = [threading.Thread(target=drive, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        stats = eng.stats()
        # the pool prices a row's state as the positions it would be
        state_tokens = -(-stats["state_row_bytes"]
                         // stats["kv_position_bytes"])
        bt = eng._kv.block_tokens
        assert eng._kv.row_state_tokens == state_tokens > 0
        assert eng._kv.row_cost(10) == -(-(10 + state_tokens) // bt)
        assert eng._kv.ledger.budget == 2 * 4 * (-(-(128 + state_tokens)
                                                   // bt))
    finally:
        eng.close()
    assert got == alone
    model = decoder_for(cfg)
    assert stats["state_row_bytes"] == row_bytes(model, cfg) > 0
    assert stats["kv_position_bytes"] == position_bytes(model, cfg)
    assert stats["decode_state_rows_live"] > 0
    assert stats["linear_scan_prompt_tokens"] == 34


# ------------------------------------------------------------- (v)
def test_leaves_are_declared_by_kind_and_by_sort(toy):
    _, cfg, _ = toy
    model = decoder_for(cfg)
    leaves = model.cache_leaves(cfg)
    assert set(leaves) == set(model.layer_kinds(cfg))
    assert [(x.name, x.positional) for x in leaves["full_attention"]] == [
        ("k", True), ("v", True)]
    assert [(x.name, x.positional) for x in leaves["linear_attention"]] == [
        ("state", False), ("conv", False)]
    rows = row_leaves(model, cfg)
    assert rows == {"state", "conv"}
    cache = model.init_cache(cfg, 3, 64)
    # each leaf is stacked over the layers of ITS kind
    assert cache["k"].shape[:3] == (1, 3, 64)
    assert cache["state"].shape == (5, 3, 4, 8, 16)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (5, 3, 3, 4 * (8 + 8 + 16))
    assert grid_dims(cache, rows) == (3, 64)
    assert position_bytes(model, cfg) == sum(
        cache[n][:, 0, 0].size * cache[n].dtype.itemsize for n in ("k", "v"))
    assert row_bytes(model, cfg) == sum(
        cache[n][:, 0].size * cache[n].dtype.itemsize for n in rows)
    chunk = model.init_chunk(cfg, cache, 3, 8)
    assert chunk["k"].shape == (1, 3, 8) + cache["k"].shape[3:]
    assert chunk["state"] is cache["state"]      # the grid's own, carried
    # the published widths: 26.5 MB of state a row, 4 layers of K/V a
    # position stored at 32 heads (30 rounded up to the bf16 sublane tile)
    real = HybridLinearConfig()
    big = decoder_for(real)
    assert row_bytes(big, real) == 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert position_bytes(big, real) == 4 * 2 * 32 * 128 * 2
    assert decode_attention.kv_heads_stored(real.n_kv_heads,
                                            real.compute_dtype) == 32
    # 512 keys x 32 heads x 128 x 2 planes x 2 buffers is over a kernel's
    # VMEM: the key block is the largest that fits
    assert decode_attention.ragged_key_block(4096, 32, 128,
                                             jnp.bfloat16) is None
    decode_attention._FORCE_INTERPRET = True
    try:
        assert decode_attention.ragged_key_block(
            4096, 32, 128, jnp.bfloat16) == 256
        assert decode_attention.ragged_key_block(
            2048, 8, 128, jnp.int8) == 512
    finally:
        decode_attention._FORCE_INTERPRET = False


def test_kernels_in_interpret_mode_serve_the_same_tokens(monkeypatch):
    """The paths a TPU takes, interpreted: the gated-delta kernel at a
    128-token bucket and the ragged decode kernel over the padded heads (3
    heads of 128 stored at 8, float32; float32 queries) against the paths
    the CPU takes, on the program's own random weights."""
    cfg = HybridLinearConfig.tiny(n_heads=3, n_kv_heads=3, head_dim=128,
                                  max_seq_len=256)
    params = hybrid_linear.init(jax.random.key(2), cfg)
    prompt = tokens_of(100, seed=8)

    def served():
        gen = RollingGenerator(params, cfg, max_slots=2, max_len=256,
                               steps_per_call=4)
        rid = gen.submit(prompt, max_new_tokens=8)
        return gen, gen.run()[rid]

    plain, want = served()
    assert plain._ragged_block is None
    monkeypatch.setattr(gated_delta, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(decode_attention, "_FORCE_INTERPRET", True)
    gen, got = served()
    assert gen._ragged_block == 256 and gen.cache["k"].shape[3] == 8
    assert got == want
    s = gen.stats()
    assert s["decode_kv_positions_read"] == s["decode_kv_positions_grid"] // 2
