"""``models/decoder.py`` after its widening (leaves by kind; positional and
row-state leaves): the two decoders that were there declare what they
declared, keep no row state, and serve, export and import as they did."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetorch_tpu.models import (HybridLinearConfig, LatentMoEConfig,
                                  LlamaConfig, latent_moe, llama)
from kubetorch_tpu.models.decoder import (CacheLeaf, LlamaDecoder,
                                          decoder_for, grid_dims,
                                          position_bytes, row_bytes,
                                          row_leaves)
from kubetorch_tpu.models.rolling import RollingGenerator

OLD = {
    "llama": (LlamaConfig.tiny(), llama.init, {"dense": ["k", "v"]}),
    "latent_moe": (LatentMoEConfig.tiny(), latent_moe.init,
                   {"dense": ["ckr"], "moe": ["ckr"]}),
}
NEW_COUNTERS = ("decode_state_rows_live", "decode_state_rows_touched",
                "linear_scan_positions", "linear_scan_prompt_tokens",
                "state_row_bytes")


def test_a_leaf_is_positional_unless_it_says_otherwise():
    leaf = CacheLeaf("k", (2, 16), jnp.float32)
    assert leaf.positional is True and leaf[:3] == ("k", (2, 16), jnp.float32)


@pytest.mark.parametrize("name", sorted(OLD))
def test_old_decoders_declare_what_they_declared(name):
    cfg, _, names = OLD[name]
    model = decoder_for(cfg)
    leaves = model.cache_leaves(cfg)
    assert {kind: [x.name for x in ls] for kind, ls in leaves.items()} == names
    assert all(x.positional for ls in leaves.values() for x in ls)
    assert row_leaves(model, cfg) == frozenset()
    assert row_bytes(model, cfg) == 0
    cache = model.init_cache(cfg, 3, 64)
    # one array a leaf, stacked over ALL layers: every kind lists the leaf
    assert all(x.shape[:3] == (cfg.n_layers, 3, 64) for x in cache.values())
    assert grid_dims(cache) == (3, 64)
    assert position_bytes(model, cfg) == sum(
        x[:, 0, 0].size * x.dtype.itemsize for x in cache.values())
    chunk = model.init_chunk(cfg, cache, 3, 8)
    assert set(chunk) == set(cache) and all(
        chunk[n].shape == cache[n].shape[:2] + (8,) + cache[n].shape[3:]
        for n in cache)
    assert model.state_rows_touched(cfg, 8, 3) == 0
    assert model.scan_positions(cfg, 1, 4096) == 0


@pytest.mark.parametrize("kernel", [False, True])
def test_the_hybrid_counts_the_state_rows_its_step_touches(kernel,
                                                           monkeypatch):
    """Every row of the grid where the XLA step holds an idle row by
    ``alpha = 1, beta = 0`` (the CPU); the rows that decode where the step
    kernel engages (one TPU device; forced here)."""
    from kubetorch_tpu.ops import gated_delta

    monkeypatch.setattr(gated_delta, "_FORCE_INTERPRET", kernel)
    cfg = HybridLinearConfig.tiny()
    assert decoder_for(cfg).state_rows_touched(cfg, 8, 3) == (
        3 if kernel else 8)


def test_the_int8_grid_keeps_its_four_positional_leaves():
    cfg = LlamaConfig.tiny()
    leaves = LlamaDecoder.cache_leaves(cfg, quantized=True)["dense"]
    assert [(x.name, x.positional) for x in leaves] == [
        ("k", True), ("v", True), ("ks", True), ("vs", True)]
    cache = LlamaDecoder.init_cache(cfg, 2, 32, quantized=True)
    assert grid_dims(cache) == (2, 32)
    assert position_bytes(LlamaDecoder, cfg, True) == cfg.n_layers * 2 * (
        cfg.n_kv_heads * cfg.head_dim + cfg.n_kv_heads * 4)


def test_grid_dims_refuses_leaves_that_disagree():
    good = {"k": jnp.zeros((2, 3, 64, 4)), "state": jnp.zeros((5, 3, 7, 9))}
    assert grid_dims(good, rows={"state"}) == (3, 64)
    with pytest.raises(ValueError, match="disagree"):
        grid_dims(good)


@pytest.mark.parametrize("name, kv", [("llama", "bf16"), ("llama", "int8"),
                                      ("latent_moe", "bf16")])
def test_old_decoders_export_import_and_count_as_before(name, kv):
    cfg, init, _ = OLD[name]
    params = init(jax.random.key(0), cfg)
    prompt = [int(t) for t in np.random.default_rng(1).integers(1, 500, 19)]

    def gen():
        return RollingGenerator(params, cfg, max_slots=2, max_len=128,
                                steps_per_call=4, kv_dtype=kv)

    whole_gen = gen()
    rid = whole_gen.submit(prompt, max_new_tokens=16)
    whole = whole_gen.run()[rid]
    stats = whole_gen.stats()
    assert [stats[k] for k in NEW_COUNTERS] == [0] * len(NEW_COUNTERS)
    a = gen()
    rid = a.submit(prompt, max_new_tokens=16)
    first = [t for _ in range(2) for _, new, _ in a.step() for t in new]
    state = a.export_row(rid)
    assert "row_state" not in state
    assert set(state["kv"]) == set(a.cache)
    b = gen()
    new_rid = b.import_row(state)
    assert first + b.run()[new_rid] == whole


def test_a_plain_export_does_not_fit_a_grid_with_row_state():
    from kubetorch_tpu.exceptions import KVGeometryMismatch
    from kubetorch_tpu.models import hybrid_linear

    cfg = LlamaConfig.tiny()
    a = RollingGenerator(llama.init(jax.random.key(0), cfg), cfg,
                         max_slots=2, max_len=128, steps_per_call=4)
    rid = a.submit([1, 2, 3, 4], max_new_tokens=8)
    a.step()
    state = a.export_row(rid)
    hyb = HybridLinearConfig.tiny()
    b = RollingGenerator(hybrid_linear.init(jax.random.key(0), hyb), hyb,
                         max_slots=2, max_len=128, steps_per_call=4)
    with pytest.raises(KVGeometryMismatch, match="row-state"):
        b.import_row(state)
