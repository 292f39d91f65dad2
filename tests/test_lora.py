"""LoRA adapters: zero-effect init, exact merge math, frozen-base
fine-tuning through the Trainer, serving composition, size accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kubetorch_tpu.models import LlamaConfig, llama
from kubetorch_tpu.models import lora as lora_mod
from kubetorch_tpu.models.lora import LoraConfig
from kubetorch_tpu.parallel import MeshSpec

pytestmark = pytest.mark.level("unit")


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init(jax.random.key(0), cfg)


def test_init_is_zero_effect(cfg, params):
    lcfg = LoraConfig(rank=4)
    adapters = lora_mod.init(jax.random.key(1), params, lcfg)
    merged = lora_mod.merge(params, adapters, lcfg)
    toks = jnp.array([[3, 1, 4, 1, 5]])
    np.testing.assert_allclose(
        np.asarray(llama.forward(params, toks, cfg)),
        np.asarray(llama.forward(merged, toks, cfg)), rtol=0, atol=0)


def test_merge_math_is_exact(cfg, params):
    lcfg = LoraConfig(rank=2, alpha=8.0, targets=("wq",))
    adapters = lora_mod.init(jax.random.key(2), params, lcfg)
    adapters["wq"]["b"] = jax.random.normal(
        jax.random.key(3), adapters["wq"]["b"].shape,
        adapters["wq"]["b"].dtype)
    merged = lora_mod.merge(params, adapters, lcfg)
    l0 = 1
    expect = (params["layers"]["wq"][l0].astype(jnp.float32)
              + (8.0 / 2)
              * adapters["wq"]["a"][l0].astype(jnp.float32)
              @ adapters["wq"]["b"][l0].astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(merged["layers"]["wq"][l0]),
        np.asarray(expect.astype(params["layers"]["wq"].dtype)),
        rtol=1e-6, atol=1e-6)
    # untargeted weights are the same objects
    assert merged["layers"]["w_up"] is params["layers"]["w_up"]


def test_unknown_target_raises(cfg, params):
    with pytest.raises(ValueError, match="no lora targets"):
        lora_mod.init(jax.random.key(0), params,
                      LoraConfig(targets=("nope",)))


def test_lora_trainer_learns_with_frozen_base(cfg, params):
    from kubetorch_tpu.training.trainer import Trainer

    mesh = MeshSpec(dp=-1).build()
    lcfg = LoraConfig(rank=4, alpha=8.0)
    trainer = Trainer.lora(
        cfg, mesh, params, lcfg,
        optimizer=optax.adamw(1e-2))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (4, 33))
    batch = {"inputs": jnp.asarray(toks[:, :-1], jnp.int32),
             "targets": jnp.asarray(toks[:, 1:], jnp.int32)}
    losses = [float(trainer.step(batch)["loss"]) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.05, losses
    # the trained tree IS the adapter tree (adapter-sized optimizer state)
    assert set(trainer.state["params"]) <= set(LoraConfig().targets)
    # base params were never touched
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["wq"]),
        np.asarray(llama.init(jax.random.key(0), cfg)["layers"]["wq"]))
    # merged model actually changed
    merged = lora_mod.merge(params, trainer.state["params"], lcfg)
    assert not np.allclose(np.asarray(merged["layers"]["wq"]),
                           np.asarray(params["layers"]["wq"]))


def test_merged_adapters_serve_and_quantize(cfg, params):
    from kubetorch_tpu.models.generate import Generator
    from kubetorch_tpu.models.quant import quantize_params

    lcfg = LoraConfig(rank=4)
    adapters = lora_mod.init(jax.random.key(5), params, lcfg)
    adapters = jax.tree.map(
        lambda x: x + 0.01 if x.ndim == 3 else x, adapters)
    merged = lora_mod.merge(params, adapters, lcfg)
    out = Generator(merged, cfg).generate(
        [[3, 1, 4]], max_new_tokens=4, temperature=0.0)
    assert len(out[0]) == 4
    qmerged = jax.jit(quantize_params)(merged)
    out_q = Generator(qmerged, cfg).generate(
        [[3, 1, 4]], max_new_tokens=4, temperature=0.0)
    assert len(out_q[0]) == 4


def test_adapter_bytes_are_tiny(cfg, params):
    lcfg = LoraConfig(rank=8)
    adapters = lora_mod.init(jax.random.key(6), params, lcfg)
    base_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(params))
    assert lora_mod.nbytes(adapters) < 0.2 * base_bytes
    assert lora_mod.num_params(adapters) > 0


# ---------------------------------------------------------- multi-adapter
def _noisy_adapters(key, params, lcfg, scale=0.05):
    ad = lora_mod.init(key, params, lcfg)
    ks = jax.random.split(key, len(ad))
    for k, name in zip(ks, sorted(ad)):
        ad[name]["b"] = (jax.random.normal(k, ad[name]["b"].shape,
                                           jnp.float32) * scale
                         ).astype(ad[name]["b"].dtype)
    return ad


def test_multi_adapter_prefill_logits_match_merged(cfg, params):
    from kubetorch_tpu.models.lora import stack_adapters

    lcfg = LoraConfig(rank=4, alpha=8.0)
    ads = [_noisy_adapters(jax.random.key(i + 10), params, lcfg)
           for i in range(2)]
    stacked = stack_adapters(ads, lcfg)
    B, P, M = 3, 6, 10
    toks = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(P)[None], (B, P))
    mask = jnp.broadcast_to(
        jnp.arange(M)[None, None, :] <= jnp.arange(P)[None, :, None],
        (B, P, M))
    slots = jnp.asarray([0, 1, -1], jnp.int32)
    cache = llama.init_cache(cfg, B, M)
    got, _ = llama.forward_cached(
        params, toks, positions, cache, 0, mask, cfg,
        lora={"adapters": stacked, "slots": slots, "scale": lcfg.scale})
    # row 0 ≡ merged adapter 0, row 1 ≡ merged adapter 1, row 2 ≡ base
    for row, ref_params in ((0, lora_mod.merge(params, ads[0], lcfg)),
                            (1, lora_mod.merge(params, ads[1], lcfg)),
                            (2, params)):
        cache2 = llama.init_cache(cfg, 1, M)
        ref, _ = llama.forward_cached(
            ref_params, toks[row:row + 1], positions[:1], cache2, 0,
            mask[row:row + 1], cfg)
        np.testing.assert_allclose(np.asarray(got[row]),
                                   np.asarray(ref[0]),
                                   rtol=2e-4, atol=2e-4)


def test_multi_adapter_generate_per_request(cfg, params):
    from kubetorch_tpu.models.generate import Generator
    from kubetorch_tpu.models.lora import stack_adapters

    lcfg = LoraConfig(rank=4, alpha=8.0)
    ads = [_noisy_adapters(jax.random.key(i + 20), params, lcfg, 0.2)
           for i in range(2)]
    stacked = stack_adapters(ads, lcfg)
    gen = Generator(params, cfg, adapters=stacked,
                    adapter_scale=lcfg.scale)
    prompts = [[3, 7, 11], [3, 7, 11], [3, 7, 11]]
    out = gen.generate(prompts, max_new_tokens=6, temperature=0.0,
                       adapter_ids=[0, 1, -1])
    # the base row must be token-identical to a no-adapter Generator
    # (the −1 index masks the delta to exactly zero)
    base = Generator(params, cfg).generate([prompts[2]], max_new_tokens=6,
                                           temperature=0.0)
    assert out[2] == base[0]
    # different adapters actually steer generation apart
    assert out[0] != out[2] or out[1] != out[2]
    # merged single-adapter generation agrees with the batched select
    m0 = Generator(lora_mod.merge(params, ads[0], lcfg), cfg).generate(
        [prompts[0]], max_new_tokens=6, temperature=0.0)
    assert out[0] == m0[0]


def test_multi_adapter_fused_quantized_serving(cfg, params):
    from kubetorch_tpu.models.generate import Generator
    from kubetorch_tpu.models.lora import stack_adapters
    from kubetorch_tpu.models.quant import (
        fuse_decode_layers,
        quantize_params,
    )

    lcfg = LoraConfig(rank=4, alpha=8.0)
    ads = [_noisy_adapters(jax.random.key(i + 30), params, lcfg, 0.2)
           for i in range(2)]
    qparams = jax.jit(quantize_params)(params)
    qparams = {**qparams, "layers": fuse_decode_layers(qparams["layers"])}
    stacked = stack_adapters(ads, lcfg,
                             layer_names=set(qparams["layers"]))
    assert "wqkv" in stacked and "wgu" in stacked
    gen = Generator(qparams, cfg, kv_dtype="int8", adapters=stacked,
                    adapter_scale=lcfg.scale)
    prompts = [[2, 4, 6], [2, 4, 6]]
    out = gen.generate(prompts, max_new_tokens=5, temperature=0.0,
                       adapter_ids=[0, -1])
    assert all(len(o) == 5 for o in out)
    base = Generator(qparams, cfg, kv_dtype="int8").generate(
        [prompts[1]], max_new_tokens=5, temperature=0.0)
    assert out[1] == base[0]


def test_adapter_id_validation(cfg, params):
    from kubetorch_tpu.models.generate import Generator
    from kubetorch_tpu.models.lora import stack_adapters

    lcfg = LoraConfig(rank=2)
    stacked = stack_adapters(
        [lora_mod.init(jax.random.key(0), params, lcfg)], lcfg)
    with pytest.raises(ValueError, match="adapter_scale"):
        Generator(params, cfg, adapters=stacked)
    gen = Generator(params, cfg, adapters=stacked,
                    adapter_scale=lcfg.scale)
    with pytest.raises(ValueError, match="out of range"):
        gen.generate([[1, 2]], max_new_tokens=2, adapter_ids=[3])
    with pytest.raises(ValueError, match="no .*adapters|adapters"):
        Generator(params, cfg).generate([[1, 2]], max_new_tokens=2,
                                        adapter_ids=[0])


def test_multi_adapter_rolling_matches_static(cfg, params):
    from kubetorch_tpu.models.generate import Generator
    from kubetorch_tpu.models.lora import stack_adapters
    from kubetorch_tpu.models.rolling import RollingGenerator

    lcfg = LoraConfig(rank=4, alpha=8.0)
    ads = [_noisy_adapters(jax.random.key(i + 40), params, lcfg, 0.2)
           for i in range(2)]
    stacked = stack_adapters(ads, lcfg)
    eng = RollingGenerator(params, cfg, max_slots=4, steps_per_call=4,
                           adapters=stacked, adapter_scale=lcfg.scale)
    prompt = [3, 7, 11]
    r0 = eng.submit(prompt, max_new_tokens=8, adapter_id=0)
    r1 = eng.submit(prompt, max_new_tokens=8, adapter_id=1)
    rb = eng.submit(prompt, max_new_tokens=8)            # base
    out = eng.run()

    gen = Generator(params, cfg, adapters=stacked, adapter_scale=lcfg.scale)
    ref = gen.generate([prompt] * 3, max_new_tokens=8, temperature=0.0,
                       adapter_ids=[0, 1, -1])
    assert out[r0] == ref[0]
    assert out[r1] == ref[1]
    assert out[rb] == ref[2]
    # adapters released with the slot: a follow-up base request on a
    # reused slot must not inherit the old adapter
    rb2 = eng.submit(prompt, max_new_tokens=8)
    out2 = eng.run()
    assert out2[rb2] == ref[2]


def test_rolling_adapter_validation(cfg, params):
    from kubetorch_tpu.models.lora import stack_adapters
    from kubetorch_tpu.models.rolling import RollingGenerator

    lcfg = LoraConfig(rank=2)
    stacked = stack_adapters(
        [lora_mod.init(jax.random.key(0), params, lcfg)], lcfg)
    with pytest.raises(ValueError, match="adapter_scale"):
        RollingGenerator(params, cfg, adapters=stacked)
    eng = RollingGenerator(params, cfg, max_slots=2, adapters=stacked,
                           adapter_scale=lcfg.scale)
    with pytest.raises(ValueError, match="out of range"):
        eng.submit([1, 2], adapter_id=5)
    # prefix KV is weight-dependent: a base-model prefix cannot serve an
    # adapted request (register a per-adapter prefix instead)
    pid = eng.register_prefix([1, 2, 3, 4])
    with pytest.raises(ValueError, match="weight-dependent"):
        eng.submit([5], prefix_id=pid, adapter_id=0)
    pid0 = eng.register_prefix([1, 2, 3, 4], adapter_id=0)
    with pytest.raises(ValueError, match="weight-dependent"):
        eng.submit([5], prefix_id=pid0, adapter_id=-1)
    with pytest.raises(ValueError, match="out of range"):
        eng.register_prefix([1, 2], adapter_id=7)
    plain = RollingGenerator(params, cfg, max_slots=2)
    with pytest.raises(ValueError, match="no .*adapters|adapters"):
        plain.submit([1, 2], adapter_id=0)


def test_stack_partial_fused_coverage_raises(cfg, params):
    from kubetorch_tpu.models.lora import stack_adapters

    lcfg = LoraConfig(rank=2, targets=("wq", "wv", "wo"))
    ads = [lora_mod.init(jax.random.key(0), params, lcfg)]
    with pytest.raises(ValueError, match="cover all of"):
        stack_adapters(ads, lcfg, layer_names={"wqkv", "wo", "w_down"})
    # unfused layout: partial targets are fine
    out = stack_adapters(ads, lcfg)
    assert set(out) == {"wq", "wv", "wo"}


def test_fused_tree_unfused_adapters_rejected(cfg, params):
    """Adapters stacked WITHOUT layer_names must not silently lose their
    qkv/gate-up deltas on a fused serving tree (ADVICE r4 medium)."""
    from kubetorch_tpu.models.generate import Generator
    from kubetorch_tpu.models.lora import stack_adapters
    from kubetorch_tpu.models.quant import (
        fuse_decode_layers,
        quantize_params,
    )
    from kubetorch_tpu.models.rolling import RollingGenerator

    lcfg = LoraConfig(rank=2)
    stacked = stack_adapters(
        [lora_mod.init(jax.random.key(0), params, lcfg)], lcfg)
    qparams = jax.jit(quantize_params)(params)
    qparams = {**qparams, "layers": fuse_decode_layers(qparams["layers"])}
    with pytest.raises(ValueError, match="stack_adapters"):
        Generator(qparams, cfg, kv_dtype="int8", adapters=stacked,
                  adapter_scale=lcfg.scale)
    with pytest.raises(ValueError, match="stack_adapters"):
        RollingGenerator(qparams, cfg, kv_dtype="int8", adapters=stacked,
                         adapter_scale=lcfg.scale)
    # correctly re-stacked adapters pass the same check
    ok = stack_adapters([lora_mod.init(jax.random.key(0), params, lcfg)],
                        lcfg, layer_names=set(qparams["layers"]))
    Generator(qparams, cfg, kv_dtype="int8", adapters=ok,
              adapter_scale=lcfg.scale)


def test_rolling_negative_adapter_id_rejected(cfg, params):
    from kubetorch_tpu.models.lora import stack_adapters
    from kubetorch_tpu.models.rolling import RollingGenerator

    lcfg = LoraConfig(rank=2)
    stacked = stack_adapters(
        [lora_mod.init(jax.random.key(0), params, lcfg)], lcfg)
    eng = RollingGenerator(params, cfg, max_slots=2, adapters=stacked,
                           adapter_scale=lcfg.scale)
    with pytest.raises(ValueError, match="out of range"):
        eng.submit([1, 2], adapter_id=-5)
    # -1 = base model stays valid
    eng.submit([1, 2], max_new_tokens=2, adapter_id=-1)


def test_fused_stack_block_diagonal_matches_unfused_math(cfg, params):
    """PR 16 satellite: the fused serving layout (A concat on the rank
    axis, B block-diagonal over the concatenated output) is
    ALGEBRAICALLY the per-target deltas laid side by side — per slot,
    per layer, to float32 exactness."""
    from kubetorch_tpu.models.lora import stack_adapters
    from kubetorch_tpu.models.quant import FUSE_GROUPS

    lcfg = LoraConfig(rank=3, alpha=6.0)
    ads = [_noisy_adapters(jax.random.key(i + 50), params, lcfg, 0.1)
           for i in range(3)]
    unfused = stack_adapters(ads, lcfg)
    fused = stack_adapters(
        ads, lcfg, layer_names={"wqkv", "wgu", "wo", "w_down"})
    assert set(fused) == {"wqkv", "wgu", "wo", "w_down"}
    for fused_name, members in FUSE_GROUPS:
        fa = fused[fused_name]["a"].astype(jnp.float32)
        fb = fused[fused_name]["b"].astype(jnp.float32)
        # [L, n, K, sum(N)] delta through the fused factors
        got = jnp.einsum("lnkr,lnrm->lnkm", fa, fb)
        want = jnp.concatenate(
            [jnp.einsum("lnkr,lnrm->lnkm",
                        unfused[m]["a"].astype(jnp.float32),
                        unfused[m]["b"].astype(jnp.float32))
             for m in members], axis=-1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    # untouched targets pass through identical
    np.testing.assert_array_equal(np.asarray(fused["wo"]["a"]),
                                  np.asarray(unfused["wo"]["a"]))


def test_validate_adapter_targets_messages_pinned(cfg, params):
    """The fail-fast messages engines rely on are API: the fused-tree
    hint must name stack_adapters(..., layer_names=) and the plain miss
    must list what the layer dict has."""
    from kubetorch_tpu.models.lora import validate_adapter_targets

    layers_fused = {"wqkv": 1, "wgu": 1, "wo": 1, "w_down": 1}
    with pytest.raises(ValueError) as err:
        validate_adapter_targets(
            {"wq": {}, "wk": {}, "wv": {}, "wo": {}}, layers_fused)
    msg = str(err.value)
    assert "adapter targets ['wk', 'wq', 'wv'] are absent" in msg
    assert "FUSED weights ['wqkv']" in msg
    assert "stack_adapters(..., layer_names=params['layers'])" in msg
    with pytest.raises(ValueError) as err2:
        validate_adapter_targets({"nope": {}}, {"wq": 1, "wo": 1})
    assert ("adapter targets ['nope'] not found in the serving layer "
            "dict (have ['wo', 'wq'])") in str(err2.value)
    # full coverage: silent success
    validate_adapter_targets(
        {"wqkv": {}, "wgu": {}, "wo": {}}, layers_fused)


def test_stack_partial_fuse_message_pinned(cfg, params):
    from kubetorch_tpu.models.lora import stack_adapters

    lcfg = LoraConfig(rank=2, targets=("wq", "wv", "wo"))
    ads = [lora_mod.init(jax.random.key(0), params, lcfg)]
    with pytest.raises(ValueError) as err:
        stack_adapters(ads, lcfg, layer_names={"wqkv", "wo"})
    msg = str(err.value)
    assert "cover all of ('wq', 'wk', 'wv') or none" in msg
    assert "have ('wq', 'wv')" in msg
    assert "serve unfused" in msg


def test_pad_adapter_slots_fixed_axis(cfg, params):
    """PR 16: the pool's fixed-axis contract — padded tail slots are
    exact zero deltas (serve the base model), and over-padding an
    already-wider tree refuses with the KT_LORA_SLOTS hint."""
    from kubetorch_tpu.models.generate import Generator
    from kubetorch_tpu.models.lora import pad_adapter_slots, stack_adapters

    lcfg = LoraConfig(rank=2, alpha=4.0)
    ads = [_noisy_adapters(jax.random.key(60), params, lcfg, 0.2)]
    padded = pad_adapter_slots(stack_adapters(ads, lcfg), 4)
    assert all(ab["a"].shape[1] == 4 and ab["b"].shape[1] == 4
               for ab in padded.values())
    gen = Generator(params, cfg, adapters=padded,
                    adapter_scale=lcfg.scale)
    prompt = [3, 7, 11]
    out = gen.generate([prompt] * 3, max_new_tokens=6, temperature=0.0,
                       adapter_ids=[0, 2, -1])
    base = Generator(params, cfg).generate([prompt], max_new_tokens=6,
                                           temperature=0.0)
    assert out[1] == base[0]          # zero-padded slot == base model
    assert out[2] == base[0]
    assert out[0] != base[0]          # the loaded slot still steers
    with pytest.raises(ValueError, match="raise KT_LORA_SLOTS"):
        pad_adapter_slots(padded, 2)


def test_lora_select_compiled_cost_flat_in_adapter_axis():
    """The per-row select is a gather: each row reads its own rank-r
    factors, so the compiled FLOPs of ``_lora_apply`` do not grow as the
    adapter axis widens 1 -> 8 (a one-hot select would stream every
    resident adapter through the matmul). A compiler count, not a time."""
    B, K, r, N = 8, 64, 8, 64

    def flops(n_slots):
        def select(h, a, b, slots):
            return llama._lora_apply(h, ({"wq": {"a": a, "b": b}}, slots, 2.0),
                                     "wq")

        compiled = jax.jit(select).lower(
            jax.ShapeDtypeStruct((B, 1, K), jnp.float32),
            jax.ShapeDtypeStruct((n_slots, K, r), jnp.float32),
            jax.ShapeDtypeStruct((n_slots, r, N), jnp.float32),
            jax.ShapeDtypeStruct((B,), jnp.int32)).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return cost["flops"]

    one, eight = flops(1), flops(8)
    assert one >= 2 * B * r * (K + N)      # both products are counted
    # the wider axis adds the gather's index clamp (a few integer ops a
    # row); a one-hot select would cost eight times the products
    assert one <= eight < 1.01 * one, (one, eight)
