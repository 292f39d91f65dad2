"""An admission's own causal attention through the flash kernel
(``ops/flash_attention.py::prefill_attention``, PR 30) against the einsum
pair over the private cache that it stands in for
(``ops/cached_attention.py``: ``cached_attn_q`` / ``cached_attn``), in
interpret mode on the CPU.

Tolerance. Over an int8 private cache the oracle attends to K and V as the
cache holds them (rounded to 127 levels a head vector) and the kernel to the
K and V the projection made, so the two differ by what the int8 rounding
does to a convex mix of values of unit deviation: 2^-5 x max|v| bounds it
(seen: a quarter of that). Over a bfloat16 cache both read the same K and V
and multiply float32 operands, so only the order of float32 sums differs,
but both round their result to bfloat16, where two neighbours of one value
lie an ulp apart (2^-7 relative): 2^-7 x max|v|. A float32 model, whose
result is not rounded: 1e-5 x max|v|.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetorch_tpu.models import llama
from kubetorch_tpu.models.configs import LlamaConfig
from kubetorch_tpu.models.rolling import RollingGenerator
from kubetorch_tpu.ops import cached_attention, flash_attention

H, HKV, D = 32, 8, 128              # GQA 32 / 8, as the chat cell
BUCKETS = (256, 512, 1024)
TOL = {"int8": 2.0 ** -5, "bf16": 2.0 ** -7, "f32": 1e-5}   # x max|v|


def _qkv(n, p_pad, dtype, heads=(H, HKV)):
    h, hkv = heads
    kq, kk, kv = jax.random.split(jax.random.key(p_pad + n), 3)
    return (jax.random.normal(kq, (n, p_pad, h, D), dtype),
            jax.random.normal(kk, (n, p_pad, hkv, D), dtype),
            jax.random.normal(kv, (n, p_pad, hkv, D), dtype))


def _causal_mask(p_pad, lens):
    m = jnp.arange(p_pad)[None, None, :]
    t = jnp.arange(p_pad)[None, :, None]
    return (m <= t) & (m < lens[:, None, None])


def _prompt_lens(which, p_pad, n):
    first = {"one": 1, "half": p_pad // 2 + 1, "full": p_pad}[which]
    return jnp.asarray([first, p_pad // 3 + 2][:n], jnp.int32)


@pytest.mark.level("unit")
@pytest.mark.parametrize("n", [1, 2])
@pytest.mark.parametrize("which", ["one", "half", "full"])
@pytest.mark.parametrize("p_pad", BUCKETS)
@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_flash_path_matches_einsum_pair_on_the_real_rows(kv, p_pad, which, n):
    """Every real query (``t < len``) attends as under the einsum pair with
    its causal-and-length mask; the kernel takes no length, and what it
    computes for the rows of padding is finite."""
    heads = (H, HKV) if p_pad < 1024 else (8, 2)    # interpret-mode time
    q, k, v = _qkv(n, p_pad, jnp.bfloat16, heads)
    lens = _prompt_lens(which, p_pad, n)
    mask = _causal_mask(p_pad, lens)
    if kv == "int8":
        kq, ks = llama._kv_quantize(k)
        vq, vs = llama._kv_quantize(v)
        want = cached_attention.cached_attn_q(q, kq, vq, ks, vs, mask)
    else:
        want = cached_attention.cached_attn(q, k, v, mask)
    got = flash_attention.prefill_attention(q, k, v)
    assert got.shape == want.shape and got.dtype == want.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    bound = TOL[kv] * float(jnp.max(jnp.abs(v.astype(jnp.float32))))
    for b in range(n):
        real = int(lens[b])
        assert np.abs(got[b, :real] - want[b, :real]).max() <= bound


@pytest.mark.level("unit")
def test_float32_model_differs_by_the_order_of_sums_only():
    q, k, v = _qkv(1, 256, jnp.float32, (4, 2))
    lens = jnp.asarray([200], jnp.int32)
    want = cached_attention.cached_attn(q, k, v, _causal_mask(256, lens))
    got = flash_attention.prefill_attention(q, k, v)
    bound = TOL["f32"] * float(jnp.max(jnp.abs(v)))
    assert float(jnp.abs(got[0, :200] - want[0, :200]).max()) <= bound


# ---- which calls take the kernel

def _cfg(**kw):
    base = dict(vocab_size=256, embed_dim=64, n_layers=2, n_heads=4,
                n_kv_heads=2, head_dim=128, mlp_dim=128, remat=False,
                dtype="float32", param_dtype="float32", max_seq_len=512)
    base.update(kw)
    return LlamaConfig(**base)


@pytest.mark.level("unit")
@pytest.mark.parametrize("case,engages", [
    ("own_prefill", True),
    ("cpu_without_the_hook", False),
    ("write_at_not_zero", False),
    ("write_at_traced_zero", False),
    ("cache_longer_than_the_call", False),
    ("bucket_under_the_threshold", False),
    ("head_dim_the_kernel_cannot_tile", False),
])
def test_engages_rule(case, engages, monkeypatch):
    """``prefill_engages`` from what it can see: a static 0, a cache of the
    call's own length, a bucket from the threshold up, a tileable shape, one
    TPU device (here: the test hook)."""
    t = flash_attention._PREFILL_MIN
    args = dict(t=t, cache_len=t, write_at=0, n_heads=H, n_kv_heads=HKV,
                head_dim=D)
    if case != "cpu_without_the_hook":
        monkeypatch.setattr(flash_attention, "_FORCE_INTERPRET", True)
    if case == "write_at_not_zero":
        args["write_at"] = 8
    elif case == "write_at_traced_zero":
        args["write_at"] = jnp.int32(0)
    elif case == "cache_longer_than_the_call":
        args["cache_len"] = 2 * t
    elif case == "bucket_under_the_threshold":
        args["t"] = args["cache_len"] = t // 2
    elif case == "head_dim_the_kernel_cannot_tile":
        args["head_dim"] = 64
    assert flash_attention.prefill_engages(**args) is engages


def _lowered_calls(monkeypatch, **kwargs):
    """How many kernel calls ``forward_cached`` traces with the hook on and
    the threshold at the toy bucket."""
    monkeypatch.setattr(flash_attention, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(flash_attention, "_PREFILL_MIN", 256)
    cfg = _cfg()
    params = llama.init(jax.random.key(0), cfg)
    t = 256
    cache_len = kwargs.pop("cache_len", t)
    tokens = jnp.ones((1, t), jnp.int32)
    positions = jnp.arange(t)[None, :]
    lens = jnp.asarray([100], jnp.int32)
    mask = jnp.pad(_causal_mask(t, lens),
                   ((0, 0), (0, 0), (0, cache_len - t)))
    cache = llama.init_cache(cfg, 1, cache_len,
                             quantized=kwargs.pop("quantized", True))
    chunked = kwargs.pop("chunked", False)
    write_at = kwargs.pop("write_at", 0)
    if chunked:
        kwargs.update(
            chunk={"k": jnp.zeros((cfg.n_layers, 1, t, 2, D), jnp.bfloat16),
                   "v": jnp.zeros((cfg.n_layers, 1, t, 2, D), jnp.bfloat16)},
            chunk_col=0, chunk_mask=_causal_mask(t, lens))

    def run(params, tokens, cache):
        return llama.forward_cached(params, tokens, positions, cache,
                                    write_at, mask, cfg, **kwargs)[0]

    jaxpr = str(jax.make_jaxpr(run)(params, tokens, cache))
    return jaxpr.count("admit_flash_attention")


@pytest.mark.level("unit")
@pytest.mark.parametrize("case,taken", [
    ("int8_own_prefill", True),
    ("bf16_own_prefill", True),
    ("no_causal_lens", False),
    ("chunk_mode", False),
    ("static_generator_cache", False),
    ("write_at_8", False),
])
def test_forward_cached_takes_the_kernel_only_for_a_prompts_own_prefill(
        case, taken, monkeypatch):
    lens = jnp.asarray([100], jnp.int32)
    kwargs = {"causal_lens": lens}
    if case == "bf16_own_prefill":
        kwargs["quantized"] = False
    elif case == "no_causal_lens":
        kwargs = {}
    elif case == "chunk_mode":
        kwargs["chunked"] = True
    elif case == "static_generator_cache":
        kwargs["cache_len"] = 512
    elif case == "write_at_8":
        kwargs.update(write_at=8, cache_len=512)
    assert bool(_lowered_calls(monkeypatch, **kwargs)) is taken


# ---- the generator: same tokens, and the two counters

def _drive(params, cfg, kvd, prompts):
    eng = RollingGenerator(params, cfg, max_slots=4, max_len=512,
                           steps_per_call=4, kv_dtype=kvd)
    rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
    toks = {r: [] for r in rids}
    for _ in range(3):
        for rid, new, _ in eng.step():
            toks[rid].extend(new)
    return toks, eng


@pytest.mark.level("minimal")
@pytest.mark.parametrize("kvd", ["bf16", "int8"])
def test_generator_emits_the_same_greedy_tokens_through_the_kernel(
        kvd, monkeypatch):
    """A toy engine admits a 256-bucket and two short prompts; with the hook
    on (and the threshold at the toy bucket) the long one's prefill runs
    the kernel, the tokens are the einsum engine's, and ``stats()`` says
    which padded positions took it."""
    cfg = _cfg()
    params = llama.init(jax.random.key(0), cfg)
    prompts = [[(7 * i) % 250 + 1 for i in range(n)] for n in (5, 140, 40)]
    want, ref = _drive(params, cfg, kvd, prompts)
    s = ref.stats()
    assert s["prefill_positions"] == 16 + 256 + 64
    assert s["prefill_flash_positions"] == 0        # CPU, no hook
    monkeypatch.setattr(flash_attention, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(flash_attention, "_PREFILL_MIN", 256)
    got, eng = _drive(params, cfg, kvd, prompts)
    assert got == want
    s = eng.stats()
    assert s["prefill_positions"] == 16 + 256 + 64
    assert s["prefill_flash_positions"] == 256


@pytest.mark.level("unit")
@pytest.mark.parametrize("script,positions,flash", [
    ([(1, 16, True)], 16, 0),
    ([(1, 256, True), (1, 512, True)], 768, 768),
    ([(2, 256, True), (1, 128, True)], 640, 512),
    ([(1, 256, False), (1, 256, True)], 512, 256),   # prefix-extended: no
])
def test_admission_counters_sum_a_scripted_list(script, positions, flash,
                                                monkeypatch):
    """``prefill_positions`` sums rows x bucket over every bucketed
    admission, ``prefill_flash_positions`` those the kernel takes: buckets
    from the threshold up of a prompt's own prefill."""
    monkeypatch.setattr(flash_attention, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(flash_attention, "_PREFILL_MIN", 256)
    cfg = _cfg()
    eng = RollingGenerator(llama.init(jax.random.key(0), cfg), cfg,
                           max_slots=2, max_len=512, steps_per_call=4)
    for rows, p_pad, own in script:
        eng._count_admission(rows, p_pad, own)
    s = eng.stats()
    assert (s["prefill_positions"], s["prefill_flash_positions"]) == (
        positions, flash)


@pytest.mark.level("unit")
def test_admission_counters_without_the_hook_count_no_flash_position():
    cfg = _cfg()
    eng = RollingGenerator(llama.init(jax.random.key(0), cfg), cfg,
                           max_slots=2, max_len=512, steps_per_call=4)
    eng._count_admission(1, 2048, True)
    s = eng.stats()
    assert (s["prefill_positions"], s["prefill_flash_positions"]) == (2048, 0)


# ---- the training side is untouched

@pytest.mark.level("unit")
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_training_kernel_lowers_as_before_with_the_argument_at_its_default(
        what, monkeypatch):
    """``flash_attention(q, k, v, causal=True)`` lowers to the same text
    whether ``_flash_forward`` is left the default of its one new static
    argument (the custom call's ``name``) or handed it explicitly; the name
    serving gives it is in serving's trace alone."""
    q, k, v = _qkv(1, 512, jnp.bfloat16, (4, 2))

    def text():
        # new function objects each time: nothing traced before is reused
        def attend(q, k, v):
            return flash_attention.flash_attention(q, k, v, causal=True)

        def loss(q, k, v):
            return attend(q, k, v).astype(jnp.float32).sum()

        fn = (attend if what == "forward"
              else jax.grad(loss, argnums=(0, 1, 2)))
        return (jax.jit(fn).lower(q, k, v).as_text(),
                str(jax.make_jaxpr(fn)(q, k, v)))

    default = text()
    assert "admit_flash_attention" not in default[1]
    inner = flash_attention._flash_forward

    def explicit(*a, **kw):
        return inner(*a, **{"name": None, **kw})

    monkeypatch.setattr(flash_attention, "_flash_forward", explicit)
    assert text() == default

    monkeypatch.setattr(flash_attention, "_flash_forward", inner)
    served = str(jax.make_jaxpr(flash_attention.prefill_attention)(q, k, v))
    assert "admit_flash_attention" in served
