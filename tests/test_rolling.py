"""Continuous-batching tests: greedy equivalence with isolated Generator
runs, mid-flight admission, slot reuse, compile stability (no reference
analogue — vLLM-core scheduling owned natively, see models/rolling.py)."""

import jax
import numpy as np
import pytest

from kubetorch_tpu.models import LlamaConfig, llama
from kubetorch_tpu.models.generate import Generator
from kubetorch_tpu.models.rolling import RollingGenerator, _bucket


def _cfg():
    return LlamaConfig(vocab_size=256, embed_dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, head_dim=16, mlp_dim=128, remat=False,
                       dtype="float32", param_dtype="float32",
                       max_seq_len=128)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = llama.init(jax.random.key(0), cfg)
    return params, cfg


@pytest.mark.level("unit")
def test_bucket():
    assert _bucket(3) == 16
    assert _bucket(16) == 16
    assert _bucket(17) == 32
    assert _bucket(100) == 128


@pytest.mark.level("minimal")
def test_rolling_greedy_matches_isolated_generator(model):
    """Tokens from the shared rolling batch must equal each prompt's
    isolated greedy generation — the correctness bar for continuous
    batching."""
    params, cfg = model
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [11, 22, 33, 44, 55, 66, 7]]
    n_new = 12

    gen = Generator(params, cfg)
    isolated = [gen.generate([p], max_new_tokens=n_new, temperature=0.0,
                             seed=0)[0] for p in prompts]

    eng = RollingGenerator(params, cfg, max_slots=4)
    rids = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    out = eng.run()
    for rid, expect in zip(rids, isolated):
        assert out[rid] == expect, (rid, out[rid], expect)


@pytest.mark.level("minimal")
def test_midflight_admission_and_slot_reuse(model):
    """A request arriving mid-decode joins without disturbing running
    sequences; freed slots are reused; short requests finish first."""
    params, cfg = model
    gen = Generator(params, cfg)
    pa, pb, pc = [1, 2, 3], [4, 5, 6, 7], [10, 20]
    iso = {
        "a": gen.generate([pa], max_new_tokens=10, temperature=0.0)[0],
        "b": gen.generate([pb], max_new_tokens=4, temperature=0.0)[0],
        "c": gen.generate([pc], max_new_tokens=6, temperature=0.0)[0],
    }

    eng = RollingGenerator(params, cfg, max_slots=2)  # forces queueing
    ra = eng.submit(pa, max_new_tokens=10)
    rb = eng.submit(pb, max_new_tokens=4)
    rc = eng.submit(pc, max_new_tokens=6)  # queued until a slot frees

    seen = {ra: [], rb: [], rc: []}
    steps = 0
    while eng.pending:
        for rid, toks, done in eng.step():
            seen[rid].extend(toks)
        steps += 1
        assert steps < 100
    assert seen[ra] == iso["a"]
    assert seen[rb] == iso["b"]
    assert seen[rc] == iso["c"]
    # b (4 tokens) freed its slot for c while a (10 tokens) kept running
    assert len(eng._free) == eng.max_slots


@pytest.mark.level("minimal")
def test_eos_frees_slot(model):
    params, cfg = model
    eng = RollingGenerator(params, cfg, max_slots=2, eos_id=0)
    rid = eng.submit([1, 2, 3], max_new_tokens=50)
    out = eng.run()
    toks = out[rid]
    # either hit eos (ends with 0) or ran to the cap
    assert len(toks) <= 50
    if 0 in toks:
        assert toks[-1] == 0 and toks.count(0) == 1


@pytest.mark.level("minimal")
def test_rolling_service_concurrent_callers(model):
    """Threaded callers (the kt.cls pod-server execution model) share one
    batch and each gets its own isolated-generation-equivalent result."""
    import threading

    from kubetorch_tpu.models.rolling import RollingService

    params, cfg = model
    gen = Generator(params, cfg)
    prompts = [[1, 2, 3], [4, 5, 6, 7], [10, 20], [8, 9]]
    iso = [gen.generate([p], max_new_tokens=6, temperature=0.0)[0]
           for p in prompts]

    svc = RollingService(RollingGenerator(params, cfg, max_slots=2))
    results = [None] * len(prompts)
    errors = []

    def call(i):
        try:
            results[i] = svc.generate(prompts[i], max_new_tokens=6,
                                      timeout=120)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(150)
    assert not errors, errors
    assert results == iso


@pytest.mark.level("minimal")
def test_rolling_under_tp_mesh(model):
    """Continuous batching on a sharded model: tp=2 mesh over the virtual
    8-device farm, params placed by logical axes, same greedy tokens."""
    import jax as _jax

    from kubetorch_tpu.models import llama as _llama
    from kubetorch_tpu.parallel import MeshSpec
    from kubetorch_tpu.parallel.sharding import (
        ShardingRules,
        named_sharding,
    )

    params, cfg = model
    mesh = MeshSpec(tp=2).build(_jax.devices()[:2])
    rules = ShardingRules.default()
    axes = _llama.param_logical_axes(cfg)
    shardings = _jax.tree.map(
        lambda ax: named_sharding(mesh, rules, *ax), axes,
        is_leaf=lambda x: isinstance(x, tuple))
    sharded = _jax.tree.map(_jax.device_put, params, shardings)
    prompts = [[1, 2, 3, 4], [7, 8]]
    gen = Generator(params, cfg)
    iso = [gen.generate([p], max_new_tokens=6, temperature=0.0)[0]
           for p in prompts]
    eng = RollingGenerator(sharded, cfg, max_slots=2, mesh=mesh,
                           rules=rules)
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    out = eng.run()
    for rid, expect in zip(rids, iso):
        assert out[rid] == expect


@pytest.mark.level("minimal")
def test_prefix_caching_matches_full_prompt(model):
    """register_prefix + suffix submits must produce the same greedy
    tokens as isolated generation over the concatenated prompt — including
    the prefix-pad garbage edge (prefix 5 pads to 16; 1-token suffix)."""
    params, cfg = model
    gen = Generator(params, cfg)
    prefix = [11, 12, 13, 14, 15]           # pads to bucket 16 → garbage gap
    suffixes = [[21, 22, 23], [31], [41, 42, 43, 44, 45, 46, 47]]
    iso = [gen.generate([prefix + s], max_new_tokens=8,
                        temperature=0.0)[0] for s in suffixes]

    eng = RollingGenerator(params, cfg, max_slots=4)
    pid = eng.register_prefix(prefix)
    rids = [eng.submit(s, max_new_tokens=8, prefix_id=pid)
            for s in suffixes]
    out = eng.run()
    for rid, expect in zip(rids, iso):
        assert out[rid] == expect, (rid, out[rid], expect)
    # mixed traffic: un-prefixed requests still work alongside
    plain = eng.submit([1, 2, 3], max_new_tokens=4)
    mixed = eng.submit(suffixes[0], max_new_tokens=4, prefix_id=pid)
    out2 = eng.run()
    assert out2[plain] == gen.generate([[1, 2, 3]], max_new_tokens=4,
                                       temperature=0.0)[0]
    assert out2[mixed] == iso[0][:4]


@pytest.mark.level("minimal")
def test_stop_sequences(model):
    """Generation halts when a stop sequence appears, including stop
    sequences that span a chunk boundary."""
    params, cfg = model
    gen = Generator(params, cfg)
    prompt = [1, 2, 3]
    free = gen.generate([prompt], max_new_tokens=20, temperature=0.0)[0]
    assert len(free) == 20
    # choose a stop seq from the greedy continuation spanning positions
    # 5..7 — i.e. crossing the steps_per_call=6 chunk boundary
    stop_seq = free[5:8]

    def earliest_end(tokens, seq):
        for end in range(len(seq), len(tokens) + 1):
            if tokens[end - len(seq):end] == seq:
                return end
        return None

    eng = RollingGenerator(params, cfg, max_slots=2, steps_per_call=6)
    rid = eng.submit(prompt, max_new_tokens=20, stop=[stop_seq])
    out = eng.run()[rid]
    # cut right after the EARLIEST completion of the stop sequence (greedy
    # continuations repeat tokens, so it may complete before position 8)
    assert out == free[:earliest_end(free, stop_seq)]
    # un-matched stop sequences don't interfere
    rid2 = eng.submit(prompt, max_new_tokens=10, stop=[[99999 % cfg.vocab_size,
                                                        1234 % cfg.vocab_size]])
    assert eng.run()[rid2] == free[:10]


@pytest.mark.level("minimal")
def test_repetition_penalty_reduces_repeats(model):
    """Greedy decode of this tiny random model degenerates into repeats;
    a repetition penalty must break the loop (and penalty=1.0 must stay
    exactly equal to the un-penalized path — covered by the equivalence
    tests running through the same code)."""
    params, cfg = model
    prompt = [1, 2, 3]
    eng = RollingGenerator(params, cfg, max_slots=2)
    rid0 = eng.submit(prompt, max_new_tokens=24)
    base = eng.run()[rid0]
    rid1 = eng.submit(prompt, max_new_tokens=24, repetition_penalty=1.5)
    pen = eng.run()[rid1]

    def repeats(seq):
        return sum(1 for a, b in zip(seq, seq[1:]) if a == b)

    assert pen != base
    assert repeats(pen) < repeats(base), (repeats(pen), repeats(base))


@pytest.mark.level("minimal")
def test_prefill_bucket_compile_stability(model):
    """Prompts in the same bucket reuse one prefill compile."""
    params, cfg = model
    eng = RollingGenerator(params, cfg, max_slots=4)
    for p in ([1, 2], [3, 4, 5], [6] * 10, [7] * 16):  # all bucket ≤16
        eng.submit(p, max_new_tokens=2)
    eng.run()
    # jit cache: one entry per distinct p_pad bucket
    sizes = eng._prefill._cache_size()
    assert sizes == 1, sizes


@pytest.mark.level("minimal")
def test_admit_width_chunked_admission_parity(model):
    """admit_width < arrivals splits admission into several narrow
    prefill calls (the 8B serving layout: 112 slots, width-16 prefills);
    tokens must still match unchunked greedy admission exactly."""
    params, cfg = model
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    n_new = 8

    wide = RollingGenerator(params, cfg, max_slots=8)
    rids_w = [wide.submit(p, max_new_tokens=n_new) for p in prompts]
    expect = wide.run()

    narrow = RollingGenerator(params, cfg, max_slots=8, admit_width=2)
    rids_n = [narrow.submit(p, max_new_tokens=n_new) for p in prompts]
    got = narrow.run()
    for rw, rn in zip(rids_w, rids_n):
        assert got[rn] == expect[rw], (rn, got[rn], expect[rw])


@pytest.mark.level("minimal")
def test_long_prefix_bucket_overshoot_clamps_to_grid(model):
    """A prefix whose BUCKET plus the suffix bucket exceeds max_len (the
    real tokens fit) must still admit — the prefixed own-cache clamps to
    the grid width instead of splicing a wider block (r4 review find)."""
    params, cfg = model
    max_len = 80
    eng = RollingGenerator(params, cfg, max_slots=2, max_len=max_len,
                           steps_per_call=2)
    prefix = [(i % 200) + 1 for i in range(40)]   # buckets to 64
    pid = eng.register_prefix(prefix)
    # suffix buckets to 16; 64 + 16 = 80 == max_len here, and with a
    # 33-token prefix bucket overshoot is exercised via a second engine
    rid = eng.submit([5, 6, 7], max_new_tokens=8, prefix_id=pid)
    out = eng.run()
    assert len(out[rid]) == 8

    gen = Generator(params, cfg)
    expect = gen.generate([prefix + [5, 6, 7]], max_new_tokens=8,
                          temperature=0.0)[0]
    assert out[rid] == expect


@pytest.mark.level("unit")
def test_int8_grid_rolling_matches_bf16_rolling(model):
    """kv_dtype='int8' rolling decode: same engine semantics at half the
    grid bytes. Near-ties aside, greedy tokens agree with the bf16 grid
    (same bar as the static Generator's int8-KV test)."""
    from kubetorch_tpu.models.rolling import RollingGenerator

    params, cfg = model
    prompts = [[3, 7, 11, 2], [5, 1], [9, 9, 9, 9, 9, 9]]
    outs = {}
    for kvd in ("bf16", "int8"):
        eng = RollingGenerator(params, cfg, max_slots=4, steps_per_call=4,
                               kv_dtype=kvd)
        rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
        res = eng.run()
        outs[kvd] = [res[r] for r in rids]
    assert all(len(o) == 12 for o in outs["int8"])
    # Quantization noise on a 2-layer/256-vocab toy flips near-tie argmaxes
    # and every flip diverges the rest of that row, so full-horizon
    # identity is not the contract. What is: the first chunk (before any
    # divergence can compound) agrees, and overall agreement stays high
    # (deterministic inputs — this is a regression pin, not a coin flip).
    first_chunk = sum(a == b for x, y in zip(outs["bf16"], outs["int8"])
                      for a, b in zip(x[:4], y[:4]))
    assert first_chunk >= 11, (first_chunk, outs)
    total = sum(len(o) for o in outs["bf16"])
    agree = sum(a == b for x, y in zip(outs["bf16"], outs["int8"])
                for a, b in zip(x, y))
    assert agree >= int(0.7 * total), (agree, total, outs)


@pytest.mark.level("minimal")
def test_int8_grid_prefix_matches_full_prompt(model):
    """Shared prefixes compose with the int8 serving grid: the prefix
    fills a QUANTIZED private cache at registration, so spliced rows are
    bit-identical to a full-prompt int8 admission — greedy outputs
    match exactly (same engine, no cross-dtype near-tie caveat)."""
    import jax.numpy as jnp

    from kubetorch_tpu.models.rolling import RollingGenerator

    params, cfg = model
    prefix = [11, 12, 13, 14, 15]
    suffixes = [[21, 22, 23], [31], [41, 42, 43, 44, 45, 46, 47]]

    full = RollingGenerator(params, cfg, max_slots=4, kv_dtype="int8",
                            admit_width=1)
    assert full.cache["k"].dtype == jnp.int8 and "ks" in full.cache
    rid_f = [full.submit(prefix + s, max_new_tokens=8) for s in suffixes]
    out_f = full.run()

    eng = RollingGenerator(params, cfg, max_slots=4, kv_dtype="int8",
                           admit_width=1)
    pid = eng.register_prefix(prefix)
    assert eng._prefixes[pid]["planes"]["k"].dtype == jnp.int8
    rid_p = [eng.submit(s, max_new_tokens=8, prefix_id=pid)
             for s in suffixes]
    out_p = eng.run()
    got = [out_p[r] for r in rid_p]
    want = [out_f[r] for r in rid_f]
    # full-prompt admission buckets prefix+suffix together while the
    # prefixed path buckets only the suffix — different einsum widths can
    # flip near-tie argmaxes on this toy model, so hold the same
    # agreement bar as the int8-vs-bf16 test rather than bit identity
    total = sum(len(o) for o in want)
    agree = sum(a == b for x, y in zip(want, got) for a, b in zip(x, y))
    assert agree >= int(0.7 * total), (agree, total, want, got)
    first_chunk = sum(a == b for x, y in zip(want, got)
                      for a, b in zip(x[:4], y[:4]))
    assert first_chunk >= 11, (first_chunk, want, got)


@pytest.mark.level("minimal")
def test_prefix_with_adapter_matches_merged_model(model):
    """A prefix registered under adapter i + adapted suffix decode must
    equal generation with that adapter merged into the weights."""
    import jax.numpy as jnp

    from kubetorch_tpu.models import lora as lora_mod
    from kubetorch_tpu.models.lora import LoraConfig, stack_adapters
    from kubetorch_tpu.models.rolling import RollingGenerator

    params, cfg = model
    lcfg = LoraConfig(rank=4, alpha=8.0)
    ad = lora_mod.init(jax.random.key(3), params, lcfg)
    for name in ad:
        ad[name]["b"] = (jax.random.normal(
            jax.random.key(11), ad[name]["b"].shape,
            jnp.float32) * 0.2).astype(ad[name]["b"].dtype)
    stacked = stack_adapters([ad], lcfg)
    prefix = [11, 12, 13, 14, 15]
    suffix = [21, 22, 23]

    merged = lora_mod.merge(params, ad, lcfg)
    ref_eng = RollingGenerator(merged, cfg, max_slots=2)
    rpid = ref_eng.register_prefix(prefix)
    rr = ref_eng.submit(suffix, max_new_tokens=8, prefix_id=rpid)
    want = ref_eng.run()[rr]

    eng = RollingGenerator(params, cfg, max_slots=2, adapters=stacked,
                           adapter_scale=lcfg.scale)
    pid = eng.register_prefix(prefix, adapter_id=0)
    r = eng.submit(suffix, max_new_tokens=8, prefix_id=pid, adapter_id=0)
    got = eng.run()[r]
    assert got == want, (got, want)


@pytest.mark.level("unit")
def test_kv_dtype_validated(model):
    from kubetorch_tpu.models.rolling import RollingGenerator

    params, cfg = model
    with pytest.raises(ValueError, match="kv_dtype"):
        RollingGenerator(params, cfg, max_slots=2, kv_dtype="fp8")


# --------------------------------------------------------------- spec


@pytest.mark.level("minimal")
def test_spec_rolling_matches_plain_rolling(model):
    """Speculative continuous batching (spec_k>1) must be greedy
    token-identical to the plain engine — drafts only survive where they
    equal the model's own argmax, so the emitted stream is the same."""
    params, cfg = model
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [11, 22, 33, 44, 55, 66, 7]]
    n_new = 12

    plain = RollingGenerator(params, cfg, max_slots=4, steps_per_call=4)
    rid_p = [plain.submit(p, max_new_tokens=n_new) for p in prompts]
    out_p = plain.run()

    spec = RollingGenerator(params, cfg, max_slots=4, steps_per_call=2,
                            spec_k=4)
    rid_s = [spec.submit(p, max_new_tokens=n_new) for p in prompts]
    out_s = spec.run()
    for rp, rs in zip(rid_p, rid_s):
        assert out_p[rp] == out_s[rs], (out_p[rp], out_s[rs])
    stats = spec.spec_stats
    # device-side acceptance count includes the surplus tokens trimmed
    # at each request's budget boundary, so >= the delivered total
    assert stats["emitted"] >= 3 * n_new
    assert stats["tokens_per_pass"] >= 1.0


@pytest.mark.level("minimal")
def test_spec_rolling_midflight_admission(model):
    """Requests joining an in-flight speculative batch decode correctly
    and reuse freed slots (the continuous-batching contract, spec on)."""
    params, cfg = model
    plain = RollingGenerator(params, cfg, max_slots=2, steps_per_call=4)
    spec = RollingGenerator(params, cfg, max_slots=2, steps_per_call=2,
                            spec_k=4)
    outs = {}
    for name, eng in (("plain", plain), ("spec", spec)):
        acc = {}
        r1 = eng.submit([1, 2, 3], max_new_tokens=6)
        r2 = eng.submit([4, 5], max_new_tokens=10)
        for rid, toks, _ in eng.step():
            acc.setdefault(rid, []).extend(toks)
        # arrives mid-flight; max_slots=2 so it queues until r1 frees
        r3 = eng.submit([6, 7, 8, 9], max_new_tokens=6)
        for rid, toks in eng.run().items():
            acc.setdefault(rid, []).extend(toks)
        outs[name] = [acc[r] for r in (r1, r2, r3)]
    assert outs["plain"] == outs["spec"], outs


@pytest.mark.level("minimal")
def test_spec_rolling_repetitive_accepts_multiple(model):
    """A looping continuation must clear >1.5 tokens per verify pass —
    the regime the speculative engine exists for."""
    params, cfg = model
    gen = Generator(params, cfg)
    warm = gen.generate([[5, 9, 13]], max_new_tokens=32,
                        temperature=0.0)[0]
    prompt = [5, 9, 13] + warm[:24]

    plain = RollingGenerator(params, cfg, max_slots=2, steps_per_call=4)
    rp = plain.submit(prompt, max_new_tokens=24)
    out_p = plain.run()[rp]

    spec = RollingGenerator(params, cfg, max_slots=2, steps_per_call=2,
                            spec_k=8, spec_ngram=2)
    rs = spec.submit(prompt, max_new_tokens=24)
    out_s = spec.run()[rs]
    assert out_s == out_p
    assert spec.spec_stats["tokens_per_pass"] > 1.5, spec.spec_stats


@pytest.mark.level("minimal")
def test_spec_rolling_int8_grid(model):
    """Speculation composes with the int8 serving grid: verify reads the
    quantized grid + bf16 chunk, accepted prefixes quantize at the
    merge. Same agreement bar as the plain int8-vs-bf16 test."""
    params, cfg = model
    prompts = [[3, 7, 11, 2], [5, 1], [9, 9, 9, 9, 9, 9]]
    outs = {}
    for name, kw in (("plain", {}), ("spec", {"spec_k": 4})):
        eng = RollingGenerator(params, cfg, max_slots=4, steps_per_call=2,
                               kv_dtype="int8", **kw)
        rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
        res = eng.run()
        outs[name] = [res[r] for r in rids]
    # int8 quantization boundaries differ between per-round merges
    # (spec) and per-chunk merges (plain) only in that the spec path
    # reads freshly-quantized rows earlier; values written are identical
    # per token, so greedy streams agree modulo near-tie flips.
    total = sum(len(o) for o in outs["plain"])
    agree = sum(a == b for x, y in zip(outs["plain"], outs["spec"])
                for a, b in zip(x, y))
    assert agree >= int(0.7 * total), (agree, total, outs)
    first_chunk = sum(a == b for x, y in zip(outs["plain"], outs["spec"])
                      for a, b in zip(x[:4], y[:4]))
    assert first_chunk >= 11, (first_chunk, outs)


@pytest.mark.level("minimal")
def test_spec_rolling_with_adapters(model):
    """Per-request LoRA rides the verify forward: spec+adapters is
    token-identical to plain rolling+adapters."""
    import jax.numpy as jnp

    from kubetorch_tpu.models import lora as lora_mod
    from kubetorch_tpu.models.lora import LoraConfig, stack_adapters

    params, cfg = model
    lcfg = LoraConfig(rank=4, alpha=8.0)
    ads = []
    for i in range(2):
        ad = lora_mod.init(jax.random.key(i + 1), params, lcfg)
        for name in ad:
            ad[name]["b"] = (jax.random.normal(
                jax.random.key(i + 7), ad[name]["b"].shape,
                jnp.float32) * 0.2).astype(ad[name]["b"].dtype)
        ads.append(ad)
    stacked = stack_adapters(ads, lcfg)
    prompts = [[3, 7, 11], [3, 7, 11], [3, 7, 11]]
    aids = [0, 1, -1]
    outs = {}
    for name, kw in (("plain", {}), ("spec", {"spec_k": 4})):
        eng = RollingGenerator(params, cfg, max_slots=4, steps_per_call=2,
                               adapters=stacked, adapter_scale=lcfg.scale,
                               **kw)
        rids = [eng.submit(p, max_new_tokens=10, adapter_id=a)
                for p, a in zip(prompts, aids)]
        res = eng.run()
        outs[name] = [res[r] for r in rids]
    assert outs["plain"] == outs["spec"], outs
    # adapters actually steer: adapted rows differ from the base row
    assert (outs["spec"][0] != outs["spec"][2]
            or outs["spec"][1] != outs["spec"][2])


@pytest.mark.level("unit")
def test_spec_rolling_validation(model):
    params, cfg = model
    with pytest.raises(ValueError, match="spec_k"):
        RollingGenerator(params, cfg, max_slots=2, spec_k=1)
    eng = RollingGenerator(params, cfg, max_slots=2, spec_k=4,
                           steps_per_call=2)
    with pytest.raises(ValueError, match="repetition_penalty"):
        eng.submit([1, 2], max_new_tokens=4, repetition_penalty=1.3)
    # sampling is supported (exact rejection sampling per slot)
    eng.submit([1, 2], max_new_tokens=4, temperature=0.7)


@pytest.mark.level("minimal")
def test_spec_rolling_eos_and_stop(model):
    """eos/stop trimming happens host-side per chunk — identical
    behavior with speculation on (both engines see the same stream)."""
    params, cfg = model
    plain = RollingGenerator(params, cfg, max_slots=2, steps_per_call=4)
    probe = plain.submit([2, 4, 6], max_new_tokens=16)
    stream = plain.run()[probe]
    eos = stream[5]
    stop_seq = stream[2:4]

    for kw in ({}, {"spec_k": 4, "steps_per_call": 2}):
        eng = RollingGenerator(params, cfg, max_slots=2, eos_id=eos,
                               **({"steps_per_call": 4} | kw))
        r = eng.submit([2, 4, 6], max_new_tokens=16)
        out = eng.run()[r]
        assert out == stream[:6], (kw, out)
        eng2 = RollingGenerator(params, cfg, max_slots=2,
                                **({"steps_per_call": 4} | kw))
        r2 = eng2.submit([2, 4, 6], max_new_tokens=16, stop=[stop_seq])
        out2 = eng2.run()[r2]
        assert out2 == stream[:4], (kw, out2)


@pytest.mark.level("minimal")
def test_spec_rolling_with_prefix(model):
    """Speculation + shared prefix: prefix tokens seed the draft
    haystack, and the emitted stream equals the plain prefixed engine."""
    params, cfg = model
    prefix = [11, 12, 13, 14, 15]
    suffixes = [[21, 22, 23], [31]]
    outs = {}
    for name, kw in (("plain", {"steps_per_call": 4}),
                     ("spec", {"spec_k": 4, "steps_per_call": 2})):
        eng = RollingGenerator(params, cfg, max_slots=2, **kw)
        pid = eng.register_prefix(prefix)
        rids = [eng.submit(s, max_new_tokens=10, prefix_id=pid)
                for s in suffixes]
        res = eng.run()
        outs[name] = [res[r] for r in rids]
    assert outs["plain"] == outs["spec"], outs


@pytest.mark.level("minimal")
def test_serving_width_rolling_int8_parity(model):
    """Serving-shaped engine OFF-chip (VERDICT r4 weak #7): 64 slots ×
    admit_width 16 × int8 grid — wide deferred-merge/one-hot-select
    machinery regression-guarded without a TPU session. 20 staggered
    requests exercise multi-wave chunked admission, slot reuse, and the
    one-hot merge at batch widths the toy tests never reach; every
    request must match its isolated single-slot generation."""
    params, cfg = model
    rng = np.random.RandomState(7)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, rng.randint(2, 12))]
               for _ in range(20)]
    budgets = [int(b) for b in rng.randint(4, 12, 20)]

    iso = {}
    ref = RollingGenerator(params, cfg, max_slots=1, steps_per_call=4,
                           kv_dtype="int8")
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        rid = ref.submit(p, max_new_tokens=b)
        iso[i] = ref.run()[rid]

    eng = RollingGenerator(params, cfg, max_slots=64, steps_per_call=4,
                           admit_width=16, kv_dtype="int8")
    first = [eng.submit(p, max_new_tokens=b)
             for p, b in zip(prompts[:12], budgets[:12])]
    acc = {r: [] for r in first}
    for rid, toks, _ in eng.step():                 # one chunk in flight
        acc[rid].extend(toks)
    late = [eng.submit(p, max_new_tokens=b)         # staggered arrivals
            for p, b in zip(prompts[12:], budgets[12:])]
    for r in late:
        acc[r] = []
    for rid, toks in eng.run().items():
        acc[rid].extend(toks)

    rids = first + late
    mismatch = sum(acc[r] != iso[i] for i, r in enumerate(rids))
    # int8 near-tie flips across admission widths are possible on the toy
    # model but rare; the machinery bar is: every stream full-length and
    # almost all streams identical to isolated generation
    assert all(len(acc[r]) == budgets[i] for i, r in enumerate(rids)), acc
    assert mismatch <= 2, (
        mismatch, [(acc[r], iso[i]) for i, r in enumerate(rids)
                   if acc[r] != iso[i]])


@pytest.mark.level("minimal")
def test_spec_rolling_sampled_matches_plain_distribution(model):
    """temperature>0 on a speculative engine: exact per-slot rejection
    sampling — the emitted stream must be distributed as non-speculative
    sampling. Monte-Carlo over the first two tokens (top_k=4 keeps the
    support small), identical prompts as independent requests."""
    import collections

    params, cfg = model
    B = 768
    prompt = [3, 7, 11, 2, 9]

    def hist(eng):
        rids = [eng.submit(list(prompt), max_new_tokens=2,
                           temperature=1.0) for _ in range(B)]
        res = eng.run()
        return collections.Counter(tuple(res[r]) for r in rids)

    plain = RollingGenerator(params, cfg, max_slots=128, top_k=4,
                             steps_per_call=2, seed=11)
    h_plain = hist(plain)
    spec = RollingGenerator(params, cfg, max_slots=128, top_k=4,
                            steps_per_call=1, spec_k=4, seed=22)
    h_spec = hist(spec)
    keys = set(h_plain) | set(h_spec)
    tv = 0.5 * sum(abs(h_plain.get(t, 0) / B - h_spec.get(t, 0) / B)
                   for t in keys)
    assert tv < 0.12, (tv, h_plain.most_common(5), h_spec.most_common(5))


@pytest.mark.level("minimal")
def test_spec_rolling_sampled_accepts_drafts(model):
    """Sampling must still ACCEPT drafts on loopy low-temperature
    traffic (zero-acceptance rejection sampling is just plain sampling —
    the distribution test alone can't see that regression)."""
    params, cfg = model
    gen = Generator(params, cfg)
    warm = gen.generate([[5, 9, 13]], max_new_tokens=32,
                        temperature=0.0)[0]
    loopy = [5, 9, 13] + warm[:24]
    eng = RollingGenerator(params, cfg, max_slots=4, spec_k=8,
                           spec_ngram=2, steps_per_call=2, top_k=4,
                           seed=3)
    rids = [eng.submit(list(loopy), max_new_tokens=16, temperature=0.2)
            for _ in range(4)]
    res = eng.run()
    assert all(len(res[r]) == 16 for r in rids)
    assert eng.spec_stats["tokens_per_pass"] > 1.0, eng.spec_stats


@pytest.mark.level("minimal")
def test_spec_rolling_service_token_streaming(model):
    """generate_iter yields tokens INCREMENTALLY from a speculative
    engine (per decode chunk, one int at a time) — verified by
    observing the first token while the request is still mid-flight,
    under a deadline so a dead driver thread fails instead of hanging."""
    import queue as _queue
    import threading

    from kubetorch_tpu.models.rolling import RollingService

    params, cfg = model
    svc = RollingService(RollingGenerator(params, cfg, max_slots=2,
                                          spec_k=4, steps_per_call=1))
    plain = RollingGenerator(params, cfg, max_slots=2, steps_per_call=4)
    rid = plain.submit([1, 2, 3], max_new_tokens=10)
    want = plain.run()[rid]

    seen = _queue.Queue()
    got = []

    def consume():
        for i, tok in enumerate(svc.generate_iter([1, 2, 3],
                                                  max_new_tokens=10)):
            got.append(tok)
            if i == 0:
                # first token observed while the request is still
                # decoding — incremental delivery, not a drained batch
                seen.put(svc.engine.pending)
        seen.put("done")

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    pending_at_first = seen.get(timeout=60)
    assert pending_at_first > 0, "first token arrived only after drain"
    assert seen.get(timeout=60) == "done"
    t.join(10)
    assert not t.is_alive()
    assert got == want, (got, want)


@pytest.mark.level("minimal")
def test_spec_warmup_compiles_sampling_executable(model):
    """warmup(sampling=True) pre-flips the sticky sampling upgrade so
    the first temperature>0 request doesn't compile mid-traffic; the
    engine still serves greedy traffic identically afterwards."""
    params, cfg = model
    eng = RollingGenerator(params, cfg, max_slots=2, spec_k=4,
                           steps_per_call=2)
    eng.warmup(prompt_buckets=(16,), sampling=True)
    assert eng._spec_sampling
    plain = RollingGenerator(params, cfg, max_slots=2, steps_per_call=4)
    rid_p = plain.submit([1, 2, 3], max_new_tokens=8)
    want = plain.run()[rid_p]
    rid = eng.submit([1, 2, 3], max_new_tokens=8)       # greedy request
    assert eng.run()[rid] == want
    rid_s = eng.submit([1, 2, 3], max_new_tokens=8, temperature=0.9)
    assert len(eng.run()[rid_s]) == 8


@pytest.mark.level("minimal")
def test_rolling_decoder_remote_facing_driver(model):
    """RollingDecoder: the JSON-able submit/step wrapper driven through
    the pipelined call channel. Events must be plain types (survive the
    json wire), match the engine's own output, and step() must report
    the measured device time the latency decomposition checks against."""
    import json

    from kubetorch_tpu.models.rolling import RollingDecoder

    params, cfg = model
    eng = RollingGenerator(params, cfg, max_slots=4)
    dec = RollingDecoder(eng)
    rid = dec.submit([1, 2, 3, 4, 5], max_new_tokens=10)
    got = []
    while True:
        out = dec.step()
        json.dumps(out)  # the whole step result must be wire-safe
        assert out["device_ms"] > 0
        for erid, toks, done in out["events"]:
            if erid == rid:
                got.extend(toks)
        if not out["pending"]:
            break
    gen = Generator(params, cfg)
    expect = gen.generate([[1, 2, 3, 4, 5]], max_new_tokens=10,
                          temperature=0.0, seed=0)[0]
    assert got == expect
    assert dec.stats()["free_slots"] == 4


# ---------------------------------------------------------------------
# ISSUE 10: row-granular admission (splice correctness), eviction, and
# chunked grid-resident prefill — the model-level half of the serving
# engine's scheduler.


@pytest.mark.level("minimal")
def test_admit_into_live_batch_splices_identically(model):
    """_admit_group/_finish_admit splice correctness: a row admitted
    into a LIVE batch (neighbor rows mid-decode at depth) decodes
    token-identically to a fresh-batch run of the same prompt."""
    params, cfg = model
    p_bg, p_new = [1, 2, 3, 4], [42, 17, 9]
    gen = Generator(params, cfg)
    iso_new = gen.generate([p_new], max_new_tokens=8, temperature=0.0)[0]

    eng = RollingGenerator(params, cfg, max_slots=3)
    eng.submit(p_bg, max_new_tokens=24)
    eng.step()
    eng.step()                       # background row is deep in decode
    rid = eng.submit(p_new, max_new_tokens=8)
    got = []
    while eng.pending:
        for r, toks, done in eng.step():
            if r == rid:
                got.extend(toks)
    assert got == iso_new, (got, iso_new)


@pytest.mark.level("minimal")
def test_evicted_row_cache_plane_is_reusable(model):
    """evict() frees the row immediately and a new request admitted
    into the SAME slot decodes identically to a fresh-batch run — the
    stale K/V beyond the new depth is never attended."""
    params, cfg = model
    gen = Generator(params, cfg)
    iso = gen.generate([[9, 8, 7]], max_new_tokens=6, temperature=0.0)[0]

    eng = RollingGenerator(params, cfg, max_slots=1)   # one row only
    ra = eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=40)
    eng.step()
    eng.step()                                # row holds deep stale K/V
    assert eng.evict(ra)
    assert not eng.evict(ra)                  # second evict: gone
    assert eng.free_rows == 1
    rc = eng.submit([9, 8, 7], max_new_tokens=6)
    out = []
    while eng.pending:
        for r, toks, done in eng.step():
            assert r == rc, "evicted rid must never emit again"
            out.extend(toks)
    assert out == iso, (out, iso)


@pytest.mark.level("minimal")
def test_evict_queued_and_prefilling(model):
    params, cfg = model
    eng = RollingGenerator(params, cfg, max_slots=1, prefill_chunk=8)
    ra = eng.submit([1, 2], max_new_tokens=30)
    rb = eng.submit([3, 4], max_new_tokens=4)          # queued behind a
    assert eng.evict(rb)                               # queued evict
    assert eng.queued == 1                             # only ra remains
    eng.step()
    # long prompt enters chunked prefill once the row frees
    eng.evict(ra)
    rc = eng.submit(list(range(1, 25)), max_new_tokens=4)
    eng.admit()
    assert eng.prefilling_rows == 1
    assert eng.evict(rc)                               # mid-prefill evict
    assert eng.prefilling_rows == 0 and eng.free_rows == 1
    assert eng.pending == 0


@pytest.mark.level("minimal")
def test_chunked_prefill_token_identity_and_no_stall(model):
    """A long prompt prefilled in chunks interleaved with decode steps
    yields byte-identical tokens to its isolated run, and the live
    neighbor row emits on EVERY step of the prefill window (no decode
    stall)."""
    params, cfg = model
    gen = Generator(params, cfg)
    long_p = list(range(1, 25))                        # 24 toks, chunk 8
    short_p = [5, 6, 7]
    iso_long = gen.generate([long_p], max_new_tokens=10,
                            temperature=0.0)[0]
    iso_short = gen.generate([short_p], max_new_tokens=40,
                             temperature=0.0)[0]

    eng = RollingGenerator(params, cfg, max_slots=4, prefill_chunk=8)
    rs = eng.submit(short_p, max_new_tokens=40)
    seen = {rs: []}
    for _, toks, _ in eng.step():                      # short is live
        seen[rs].extend(toks)
    rl = eng.submit(long_p, max_new_tokens=10)
    seen[rl] = []
    prefill_window_emits = []
    while eng.pending:
        prefilling = eng.prefilling_rows > 0 or eng.queued > 0
        events = eng.step()
        if prefilling:
            prefill_window_emits.append(
                any(r == rs and toks for r, toks, _ in events))
        for r, toks, done in events:
            seen[r].extend(toks)
    assert seen[rl] == iso_long, (seen[rl], iso_long)
    assert seen[rs] == iso_short, (seen[rs], iso_short)
    # every step of the prefill window also emitted live tokens
    assert prefill_window_emits and all(prefill_window_emits), \
        prefill_window_emits


@pytest.mark.level("minimal")
def test_chunked_prefill_matches_oneshot_admission(model):
    """The chunked grid-resident prefill and the one-shot private-cache
    admission are the same function of the prompt: identical greedy
    tokens from either path."""
    params, cfg = model
    prompt = list(range(7, 47))                        # 40 tokens
    eng_a = RollingGenerator(params, cfg, max_slots=2)
    ra = eng_a.submit(prompt, max_new_tokens=12)
    out_a = eng_a.run()[ra]
    eng_b = RollingGenerator(params, cfg, max_slots=2, prefill_chunk=16)
    rb = eng_b.submit(prompt, max_new_tokens=12)
    out_b = eng_b.run()[rb]
    assert out_a == out_b, (out_a, out_b)


@pytest.mark.level("minimal")
def test_chunked_prefill_composes_with_spec(model):
    """ISSUE 14 tentpole: the prefill_chunk × spec_k ctor
    incompatibility is LIFTED — a long prompt prefills into the grid
    chunk by chunk and the draft haystack seeds at activation, so the
    spec stream stays token-identical to the plain engine's. Bad chunk
    sizes still raise."""
    params, cfg = model
    prompt = [(i * 7) % 50 + 2 for i in range(40)]   # > chunk of 16
    plain = RollingGenerator(params, cfg, max_slots=2)
    rp = plain.submit(prompt, max_new_tokens=12)
    out_p = plain.run()[rp]
    spec = RollingGenerator(params, cfg, max_slots=2, prefill_chunk=16,
                            spec_k=4, steps_per_call=2)
    rs = spec.submit(prompt, max_new_tokens=12)
    out_s = spec.run()[rs]
    assert out_p == out_s, (out_p, out_s)
    assert spec.spec_stats["rounds"] > 0
    with pytest.raises(ValueError):
        RollingGenerator(params, cfg, max_slots=2, prefill_chunk=0)


def _drive_three_chunks(params, cfg, kvd):
    """Three decode chunks over rows at different depths, one row freed
    after the first chunk and its slot re-admitted: (tokens by rid, the
    dequantized merged grid, the engine)."""
    eng = RollingGenerator(params, cfg, max_slots=4, max_len=384,
                           steps_per_call=4, kv_dtype=kvd)
    prompts = [[(7 * i) % 250 + 1 for i in range(n)] for n in (5, 140, 250)]
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    rids.append(eng.submit([3, 1, 4, 1, 5], max_new_tokens=4))   # one chunk
    toks = {r: [] for r in rids}
    for chunk in range(3):
        if chunk == 1:      # the short row is done: its slot is free again
            assert eng.free_rows == 1
            rids.append(eng.submit([2, 7, 1, 8, 2, 8], max_new_tokens=12))
            toks[rids[-1]] = []
        for rid, new, _ in eng.step():
            toks[rid].extend(new)
        # the host's mirror of the decoding rows' depths is the device's
        for slot in eng._slots:
            assert eng._depth[slot] == int(eng._dpos[slot])
    grid = {k: np.asarray(v, np.float32) for k, v in eng.cache.items()}
    if "ks" in grid:
        grid = {"k": grid["k"] * grid["ks"][..., None],
                "v": grid["v"] * grid["vs"][..., None]}
    return toks, grid, eng


@pytest.mark.level("minimal")
@pytest.mark.parametrize("kvd", ["bf16", "int8"])
def test_ragged_decode_kernel_matches_einsum_engine(kvd, monkeypatch):
    """ISSUE 25: single-position decode through the ragged Pallas kernel
    (forced on here, in interpret mode, by the ops module's test hook) is
    the engine the einsum pair gives: same greedy tokens, same merged
    grid, and ``stats()`` says how much of the grid each read."""
    from kubetorch_tpu.ops import decode_attention

    cfg = LlamaConfig(vocab_size=256, embed_dim=64, n_layers=2, n_heads=8,
                      n_kv_heads=4, head_dim=128, mlp_dim=128, remat=False,
                      dtype="float32", param_dtype="float32",
                      max_seq_len=384)
    params = llama.init(jax.random.key(0), cfg)
    want_toks, want_grid, ref = _drive_three_chunks(params, cfg, kvd)
    assert ref._ragged_block is None
    monkeypatch.setattr(decode_attention, "_FORCE_INTERPRET", True)
    got_toks, got_grid, eng = _drive_three_chunks(params, cfg, kvd)
    assert eng._ragged_block == 128            # 384 = 3 key blocks
    assert got_toks == want_toks
    # f32 grid: f32 operands both ways, only the order of sums differs.
    # int8 grid: bf16 rounding of different numbers (see
    # tests/test_decode_attention.py) moves layer 1's K/V by ~2^-8
    # relative, which may step a quantized value by one level (1/127).
    tol = 1e-4 if kvd == "bf16" else 2.0 / 127
    for name in ("k", "v"):
        scale = np.abs(want_grid[name]).max()
        assert np.abs(got_grid[name] - want_grid[name]).max() <= tol * scale

    s_ref, s_eng = ref.stats(), eng.stats()
    for s in (s_ref, s_eng):
        assert (0 < s["decode_kv_positions_live"]
                <= s["decode_kv_positions_read"]
                <= s["decode_kv_positions_grid"])
    assert s_ref["decode_kv_positions_grid"] == 3 * 4 * 384
    assert s_ref["decode_kv_positions_read"] == \
        s_ref["decode_kv_positions_grid"]                  # exactly 1.0
    assert s_eng["decode_kv_positions_live"] == \
        s_ref["decode_kv_positions_live"]
    assert s_eng["decode_kv_positions_read"] < \
        s_eng["decode_kv_positions_grid"]
    assert s_eng["decode_kv_positions_read"] % 128 == 0


# ---------------------------------------------------------------------
# ISSUE 38: the admission draws the row's first token, the decode chunk
# behind it takes it as given, and the host reads it behind that chunk's
# dispatch.
DECODERS = ("dense", "latent", "hybrid")


def _toy_engine(decoder, **kw):
    from test_grid_write import _toy

    params, cfg, _ = _toy(decoder)
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 96)
    kw.setdefault("steps_per_call", 4)
    return RollingGenerator(params, cfg, **kw)


def _as_the_parent(eng):
    """Drive ``step()`` as it ran before the admission drew anything: the
    fresh rows' carried tokens are dropped after every admission, so step 0
    of the next chunk draws each one's first token from its prefill's
    logits."""
    import jax.numpy as jnp

    eng.admit()
    eng.prefill_step()
    fresh = np.zeros(eng.max_slots, bool)
    fresh[list(eng._first_pending)] = True
    eng._first_pending.clear()
    eng._dnt_valid = eng._dnt_valid & ~jnp.asarray(fresh)
    return eng.decode_step()


@pytest.mark.level("minimal")
@pytest.mark.parametrize("decoder", DECODERS)
def test_first_token_at_admission_keeps_the_greedy_lists(decoder):
    """``step()`` by hand returns the token lists it always did, chunk by
    chunk and in the rows' order: the token the admission drew heads the
    row's list of its first chunk. A request of one token and one cut by a
    stop sequence on its first token finish there. And every admitted row's
    first token was read at its admission."""
    prompts = [([1, 2, 3, 4, 5], 9, None), ([9, 8, 7], 1, None),
               ([11, 22, 33, 44, 55, 66, 7], 6, None), ([5, 4], 3, None)]
    want_eng = _toy_engine(decoder)
    for p, n, _ in prompts:
        want_eng.submit(p, max_new_tokens=n)
    want = []
    while want_eng.pending:
        want.append(_as_the_parent(want_eng))
    assert want_eng.stats()["first_tokens_at_admit"] == 0
    # a stop sequence that is the first token the third prompt emits
    first = next(toks[0] for chunk in want for rid, toks, _ in chunk
                 if rid == 2)
    for stops in (None, [[first]]):
        eng = _toy_engine(decoder)
        for p, n, _ in prompts:
            eng.submit(p, max_new_tokens=n, stop=stops)
        got = []
        while eng.pending:
            got.append(eng.step())
        if stops is None:
            assert got == want
        else:
            third = [(toks, done) for chunk in got for rid, toks, done
                     in chunk if rid == 2]
            assert third == [([first], True)]
        stats = eng.stats()
        assert stats["admitted"] == len(prompts)
        assert stats["first_tokens_at_admit"] == stats["admitted"]
        assert eng.free_rows == 3 and not eng._first_pending


@pytest.mark.level("minimal")
@pytest.mark.parametrize("decoder", DECODERS)
def test_a_sampled_rows_first_token_is_the_one_its_chunk_forwards(decoder):
    """A sampled row must not draw twice: the token that went out at the
    admission is the one step 0 of the decode chunk feeds the model. The
    served sequence is re-scored by a plain forward (a second engine's
    prefill over prompt + served tokens): its logits after the chunk's last
    token are the generator's carried logits only if the cache holds the
    served first token, and not if it holds another."""
    eng = _toy_engine(decoder, seed=7)
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [3, 1, 4, 1, 5, 9, 2, 6]]
    sent = []
    eng.first_frames = sent.extend          # as the serving engine hears it
    rids = [eng.submit(p, max_new_tokens=20, temperature=3.0)
            for p in prompts]
    eng.admit()
    drawn = {slot: int(np.asarray(first)[i])
             for slot, (first, i) in eng._first_pending.items()}
    assert np.asarray(eng._dnt_valid).all()
    served = {rid: toks for rid, toks, _ in eng.decode_step()}
    assert not np.asarray(eng._dnt_valid).any()         # spent
    # the first frame held the drawn token alone, the chunk's the other 3
    assert sorted(sent) == sorted(
        (req.rid, [drawn[slot]], False) for slot, req in eng._slots.items())
    assert all(len(toks) == 3 for toks in served.values())
    scorer = _toy_engine(decoder)

    def rescored(seq):
        scorer.submit(seq, max_new_tokens=1)
        scorer.admit()
        (slot,) = scorer._slots
        out = np.asarray(scorer._logits[slot])
        scorer.run()
        return out

    for slot, req in eng._slots.items():
        seq = prompts[rids.index(req.rid)] + [drawn[slot]] + served[req.rid]
        assert req.tokens == seq[len(req.prompt):]
        carried = np.asarray(eng._logits[slot])
        np.testing.assert_allclose(rescored(seq), carried, rtol=2e-3,
                                   atol=2e-3)
        other = list(seq)
        other[len(req.prompt)] = (drawn[slot] + 1) % eng.cfg.vocab_size
        assert np.abs(rescored(other) - carried).max() > 0.02
    # and the admission did sample: greedy rows would have taken others
    want = _toy_engine(decoder)
    for p in prompts:
        want.submit(p, max_new_tokens=20)
    want.admit()
    assert drawn != {slot: int(np.asarray(first)[i]) for slot, (first, i)
                     in want._first_pending.items()}


@pytest.mark.level("minimal")
@pytest.mark.parametrize("path", ["prefix", "chunked", "spec", "penalty"])
def test_every_admission_path_draws_its_first_token(model, path):
    """Prefix admissions, a chunked prefill's last chunk, a speculating
    generator and a row under a repetition penalty: the lists of ``step()``
    are the parent's, and every row's first token was read at its
    admission."""
    params, cfg = model
    kw = {"chunked": dict(prefill_chunk=8), "spec": dict(spec_k=4)}.get(
        path, {})
    submit = {"penalty": dict(repetition_penalty=1.7)}.get(path, {})
    prompts = [list(range(1, 20)), [9, 8, 7], [5, 5, 5, 5, 6, 5, 5]]

    def build():
        eng = RollingGenerator(params, cfg, max_slots=2, max_len=96,
                               steps_per_call=4, **kw)
        if path == "prefix":
            submit["prefix_id"] = eng.register_prefix([7, 7, 3, 1])
        for p in prompts:
            eng.submit(p, max_new_tokens=7, **submit)
        return eng

    want_eng, eng = build(), build()
    want, got = [], []
    while want_eng.pending:
        want.append(_as_the_parent(want_eng))
    while eng.pending:
        got.append(eng.step())
    assert got == want
    stats = eng.stats()
    assert stats["first_tokens_at_admit"] == stats["admitted"] == 3


@pytest.mark.level("minimal")
def test_a_row_that_leaves_before_it_decodes_takes_its_token_along(model):
    """An admitted row exported or evicted before any chunk: its drawn token
    is not read (the counter says so), is not the slot's next occupant's,
    and the exported row, imported elsewhere, draws from its logits."""
    params, cfg = model
    gen = Generator(params, cfg)
    iso = gen.generate([[9, 8, 7]], max_new_tokens=6, temperature=0.0)[0]
    eng = RollingGenerator(params, cfg, max_slots=1, max_len=96,
                           steps_per_call=4)
    rid = eng.submit([1, 2, 3, 4], max_new_tokens=6)
    eng.admit()
    state = eng.export_row(rid)
    assert len(np.asarray(state["tokens"])) == 0     # a handoff's zero tokens
    eng.evict(rid)
    assert not eng._first_pending
    assert not np.asarray(eng._dnt_valid).any()
    # the next occupant arrives by import: no carried token, its own logits
    other = RollingGenerator(params, cfg, max_slots=1, max_len=96,
                             steps_per_call=4)
    r2 = other.submit([9, 8, 7], max_new_tokens=6)
    other.admit()
    eng.import_row(other.export_row(r2))
    out = [t for chunk in iter(eng.step, []) for _, toks, _ in chunk
           for t in toks]
    assert out == iso
    stats = eng.stats()
    assert stats["admitted"] == 1 and stats["first_tokens_at_admit"] == 0
