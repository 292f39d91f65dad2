"""The ragged decode-attention kernel (ops/decode_attention.py) against the
einsum pair it stands in for (``ops/cached_attention.py``:
``cached_attn_merged_q`` / ``cached_attn_merged``), in interpret mode at toy
widths.

Tolerance. Both sides accumulate in f32. Over an int8 grid both feed the
MXU bf16 operands, but they round DIFFERENT numbers to bf16 before PV: the
einsum pair rounds the normalised probabilities times ``vs``, the kernel the
un-normalised ``exp(s - running max)`` times ``vs`` and divides afterwards.
Each rounding is at most 2^-9 relative per element, and the output is a
convex mix of the values, so the two can differ by at most
2 x 2^-9 x max|v| = 2^-8 x max|v| in any element (seen: a third of that).
Over a float grid the oracle computes in f32 throughout and the kernel keeps
f32 operands for an f32 grid, so only the order of f32 sums differs:
1e-5 x max|v|.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetorch_tpu.models import llama
from kubetorch_tpu.ops import cached_attention, decode_attention

BLOCK = 128                         # (serving picks 512 of [512, 256, 128])
M = 4 * BLOCK                       # four key blocks
HKV, G, D = 4, 4, 128               # GQA 4:1
H = HKV * G
L, B, K = 2, 4, 8                   # layers, rows, chunk columns
LAYER, COL = 1, 2                   # the layer read; chunk columns 0..COL live

DEPTHS = (0, 1, BLOCK - 1, BLOCK, BLOCK + 1, M - 8)
PATTERNS = ("all_active", "one_inactive", "mixed_depths")
TOL = {"int8": 2.0 ** -8, "bf16": 1e-5}   # x max|v|; "bf16" = float grid


def _planes(kv: str, key):
    """A stacked grid [L, B, M, HKV, D] (+ scales for int8), a chunk and a
    query, all seeded."""
    kk, kv_, kq, ke = jax.random.split(key, 4)
    shape = (L, B, M, HKV, D)
    if kv == "int8":
        k, sk = llama._kv_quantize(
            jax.random.normal(kk, shape, jnp.float32).reshape(-1, *shape[2:]))
        v, sv = llama._kv_quantize(
            jax.random.normal(kv_, shape, jnp.float32).reshape(-1, *shape[2:]))
        grid = (k.reshape(shape), v.reshape(shape),
                sk.reshape(shape[:-1]), sv.reshape(shape[:-1]))
    else:
        grid = (jax.random.normal(kk, shape, jnp.float32),
                jax.random.normal(kv_, shape, jnp.float32), None, None)
    q = jax.random.normal(kq, (B, 1, H, D), jnp.float32)
    ek, ev = jax.random.normal(ke, (2, B, K, HKV, D), jnp.float32)
    return grid, q, ek, ev


def _rows(pattern: str, depth: int):
    """(pos0 [B], active [B]) of one case."""
    pos0 = np.full(B, depth, np.int32)
    active = np.ones(B, bool)
    if pattern == "one_inactive":
        active[1] = False
    elif pattern == "mixed_depths":
        pos0 = np.array([depth, 5, BLOCK + 3, M - 8], np.int32)
    return jnp.asarray(pos0), jnp.asarray(active)


def _both(grid, q, ek, ev, pos0, active):
    """(oracle, kernel path) outputs [B, 1, H, D] for layer LAYER, and the
    largest |v| either could have mixed."""
    gk, gv, gks, gvs = grid
    gmask = ((jnp.arange(M)[None, None, :] < pos0[:, None, None])
             & active[:, None, None])
    emask = ((jnp.arange(K)[None, None, :] <= COL) & active[:, None, None])
    if gks is not None:
        want = cached_attention.cached_attn_merged_q(
            q, gk[LAYER], gv[LAYER], gks[LAYER], gvs[LAYER], ek, ev, gmask,
            emask)
    else:
        want = cached_attention.cached_attn_merged(
            q, gk[LAYER], gv[LAYER], ek, ev, gmask, emask)
    got = cached_attention.cached_attn_ragged(
        q, gk, gv, gks, gvs, jnp.int32(LAYER),
        decode_attention.plan(jnp.where(active, pos0, 0), M, BLOCK), ek, ev,
        emask)
    vmax = float(max(jnp.abs(ev).max(), jnp.abs(
        gv[LAYER] * (1.0 if gvs is None else gvs[LAYER][..., None])).max()))
    return np.asarray(want), np.asarray(got), vmax


@pytest.mark.level("unit")
@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_kernel_matches_einsum_pair(kv, depth, pattern):
    grid, q, ek, ev = _planes(kv, jax.random.key(7))
    pos0, active = _rows(pattern, depth)
    want, got, vmax = _both(grid, q, ek, ev, pos0, active)
    rows = np.asarray(active)
    assert np.isfinite(got).all()
    assert np.abs(got - want)[rows].max() <= TOL[kv] * vmax, (
        np.abs(got - want)[rows].max(), vmax)


@pytest.mark.level("unit")
@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_dead_positions_are_not_read_into_the_result(kv):
    """Poison every grid position at or past each row's depth — NaN in the
    float planes (the int8 planes take their extreme value), inf in the
    scales — in EVERY layer: the output must stay finite and unchanged."""
    grid, q, ek, ev = _planes(kv, jax.random.key(11))
    pos0 = jnp.asarray([0, 1, BLOCK, M - 8], jnp.int32)
    active = jnp.asarray([False, True, True, True])
    depth = jnp.where(active, pos0, 0)
    emask = ((jnp.arange(K)[None, None, :] <= COL) & active[:, None, None])

    def run(planes):
        return np.asarray(cached_attention.cached_attn_ragged(
            q, *planes, jnp.int32(LAYER),
            decode_attention.plan(depth, M, BLOCK), ek, ev, emask))

    dead = (jnp.arange(M)[None, :] >= depth[:, None])[None, :, :, None]
    gk, gv, gks, gvs = grid
    if kv == "int8":
        bad = (jnp.where(dead[..., None], jnp.int8(-128), gk),
               jnp.where(dead[..., None], jnp.int8(127), gv),
               jnp.where(dead, jnp.inf, gks), jnp.where(dead, jnp.inf, gvs))
    else:
        bad = (jnp.where(dead[..., None], jnp.nan, gk),
               jnp.where(dead[..., None], jnp.nan, gv), None, None)
    clean, poisoned = run(grid), run(bad)
    assert np.isfinite(poisoned).all()
    np.testing.assert_array_equal(poisoned, clean)


@pytest.mark.level("unit")
def test_engages_only_where_the_kernel_covers_the_shape(monkeypatch):
    """On this backend (CPU) nothing engages; with the test hook the shape
    rules decide."""
    args = (M, HKV, D, jnp.int8)
    assert not decode_attention.engages(1, *args)
    monkeypatch.setattr(decode_attention, "_FORCE_INTERPRET", True)
    assert decode_attention.engages(1, *args)
    assert not decode_attention.engages(2, *args)            # T > 1
    assert not decode_attention.engages(1, M + 8, HKV, D, jnp.int8)
    assert not decode_attention.engages(1, M, HKV, 64, jnp.int8)
    assert not decode_attention.engages(1, M, 2, D, jnp.int8)  # 4 a word
    assert decode_attention.engages(1, M, 2, D, jnp.bfloat16)


# ---- compiled for the chip, without the chip (no time, no result: what the
# TPU's compiler accepts, and which layouts it gives the planes)

@pytest.fixture(scope="module")
def v5e_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.level("unit")
@pytest.mark.parametrize("kv", ["int8", "bf16"])
def test_kernel_compiles_for_v5e_with_the_planes_as_stored(kv, v5e_chip):
    """Mistral-7B widths (32 heads, 8 KV heads x 128), 32 slots x 2048:
    Mosaic takes the kernel, and the module around it neither copies nor
    transposes anything the size of a plane — the stacked K/V go into the
    custom call as they lie, the scales through a bitcast."""
    layers, b, m, hkv, h, d = 4, 32, 2048, 8, 32, 128
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    scales = spec((layers, b, m, hkv), jnp.float32) if kv == "int8" else None

    def attend(q, k, v, ks, vs, layer, depth):
        return decode_attention.ragged_decode_attention(
            q, k, v, ks, vs, layer, decode_attention.plan(depth, m))

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(attend).lower(
            spec((b, h, d), jnp.bfloat16), spec((layers, b, m, hkv, d), dt),
            spec((layers, b, m, hkv, d), dt), scales, scales,
            spec((), jnp.int32), spec((b,), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    assert text.count("tpu_custom_call") == 1
    plane = re.compile(rf"\[{layers},{b},(2048,8|8,2048)[,\]]")
    moved = [line.strip()[:160] for line in text.splitlines()
             if plane.search(line.split("=", 1)[-1].split("(", 1)[0])
             and re.search(r"\b(copy|transpose|fusion)\(", line)]
    assert not moved, moved


# The latent-attention decoder's kernels at the published widths of the
# benchmark's second configuration, compiled for the same described chip
# (this is the one test file whose worker may load the TPU's compiler).
@pytest.mark.parametrize("kernel", ["latent_decode", "latent_prefill",
                                    "grouped_decode", "grouped_prefill",
                                    "grouped_tall"])
def test_latent_and_grouped_kernels_compile_for_v5e(kernel, v5e_chip):
    """32 heads over one 640-wide latent leaf at 32 slots x 8192 (the stack
    goes into the custom call as it lies); blocked prefill attention with
    key width 192 (padded to 256) beside value width 128 at 8192 tokens;
    the grouped product of 128 experts of 2048 x 1536 over a decode step's
    192 pairs and a prefill's 49152 (row tiles of 128), and over the 262144
    pairs of a 32768-token admission at 8 experts a token in one pass (the
    tall row tile, PR 43)."""
    from kubetorch_tpu.ops import grouped_matmul, latent_attention

    bf16 = jnp.bfloat16

    def spec(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    if kernel == "latent_decode":
        b, m = 32, 8192

        def fn(q, grid, layer, depth):
            return latent_attention.ragged_decode_attention(
                q, grid, layer, latent_attention.plan(depth, m), 512,
                192 ** -0.5)
        args = (spec((b, 32, 640)), spec((8, b, m, 640)),
                spec((), jnp.int32), spec((b,), jnp.int32))
    elif kernel == "latent_prefill":
        t = 8192

        def fn(qn, qr, kn, kr, v):
            return latent_attention.prefill_attention(
                qn, qr, kn, kr, v, 192 ** -0.5, interpret=False)
        args = (spec((1, t, 32, 128)), spec((1, t, 32, 64)),
                spec((1, t, 32, 128)), spec((1, t, 64)),
                spec((1, t, 32, 128)))
    else:
        rows = {"grouped_decode": 192, "grouped_prefill": 49152,
                "grouped_tall": 262144}[kernel]
        assert grouped_matmul.tiles_for(rows, 128, 2048, 1536)[0] == (
            grouped_matmul._TALL if kernel == "grouped_tall" else 128)

        def fn(lhs, rhs, layer, sizes):
            return grouped_matmul.grouped_matmul(lhs, rhs, layer, sizes,
                                                 interpret=False)
        args = (spec((rows, 2048)), spec((7, 128, 2048, 1536)),
                spec((), jnp.int32), spec((128,), jnp.int32))
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    assert text.count("tpu_custom_call") == 1
    # nothing the size of the cache or of a layer's experts is copied
    big = re.compile(r"bf16\[(8,32,8192,640|128,2048,1536)\]")
    moved = [line.strip()[:160] for line in text.splitlines()
             if big.search(line.split("=", 1)[-1].split("(", 1)[0])
             and re.search(r"\b(copy|transpose|fusion)\(", line)]
    assert not moved, moved


# The once-a-chunk merge (ops/grid_write.py) at both serving cells' grids,
# compiled for the same described chip: PR 28's mechanism as the v5e's
# compiler sees it.
@pytest.mark.level("unit")
@pytest.mark.parametrize("cell", ["chat_int8", "kanana2_latent",
                                  "docqa_extend_int8"])
def test_merge_compiles_for_v5e_in_place_with_window_temporaries(
        cell, v5e_chip):
    """32 layers x 32 slots x 2048 int8 planes with f32 scales under an
    8-column chunk; 8 layers x 32 x 8192 x 640 bf16 latents; 6 slots x 8192
    under a 256-column prefill chunk. The grid is updated in place (all of
    it aliased to the donated argument), the temporaries are windows and
    copies of the chunk — where the select this replaced rewrote every
    layer — and no gather, scatter, select, copy or product makes anything
    the size of a plane: only the in-place slice updates do."""
    from kubetorch_tpu.models import latent_moe

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    if cell == "kanana2_latent":
        layers, b, m, k = 8, 32, 8192, 8
        merge = latent_moe.merge_chunk_into_grid
        cache = {"ckr": spec((layers, b, m, 640), jnp.bfloat16)}
        chunk = {"ckr": spec((layers, b, k, 640), jnp.bfloat16)}
    else:
        layers, b, m, k = ((32, 32, 2048, 8) if cell == "chat_int8"
                           else (32, 6, 8192, 256))
        merge = llama.merge_chunk_into_grid
        cache = {"k": spec((layers, b, m, 8, 128), jnp.int8),
                 "v": spec((layers, b, m, 8, 128), jnp.int8),
                 "ks": spec((layers, b, m, 8), jnp.float32),
                 "vs": spec((layers, b, m, 8), jnp.float32)}
        chunk = {n: spec((layers, b, k, 8, 128), jnp.bfloat16)
                 for n in ("k", "v")}
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(merge, donate_argnums=(0,)).lower(
            cache, chunk, spec((b,), jnp.int32),
            spec((b,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    memory = compiled.memory_analysis()
    grid_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                     for x in cache.values())
    chunk_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in chunk.values())
    assert memory.alias_size_in_bytes >= grid_bytes
    # under the chunk's own bytes, which is what follows ``rows x K`` (read
    # here, PR 28: 0.58, 0.16 and 152 MB — the 256-column chunk quantised
    # and padded — against chunks of 8.4, 2.6 and 201 MB; one layer of the
    # three grids is 138, 336 and 104 MB)
    assert memory.temp_size_in_bytes < chunk_bytes
    text = compiled.as_text()
    plane = re.compile(rf"\[({layers},)?{b},{m}[,\]]")
    made = [line.strip()[:160] for line in text.splitlines()
            if plane.search(line.split("=", 1)[-1].split("(", 1)[0])
            and re.search(r"\b(copy|transpose|gather|scatter|select|"
                          r"convolution|dot)\(", line)]
    assert not made, made


# The chat cell's admission executable (``RollingGenerator._prefill_impl``,
# Mistral-7B int8, 32 slots x 2048) at its two largest buckets, compiled for
# the same described chip with its attention as the einsum pair over the
# private cache and as the flash kernel (PR 30). ``prefill_engages`` asks the
# backend, which is the CPU here, so the test answers for it.
def _chat_admission(v5e_chip, p_pad, width=1):
    """(``compile()`` of the chat cell's ``_prefill_impl`` for ``width``
    rows of ``p_pad`` on the described chip, traced anew at every call; the
    grid's leaves)."""
    from kubetorch_tpu.models import quant
    from kubetorch_tpu.models.configs import LlamaConfig
    from kubetorch_tpu.models.rolling import RollingGenerator
    from kubetorch_tpu.parallel.sharding import ShardingRules

    layers, b, m, vocab = 32, 32, 2048, 32768
    cfg = LlamaConfig(vocab_size=vocab, embed_dim=4096, n_layers=layers,
                      n_heads=32, n_kv_heads=8, head_dim=128, mlp_dim=14336,
                      rope_theta=1e6, rms_eps=1e-5, tie_embeddings=False,
                      max_seq_len=m, remat=False, dtype="bfloat16",
                      param_dtype="bfloat16")

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    params = jax.tree.map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(lambda k: quant.init_quantized(k, cfg, fuse=True),
                       jax.random.key(0)))
    cache = {"k": spec((layers, b, m, 8, 128), jnp.int8),
             "v": spec((layers, b, m, 8, 128), jnp.int8),
             "ks": spec((layers, b, m, 8), jnp.float32),
             "vs": spec((layers, b, m, 8), jnp.float32)}
    args = (params, cache, spec((b, vocab), jnp.float32),
            spec((b,), jnp.int32), spec((b,), jnp.bool_),
            spec((b,), jnp.int32), spec((b,), jnp.bool_),  # carried token
            spec((width, p_pad), jnp.int32), spec((width,), jnp.int32),
            spec((width,), jnp.int32),
            # the admitted rows' sampler inputs: the admission draws
            spec((width,), jnp.float32), spec((width,), jnp.float32),
            spec((width, 64), jnp.int32),
            spec((2,), jnp.uint32))
    rules = ShardingRules.default()

    def compile():
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            return jax.jit(
                lambda *a: RollingGenerator._prefill_impl(
                    *a, None, p_pad=p_pad, top_k=None, top_p=None, cfg=cfg,
                    rules=rules),
                donate_argnums=(1, 2, 3, 4, 5, 6)).lower(*args).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)

    return compile, cache


@pytest.mark.level("unit")
@pytest.mark.parametrize("p_pad", [1024, 2048])
def test_admission_compiles_for_v5e_without_the_scores(p_pad, v5e_chip,
                                                       monkeypatch):
    """Mosaic takes the kernel at the cell's widths inside the whole
    executable (one custom call in the layer scan), the grid stays aliased in
    place, and the temporaries fall by most of the float32 scores
    ``[32 heads, p_pad, p_pad]`` (537 MB at 2048; read here, PR 30: 0.831 ->
    0.376 GB at 2048 and 0.205 -> 0.071 GB at 1024; what is left is the
    private cache and the MLP's activations)."""
    from kubetorch_tpu.ops import flash_attention

    compile, cache = _chat_admission(v5e_chip, p_pad)

    def compiled(on_tpu: bool):
        monkeypatch.setattr(flash_attention, "_one_tpu_device",
                            lambda: on_tpu)
        return compile()

    einsum, flash = compiled(False), compiled(True)
    assert einsum.as_text().count("tpu_custom_call") == 0
    assert flash.as_text().count("tpu_custom_call") == 1
    grid_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                     for x in cache.values())
    scores = 32 * p_pad * p_pad * 4
    for exe in (einsum, flash):
        assert exe.memory_analysis().alias_size_in_bytes >= grid_bytes
    saved = (einsum.memory_analysis().temp_size_in_bytes
             - flash.memory_analysis().temp_size_in_bytes)
    assert saved >= 0.8 * scores, (saved, scores)


@pytest.mark.level("unit")
def test_admission_of_two_rows_compiles_for_v5e_landing_in_place(
        v5e_chip, monkeypatch):
    """A width-2 admission at the chat cell's largest bucket (PR 32): the
    rows land by slice update on the aliased grid, so the executable holds
    no select, gather, scatter or copy the size of a grid leaf, and its
    temporaries are two rows' private caches and activations (read here,
    PR 32: 0.749 GB, twice the 0.379 of width 1). Under the gather + select
    it replaced the chip's compiler refused this executable outright
    (``RESOURCE_EXHAUSTED``: 17.14 GB of 15.75; on the chip, PR 23, the
    splice wanted 6 GB), which is what held ``admit_rows`` at 1."""
    from kubetorch_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "_one_tpu_device", lambda: True)
    compile, cache = _chat_admission(v5e_chip, 2048, width=2)
    exe = compile()
    memory = exe.memory_analysis()
    grid_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                     for x in cache.values())
    assert memory.alias_size_in_bytes >= grid_bytes
    assert memory.temp_size_in_bytes < 1.0e9, memory.temp_size_in_bytes
    plane = re.compile(r"\[(32,)?32,2048[,\]]")
    made = [line.strip()[:160] for line in exe.as_text().splitlines()
            if plane.search(line.split("=", 1)[-1].split("(", 1)[0])
            and re.search(r"\b(copy|transpose|gather|scatter|select|"
                          r"convolution|dot)\(", line)]
    assert not made, made


# The hybrid linear-attention decoder (models/hybrid_linear.py) at the
# published widths of the benchmark's third serving configuration
# (Olmo-Hybrid-7B: 30 heads of 128, linear 30 x 96 keys and 30 x 192 values,
# 12 linear + 4 full layers, 16 slots x 4096), compiled for the same
# described chip (PR 31). Its kernels ask the backend, which is the CPU
# here, so the tests answer for it.
def _head_chunk_factors(text: str):
    """Lines of a compiled module that make a float32 array a (head, chunk)
    pair of the hybrid's 30 linear heads (``[1, 30, N, 128, .]``)."""
    return [line.strip()[:160] for line in text.splitlines()
            if re.search(r"f32\[1,30,\d+,128,\d+\]", line)]


@pytest.mark.level("unit")
def test_gated_delta_kernel_compiles_for_v5e_at_the_published_widths(
        v5e_chip, monkeypatch):
    """Mosaic takes the chunked scan's kernel with 96-wide keys and 192-wide
    values as they are (no pad to the lane tile outside the call) at the
    1024 and the 4096 bucket, and since PR 41 the kernel builds a chunk's
    factors itself: XLA makes no float32 array a (head, chunk) pair
    (``[1, 30, N, 128, 128]`` for ``a``, ``T``, ``p``; ``[.., 128, 96 | 192
    | 256]`` for ``w``, ``u``, ``qg``, ``kd``), only the relayouts of q, k,
    v, o and the per-token scalars, and the scan's temporaries are under
    those of the XLA-made factors (compiled here, PR 41: 0.054 GB at 1024
    and 0.497 GB at 4096 then, none at either now)."""
    from kubetorch_tpu.ops import gated_delta

    b, h, dk, dv = 1, 30, 96, 192

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for t, temporaries in ((1024, 0.02e9), (4096, 0.2e9)):
            exe = jax.jit(gated_delta.prefill_scan).lower(
                spec((b, t, h, dk)), spec((b, t, h, dk)), spec((b, t, h, dv)),
                spec((b, t, h), jnp.float32), spec((b, t, h), jnp.float32),
                spec((b, h, dk, dv), jnp.float32)).compile()
            text = exe.as_text()
            assert text.count("tpu_custom_call") == 1, t
            assert "gated_delta_prefill" in text, t
            assert not _head_chunk_factors(text), t
            assert exe.memory_analysis().temp_size_in_bytes < temporaries, t
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.mark.level("unit")
def test_gated_delta_step_kernel_compiles_for_v5e_on_the_leaf_in_place(
        v5e_chip, monkeypatch):
    """Mosaic takes a row's ``(30, 96, 192)`` float32 block of the stacked
    state leaf as it is (a lane dim of one and a half tiles: the leaf is
    stored at 256 lanes, 566 MB for the 425 it holds), the leaf is the
    call's input and output in place, and nothing the size of the leaf or
    of a layer of it is made beside it (PR 36)."""
    from kubetorch_tpu.ops import gated_delta

    layers, b, h, dk, dv = 12, 16, 30, 96, 192

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def step(q, k, v, log_alpha, beta, states, layer, live):
        return gated_delta.step_rows(q, k, v, log_alpha, beta, states, layer,
                                     gated_delta.step_plan(live))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        exe = jax.jit(step, donate_argnums=(5,)).lower(
            spec((b, h, dk), jnp.bfloat16), spec((b, h, dk), jnp.bfloat16),
            spec((b, h, dv), jnp.bfloat16), spec((b, h)), spec((b, h)),
            spec((layers, b, h, dk, dv)), spec((), jnp.int32),
            spec((b,), jnp.bool_)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    text, mem = exe.as_text(), exe.memory_analysis()
    assert text.count("tpu_custom_call") == 1 and "gated_delta_step" in text
    stored = layers * b * h * dk * 256 * 4
    assert mem.alias_size_in_bytes >= stored
    assert mem.temp_size_in_bytes < 1 << 20, mem.temp_size_in_bytes


@pytest.mark.level("unit")
@pytest.mark.parametrize("which", ["decode", "admit_1024"])
def test_hybrid_executables_compile_for_v5e_with_state_beside_kv(
        which, v5e_chip, monkeypatch):
    """The cell's decode and admission executables whole. The ragged decode
    kernel takes the 30 K/V heads stored at 32 with a key block that fits
    its VMEM (30 as they are, or 512 keys a block, it refuses); the grid AND
    the row-state leaves stay aliased in place; the decode chunk's
    temporaries hold no copy of a weight stack or of the state (read here,
    PR 31: 0.012 GB; 0.57 GB while the output gate kept a head axis of
    192 beside its weights); since PR 36 the linear layers' step is the
    kernel ``gated_delta_step`` on the state leaf in place, the leaf riding
    the layer loops as a carry: no operation but the kernel makes an array
    of the leaf's shape, and the temporaries are as they were."""
    from kubetorch_tpu.models import HybridLinearConfig, hybrid_linear
    from kubetorch_tpu.models.rolling import RollingGenerator
    from kubetorch_tpu.parallel.sharding import ShardingRules

    cfg = HybridLinearConfig(max_seq_len=4096)
    b, m, vocab = 16, 4096, cfg.vocab_size

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def specs(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    params = specs(jax.eval_shape(
        lambda: hybrid_linear.init(jax.random.key(0), cfg)))
    cache = specs(jax.eval_shape(
        lambda: hybrid_linear.init_cache(cfg, b, m)))
    assert cache["k"].shape == (4, b, m, 32, 128)
    state = (spec((b, vocab), jnp.float32), spec((b,), jnp.int32),
             spec((b,), jnp.bool_), spec((b,), jnp.int32),
             spec((b,), jnp.bool_))

    def draw(n):
        """A sampler's inputs for ``n`` rows."""
        return (spec((n,), jnp.float32), spec((n,), jnp.float32),
                spec((n, 64), jnp.int32),
                spec((2,), jnp.uint32))

    rules = ShardingRules.default()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if which == "decode":
            exe = jax.jit(
                lambda *a: RollingGenerator._decode_impl(
                    *a, None, top_k=None, top_p=None, n_steps=8, cfg=cfg,
                    rules=rules), donate_argnums=(1, 2, 3, 6)).lower(
                params, cache, *state, *draw(b)).compile()
        else:
            exe = jax.jit(
                lambda *a: RollingGenerator._prefill_impl(
                    *a, None, p_pad=1024, top_k=None, top_p=None, cfg=cfg,
                    rules=rules),
                donate_argnums=(1, 2, 3, 4, 5, 6)).lower(
                params, cache, *state, spec((1, 1024), jnp.int32),
                spec((1,), jnp.int32), spec((1,), jnp.int32),
                *draw(1)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    text, mem = exe.as_text(), exe.memory_analysis()
    cache_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in cache.values())
    assert mem.alias_size_in_bytes >= cache_bytes
    if which == "decode":
        assert "ragged_decode_attention" in text
        assert "gated_delta_prefill" not in text
        assert "gated_delta_step" in text
        made = [line for line in text.splitlines()
                if " = f32[12,16,30,96,192]" in line and not any(
                    op in line for op in ("parameter(", "get-tuple-element(",
                                          "bitcast("))]
        assert made == [], made
        assert mem.temp_size_in_bytes < 0.05e9, mem.temp_size_in_bytes
    else:
        assert "gated_delta_prefill" in text
        assert "admit_flash_attention" in text
        # since PR 41 the scan's kernel builds its factors: XLA makes no
        # float32 array a (head, chunk) pair in the admission
        assert _head_chunk_factors(text) == []


# The fourth decoder (models/window_moe.py, SmallThinker-21BA3B widths: 28
# query heads over 4 kv heads x 128, window 4096 on a grid of 32 x 16384, 64
# experts of 768 top 6) compiled for the same described chip: the ragged
# kernel over a ring with 7 query heads a kv head, the banded admission
# kernel, and the cell's whole decode and 16384-bucket admission executables.
@pytest.mark.parametrize("leaf", ["ring", "plane"])
def test_ragged_kernel_compiles_for_v5e_at_seven_query_heads_a_kv_head(
        leaf, v5e_chip):
    """4 bfloat16 kv heads are two whole packed words a position and go in
    as they lie (no padding: the argument bytes are the leaves'), float32
    queries of 7 heads a kv head are sliced by row; the ring's work list
    carries two more scalars a row."""
    layers, b, hkv, h, d = 6, 32, 4, 28, 128
    m = 4096 if leaf == "ring" else 16384

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def attend(q, k, v, layer, depth):
        items = (decode_attention.ring_plan(depth, 3, m) if leaf == "ring"
                 else decode_attention.plan(depth, m))
        return decode_attention.ragged_decode_attention(
            q, k, v, None, None, layer, items)

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        exe = jax.jit(attend).lower(
            spec((b, h, d), jnp.float32),
            spec((layers, b, m, hkv, d), jnp.bfloat16),
            spec((layers, b, m, hkv, d), jnp.bfloat16),
            spec((), jnp.int32), spec((b,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    text, mem = exe.as_text(), exe.memory_analysis()
    assert text.count("tpu_custom_call") == 1
    planes = 2 * layers * b * m * hkv * d * 2
    assert planes <= mem.argument_size_in_bytes < planes + (1 << 20)
    assert mem.temp_size_in_bytes < (8 << 20)


def test_banded_admission_kernel_compiles_for_v5e_on_the_bands_grid(
        v5e_chip):
    """16384 positions in blocks of 1024 under the window of 4096: the key
    axis of the grid is five blocks, not sixteen."""
    from kubetorch_tpu.ops import flash_attention

    t, h, hkv, d = 16384, 28, 4, 128

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e_chip)

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(
            lambda q, k, v: flash_attention._flash_forward(
                q, k, v, scale=d ** -0.5, causal=True, block_q=1024,
                block_k=1024, interpret=False, with_lse=False,
                name="admit_window_attention", window=4096)[0]).lower(
            spec((1, h, t, d)), spec((1, hkv, t, d)),
            spec((1, hkv, t, d))).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    assert text.count("tpu_custom_call") == 1
    assert "admit_window_attention" in text
    assert flash_attention.band_width(16, 1024, 1024, 4096) == 5


@pytest.mark.parametrize("which", ["decode", "admit_16384", "admit_512"])
def test_window_moe_executables_compile_for_v5e_with_rings_beside_planes(
        which, v5e_chip, monkeypatch):
    """The cell's decode and admission executables whole, at 32 slots x
    16384. Weights 7.94 GB and K/V 3.76 GB (2.15 of planes, 1.61 of rings)
    are arguments, every cache leaf stays aliased in place; the decode
    chunk's temporaries are megabytes (no expert stack is sliced, no ring
    unrolled); the 16384 bucket's stay under 1.5 GB with the experts
    taking the whole sequence in ONE pass (1.407 GB read here, PR 43,
    against 1.371 in pieces of 4096 tokens: the pass sums a token's rows one
    gather a choice, where PR 40's 2.8 GB held a float32 copy of all of
    them), its window layers attend through the banded kernel and its full
    layers through the plain one; a bucket under 1024 takes the einsum
    pair."""
    from kubetorch_tpu.models import WindowMoEConfig, window_moe
    from kubetorch_tpu.models.rolling import RollingGenerator
    from kubetorch_tpu.parallel.sharding import ShardingRules

    cfg = WindowMoEConfig(max_seq_len=16384)
    b, m, vocab = 32, 16384, cfg.vocab_size

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def specs(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    params = specs(jax.eval_shape(
        lambda: window_moe.init(jax.random.key(0), cfg)))
    cache = specs(jax.eval_shape(lambda: window_moe.init_cache(cfg, b, m)))
    assert cache["k"].shape == (2, b, m, 4, 128)
    assert cache["wk"].shape == (6, b, 4096, 4, 128)
    state = (spec((b, vocab), jnp.float32), spec((b,), jnp.int32),
             spec((b,), jnp.bool_), spec((b,), jnp.int32),
             spec((b,), jnp.bool_))

    def draw(n):
        return (spec((n,), jnp.float32), spec((n,), jnp.float32),
                spec((n, 64), jnp.int32), spec((2,), jnp.uint32))

    rules = ShardingRules.default()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if which == "decode":
            exe = jax.jit(
                lambda *a: RollingGenerator._decode_impl(
                    *a, None, top_k=None, top_p=None, n_steps=8, cfg=cfg,
                    rules=rules), donate_argnums=(1, 2, 3, 6)).lower(
                params, cache, *state, *draw(b)).compile()
        else:
            p_pad = int(which.split("_")[1])
            exe = jax.jit(
                lambda *a: RollingGenerator._prefill_impl(
                    *a, None, p_pad=p_pad, top_k=None, top_p=None, cfg=cfg,
                    rules=rules),
                donate_argnums=(1, 2, 3, 4, 5, 6)).lower(
                params, cache, *state, spec((1, p_pad), jnp.int32),
                spec((1,), jnp.int32), spec((1,), jnp.int32),
                *draw(1)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    text, mem = exe.as_text(), exe.memory_analysis()
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    cache_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in cache.values())
    assert 7.9e9 < weights < 8.0e9 and 3.75e9 < cache_bytes < 3.77e9
    assert mem.alias_size_in_bytes >= cache_bytes
    assert "moe_grouped_matmul" in text
    if which == "decode":
        assert "ragged_decode_attention" in text
        assert mem.temp_size_in_bytes < 0.05e9, mem.temp_size_in_bytes
    elif which == "admit_16384":
        assert "admit_window_attention" in text
        assert "admit_flash_attention" in text
        assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
        # what the chip must hold at once fits its 16 GB with room
        assert weights + cache_bytes + mem.temp_size_in_bytes < 14.0e9
    else:
        assert "admit_window_attention" not in text
        assert "admit_flash_attention" not in text
        assert mem.temp_size_in_bytes < 0.1e9, mem.temp_size_in_bytes


# ---- the fifth decoder (models/indexed_moe.py, ops/indexed_attention.py) at
# the published widths of keye-vl-2.0-30b-a3b-bf16-serve, 16 slots x 32768

@pytest.mark.parametrize("kernel", ["index_select", "admit_attention",
                                    "index_choice", "decode_attention"])
def test_indexed_attention_kernels_compile_for_v5e(kernel, v5e_chip):
    """The four kernels of the learned sparse attention at the cell's
    largest shapes: the choice of a 32768-position admission (a VMEM scratch
    of 128 x 32768 order keys, 16 MB, under a raised limit), its attention
    under the int8 mask, a decode step's threshold search over 16 rows of
    32768 + 8 order keys, and its ragged read of 16 rows of bfloat16 K/V (4
    kv heads: two whole words a position) with the choice as a mask."""
    from kubetorch_tpu.ops import indexed_attention

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    T, B, M, L = 32768, 16, 32768, 4
    bf16, i32 = jnp.bfloat16, jnp.int32
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if kernel == "index_select":
            exe = jax.jit(lambda *a: indexed_attention.index_select(
                *a, topk=2048)).lower(
                spec((1, T, 16, 64), bf16), spec((1, T, 64), bf16),
                spec((1, T, 16), jnp.float32), spec((1,), i32)).compile()
        elif kernel == "admit_attention":
            exe = jax.jit(indexed_attention.admit_indexed_attention).lower(
                spec((1, T, 32, 128), bf16), spec((1, T, 4, 128), bf16),
                spec((1, T, 4, 128), bf16), spec((1, T, T), jnp.int8)
            ).compile()
        elif kernel == "index_choice":
            exe = jax.jit(lambda keys: indexed_attention.index_choice(
                keys, topk=2048)).lower(spec((B, M + 8), i32)).compile()
        else:
            exe = jax.jit(
                lambda q, k, v, depth, keys, thr, tie:
                indexed_attention.indexed_decode_attention(
                    q, k, v, jnp.int32(1), decode_attention.plan(depth, M),
                    keys, thr, tie)).lower(
                spec((B, 32, 128), bf16), spec((L, B, M, 4, 128), bf16),
                spec((L, B, M, 4, 128), bf16), spec((B,), i32),
                spec((B, M), i32), spec((B,), i32), spec((B,), i32)
            ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    text = exe.as_text()
    assert "tpu_custom_call" in text
    assert {"index_select": "index_select",
            "admit_attention": "admit_indexed_attention",
            "index_choice": "index_choice",
            "decode_attention": "indexed_decode_attention"}[kernel] in text


@pytest.mark.parametrize("which", ["decode", "admit_32768", "admit_2048"])
def test_indexed_moe_executables_compile_for_v5e_with_the_index_key_beside_kv(
        which, v5e_chip, monkeypatch):
    """The cell's decode and admission executables whole, at 16 slots x
    32768 and 4 layers. Weights 6.25 GB and the cache 4.56 GB (4.29 of K and
    V, 0.27 of index keys) are arguments, every cache leaf stays aliased in
    place; the decode chunk holds the two decode kernels and its
    temporaries stay under 0.05 GB (5 MB read here, PR 42: the scores
    and order keys of 16 rows); the 32768 bucket chooses and attends through
    the two admission kernels and its temporaries (the int8 choice of one
    layer is 1.07 GB; 3.259 GB read here, PR 43, with the experts taking
    the 262144 pairs in ONE pass, against 3.116 in pieces of 4096 tokens
    and 3.385 in two of 16384) leave room on 16 GB; the 2048 bucket, where every
    query sees everything, attends through the plain flash kernel and holds
    no kernel of the index."""
    from kubetorch_tpu.models import IndexedMoEConfig, indexed_moe
    from kubetorch_tpu.models.rolling import RollingGenerator
    from kubetorch_tpu.parallel.sharding import ShardingRules

    cfg = IndexedMoEConfig()
    b, m, vocab = 16, 32768, cfg.vocab_size

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def specs(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    params = specs(jax.eval_shape(
        lambda: indexed_moe.init(jax.random.key(0), cfg)))
    cache = specs(jax.eval_shape(lambda: indexed_moe.init_cache(cfg, b, m)))
    assert cache["k"].shape == (4, b, m, 4, 128)
    assert cache["ik"].shape == (4, b, m, 64)
    state = (spec((b, vocab), jnp.float32), spec((b,), jnp.int32),
             spec((b,), jnp.bool_), spec((b,), jnp.int32),
             spec((b,), jnp.bool_))

    def draw(n):
        return (spec((n,), jnp.float32), spec((n,), jnp.float32),
                spec((n, 64), jnp.int32), spec((2,), jnp.uint32))

    rules = ShardingRules.default()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if which == "decode":
            exe = jax.jit(
                lambda *a: RollingGenerator._decode_impl(
                    *a, None, top_k=None, top_p=None, n_steps=8, cfg=cfg,
                    rules=rules), donate_argnums=(1, 2, 3, 6)).lower(
                params, cache, *state, *draw(b)).compile()
        else:
            p_pad = int(which.split("_")[1])
            exe = jax.jit(
                lambda *a: RollingGenerator._prefill_impl(
                    *a, None, p_pad=p_pad, top_k=None, top_p=None, cfg=cfg,
                    rules=rules),
                donate_argnums=(1, 2, 3, 4, 5, 6)).lower(
                params, cache, *state, spec((1, p_pad), jnp.int32),
                spec((1,), jnp.int32), spec((1,), jnp.int32),
                *draw(1)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    text, mem = exe.as_text(), exe.memory_analysis()
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    cache_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in cache.values())
    assert 6.2e9 < weights < 6.3e9 and 4.55e9 < cache_bytes < 4.57e9
    assert mem.alias_size_in_bytes >= cache_bytes
    assert "moe_grouped_matmul" in text
    if which == "decode":
        assert "indexed_decode_attention" in text
        assert "index_choice" in text
        assert "index_select" not in text
        assert mem.temp_size_in_bytes < 0.05e9, mem.temp_size_in_bytes
    elif which == "admit_32768":
        assert "index_select" in text
        assert "admit_indexed_attention" in text
        assert "admit_flash_attention" not in text
        assert mem.temp_size_in_bytes < 3.3e9, mem.temp_size_in_bytes
        # what the chip must hold at once fits its 16 GB with room
        assert weights + cache_bytes + mem.temp_size_in_bytes < 15.0e9
    else:
        assert "admit_flash_attention" in text
        assert "index_select" not in text
        assert "admit_indexed_attention" not in text
        assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes


# The sixth decoder (models/hybrid_latent_moe.py, Ling-3.0-flash widths: 32
# KDA heads of 128 x 128 beside one latent plane of 512 + 64, 128 of 512
# experts of 768 held, 32 slots x 32768) compiled for the same described
# chip: its two kernels (ops/kda.py) and the cell's whole decode and
# 32768-bucket admission executables.
@pytest.mark.level("unit")
@pytest.mark.parametrize("kernel", ["kda_prefill", "kda_step"])
def test_kda_kernels_compile_for_v5e_at_the_cells_shapes(kernel, v5e_chip,
                                                         monkeypatch):
    """``kda_prefill`` at the published head shape on a segment of the top
    bucket (8192 tokens) and on the smallest bucket: ``q | k | v`` go in as
    the mixer holds them (no transpose beside the call: the temporaries are
    the running sums and their scan's buffers). ``kda_step`` on a row's ``(32, 128,
    128)`` float32 block of the stacked state leaf (6 layers x 32 rows, 0.40
    GB), the leaf the call's input and output in place."""
    from kubetorch_tpu.ops import kda

    layers, b, h, dk, dv = 6, 32, 32, 128, 128

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def step(q, k, v, a, beta, states, layer, live):
        return kda.step_rows(q, k, v, a, beta, states, layer,
                             kda.step_plan(live))

    bf16 = jnp.bfloat16
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if kernel == "kda_step":
            exe = jax.jit(step, donate_argnums=(5,)).lower(
                spec((b, h, dk), bf16), spec((b, h, dk), bf16),
                spec((b, h, dv), bf16), spec((b, h, dk)), spec((b, h)),
                spec((layers, b, h, dk, dv)), spec((), jnp.int32),
                spec((b,), jnp.bool_)).compile()
            text, mem = exe.as_text(), exe.memory_analysis()
            assert text.count("tpu_custom_call") == 1 and kernel in text
            assert mem.alias_size_in_bytes >= layers * b * h * dk * dv * 4
            assert mem.temp_size_in_bytes < 1 << 20, mem.temp_size_in_bytes
            return
        for t in (256, 8192):
            exe = jax.jit(kda.prefill_scan).lower(
                spec((1, t, h, dk), bf16), spec((1, t, h, dk), bf16),
                spec((1, t, h, dv), bf16), spec((1, t, h, dk)),
                spec((1, t, h)), spec((1, h, dk, dv))).compile()
            text = exe.as_text()
            assert text.count("tpu_custom_call") == 1 and kernel in text, t
            # the running sums (float32, a channel a token) and the
            # cumulative sum's own buffers (0.369 GB at 8192, read here)
            assert exe.memory_analysis().temp_size_in_bytes < 3 * (
                t * h * dk * 4) + (1 << 20), t
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.mark.level("unit")
@pytest.mark.parametrize("which", ["decode", "admit_32768"])
def test_hybrid_latent_moe_executables_compile_for_v5e_with_state_beside_a_latent_plane(
        which, v5e_chip, monkeypatch):
    """The cell's decode and top-bucket admission executables whole, from
    the configuration file: weights 10.48 GB (128 of 512 experts a layer, a
    quarter of the vocabulary) + latent plane, state and tails 1.76 GB as
    arguments, the cache aliased in place. Decode takes ``kda_step`` on the
    state leaf, the ragged latent kernel and the grouped product, and makes
    nothing of the leaf's shape beside it; the 32768 bucket takes
    ``kda_prefill`` in segments of 8192 tokens, the latent flash kernel and
    the experts in pieces, and what the chip must hold at once fits (read
    here, PR 47: temporaries 0.17 GB and 2.81 GB)."""
    import json
    from pathlib import Path

    from benchmark import families
    from kubetorch_tpu.models import hybrid_latent_moe
    from kubetorch_tpu.models.rolling import RollingGenerator
    from kubetorch_tpu.parallel.sharding import ShardingRules

    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                         / "configs" / "ling-3.0-flash-bf16-serve.json"
                         ).read_text())
    b, m = 32, 32768
    cfg = families.load(config, "serve").program_config(
        config, "serve", {"max_len": m})
    vocab = cfg.vocab_size

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def specs(tree):
        return jax.tree.map(lambda x: spec(x.shape, x.dtype), tree)

    params = specs(jax.eval_shape(
        lambda: hybrid_latent_moe.init(jax.random.key(0), cfg)))
    cache = specs(jax.eval_shape(
        lambda: hybrid_latent_moe.init_cache(cfg, b, m)))
    assert cache["ckr"].shape == (1, b, m, 640)
    assert cache["state"].shape == (6, b, 32, 128, 128)
    state = (spec((b, vocab), jnp.float32), spec((b,), jnp.int32),
             spec((b,), jnp.bool_), spec((b,), jnp.int32),
             spec((b,), jnp.bool_))

    def draw(n):
        return (spec((n,), jnp.float32), spec((n,), jnp.float32),
                spec((n, 64), jnp.int32), spec((2,), jnp.uint32))

    rules = ShardingRules.default()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        if which == "decode":
            exe = jax.jit(
                lambda *a: RollingGenerator._decode_impl(
                    *a, None, top_k=None, top_p=None, n_steps=8, cfg=cfg,
                    rules=rules), donate_argnums=(1, 2, 3, 6)).lower(
                params, cache, *state, *draw(b)).compile()
        else:
            exe = jax.jit(
                lambda *a: RollingGenerator._prefill_impl(
                    *a, None, p_pad=m, top_k=None, top_p=None, cfg=cfg,
                    rules=rules),
                donate_argnums=(1, 2, 3, 4, 5, 6)).lower(
                params, cache, *state, spec((1, m), jnp.int32),
                spec((1,), jnp.int32), spec((1,), jnp.int32),
                *draw(1)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
    text, mem = exe.as_text(), exe.memory_analysis()
    weights = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                  for x in jax.tree.leaves(params))
    cache_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in cache.values())
    assert 10.4e9 < weights < 10.6e9 and 1.75e9 < cache_bytes < 1.77e9
    assert mem.alias_size_in_bytes >= cache_bytes
    assert "moe_grouped_matmul" in text
    if which == "decode":
        assert "kda_step" in text and "kda_prefill" not in text
        assert "latent_decode_attention" in text
        made = [line for line in text.splitlines()
                if " = f32[6,32,32,128,128]" in line and not any(
                    op in line for op in ("parameter(", "get-tuple-element(",
                                          "bitcast("))]
        assert made == [], made
        assert mem.temp_size_in_bytes < 0.25e9, mem.temp_size_in_bytes
    else:
        assert "kda_prefill" in text and "kda_step" not in text
        assert "latent_prefill_attention" in text
        assert mem.temp_size_in_bytes < 3.0e9, mem.temp_size_in_bytes
        # what the chip must hold at once fits its 16.9 GB with room
        assert weights + cache_bytes + mem.temp_size_in_bytes < 15.5e9
