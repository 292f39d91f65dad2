"""The two cache writes of ``ops/grid_write.py`` against the selects over
whole planes they replaced, which live on here as the oracles: the
once-a-chunk merge (through the decoders' ``merge_chunk_into_grid``) against
PR 28's one-hot select, an admission's landing (through
``RollingGenerator._finish_admit``) against PR 32's gather + masked select.
Bit-equal on every leaf, and built from slice updates only — nothing the
size of a layer's ``[B, M]`` plane is computed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetorch_tpu.models import (HybridLinearConfig, LatentMoEConfig,
                                  LlamaConfig, hybrid_linear, latent_moe,
                                  llama)
from kubetorch_tpu.models.decoder import decoder_for, grid_dims, row_leaves
from kubetorch_tpu.models.rolling import RollingGenerator
from kubetorch_tpu.ops import grid_write

L, B, M = 3, 8, 160
KINDS = ("int8", "bf16", "f32", "latent")


# ------------------------------------------------------------- the oracle
def _select_leaf(grid, cols, start, count):
    """The select as it stood until PR 28: a one-hot einsum picks each grid
    position's chunk column, over all ``M`` positions of a layer, and a
    ``where`` rewrites the whole layer. ``grid`` [L, B, M, ...], ``cols``
    [L, B, K, ...] of the grid's dtype."""
    m, k = grid.shape[2], cols.shape[2]
    idx = jnp.arange(m)[None, :] - start[:, None]                  # [B, M]
    inwin = (idx >= 0) & (idx < count[:, None])
    wide = jnp.float32 if grid.dtype == jnp.int8 else grid.dtype
    onehot = ((jnp.arange(k)[None, None, :] == idx[:, :, None])
              & inwin[:, :, None]).astype(wide)                    # [B, M, K]

    def layer(g, c):
        new = jnp.einsum("bmk,bkx->bmx", onehot,
                         c.reshape(c.shape[:2] + (-1,)).astype(wide))
        keep = inwin.reshape(inwin.shape + (1,) * (g.ndim - 2))
        return jnp.where(keep, new.reshape(g.shape).astype(g.dtype), g)

    return jnp.stack([layer(g, c) for g, c in zip(grid, cols)])


def _select_dense(cache, chunk, start, count):
    """``llama.merge_chunk_into_grid`` before PR 28."""
    if "ks" in cache:
        qk, sk = llama._kv_quantize(chunk["k"])
        qv, sv = llama._kv_quantize(chunk["v"])
        cols = {"k": qk, "v": qv, "ks": sk, "vs": sv}
    else:
        cols = {n: chunk[n].astype(cache[n].dtype) for n in cache}
    return {n: _select_leaf(cache[n], cols[n], start, count) for n in cache}


def _select_latent(cache, chunk, start, count):
    """``latent_moe.merge_chunk_into_grid`` before PR 28."""
    return {n: _select_leaf(cache[n], chunk[n].astype(cache[n].dtype),
                            start, count) for n in cache}


# ------------------------------------------------------------------ inputs
def _grid(kind, k, seed=0):
    """(merge, oracle, cache, chunk) of one grid kind at toy widths."""
    rng = np.random.default_rng(seed)
    if kind == "latent":
        cache = {"ckr": jnp.asarray(rng.standard_normal((L, B, M, 24)),
                                    jnp.bfloat16)}
        chunk = {"ckr": jnp.asarray(rng.standard_normal((L, B, k, 24)),
                                    jnp.bfloat16)}
        return (latent_moe.merge_chunk_into_grid, _select_latent, cache,
                chunk)
    vec = (L, B, M, 2, 16)
    if kind == "int8":
        cache = {"k": jnp.asarray(rng.integers(-127, 128, vec), jnp.int8),
                 "v": jnp.asarray(rng.integers(-127, 128, vec), jnp.int8),
                 "ks": jnp.asarray(rng.random(vec[:4]) + 0.1, jnp.float32),
                 "vs": jnp.asarray(rng.random(vec[:4]) + 0.1, jnp.float32)}
        cdt = jnp.bfloat16                  # the chunk of an int8 grid
    else:
        cdt = jnp.bfloat16 if kind == "bf16" else jnp.float32
        cache = {"k": jnp.asarray(rng.standard_normal(vec), cdt),
                 "v": jnp.asarray(rng.standard_normal(vec), cdt)}
    chunk = {n: jnp.asarray(rng.standard_normal((L, B, k, 2, 16)), cdt)
             for n in ("k", "v")}
    return llama.merge_chunk_into_grid, _select_dense, cache, chunk


def _cases(k):
    """name -> (start [B], count [B]) for a chunk of ``k`` columns."""
    rng = np.random.default_rng(k)
    mixed = np.array([0, 1, k - 1, k, k, 0, 1, k - 1])
    return {
        # every count of interest within one batch, at random depths
        "mixed_counts": (rng.integers(0, M - k, B), mixed),
        # an inactive row between active ones, neighbours' depths equal
        "inactive_between": (np.full(B, 17), np.array([k, 0, k, 0, 0, k, 0,
                                                        k])),
        # the last column lands on the grid's last position
        "ends_at_M": (np.array([M - k, M - 1, M - k + 1, 0, 5, M - 2,
                                M - k, 9]),
                      np.array([k, 1, k - 1, k, 0, 2, k - 1, 1])),
        # the window would pass M: the columns past it are dropped, what
        # fits lands where it belongs (no shift back) — and a row wholly
        # outside lands nothing
        "passes_M": (np.array([M - 3, M - 1, M, M + 5, M - k + 1, 3, M - 2,
                               M + k]),
                     np.array([k, k, k, 3, k, k, 0, 1])),
        "all_idle": (rng.integers(0, M - k, B), np.zeros(B, int)),
        "all_full": (rng.integers(0, M - k, B), np.full(B, k)),
    }


CASES = tuple(_cases(8))


@pytest.mark.level("unit")
@pytest.mark.parametrize("k", [8, 128])          # decode K, prefill-extend C
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", KINDS)
def test_merge_is_the_select_bit_for_bit(kind, case, k):
    merge, oracle, cache, chunk = _grid(kind, k)
    start, count = (jnp.asarray(x, jnp.int32) for x in _cases(k)[case])
    # both compiled: eager and compiled division round one value in 10^5
    # of a chunk's quantisation differently, which is not the merge's
    want = jax.jit(oracle)(cache, chunk, start, count)
    got = jax.jit(merge)(cache, chunk, start, count)
    assert set(got) == set(cache)
    for name in cache:
        assert got[name].dtype == cache[name].dtype
        assert got[name].shape == cache[name].shape
        np.testing.assert_array_equal(
            np.asarray(got[name].astype(jnp.float32)),
            np.asarray(want[name].astype(jnp.float32)), err_msg=name)
    # and, without the oracle: outside [start, start + count) of each row
    # the grid is what it was
    pos = np.arange(M)[None, :]
    s, n = np.asarray(start)[:, None], np.asarray(count)[:, None]
    outside = ~((pos >= s) & (pos < s + n))                       # [B, M]
    for name in cache:
        old = np.asarray(cache[name].astype(jnp.float32))
        new = np.asarray(got[name].astype(jnp.float32))
        np.testing.assert_array_equal(new[:, outside], old[:, outside])


@pytest.mark.level("unit")
def test_chunk_wider_than_the_grid_lands_what_fits():
    """``K > M`` (a toy grid under a wide prefill chunk): the columns that
    have a position land, the rest drop."""
    grid = {"x": jnp.zeros((2, 3, 4, 5), jnp.float32)}
    cols = {"x": jnp.arange(2 * 3 * 6 * 5, dtype=jnp.float32
                            ).reshape(2, 3, 6, 5) + 1}
    start = jnp.asarray([0, 2, 1], jnp.int32)
    count = jnp.asarray([6, 6, 0], jnp.int32)
    got = grid_write.write_columns(grid, cols, start, count)["x"]
    want = _select_leaf(grid["x"], cols["x"], start, count)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(got[:, 1, :2].sum()) == 0 and float(got[:, 2].sum()) == 0


# ---------------------------------------------------------- the mechanism
def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.level("unit")
@pytest.mark.parametrize("kind", KINDS)
def test_nothing_the_size_of_a_plane_is_computed(kind):
    """The traced merge holds no operation whose result is a whole
    ``[B, M, ...]`` layer of a leaf (or a stack of them) except the
    in-place slice updates and the loop that carries the leaves: no select,
    no einsum, no gather or scatter over the grid."""
    merge, _, cache, chunk = _grid(kind, 8)
    start = jnp.zeros((B,), jnp.int32)
    jaxpr = jax.make_jaxpr(merge)(cache, chunk, start, start)
    planes = {v.shape for v in cache.values()} | {
        v.shape[1:] for v in cache.values()}
    grid_sized = [e.primitive.name for e in _eqns(jaxpr.jaxpr)
                  if any(getattr(v.aval, "shape", None) in planes
                         for v in e.outvars)]
    assert set(grid_sized) == {"dynamic_update_slice", "while"}, grid_sized
    assert grid_sized.count("dynamic_update_slice") == len(cache)
    names = {e.primitive.name for e in _eqns(jaxpr.jaxpr)}
    assert not names & {"gather", "scatter", "scatter-add", "dot_general"}
    # (what the v5e's compiler makes of it at both serving cells' shapes —
    # temporaries of a window, the grid aliased in place — is held by
    # tests/test_decode_attention.py, where the TPU compiles live)


# -------------------------------------------- through the three executables
def _cfg():
    return LlamaConfig(vocab_size=256, embed_dim=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, head_dim=16, mlp_dim=128, remat=False,
                       dtype="float32", param_dtype="float32",
                       max_seq_len=128)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return llama.init(jax.random.key(0), cfg), cfg


def _run(params, cfg, **engine):
    """Tokens by request, the final grid and the counters of one toy run:
    three prompts over two chunks' worth of decode, one of them long
    enough to take the chunked prefill where that is on."""
    eng = RollingGenerator(params, cfg, max_slots=4, max_len=128, **engine)
    prompts = [[1, 2, 3, 4, 5], [(5 * i) % 200 + 3 for i in range(40)],
               [9, 8, 7]]
    rids = [eng.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, (12, 9, 5))]
    out = eng.run()
    grid = {n: np.asarray(v.astype(jnp.float32))
            for n, v in eng.cache.items()}
    return [out[r] for r in rids], grid, eng.stats()


ENGINES = {
    "decode": dict(steps_per_call=4),
    "decode_int8": dict(steps_per_call=4, kv_dtype="int8"),
    "prefill_extend": dict(steps_per_call=4, prefill_chunk=16),
    "prefill_extend_int8": dict(steps_per_call=4, prefill_chunk=16,
                                kv_dtype="int8"),
    "decode_spec": dict(steps_per_call=2, spec_k=4),
    "decode_spec_int8": dict(steps_per_call=2, spec_k=4, kv_dtype="int8"),
}


@pytest.mark.level("minimal")
@pytest.mark.parametrize("path", list(ENGINES))
def test_engine_tokens_and_grid_equal_the_selects(model, path, monkeypatch):
    """One toy engine run through ``_decode_impl``, ``_prefill_extend_impl``
    and ``_decode_spec_impl`` each: the same tokens and the same grid, bit
    for bit, as with the parent's select in the merge's place; and the
    counters say that only the landing rows' windows were written."""
    params, cfg = model
    toks, grid, stats = _run(params, cfg, **ENGINES[path])
    monkeypatch.setattr(llama, "merge_chunk_into_grid", _select_dense)
    want_toks, want_grid, _ = _run(params, cfg, **ENGINES[path])
    assert toks == want_toks
    for name in grid:
        np.testing.assert_array_equal(grid[name], want_grid[name],
                                      err_msg=name)
    new, written = (stats["merge_positions_new"],
                    stats["merge_positions_written"])
    assert 0 < new <= written
    if path.startswith("decode_spec"):
        # a round lands 1..k of a k-column window, and nothing for a row
        # that sits it out
        assert written <= 4 * new
    elif path.startswith("decode"):
        assert written == new              # 4 of 4 columns, every landing row
        assert new % 4 == 0


@pytest.mark.level("minimal")
def test_counters_count_nothing_for_rows_that_land_nothing(model):
    """``merge_positions_*`` from the host's mirror: a decode chunk counts
    its decoding rows x steps and nothing for free rows; a prefill chunk
    counts the prompt's tokens against a window of ``prefill_chunk``."""
    params, cfg = model
    eng = RollingGenerator(params, cfg, max_slots=4, max_len=128,
                           steps_per_call=4)
    assert eng.stats()["merge_positions_new"] == 0
    eng.submit([1, 2, 3], max_new_tokens=8)
    eng.submit([4, 5, 6, 7], max_new_tokens=8)
    eng.step()                              # two of four rows decode
    s = eng.stats()
    assert s["merge_positions_new"] == s["merge_positions_written"] == 2 * 4
    eng.step()
    assert eng.stats()["merge_positions_written"] == 2 * 2 * 4
    assert grid_write.positions_written([0, 3, 0, 8], 8) == 16
    assert grid_write.positions_written(np.zeros(5, int), 8) == 0

    chunked = RollingGenerator(params, cfg, max_slots=4, max_len=128,
                               steps_per_call=4, prefill_chunk=16)
    chunked.submit(list(range(1, 41)), max_new_tokens=1)   # 16 + 16 + 8
    while chunked.prefilling_rows or chunked.queued:
        chunked.prefill_step() if chunked.prefilling_rows else chunked.step()
    s = chunked.stats()
    assert s["merge_positions_new"] >= 40
    assert s["merge_positions_written"] >= 3 * 16


# =========================================================================
# An admission's landing: ``write_rows`` against the gather + masked select
# ``_finish_admit`` held until PR 32.
def _select_rows(cache, own, slots, rows=frozenset()):
    """The splice as it stood until PR 32: every grid row gathers the
    own-cache row that names it (``plane_o[:, sel]``, ``[L, B, M_own, ...]``)
    and a ``where`` over all ``B`` rows keeps the others; ``rows`` names the
    row-state leaves."""
    B = grid_dims(cache, rows)[0]
    M_own = grid_dims(own, rows)[1]
    onehot = slots[None, :] == jnp.arange(B)[:, None]       # [B, N]
    sel = jnp.argmax(onehot, axis=1)                        # [B]
    any_valid = onehot.any(axis=1)

    def splice(kk):
        plane_c, plane_o = cache[kk], own[kk]
        v = any_valid.reshape((1, B) + (1,) * (plane_c.ndim - 2))
        if kk in rows:
            return jnp.where(v, plane_o[:, sel], plane_c)
        return jax.lax.dynamic_update_slice_in_dim(
            plane_c, jnp.where(v, plane_o[:, sel], plane_c[:, :, :M_own]),
            0, axis=2)

    return {kk: splice(kk) for kk in cache}


ROW_KINDS = ("bf16", "int8", "latent", "hybrid")


def _fill(tree, seed):
    """The same leaves, every element drawn (int8 over its whole range)."""
    rng = np.random.default_rng(seed)
    return {n: jnp.asarray(
        rng.integers(-127, 128, x.shape) if x.dtype == jnp.int8
        else rng.standard_normal(x.shape), x.dtype) for n, x in tree.items()}


def _admission(kind, n, span, seed=0):
    """(grid, own cache of ``n`` rows x ``span`` positions, row-state leaf
    names) of one decoder's leaf kinds at toy widths."""
    if kind == "hybrid":        # K/V of 1 layer beside row state of 5
        cfg = HybridLinearConfig.tiny()
        rows = row_leaves(decoder_for(cfg), cfg)
        return (_fill(hybrid_linear.init_cache(cfg, B, M), seed),
                _fill(hybrid_linear.init_cache(cfg, n, span), seed + 1), rows)
    if kind == "latent":
        cfg = LatentMoEConfig.tiny()
        grid, own = (latent_moe.init_cache(cfg, b, m, dtype=jnp.bfloat16)
                     for b, m in ((B, M), (n, span)))
    else:
        cfg = _cfg()
        grid, own = (llama.init_cache(
            cfg, b, m, dtype=jnp.bfloat16, quantized=kind == "int8")
            for b, m in ((B, M), (n, span)))
    return _fill(grid, seed), _fill(own, seed + 1), frozenset()


# name -> the slots of an admission's (padded) width; ``B`` is a dummy row
SLOTS = {
    "one_first": [0], "one_last": [B - 1], "one_dummy": [B],
    "three": [B - 1, 0, 3], "three_one_dummy": [2, B, B - 1],
    "three_dummy_first": [B, 5, B], "three_all_dummy": [B, B, B],
}


@pytest.mark.level("unit")
@pytest.mark.parametrize("span", [64, M], ids=["part", "whole"])
@pytest.mark.parametrize("case", list(SLOTS))
@pytest.mark.parametrize("kind", ROW_KINDS)
def test_row_write_is_the_select_bit_for_bit(kind, case, span):
    slots = jnp.asarray(SLOTS[case], jnp.int32)
    grid, own, rows = _admission(kind, len(SLOTS[case]), span)
    want = jax.jit(lambda *a: _select_rows(*a, rows))(grid, own, slots)
    got = jax.jit(grid_write.write_rows)(grid, own, slots)
    assert set(got) == set(grid)
    for name in grid:
        assert got[name].dtype == grid[name].dtype
        np.testing.assert_array_equal(
            np.asarray(got[name].astype(jnp.float32)),
            np.asarray(want[name].astype(jnp.float32)), err_msg=name)
    # and, without the oracle: an admitted row holds its own-cache row from
    # the front, a dummy lands nowhere, everything else is what it was
    for name in grid:
        old = np.array(grid[name].astype(jnp.float32))
        new = np.asarray(got[name].astype(jnp.float32))
        src = np.asarray(own[name].astype(jnp.float32))
        for n, b in enumerate(SLOTS[case]):
            if b == B:
                continue
            if name in rows:
                old[:, b] = src[:, n]
            else:
                old[:, b, :span] = src[:, n]
        np.testing.assert_array_equal(new, old, err_msg=name)


@pytest.mark.level("unit")
@pytest.mark.parametrize("n", [1, 3])
@pytest.mark.parametrize("kind", ROW_KINDS)
def test_an_admission_computes_nothing_the_size_of_a_plane(kind, n):
    """The traced ``_finish_admit`` holds no operation whose result is a
    whole grid leaf (or a layer of one) except the in-place slice updates
    and the loop that carries the leaves, and no gather, scatter or select
    that reads or makes a leaf of the grid or of the own cache: the scatters
    left are the ``[B, V]`` and ``[B]`` per-slot sets (the rows' first
    tokens, drawn by the admission, among them)."""
    grid, own, _ = _admission(kind, n, 64)
    vocab = 32
    jaxpr = jax.make_jaxpr(RollingGenerator._finish_admit)(
        grid, own, jnp.zeros((n, vocab)), jnp.zeros((B, vocab)),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool),
        jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), jnp.int32))
    planes = {v.shape for v in grid.values()} | {
        v.shape[1:] for v in grid.values()}
    eqns = list(_eqns(jaxpr.jaxpr))
    grid_sized = [e.primitive.name for e in eqns
                  if any(getattr(v.aval, "shape", None) in planes
                         for v in e.outvars)]
    assert set(grid_sized) == {"dynamic_update_slice", "while"}, grid_sized
    assert grid_sized.count("dynamic_update_slice") == len(grid)
    leaves = planes | {v.shape for v in own.values()}
    over_a_leaf = [e.primitive.name for e in eqns
                   if e.primitive.name.split("-")[0] in (
                       "gather", "scatter", "select_n", "dot_general")
                   and any(getattr(v.aval, "shape", None) in leaves
                           for v in (*e.invars, *e.outvars))]
    assert not over_a_leaf, over_a_leaf
    # (what the v5e's compiler makes of it inside the cells' whole admission
    # executables is held by tests/test_decode_attention.py)


def _toy(decoder):
    """(params, cfg, row-state leaf names) of a decoder at toy widths."""
    if decoder == "dense":
        cfg = _cfg()
        return llama.init(jax.random.key(0), cfg), cfg, frozenset()
    if decoder == "latent":
        cfg = LatentMoEConfig.tiny()
        return latent_moe.init(jax.random.key(1), cfg), cfg, frozenset()
    cfg = HybridLinearConfig.tiny()
    return (hybrid_linear.init(jax.random.key(2), cfg), cfg,
            row_leaves(decoder_for(cfg), cfg))


def _admit_run(params, cfg, prefix=0, **engine):
    """Tokens by request, the final grid and the counters of one toy run
    whose admissions are bucketed: two prompts of one bucket go in one call
    of the full width (two dummy rows beside them), the third alone; with
    ``prefix``, behind a shared prefix of that many tokens."""
    eng = RollingGenerator(params, cfg, max_slots=4, max_len=128,
                           steps_per_call=4, **engine)
    pid = (eng.register_prefix([(7 * i) % 190 + 2 for i in range(prefix)])
           if prefix else None)
    prompts = [[1, 2, 3, 4, 5], [(5 * i) % 200 + 3 for i in range(40)],
               [9, 8, 7]]
    rids = [eng.submit(p, max_new_tokens=n, prefix_id=pid)
            for p, n in zip(prompts, (12, 9, 5))]
    out = eng.run()
    grid = {n: np.asarray(v.astype(jnp.float32))
            for n, v in eng.cache.items()}
    return [out[r] for r in rids], grid, eng.stats()


# (decoder, shared prefix's tokens, engine): a prefix of 20 makes an own
# cache of 32 + the bucket, one of 70 fills its 128 bucket, so the own cache
# is cut at the grid's end (M_own == M)
ADMISSIONS = {
    "bucketed": ("dense", 0, {}),
    "bucketed_int8": ("dense", 0, dict(kv_dtype="int8")),
    "prefixed": ("dense", 20, {}),
    "prefixed_int8": ("dense", 20, dict(kv_dtype="int8")),
    "prefixed_to_the_end": ("dense", 70, {}),
    "bucketed_latent": ("latent", 0, {}),
    "bucketed_hybrid": ("hybrid", 0, {}),
}


@pytest.mark.level("minimal")
@pytest.mark.parametrize("path", list(ADMISSIONS))
def test_engine_tokens_and_grid_equal_the_row_selects(path, monkeypatch):
    """One toy engine run through ``_prefill_impl`` or ``_prefill_px_impl``:
    the same tokens and the same grid, bit for bit, as with the parent's
    gather + select in the row writer's place; and the counters say that
    only the admitted rows' spans were written."""
    decoder, prefix, engine = ADMISSIONS[path]
    params, cfg, rows = _toy(decoder)
    toks, grid, stats = _admit_run(params, cfg, prefix, **engine)
    monkeypatch.setattr(
        grid_write, "write_rows",
        lambda cache, own, slots: _select_rows(cache, own, slots, rows))
    want_toks, want_grid, _ = _admit_run(params, cfg, prefix, **engine)
    assert toks == want_toks
    for name in grid:
        np.testing.assert_array_equal(grid[name], want_grid[name],
                                      err_msg=name)
    new, written = (stats["admit_positions_new"],
                    stats["admit_positions_written"])
    # three rows: two of the 16 bucket together, one of the 64 bucket, each
    # behind the prefix's bucket and cut at the grid's 128
    behind = 0 if not prefix else 32 if prefix <= 32 else 128
    assert new == written == 2 * min(behind + 16, 128) + min(behind + 64, 128)


@pytest.mark.level("minimal")
def test_admit_counters_count_nothing_for_a_dummy_row():
    """``admit_positions_*`` from the host: an admission of one counts its
    bucket once, an admission of two padded to the full width counts two
    (the dummy rows land nothing and count in neither)."""
    assert grid_write.row_positions_written([3], 4, 64) == 64
    assert grid_write.row_positions_written([1, 4, 0, 4], 4, 16) == 32
    assert grid_write.row_positions_written([4, 4], 4, 16) == 0
    params, cfg, _ = _toy("dense")
    eng = RollingGenerator(params, cfg, max_slots=4, max_len=128,
                           steps_per_call=4)
    assert eng.stats()["admit_positions_new"] == 0
    eng.submit(list(range(1, 41)), max_new_tokens=4)
    eng.step()
    s = eng.stats()
    assert s["admit_positions_new"] == s["admit_positions_written"] == 64
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.submit([4, 5, 6, 7], max_new_tokens=4)
    eng.step()                  # one call of width 4: two rows, two dummies
    s = eng.stats()
    assert s["admit_positions_new"] == s["admit_positions_written"] == 96
    # a chunked prefill lands through the merge, not through an admission
    chunked = RollingGenerator(params, cfg, max_slots=4, max_len=128,
                               steps_per_call=4, prefill_chunk=16)
    chunked.submit(list(range(1, 41)), max_new_tokens=1)
    chunked.run()
    assert chunked.stats()["admit_positions_new"] == 0
