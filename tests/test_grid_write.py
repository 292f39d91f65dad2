"""The once-a-chunk cache write (``ops/grid_write.py``, through both
decoders' ``merge_chunk_into_grid``) against the one-hot select it replaced
in PR 28, which lives on here as the oracle: bit-equal on every leaf, and
built from slice updates only — nothing the size of a layer's ``[B, M]``
plane is computed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetorch_tpu.models import LlamaConfig, latent_moe, llama
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
