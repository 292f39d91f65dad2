"""``models/decoder.py`` after its widening (leaves by kind; positional and
row-state leaves): the two decoders that were there declare what they
declared, keep no row state, and serve, export and import as they did. And
the rule between decoders: none imports another, ``ops/`` imports no model,
and the defaults the six classes inherit are what each returned itself."""

import ast
import importlib
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetorch_tpu.models import (HybridLatentMoEConfig, HybridLinearConfig,
                                  IndexedMoEConfig, LatentMoEConfig,
                                  LlamaConfig, WindowMoEConfig, latent_moe,
                                  llama)
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


# ------------------------------------------- a decoder is a file of its own
REPO = Path(__file__).resolve().parents[1]
PACKAGE = "kubetorch_tpu.models"
# module -> (its toy configuration, the kernels under ``ops/`` it loads that
# the dense decoder's path does not: the sixth shares two with its siblings)
DECODERS = {
    "llama": (LlamaConfig.tiny, ()),
    "latent_moe": (LatentMoEConfig.tiny, ("latent_attention",)),
    "hybrid_linear": (HybridLinearConfig.tiny, ("gated_delta",)),
    "window_moe": (WindowMoEConfig.tiny, ()),
    "indexed_moe": (IndexedMoEConfig.tiny, ("indexed_attention",)),
    "hybrid_latent_moe": (HybridLatentMoEConfig.tiny,
                          ("kda", "latent_attention", "gated_delta")),
}


def _imported(path: Path, package: str):
    """Every module a file imports, at module level or inside a function,
    by its full name (``from a.b import c`` gives ``a.b`` and ``a.b.c``)."""
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:      # relative: ``.`` is ``package`` itself
                parts = package.split(".")
                up = parts[:len(parts) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            names.add(base)
            names.update(f"{base}.{alias.name}" for alias in node.names)
    return names


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_a_decoder_imports_no_other_decoder(name):
    """What two decoders share lives in ``models/decoder.py``,
    ``models/experts.py`` or ``ops/``: a decoder's file names no sibling in
    an import, not even inside a function."""
    seen = _imported(REPO / "kubetorch_tpu" / "models" / f"{name}.py",
                     PACKAGE)
    others = {f"{PACKAGE}.{other}" for other in DECODERS if other != name}
    assert not seen & others, sorted(seen & others)


def test_ops_import_nothing_from_models():
    files = sorted((REPO / "kubetorch_tpu" / "ops").glob("*.py"))
    assert len(files) > 10
    for path in files:
        bad = [m for m in _imported(path, "kubetorch_tpu.ops")
               if m == PACKAGE or m.startswith(PACKAGE + ".")]
        assert not bad, (path.name, bad)


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_a_decoder_loads_no_other_decoder(name):
    """A decoder enters only through ``decoder_for`` and the lazy module
    attribute: importing the engine's path and ONE decoder loads no other
    decoder's module and none of the kernels another uses and it does not
    (``llama`` excepted, which ``models/__init__.py`` imports)."""
    others = [other for other in DECODERS if other not in (name, "llama")]
    banned = [f"{PACKAGE}.{other}" for other in others] + [
        f"kubetorch_tpu.ops.{kernel}" for other in others
        for kernel in DECODERS[other][1] if kernel not in DECODERS[name][1]]
    code = ("import sys; import kubetorch_tpu.models.rolling, "
            f"kubetorch_tpu.serving.engine, {PACKAGE}.{name}; "
            f"bad = [m for m in {banned!r} if m in sys.modules]; "
            "print(bad); sys.exit(1 if bad else 0)")
    out = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                         capture_output=True, text=True,
                         env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                              "PYTHONPATH": str(REPO)})
    assert out.returncode == 0, out.stdout + out.stderr


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_the_inherited_defaults_are_what_each_class_returned(name):
    """``Decoder``'s defaults against what the five classes spelled out
    themselves before they inherited them: no state rows and no scan but
    the hybrid's, a chunk of zeros in the grid's own dtypes for the columns
    (the dense decoder's bfloat16 over an int8 grid and the hybrid's carried
    row state are their own), and a refusal that names the feature and the
    module (the dense decoder refuses nothing)."""
    module = importlib.import_module(f"{PACKAGE}.{name}")
    cfg = DECODERS[name][0]()
    model = decoder_for(cfg)
    assert model.__mro__[1].__name__ == "Decoder"
    hybrid = name in ("hybrid_linear", "hybrid_latent_moe")
    assert model.state_rows_touched(cfg, 8, 3) == (8 if hybrid else 0)
    assert (model.scan_positions(cfg, 2, 256) > 0) == hybrid
    if name not in ("latent_moe", "window_moe", "indexed_moe",
                    "hybrid_latent_moe"):
        assert model.counters == () and model.prefill_counters(cfg, 9) == {}
    cache = model.init_cache(cfg, 3, 64)
    chunk = model.init_chunk(cfg, cache, 3, 8)
    assert set(chunk) == set(cache)
    rows = row_leaves(model, cfg)
    for leaf, grid in cache.items():
        if leaf in rows:
            assert chunk[leaf] is grid
        else:
            assert chunk[leaf].shape == grid.shape[:2] + (8,) + grid.shape[3:]
            assert chunk[leaf].dtype == grid.dtype
            assert not np.asarray(chunk[leaf], np.float32).any()
    # every feature at once: the words of each, int8 first, and the module
    asked = dict(kv_dtype="int8", spec=True, adapters=True, mesh=True,
                 prefix=True, handoff=True)
    if name == "llama":
        assert model.refused == {}
        assert model.check_serving(cfg, **asked) is None
        return
    assert model.check_serving(cfg, spec=False, prefix=False) is None
    with pytest.raises(NotImplementedError) as err:
        model.check_serving(cfg, **asked)
    said = str(err.value)
    assert f"(models/{name}.py) does not carry " in said
    assert said.endswith("; ".join(module._REFUSED[f] for f in asked))
    with pytest.raises(NotImplementedError, match="int8"):
        model.init_cache(cfg, 3, 64, quantized=True)
