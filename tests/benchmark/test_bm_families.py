"""The door through which an architecture enters the harness
(``benchmark/families``): the dense decoder passes through it unchanged, a
second family made of new files only runs to ``correct``, fails its control
and fails when broken underneath, and the scorers' layer loops take a stack
whose layers differ in shape, compiling once a kind."""

import copy
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO), str(HERE)]

from benchmark import families, manifest  # noqa: E402
from benchmark.opcounts import llama_dense as dense_ops  # noqa: E402
from test_bm_rehearsal import last_line, run, shape  # noqa: E402

MOE = "rehearsal-moe-serve"
OLD_CONFIGS = ["mistral-7b-v0.3-bf16-train-fsdp4",
               "mistral-7b-v0.3-bf16-train", "mistral-7b-v0.3-int8-serve",
               "rehearsal-serve", "rehearsal-train"]


# ------------------------------------------------ the dense decoder, unmoved
@pytest.mark.parametrize("name", OLD_CONFIGS)
def test_a_configuration_without_the_key_is_llama_dense(name):
    config = manifest.read(f"configs/{name}.json")
    assert "family" not in config
    family = families.load(config)
    assert family.__name__ == "benchmark.families.llama_dense"
    d = family.dims(config)
    assert family.layer_kinds(d) == ("dense",) * d["L"]
    assert set(family.controls()) == {"w4", "fp8"}


def test_nothing_of_the_harness_names_the_second_family():
    files = [p for pattern in ("*.py", "reference/*.py", "readers/*.py",
                               "families/*.py", "opcounts/*.py")
             for p in (REPO / "benchmark").glob(pattern)
             if p.name != "rehearsal_moe.py"]
    assert len(files) > 20
    for path in files:
        text = path.read_text()
        assert "rehearsal_moe" not in text and "rehearsal-moe" not in text, path


def test_the_harness_names_the_dense_decoder_only_behind_the_door():
    names = ("LlamaConfig", "llama_config_keys", "dense_f32", "serving_layer",
             "training_layer", "opcounts.llama_dense", "opcounts import",
             "reference.model", "reference import model")
    for path in (REPO / "benchmark").rglob("*.py"):
        rel = path.relative_to(REPO / "benchmark")
        if rel.parts[0] == "families" or str(rel) == "weights.py":
            continue
        text = path.read_text()
        assert not [n for n in names if n in text], path


def test_weights_through_the_door_are_bit_equal():
    import jax

    from benchmark import weights

    config = manifest.read("configs/rehearsal-serve.json")
    family = families.load(config, "serve")
    d = family.dims(config)
    seed = 2**31 + 17
    mine, direct = family.serving_tree(seed, d), weights.serving_tree(seed, d)
    assert jax.tree.structure(mine) == jax.tree.structure(direct)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(direct)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    key = weights.root_key(seed)
    for layer in range(d["L"]):
        ref = family.reference_layer(key, layer, d, "dense", "serve")
        want = weights.dense_f32(weights.serving_layer(key, layer, d), d)
        assert sorted(ref) == sorted(want)
        for name in want:
            assert np.array_equal(ref[name], want[name]), name
        layer_of_tree = jax.tree.map(lambda x: x[layer], direct["layers"])
        for name, x in weights.dense_f32(layer_of_tree, d).items():
            assert np.array_equal(ref[name], x), name
    glob = family.reference_globals(key, d, "serve")
    for name, x in weights.serving_globals(key, d).items():
        assert np.array_equal(glob[name], np.asarray(x, np.float32))
    train = manifest.read("configs/rehearsal-train.json")
    td = family.dims(train)
    for a, b in zip(jax.tree.leaves(family.training_tree(key, td)),
                    jax.tree.leaves(weights.training_tree(key, td))):
        assert np.array_equal(a, b)


def test_op_counts_through_the_door_equal_the_dense_counts():
    cell = manifest.cell("mistral7b-chat-steady")
    config = cell["config_json"]
    family = families.load(config, "serve")
    d = family.dims(config)
    ctx = {"dims": d, "config": config,
           "trace_live": {"positions": 6032.0, "rows": 19.0},
           "trace_stats_delta": {"prefill_tokens_executed": 3000},
           "mean_prompt_len": 412.5, "seq": 4096}
    assert family.decode_step_bytes(ctx) == dense_ops.decode_step_bytes(
        d, "int8", 6032.0)
    assert family.prefill_flops(ctx) == dense_ops.prefill_flops(
        d, 3000, 3000 * 412.5)
    assert family.train_flops_per_token(ctx) == \
        dense_ops.train_flops_per_token(d, 4096)
    # nothing to read: the readers then leave the metric out
    assert family.decode_step_bytes({**ctx, "trace_live": None}) is None
    assert family.prefill_flops({**ctx, "trace_stats_delta": {}}) is None


def test_roofline_readers_take_their_counts_from_the_family():
    from benchmark.readers import roofline

    cell = manifest.cell("mistral7b-chat-steady")
    config = cell["config_json"]
    d = families.load(config).dims(config)
    peaks = manifest.read("peaks.json")["TPU v5 lite"]
    ctx = {"dims": d, "config": config, "peaks": peaks, "seq": 4096,
           "train_tok_s_chip": 17250.0}
    want = (100.0 * dense_ops.train_flops_per_token(d, 4096) * 17250.0
            / peaks["bf16_flops"])
    assert roofline.trainer_mfu(ctx) == want
    assert roofline.decode_hbm_roofline({**ctx, "trace": None}) is None
    assert roofline.prefill_mfu({**ctx, "trace": None}) is None


# --------------------------------------------------------- manifest.check
def _with_files(monkeypatch, changes):
    """``manifest.read`` and the configuration files' text, with some keys
    of some files changed: {relative path: {key: value}}."""
    real_read, real_text = manifest.read, Path.read_text

    def read(rel):
        return {**real_read(rel), **changes.get(rel, {})}

    def read_text(self, *a, **k):
        text = real_text(self, *a, **k)
        for rel, new in changes.items():
            if self == manifest.ROOT / rel:
                return json.dumps({**json.loads(text), **new})
        return text

    monkeypatch.setattr(manifest, "read", read)
    monkeypatch.setattr(Path, "read_text", read_text)


@pytest.mark.parametrize("changes, needle", [
    ({"configs/mistral-7b-v0.3-int8-serve.json": {"family": "no_such"}},
     "unknown family 'no_such'"),
    ({"configs/mistral-7b-v0.3-int8-serve.json": {"family": "not a name"}},
     "not a family name"),
    ({"cells/mistral7b-chat-steady.json": {"controls": ["w4", "int2"]}},
     "control 'int2' is not one of its family's"),
    ({"configs/mistral-7b-v0.3-bf16-train.json": {"family": "rehearsal_moe"}},
     "lacks ['training_tree', 'program_leaf', 'train_flops_per_token']"),
])
def test_check_names_an_unknown_family_and_an_unknown_control(
        monkeypatch, changes, needle):
    bench = manifest.benchmark_json()
    assert manifest.check(copy.deepcopy(bench)) == []
    _with_files(monkeypatch, changes)
    errors = manifest.check(bench)
    assert any(needle in e for e in errors), errors


def test_load_names_what_a_family_lacks(monkeypatch):
    stub = types.ModuleType("benchmark.families.half_done")
    stub.dims = lambda config: {}
    monkeypatch.setitem(sys.modules, "benchmark.families.half_done", stub)
    with pytest.raises(LookupError, match="lacks .*'controls'.*'head'"):
        families.load({"family": "half_done"})


# ------------------------------- the second family, made of new files only
@pytest.fixture(scope="module")
def moe_run():
    return run(["benchmark/run.py", "--workload", MOE, "--seed", "3",
                "--seconds", "5", "--trace", "1", "--rehearsal", "1",
                "--control", "1"])


def test_second_family_prints_a_contract_line(moe_run):
    line = last_line(moe_run)
    shape(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    assert {"loadgen_late_p90_ms", "launch_ready_s", "compile_s",
            "rows_per_step"} <= set(line["metrics"])
    config = manifest.read(f"configs/{MOE}.json")
    assert config["family"] == "rehearsal_moe"
    assert families.load(config, "serve").layer_kinds(
        {"L": 2}) == ("moe", "moe")


def test_compared_numbers_come_last_and_on_standard_error(moe_run):
    line = last_line(moe_run)
    assert list(line)[-1] == "compared"
    compared = line["compared"]
    names = [c["name"] for c in compared]
    assert {"compiles_in_window", "failed_requests",
            "served_token_gap_max_logits",
            "served_token_gap_mean_logits"} <= set(names)
    assert all(set(c) == {"name", "value", "limit", "ok"} for c in compared)
    assert line["correct"] == all(c["ok"] for c in compared)
    tail = [ln for ln in moe_run.stderr.strip().splitlines()
            if "cpu_aot_loader" not in ln][-len(compared):]
    assert [ln.split()[1].rstrip(":") for ln in tail] == names
    assert all(ln.startswith("compared ") and "(limit " in ln for ln in tail)


def test_a_failed_check_stays_when_its_name_comes_again(capsys):
    from benchmark import report

    checks = report.Checks()
    checks.limit("gap", 0.5, 0.1)
    checks.limit("gap", 0.01, 0.1)
    assert [c["ok"] for c in checks.compared] == [False, True]
    assert checks.correct is False
    assert "# check gap: 0.5 (limit 0.1) FAILED" in capsys.readouterr().out


def test_second_family_passes_sound_and_fails_its_control(moe_run):
    ref = last_line(moe_run)["reference"]
    limit = manifest.read(f"cells/{MOE}.json")["correct"]
    assert ref["served_tokens"] >= 100
    assert ref["gap_max"] <= limit["gap_max_limit"] < \
        ref["control_bf16_gap_max"]
    assert ref["gap_mean"] <= limit["gap_mean_limit"] < \
        ref["control_bf16_gap_mean"]


def test_second_family_broken_underneath_is_not_correct():
    proc = run([str(HERE / "bm_drive_broken.py"), "serve", MOE])
    line = last_line(proc)
    shape(line)
    assert line["correct"] is False
    ok = {c["name"]: c["ok"] for c in line["compared"]}
    assert ok["served_token_gap_max_logits"] is False
    assert ok["served_token_gap_mean_logits"] is False


# ----------------- the scorers' layer loops, on a stack of two layer kinds
class TwoKinds:
    """A stub family, no program: the first layer is wide, the rest are
    narrow and carry a leaf the wide one lacks. ``traces`` counts how often
    each kind's block is traced, i.e. compiled."""

    WIDTH = {"wide": 24, "narrow": 6}

    def __init__(self):
        self.traces = {"wide": 0, "narrow": 0}

    def dims(self, config):
        return {"L": 4, "E": 8, "V": 32}

    def layer_kinds(self, d):
        return ("wide",) + ("narrow",) * (d["L"] - 1)

    def program_leaf(self, kind, name):
        return f"{kind}_layers/{name}"

    def _draw(self, key, tag, shape, layer=0):
        import jax

        key = jax.random.fold_in(jax.random.fold_in(key, tag), layer)
        return jax.random.normal(key, shape) * shape[0] ** -0.5

    def reference_globals(self, key, d, path):
        import jax.numpy as jnp

        return {"embedding": self._draw(key, 1, (d["V"], d["E"])),
                "final_norm": jnp.ones(d["E"]),
                "lm_head": self._draw(key, 2, (d["E"], d["V"]))}

    def reference_layer(self, key, layer, d, kind, path):
        m = self.WIDTH[kind]
        w = {"up": self._draw(key, 3, (d["E"], m), layer),
             "down": self._draw(key, 4, (m, d["E"]), layer)}
        if kind == "narrow":
            w["shift"] = 0.1 * self._draw(key, 5, (d["E"],), layer)
        return w

    def block(self, x, w, positions, d, lower, kind):
        import jax.numpy as jnp

        self.traces[kind] += 1
        assert (lower, w["up"].shape[1]) == (None, self.WIDTH[kind])
        y = x + jnp.tanh(x @ w["up"]) @ w["down"]
        return y + w["shift"] if kind == "narrow" else y

    def head(self, x, final_norm, lm_head, d, lower):
        return (x * final_norm) @ lm_head


@pytest.fixture
def stub_config(tmp_path):
    path = tmp_path / "two-kinds.json"
    path.write_text(json.dumps({"train": {"optimizer": {
        "learning_rate": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
        "weight_decay": 0.1}}}))
    return str(path)


def test_serving_scorer_compiles_once_a_kind(stub_config, monkeypatch):
    from benchmark.reference import score_serve

    fam = TwoKinds()
    monkeypatch.setattr(families, "load", lambda config, kind=None: fam)
    rng = np.random.default_rng(0)
    requests = [{"id": i, "prompt": rng.integers(0, 32, n_p).tolist(),
                 "served": rng.integers(0, 32, n_s).tolist()}
                for i, (n_p, n_s) in enumerate([(9, 5), (14, 7), (5, 3)])]
    out = score_serve.score({"config_file": stub_config, "seed": 7,
                             "buckets": [32], "requests": requests})
    assert fam.traces == {"wide": 1, "narrow": 1}
    assert out["served_tokens"] == 15 and np.isfinite(out["gap_max"])
    # the reference's own greedy tokens have no gap
    assert out["gap_max"] >= out["gap_mean"] >= 0.0


def test_training_scorer_compiles_once_a_kind_and_groups_leaves_by_kind(
        stub_config, monkeypatch):
    import jax
    import jax.numpy as jnp

    from benchmark import weights
    from benchmark.reference import score_train

    fam = TwoKinds()
    monkeypatch.setattr(families, "load", lambda config, kind=None: fam)
    seed, rows, seq = 11, 2, 8
    out = score_train.score({"config_file": stub_config, "seed": seed,
                             "rows": rows, "seq": seq})
    # one forward and one backward program a kind, whatever the depth,
    # the rows and the steps
    assert fam.traces == {"wide": 2, "narrow": 2}
    assert len(out["loss"]) == 3
    leaves = {"embedding", "final_norm", "lm_head", "wide_layers/up",
              "wide_layers/down", "narrow_layers/up", "narrow_layers/down",
              "narrow_layers/shift"}
    assert set(out["grad_norm"]) == set(out["delta_norm"]) == leaves
    assert set(out["grad_sample"]) == leaves

    # the same loss and gradient from one whole-model function
    d, key = fam.dims({}), weights.root_key(seed)
    kinds = fam.layer_kinds(d)
    params = {**fam.reference_globals(key, d, "train"), "layers": [
        fam.reference_layer(key, l, d, k, "train")
        for l, k in enumerate(kinds)]}
    toks = jnp.asarray(weights.batch_tokens(seed, 0, rows, seq, d["V"]))

    def loss(p):
        x = p["embedding"][toks[:, :-1]]
        for w, k in zip(p["layers"], kinds):
            x = fam.block(x, w, None, d, None, k)
        logits = fam.head(x, p["final_norm"], p["lm_head"], d, None)
        logz = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, toks[:, 1:, None], -1)[..., 0]
        return jnp.mean(logz - gold)

    value, grads = jax.value_and_grad(loss)(params)
    assert out["loss"][0] == pytest.approx(float(value), rel=1e-5)
    narrow = [g for g, k in zip(grads["layers"], kinds) if k == "narrow"]
    want = {"lm_head": jnp.linalg.norm(grads["lm_head"]),
            "wide_layers/up": jnp.linalg.norm(grads["layers"][0]["up"]),
            "narrow_layers/shift": jnp.sqrt(sum(
                jnp.sum(g["shift"] ** 2) for g in narrow))}
    for leaf, norm in want.items():
        assert out["grad_norm"][leaf] == pytest.approx(float(norm), rel=1e-4)
    # a stacked leaf's sample is read at (layer of the kind, position)
    up = np.stack([np.asarray(g["up"]) for g in narrow]).reshape(-1)
    pos = weights.sample_positions(seed, "narrow_layers/up", up.size)
    assert out["grad_sample"]["narrow_layers/up"] == pytest.approx(
        up[pos].tolist(), rel=1e-4, abs=1e-7)
