"""Device-array transfer + Orbax checkpoint/resume tests."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kubetorch_tpu.data_store.client import DataStoreClient
from kubetorch_tpu.data_store.device_transfer import (
    get_arrays,
    pack_arrays,
    put_arrays,
    unpack_arrays,
)


@pytest.fixture(autouse=True)
def _isolated_store(tmp_path, monkeypatch):
    monkeypatch.setenv("KT_LOCAL_STORE", str(tmp_path / "store"))
    import kubetorch_tpu.data_store.client as client_mod

    monkeypatch.setattr(client_mod, "_LOCAL_STORE", tmp_path / "store")
    DataStoreClient._default = None
    yield
    DataStoreClient._default = None


def test_pack_unpack_roundtrip():
    tree = {"w": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
            "nested": {"b": jnp.ones((5,), jnp.float32),
                       "step": jnp.asarray(7, jnp.int32)}}
    blob = pack_arrays(tree)
    out = unpack_arrays(blob, template=tree)
    assert out["w"].dtype == np.dtype("bfloat16")
    np.testing.assert_array_equal(np.asarray(tree["w"]), out["w"])
    np.testing.assert_array_equal(out["nested"]["b"], np.ones((5,)))
    assert out["nested"]["step"] == 7


def test_put_get_arrays_with_resharding():
    from kubetorch_tpu.parallel import MeshSpec, named_sharding, ShardingRules

    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    put_arrays("weights/latest", tree)

    mesh = MeshSpec(fsdp=4, tp=2).build()
    rules = ShardingRules.default()
    sharding = named_sharding(mesh, rules, "embed_fsdp", "heads")
    out = get_arrays("weights/latest", template=tree,
                     shardings={"w": sharding})
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert out["w"].sharding == sharding  # landed sharded on the new mesh


def test_checkpoint_save_restore_sharded(tmp_path):
    import optax

    from kubetorch_tpu.models import LlamaConfig
    from kubetorch_tpu.parallel import MeshSpec
    from kubetorch_tpu.training import Trainer
    from kubetorch_tpu.training.checkpoint import CheckpointManager

    cfg = LlamaConfig.tiny()
    mesh = MeshSpec(fsdp=4, tp=2).build()
    trainer = Trainer(cfg, mesh, optimizer=optax.adam(1e-2))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (4, 17))
    batch = {"inputs": jnp.asarray(toks[:, :-1], jnp.int32),
             "targets": jnp.asarray(toks[:, 1:], jnp.int32)}
    trainer.step(batch)
    trainer.step(batch)

    manager = CheckpointManager(tmp_path / "ckpt")
    manager.save(2, trainer.state, wait=True)
    assert manager.latest_step() == 2

    # Restore onto a DIFFERENT mesh layout.
    mesh2 = MeshSpec(dp=2, fsdp=2, tp=2).build()
    trainer2 = Trainer(cfg, mesh2, optimizer=optax.adam(1e-2))
    restored = manager.restore(trainer2.state)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(restored["params"]["embedding"])),
        np.asarray(jax.device_get(trainer.state["params"]["embedding"])),
        rtol=1e-6)
    assert int(jax.device_get(restored["step"])) == 2
    # Restored state trains.
    trainer2.state = restored
    metrics = trainer2.step(batch)
    assert bool(jnp.isfinite(metrics["loss"]))


@pytest.mark.level("unit")
def test_save_wait_true_is_durable_on_return(tmp_path):
    """Satellite (ISSUE 5): ``save(wait=True)`` must leave the step
    finalized and restorable the moment it returns — the preemption
    grace window depends on it (an async save races the SIGKILL). A
    FRESH manager (a restarted pod) must see and restore it with no
    ``wait_until_finished`` help from the saving process."""
    from kubetorch_tpu.training.checkpoint import CheckpointManager

    state = {"w": jnp.arange(8, dtype=jnp.float32),
             "step": jnp.asarray(5, jnp.int32)}
    manager = CheckpointManager(tmp_path / "ck")
    manager.save(5, state, wait=True)
    assert manager.latest_step() == 5  # visible immediately

    fresh = CheckpointManager(tmp_path / "ck")
    assert fresh.latest_step() == 5
    out = fresh.restore({"w": jnp.zeros(8, jnp.float32),
                         "step": jnp.asarray(0, jnp.int32)})
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.arange(8, dtype=np.float32))
    assert int(out["step"]) == 5


@pytest.mark.level("unit")
def test_push_to_store_unconfigured_raises(tmp_path, monkeypatch):
    """Satellite (ISSUE 5): with no remote store configured,
    ``push_to_store`` used to silently land the checkpoint on the
    pod-local filesystem — lost with the very pod whose preemption the
    push exists to survive. Now it raises the typed StoreUnconfigured;
    laptop mode / tests opt back in with ``allow_local=True``."""
    from kubetorch_tpu.exceptions import StoreUnconfigured
    from kubetorch_tpu.training.checkpoint import CheckpointManager

    monkeypatch.delenv("KT_STORE_URL", raising=False)
    DataStoreClient._default = None
    manager = CheckpointManager(tmp_path / "ck")
    manager.save(1, {"w": jnp.ones(4, jnp.float32)}, wait=True)

    with pytest.raises(StoreUnconfigured) as err:
        manager.push_to_store("ckpts/svc")
    assert "allow_local=True" in str(err.value)

    # explicit opt-in still lands in the (isolated) local store
    pushed = manager.push_to_store("ckpts/svc", allow_local=True)
    assert pushed == "ckpts/svc/1"
    from kubetorch_tpu.training.checkpoint import CheckpointManager as CM

    pulled = CM.pull_from_store("ckpts/svc", tmp_path / "pulled", 1)
    out = pulled.restore({"w": jnp.zeros(4, jnp.float32)})
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(4))


def test_resume_or_init(tmp_path):
    from kubetorch_tpu.training.checkpoint import (
        resume_or_init,
        save_for_resume,
    )

    def init_fn():
        return {"w": jnp.zeros((4,)), "step": jnp.asarray(0)}

    state, step = resume_or_init(tmp_path / "r", init_fn)
    assert step == 0
    state = {"w": jnp.ones((4,)) * 5, "step": jnp.asarray(3)}
    save_for_resume(tmp_path / "r", state, 3)
    state2, step2 = resume_or_init(tmp_path / "r", init_fn)
    assert step2 == 3
    np.testing.assert_array_equal(np.asarray(state2["w"]), 5 * np.ones(4))


@pytest.mark.level("unit")
def test_device_get_chunked_matches_per_leaf():
    """Chunked staging (O(total/chunk) fetches) must reproduce every
    leaf exactly — mixed dtypes, chunk-boundary splits, 0-d leaves, and
    the multi-device-sharded fallback."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubetorch_tpu.data_store.device_transfer import device_get_chunked

    rng = np.random.default_rng(0)
    tree = {
        "a": jnp.asarray(rng.random((64, 32)), jnp.float32),
        "b": jnp.asarray(rng.random((128,)), jnp.bfloat16),
        "c": jnp.asarray(rng.integers(-100, 100, (16, 4)), jnp.int8),
        "d": jnp.asarray(3.5, jnp.float32),            # 0-d
        "e": jnp.asarray(rng.random((100, 7)), jnp.float32),
        "np": rng.random((5,)),                        # numpy passthrough
    }
    leaves, treedef = jax.tree.flatten(tree)
    # tiny chunk budget forces multiple flushes and single-leaf batches
    got = device_get_chunked(leaves, chunk_bytes=4096)
    assert len(got) == len(leaves)
    for g, leaf in zip(got, leaves):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(jax.device_get(leaf)))
        assert g.shape == np.asarray(leaf).shape

    # sharded leaf: falls back to the direct fetch, still exact
    from jax.sharding import NamedSharding, PartitionSpec

    from kubetorch_tpu.parallel import MeshSpec

    mesh = MeshSpec(dp=2).build(jax.devices()[:2])
    sh = jax.device_put(jnp.arange(32, dtype=jnp.float32).reshape(2, 16),
                        NamedSharding(mesh, PartitionSpec("dp")))
    got = device_get_chunked([sh, tree["a"]], chunk_bytes=1 << 20)
    np.testing.assert_array_equal(got[0],
                                  np.arange(32, dtype=np.float32).reshape(2, 16))
    np.testing.assert_array_equal(got[1], np.asarray(tree["a"]))
