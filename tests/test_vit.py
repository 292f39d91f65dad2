"""ViT model tests (BASELINE config #4 path)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kubetorch_tpu.models import ViTConfig
from kubetorch_tpu.models import vit
from kubetorch_tpu.parallel import MeshSpec, ShardingRules, named_sharding


@pytest.fixture(scope="module")
def cfg():
    return ViTConfig.tiny()


def _batch(cfg, B=4, seed=0):
    rng = np.random.default_rng(seed)
    images = jnp.asarray(rng.normal(size=(B, cfg.image_size, cfg.image_size,
                                          3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, cfg.num_classes, (B,)), jnp.int32)
    return images, labels


def test_forward_shapes(cfg):
    params = vit.init(jax.random.key(0), cfg)
    images, _ = _batch(cfg)
    logits = vit.forward(params, images, cfg)
    assert logits.shape == (4, cfg.num_classes)
    assert bool(jnp.isfinite(logits).all())


def test_logical_axes_cover_params(cfg):
    params = vit.init(jax.random.key(0), cfg)
    axes = vit.param_logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    for leaf, ax in zip(jax.tree.leaves(params),
                        jax.tree.leaves(axes, is_leaf=lambda x:
                                        isinstance(x, tuple))):
        assert leaf.ndim == len(ax)


def test_sharded_forward_matches(cfg):
    mesh = MeshSpec(dp=2, fsdp=2, tp=2).build()
    rules = ShardingRules.default()
    params = vit.init(jax.random.key(0), cfg)
    images, _ = _batch(cfg)
    ref = vit.forward(params, images, cfg)
    axes = vit.param_logical_axes(cfg)
    shardings = jax.tree.map(
        lambda ax: named_sharding(mesh, rules, *ax), axes,
        is_leaf=lambda x: isinstance(x, tuple))
    sharded = jax.device_put(params, shardings)
    with jax.set_mesh(mesh):
        out = jax.jit(lambda p, x: vit.forward(p, x, cfg, rules))(
            sharded, images)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)


def test_training_learns(cfg):
    params = vit.init(jax.random.key(0), cfg)
    images, labels = _batch(cfg)
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            logits = vit.forward(p, images, cfg)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses
