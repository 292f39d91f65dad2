"""Pallas int8 weight-streaming matmul: parity, block picking, gating
(ops/quant_matmul.py — no reference analogue, owned serving compute)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetorch_tpu.ops import quant_matmul


def _ref(x, w_q, scale):
    return (x.astype(jnp.float32)
            @ (w_q.astype(jnp.float32) * scale.reshape(1, -1)
               .astype(jnp.float32)))


@pytest.mark.level("unit")
@pytest.mark.parametrize("b,k,n", [(8, 256, 512), (64, 512, 1024),
                                   (16, 384, 256)])
def test_int8_matmul_parity(b, k, n):
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (b, k), jnp.float32)
    w = jax.random.randint(kw, (k, n), -127, 128, jnp.int8)
    scale = jnp.abs(jax.random.normal(jax.random.key(2), (n,),
                                      jnp.float32)) * 0.01 + 1e-4
    got = quant_matmul.int8_matmul(x, w, scale, interpret=True)
    want = _ref(x, w, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.level("unit")
def test_int8_matmul_bf16_matches_wload_semantics():
    """Kernel result ≈ the einsum path on dequantized weights (the exact
    computation llama._wload feeds decode) within bf16 tolerance."""
    b, k, n = 4, 128, 256
    x = jax.random.normal(jax.random.key(0), (b, k), jnp.bfloat16)
    w = jax.random.randint(jax.random.key(1), (k, n), -127, 128, jnp.int8)
    scale = jnp.full((n,), 0.01, jnp.bfloat16)
    got = quant_matmul.int8_matmul(x, w, scale, interpret=True)
    assert got.dtype == jnp.bfloat16
    wd = w.astype(jnp.bfloat16) * scale.reshape(1, -1)
    want = jnp.einsum("bk,kn->bn", x, wd)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-1)


@pytest.mark.level("unit")
def test_int8_matmul_under_jit_and_block_sizes():
    b, k, n = 8, 256, 1024
    x = jax.random.normal(jax.random.key(0), (b, k), jnp.float32)
    w = jax.random.randint(jax.random.key(1), (k, n), -127, 128, jnp.int8)
    scale = jnp.full((n,), 0.02, jnp.float32)
    want = _ref(x, w, scale)
    for bn in (128, 256, 512):
        got = jax.jit(lambda a: quant_matmul.int8_matmul(
            a, w, scale, block_n=bn, interpret=True))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.level("unit")
def test_pick_block_n_vmem_budget():
    # small K: biggest block
    assert quant_matmul.pick_block_n(64, 4096, 14336) == 512
    # the 8B down-projection (K=14336): 512 would blow the 16 MiB scoped
    # VMEM limit double-buffered, must drop to 256
    assert quant_matmul.pick_block_n(64, 14336, 4096) == 256
    # nothing divides N
    assert quant_matmul.pick_block_n(64, 512, 300) is None


@pytest.mark.level("unit")
def test_viability_gate():
    x = jnp.zeros((2, 1, 64), jnp.bfloat16)
    w8 = jnp.zeros((64, 128), jnp.int8)
    wf = jnp.zeros((64, 128), jnp.bfloat16)
    s = jnp.zeros((128,), jnp.bfloat16)
    # no scale / non-int8 weights never take the kernel
    assert not quant_matmul.decode_matmul_viable(x, w8, None)
    assert not quant_matmul.decode_matmul_viable(x, wf, s)
    # prefill-shaped activations (many tokens) stay on the einsum
    big = jnp.zeros((64, 128, 64), jnp.bfloat16)
    assert not quant_matmul.decode_matmul_viable(big, w8, s)
    # CPU backend (the test env) never takes the kernel: the decode path
    # must be identical with and without quantized params present
    assert not quant_matmul.decode_matmul_viable(x, w8, s)


@pytest.mark.level("unit")
def test_viability_gate_rejects_live_mesh():
    """Under a >1-device mesh the einsum path must win (an unpartitioned
    pallas call would force operand all-gathers)."""
    from kubetorch_tpu.parallel.mesh import MeshSpec

    x = jnp.zeros((2, 1, 64), jnp.bfloat16)
    w8 = jnp.zeros((64, 128), jnp.int8)
    s = jnp.zeros((128,), jnp.bfloat16)
    with jax.set_mesh(MeshSpec(fsdp=-1).build()):
        assert not quant_matmul.decode_matmul_viable(x, w8, s)
