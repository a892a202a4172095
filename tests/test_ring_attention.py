"""Ring attention == dense attention, values and gradients.

Runs on the 8-device virtual CPU mesh (conftest.py). The sequence axis is
genuinely sharded, so the ppermute ring and the online-softmax
accumulation are both exercised.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.ring_attention import dense_attention, ring_attention
from elasticdl_tpu.parallel.mesh import make_mesh

B, S, H, D = 2, 32, 4, 8


def _qkv(seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D), dtype) * 0.3
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("mesh_shape,axes", [
    ((4,), ("sp",)),
    ((2, 2, 2), ("dp", "sp", "tp")),
    ((8,), ("sp",)),
])
def test_ring_matches_dense(causal, mesh_shape, axes):
    q, k, v = _qkv()
    mesh = make_mesh(mesh_shape, axes,
                     devices=jax.devices()[: int(np.prod(mesh_shape))])
    want = dense_attention(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_gradients_match_dense():
    q, k, v = _qkv(seed=1)
    mesh = make_mesh((4,), ("sp",), devices=jax.devices()[:4])

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_sp_absent_falls_back_to_dense():
    q, k, v = _qkv(seed=2)
    mesh = make_mesh((4,), ("dp",), devices=jax.devices()[:4])
    got = ring_attention(q, k, v, mesh, causal=True)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_no_sp_axis_runs_flash_per_shard():
    """A dp x tp mesh needs no ring, and on the TPU backend it must not
    fall to the dense reference either: each device runs the flash
    kernel on its own (batch, head) shard. Values and grads == dense
    (interpret mode on the CPU mesh; the CPU's own choice stays dense,
    test_sp_absent_falls_back_to_dense)."""
    q, k, v = _qkv(seed=8)
    mesh = make_mesh((2, 2), ("dp", "tp"), devices=jax.devices()[:4])

    @jax.jit
    def sharded(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True,
                              use_pallas=True, interpret=True)

    got = sharded(q, k, v)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    gs = jax.grad(lambda *a: jnp.sum(sharded(*a) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(
        lambda *a: jnp.sum(dense_attention(*a, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_ring_matches_dense(causal):
    """The fused (flash_chunk_update) ring == dense, values and grads
    (interpret mode on the CPU mesh)."""
    q, k, v = _qkv(seed=6)
    mesh = make_mesh((4,), ("sp",), devices=jax.devices()[:4])

    def ring_p(q, k, v):
        return ring_attention(q, k, v, mesh, causal=causal,
                              use_pallas=True, interpret=True)

    got = ring_p(q, k, v)
    want = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    gr = jax.grad(lambda *a: jnp.sum(ring_p(*a) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(
        lambda *a: jnp.sum(dense_attention(*a, causal=causal) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_pallas_ring_composes_with_dp_tp():
    q, k, v = _qkv(seed=7)
    mesh = make_mesh((2, 2, 2), ("dp", "sp", "tp"),
                     devices=jax.devices()[:8])

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True,
                              use_pallas=True, interpret=True)

    got = f(q, k, v)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_under_jit_with_batch_sharding():
    q, k, v = _qkv(seed=3)
    mesh = make_mesh((2, 2, 2), ("dp", "sp", "tp"),
                     devices=jax.devices()[:8])

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True)

    got = f(q, k, v)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
