"""Sequence/context parallelism: ring attention + Ulysses vs dense reference.

The reference has no sequence parallelism (SURVEY §5.7); these tests cover the
TPU-native extension on an 8-virtual-device CPU mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.kernels.ring_attention import (
    ring_attention, ulysses_attention, _dense_attention)
from jax import shard_map


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("sep",))


def _qkv(b=2, t=32, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    q, k, v = _qkv()
    mesh = _mesh()
    spec = P(None, "sep", None, None)

    def f(qs, ks, vs):
        return ring_attention(qs, ks, vs, axis_name="sep", causal=causal)

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec))(q, k, v)
    ref = _dense_attention(q, k, v, causal, 1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    q, k, v = _qkv(h=8)
    mesh = _mesh()
    spec = P(None, "sep", None, None)

    def f(qs, ks, vs):
        return ulysses_attention(qs, ks, vs, axis_name="sep", causal=causal)

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec))(q, k, v)
    ref = _dense_attention(q, k, v, causal, 1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_match_dense():
    q, k, v = _qkv(b=1, t=16, h=2, d=8)
    mesh = _mesh()
    spec = P(None, "sep", None, None)
    scale = 1.0 / np.sqrt(q.shape[-1])

    def ring_loss(qs, ks, vs):
        f = shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis_name="sep",
                                           causal=True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
        return jnp.sum(f(qs, ks, vs) ** 2)

    def dense_loss(qs, ks, vs):
        return jnp.sum(_dense_attention(qs, ks, vs, True, scale) ** 2)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_sdpa_routes_to_ring_under_sep():
    """nn.functional.scaled_dot_product_attention inside shard_map over a
    sep-sharded sequence must compute GLOBAL attention (via the ring), not
    shard-local attention."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.tensor import Tensor

    q, k, v = _qkv(t=32)
    mesh = _mesh()
    spec = P(None, "sep", None, None)

    def f(qs, ks, vs):
        out = F.scaled_dot_product_attention(
            Tensor(qs, _internal=True), Tensor(ks, _internal=True),
            Tensor(vs, _internal=True), is_causal=True)
        return out._value if isinstance(out, Tensor) else out

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=(spec,) * 3,
                                out_specs=spec))(q, k, v)
    ref = _dense_attention(q, k, v, True, 1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
