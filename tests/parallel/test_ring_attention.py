"""Ring attention == full attention, with the sequence sharded over 8 devices."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from replay_tpu.parallel import full_attention_reference, ring_attention

B, L, H, D = 2, 32, 2, 8  # L = 32 over 8 devices -> 4 tokens per shard


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()), ("sp",))


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32)) for _ in range(3))


@pytest.mark.jax
@pytest.mark.smoke
@pytest.mark.parametrize("causal", [False, True], ids=["bidirectional", "causal"])
def test_matches_full_attention(mesh, qkv, causal):
    q, k, v = qkv
    got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal))(q, k, v)
    want = full_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.jax
def test_respects_padding(mesh, qkv):
    q, k, v = qkv
    padding = jnp.asarray(np.random.default_rng(1).random((B, L)) > 0.3)
    got = jax.jit(
        lambda q, k, v, padding: ring_attention(q, k, v, mesh, causal=True, padding_mask=padding)
    )(q, k, v, padding)
    want = full_attention_reference(q, k, v, causal=True, padding_mask=padding)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.jax
def test_rejects_indivisible_length(mesh, qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="divisible"):
        ring_attention(q[:, :30], k[:, :30], v[:, :30], mesh)
