"""TP vocab-sharded fused logsumexp == replicated fused_lse == plain jnp CE.

The sharded head (replay_tpu.parallel.sharded_ce) splits the item table
``[I/n_tp, E]`` per device over the mesh's ``model`` axis, runs the tile-wise
online max/sum per shard and combines with a psum-style two-pass reduction
inside ``shard_map``; the backward psums ``dh`` across shards and keeps ``dW``
shard-local. Parity is checked fwd + grads on the virtual 8-device CPU mesh
(DP×TP), including a catalog NOT divisible by ``n_tp`` (shard padding masked
inside the kernel) and a shard spanning several catalog tiles.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from replay_tpu.ops.fused_ce import fused_lse
from replay_tpu.parallel import sharded_fused_lse

pytestmark = pytest.mark.jax


def make_mesh(data: int, model: int) -> Mesh:
    devices = np.array(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devices, ("data", "model"))


def make_data(n, items, embed, seed=0):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((n, embed)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((items, embed)), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    return h, w, g


def assert_parity(mesh, h, w, g, item_tile=None, data_axis="data"):
    """Sharded fwd/grads vs replicated fused_lse vs plain jnp logsumexp.

    Every kernel call is jitted, as the trainer runs it: dispatched eagerly the
    interpreted shard_map costs ~3 s a call on the 8-device CPU mesh."""

    def sharded_lse(h, w):
        return sharded_fused_lse(
            h, w, mesh, data_axis=data_axis, tile=8, item_tile=item_tile, interpret=True
        )

    want = jax.nn.logsumexp(h @ w.T, axis=-1)
    replicated = jax.jit(lambda h, w: fused_lse(h, w, 8, item_tile, True))(h, w)
    got = jax.jit(sharded_lse)(h, w)
    np.testing.assert_allclose(np.asarray(replicated), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

    def ref(h, w):
        return jnp.sum(jax.nn.logsumexp(h @ w.T, axis=-1) * g)

    def sharded(h, w):
        return jnp.sum(sharded_lse(h, w) * g)

    ref_dh, ref_dw = jax.jit(jax.grad(ref, argnums=(0, 1)))(h, w)
    got_dh, got_dw = jax.jit(jax.grad(sharded, argnums=(0, 1)))(h, w)
    np.testing.assert_allclose(np.asarray(got_dh), np.asarray(ref_dh), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_dw), np.asarray(ref_dw), rtol=2e-4, atol=2e-5)


@pytest.mark.smoke
def test_sharded_matches_replicated_and_jnp_dp_tp():
    """4×2 DP×TP mesh, catalog divisible by n_tp: exact-shape sharding."""
    h, w, g = make_data(32, 64, 16)
    assert_parity(make_mesh(4, 2), h, w, g)


@pytest.mark.smoke
def test_non_divisible_catalog_padding_masked():
    """37 items over n_tp=2: the padded shard tail must contribute exactly
    nothing to the softmax — forward AND both gradients."""
    h, w, g = make_data(16, 37, 8, seed=1)
    assert_parity(make_mesh(4, 2), h, w, g)


def test_data_parallel_mesh_with_the_table_replicated():
    """``data`` = 4, ``model`` = 1 (the four-chip benchmark cell; the route ``CE``
    takes under such a mesh): each device runs the kernels on its own rows over
    the whole catalog, and ``shard_map``'s transpose sums ``dW`` over ``data``."""
    h, w, g = make_data(32, 37, 16, seed=5)
    assert_parity(make_mesh(4, 1), h, w, g)


def test_multi_tile_shard():
    """Each 300-row shard sweeps several 128-column catalog tiles: the online
    max/sum inside a shard composes with the cross-shard combine."""
    h, w, g = make_data(16, 600, 8, seed=2)
    assert_parity(make_mesh(4, 2), h, w, g, item_tile=128)


def test_mostly_empty_shards():
    """A 5-item catalog over 8 shards: shards past the catalog are ENTIRELY
    padding and must yield a ~-1e30 local lse (finite — the kernel's mask is
    not -inf exactly so this case cannot NaN) that vanishes in the combine."""
    h, w, g = make_data(8, 5, 8, seed=3)
    assert_parity(make_mesh(1, 8), h, w, g)


def test_rows_replicated_without_data_axis():
    """data_axis=None replicates the rows over the shard groups (pure-TP
    call sites); values still match the replicated kernel."""
    h, w, g = make_data(12, 37, 8, seed=4)
    assert_parity(make_mesh(4, 2), h, w, g, data_axis=None)


def test_rejects_missing_axes():
    h, w, _ = make_data(8, 16, 8)
    mesh = make_mesh(4, 2)
    with pytest.raises(ValueError, match="no 'tp' axis"):
        sharded_fused_lse(h, w, mesh, axis_name="tp", interpret=True)
    with pytest.raises(ValueError, match="do not divide"):
        sharded_fused_lse(h[:3], w, mesh, interpret=True)


def test_num_valid_masks_table_tail():
    """The kernel-level seam the sharded wrapper relies on: a traced
    num_valid < table rows masks the tail out of the softmax."""
    h, w, _ = make_data(8, 24, 8, seed=5)
    want = jax.nn.logsumexp(h @ w[:17].T, axis=-1)
    got = fused_lse(h, w, 8, None, True, num_valid=jnp.int32(17))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
