"""Pallas fused catalog logsumexp == plain jnp (interpret mode on CPU)."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from replay_tpu.ops.fused_ce import fused_lse

pytestmark = pytest.mark.jax


@pytest.fixture
def data():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((300, 64)), jnp.float32)  # N not a tile multiple
    w = jnp.asarray(rng.standard_normal((1000, 64)), jnp.float32)  # I not a lane multiple
    return h, w


def test_forward_matches_logsumexp(data):
    h, w = data
    want = jax.nn.logsumexp(h @ w.T, axis=-1)
    got = fused_lse(h, w, 128, None, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# each case reaches one corner of the two-sweep schedule (PW from the forward's
# accumulator, the row weight on the dW kernel's rows); unnamed keys take BASE's
BASE = dict(n=64, items=1000, embed=32, tile=64, item_tile=256, dtype=jnp.float32,
            zero_rows=False, rising=False, num_valid=None, masked_shard=False)
GRAD_CASES = {
    "normal": dict(n=300, embed=64, tile=128, item_tile=None),
    "zero-cotangents": dict(n=300, embed=64, tile=128, item_tile=None, zero_rows=True),
    "padded-rows": dict(n=13, items=200, embed=16, tile=8, item_tile=128),
    "ragged-catalog": {},
    # every catalog tile's logits outgrow the last: the running max moves at each
    # tile, and with it the rescale of the PW accumulator
    "rising-logits": dict(item_tile=128, rising=True),
    "bf16": dict(n=300, embed=64, tile=128, dtype=jnp.bfloat16),
    "num-valid-tail": dict(num_valid=700),
    "fully-masked-shard": dict(zero_rows=True, masked_shard=True),
}


@pytest.mark.parametrize("case", list(GRAD_CASES.values()), ids=list(GRAD_CASES))
def test_gradients_match(case):
    """dh and dW of ``sum(g · lse)`` against ``jax.grad`` of the plain logsumexp,
    under a cotangent that is not uniform over the rows."""
    c = {**BASE, **case}
    n, items, dtype, tile, item_tile = c["n"], c["items"], c["dtype"], c["tile"], c["item_tile"]
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((n, c["embed"])), dtype)
    w = rng.standard_normal((items, c["embed"]))
    if c["rising"]:
        w *= 0.25 * (1 + np.arange(items)[:, None] // item_tile)
    w = jnp.asarray(w, dtype)
    shard = jnp.asarray(rng.standard_normal((items // 2, c["embed"])), dtype)
    g = rng.standard_normal(n)
    if c["zero_rows"]:
        g[::3] = 0.0
    g = jnp.asarray(g, jnp.float32)
    valid = items if c["num_valid"] is None else c["num_valid"]

    def ref(h, w, shard):
        logits = h.astype(jnp.float32) @ w.astype(jnp.float32)[:valid].T
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) * g)

    def empty_shard_lse(h, shard):
        return fused_lse(h, shard, tile, item_tile, True, num_valid=0)

    def fused(h, w, shard):
        lse = fused_lse(h, w, tile, item_tile, True, num_valid=valid)
        if c["masked_shard"]:
            # the sharded wrapper's combine beside a shard that is all padding:
            # its softmax share, the cotangent it gets, is 0
            lse = jax.nn.logsumexp(jnp.stack([lse, empty_shard_lse(h, shard)]), axis=0)
        return jnp.sum(lse * g)

    ref_dh, ref_dw, _ = jax.jit(jax.grad(ref, argnums=(0, 1, 2)))(h, w, shard)
    got_dh, got_dw, got_dshard = jax.jit(jax.grad(fused, argnums=(0, 1, 2)))(h, w, shard)
    assert got_dh.dtype == dtype and got_dw.dtype == dtype
    tol = dict(rtol=2e-4, atol=2e-5) if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got_dh, np.float32), np.asarray(ref_dh, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(got_dw, np.float32), np.asarray(ref_dw, np.float32), **tol)
    assert not np.asarray(got_dw[valid:]).any()  # masked rows get exactly nothing
    assert not np.asarray(got_dh)[np.asarray(g) == 0].any()
    if c["masked_shard"]:
        assert np.isfinite(np.asarray(jax.jit(empty_shard_lse)(h, shard))).all()
        assert not np.asarray(got_dshard).any()


def test_undifferentiated_lse_keeps_its_bits():
    """The forward that also writes PW keeps the max/sum recurrence of the
    single-output kernel it replaced: in interpret mode its ``lse`` bits are the
    ones that kernel gave (values pinned from it), called alone or under a vjp.
    (On the chip Mosaic may round the last bit otherwise: PERF.md, Findings.)"""
    rng = np.random.default_rng(0)
    # small dyadic values: every product and sum of the logits is exact, so only
    # the kernel's exp/log recurrence sets the bits; later catalog tiles are larger
    h = rng.integers(-3, 4, (12, 16)).astype(np.float32) / 4
    w = rng.integers(-3, 4, (300, 16)).astype(np.float32) / 4 * (1 + np.arange(300)[:, None] // 128)
    h, w = jnp.asarray(h), jnp.asarray(w)
    pinned = [
        0x40E7B00A, 0x40FF47CC, 0x410B671B, 0x40F24AE6, 0x40DE4CF0, 0x40D4EA6C,
        0x4100A7D8, 0x4103A513, 0x4126BC27, 0x40E7E61C, 0x40FC479B, 0x4123BCF3,
    ]
    lse = jax.jit(lambda h, w: fused_lse(h, w, 8, 128, True))(h, w)
    primal, _ = jax.jit(lambda h, w: jax.vjp(lambda h: fused_lse(h, w, 8, 128, True), h))(h, w)
    assert np.asarray(lse).view(np.uint32).tolist() == pinned
    assert np.asarray(primal).view(np.uint32).tolist() == pinned


def pallas_calls(jaxpr):
    """The ``pallas_call`` equations of ``jaxpr``, nested programs included."""
    found = sum(eqn.primitive.name == "pallas_call" for eqn in jaxpr.eqns)
    for eqn in jaxpr.eqns:
        found += sum(pallas_calls(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
    return found


@pytest.mark.parametrize("route", ["fused", "sharded"])
def test_gradient_sweeps_the_catalog_twice(route):
    """The value and both gradients take two kernels: the forward that writes lse
    and PW, and dW; dh is an elementwise op on PW. An evaluation forward takes one."""
    from jax.sharding import Mesh

    from replay_tpu.parallel import sharded_fused_lse

    h = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((37, 16), jnp.float32)
    if route == "fused":
        lse = partial(fused_lse, tile=8, interpret=True)
    else:
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("data", "model"))
        lse = partial(sharded_fused_lse, mesh=mesh, tile=8, interpret=True)
    grads = jax.grad(lambda h, w: lse(h, w).sum(), (0, 1))
    assert pallas_calls(jax.make_jaxpr(grads)(h, w).jaxpr) == 2
    assert pallas_calls(jax.make_jaxpr(lse)(h, w).jaxpr) == 1


def test_bf16_inputs_accumulate_in_f32(data):
    h, w = data
    got = fused_lse(h.astype(jnp.bfloat16), w.astype(jnp.bfloat16), 128, None, True)
    want = jax.nn.logsumexp(
        h.astype(jnp.bfloat16).astype(jnp.float32) @ w.astype(jnp.bfloat16).astype(jnp.float32).T,
        axis=-1,
    )
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_item_tiling_matches_single_tile(data):
    """Catalog swept in multiple tiles (online max/sum) == one-tile answer."""
    h, w = data
    g = jnp.asarray(np.random.default_rng(2).standard_normal(h.shape[0]), jnp.float32)
    want = jax.nn.logsumexp(h @ w.T, axis=-1)
    got = fused_lse(h, w, 128, 256, True)  # 1000 items -> 4 catalog tiles
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)

    def ref(h, w):
        return jnp.sum(jax.nn.logsumexp(h @ w.T, axis=-1) * g)

    def fused(h, w):
        return jnp.sum(fused_lse(h, w, 128, 256, True) * g)

    ref_dh, ref_dw = jax.grad(ref, argnums=(0, 1))(h, w)
    got_dh, got_dw = jax.grad(fused, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(got_dh), np.asarray(ref_dh), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_dw), np.asarray(ref_dw), rtol=2e-4, atol=2e-5)


def test_single_row_and_tiny_catalog():
    h = jnp.ones((1, 8), jnp.float32)
    w = jnp.ones((3, 8), jnp.float32)
    got = fused_lse(h, w, 8, None, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jax.nn.logsumexp(h @ w.T, -1)), rtol=1e-5)


@pytest.mark.smoke
def test_cefused_trains_identically_to_ce():
    """CEFused through the Trainer matches CE step losses (shared seed)."""
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn import Trainer
    from replay_tpu.nn.loss import CE, CEFused
    from replay_tpu.nn.sequential.sasrec import SasRec

    n_items, length, batch_size = 50, 8, 4
    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id",
            FeatureType.CATEGORICAL,
            is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            cardinality=n_items,
            embedding_dim=16,
        )
    )
    rng = np.random.default_rng(0)
    items = rng.integers(0, n_items, size=(batch_size, length + 1)).astype(np.int32)
    batch = {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": np.ones((batch_size, length), bool),
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": np.ones((batch_size, length, 1), bool),
    }

    def run(loss):
        model = SasRec(
            schema=schema, embedding_dim=16, num_blocks=1, num_heads=1,
            max_sequence_length=length, dropout_rate=0.0,
        )
        trainer = Trainer(model=model, loss=loss)
        state = trainer.init_state(batch)
        losses = []
        for _ in range(3):
            state, value = trainer.train_step(state, batch)
            losses.append(float(value))
        return losses

    plain, fused = run(CE()), run(CEFused(tile=8))
    np.testing.assert_allclose(fused, plain, rtol=1e-4)
    assert fused[-1] < fused[0]  # and it actually learns


def test_vmem_guard_shrinks_item_tile(caplog):
    """The [row_tile, item_tile] working set is budgeted UP FRONT: a config
    that would blow the Mosaic VMEM limit at compile time (the round-3 16 MB
    bwd-kernel incident: tile=256 x item_tile=4096 at d=300) auto-shrinks the
    item tile lane-aligned, with one warning recording the decision."""
    import logging

    from replay_tpu.ops.fused_ce import (
        _LANE,
        _VMEM_BUDGET_BYTES,
        _resolve_item_tile,
        _shrink_warned,
        _working_set_bytes,
    )

    _shrink_warned.clear()
    with caplog.at_level(logging.WARNING, logger="replay_tpu"):
        shrunk = _resolve_item_tile(1_000_000, None, 256, 300)
    assert shrunk < 4096
    assert shrunk % _LANE == 0
    assert _working_set_bytes(256, shrunk, 300) <= _VMEM_BUDGET_BYTES
    warnings = [r for r in caplog.records if "item_tile" in r.getMessage()]
    assert len(warnings) == 1
    # the same configuration warns ONCE, not once per trace
    with caplog.at_level(logging.WARNING, logger="replay_tpu"):
        assert _resolve_item_tile(1_000_000, None, 256, 300) == shrunk
    assert len([r for r in caplog.records if "item_tile" in r.getMessage()]) == 1


def test_vmem_guard_keeps_small_configs_unchanged():
    """The bench/test shapes that fit must resolve exactly as before."""
    from replay_tpu.ops.fused_ce import _resolve_item_tile

    assert _resolve_item_tile(1000, None, 128, 64) == 1024  # lane-padded catalog
    assert _resolve_item_tile(27278, None, 256, 64) == 4096  # the default tile
    assert _resolve_item_tile(1000, 256, 128, 64) == 256  # explicit, in budget


@pytest.mark.parametrize("embed, item_tile", [(64, 4096), (128, 2048), (192, 2048), (256, 2048), (300, 1024)])
def test_vmem_guard_tile_by_width(embed, item_tile):
    """The catalog tile CE's head gets at 512 rows a program on ML-20M's 27,278
    items, at each width of the width table (``nn/loss/ce.py``)."""
    from replay_tpu.ops.fused_ce import _resolve_item_tile

    assert _resolve_item_tile(27278, None, 512, embed) == item_tile


def test_vmem_guard_shrinks_explicit_item_tile(caplog):
    """An explicit item_tile beyond budget shrinks too — the guard exists to
    prevent the compile-time failure, not to trust the caller."""
    import logging

    from replay_tpu.ops.fused_ce import _resolve_item_tile, _shrink_warned

    _shrink_warned.clear()
    with caplog.at_level(logging.WARNING, logger="replay_tpu"):
        shrunk = _resolve_item_tile(1_000_000, 16384, 512, 512)
    assert shrunk < 16384


def test_cefused_refuses_non_tying_head_model():
    """A model without the bias-free-head declaration cannot bind CEFused —
    it would silently train with a different loss than CE (advisor r3)."""
    import flax.linen as nn

    from replay_tpu.nn import Trainer
    from replay_tpu.nn.loss import CEFused

    class BiasedHead(nn.Module):
        # exposes get_item_weights but get_logits is NOT plain h . W^T
        def __call__(self, feature_tensors, padding_mask):
            return jnp.zeros((1, 4, 8))

        def get_logits(self, hidden, candidates_to_score=None):
            return jnp.zeros((1, 4, 10))

        def get_item_weights(self):
            return jnp.zeros((10, 8))

    trainer = Trainer(model=BiasedHead(), loss=CEFused())
    with pytest.raises(ValueError, match="logits_via_item_weights"):
        trainer._build_train_step()
