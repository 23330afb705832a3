"""Tiled flash attention: parity with full attention at every shape class the
single-block kernel cannot reach (interpret mode — no TPU needed)."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from replay_tpu.ops.flash_tiled import (
    NEG_INF,
    flash_attention_tiled,
    padding_mask_bias,
)

pytestmark = pytest.mark.jax


def reference(q, k, v, padding_mask, causal):
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    s = jnp.where(padding_mask[:, None, None, :], s, NEG_INF)
    if causal:
        length = q.shape[2]
        tri = np.tril(np.ones((length, length), bool))
        s = jnp.where(tri[None, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    # rows with no valid key: define output 0 (the kernel's convention)
    dead = jnp.max(s, axis=-1, keepdims=True) <= NEG_INF / 2
    probs = jnp.where(dead, 0.0, probs)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "batch,heads,length,dim,block",
    [
        (2, 2, 16, 8, 8),     # multiple blocks, exact division
        (1, 1, 23, 8, 8),     # ragged: L % block != 0
        (2, 1, 7, 4, 16),     # single block bigger than L
        (1, 2, 65, 16, 32),   # ragged again, larger dim
    ],
)
def test_matches_reference(batch, heads, length, dim, block, causal):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(batch, heads, length, dim)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(batch, heads, length, dim)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(batch, heads, length, dim)).astype(np.float32))
    lengths = rng.integers(1, length + 1, batch)
    padding_mask = jnp.asarray(np.arange(length)[None, :] < lengths[:, None])
    got = jax.jit(flash_attention_tiled, static_argnums=(4, 5, 6, 7))(
        q, k, v, padding_mask_bias(padding_mask), causal, block, block, True
    )
    want = jax.jit(reference, static_argnums=4)(q, k, v, padding_mask, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_all_padded_batch_row_is_zero_and_finite():
    q = jnp.ones((1, 1, 8, 4), jnp.float32)
    mask = jnp.zeros((1, 8), bool)  # nothing valid
    out = flash_attention_tiled(q, q, q, padding_mask_bias(mask), True, 4, 4, True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    rng = np.random.default_rng(1)
    batch, heads, length, dim, block = 2, 2, 19, 8, 8
    q = jnp.asarray(rng.normal(size=(batch, heads, length, dim)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(batch, heads, length, dim)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(batch, heads, length, dim)).astype(np.float32))
    lengths = rng.integers(2, length + 1, batch)
    padding_mask = jnp.asarray(np.arange(length)[None, :] < lengths[:, None])
    bias = padding_mask_bias(padding_mask)

    def tiled_loss(q, k, v, bias):
        out = flash_attention_tiled(q, k, v, bias, causal, block, block, True)
        return jnp.sum(out**2)

    def ref_loss(q, k, v, bias):
        scale = 1.0 / np.sqrt(dim)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale + bias[:, None, None, :]
        if causal:
            tri = np.tril(np.ones((length, length), bool))
            s = jnp.where(tri[None, None], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        return jnp.sum(out**2)

    # dbias included: the kv_bias cotangent is part of the custom VJP
    got = jax.jit(jax.grad(tiled_loss, argnums=(0, 1, 2, 3)))(q, k, v, bias)
    want = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2, 3)))(q, k, v, bias)
    for g, w, name in zip(got, want, ["q", "k", "v", "bias"]):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5, err_msg=name
        )


def test_long_sequence_runs_blockwise():
    """L=2048 — the single-block kernel's OOM regime — streams through
    fixed-size blocks (interpret mode checks indexing, not memory)."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 1, 2048, 8)).astype(np.float32))
    mask = jnp.ones((1, 2048), bool)
    out = flash_attention_tiled(q, q, q, padding_mask_bias(mask), True, 256, 256, True)
    assert out.shape == (1, 1, 2048, 8)
    # causal row 0 attends only to itself: output == v[0]
    np.testing.assert_allclose(
        np.asarray(out[0, 0, 0]), np.asarray(q[0, 0, 0]), rtol=1e-5
    )


@pytest.mark.parametrize("model_kind", ["sasrec", "bert4rec", "twotower"])
def test_model_tiled_route_matches_default(model_kind):
    """use_flash='tiled' through the REAL model API (mask never materialized)
    equals the default path on real rows — the production long-L entry point."""
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn.sequential.bert4rec import Bert4Rec
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.nn.sequential.twotower import TwoTower

    num_items, seq_len = 12, 10
    schema = TensorSchema(TensorFeatureInfo(
        "item_id", FeatureType.CATEGORICAL, is_seq=True,
        feature_hint=FeatureHint.ITEM_ID, cardinality=num_items, embedding_dim=8))
    cls = {"sasrec": SasRec, "bert4rec": Bert4Rec, "twotower": TwoTower}[model_kind]
    kwargs = dict(schema=schema, embedding_dim=8, num_blocks=2, num_heads=2,
                  max_sequence_length=seq_len)
    plain = cls(**kwargs)
    tiled = cls(**kwargs, use_flash="tiled")

    rng = np.random.default_rng(0)
    ids = np.full((3, seq_len), num_items, np.int32)
    lengths = rng.integers(2, seq_len + 1, 3)
    for b, n in enumerate(lengths):
        ids[b, seq_len - n:] = rng.integers(0, num_items, n)
    mask = ids != num_items
    params = jax.jit(plain.init)(jax.random.PRNGKey(0), {"item_id": ids}, mask)["params"]

    want = jax.jit(plain.apply)({"params": params}, {"item_id": ids}, mask)
    got = jax.jit(tiled.apply)({"params": params}, {"item_id": ids}, mask)
    # padded rows differ only by the diagonal-rescue convention and are zeroed
    # by the keep-mask between blocks; real rows must match
    np.testing.assert_allclose(
        np.asarray(got)[np.asarray(mask)], np.asarray(want)[np.asarray(mask)],
        rtol=2e-5, atol=2e-5,
    )


@pytest.mark.parametrize("flash", [False, "tiled"])
def test_remat_trains_with_each_attention_route(flash):
    """remat=True (jax.checkpoint over blocks, static_argnums covering the
    deterministic + causal flags) trains through both attention routes —
    previously uncovered."""
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn import OptimizerFactory, Trainer
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.sasrec import SasRec

    schema = TensorSchema(TensorFeatureInfo(
        "item_id", FeatureType.CATEGORICAL, is_seq=True,
        feature_hint=FeatureHint.ITEM_ID, cardinality=12, embedding_dim=8))
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1,
                   max_sequence_length=6, remat=True, use_flash=flash)
    trainer = Trainer(model=model, loss=CE(),
                      optimizer=OptimizerFactory(name="sgd", learning_rate=0.1))
    rng = np.random.default_rng(0)
    items = rng.integers(0, 12, (4, 7)).astype(np.int32)
    mask = np.ones((4, 6), bool)
    batch = {"feature_tensors": {"item_id": items[:, :-1]}, "padding_mask": mask,
             "positive_labels": items[:, 1:, None], "target_padding_mask": mask[:, :, None]}
    state = trainer.init_state(batch)
    losses = []
    for _ in range(4):
        state, loss_value = trainer.train_step(state, batch)
        losses.append(float(loss_value))
    assert losses[-1] < losses[0]


def test_tiled_misuse_guards():
    """Silent-misconfiguration guards: diff encoder + tiled raises at init,
    and a custom additive mask cannot be silently dropped."""
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn.attention import dot_product_attention
    from replay_tpu.nn.sequential.sasrec import SasRec

    schema = TensorSchema(TensorFeatureInfo(
        "item_id", FeatureType.CATEGORICAL, is_seq=True,
        feature_hint=FeatureHint.ITEM_ID, cardinality=8, embedding_dim=8))
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1,
                   max_sequence_length=4, encoder_type="diff", use_flash="tiled")
    with pytest.raises(ValueError, match="tiled"):
        model.init(jax.random.PRNGKey(0), {"item_id": np.zeros((1, 4), np.int32)},
                   np.ones((1, 4), bool))

    q = jnp.ones((1, 1, 4, 4), jnp.float32)
    with pytest.raises(ValueError, match="padding_mask"):
        dot_product_attention(q, q, q, None, use_flash="tiled")
    with pytest.raises(ValueError, match="additive mask"):
        dot_product_attention(q, q, q, jnp.zeros((1, 1, 4, 4)), use_flash="tiled",
                              padding_mask=jnp.ones((1, 4), bool))



# ---------------------------------------------------------------------------
# grouped-query heads and the band (0 <= i - j < window) on the same route


def banded_reference(q, k, v, bias, window):
    """Plain banded softmax with a materialised mask; key/value head h // group."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    length = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1]) + bias[:, None, None, :]
    distance = np.arange(length)[:, None] - np.arange(length)[None, :]
    seen = (distance >= 0) & (distance < (length if window is None else window))
    s = jnp.where(seen[None, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    probs = jnp.where(jnp.max(s, axis=-1, keepdims=True) <= NEG_INF / 2, 0.0, probs)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def banded_inputs(length, heads=4, kv_heads=2, dim=8, batch=2, padded=True, seed=3):
    rng = np.random.default_rng(seed)
    draw = lambda h: jnp.asarray(rng.normal(size=(batch, h, length, dim)).astype(np.float32))  # noqa: E731
    # left padding, as the batcher makes it: a real query always sees itself
    starts = rng.integers(1, length // 2, batch) if padded else np.zeros(batch, int)
    mask = jnp.asarray(np.arange(length)[None, :] >= starts[:, None])
    return draw(heads), draw(kv_heads), draw(kv_heads), mask


def output_and_grads(attention, weight, *args):
    """``attention(*args)`` and the gradients of ``sum(attention(*args) * weight)``
    to every argument, as one program."""

    @jax.jit
    def run(weight, *args):
        out, pull = jax.vjp(attention, *args)
        return out, pull(weight)

    return run(weight, *args)


# (length, block, window): smaller than / equal to / no multiple of the block,
# window >= L (the causal route), L no multiple of the block, no window at all
BANDS = [(32, 8, 3), (32, 8, 8), (32, 8, 13), (32, 8, 32), (32, 8, 100), (29, 8, 11),
         (29, 8, None), (21, 16, 5)]


@pytest.mark.parametrize("length,block,window", BANDS)
def test_grouped_banded_route_matches_the_plain_banded_softmax(length, block, window):
    q, k, v, mask = banded_inputs(length)
    bias = padding_mask_bias(mask)

    def fused(q, k, v, bias):
        return flash_attention_tiled(q, k, v, bias, True, block, block, True, window)

    keep = np.asarray(mask)[:, None, :, None]  # padded query rows are zeroed by the model
    weight = jnp.asarray(np.random.default_rng(4).normal(size=q.shape).astype(np.float32)) * keep
    got, grads = output_and_grads(fused, weight, q, k, v, bias)
    want, wanted = output_and_grads(partial(banded_reference, window=window), weight, q, k, v, bias)
    np.testing.assert_allclose(got * keep, want * keep, rtol=2e-5, atol=2e-5)
    for g, w, name in zip(grads, wanted, "qkvb"):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5, err_msg=name)


def test_a_window_past_the_sequence_is_the_causal_route_bit_for_bit():
    q, k, v, mask = banded_inputs(32, heads=2, kv_heads=2)
    bias = padding_mask_bias(mask)
    causal = flash_attention_tiled(q, k, v, bias, True, 8, 8, True)
    np.testing.assert_array_equal(
        np.asarray(flash_attention_tiled(q, k, v, bias, True, 8, 8, True, 32)), np.asarray(causal)
    )
    with pytest.raises(ValueError, match="causal band"):
        flash_attention_tiled(q, k, v, bias, False, 8, 8, True, 4)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention_tiled(q, k[:, :1].repeat(3, 1), v[:, :1].repeat(3, 1), bias, True, 8, 8, True)


@pytest.mark.parametrize("window", [8, 13, None], ids=lambda w: f"window{w}")
def test_blocks_outside_the_band_are_never_multiplied(window):
    """The witness a route that MASKS the band instead of skipping it fails: for
    each query block, every key and value block the schedule leaves out is NaN. A
    product with such a block poisons the output (0 * NaN) whatever mask follows;
    the route's outputs and its query gradient stay what they were."""
    from replay_tpu.ops.flash_tiled import kv_block_range

    length, block = 48, 8
    q, k, v, mask = banded_inputs(length, padded=False)
    bias = padding_mask_bias(mask)

    @jax.jit
    def fused(q, k, v):
        return flash_attention_tiled(q, k, v, bias, True, block, block, True, window)

    # the query gradient of the loss that reads the query rows ``only`` weighs
    dq_of = jax.jit(jax.grad(lambda q, k, v, only: jnp.sum(fused(q, k, v) ** 2 * only)))
    clean = fused(q, k, v)
    clean_dq = dq_of(q, k, v, jnp.ones_like(q))
    first, last = kv_block_range(np.arange(length // block), block, block, length // block, True, window, np)
    for i in (0, 2, 5):
        outside = np.ones(length, bool)
        outside[first[i] * block : (last[i] + 1) * block] = False
        poison = lambda t: jnp.where(outside[None, None, :, None], jnp.nan, t)  # noqa: E731
        rows = slice(i * block, (i + 1) * block)
        out = fused(q, poison(k), poison(v))
        np.testing.assert_array_equal(np.asarray(out[:, :, rows]), np.asarray(clean[:, :, rows]))
        only = jnp.zeros_like(q).at[:, :, rows].set(1.0)  # the loss reads this query block alone
        dq = dq_of(q, poison(k), poison(v), only)
        np.testing.assert_allclose(dq[:, :, rows], clean_dq[:, :, rows], rtol=1e-5, atol=1e-6)
    if window is not None:
        assert (last - first + 1).max() <= -(-(window - 1) // block) + 1 < length // block


@pytest.mark.parametrize(
    "length,block,window,visited,needed",
    [
        (8192, 256, 1024, 150, 7_864_832),  # 1 + 2 + 3 + 4 + 28 x 5 blocks: 1.25 of the band
        (8192, 512, 1024, 45, 7_864_832),   # 1 + 2 + 14 x 3: 1.5 of the band
        (8192, 512, None, 136, 33_558_528),  # the causal half square
        (8192, 256, 8192, 528, 33_558_528),
        (50, 256, 7, 1, 7 * 50 - 21),       # one block holds the sequence
    ],
)
def test_block_counts_of_the_schedule(length, block, window, visited, needed):
    from replay_tpu.ops.flash_tiled import block_counts

    counted = block_counts(length, block, block, True, window)
    assert counted["visited"] == visited and counted["needed"] == needed
    assert counted["block_area"] == min(block, length) ** 2
    # brute force over every pair at a size where that is cheap
    small = block_counts(61, 8, 8, True, 13)
    distance = np.arange(61)[:, None] - np.arange(61)[None, :]
    seen = (distance >= 0) & (distance < 13)
    assert small["needed"] == seen.sum()
    touched = {(i // 8, j // 8) for i, j in zip(*np.nonzero(seen))}
    assert small["visited"] == len(touched)  # every block visited holds a visible pair, and no other


@pytest.mark.parametrize("block_q,block_k", [(8, 8), (16, 8), (8, 16)])
@pytest.mark.parametrize("window", [5, 16, 23, None])
def test_both_backward_kernels_walk_the_forwards_pairs(block_q, block_k, window):
    """dq lists the pairs by query block, dk/dv by kv block: the same set."""
    from replay_tpu.ops.flash_tiled import kv_block_range, q_block_range

    num_q, num_k = -(-61 // block_q), -(-61 // block_k)
    first, last = kv_block_range(np.arange(num_q), block_q, block_k, num_k, True, window, np)
    by_query = {(i, j) for i in range(num_q) for j in range(first[i], last[i] + 1)}
    first, last = q_block_range(np.arange(num_k), block_q, block_k, num_q, True, window, np)
    by_key = {(i, j) for j in range(num_k) for i in range(first[j], last[j] + 1)}
    assert by_query == by_key and len(by_query) < num_q * num_k


# ---------------------------------------------------------------------------
# value (and output, and cotangent) width apart from the query/key width: latent
# attention scores over 192 = 128 + a 64-wide rotary part and mixes values of 128


def narrow_value_inputs(length, dim, value_dim, heads, kv_heads, seed=5):
    q, k, _, mask = banded_inputs(length, heads=heads, kv_heads=kv_heads, dim=dim, seed=seed)
    v = banded_inputs(length, heads=heads, kv_heads=kv_heads, dim=value_dim, seed=seed + 1)[2]
    return q, k, v, mask


# (length, block, window, key width, value width, heads, key/value heads)
WIDTHS = [(32, 8, None, 12, 8, 4, 4), (29, 8, 13, 12, 8, 4, 2), (21, 16, 5, 8, 16, 2, 1)]


@pytest.mark.parametrize("length,block,window,dim,value_dim,heads,kv_heads", WIDTHS)
def test_value_width_apart_from_key_width_matches_the_dense_formula(
    length, block, window, dim, value_dim, heads, kv_heads
):
    q, k, v, mask = narrow_value_inputs(length, dim, value_dim, heads, kv_heads)
    bias = padding_mask_bias(mask)

    def fused(q, k, v, bias):
        return flash_attention_tiled(q, k, v, bias, True, block, block, True, window)

    keep = np.asarray(mask)[:, None, :, None]
    weight = jnp.asarray(np.random.default_rng(4).normal(size=(*q.shape[:-1], value_dim)).astype(np.float32))
    weight = weight * keep
    got, grads = output_and_grads(fused, weight, q, k, v, bias)
    assert got.shape == (*q.shape[:-1], value_dim)
    want, wanted = output_and_grads(partial(banded_reference, window=window), weight, q, k, v, bias)
    np.testing.assert_allclose(got * keep, want * keep, rtol=2e-5, atol=2e-5)
    for g, w, name in zip(grads, wanted, "qkvb"):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("window", [None, 13], ids=lambda w: f"window{w}")
def test_narrow_values_equal_the_equal_width_route_on_zero_padded_values(window):
    """The equal-width call is the route as it was (one width for every block
    spec); what the narrow call adds must give, bit for bit, what that route
    gives for values padded with zero columns up to the key width."""
    q, k, v, mask = narrow_value_inputs(32, 12, 8, 4, 2)
    bias = padding_mask_bias(mask)
    padded = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, 4)))
    route = jax.jit(lambda q, k, values: flash_attention_tiled(q, k, values, bias, True, 8, 8, True, window))
    weight = jnp.asarray(np.random.default_rng(6).normal(size=(*q.shape[:-1], 8)).astype(np.float32))
    # each width's gradients of the loss that reads the first 8 columns of the output
    gradients = jax.jit(jax.grad(
        lambda q, k, values: jnp.sum(route(q, k, values)[..., :8] * weight), argnums=(0, 1, 2)
    ))
    narrow, wide = gradients(q, k, v), gradients(q, k, padded)
    np.testing.assert_array_equal(np.asarray(route(q, k, v)), np.asarray(route(q, k, padded))[..., :8])
    for g, w in zip(narrow, (wide[0], wide[1], wide[2][..., :8])):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="contract over one width"):
        flash_attention_tiled(q, k[..., :8], v, bias, True, 8, 8, True, window)
