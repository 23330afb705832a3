"""Each layer kind of the layer-pattern model against the plain reference
(``benchmark/reference/lfm2_moe.py``) on seeded float32 weights at toy size, the
eight expert shares against the uncut layer (guide section 4), and the properties
each kind is there for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as reference
from replay_tpu.nn.attention import GroupedQueryAttention, dot_product_attention, rotary_embedding
from replay_tpu.nn.conv import GatedShortConv
from replay_tpu.nn.ffn import SwiGLU
from replay_tpu.nn.mask import causal_attention_mask
from replay_tpu.nn.moe import SparseExperts, route

D, LENGTH, BATCH = 16, 12, 3
MODEL = {
    "embedding_dim": D, "num_items": 30, "max_sequence_length": LENGTH, "ffn_dim": 24,
    "norm_eps": 1e-5, "conv": {"kernel": 3},
    "layers": {"layer_types": ["conv", "full_attention"], "num_dense_layers": 1},
    "attention": {"num_heads": 4, "num_kv_heads": 2, "head_dim": 8, "rope_theta": 1e6},
    "experts": {"num_experts": 64, "experts_held": 64, "expert_offset": 0,
                "experts_per_token": 4, "expert_dim": 8, "routed_scale": 1.0},
}
TOL = dict(rtol=2e-5, atol=2e-6)


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda key: reference.init_params(MODEL, key))(jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def inputs():
    x = jax.random.normal(jax.random.PRNGKey(6), (BATCH, LENGTH, D), jnp.float32)
    padding = jnp.arange(LENGTH)[None, :] >= jnp.array([[0], [4], [9]])  # left padding
    return x * padding[..., None], padding


def moe_params(weights, p="layers.1.moe.", start=0, held=64):
    rows = slice(start, start + held)
    return {
        "router": {"kernel": weights[p + "router"]}, "expert_bias": weights[p + "bias"],
        "gate": weights[p + "w1"][rows], "value": weights[p + "w3"][rows],
        "out": weights[p + "w2"][rows],
    }


def experts_layer(start=0, held=64):
    return SparseExperts(num_experts=64, experts_held=held, expert_offset=start, top_k=4,
                         hidden_dim=8)


def apply_share(params, x, padding, start, held=8):
    """(output, counters) of the share ``start .. start + held - 1``, as one program."""
    return jax.jit(
        lambda p, x, m: experts_layer(start, held).apply({"params": p}, x, m, mutable=["counters"])
    )(params, x, padding)


def reference_share(weights, x, padding, model):
    return jax.jit(
        lambda w, x, keep: reference.sparse_ffn(w, "layers.1.moe.", x, keep, model, "f32")[0]
    )(weights, x, padding.astype(jnp.float32))


def test_conv_mixer_matches_the_reference_and_is_causal(weights, inputs):
    x, _ = inputs
    p = "layers.0.conv."
    params = {"in_proj": {"kernel": weights[p + "w_in"]}, "kernel": weights[p + "kernel"],
              "out_proj": {"kernel": weights[p + "w_out"]}}
    layer = GatedShortConv(3)
    out = layer.apply({"params": params}, x)
    np.testing.assert_allclose(out, reference.conv_mixer(weights, p, x, "f32"), **TOL)
    # the output at t does not move when the inputs after t do
    cut = 7
    later = x.at[:, cut + 1 :].set(jax.random.normal(jax.random.PRNGKey(9), x[:, cut + 1 :].shape))
    moved = layer.apply({"params": params}, later)
    np.testing.assert_array_equal(np.asarray(out[:, : cut + 1]), np.asarray(moved[:, : cut + 1]))
    assert not np.allclose(out[:, cut + 1 :], moved[:, cut + 1 :])
    # and it reaches back exactly kernel - 1 positions
    nudged = layer.apply({"params": params}, x.at[:, cut].add(1.0))
    changed = np.abs(np.asarray(nudged - out)).max(axis=(0, 2)) > 0
    assert changed.tolist() == [cut <= t <= cut + 2 for t in range(LENGTH)]


def test_grouped_query_rotary_attention_matches_the_reference(weights, inputs):
    x, padding = inputs
    p = "layers.1.attn."
    params = {
        "query": {"kernel": weights[p + "wq"]}, "key": {"kernel": weights[p + "wk"]},
        "value": {"kernel": weights[p + "wv"]}, "out": {"kernel": weights[p + "wo"]},
        "q_norm": {"scale": weights[p + "q_norm.scale"]},
        "k_norm": {"scale": weights[p + "k_norm.scale"]},
    }
    layer = GroupedQueryAttention(num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=1e6,
                                  norm_eps=1e-5)
    out = jax.jit(layer.apply)({"params": params}, x, causal_attention_mask(padding))
    expected = jax.jit(lambda w, x, m: reference.attention_mixer(w, p, x, m, MODEL, "f32"))(
        weights, x, padding
    )
    keep = np.asarray(padding)[..., None]
    np.testing.assert_allclose(out * keep, expected * keep, **TOL)


def test_grouped_heads_equal_repeated_key_value_heads():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, h, 6, 8)) for i, h in ((1, 4), (2, 2), (3, 2)))
    mask = causal_attention_mask(jnp.ones((2, 6), bool))
    attend = jax.jit(dot_product_attention)
    grouped = attend(q, k, v, mask)
    repeated = attend(q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1), mask)
    np.testing.assert_allclose(grouped, repeated, **TOL)
    with pytest.raises(ValueError, match="do not divide"):
        dot_product_attention(q[:, :3], k, v, mask)


def test_rotary_scores_depend_on_the_distance_only():
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (1, 1, 1, 8)) for i in (1, 2))
    score = lambda a, b: jnp.sum(  # noqa: E731
        rotary_embedding(q, jnp.array([a]), 1e4) * rotary_embedding(k, jnp.array([b]), 1e4)
    )
    assert float(score(7, 3)) == pytest.approx(float(score(104, 100)), rel=1e-4)
    assert float(score(7, 3)) != pytest.approx(float(score(7, 4)), rel=1e-3)


def test_dense_swiglu_matches_the_reference(weights, inputs):
    x, _ = inputs
    p = "layers.0.ffn."
    params = {"gate": {"kernel": weights[p + "w1"]}, "value": {"kernel": weights[p + "w3"]},
              "out": {"kernel": weights[p + "w2"]}}
    out = SwiGLU(24, D).apply({"params": params}, x)
    expected = reference._swiglu(x, weights[p + "w1"], weights[p + "w3"], weights[p + "w2"], "f32")
    np.testing.assert_allclose(out, expected, **TOL)


@pytest.mark.parametrize("start", range(0, 64, 8))
def test_one_share_of_the_experts_matches_the_reference_given_the_same_share(weights, inputs, start):
    x, padding = inputs
    share = {**MODEL, "experts": {**MODEL["experts"], "experts_held": 8, "expert_offset": start}}
    params = moe_params(weights, start=start, held=8)
    out, counted = apply_share(params, x, padding, start)
    sliced = {**weights, **{f"layers.1.moe.{w}": params[n] for w, n in (("w1", "gate"), ("w3", "value"), ("w2", "out"))}}
    expected = reference_share(sliced, x, padding, share)
    np.testing.assert_allclose(out, expected, **TOL)
    selected, _ = reference.routing(weights, "layers.1.moe.", x, MODEL)
    local = np.asarray(selected)[np.asarray(padding)] - start
    expected_load = [(local == e).sum() for e in range(8)]
    assert counted["counters"]["expert_load"].tolist() == expected_load
    assert int(counted["counters"]["dropped_assignments"]) == 0


def test_the_eight_shares_sum_to_the_uncut_layer(weights, inputs):
    """Guide section 4: what every chip's share gives adds up to what the uncut
    reference gives for the whole layer (there is no shared expert to count once)."""
    x, padding = inputs
    whole = reference_share(weights, x, padding, MODEL)
    total, load = 0.0, 0
    for start in range(0, 64, 8):
        out, counted = apply_share(moe_params(weights, start=start, held=8), x, padding, start)
        total = total + out
        load += int(counted["counters"]["expert_load"].sum())
    np.testing.assert_allclose(total, whole, **TOL)
    assert load == 4 * int(padding.sum())  # every assignment of every real token, once
    assert float(jnp.abs(whole).max()) > 0.1


def test_the_expert_bias_moves_the_selection_and_not_the_weights():
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3), (50, 16)))
    plain_sel, plain_w = route(scores, jnp.zeros(16), 4)
    bias = jnp.zeros(16).at[5].set(10.0)
    sel, w = route(scores, bias, 4)
    assert (np.asarray(sel) == 5).any(axis=-1).all() and not (np.asarray(plain_sel) == 5).any(axis=-1).all()
    picked = np.take_along_axis(np.asarray(scores), np.asarray(sel), axis=-1)
    np.testing.assert_allclose(w, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-4)
    assert np.asarray(plain_w).shape == (50, 4)


def test_the_reference_that_forgets_the_bias_selects_other_experts(weights, inputs):
    x, _ = inputs
    selected, _ = reference.routing(weights, "layers.1.moe.", x, MODEL)
    forgotten, _ = reference.routing(weights, "layers.1.moe.", x, MODEL, fault="no_bias")
    changed = np.any(np.sort(selected, -1) != np.sort(forgotten, -1), axis=-1).mean()
    assert 0.02 < changed < 0.9  # the seeded bias is non-zero, and does not pick alone


def test_no_assignment_is_dropped_when_the_router_is_forced_onto_one_expert(weights, inputs):
    x, padding = inputs
    forced = {**weights, "layers.1.moe.bias": jnp.zeros(64).at[3].set(100.0)}
    share = {**MODEL, "experts": {**MODEL["experts"], "experts_held": 8}}
    params = moe_params(forced, held=8)
    out, counted = apply_share(params, x, padding, 0)
    load = counted["counters"]["expert_load"]
    assert int(load[3]) == int(padding.sum())  # every real token, on the one expert
    assert int(counted["counters"]["dropped_assignments"]) == 0
    sliced = {**forced, **{f"layers.1.moe.{w}": params[n] for w, n in (("w1", "gate"), ("w3", "value"), ("w2", "out"))}}
    np.testing.assert_allclose(out, reference_share(sliced, x, padding, share), **TOL)
    # the gradient reaches the experts' kernels and the router, never the bias
    grads = jax.jit(jax.grad(
        lambda p: jnp.sum(experts_layer(0, 8).apply({"params": p}, x, padding) ** 2)
    ))(params)
    assert float(jnp.abs(grads["expert_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["gate"][3]).max()) > 0 and float(jnp.abs(grads["router"]["kernel"]).max()) > 0


@pytest.mark.parametrize(
    "row,sizes,dropped",
    [
        ([0, 1, 2, 3, 4, 5], [2, 1, 1], 0),  # the sort, the sizes and the way back agree
        ([0, 1, 3, 2, 4, 5], [2, 1, 1], 2),  # the way back swaps two experts' rows
        ([0, 1, 2, 3, 4, 5], [1, 1, 1], 3),  # an expert's kernel is given a row too few
        ([4, 1, 2, 3, 0, 5], [2, 1, 1], 1),  # a held assignment is fetched from a dead row
    ],
    ids=["agree", "swapped", "short_group", "dead_row"],
)
def test_the_dropped_counter_reads_the_way_back_against_the_groups(row, sizes, dropped):
    """Six assignments in sorted order: experts 0, 0, 1, 2 held here, two absent."""
    from replay_tpu.nn.moe import unserved

    slot = jnp.array([0, 0, 1, 2, 3, 3])  # 3: not held here
    held_here = jnp.array([True, True, True, True, False, False])
    assert int(unserved(slot, held_here, jnp.array(row), jnp.array(sizes))) == dropped


def test_experts_outside_the_layer_are_refused():
    with pytest.raises(ValueError, match="not among"):
        experts_layer(60, 8).init(jax.random.PRNGKey(0), jnp.zeros((2, 4, D)))
