"""Each part of the window-and-full layer-pattern model against the plain
reference (``benchmark/reference/mellum_moe.py``) on seeded float32 weights at toy
size: the two kinds of attention layer (fused route, band, YaRN), the softmax
router, the eight expert shares against the uncut layer (guide section 4), the
untied head, and the whole model's loss, gradients and loads."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mellum_moe as reference
from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn.attention import (
    GroupedQueryAttention, rotary_arguments, rotary_embedding, yarn_correction_range, yarn_inv_freq,
)
from replay_tpu.nn.loss import CE
from replay_tpu.nn.moe import SparseExperts, route_softmax
from replay_tpu.nn.sequential import HybridRec

pytestmark = pytest.mark.jax

D, LENGTH, BATCH, ITEMS, WINDOW = 16, 24, 2, 30, 7
YARN = {"rope_type": "yarn", "rope_theta": 100, "factor": 4, "original_max_position_embeddings": 8,
        "beta_fast": 0.5, "beta_slow": 0.05, "attention_factor": 0.1 * math.log(4) + 1}
ROPE = {"full_attention": YARN, "sliding_attention": {"rope_type": "default", "rope_theta": 100}}
KINDS = ["sliding_attention", "sliding_attention", "full_attention"]
MODEL = {
    "embedding_dim": D, "num_items": ITEMS, "max_sequence_length": LENGTH, "norm_eps": 1e-6,
    "layers": {"layer_types": KINDS, "num_dense_layers": 0},
    "attention": {"num_heads": 4, "num_kv_heads": 2, "head_dim": 8, "rope_theta": 100.0,
                  "sliding_window": WINDOW, "rope_parameters": ROPE},
    "experts": {"num_experts": 64, "experts_held": 64, "expert_offset": 0,
                "experts_per_token": 8, "expert_dim": 8, "routed_scale": 1.0},
}
SHARE = {**MODEL, "experts": {**MODEL["experts"], "experts_held": 8, "expert_offset": 16}}
TOL = dict(rtol=2e-5, atol=2e-6)
PUBLISHED = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
             "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
             "attention_factor": 1.2772588722239782}


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda key: reference.init_params(MODEL, key))(jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def inputs():
    x = jax.random.normal(jax.random.PRNGKey(6), (BATCH, LENGTH, D), jnp.float32)
    padding = jnp.arange(LENGTH)[None, :] >= jnp.array([[0], [5]])  # left padding
    return x * padding[..., None], padding


def test_yarn_frequencies_against_values_worked_by_hand():
    """The published parameters: theta 5e5, D 128, s 16, L0 8192, beta 32 / 1.
    dim(32) = 128 ln(8192 / 64 pi) / (2 ln 5e5) = 18.08, dim(1) = 34.98."""
    assert yarn_correction_range(128, 500000, 8192, 32, 1) == (18, 35)
    freq = np.asarray(yarn_inv_freq(128, 500000, 16, 8192, 32, 1), np.float64)
    plain = 500000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(freq[:19], plain[:19], rtol=1e-6)  # fast pairs: untouched
    np.testing.assert_allclose(freq[35:], plain[35:] / 16, rtol=1e-6)  # slow pairs: interpolated
    # pair 26 sits 8/17 of the way: theta^(-26/64) * (9/17 + 8/17/16) = 2.7044e-3
    assert freq[26] == pytest.approx(500000.0 ** (-26 / 64) * (9 / 17 + 8 / 17 / 16), rel=1e-6)
    assert freq[26] == pytest.approx(2.7043824e-3, rel=1e-5)
    assert np.all(np.diff(freq) < 0)
    arguments = rotary_arguments(128, 500000, PUBLISHED)
    assert arguments["attention_factor"] == pytest.approx(0.1 * math.log(16) + 1, abs=1e-15)
    assert rotary_arguments(128, 500000, {**PUBLISHED, "attention_factor": None})[
        "attention_factor"] == pytest.approx(1.2772588722239782)
    np.testing.assert_array_equal(arguments["inv_freq"], yarn_inv_freq(128, 500000, 16, 8192))
    assert rotary_arguments(128, 5e5, {"rope_type": "default", "rope_theta": 5e5}) == {"theta": 5e5}
    assert rotary_arguments(128, 5e5, None) == {"theta": 5e5}
    with pytest.raises(ValueError, match="rope_type"):
        rotary_arguments(128, 5e5, {"rope_type": "linear"})
    ours, factor = reference.rotary_frequencies(
        {"head_dim": 128, "rope_parameters": {"full_attention": PUBLISHED}}, "full_attention")
    np.testing.assert_allclose(ours, arguments["inv_freq"], rtol=1e-7)
    assert factor == PUBLISHED["attention_factor"]


def test_rotary_from_a_frequency_vector_keeps_the_one_theta_result_bit_for_bit():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 10, 8))
    positions = jnp.arange(10)
    plain = rotary_embedding(x, positions, 100.0)
    inv_freq = 100.0 ** (-jnp.arange(4, dtype=jnp.float32) / 4)
    np.testing.assert_array_equal(plain, rotary_embedding(x, positions, inv_freq=inv_freq))
    scaled = rotary_embedding(x, positions, inv_freq=inv_freq, attention_factor=1.25)
    np.testing.assert_allclose(scaled, 1.25 * plain, rtol=1e-5, atol=1e-6)


def attention_params(weights, p):
    return {
        "query": {"kernel": weights[p + "wq"]}, "key": {"kernel": weights[p + "wk"]},
        "value": {"kernel": weights[p + "wv"]}, "out": {"kernel": weights[p + "wo"]},
        "q_norm": {"scale": weights[p + "q_norm.scale"]},
        "k_norm": {"scale": weights[p + "k_norm.scale"]},
    }


@pytest.mark.parametrize("layer,kind", [(0, "sliding_attention"), (2, "full_attention")])
def test_each_attention_kind_matches_the_reference_on_the_fused_route(weights, inputs, layer, kind):
    x, padding = inputs
    p = f"layers.{layer}.attn."
    module = GroupedQueryAttention(
        num_heads=4, num_kv_heads=2, head_dim=8, rope_theta=100.0, norm_eps=1e-6,
        window=WINDOW if kind == "sliding_attention" else None, rope_scaling=ROPE[kind],
    )
    out, counted = jax.jit(
        lambda params, x, m: module.apply({"params": params}, x, None, m, mutable=["counters"])
    )(attention_params(weights, p), x, padding)
    expected = jax.jit(lambda w, x, m: reference.attention_mixer(w, p, x, m, MODEL, kind, "f32"))(
        weights, x, padding
    )
    keep = np.asarray(padding)[..., None]
    np.testing.assert_allclose(out * keep, expected * keep, **TOL)
    # and each planted fault is another function
    fault = "no_window" if kind == "sliding_attention" else "no_yarn"
    other = jax.jit(
        lambda w, x, m: reference.attention_mixer(w, p, x, m, MODEL, kind, "f32", fault)
    )(weights, x, padding)
    assert float(jnp.abs((other - expected) * keep).max()) > 1e-2
    counters = counted["counters"]
    assert counters["attention_blocks_visited"].tolist() == [1, 1]  # one block holds 24 positions
    pairs = WINDOW * LENGTH - WINDOW * (WINDOW - 1) // 2 if kind == "sliding_attention" else LENGTH * (LENGTH + 1) // 2
    assert float(counters["attention_blocks_needed"]) == pytest.approx(pairs / LENGTH**2)


def test_a_window_needs_the_fused_route():
    layer = GroupedQueryAttention(num_heads=4, num_kv_heads=2, head_dim=8, window=3)
    x = jnp.zeros((1, 6, 16))
    with pytest.raises(ValueError, match="band"):
        layer.init(jax.random.PRNGKey(0), x, jnp.zeros((1, 1, 6, 6)))


def test_softmax_router_takes_the_top_k_of_p_and_renormalises():
    logits = jax.random.normal(jax.random.PRNGKey(3), (50, 64))
    selected, weights = route_softmax(logits, 8)
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    np.testing.assert_allclose(weights.sum(axis=-1), 1.0, rtol=1e-6)
    for row in range(50):
        assert set(np.asarray(selected[row]).tolist()) == set(np.argsort(-probs[row])[:8].tolist())
        picked = probs[row, np.asarray(selected[row])]
        np.testing.assert_allclose(weights[row], picked / picked.sum(), rtol=1e-5)


def moe_params(weights, p="layers.1.moe.", start=0, held=64):
    rows = slice(start, start + held)
    return {"router": {"kernel": weights[p + "router"]}, "gate": weights[p + "w1"][rows],
            "value": weights[p + "w3"][rows], "out": weights[p + "w2"][rows]}


def apply_share(params, x, padding, start, held=8):
    layer = SparseExperts(num_experts=64, experts_held=held, expert_offset=start, top_k=8,
                          hidden_dim=8, router="softmax")
    return jax.jit(lambda p, x, m: layer.apply({"params": p}, x, m, mutable=["counters"]))(
        params, x, padding
    )


def reference_share(weights, x, padding, model, fault=None):
    return jax.jit(
        lambda w, x, keep: reference.sparse_ffn(w, "layers.1.moe.", x, keep, model, "f32", fault)[0]
    )(weights, x, padding.astype(jnp.float32))


def test_the_eight_shares_of_the_softmax_router_sum_to_the_uncut_layer(weights, inputs):
    """Guide section 4: what every chip's share gives adds up to what the uncut
    reference gives for the whole layer (there is no shared expert to count once)."""
    x, padding = inputs
    whole = reference_share(weights, x, padding, MODEL)
    total, load = 0.0, 0
    for start in range(0, 64, 8):
        params = moe_params(weights, start=start, held=8)
        assert "expert_bias" not in params  # this router has no selection bias
        out, counted = apply_share(params, x, padding, start)
        total = total + out
        load += int(counted["counters"]["expert_load"].sum())
        if start == 16:  # one share against the reference given the same share
            sliced = {**weights, **{f"layers.1.moe.{w}": params[n]
                                    for w, n in (("w1", "gate"), ("w3", "value"), ("w2", "out"))}}
            np.testing.assert_allclose(out, reference_share(sliced, x, padding, SHARE), **TOL)
            assert int(counted["counters"]["dropped_assignments"]) == 0
    np.testing.assert_allclose(total, whole, **TOL)
    assert load == 8 * int(padding.sum())  # every assignment of every real token, once
    assert float(jnp.abs(whole).max()) > 0.1
    # without the renormalisation the layer's output is smaller by sum p[sel] < 1
    slack = reference_share(weights, x, padding, MODEL, fault="no_renorm")
    assert float(jnp.abs(slack).max()) < 0.9 * float(jnp.abs(whole).max())


# -- the whole model: HybridRec in this pattern against the reference ------------

SCHEMA = TensorSchema(TensorFeatureInfo(
    "item_id", FeatureType.CATEGORICAL, is_seq=True, feature_hint=FeatureHint.ITEM_ID,
    cardinality=ITEMS, embedding_dim=D))


def program_tree(weights, model):
    tree = {
        "embedder": {"embedding_item_id": {"table": {"embedding": weights["item_table"]}}},
        "output_table": weights["output_table"], "final_norm": {"scale": weights["final_norm.scale"]},
        "encoder": {},
    }
    for i in range(len(KINDS)):
        p = f"layers.{i}."
        tree["encoder"][f"layer_{i}"] = {
            "mixer_norm": {"scale": weights[p + "mixer_norm.scale"]},
            "ffn_norm": {"scale": weights[p + "ffn_norm.scale"]},
            "attention": attention_params(weights, p + "attn."),
            "moe": moe_params(weights, p + "moe.", model["experts"]["expert_offset"],
                              model["experts"]["experts_held"]),
        }
    return tree


def test_hybridrec_in_this_pattern_matches_the_reference_loss_gradients_and_loads(weights):
    model = HybridRec(
        schema=SCHEMA, layer_types=tuple(KINDS), num_dense_layers=0, num_heads=4, num_kv_heads=2,
        head_dim=8, rope_theta=100.0, rope_scaling=ROPE, sliding_window=WINDOW, fused_attention=True,
        expert_dim=8, num_experts=64, experts_held=8, expert_offset=16, experts_per_token=8,
        router="softmax", tie_embeddings=False, norm_eps=1e-6,
    )
    shared = {k: (v[16:24] if k.endswith(("moe.w1", "moe.w3", "moe.w2")) else v) for k, v in weights.items()}
    rng = np.random.default_rng(2)
    padding = np.arange(LENGTH)[None, :] >= np.array([[0], [6]])
    ids = np.where(padding, rng.integers(0, ITEMS, (BATCH, LENGTH)), ITEMS).astype(np.int32)
    labels = rng.integers(0, ITEMS, (BATCH, LENGTH)).astype(np.int32)
    batch = {"item_id": ids, "padding_mask": padding, "labels": labels, "target_mask": padding,
             "valid": np.ones(BATCH, bool)}
    want_loss, want_grads, want_loads = reference.first_step(shared, batch, SHARE, 2)

    loss = CE()

    def program_loss(params):
        hidden, counted = model.apply({"params": params}, {"item_id": ids}, padding, mutable=["counters"])
        loss.logits_callback = lambda h: model.apply({"params": params}, h, method=HybridRec.get_logits)
        value = loss(hidden, {}, labels[..., None], None, padding, padding[..., None])
        loads = [counted["counters"]["encoder"][f"layer_{i}"]["moe"]["expert_load"] for i in range(3)]
        return value, jnp.stack(loads)

    tree = program_tree(weights, SHARE)
    (got_loss, got_loads), got = jax.jit(jax.value_and_grad(program_loss, has_aux=True))(tree)
    assert float(got_loss) == pytest.approx(want_loss, rel=1e-5)
    np.testing.assert_array_equal(got_loads, want_loads)
    assert int(np.asarray(want_loads).sum()) > 0
    flat = {
        "item_table": got["embedder"]["embedding_item_id"]["table"]["embedding"],
        "output_table": got["output_table"], "layers.2.attn.wq": got["encoder"]["layer_2"]["attention"]["query"]["kernel"],
        "layers.0.attn.wk": got["encoder"]["layer_0"]["attention"]["key"]["kernel"],
        "layers.1.moe.router": got["encoder"]["layer_1"]["moe"]["router"]["kernel"],
        "layers.1.moe.w2": got["encoder"]["layer_1"]["moe"]["out"],
        "layers.0.mixer_norm.scale": got["encoder"]["layer_0"]["mixer_norm"]["scale"],
    }
    for name, leaf in flat.items():
        np.testing.assert_allclose(leaf, want_grads[name], rtol=2e-4, atol=2e-6, err_msg=name)
    # the untied head: the input table's gradient has rows for the items that came in
    # and nothing from the head; the output table takes the whole of the head's
    came_in = np.zeros(ITEMS + 1, bool)
    came_in[ids[padding]] = True
    moved = np.abs(np.asarray(flat["item_table"])).sum(axis=1) > 0
    assert not moved[~came_in].any() and moved[came_in].all() and not came_in.all()
    assert (np.abs(np.asarray(flat["output_table"])).sum(axis=1) > 0).all()
    np.testing.assert_array_equal(
        model.apply({"params": tree}, method=HybridRec.get_item_weights), weights["output_table"])
