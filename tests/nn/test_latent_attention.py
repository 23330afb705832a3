"""The latent-attention layer-pattern model against the plain reference
(``benchmark/reference/moonlight_moe.py``) on seeded float32 weights at toy size:
the mixer (key width 12 = 8 + a 4-wide rotary part over values of 8, on the fused
route), the shared expert beside the routed share in ``PatternBlock``, the eight
expert shares plus the shared expert counted ONCE against the uncut layer (guide
section 4), and the whole model's loss, gradients and loads. The pattern through
``Trainer.fit`` with its counters in the step metrics is the toy cell of
``tests/benchmark/test_benchmark_latent.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import moonlight_moe as reference
from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn.attention import LatentAttention
from replay_tpu.nn.blocks import MIXERS, PatternBlock
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential import HybridRec

pytestmark = pytest.mark.jax

D, LENGTH, BATCH, ITEMS = 16, 24, 2, 30
KINDS = ["latent_attention"] * 3
LATENT = {"num_heads": 4, "kv_latent_dim": 12, "nope_head_dim": 8, "rope_head_dim": 4,
          "value_head_dim": 8, "rope_theta": 100.0}
MODEL = {
    "embedding_dim": D, "num_items": ITEMS, "max_sequence_length": LENGTH, "norm_eps": 1e-5,
    "ffn_dim": 40, "layers": {"layer_types": KINDS, "num_dense_layers": 1},
    "latent_attention": LATENT, "shared_experts": {"num_shared_experts": 2, "shared_expert_dim": 12},
    "experts": {"num_experts": 64, "experts_held": 64, "expert_offset": 0,
                "experts_per_token": 6, "expert_dim": 8, "routed_scale": 2.446},
}
SHARE = {**MODEL, "experts": {**MODEL["experts"], "experts_held": 8, "expert_offset": 16}}
TOL = dict(rtol=2e-5, atol=2e-6)
MIXER = dict(num_heads=4, latent_dim=12, nope_head_dim=8, rope_head_dim=4, value_head_dim=8,
             rope_theta=100.0, norm_eps=1e-5)
BLOCK = dict(
    mixer="latent_attention", sparse=True, num_heads=4, num_kv_heads=4, head_dim=8, rope_theta=100.0,
    conv_kernel=3, dense_dim=40, expert_dim=8, num_experts=64, experts_per_token=6,
    routed_scale=2.446, norm_eps=1e-5, kv_latent_dim=12, rope_head_dim=4, value_head_dim=8,
)


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda key: reference.init_params(MODEL, key))(jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def inputs():
    x = jax.random.normal(jax.random.PRNGKey(6), (BATCH, LENGTH, D), jnp.float32)
    padding = jnp.arange(LENGTH)[None, :] >= jnp.array([[0], [5]])  # left padding
    return x * padding[..., None], padding


def attention_params(weights, p):
    return {
        "query": {"kernel": weights[p + "wq"]}, "kv_down": {"kernel": weights[p + "wkv_a"]},
        "kv_norm": {"scale": weights[p + "kv_norm.scale"]},
        "kv_up": {"kernel": weights[p + "wkv_b"]}, "out": {"kernel": weights[p + "wo"]},
    }


def swiglu_params(weights, p):
    return {"gate": {"kernel": weights[p + "w1"]}, "value": {"kernel": weights[p + "w3"]},
            "out": {"kernel": weights[p + "w2"]}}


def moe_params(weights, p, start=0, held=64):
    rows = slice(start, start + held)
    return {"router": {"kernel": weights[p + "router"]}, "expert_bias": weights[p + "bias"],
            "gate": weights[p + "w1"][rows], "value": weights[p + "w3"][rows], "out": weights[p + "w2"][rows]}


def block_params(weights, i, start=0, held=64, shared=True):
    p = f"layers.{i}."
    tree = {
        "mixer_norm": {"scale": weights[p + "mixer_norm.scale"]},
        "ffn_norm": {"scale": weights[p + "ffn_norm.scale"]},
        "attention": attention_params(weights, p + "attn."),
    }
    if p + "ffn.w1" in weights:
        tree["dense_ffn"] = swiglu_params(weights, p + "ffn.")
    else:
        tree["moe"] = moe_params(weights, p + "moe.", start, held)
        if shared:
            tree["shared_expert"] = swiglu_params(weights, p + "shared.")
    return tree


@pytest.fixture(scope="module")
def mixed(weights, inputs):
    """The program's mixer and the reference's, on layer 1's weights."""
    x, padding = inputs
    out, counted = jax.jit(
        lambda params, x, m: LatentAttention(**MIXER).apply({"params": params}, x, m, mutable=["counters"])
    )(attention_params(weights, "layers.1.attn."), x, padding)
    expected = jax.jit(
        lambda w, x, m: reference.attention_mixer(w, "layers.1.attn.", x, m, MODEL, "f32")
    )(weights, x, padding)
    return out, counted["counters"], expected


def test_latent_attention_matches_the_references_mixer_on_the_fused_route(mixed, inputs):
    out, counters, expected = mixed
    keep = np.asarray(inputs[1])[..., None]
    assert out.shape == (BATCH, LENGTH, D) and float(jnp.abs(expected * keep).max()) > 0.1
    np.testing.assert_allclose(out * keep, expected * keep, **TOL)
    assert counters["attention_blocks_visited"].tolist() == [1, 1]  # one block holds 24 positions
    assert float(counters["attention_blocks_needed"]) == pytest.approx(LENGTH * (LENGTH + 1) / 2 / LENGTH**2)
    assert "latent_attention" in MIXERS


@pytest.mark.parametrize("fault", ["no_rope_key", "no_latent_norm", "scale_128"])
def test_each_fault_planted_in_the_references_mixer_moves_it(weights, inputs, mixed, fault):
    """A zeroed rotary key, a missing latent norm and scores over sqrt(8) instead
    of sqrt(12) are each another function, by far more than the program is off."""
    x, padding = inputs
    other = jax.jit(
        lambda w, x, m: reference.attention_mixer(w, "layers.1.attn.", x, m, MODEL, "f32", fault)
    )(weights, x, padding)
    keep = np.asarray(padding)[..., None]
    assert float(jnp.abs((other - mixed[2]) * keep).max()) > 1e-2
    assert float(jnp.abs((mixed[0] - mixed[2]) * keep).max()) < 1e-4


def apply_block(params, x, padding, start, held, shared_dim):
    block = PatternBlock(**BLOCK, experts_held=held, expert_offset=start, shared_expert_dim=shared_dim)
    return jax.jit(lambda p, x, m: block.apply({"params": p}, x, None, m, mutable=["counters"]))(
        params, x, padding
    )


def reference_block(weights, x, padding, model, fault=None):
    """Layer 1 of the reference: mixer, then the sparse layer (routed + shared)."""

    def block(w, x, padding):
        keep = padding.astype(jnp.float32)
        p = "layers.1."
        h = reference._rms(x, w[p + "mixer_norm.scale"], 1e-5)
        x = x + reference.attention_mixer(w, p + "attn.", h, padding, model, "f32")
        h = reference._rms(x, w[p + "ffn_norm.scale"], 1e-5)
        out, load = reference.sparse_layer(w, p, h, keep, model, "f32", fault)
        return (x + out) * keep[..., None], load

    return jax.jit(block)(weights, x, padding)


def test_the_shared_expert_sits_beside_the_routed_share_in_a_block(weights, inputs):
    x, padding = inputs
    sliced = {**weights, **{f"layers.1.moe.{w}": weights[f"layers.1.moe.{w}"][16:24] for w in ("w1", "w3", "w2")}}
    want, want_load = reference_block(sliced, x, padding, SHARE)
    out, counted = apply_block(block_params(weights, 1, 16, 8), x, padding, 16, 8, 12)
    np.testing.assert_allclose(out, want, **TOL)
    counters = counted["counters"]
    np.testing.assert_array_equal(counters["moe"]["expert_load"], want_load)
    assert int(counters["shared_expert_tokens"]) == int(padding.sum())  # not the full batch
    # without it: no parameter, no counter, and the layer the reference calls `no_shared`
    plain, counted = apply_block(block_params(weights, 1, 16, 8, shared=False), x, padding, 16, 8, 0)
    assert "shared_expert_tokens" not in counted["counters"]
    np.testing.assert_allclose(plain, reference_block(sliced, x, padding, SHARE, "no_shared")[0], **TOL)
    assert float(jnp.abs(plain - out).max()) > 0.1
    # the routed scale is in the weights: without it the routed part is 2.446x smaller
    unscaled = reference_block(sliced, x, padding, SHARE, "no_routed_scale")[0]
    unrouted = reference_block(sliced, x, padding, SHARE, "no_experts")[0]
    np.testing.assert_allclose(out - unrouted, 2.446 * (unscaled - unrouted), rtol=1e-3, atol=1e-5)
    assert float(jnp.abs(unscaled - want).max()) > 1e-2


def test_the_eight_shares_and_the_shared_expert_counted_once_sum_to_the_uncut_layer(weights, inputs):
    """Guide section 4: every chip computes its routed share AND the whole shared
    expert; the shares' routed parts plus the shared expert ONCE are the layer."""
    x, padding = inputs
    h = x  # the feed-forward's input, as given
    keep = padding.astype(jnp.float32)
    whole, _ = jax.jit(lambda w, h, k: reference.sparse_layer(w, "layers.1.", h, k, MODEL, "f32"))(weights, h, keep)
    shared = jax.jit(lambda w, h: reference.shared_ffn(w, "layers.1.shared.", h, "f32"))(weights, h)
    from replay_tpu.nn.ffn import SwiGLU
    from replay_tpu.nn.moe import SparseExperts

    ours = SwiGLU(12, D).apply({"params": swiglu_params(weights, "layers.1.shared.")}, h)
    np.testing.assert_allclose(ours, shared, **TOL)
    def every_share(shares, h, padding):  # ONE program: the eight layers, each with its offset
        out, load = 0.0, 0
        for start, params in zip(range(0, 64, 8), shares):
            layer = SparseExperts(num_experts=64, experts_held=8, expert_offset=start, top_k=6,
                                  hidden_dim=8, scale=2.446)
            part, counted = layer.apply({"params": params}, h, padding, mutable=["counters"])
            out, load = out + part, load + counted["counters"]["expert_load"].sum()
        return out, load

    shares = [moe_params(weights, "layers.1.moe.", start, 8) for start in range(0, 64, 8)]
    routed, load = jax.jit(every_share)(shares, h, padding)
    total, load = ours + routed, int(load)
    np.testing.assert_allclose(total, whole, **TOL)
    assert load == 6 * int(padding.sum())  # every assignment of every real token, once
    # counted eight times, the shared expert would be off by seven of itself
    assert float(jnp.abs(7 * shared * keep[..., None]).max()) > 0.1


# -- the whole model: HybridRec in this pattern against the reference ------------

SCHEMA = TensorSchema(TensorFeatureInfo(
    "item_id", FeatureType.CATEGORICAL, is_seq=True, feature_hint=FeatureHint.ITEM_ID,
    cardinality=ITEMS, embedding_dim=D))
PATTERN = dict(
    layer_types=tuple(KINDS), num_dense_layers=1, num_heads=4, num_kv_heads=4, head_dim=8,
    rope_head_dim=4, value_head_dim=8, kv_latent_dim=12, rope_theta=100.0, dense_dim=40, expert_dim=8,
    shared_expert_dim=12, num_experts=64, experts_held=8, expert_offset=16, experts_per_token=6,
    router="sigmoid", routed_scale=2.446, tie_embeddings=False, norm_eps=1e-5,
)


def test_hybridrec_in_this_pattern_matches_the_reference_loss_gradients_and_loads(weights):
    model = HybridRec(schema=SCHEMA, **PATTERN)
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
        layers = counted["counters"]["encoder"]
        loads = jnp.stack([layers[f"layer_{i}"]["moe"]["expert_load"] for i in (1, 2)])
        return value, (loads, jnp.stack([layers[f"layer_{i}"]["shared_expert_tokens"] for i in (1, 2)]))

    tree = {
        "embedder": {"embedding_item_id": {"table": {"embedding": weights["item_table"]}}},
        "output_table": weights["output_table"], "final_norm": {"scale": weights["final_norm.scale"]},
        "encoder": {f"layer_{i}": block_params(weights, i, 16, 8) for i in range(3)},
    }
    (got_loss, (got_loads, tokens)), got = jax.jit(jax.value_and_grad(program_loss, has_aux=True))(tree)
    assert float(got_loss) == pytest.approx(want_loss, rel=1e-5)
    np.testing.assert_array_equal(got_loads, want_loads)
    assert int(np.asarray(want_loads).sum()) > 0 and tokens.tolist() == [int(padding.sum())] * 2
    layer = lambda i: got["encoder"][f"layer_{i}"]  # noqa: E731
    flat = {
        "item_table": got["embedder"]["embedding_item_id"]["table"]["embedding"],
        "output_table": got["output_table"],
        "layers.0.attn.wq": layer(0)["attention"]["query"]["kernel"],
        "layers.2.attn.wkv_a": layer(2)["attention"]["kv_down"]["kernel"],
        "layers.1.attn.kv_norm.scale": layer(1)["attention"]["kv_norm"]["scale"],
        "layers.1.attn.wkv_b": layer(1)["attention"]["kv_up"]["kernel"],
        "layers.0.ffn.w2": layer(0)["dense_ffn"]["out"]["kernel"],
        "layers.1.moe.router": layer(1)["moe"]["router"]["kernel"],
        "layers.2.moe.w2": layer(2)["moe"]["out"],
        "layers.2.shared.w1": layer(2)["shared_expert"]["gate"]["kernel"],
    }
    for name, leaf in flat.items():
        np.testing.assert_allclose(leaf, want_grads[name], rtol=2e-4, atol=2e-6, err_msg=name)
    assert float(jnp.abs(layer(1)["moe"]["expert_bias"]).max()) == 0.0  # a buffer: no gradient


def test_a_latent_layer_says_which_sizes_it_lacks(item_only_schema):
    with pytest.raises(ValueError, match="kv_latent_dim"):
        HybridRec(schema=item_only_schema, **{**PATTERN, "kv_latent_dim": None}).init(
            jax.random.PRNGKey(0), {"item_id": np.zeros((1, 8), np.int32)}, np.ones((1, 8), bool))
