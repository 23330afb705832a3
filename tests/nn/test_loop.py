"""The looped layer-pattern model (``HybridRec(loop_steps=T)``, T > 1: an exit
gate after every pass; sandwich norms, no q/k norm) and its exit-weighted loss against the plain
reference (``benchmark/reference/ouro_loop.py``) on seeded float32 weights at toy
size: every exit's hidden states, the exit distribution, the loss and every
gradient leaf; what the loop leaves alone (the parameters, the one-pass program);
recomputation; and each fault planted in the reference moving what it should. The
model through ``Trainer.fit`` with its counters in the chunk stage log is the toy
cell of ``tests/benchmark/test_benchmark_loop.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro_loop as reference
from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import Trainer
from replay_tpu.nn.loss import CE, ExitWeightedCE, exit_distribution
from replay_tpu.nn.sequential import HybridRec

pytestmark = pytest.mark.jax

D, LENGTH, BATCH, ITEMS, STEPS = 16, 12, 2, 30, 3
MODEL = {
    "embedding_dim": D, "num_items": ITEMS, "max_sequence_length": LENGTH, "norm_eps": 1e-6,
    "ffn_dim": 24, "layers": {"layer_types": ["full_attention"] * 2, "num_dense_layers": 2},
    "attention": {"num_heads": 2, "num_kv_heads": 2, "head_dim": 8, "rope_theta": 1e6},
    "loop": {"loop_steps": STEPS, "entropy_weight": 0.1},
}
SCHEMA = TensorSchema(
    TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                      feature_hint=FeatureHint.ITEM_ID, cardinality=ITEMS, embedding_dim=D)
)
ONE_PASS = dict(
    layer_types=("full_attention",) * 2, num_dense_layers=2, num_heads=2, num_kv_heads=2,
    head_dim=8, rope_theta=1e6, dense_dim=24, norm_eps=1e-6, tie_embeddings=False,
    fused_attention=False,
)
LOOPED = dict(ONE_PASS, loop_steps=STEPS, sandwich_norms=True, qk_norm=False)


@pytest.fixture(scope="module")
def weights():
    return jax.jit(lambda key: reference.init_params(MODEL, key))(jax.random.PRNGKey(11))


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(3)
    padding = np.arange(LENGTH)[None, :] >= np.array([[0], [4]])  # left padding
    ids = np.where(padding, rng.integers(0, ITEMS, (BATCH, LENGTH)), ITEMS).astype(np.int32)
    labels = rng.integers(0, ITEMS, (BATCH, LENGTH)).astype(np.int32)
    return {"item_id": ids, "padding_mask": padding, "labels": labels, "target_mask": padding,
            "valid": np.ones(BATCH, bool)}


def program_tree(weights, sandwich=True, gate=True):
    def layer(i):
        p = f"layers.{i}."
        tree = {
            "attention": {name: {"kernel": weights[p + "attn." + w]}
                          for name, w in (("query", "wq"), ("key", "wk"), ("value", "wv"), ("out", "wo"))},
            "dense_ffn": {name: {"kernel": weights[p + "ffn." + w]}
                          for name, w in (("gate", "w1"), ("value", "w3"), ("out", "w2"))},
        }
        norms = reference.NORMS if sandwich else ("mixer_norm", "ffn_norm")
        tree.update({norm: {"scale": weights[p + norm + ".scale"]} for norm in norms})
        return tree

    tree = {
        "embedder": {"embedding_item_id": {"table": {"embedding": weights["item_table"]}}},
        "output_table": weights["output_table"], "final_norm": {"scale": weights["final_norm.scale"]},
        "encoder": {f"layer_{i}": layer(i) for i in range(2)},
    }
    if gate:
        tree["exit_gate"] = {"kernel": weights["gate.w"], "bias": weights["gate.b"]}
    return tree


def program_loss(model, loss, batch):
    """(loss, (exits, counters)) of the program on ``batch``, the Trainer's way."""

    def run(params):
        padding = batch["padding_mask"]
        hidden, sown = model.apply({"params": params}, {"item_id": batch["item_id"]}, padding,
                                   mutable=["counters", "exits"])
        loss.logits_callback = lambda h: model.apply({"params": params}, h, method=HybridRec.get_logits)
        loss.exits = sown.get("exits")
        value = loss(hidden, {}, batch["labels"][..., None], None, padding, batch["target_mask"][..., None])
        return value, (sown.get("exits"), sown["counters"], getattr(loss, "step_counters", {}), hidden)

    return run


@pytest.fixture(scope="module")
def want(weights, batch):
    """The reference's step 1: (loss, gradient, p at the valid targets)."""
    return reference.first_step(weights, batch, MODEL, 2)


@pytest.fixture(scope="module")
def compared(weights, batch):
    model = HybridRec(schema=SCHEMA, **LOOPED)
    run = program_loss(model, ExitWeightedCE(), batch)
    (got_loss, aux), got = jax.jit(jax.value_and_grad(run, has_aux=True))(program_tree(weights))
    return got_loss, aux, got


def test_every_exit_the_gate_and_the_loss_match_the_reference(weights, batch, compared, want):
    got_loss, (exits, counters, counted, last), _ = compared
    hidden, gates = jax.jit(lambda w: reference.exits(w, batch, MODEL))(weights)
    np.testing.assert_allclose(exits["hidden"], hidden, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(exits["gate_logits"], gates, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, hidden[-1], rtol=1e-5, atol=1e-5)  # what inference reads
    want_loss, _, want_p = want
    assert float(got_loss) == pytest.approx(want_loss, rel=1e-5)
    targets = batch["target_mask"] & batch["valid"][:, None]
    np.testing.assert_allclose(counted["exit_mass"], want_p.sum(axis=(1, 2)) / targets.sum(), rtol=1e-5, atol=1e-6)
    assert counted["exit_loss"].shape == (STEPS,)
    assert int(counters["loop_layer_applications"]) == STEPS * 2  # counted by the scan body
    p = exit_distribution(exits["gate_logits"])
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p, reference.exit_probabilities(gates), rtol=1e-5, atol=1e-6)


def test_every_gradient_leaf_matches_the_reference(compared, want):
    _, _, got = compared
    _, want, _ = want
    flat = jax.tree_util.tree_leaves_with_path(got)
    tree = program_tree({k: k for k in want})
    names = {jax.tree_util.keystr(path): leaf for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    assert len(flat) == len(want)
    for path, leaf in flat:
        name = names[jax.tree_util.keystr(path)]
        np.testing.assert_allclose(leaf, want[name], rtol=2e-4, atol=2e-6, err_msg=name)


def test_the_loop_adds_no_parameter_and_one_pass_is_todays_program(weights, batch):
    ids, padding = batch["item_id"], batch["padding_mask"]

    def shapes(**kwargs):
        model = HybridRec(schema=SCHEMA, **{**LOOPED, **kwargs})
        return jax.tree.map(lambda a: a.shape, jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                                              {"item_id": ids}, padding)["params"])

    assert shapes(loop_steps=2) == shapes(loop_steps=STEPS) == shapes(loop_steps=5)
    # loop_steps 1 is today's model: its parameter paths (the configurations'
    # param_paths read them), q/k norms, no gate
    today = shapes(loop_steps=1, sandwich_norms=False, qk_norm=True)
    assert set(today) == {"embedder", "encoder", "final_norm", "output_table"}
    assert set(today["encoder"]["layer_0"]) == {"mixer_norm", "ffn_norm", "attention", "dense_ffn"}
    assert set(today["encoder"]["layer_0"]["attention"]) == {"query", "key", "value", "out", "q_norm", "k_norm"}
    # the scanned body's first pass is the one-pass program on the same weights
    tree = program_tree(weights, sandwich=False, gate=False)
    plain = HybridRec(schema=SCHEMA, **{**ONE_PASS, "qk_norm": False})
    looped = HybridRec(schema=SCHEMA, **{**ONE_PASS, "qk_norm": False, "loop_steps": STEPS})
    gate = {"kernel": weights["gate.w"], "bias": weights["gate.b"]}
    want, got = jax.jit(lambda tree: (
        plain.apply({"params": tree}, {"item_id": ids}, padding),
        looped.apply({"params": {**tree, "exit_gate": gate}}, {"item_id": ids}, padding,
                     mutable=["exits"])[1]["exits"]["hidden"][0],
    ))(tree)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_recomputation_gives_the_same_loss_and_gradients(weights, batch, compared):
    got_loss, _, got = compared
    model = HybridRec(schema=SCHEMA, **LOOPED, remat=True)
    run = program_loss(model, ExitWeightedCE(), batch)
    (loss, _), grads = jax.jit(jax.value_and_grad(run, has_aux=True))(program_tree(weights))
    assert float(loss) == pytest.approx(float(got_loss), rel=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(got)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    # the Trainer plumbs the policy into the layer-pattern stack; a model without the field is refused
    trainer = Trainer(model=HybridRec(schema=SCHEMA, **LOOPED), loss=ExitWeightedCE(), remat_policy="dots")
    assert trainer.model.remat and trainer.model.remat_policy is jax.checkpoint_policies.checkpoint_dots


def test_no_entropy_and_a_gate_that_never_stops_is_ce_at_the_last_exit(weights, batch):
    tree = program_tree(weights)
    tree["exit_gate"] = {"kernel": jnp.zeros_like(weights["gate.w"]), "bias": jnp.full((1,), -60.0)}
    model = HybridRec(schema=SCHEMA, **LOOPED)
    exit_weighted, last = jax.jit(lambda tree: (
        program_loss(model, ExitWeightedCE(entropy_weight=0.0), batch)(tree)[0],
        program_loss(model, CE(), batch)(tree)[0],
    ))(tree)
    assert float(exit_weighted) == pytest.approx(float(last), rel=1e-6)


@pytest.fixture(scope="module")
def losses_by_fault(weights, batch):
    """The reference's loss with each fault (by name; ``"None"``: none), all in ONE
    compiled program."""
    weights_of = {"half_batch": batch["target_mask"] & (np.arange(BATCH * LENGTH) < BATCH * LENGTH // 2)
                  .reshape(BATCH, LENGTH)}

    def losses(w):
        return {str(fault): reference.loss_sum(
            w, batch, weights_of.get(fault, batch["target_mask"]).astype(jnp.float32), MODEL, 2, fault=fault)[0]
            for fault in reference.FAULTS}

    return jax.jit(losses)(weights)


@pytest.mark.parametrize("fault", reference.FAULTS[1:])
def test_each_fault_planted_in_the_reference_moves_the_loss(losses_by_fault, fault):
    clean, planted = float(losses_by_fault["None"]), float(losses_by_fault[fault])
    assert abs(planted - clean) / abs(clean) > 1e-3, fault
