"""Multi-chip correctness on the 8-device virtual CPU mesh.

The analogue of the reference's fake-torch.distributed tests (SURVEY.md §4): data
parallelism, vocab tensor-parallelism and metric-state psum are asserted against
single-device ground truth without any real TPU.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.metrics.builder import MetricsBuilder
from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec

# 15 items -> a 16-row table (cardinality + padding row) that divides evenly
# over a model axis of 2 or 4; an odd row count would silently skip vocab
# sharding (run_training asserts it actually happened)
NUM_ITEMS = 15
SEQ_LEN = 6
BATCH = 8


def make_schema() -> TensorSchema:
    return TensorSchema(
        TensorFeatureInfo(
            "item_id",
            FeatureType.CATEGORICAL,
            is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            cardinality=NUM_ITEMS,
            embedding_dim=16,
        )
    )


def make_train_batch(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    items = rng.integers(0, NUM_ITEMS, size=(BATCH, SEQ_LEN)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), dtype=bool)
    return {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": mask[:, :-1],
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, 1:, None],
    }


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer(mesh: Mesh, loss, shard_vocab: bool = False) -> Trainer:
    """The module's one tiny model; trainers of one mesh, loss and table layout
    share their programs (traced and lowered once a module)."""
    model = SasRec(schema=make_schema(), embedding_dim=16, num_blocks=1,
                   max_sequence_length=SEQ_LEN)
    # SGD: parity asserts exact-ish numerical equivalence, and adaptive optimizers
    # amplify device-count-dependent summation noise on near-zero gradients
    trainer = Trainer(
        model=model,
        loss=loss,
        optimizer=OptimizerFactory(name="sgd", learning_rate=0.1),
        mesh=mesh,
        shard_vocab=shard_vocab,
        seed=0,
    )
    key = (tuple(mesh.shape.items()), type(loss).__name__, shard_vocab)
    return PROGRAMS.adopt(trainer, key=key)


def run_training(mesh: Mesh, steps: int = 3, shard_vocab: bool = False):
    trainer = make_trainer(mesh, CE(), shard_vocab)
    state = trainer.init_state(make_train_batch(0))
    if shard_vocab:
        # guard against the silent-degradation mode: a table whose row count
        # does not divide the model axis stays replicated and the comparison
        # below proves nothing
        specs = [
            str(leaf.sharding.spec)
            for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
            if "embedding_" in jax.tree_util.keystr(path)
        ]
        assert any("model" in spec for spec in specs), specs
    losses = []
    for step in range(steps):
        state, loss_value = trainer.train_step(state, make_train_batch(step))
        losses.append(float(loss_value))
    return jax.tree.map(np.asarray, state.params), losses


@pytest.mark.jax
@pytest.mark.smoke
def test_data_parallel_matches_single_device():
    """DP over 8 devices must be numerically equivalent to 1 device: the XLA
    gradient all-reduce replaces DDP without changing the math."""
    params_1, losses_1 = run_training(make_mesh(jax.devices()[:1]))
    params_8, losses_8 = run_training(make_mesh(jax.devices()))
    np.testing.assert_allclose(np.array(losses_1), np.array(losses_8), rtol=2e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5),
        params_1,
        params_8,
    )


@pytest.mark.jax
def test_vocab_sharded_training_matches_replicated():
    """Sharding embedding tables over the model axis (vocab TP) must not change
    the computation — XLA all-gathers the rows when logits need them."""
    params_dp, losses_dp = run_training(make_mesh(jax.devices()))
    params_tp, losses_tp = run_training(
        make_mesh(jax.devices(), model_parallel=4), shard_vocab=True
    )
    np.testing.assert_allclose(np.array(losses_dp), np.array(losses_tp), rtol=2e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5),
        params_dp,
        params_tp,
    )


@pytest.mark.jax
def test_metrics_state_psums_across_devices():
    """Each device accumulates its shard; lax.psum of the state pytrees must equal
    the single-builder result over all the data (the sync_dist replacement)."""
    rng = np.random.default_rng(0)
    n_shards = 8
    preds = rng.integers(0, NUM_ITEMS, size=(n_shards, 4, 5))
    gts = np.where(
        rng.random((n_shards, 4, 3)) < 0.8,
        rng.integers(0, NUM_ITEMS, size=(n_shards, 4, 3)),
        -1,
    )

    def make_builder():
        return MetricsBuilder(metrics=("recall", "ndcg", "coverage"), top_k=(1, 5),
                              item_count=NUM_ITEMS)

    shard_states = []
    for s in range(n_shards):
        b = make_builder()
        b.add_prediction(preds[s], gts[s])
        shard_states.append(b.state())

    # the real collective: psum the stacked states over a mesh axis
    mesh = Mesh(np.array(jax.devices()), ("d",))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *shard_states)

    def reduce_states(state):
        return jax.tree.map(lambda x: jax.lax.psum(x, "d"), state)

    specs_in = jax.tree.map(lambda _: P("d"), stacked)
    specs_out = jax.tree.map(lambda _: P(), stacked)
    total_state = jax.shard_map(
        reduce_states, mesh=mesh, in_specs=(specs_in,), out_specs=specs_out
    )(stacked)
    # shard_map with in_specs P('d') leaves a leading per-device axis of size 1
    total_state = jax.tree.map(lambda x: x[0] if x.ndim and x.shape[0] == 1 else x, total_state)

    merged = make_builder()
    merged.load_state(total_state)

    reference = make_builder()
    for s in range(n_shards):
        reference.add_prediction(preds[s], gts[s])

    got, want = merged.get_metrics(), reference.get_metrics()
    assert set(got) == set(want)
    for key in want:
        assert got[key] == pytest.approx(want[key], rel=1e-5), key


@pytest.mark.jax
def test_fused_ce_composes_with_vocab_sharding():
    """CEFused (pallas head, interpret off-TPU) + shard_vocab on a (4, 2) mesh
    == plain CE data-parallel — the exact composition the large-catalog TPU
    configs run."""
    from replay_tpu.nn.loss import CEFused

    def losses_for(loss, model_parallel, shard_vocab):
        trainer = make_trainer(
            make_mesh(jax.devices(), model_parallel=model_parallel), loss, shard_vocab
        )
        state = trainer.init_state(make_train_batch(0))
        out = []
        for step in range(3):
            state, loss_value = trainer.train_step(state, make_train_batch(step))
            out.append(float(loss_value))
        return out

    plain = losses_for(CE(), 1, False)
    fused_sharded = losses_for(CEFused(), 2, True)
    np.testing.assert_allclose(plain, fused_sharded, rtol=2e-4)
