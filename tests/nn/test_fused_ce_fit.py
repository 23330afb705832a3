"""CEFused / CEFusedTP in the production ``fit`` scan path.

The memory-wall head is only useful if the PRODUCTION loop runs it:
``Trainer.fit(scan_chunk=K, device_feed=True, loss=CEFused())`` must be
bitwise-identical to the per-step CEFused fit (the scan invariant), agree with
plain CE to f32 softmax precision, preserve exact anomaly indices through the
sentinel, and keep the health pipeline honest — logits stats streamed over
catalog chunks for tying heads, or explicitly flagged skipped, never silently
absent (docs/performance.md "Breaking the memory wall").

The smoke test leaves ``REPLAY_TPU_RUN_DIR/fused_ce_smoke/events.jsonl`` for
the CI ``fused_ce_smoke`` gate.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE, CEFused, CEFusedTP, GBCE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.obs import HealthConfig, JsonlLogger
from replay_tpu.utils.faults import NaNInjector

NUM_ITEMS = 37  # not divisible by the dryrun-style n_tp=2 shard grid
SEQ_LEN = 8
BATCH = 8  # divisible by the 8-device data axis


def make_schema() -> TensorSchema:
    # the numerical feature is the NaN-injection surface (ids can't carry NaN)
    return TensorSchema(
        [
            TensorFeatureInfo(
                "item_id",
                FeatureType.CATEGORICAL,
                is_seq=True,
                feature_hint=FeatureHint.ITEM_ID,
                cardinality=NUM_ITEMS,
                embedding_dim=16,
            ),
            TensorFeatureInfo(
                "num_feature", FeatureType.NUMERICAL, is_seq=True, tensor_dim=1,
                embedding_dim=16,
            ),
        ]
    )


def make_batch(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    items = rng.integers(0, NUM_ITEMS, size=(BATCH, SEQ_LEN + 1)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), dtype=bool)
    return {
        "feature_tensors": {
            "item_id": items[:, :-1],
            "num_feature": rng.normal(size=(BATCH, SEQ_LEN)).astype(np.float32),
        },
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
        "negative_labels": rng.integers(0, NUM_ITEMS, size=(8,)).astype(np.int32),
    }


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer(loss, own_programs: bool = False, **kwargs) -> Trainer:
    """A trainer of the module's one tiny model. The plain ones of one loss share
    their two programs (traced and lowered once a module); ``own_programs`` is
    for the trainer whose compile counts are asserted, and any other mesh or
    health keeps its own too."""
    model = SasRec(
        schema=make_schema(), embedding_dim=16, num_blocks=1, num_heads=1,
        max_sequence_length=SEQ_LEN,
    )
    trainer = Trainer(
        model=model, loss=loss, optimizer=OptimizerFactory(learning_rate=1e-2),
        **{"mesh": make_mesh(), **kwargs},
    )
    if own_programs or kwargs:
        return PROGRAMS.share_init(trainer)  # the same model: the same fresh parameters
    return PROGRAMS.adopt(trainer, key=type(loss).__name__)


class EventSink:
    def __init__(self):
        self.events = []

    def log_event(self, event):
        self.events.append(event)

    def named(self, name):
        return [e for e in self.events if e.event == name]


def assert_params_bitwise_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


@pytest.mark.jax
@pytest.mark.smoke
def test_fused_chunked_fit_bitwise_matches_per_step_and_ce():
    """The scan invariant for the fused head: fit(scan_chunk=3, device_feed)
    with CEFused is bitwise the per-step CEFused fit (params, losses, rng),
    runs through ONE compiled scan program, and its step losses agree with
    plain CE to f32 softmax precision. Leaves the CI smoke artifact."""
    batches = [make_batch(i) for i in range(7)]

    def run(loss, scan_chunk, own_programs=False):
        trainer = make_trainer(loss, own_programs=own_programs)
        sink = EventSink()
        state = trainer.fit(
            batches, epochs=1, loggers=sink, log_every=0, scan_chunk=scan_chunk
        )
        losses = [e.payload["loss"] for e in sink.named("on_train_step")]
        return trainer, state, losses

    per_step, state_a, losses_a = run(CEFused(tile=8), None)
    chunked, state_b, losses_b = run(CEFused(tile=8), 3, own_programs=True)  # counted below
    _, _, losses_ce = run(CE(), 3)

    assert_params_bitwise_equal(state_a.params, state_b.params)
    assert np.array_equal(np.asarray(state_a.rng), np.asarray(state_b.rng))
    assert losses_a == losses_b  # host floats: bitwise step-loss parity
    assert per_step.history == chunked.history
    np.testing.assert_allclose(losses_b, losses_ce, rtol=1e-5)
    compile_report = chunked.compile_tracker.report()
    assert compile_report["train_scan"]["traces"] == 1
    assert compile_report["train_step"]["traces"] == 1

    base = os.environ.get("REPLAY_TPU_RUN_DIR")
    if base:  # CI artifact: the fused chunked fit's telemetry, re-runnable
        run_dir = os.path.join(base, "fused_ce_smoke")
        logger = JsonlLogger(run_dir, mode="w")
        trainer = make_trainer(CEFused(tile=8))
        trainer.fit(batches, epochs=1, loggers=logger, scan_chunk=3, log_every=0)
        logger.close()


@pytest.mark.jax
@pytest.mark.smoke
def test_fused_tp_chunked_fit_matches_ce_on_dp_tp_mesh():
    """CEFusedTP through fit(scan_chunk=...) on the 4×2 DP×TP mesh with the
    vocab-sharded table (37 items → non-divisible shard padding): per-step
    losses equal plain CE's to the shard-combine's f32 reassociation."""
    mesh = make_mesh(model_parallel=2)
    batches = [make_batch(i) for i in range(5)]

    def run(loss):
        trainer = make_trainer(loss, mesh=mesh, shard_vocab=True)
        sink = EventSink()
        trainer.fit(batches, epochs=1, loggers=sink, log_every=0, scan_chunk=2)
        return [e.payload["loss"] for e in sink.named("on_train_step")]

    np.testing.assert_allclose(run(CEFusedTP(tile=8)), run(CE()), rtol=1e-5)


@pytest.mark.jax
def test_fused_anomaly_indices_exact_with_nan_mid_chunk():
    """The sentinel semantics survive the fused head bitwise: a NaN landing
    mid-chunk reports the same step index, bad_steps total and per-step losses
    as the per-step CEFused fit — and the same indices as plain CE."""

    def run(loss, scan_chunk):
        injector = NaNInjector(at_steps=(4,))
        trainer = make_trainer(loss)
        sink = EventSink()
        state = trainer.fit(
            lambda epoch: injector.wrap([make_batch(i) for i in range(7)]),
            epochs=1,
            loggers=sink,
            scan_chunk=scan_chunk,
            log_every=0,
        )
        anomalies = [
            (e.step, e.payload["bad_steps_total"]) for e in sink.named("on_anomaly")
        ]
        return trainer, state, anomalies

    per_step, state_a, anomalies_a = run(CEFused(tile=8), None)
    chunked, state_b, anomalies_b = run(CEFused(tile=8), 3)
    _, state_c, anomalies_ce = run(CE(), 3)

    assert_params_bitwise_equal(state_a.params, state_b.params)
    assert int(state_a.bad_steps) == int(state_b.bad_steps) == int(state_c.bad_steps) == 1
    assert anomalies_a == anomalies_b == anomalies_ce == [(5, 1)]


@pytest.mark.jax
@pytest.mark.smoke
def test_fused_health_streams_logits_stats():
    """Health's logits-stats collector must not materialize [B, I] on the
    fused path: the streamed per-chunk stats match the full-logits stats the
    plain-CE health step reports (same catalog, same params trajectory is NOT
    required — compare against a directly computed reference)."""
    trainer = make_trainer(CEFused(tile=8), health=HealthConfig(cadence=1))
    batch = make_batch(0)
    state = trainer.init_state(batch)
    # the step donates the state: keep the pre-update params for the reference
    params = jax.tree.map(lambda x: x.copy(), state.params)
    trainer.train_step(state, batch)
    record = jax.device_get(trainer.last_step_metrics["health"])
    stats = record["logits"]
    assert set(stats) == {"mean", "absmax", "std"}

    # reference: full last-position logits from the model's own scoring head
    # (health computes its stats from the PRE-update params)
    hidden = trainer.model.apply(
        {"params": params},
        batch["feature_tensors"],
        jnp.asarray(batch["padding_mask"]),
        deterministic=True,
    )
    logits = trainer.model.apply(
        {"params": params}, hidden[:, -1, :], None,
        method=type(trainer.model).get_logits,
    )
    np.testing.assert_allclose(float(stats["mean"]), float(jnp.mean(logits)), rtol=1e-5)
    np.testing.assert_allclose(
        float(stats["absmax"]), float(jnp.max(jnp.abs(logits))), rtol=1e-5
    )
    np.testing.assert_allclose(float(stats["std"]), float(jnp.std(logits)), rtol=1e-4)


@pytest.mark.jax
def test_health_flags_skipped_without_tying_head(caplog):
    """A no-full-logits loss on a model WITHOUT a tying head cannot stream —
    the record must carry an explicit numeric skipped flag, never silently
    drop the logits block."""
    import flax.linen as nn

    class PlainModel(nn.Module):
        @nn.compact
        def __call__(self, feature_tensors, padding_mask, deterministic=True):
            embed = nn.Embed(NUM_ITEMS + 1, 16, name="embedding_item_id")
            return embed(feature_tensors["item_id"])

        def get_logits(self, hidden, candidates_to_score=None):
            # a fixed non-param projection: deliberately NOT a tying head and
            # no get_item_weights — the stream path has nothing to stream
            weights = jnp.linspace(0.0, 1.0, NUM_ITEMS * 16).reshape(NUM_ITEMS, 16)
            if candidates_to_score is None:
                return hidden @ weights.T
            if candidates_to_score.ndim == 1:
                return hidden @ weights[candidates_to_score].T
            return jnp.einsum("...e,...ke->...k", hidden, weights[candidates_to_score])

    trainer = Trainer(
        model=PlainModel(),
        loss=GBCE(catalog_size=NUM_ITEMS),
        health=HealthConfig(cadence=1),
        mesh=make_mesh(),
    )
    batch = make_batch(0)
    state = trainer.init_state(batch)
    trainer.train_step(state, batch)
    record = jax.device_get(trainer.last_step_metrics["health"])
    assert float(record["logits"]["skipped"]) == 1.0


@pytest.mark.jax
def test_cefused_unbound_callback_names_the_fix():
    loss = CEFused(tile=8)
    with pytest.raises(AttributeError, match="get_item_weights"):
        loss(
            jnp.zeros((2, 4, 8)), {}, jnp.zeros((2, 4, 1), jnp.int32), None,
            jnp.ones((2, 4), bool), jnp.ones((2, 4, 1), bool),
        )


@pytest.mark.jax
def test_cefused_rejects_mismatched_narrow_floats():
    """bf16 hidden against an f16 table is a call-site bug: named, not
    silently papered over by the kernel's f32 accumulation. The sanctioned
    flax split (narrow compute dtype vs f32 param table) still passes."""
    loss = CEFused(tile=8)
    table = jnp.zeros((NUM_ITEMS, 8), jnp.float16)
    loss.item_embeddings_callback = lambda: table
    args = (
        jnp.zeros((2, 4, 8), jnp.bfloat16), {}, jnp.zeros((2, 4, 1), jnp.int32),
        None, jnp.ones((2, 4), bool), jnp.ones((2, 4, 1), bool),
    )
    with pytest.raises(ValueError, match="bfloat16.*float16"):
        loss(*args)
    loss.item_embeddings_callback = lambda: table.astype(jnp.float32)
    assert np.isfinite(float(loss(*args)))  # bf16 hidden + f32 params: sanctioned


@pytest.mark.jax
def test_cefused_tp_without_mesh_names_the_fix():
    loss = CEFusedTP(tile=8)
    loss.item_embeddings_callback = lambda: jnp.zeros((NUM_ITEMS, 8), jnp.float32)
    with pytest.raises(AttributeError, match="loss.mesh"):
        loss(
            jnp.zeros((2, 4, 8)), {}, jnp.zeros((2, 4, 1), jnp.int32), None,
            jnp.ones((2, 4), bool), jnp.ones((2, 4, 1), bool),
        )
