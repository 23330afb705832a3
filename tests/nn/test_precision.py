"""The precision ladder's bf16 rung in the production fit path.

The policy is only sanctioned if the PRODUCTION loop runs it: ``Trainer(
precision="bf16")`` through ``fit(scan_chunk=K, device_feed=True)`` with the
``CEFused`` memory-wall head must (a) keep master params / optimizer state /
loss accumulation f32, (b) pass the f32 fit-parity gate at the
PARITY_REPORT-style threshold (same data/seed, eval metric within tolerance,
loss curves tracked — never bitwise-claimed), (c) preserve the scan
invariant bitwise WITHIN the rung, and (d) keep the health plane finite and
f32-accumulated so watchers don't false-positive on dtype alone
(docs/performance.md "The precision ladder").

The smoke test leaves ``REPLAY_TPU_RUN_DIR/precision_smoke/`` (events.jsonl +
parity_gate.json) for the CI ``precision_smoke`` gate.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import (
    HealthConfig,
    HealthWatcher,
    OptimizerFactory,
    Precision,
    Trainer,
    fit_parity_record,
    make_mesh,
)
from replay_tpu.nn.loss import CEFused, CESampled
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.obs import JsonlLogger

NUM_ITEMS = 37
SEQ_LEN = 8
BATCH = 16


def make_schema() -> TensorSchema:
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
            # a float feature exercises NumericalEmbedding's compute-dtype cast
            TensorFeatureInfo(
                "num_feature", FeatureType.NUMERICAL, is_seq=True, tensor_dim=1,
                embedding_dim=16,
            ),
        ]
    )


def make_batch(seed: int, negatives: int = 0) -> dict:
    """Learnable next-is-plus-one sequences (the parity gate needs a metric a
    2-epoch fit actually moves, not noise)."""
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, NUM_ITEMS, size=(BATCH, 1))
    items = ((starts + np.arange(SEQ_LEN + 1)) % NUM_ITEMS).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), dtype=bool)
    batch = {
        "feature_tensors": {
            "item_id": items[:, :-1],
            "num_feature": rng.normal(size=(BATCH, SEQ_LEN)).astype(np.float32),
        },
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }
    if negatives:
        batch["negative_labels"] = rng.integers(
            0, NUM_ITEMS, size=(negatives,)
        ).astype(np.int32)
    return batch


def make_val_batch(seed: int) -> dict:
    batch = make_batch(seed)
    last = batch["feature_tensors"]["item_id"][:, -1]
    return {
        "feature_tensors": batch["feature_tensors"],
        "padding_mask": batch["padding_mask"],
        "ground_truth": ((last + 1) % NUM_ITEMS)[:, None].astype(np.int32),
    }


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer(precision, loss=None, health=None) -> Trainer:
    """A trainer of the module's one tiny model; those of one precision, loss and
    health configuration share their two programs (traced and lowered once a
    module)."""
    model = SasRec(
        schema=make_schema(), embedding_dim=16, num_blocks=1, num_heads=1,
        max_sequence_length=SEQ_LEN,
    )
    loss = loss if loss is not None else CEFused(tile=8)
    trainer = Trainer(
        model=model,
        loss=loss,
        optimizer=OptimizerFactory(learning_rate=1e-2),
        precision=precision,
        health=health,
        mesh=make_mesh(),
    )
    return PROGRAMS.adopt(trainer, key=(precision, type(loss).__name__, health))


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


# --------------------------------------------------------------------------- #
# policy mechanics
# --------------------------------------------------------------------------- #
@pytest.mark.jax
def test_resolve_and_identity():
    assert Precision.resolve(None) is None
    policy = Precision.resolve("bf16")
    assert policy.name == "bf16"
    assert jnp.dtype(policy.compute_dtype) == jnp.dtype(jnp.bfloat16)
    assert jnp.dtype(policy.param_dtype) == jnp.dtype(jnp.float32)
    assert Precision.resolve(policy) is policy
    identity = Precision.resolve("f32")
    assert identity.is_identity and not policy.is_identity
    with pytest.raises(ValueError, match="Unknown precision"):
        Precision.resolve("fp8")
    with pytest.raises(TypeError, match="precision"):
        Precision.resolve(16)


@pytest.mark.jax
def test_f32_rung_is_the_identity():
    """Precision('f32') must never clone/retouch the model: the pre-precision
    trainer and the f32-rung trainer are the same program."""
    model = SasRec(
        schema=make_schema(), embedding_dim=16, num_blocks=1, num_heads=1,
        max_sequence_length=SEQ_LEN,
    )
    assert Precision.f32().apply_to_model(model) is model
    trainer = Trainer(
        model=model, loss=CEFused(tile=8),
        optimizer=OptimizerFactory(learning_rate=1e-2), mesh=make_mesh(),
        precision="f32",
    )
    assert trainer.model is model


@pytest.mark.jax
def test_bf16_clones_model_and_keeps_f32_master_state():
    trainer = make_trainer("bf16")
    assert jnp.dtype(trainer.model.dtype) == jnp.dtype(jnp.bfloat16)
    state = trainer.init_state(make_batch(0))
    for leaf in jax.tree.leaves(state.params):
        assert leaf.dtype == jnp.float32, leaf.dtype
    for leaf in jax.tree.leaves(state.opt_state):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.float32, leaf.dtype


@pytest.mark.jax
def test_bf16_rejects_model_without_dtype_field():
    import flax.linen as nn

    class PlainModel(nn.Module):
        @nn.compact
        def __call__(self, feature_tensors, padding_mask, deterministic=True):
            embed = nn.Embed(NUM_ITEMS + 1, 16, name="embedding_item_id")
            return embed(feature_tensors["item_id"])

    with pytest.raises(ValueError, match="dtype"):
        Trainer(
            model=PlainModel(), loss="ce", mesh=make_mesh(), precision="bf16"
        )


@pytest.mark.jax
def test_wrap_logits_callback_casts_to_accum():
    policy = Precision.bf16()
    assert policy.casts_logits
    wrapped = policy.wrap_logits_callback(
        lambda x: jnp.zeros((2, 3), jnp.bfloat16) + x
    )
    assert wrapped(1.0).dtype == jnp.float32
    assert not Precision.f32().casts_logits


# --------------------------------------------------------------------------- #
# the production fit: parity gate, scan invariant, events
# --------------------------------------------------------------------------- #
@pytest.mark.jax
@pytest.mark.smoke
def test_bf16_production_fit_passes_parity_gate():
    """The tentpole gate: same data/seed through the PRODUCTION path
    (scan_chunk + device feed + CEFused) at f32 and bf16 — eval ndcg@10
    within the PARITY_REPORT-style tolerance, loss curves tracked. Leaves the
    CI precision_smoke artifact."""
    batches = [make_batch(i) for i in range(6)]
    val = [make_val_batch(100)]

    def run(precision, logger=None):
        trainer = make_trainer(precision)
        trainer.fit(
            batches, epochs=2, scan_chunk=3, log_every=0,
            val_batches=lambda: val, metrics=("ndcg", "recall"), top_k=(10,),
            loggers=logger,
        )
        return trainer

    f32_trainer = run(None)
    base = os.environ.get("REPLAY_TPU_RUN_DIR")
    run_dir = os.path.join(base, "precision_smoke") if base else None
    logger = JsonlLogger(run_dir, mode="w") if run_dir else None
    bf16_trainer = run("bf16", logger=logger)
    if logger is not None:
        logger.close()

    record = fit_parity_record(
        f32_trainer.history, bf16_trainer.history, metric="ndcg@10"
    )
    assert record["passed"], record
    # the learnable pattern moved the metric: the gate is not vacuous
    assert record["f32"] > 0.2, record
    assert len(record["loss_curve_f32"]) == len(record["loss_curve_bf16"]) == 2
    assert all(np.isfinite(record["loss_curve_bf16"]))
    # loss curves track each other well inside the gate tolerance
    np.testing.assert_allclose(
        record["loss_curve_bf16"], record["loss_curve_f32"], rtol=2e-2
    )

    if run_dir:  # CI artifact: the gate record itself, machine-checkable
        static = {
            name: trainer.analyze_programs().get("train_scan", {}).get("hbm_peak_bytes")
            for name, trainer in (("f32", f32_trainer), ("bf16", bf16_trainer))
        }
        with open(os.path.join(run_dir, "parity_gate.json"), "w") as fh:
            json.dump(
                {**record, "hbm_peak_bytes": static, "backend": jax.default_backend()},
                fh, indent=1,
            )


@pytest.mark.jax
@pytest.mark.smoke
def test_bf16_scan_chunk_bitwise_matches_per_step():
    """The scan invariant holds WITHIN the bf16 rung: fit(scan_chunk=3) is
    bitwise the per-step bf16 fit (params, rng, step losses)."""
    batches = [make_batch(i) for i in range(7)]

    def run(scan_chunk):
        trainer = make_trainer("bf16")
        sink = EventSink()
        state = trainer.fit(
            batches, epochs=1, loggers=sink, log_every=0, scan_chunk=scan_chunk
        )
        return state, [e.payload["loss"] for e in sink.named("on_train_step")]

    state_a, losses_a = run(None)
    state_b, losses_b = run(3)
    assert_params_bitwise_equal(state_a.params, state_b.params)
    assert np.array_equal(np.asarray(state_a.rng), np.asarray(state_b.rng))
    assert losses_a == losses_b


@pytest.mark.jax
def test_on_fit_start_event_carries_precision():
    trainer = make_trainer("bf16")
    sink = EventSink()
    trainer.fit([make_batch(0)], epochs=1, loggers=sink, log_every=0)
    payload = sink.named("on_fit_start")[0].payload
    assert payload["precision"] == "bf16"
    assert payload["compute_dtype"] == "bfloat16"
    assert payload["param_dtype"] == "float32"
    # the f32 / no-policy fit advertises nothing (byte-identical programs)
    sink32 = EventSink()
    make_trainer(None).fit([make_batch(0)], epochs=1, loggers=sink32, log_every=0)
    assert "precision" not in sink32.named("on_fit_start")[0].payload


@pytest.mark.jax
def test_sampled_loss_accumulates_f32_under_bf16():
    """CESampled's candidate logits are a bf16×bf16 einsum under the rung —
    the policy's logits wrap must land the loss math in f32, keeping the loss
    value within the bf16 input-rounding band of the f32 run."""
    losses = {}
    for name, precision in (("f32", None), ("bf16", "bf16")):
        trainer = make_trainer(precision, loss=CESampled())
        batch = make_batch(0, negatives=8)
        state = trainer.init_state(batch)
        _, loss_value = trainer.train_step(state, batch)
        losses[name] = float(loss_value)
        # the loss scalar itself must be f32 — bf16 accumulation would
        # surface here as a bf16 scalar
        assert trainer.last_step_metrics["loss"].dtype == jnp.float32
    assert np.isfinite(losses["bf16"])
    np.testing.assert_allclose(losses["bf16"], losses["f32"], rtol=2e-2)


# --------------------------------------------------------------------------- #
# health under bf16 (satellite: watchers must not false-positive on dtype)
# --------------------------------------------------------------------------- #
@pytest.mark.jax
@pytest.mark.smoke
def test_bf16_health_stays_finite_and_f32_accumulated():
    trainer = make_trainer("bf16", health=HealthConfig(cadence=1))
    batch = make_batch(0)
    state = trainer.init_state(batch)
    state, _ = trainer.train_step(state, batch)
    health_tree = trainer.last_step_metrics["health"]
    # every health leaf is f32 ON DEVICE — norms/ratios/stats accumulate in
    # f32 regardless of the bf16 activations they were computed from
    for leaf in jax.tree.leaves(health_tree):
        assert leaf.dtype == jnp.float32, leaf.dtype
    record = jax.device_get(health_tree)
    values = [
        float(v)
        for v in jax.tree.leaves(
            jax.tree.map(lambda x: np.asarray(x, np.float64).reshape(-1).tolist(), record)
        )
    ]
    assert values and all(np.isfinite(values)), record
    # streamed logits stats exist (CEFused avoids full logits; the tying-head
    # stream path must keep working under bf16 hidden states)
    assert set(record["logits"]) == {"mean", "absmax", "std"}


@pytest.mark.jax
def test_health_watcher_no_false_positive_on_bf16():
    """A steady bf16 fit must not trip the EWMA watcher: dtype alone is not a
    blowup. (A genuine 10× norm jump still is — sanity-checked last.)"""
    watcher = HealthWatcher(alpha=0.5, blowup_factor=3.0, warmup=2)
    trainer = make_trainer("bf16", health=HealthConfig(cadence=1))
    batch = make_batch(0)
    state = trainer.init_state(batch)
    for _ in range(5):
        state, _ = trainer.train_step(state, batch)
        record = jax.tree.map(
            lambda x: x.tolist() if getattr(x, "ndim", 0) else float(x),
            jax.device_get(trainer.last_step_metrics["health"]),
        )
        record["grad_norm_global"] = float(
            trainer.last_step_metrics["grad_norm"]
        )
        assert watcher.observe(record) is None, record
    blown = dict(record)
    blown["grad_norm_global"] = 100.0 * record["grad_norm_global"]
    assert watcher.observe(blown) is not None
