"""Model-health diagnostics (replay_tpu.obs.health).

The acceptance gates for this layer:

* a health-enabled ``fit`` on the 8-device virtual mesh produces per-group
  grad/param/update norms + update ratios, activation RMS, attention entropy,
  logits stats and embedding coverage in ``events.jsonl`` with exactly ONE
  ``train_step`` compile (no retraces after step 1), and ``obs.report``
  renders the model-health section from that run;
* the health-DISABLED step lowers to the same HLO as the pre-health trainer
  (golden comparison against an in-test reimplementation of the original
  step math);
* ``HealthWatcher`` fires ``on_health_warning`` well before the non-finite
  sentinel on an lr-blowup divergence run, and can trigger the
  RecoveryPolicy rollback path.

The smoke test doubles as the CI artifact source: its events.jsonl lands in
``REPLAY_TPU_RUN_DIR/health_smoke`` and ships from the ``jax and smoke`` job,
which also runs ``obs.report`` over it.
"""

import json
import math
import os
from functools import partial

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import (
    HealthConfig,
    HealthWatcher,
    OptimizerFactory,
    RecoveryPolicy,
    Trainer,
    make_mesh,
)
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.nn.train import TrainState
from replay_tpu.obs import JsonlLogger, TensorBoardLogger
from replay_tpu.obs.health import flatten_health, param_group_key
from replay_tpu.obs.report import render, summarize_run

NUM_ITEMS = 12
SEQ_LEN = 8
BATCH = 8  # divisible by the 8-device data axis


def _run_dir(tmp_path, name):
    """CI exports REPLAY_TPU_RUN_DIR so the smoke run's health telemetry
    ships as a workflow artifact; locally the run log lands in tmp_path."""
    base = os.environ.get("REPLAY_TPU_RUN_DIR")
    return os.path.join(base, name) if base else str(tmp_path / name)


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


def make_batch(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    items = rng.integers(0, NUM_ITEMS, size=(BATCH, SEQ_LEN + 1)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), dtype=bool)
    return {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer(**kwargs) -> Trainer:
    """Every trainer of this module has a health configuration, a seed or a
    model of its own (and two have their compile counts asserted), so each keeps
    its programs; they share the jitted flax init."""
    model = SasRec(
        schema=make_schema(), embedding_dim=16, num_blocks=2, num_heads=2,
        max_sequence_length=SEQ_LEN,
    )
    kwargs.setdefault("optimizer", OptimizerFactory(name="adam", learning_rate=1e-2))
    return PROGRAMS.share_init(Trainer(model=model, loss=CE(), mesh=make_mesh(), **kwargs))


class EventSink:
    def __init__(self):
        self.events = []

    def log_event(self, event):
        self.events.append(event)

    def named(self, name):
        return [e for e in self.events if e.event == name]


# --------------------------------------------------------------------------- #
# the acceptance smoke: health-enabled fit, one compile, full payload, report
# --------------------------------------------------------------------------- #
@pytest.mark.jax
@pytest.mark.smoke
def test_health_enabled_fit_single_compile_full_payload(tmp_path):
    trainer = make_trainer(health=HealthConfig(cadence=2))
    batches = [make_batch(i) for i in range(4)]
    run_dir = _run_dir(tmp_path, "health_smoke")
    # mode="w": REPLAY_TPU_RUN_DIR is a fixed path in CI — a re-run must not
    # append a second event stream and break the counts below
    with JsonlLogger(run_dir, mode="w") as sink:
        trainer.fit(lambda: iter(batches), epochs=2, loggers=sink, log_every=0)

    # the retrace guard: enabling health is exactly ONE compiled train step
    assert trainer.compile_tracker.traces["train_step"] == 1

    lines = [json.loads(line) for line in open(os.path.join(run_dir, "events.jsonl"))]
    steps = [line for line in lines if line["event"] == "on_train_step"]
    health_steps = [line for line in steps if "health" in line]
    # cadence=2 over 8 steps: every second step event carries the record
    assert len(steps) == 8 and len(health_steps) == 4

    health = health_steps[-1]["health"]
    groups = {"embeddings", "block_0", "block_1", "head"}
    for key in ("grad_norm", "param_norm", "update_norm", "update_ratio"):
        assert set(health[key]) == groups, key
        for group, value in health[key].items():
            assert value is not None and math.isfinite(value) and value >= 0, (key, group)
    # adam's update norms are not degenerate: ratios strictly positive
    assert all(v > 0 for v in health["update_ratio"].values())
    assert math.isfinite(health["grad_norm_global"])
    # sowed per-stage activation stats from the SASRec body + encoder blocks
    assert {"embed", "block_0", "block_1", "final_norm"} <= set(health["activations"])
    for stats in health["activations"].values():
        assert math.isfinite(stats["rms"]) and stats["rms"] > 0
        assert math.isfinite(stats["absmax"]) and stats["absmax"] >= stats["rms"]
    # per-head attention entropy: one [num_heads] vector per block, in nats
    assert set(health["attention_entropy"]) == {"block_0", "block_1"}
    for per_head in health["attention_entropy"].values():
        assert len(per_head) == 2  # num_heads
        assert all(0 <= v <= math.log(SEQ_LEN) + 1e-3 for v in per_head)
    assert math.isfinite(health["attention_entropy_mean"])
    assert 0 < health["embedding_coverage"] <= 1.0
    assert math.isfinite(health["logits"]["absmax"]) and health["logits"]["std"] > 0

    # the epoch-end rollups ride the same stream (report --compare gates)
    epoch_ends = [line for line in lines if line["event"] == "on_epoch_end"]
    assert all(e["bad_steps"] == 0 for e in epoch_ends)
    assert all(math.isfinite(e["grad_norm"]) for e in epoch_ends)
    assert all("health" in e for e in epoch_ends)

    # and the run-report CLI renders the model-health section from the artifact
    summary = summarize_run(run_dir)
    assert summary["health"] is not None and summary["health_warnings"] == 0
    assert summary["bad_steps"] == 0 and math.isfinite(summary["last_grad_norm"])
    text = render(summary)
    assert "model health" in text and "group grad norms" in text and "activations" in text


@pytest.mark.jax
def test_health_payload_on_bert4rec_body(tmp_path):
    """The BERT4Rec body sows the same stage/entropy sites (bidirectional
    encoder, token-mask forward)."""
    from replay_tpu.nn.sequential.bert4rec import Bert4Rec

    model = Bert4Rec(
        schema=make_schema(), embedding_dim=16, num_blocks=1, num_heads=2,
        max_sequence_length=SEQ_LEN,
    )
    trainer = PROGRAMS.share_init(Trainer(
        model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2),
        mesh=make_mesh(), health=HealthConfig(cadence=1),
    ))
    rng = np.random.default_rng(0)
    batch = make_batch(0)
    batch["token_mask"] = rng.random((BATCH, SEQ_LEN)) > 0.2
    sink = EventSink()
    trainer.fit(lambda: iter([batch, batch]), epochs=1, loggers=sink, log_every=0)
    health = sink.named("on_train_step")[-1].payload["health"]
    assert {"embed", "block_0", "final_norm"} <= set(health["activations"])
    assert "block_0" in health["attention_entropy"]
    assert len(health["attention_entropy"]["block_0"]) == 2
    assert trainer.compile_tracker.traces["train_step"] == 1


@pytest.mark.jax
def test_attention_entropy_weighted_by_valid_positions():
    """Padded query rows are forced one-hot by the mask's diagonal rescue
    (entropy 0); the sowed per-head entropy must average over VALID rows only,
    or heavily padded batches read as collapsed attention."""
    from replay_tpu.nn import MultiHeadAttention
    from replay_tpu.nn.mask import causal_attention_mask

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 8, 16)).astype(np.float32))
    padding_mask = np.zeros((4, 8), bool)
    padding_mask[:, 4:] = True  # left-padded: half the rows are invalid
    mask = causal_attention_mask(jnp.asarray(padding_mask))
    module = MultiHeadAttention(num_heads=2)
    params = module.init(jax.random.PRNGKey(0), x, mask)["params"]

    def sowed_entropy(pad):
        _, variables = module.apply(
            {"params": params}, x, mask, padding_mask=pad, mutable=["intermediates"]
        )
        return np.asarray(variables["intermediates"]["attention_entropy"][0])

    weighted = sowed_entropy(jnp.asarray(padding_mask))
    diluted = sowed_entropy(None)  # same weights, unweighted mean
    # identical attention weights, so the only difference is the averaging:
    # dropping the zero-entropy padded rows must raise the reported value
    assert (weighted > diluted).all(), (weighted, diluted)
    assert (weighted <= math.log(8) + 1e-3).all()


@pytest.mark.jax
def test_last_health_scoped_per_fit():
    """A second fit whose first fetch has not happened yet must not attach the
    previous fit's record to its epoch-end events."""
    trainer = make_trainer(health=HealthConfig(cadence=10))  # > steps per fit
    sink = EventSink()
    trainer.fit(lambda: iter([make_batch(0) for _ in range(10)]), epochs=1,
                loggers=sink, log_every=0)
    assert trainer.last_health is not None  # fetch happened at step 10
    second = EventSink()
    trainer.fit(lambda: iter([make_batch(1) for _ in range(2)]), epochs=1,
                loggers=second, log_every=0)
    assert "health" not in second.named("on_epoch_end")[0].payload


# --------------------------------------------------------------------------- #
# golden HLO: the health-disabled step is byte-identical to the pre-health one
# --------------------------------------------------------------------------- #
def _strip_module_name(text: str) -> str:
    # the first line carries the jitted function's name (@jit_train_step vs
    # @jit_golden_step); everything below is the program
    return "\n".join(text.splitlines()[1:])


@pytest.mark.jax
def test_health_disabled_step_lowers_to_golden_hlo():
    """Golden comparison: with health=None the trainer's step must lower to
    the same HLO as a literal reimplementation of the original (pre-health)
    train-step math — the sow guards and the health branch may not leak a
    single op into the disabled path."""
    trainer = make_trainer()
    model, loss, tx = trainer.model, trainer.loss, trainer._tx
    batch = make_batch(0)
    state = trainer.init_state(batch)
    placed = trainer._put_batch(batch)

    def golden_step(state, batch):
        rng, dropout_rng, loss_rng = jax.random.split(state.rng, 3)
        target_mask = batch["target_padding_mask"]
        if "valid" in batch:
            target_mask = target_mask & batch["valid"][
                (slice(None),) + (None,) * (target_mask.ndim - 1)
            ]

        def loss_fn(params):
            kwargs = {
                name: batch[name]
                for name in ("feature_tensors", "padding_mask", "deterministic")
                if name in batch
            }
            kwargs["deterministic"] = False
            with jax.named_scope("forward"):
                hidden = model.apply(
                    {"params": params}, rngs={"dropout": dropout_rng}, **kwargs
                )
            loss.logits_callback = partial(
                model.apply, {"params": params}, method=type(model).get_logits
            )
            with jax.named_scope("loss"):
                return loss(
                    hidden,
                    batch.get("feature_tensors", {}),
                    batch["positive_labels"],
                    batch.get("negative_labels"),
                    batch["padding_mask"],
                    target_mask,
                )

        loss_value, grads = jax.value_and_grad(loss_fn)(state.params)
        grad_norm = optax.global_norm(grads)
        good = jnp.isfinite(loss_value) & jnp.isfinite(grad_norm)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)

        def keep(new, old):
            return jnp.where(good, new, old)

        new_state = TrainState(
            step=state.step + 1,
            params=jax.tree.map(keep, params, state.params),
            opt_state=jax.tree.map(keep, opt_state, state.opt_state),
            rng=rng,
            bad_steps=state.bad_steps + (~good).astype(jnp.int32),
        )
        return new_state, {"loss": loss_value, "good": good, "grad_norm": grad_norm}

    # the golden traces under the SAME rule-table sharding scope the trainer
    # installs around its programs (parallel.sharding): the model bodies'
    # shard_activation constraints are part of the production step by design
    # — what this golden pins is that the HEALTH machinery adds nothing
    from replay_tpu.parallel.sharding import sharding_scope

    with sharding_scope(trainer.sharding_rules, trainer.mesh):
        golden = _strip_module_name(
            jax.jit(golden_step, donate_argnums=0).lower(state, placed).as_text()
        )
    disabled = _strip_module_name(
        jax.jit(trainer._build_train_step(None), donate_argnums=0)
        .lower(state, placed)
        .as_text()
    )
    assert disabled == golden

    # sanity: the health-enabled variant IS a different program (the one
    # sanctioned extra compiled variant), with the health scope present
    enabled = jax.jit(
        trainer._build_train_step(HealthConfig()), donate_argnums=0
    ).lower(state, placed).as_text()
    assert _strip_module_name(enabled) != golden
    assert "health" in enabled and "health" not in disabled


@pytest.mark.jax
def test_health_step_math_identical_to_plain_step():
    """The health variant's loss/params must equal the plain step's bit for
    bit — diagnostics may observe the update, never change it."""
    plain = make_trainer(seed=3)
    health = make_trainer(seed=3, health=HealthConfig(cadence=1))
    batch = make_batch(7)
    state_a = plain.init_state(batch)
    state_b = health.init_state(batch)
    for seed in (1, 2, 3):
        state_a, loss_a = plain.train_step(state_a, make_batch(seed))
        state_b, loss_b = health.train_step(state_b, make_batch(seed))
        assert float(loss_a) == float(loss_b)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        state_a.params,
        state_b.params,
    )


# --------------------------------------------------------------------------- #
# divergence: the watcher warns BEFORE the sentinel, and can trigger recovery
# --------------------------------------------------------------------------- #
class ToyTying(nn.Module):
    """Norm-free tying model: under an oversized SGD rate its parameter norm
    grows geometrically for dozens of steps before anything overflows — the
    textbook silent-divergence window the watcher exists for (a LayerNorm'd
    encoder bounds its activations and hides the growth from the loss)."""

    vocab: int
    dim: int = 8
    logits_via_item_weights = True

    def setup(self):
        self.embedding_item = nn.Embed(self.vocab, self.dim, name="embedding_item")

    def __call__(self, feature_tensors, padding_mask, deterministic=True):
        return self.embedding_item(feature_tensors["item_id"])

    def get_logits(self, hidden, candidates_to_score=None):
        weights = self.embedding_item.embedding
        if candidates_to_score is not None:
            weights = weights[candidates_to_score]
        return hidden @ weights.T

    def forward_inference(self, feature_tensors, padding_mask, candidates_to_score=None):
        hidden = self(feature_tensors, padding_mask)[:, -1, :]
        return self.get_logits(hidden, candidates_to_score)

    def get_item_weights(self):
        return self.embedding_item.embedding


def _toy_trainer(watcher: HealthWatcher) -> Trainer:
    return PROGRAMS.share_init(Trainer(
        model=ToyTying(vocab=NUM_ITEMS),
        loss=CE(),
        optimizer=OptimizerFactory(name="sgd", learning_rate=20.0),  # lr blowup
        mesh=make_mesh(),
        health=HealthConfig(cadence=1, watcher=watcher),
    ))


@pytest.mark.jax
@pytest.mark.smoke
def test_watcher_warns_before_nonfinite_sentinel():
    K = 5  # the early-warning margin the acceptance criterion demands
    trainer = _toy_trainer(HealthWatcher(alpha=0.3, blowup_factor=5.0, warmup=3))
    sink = EventSink()
    trainer.fit(
        lambda epoch: [make_batch(i) for i in range(60)],
        epochs=1, loggers=sink, log_every=0,
    )
    warnings = sink.named("on_health_warning")
    anomalies = sink.named("on_anomaly")
    assert warnings, "divergence produced no health warning"
    assert anomalies, "the lr blowup never reached the sentinel (test setup broken)"
    first_warning, first_anomaly = warnings[0].step, anomalies[0].step
    assert first_warning + K <= first_anomaly, (first_warning, first_anomaly)
    payload = warnings[0].payload
    assert payload["signal"] in ("grad_norm", "update_ratio_max")
    assert payload["factor"] > payload["blowup_factor"] >= 5.0
    assert math.isfinite(payload["value"]) and math.isfinite(payload["ewma"])


@pytest.mark.jax
def test_watcher_triggers_recovery_rollback():
    """trigger_recovery=True routes the warning into the existing rollback
    path: on_recovery(reason='health_warning') fires while everything is
    still finite, and the restored state is the pre-blowup snapshot."""
    trainer = _toy_trainer(
        HealthWatcher(alpha=0.3, blowup_factor=5.0, warmup=3, trigger_recovery=True)
    )
    sink = EventSink()
    with pytest.raises(RuntimeError, match="budget exhausted"):
        # lr stays absurd after each backoff, so the budget eventually runs
        # out — by then several health-triggered rollbacks must have fired
        trainer.fit(
            lambda epoch: [make_batch(i) for i in range(60)],
            epochs=1, loggers=sink, log_every=0,
            recovery=RecoveryPolicy(max_consecutive_bad=50, max_restarts=2, lr_backoff=0.9),
        )
    recoveries = sink.named("on_recovery")
    assert recoveries and recoveries[0].payload["reason"] == "health_warning"
    # every trigger came from the watcher, not the sentinel: the rollback
    # happened BEFORE any non-finite step could accumulate
    assert all(r.payload["reason"] == "health_warning" for r in recoveries if "reason" in r.payload)


# --------------------------------------------------------------------------- #
# unit: watcher, grouping, flatten (host-only)
# --------------------------------------------------------------------------- #
@pytest.mark.core
def test_watcher_ewma_blowup_and_reset():
    watcher = HealthWatcher(alpha=0.5, blowup_factor=3.0, warmup=2)
    clean = {"grad_norm_global": 1.0, "update_ratio": {"head": 0.01}}
    assert watcher.observe(clean) is None
    assert watcher.observe(clean) is None
    warning = watcher.observe({"grad_norm_global": 50.0, "update_ratio": {"head": 0.01}})
    assert warning is not None and warning["signal"] == "grad_norm"
    assert warning["factor"] == pytest.approx(50.0)
    # the blowup did not poison the baseline: a clean step after it is clean
    assert watcher.observe(clean) is None
    watcher.reset()
    # post-reset: warmup starts over, the same blowup is not yet a warning
    assert watcher.observe({"grad_norm_global": 50.0}) is None


@pytest.mark.core
def test_watcher_simultaneous_blowups_poison_no_baseline():
    """When BOTH signals blow up on one fetch, the first becomes the warning
    but neither value may enter its EWMA — otherwise the second signal's
    baseline chases the blowup and masks its next real warning."""
    watcher = HealthWatcher(alpha=0.5, blowup_factor=3.0, warmup=2)
    clean = {"grad_norm_global": 1.0, "update_ratio": {"head": 0.01}}
    watcher.observe(clean)
    watcher.observe(clean)
    blown = {"grad_norm_global": 100.0, "update_ratio": {"head": 1.0}}
    warning = watcher.observe(blown)
    assert warning is not None and warning["signal"] == "grad_norm"
    # the update-ratio baseline stayed pre-blowup: a ratio-only blowup on the
    # next fetch still warns instead of being absorbed
    warning = watcher.observe({"grad_norm_global": 1.0, "update_ratio": {"head": 1.0}})
    assert warning is not None and warning["signal"] == "update_ratio_max"


@pytest.mark.core
def test_watcher_ignores_nonfinite_and_validates():
    watcher = HealthWatcher(warmup=1)
    watcher.observe({"grad_norm_global": 1.0})
    watcher.observe({"grad_norm_global": 1.0})
    assert watcher.observe({"grad_norm_global": float("nan")}) is None
    assert watcher.observe({"grad_norm_global": float("inf")}) is None
    with pytest.raises(ValueError, match="alpha"):
        HealthWatcher(alpha=0.0)
    with pytest.raises(ValueError, match="blowup_factor"):
        HealthWatcher(blowup_factor=1.0)
    with pytest.raises(ValueError, match="cadence"):
        HealthConfig(cadence=0)


@pytest.mark.core
def test_param_group_keys():
    assert param_group_key("['body']['embedder']['embedding_item_id']['embedding']") == "embeddings"
    assert param_group_key("['body']['encoder']['block_3']['ffn']['kernel']") == "block_3"
    assert param_group_key("['body']['final_norm']['scale']") == "head"
    assert param_group_key("['body']['aggregator']['positional_embedding']") == "embeddings"


@pytest.mark.core
def test_flatten_health_shapes_for_tensorboard():
    record = {
        "grad_norm": {"embeddings": 0.5, "head": 0.1},
        "attention_entropy": {"block_0": [1.0, 1.2]},
        "embedding_coverage": 0.9,
    }
    flat = flatten_health(record)
    assert flat["health/grad_norm/embeddings"] == 0.5
    assert flat["health/attention_entropy/block_0"] == [1.0, 1.2]
    assert flat["health/embedding_coverage"] == 0.9


# --------------------------------------------------------------------------- #
# TensorBoard routing: scalars + real histograms, no-op fallback preserved
# --------------------------------------------------------------------------- #
class FakeWriter:
    def __init__(self):
        self.scalars = {}
        self.histograms = {}

    def add_scalar(self, tag, value, global_step=0):
        self.scalars[tag] = (value, global_step)

    def add_histogram(self, tag, values, global_step=0):
        self.histograms[tag] = (np.asarray(values), global_step)

    def close(self):
        pass


@pytest.mark.core
def test_tensorboard_health_scalars_and_histograms(tmp_path):
    from replay_tpu.obs import TrainerEvent

    sink = TensorBoardLogger(str(tmp_path / "tb"))
    sink._writer = FakeWriter()  # backend-independent
    sink.log_event(TrainerEvent(
        event="on_train_step", step=7,
        payload={
            "loss": 1.5,
            "health": {
                "grad_norm": {"embeddings": 0.5},
                "attention_entropy": {"block_0": [1.0, 1.2, float("nan")]},
                "embedding_coverage": 0.9,
            },
        },
    ))
    writer = sink._writer
    assert writer.scalars["loss"] == (1.5, 7)
    assert writer.scalars["health/grad_norm/embeddings"] == (0.5, 7)
    assert writer.scalars["health/embedding_coverage"] == (0.9, 7)
    tag, (values, step) = next(iter(writer.histograms.items()))
    assert tag == "health/attention_entropy/block_0" and step == 7
    np.testing.assert_allclose(values, [1.0, 1.2])  # non-finite dropped
    # the health subtree is not double-logged through the scalar flattener
    assert "health/attention_entropy" not in writer.scalars


@pytest.mark.core
def test_tensorboard_log_histogram_noop_without_backend(tmp_path):
    sink = TensorBoardLogger(str(tmp_path / "tb"))
    sink._writer = None  # simulate a missing backend
    sink.log_histogram("health/x", [1.0, 2.0], step=1)  # must not raise

    class AncientWriter:
        def add_scalar(self, *a, **k):
            pass

    sink._writer = AncientWriter()  # no add_histogram attr
    sink.log_histogram("health/x", [1.0, 2.0], step=1)  # must not raise


# --------------------------------------------------------------------------- #
# report: health section + anomaly-count compare gates (host-only)
# --------------------------------------------------------------------------- #
def _write_health_run(path, bad_steps=0, warnings=0):
    os.makedirs(path, exist_ok=True)
    health = {
        "grad_norm": {"embeddings": 0.4, "block_0": 0.2, "head": 0.1},
        "param_norm": {"embeddings": 3.0, "block_0": 13.0, "head": 3.9},
        "update_norm": {"embeddings": 0.05, "block_0": 0.18, "head": 0.02},
        "update_ratio": {"embeddings": 0.016, "block_0": 0.013, "head": 0.005},
        "grad_norm_global": 0.64,
        "activations": {"embed": {"rms": 0.97, "absmax": 2.9}},
        "attention_entropy": {"block_0": [1.18, 1.11]},
        "attention_entropy_mean": 1.14,
        "embedding_coverage": 0.95,
        "logits": {"mean": -0.35, "absmax": 1.37, "std": 0.36},
    }
    events = [
        {"event": "on_fit_start", "time": 1.0, "epoch": 0, "epochs": 1},
        {"event": "on_train_step", "time": 2.0, "step": 1, "epoch": 0, "loss": 2.0,
         "lr": 1e-2, "samples_per_sec": 100.0, "steps_per_sec": 12.5,
         "step_seconds": 0.08, "health": health},
        *({"event": "on_health_warning", "time": 2.5, "step": 2, "epoch": 0,
           "signal": "grad_norm", "value": 10.0, "ewma": 1.0, "factor": 10.0,
           "blowup_factor": 5.0} for _ in range(warnings)),
        {"event": "on_epoch_end", "time": 3.0, "step": 2, "epoch": 0,
         "record": {"epoch": 0, "train_loss": 1.9}, "bad_steps": bad_steps,
         "grad_norm": 0.64, "health": health},
        {"event": "on_fit_end", "time": 4.0, "step": 2,
         "telemetry": {"steps": 1.0, "elapsed_seconds": 0.1, "steps_per_sec": 10.0,
                       "samples_per_sec": 80.0},
         "compile": {"train_step": {"traces": 1, "compile_seconds": 0.5}},
         "peak_memory_bytes": None, "history_len": 1, "bad_steps": bad_steps},
    ]
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        for event in events:
            fh.write(json.dumps(event) + "\n")
    return path


@pytest.mark.core
def test_report_renders_model_health_section(tmp_path):
    run = _write_health_run(str(tmp_path / "run"), warnings=2)
    summary = summarize_run(run)
    assert summary["health_warnings"] == 2
    assert summary["health"]["embedding_coverage"] == 0.95
    assert summary["last_grad_norm"] == pytest.approx(0.64)
    text = render(summary)
    assert "model health" in text
    assert "grad_norm 0.64" in text and "warnings 2" in text
    assert "emb coverage 95%" in text and "attn entropy 1.140 nats" in text
    assert "group grad norms" in text and "block_0" in text
    assert "activations" in text and "embed rms 0.97" in text


@pytest.mark.core
def test_compare_gates_on_anomaly_counts(tmp_path):
    from replay_tpu.obs.report import compare_runs

    baseline = summarize_run(_write_health_run(str(tmp_path / "base"), bad_steps=0))
    candidate = summarize_run(
        _write_health_run(str(tmp_path / "cand"), bad_steps=3, warnings=1)
    )
    lines, regressions = compare_runs(candidate, baseline)
    assert any("bad_steps: 3 vs 0" in line for line in lines)
    assert any("bad_steps increased 0 -> 3" in r for r in regressions)
    assert any("health warnings increased 0 -> 1" in r for r in regressions)
    # same counts in both directions is NOT a regression
    lines, regressions = compare_runs(baseline, baseline)
    assert not regressions
