"""The route of the full-softmax head (``nn.loss.ce.full_softmax_route``).

``CE`` chooses between writing the logits (PLAIN) and the fused log-sum-exp head
(FUSED; SHARDED under a mesh) from what it can observe at trace time. On the CPU the
rule always answers PLAIN, so the tests that drive the fused route tell the rule it
sees a TPU (``ce._backend``) and hand the kernels the interpreter: a test steers the
code, no option of the program does.
"""

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE, CEFused, CEWeighted
from replay_tpu.nn.loss import ce
from replay_tpu.nn.loss.ce import FUSED, FUSED_MAX_WIDTH, PLAIN, SHARDED, full_softmax_route
from replay_tpu.obs.trace import chunk_stage_log

pytestmark = pytest.mark.jax

bf16, f32 = jnp.bfloat16, jnp.float32
DP4 = {"data": 4, "model": 1, "seq": 1}
BASE = dict(
    backend="tpu", tying_head=True, positives=1, rows=64, width=64, hidden_dtype=bf16,
    table_dtype=f32, mesh_shape=None, row_axes=("data",), vocab_axis="model",
)
RULE_CASES = [
    ("tpu-tying-d64", {}, FUSED),
    ("cpu", {"backend": "cpu"}, PLAIN),
    ("gpu", {"backend": "gpu"}, PLAIN),
    ("no-tying-head", {"tying_head": False, "table_dtype": None}, PLAIN),
    ("two-positives", {"positives": 2}, PLAIN),
    ("at-the-crossover", {"width": FUSED_MAX_WIDTH}, FUSED),
    ("past-the-crossover", {"width": FUSED_MAX_WIDTH + 1}, PLAIN),
    ("d300", {"width": 300}, PLAIN),
    ("d2048", {"width": 2048}, PLAIN),
    ("bf16-bf16", {"table_dtype": bf16}, FUSED),
    ("f32-f32", {"hidden_dtype": f32}, FUSED),
    ("f32-bf16", {"hidden_dtype": f32, "table_dtype": bf16}, FUSED),
    ("bf16-f16", {"table_dtype": jnp.float16}, PLAIN),
    ("bf16-int8", {"table_dtype": jnp.int8}, PLAIN),
    ("one-device-mesh", {"mesh_shape": {"data": 1, "model": 1, "seq": 1}}, FUSED),
    ("dp4", {"mesh_shape": DP4}, SHARDED),
    ("dp4-rows-do-not-divide", {"mesh_shape": DP4, "rows": 66}, PLAIN),
    ("dp4-past-the-crossover", {"mesh_shape": DP4, "width": 300}, PLAIN),
    ("dp4-cpu", {"mesh_shape": DP4, "backend": "cpu"}, PLAIN),
    ("dp2-tp2", {"mesh_shape": {"data": 2, "model": 2, "seq": 1}}, SHARDED),
    ("dp2-sp2", {"mesh_shape": {"data": 2, "model": 1, "seq": 2}, "row_axes": ("data", "seq")}, SHARDED),
    ("dp2-sp2-rows-do-not-divide",
     {"mesh_shape": {"data": 2, "model": 1, "seq": 2}, "row_axes": ("data", "seq"), "rows": 6}, PLAIN),
    ("tp-only-rows-replicated", {"mesh_shape": {"data": 1, "model": 4, "seq": 1}, "row_axes": ()}, SHARDED),
    ("mesh-without-the-vocab-axis", {"mesh_shape": {"x": 4}, "row_axes": ()}, PLAIN),
    ("mesh-without-a-row-axis", {"mesh_shape": {"model": 4}}, PLAIN),
]


@pytest.mark.parametrize("overrides,want", [c[1:] for c in RULE_CASES], ids=[c[0] for c in RULE_CASES])
def test_rule(overrides, want):
    assert full_softmax_route(**{**BASE, **overrides}) == want


def test_the_crossover_lies_under_the_width_where_the_fused_head_lost():
    assert 64 <= FUSED_MAX_WIDTH < 300


# -- the loss alone: value and gradient of both routes ----------------------- #
ITEMS, WIDTH, BATCH, LENGTH = 37, 16, 4, 8


def head_inputs(seed=0):
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.standard_normal((BATCH, LENGTH, WIDTH)), f32)
    table = jnp.asarray(rng.standard_normal((ITEMS, WIDTH)) * 0.3, f32)
    labels = jnp.asarray(rng.integers(0, ITEMS, (BATCH, LENGTH, 1)), jnp.int32)
    mask = jnp.asarray(rng.random((BATCH, LENGTH, 1)) < 0.8)
    return hidden, table, labels, mask


def as_on_a_tpu(monkeypatch, loss):
    """The rule sees a TPU; the kernels run interpreted at a toy row tile."""
    monkeypatch.setattr(ce, "_backend", lambda: "tpu")
    loss.interpret, loss.tile = True, 8
    return loss


def value_and_grads(loss, hidden, table, labels, mask, bind_table=True):
    def f(hidden, table):
        loss.logits_callback = lambda h: jnp.einsum("...e,ie->...i", h, table)
        loss.item_embeddings_callback = (lambda: table) if bind_table else None
        return loss(hidden, {}, labels, None, mask[..., 0], mask)

    return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(hidden, table)


@pytest.mark.parametrize(
    "make_loss", [CE, lambda: CEWeighted(np.linspace(0.5, 1.5, ITEMS).astype(np.float32))],
    ids=["CE", "CEWeighted"],
)
def test_fused_route_matches_the_plain_route(monkeypatch, make_loss):
    inputs = head_inputs()
    plain = make_loss()
    want, (want_dh, want_dw) = value_and_grads(plain, *inputs)
    assert plain.route == PLAIN and not plain.avoid_full_logits  # the CPU: no kernel chosen
    fused = as_on_a_tpu(monkeypatch, make_loss())
    got, (got_dh, got_dw) = value_and_grads(fused, *inputs)
    assert fused.route == FUSED and fused.avoid_full_logits
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got_dh, want_dh, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(got_dw, want_dw, rtol=1e-4, atol=1e-6)


def parents_ce(loss, model_embeddings, positive_labels, target_padding_mask):
    """``CE.__call__`` as it read before ``position_nll`` was factored out of it."""
    loss.route = loss._choose_route(model_embeddings, positive_labels.shape[-1])
    if loss.route == PLAIN:
        logits = loss.logits_callback(model_embeddings)
        labels = jnp.clip(positive_labels[..., 0], 0, logits.shape[-1] - 1)
        nll = ce._softmax_nll(logits, labels)
    else:
        labels, nll = loss._fused_nll(model_embeddings, positive_labels)
    weights = loss._label_weights(labels, nll.dtype)
    mask = target_padding_mask[..., 0].astype(nll.dtype) * weights
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


@pytest.mark.parametrize("on_a_tpu", [False, True], ids=["plain", "fused"])
def test_factoring_out_the_per_position_nll_leaves_ces_program_as_it_was(monkeypatch, on_a_tpu):
    hidden, table, labels, mask = head_inputs()
    loss = as_on_a_tpu(monkeypatch, CE()) if on_a_tpu else CE()

    def traced(call):
        def f(hidden, table, labels, padding, mask):
            loss.logits_callback = lambda h: jnp.einsum("...e,ie->...i", h, table)
            loss.item_embeddings_callback = lambda: table
            return call(hidden, labels, padding, mask)

        args = (hidden, table, labels, mask[..., 0], mask)
        return str(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1)))(*args))

    now = traced(lambda h, labels, padding, mask: loss(h, {}, labels, None, padding, mask))
    before = traced(lambda h, labels, padding, mask: parents_ce(loss, h, labels, mask))
    assert loss.route == (FUSED if on_a_tpu else PLAIN)
    assert now == before


def test_without_a_bound_table_the_head_stays_plain_on_a_tpu(monkeypatch):
    loss = as_on_a_tpu(monkeypatch, CE())
    value_and_grads(loss, *head_inputs(), bind_table=False)
    assert loss.route == PLAIN


def test_the_forced_classes_keep_their_route_whatever_the_backend():
    loss = CEFused(tile=8, interpret=True)
    assert loss.avoid_full_logits  # before any trace: health may ask
    want, _ = value_and_grads(CE(), *head_inputs())
    got, _ = value_and_grads(loss, *head_inputs())
    assert loss.route == FUSED
    np.testing.assert_allclose(got, want, rtol=1e-6)


# -- the trainer: what it binds, what the stage log says --------------------- #
class ToyTying(nn.Module):
    """An embedding and a bias-free tying head over it."""

    logits_via_item_weights = True

    def setup(self):
        self.embedding_item = nn.Embed(ITEMS, WIDTH, name="embedding_item")

    def __call__(self, feature_tensors, padding_mask):
        return self.embedding_item(feature_tensors["item_id"])

    def get_logits(self, hidden, candidates_to_score=None):
        return hidden @ self.embedding_item.embedding.T

    def get_item_weights(self):
        return self.embedding_item.embedding


class ToyBiased(ToyTying):
    """The same table under a head that adds a bias: no declaration."""

    logits_via_item_weights = False

    def get_logits(self, hidden, candidates_to_score=None):
        return hidden @ self.embedding_item.embedding.T + 1.0


def make_batch(seed):
    rng = np.random.default_rng(seed)
    items = rng.integers(0, ITEMS, (BATCH, LENGTH + 1)).astype(np.int32)
    mask = np.ones((BATCH, LENGTH), bool)
    return {
        "feature_tensors": {"item_id": items[:, :-1]}, "padding_mask": mask,
        "positive_labels": items[:, 1:, None], "target_padding_mask": mask[:, :, None],
    }


SCAN_CHUNK = 2


def chunked_fit(model, loss, devices):
    trainer = Trainer(
        model=model, loss=loss, optimizer=OptimizerFactory(name="sgd", learning_rate=0.1),
        mesh=make_mesh(jax.devices()[:devices]),
    )
    before = len(chunk_stage_log())
    losses = []
    sink = type("Sink", (), {"log_event": lambda self, e: e.event == "on_train_step" and losses.append(e.payload["loss"])})()
    trainer.fit([make_batch(i) for i in range(2 * SCAN_CHUNK)], epochs=1, loggers=sink,
                log_every=0, scan_chunk=SCAN_CHUNK)
    return trainer, losses, chunk_stage_log()[before:]


@pytest.fixture(scope="module")
def plain_fit():
    return chunked_fit(ToyTying(), CE(), 1)


def test_on_the_cpu_a_tying_model_gets_the_table_and_the_plain_route(plain_fit):
    trainer, losses, records = plain_fit
    loss = trainer.loss
    assert loss.item_embeddings_callback is not None and loss.mesh is trainer.mesh
    assert loss.route == PLAIN
    assert [r["ce_fused_steps"] for r in records] == [0, 0]


@pytest.mark.parametrize("devices,route", [(1, FUSED), (4, SHARDED)], ids=["one-device", "dp4"])
def test_fused_fit_follows_the_plain_one_and_counts_its_steps(monkeypatch, plain_fit, devices, route):
    trainer, losses, records = chunked_fit(ToyTying(), as_on_a_tpu(monkeypatch, CE()), devices)
    assert trainer.loss.route == route
    assert trainer.loss.data_axis == "data" and trainer.loss.axis_name == "model"
    assert [r["ce_fused_steps"] for r in records] == [SCAN_CHUNK, SCAN_CHUNK]
    np.testing.assert_allclose(losses, plain_fit[1], rtol=2e-6)


def test_a_model_without_the_declaration_is_refused_nothing_and_stays_plain(monkeypatch):
    loss = as_on_a_tpu(monkeypatch, CE())
    loss.item_embeddings_callback = lambda: None  # left over from another trainer's model
    trainer, losses, records = chunked_fit(ToyBiased(), loss, 1)
    assert loss.item_embeddings_callback is None and loss.route == PLAIN
    assert [r["ce_fused_steps"] for r in records] == [0, 0] and np.isfinite(losses).all()


@pytest.mark.parametrize("on_a_tpu,streamed", [(False, 0), (True, 1)], ids=["plain", "fused"])
def test_health_streams_its_logits_statistics_only_on_the_fused_route(monkeypatch, on_a_tpu, streamed):
    """``avoid_full_logits`` is a property of the route the traced step took."""
    import replay_tpu.obs.health as health
    from replay_tpu.obs import HealthConfig

    calls, real = [], health.streamed_logits_stats
    monkeypatch.setattr(
        health, "streamed_logits_stats", lambda *a, **k: calls.append(1) or real(*a, **k)
    )
    loss = as_on_a_tpu(monkeypatch, CE()) if on_a_tpu else CE()
    trainer = Trainer(
        model=ToyTying(), loss=loss, optimizer=OptimizerFactory(name="sgd", learning_rate=0.1),
        mesh=make_mesh(jax.devices()[:1]), health=HealthConfig(cadence=1),
    )
    batch = make_batch(0)
    trainer.train_step(trainer.init_state(batch), batch)
    stats = jax.device_get(trainer.last_step_metrics["health"])["logits"]
    assert len(calls) == streamed and loss.avoid_full_logits == bool(streamed)
    assert np.isfinite(float(stats["mean"])) and float(stats["absmax"]) > 0
