"""HybridRec, the layer-pattern next-item model, on the normal path: the same
``schema=`` constructor, ``Trainer.fit`` and ``CE`` as SasRec; the expert layers'
counters in the train metrics, the step events and the chunk stage log; every
parameter annotated by the one sharding-rule table."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replay_tpu.nn import OptimizerFactory, Trainer
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential import HybridRec, SasRec
from replay_tpu.obs.trace import Tracer, chunk_stage_log
from replay_tpu.parallel.sharding import LOGICAL_AXES, ShardingRules, logical_axes_tree

KEY = jax.random.PRNGKey(0)
PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py
KWARGS = dict(layer_types=("conv", "full_attention", "conv"), num_dense_layers=1, num_heads=4,
              num_kv_heads=2, dense_dim=24, expert_dim=8, num_experts=8, experts_held=4,
              expert_offset=2, experts_per_token=2)


@pytest.fixture
def model(item_only_schema):
    return HybridRec(schema=item_only_schema, **KWARGS)


def train_batches(count=4, rows=4, length=8, items=20, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        ids = rng.integers(0, items, (rows, length)).astype(np.int32)
        padding = np.arange(length)[None, :] >= rng.integers(0, 4, (rows, 1))
        out.append({
            "feature_tensors": {"item_id": np.where(padding, ids, items).astype(np.int32)},
            "padding_mask": padding,
            "positive_labels": rng.integers(0, items, (rows, length, 1)).astype(np.int32),
            "target_padding_mask": padding[..., None],
        })
    return out


def test_forward_and_scoring_shapes_match_sasrecs(model, batch):
    features, padding_mask = batch
    features = {"item_id": features["item_id"]}
    variables = jax.jit(model.init)(KEY, features, padding_mask)
    hidden = jax.jit(model.apply)(variables, features, padding_mask)
    assert hidden.shape == (4, 8, 16) and np.isfinite(np.asarray(hidden)).all()
    infer = jax.jit(partial(model.apply, method=HybridRec.forward_inference))
    scores = infer(variables, features, padding_mask)
    assert scores.shape == (4, 20)
    picked = infer(variables, features, padding_mask, jnp.array([1, 5, 7]))
    np.testing.assert_allclose(picked, np.asarray(scores)[:, [1, 5, 7]], rtol=2e-5)
    assert "positional_embedding" not in str(jax.tree_util.tree_structure(variables))
    assert HybridRec.logits_via_item_weights and SasRec.logits_via_item_weights


def test_padding_positions_never_reach_a_real_position(model, batch):
    features, padding_mask = batch
    ids = np.asarray(features["item_id"])
    padded = np.asarray(padding_mask).copy()
    padded[:, :3] = False  # left padding, as the batcher makes it
    variables = jax.jit(model.init)(KEY, {"item_id": ids}, padded)
    out = jax.jit(model.apply)(variables, {"item_id": ids}, padded)
    other = ids.copy()
    other[:, :3] = (other[:, :3] + 7) % 20  # another item under the padding
    moved = jax.jit(model.apply)(variables, {"item_id": other}, padded)
    np.testing.assert_array_equal(np.asarray(out[:, 3:]), np.asarray(moved[:, 3:]))


def test_every_parameter_is_annotated_and_experts_have_their_axis(model, batch):
    features, padding_mask = batch
    params = jax.jit(model.init)(KEY, {"item_id": features["item_id"]}, padding_mask)["params"]
    annotated = logical_axes_tree(params)
    moe = annotated["encoder"]["layer_1"]["moe"]
    assert moe["gate"] == ("expert", "embed", "mlp") and moe["out"] == ("expert", "mlp", "embed")
    assert moe["router"]["kernel"] == ("embed", None) and moe["expert_bias"] == (None,)
    attention = annotated["encoder"]["layer_1"]["attention"]
    assert attention["query"]["kernel"] == ("embed", "heads") and attention["q_norm"]["scale"] == ("kv",)
    conv = annotated["encoder"]["layer_0"]["conv"]
    assert conv["in_proj"]["kernel"] == ("embed", "mlp") and conv["kernel"] == (None, "embed")
    named = [any(name is not None for name in axes)
             for axes in jax.tree.leaves(annotated, is_leaf=lambda x: isinstance(x, tuple))]
    assert sum(named) == len(named) - 2  # all but the two selection biases
    assert "expert" in LOGICAL_AXES and ShardingRules.default().mesh_axis("expert") is None


@pytest.mark.parametrize("scan_chunk", [None, 2], ids=["per_step", "scan_chunks"])
def test_fit_trains_and_carries_the_expert_counters(model, scan_chunk):
    events = []

    class Sink:
        def log_event(self, event):
            if event.event == "on_train_step":
                events.append(event)

    trainer = PROGRAMS.adopt(
        Trainer(model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2), seed=3)
    )
    tracer = Tracer()
    batches = train_batches()
    before = len(chunk_stage_log())
    state = trainer.fit(batches, epochs=2, scan_chunk=scan_chunk, loggers=Sink(), log_every=0,
                        tracer=tracer)
    assert int(state.step) == 8 and int(state.bad_steps) == 0
    assert events[-1].payload["loss"] < events[0].payload["loss"]
    real_tokens = [int(b["padding_mask"].sum()) for b in batches]
    for event, tokens in zip(events, real_tokens * 2):
        counted = event.payload["counters"]
        load = np.asarray(counted["expert_load"])  # [expert layers, held experts]
        assert load.shape == (2, 4) and counted["dropped_assignments"] == [0, 0]
        assert (load.sum(axis=1) <= 2 * tokens).all() and load.sum() > 0
    metrics = trainer.last_step_metrics["counters"]
    assert metrics["expert_load"].shape[-2:] == (2, 4)
    if scan_chunk:
        records = chunk_stage_log()[before:]
        assert len(records) == 4
        assert np.asarray(records[-1]["counters"]["expert_load"]).shape == (2, 2, 4)
        spans = [e for e in tracer.to_chrome_trace()["traceEvents"] if e["name"] == "account"]
        assert len(spans) == 4 and all(e["args"]["dropped_assignments"] == 0 for e in spans)
        # the span that follows a chunk's sync carries what the chunk counted
        assert sorted(e["args"]["expert_load"] for e in spans) == sorted(
            int(np.sum(r["counters"]["expert_load"])) for r in records
        )


def test_the_expert_bias_is_a_buffer_the_optimizer_leaves_alone(model):
    trainer = PROGRAMS.adopt(
        Trainer(model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2), seed=3)
    )
    batches = train_batches()
    state = trainer.init_state(batches[0])
    bias = jax.tree.map(
        lambda x: x + 0.3, state.params["encoder"]["layer_1"]["moe"]["expert_bias"]
    )
    params = jax.tree.map(lambda x: x, state.params)
    params["encoder"]["layer_1"]["moe"]["expert_bias"] = bias
    state = trainer.init_state(batches[0], params=params)
    state = trainer.fit(batches, epochs=1, state=state, log_every=0)
    after = state.params["encoder"]["layer_1"]["moe"]
    np.testing.assert_array_equal(np.asarray(after["expert_bias"]), np.asarray(bias))
    assert not np.allclose(after["gate"], params["encoder"]["layer_1"]["moe"]["gate"])


# -- the window-and-full pattern: sliding + full attention on the fused route,
#    YaRN by layer type, softmax router, no dense layer, an untied head

WINDOWED = dict(
    layer_types=("sliding_attention", "sliding_attention", "full_attention"), num_dense_layers=0,
    num_heads=4, num_kv_heads=2, expert_dim=8, num_experts=8, experts_held=4, expert_offset=2,
    experts_per_token=2, router="softmax", sliding_window=3, fused_attention=True,
    tie_embeddings=False, rope_theta=100.0,
    rope_scaling={"full_attention": {"rope_type": "yarn", "rope_theta": 100, "factor": 4,
                                     "original_max_position_embeddings": 8, "beta_fast": 0.5,
                                     "beta_slow": 0.05, "attention_factor": 1.14},
                  "sliding_attention": {"rope_type": "default", "rope_theta": 100}},
)


def test_the_windowed_pattern_trains_through_fit_and_carries_both_kinds_of_counter(item_only_schema):
    model = HybridRec(schema=item_only_schema, **WINDOWED)
    trainer = PROGRAMS.share_init(
        Trainer(model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2), seed=3)
    )
    batches = train_batches()
    for b in batches:  # items 12..19 never come in
        ids = b["feature_tensors"]["item_id"]
        b["feature_tensors"]["item_id"] = np.where(b["padding_mask"], ids % 12, 20).astype(np.int32)
    before = len(chunk_stage_log())
    first = trainer.init_state(batches[0])
    tables = lambda state: (  # noqa: E731
        np.asarray(state.params["embedder"]["embedding_item_id"]["table"]["embedding"]),
        np.asarray(state.params["output_table"]),
    )
    table_before, head_before = tables(first)
    losses = []

    class Sink:
        def log_event(self, event):
            if event.event == "on_train_step":
                losses.append(event.payload["loss"])

    state = trainer.fit(batches, epochs=2, scan_chunk=2, state=first, loggers=Sink(), log_every=0)
    assert int(state.step) == 8 and int(state.bad_steps) == 0 and losses[-1] < losses[0]
    counted = chunk_stage_log()[before:][-1]["counters"]
    assert np.asarray(counted["expert_load"]).shape == (2, 3, 4)  # every layer is sparse
    assert np.asarray(counted["attention_blocks_visited"]).tolist() == [[[1, 1]] * 3] * 2
    # one block holds the 8 positions: the band is 3*8 - 3 = 21 of its 64 pairs, the half square 36
    np.testing.assert_allclose(counted["attention_blocks_needed"], [[21 / 64, 21 / 64, 36 / 64]] * 2)
    params = state.params
    assert "expert_bias" not in params["encoder"]["layer_0"]["moe"]
    assert logical_axes_tree(params)["output_table"] == ("vocab", "embed")
    # the head is its own table: items no batch brought in keep their input row, and lose their output row
    table_after, head_after = tables(state)
    np.testing.assert_array_equal(table_after[12:20], table_before[12:20])
    assert (np.abs(table_after[:12] - table_before[:12]).sum(axis=1) > 0).all()
    assert (np.abs(head_after - head_before).sum(axis=1) > 0).all()
    scores = model.apply({"params": params}, jnp.ones((2, 16)), method=HybridRec.get_logits)
    picked = model.apply({"params": params}, jnp.ones((2, 16)), jnp.array([1, 5]), method=HybridRec.get_logits)
    np.testing.assert_allclose(picked, np.asarray(scores)[:, [1, 5]], rtol=2e-5)


def test_a_pattern_needs_the_mask_only_for_a_full_layer_on_the_standard_route(item_only_schema):
    from replay_tpu.nn.blocks import needs_mask

    kinds = WINDOWED["layer_types"]
    assert not needs_mask(kinds, True) and needs_mask(kinds, False) and not needs_mask(kinds[:2], False)
    with pytest.raises(ValueError, match="sliding_window"):
        model = HybridRec(schema=item_only_schema, **{**WINDOWED, "sliding_window": None})
        ids = np.zeros((1, 8), np.int32)
        model.init(KEY, {"item_id": ids}, np.ones((1, 8), bool))
    with pytest.raises(ValueError, match="unknown router"):
        model = HybridRec(schema=item_only_schema, **{**WINDOWED, "router": "argmax"})
        model.init(KEY, {"item_id": np.zeros((1, 8), np.int32)}, np.ones((1, 8), bool))
