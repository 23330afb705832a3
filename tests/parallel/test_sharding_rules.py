"""The ONE sharding-rule table (parallel.sharding): logical-axis rules drive
every placement, the non-divisible fallback warns loudly, the ring-attention
DP×TP×SP production fit matches the unsharded fit, and the compiled SP program
moves exactly the intended collectives (ppermute ring traffic, no table
gather, no full-sequence all-gather).
"""

import warnings

import numpy as np
import pytest

from replay_tpu.parallel.sharding import (
    LOGICAL_AXES,
    ShardingRules,
    ShardingRuleWarning,
    _reset_rule_warnings,
    logical_axes,
)

# --------------------------------------------------------------------------- #
# core tier: the rule table + annotator are pure python
# --------------------------------------------------------------------------- #


@pytest.mark.core
def test_default_table_maps_the_dp_tp_sp_layout():
    rules = ShardingRules.default(shard_vocab=True)
    assert rules.mesh_axis("batch") == "data"
    assert rules.mesh_axis("length") == "seq"
    assert rules.mesh_axis("vocab") == "model"
    assert rules.mesh_axis("embed") is None
    assert ShardingRules.default().mesh_axis("vocab") is None  # TP is opt-in
    described = rules.describe()
    assert described["batch"] == "data" and described["vocab"] == "model"


@pytest.mark.core
def test_unknown_logical_name_is_an_error_not_replication():
    rules = ShardingRules.default()
    with pytest.raises(KeyError, match="unknown logical axis"):
        rules.mesh_axis("vocabb")
    with pytest.raises(KeyError, match="unknown logical axis"):
        rules.with_rule("vocabb", "model")


@pytest.mark.core
def test_with_rule_is_immutable_override():
    base = ShardingRules.default()
    tp = base.with_rule("vocab", "model")
    assert base.mesh_axis("vocab") is None
    assert tp.mesh_axis("vocab") == "model"


@pytest.mark.core
def test_annotator_covers_the_model_param_families():
    class Leaf:
        def __init__(self, *shape):
            self.shape = shape

    cases = {
        "body/embedder/embedding_item_id/table/embedding": (Leaf(16, 8), ("vocab", "embed")),
        "body/aggregator/positional_embedding": (Leaf(50, 8), ("position", "embed")),
        "body/mask_embedding": (Leaf(8,), ("embed",)),
        "body/encoder/block_0/attention/query/kernel": (Leaf(8, 8), ("embed", "heads")),
        "body/encoder/block_0/attention/out/kernel": (Leaf(8, 8), ("heads", "embed")),
        "body/encoder/block_0/ffn/inner/kernel": (Leaf(8, 32), ("embed", "mlp")),
        "body/encoder/block_0/ffn/outer/kernel": (Leaf(32, 8), ("mlp", "embed")),
        "body/encoder/block_0/attn_norm/scale": (Leaf(8,), ("embed",)),
        "body/final_norm/bias": (Leaf(8,), ("embed",)),
        # scan_blocks stacks a leading layers axis on every block param
        "body/encoder/blocks/block/attention/query/kernel": (
            Leaf(2, 8, 8), ("layers", "embed", "heads"),
        ),
        # unknown leaves replicate — never guessed from shapes
        "some/unknown/param": (Leaf(4, 4), (None, None)),
    }
    for path, (leaf, want) in cases.items():
        assert logical_axes(path, leaf) == want, path
    assert all(
        name in LOGICAL_AXES
        for _, (leaf, want) in cases.items()
        for name in want
        if name is not None
    )


# --------------------------------------------------------------------------- #
# jax tier: placement, parity, refusal and collective invariants on the
# virtual 8-device mesh
# --------------------------------------------------------------------------- #
NUM_ITEMS = 15  # 16-row table (cardinality + padding) divides model axes 2/4
SEQ_LEN = 8  # divides seq axes 2/4
BATCH = 4


def make_schema(cardinality=NUM_ITEMS):
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema

    return TensorSchema(
        TensorFeatureInfo(
            "item_id",
            FeatureType.CATEGORICAL,
            is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            cardinality=cardinality,
            embedding_dim=16,
        )
    )


def make_batch(seed, batch=BATCH, num_items=NUM_ITEMS):
    rng = np.random.default_rng(seed)
    items = rng.integers(0, num_items, size=(batch, SEQ_LEN + 1)).astype(np.int32)
    mask = np.ones((batch, SEQ_LEN), dtype=bool)
    return {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer(mesh, use_flash=False, num_items=NUM_ITEMS, loss=None, **kwargs):
    """Every trainer's flax init runs under ``jax.jit`` (``share_init``: eagerly
    the ring route's ``shard_map`` is dispatched op by op on the 8-device mesh);
    the plain one-device trainers, the one configuration built more than once,
    share their two programs."""
    from replay_tpu.nn import OptimizerFactory, Trainer
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.sasrec import SasRec

    model = SasRec(
        schema=make_schema(num_items), embedding_dim=16, num_blocks=2,
        max_sequence_length=SEQ_LEN, use_flash=use_flash,
    )
    trainer = Trainer(
        model=model,
        loss=loss if loss is not None else CE(),
        # SGD: parity asserts near-exact equivalence; adaptive optimizers
        # amplify device-count-dependent summation noise (test_mesh_training)
        optimizer=OptimizerFactory(name="sgd", learning_rate=0.1),
        mesh=mesh,
        seed=0,
        **kwargs,
    )
    plain = mesh.size == 1 and not use_flash and num_items == NUM_ITEMS and loss is None
    if plain and not kwargs:
        return PROGRAMS.adopt(trainer)
    return PROGRAMS.share_init(trainer)


@pytest.mark.jax
@pytest.mark.smoke
def test_params_placed_by_the_rule_table():
    import jax

    from replay_tpu.nn import make_mesh

    trainer = make_trainer(make_mesh(model_parallel=2), shard_vocab=True)
    state = trainer.init_state(make_batch(0))
    specs = {
        jax.tree_util.keystr(path): str(leaf.sharding.spec)
        for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
    }
    vocab = [spec for path, spec in specs.items() if "embedding_item_id" in path]
    assert vocab and all("model" in spec for spec in vocab), specs
    others = [
        spec for path, spec in specs.items() if "embedding_item_id" not in path
    ]
    assert others and all("model" not in spec for spec in others), specs


@pytest.mark.jax
@pytest.mark.smoke
def test_non_divisible_vocab_warns_once_and_replicates():
    """Satellite: the silent shard_vocab fallback is now loud — a table whose
    rows don't divide the model axis warns ONCE with the shape/axis, then
    replicates."""
    import jax

    from replay_tpu.nn import make_mesh

    _reset_rule_warnings()
    # cardinality 14 -> 15-row table: not divisible by the 2-way model axis
    trainer = make_trainer(make_mesh(model_parallel=2), num_items=14, shard_vocab=True)
    with pytest.warns(ShardingRuleWarning, match=r"15 rows.*2-way.*model"):
        state = trainer.init_state(make_batch(0, num_items=14))
    specs = [
        str(leaf.sharding.spec)
        for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
        if "embedding_item_id" in jax.tree_util.keystr(path)
    ]
    assert specs and all("model" not in spec for spec in specs), specs
    # once per process: the same offending leaf does not warn again
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        trainer.init_state(make_batch(1, num_items=14))
    assert not [w for w in caught if issubclass(w.category, ShardingRuleWarning)]


@pytest.mark.jax
@pytest.mark.smoke
def test_ring_sp_fit_matches_unsharded_fit():
    """The SP production path: a DP×TP×SP chunked fit through ring attention
    equals the single-device fit (losses and params)."""
    import jax

    from replay_tpu.nn import make_mesh

    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")

    def run(mesh, use_flash, **kwargs):
        trainer = make_trainer(mesh, use_flash=use_flash, **kwargs)
        batches = [make_batch(s) for s in range(4)]
        state = trainer.fit(batches, epochs=1, scan_chunk=2, log_every=0)
        return (
            [float(r["train_loss"]) for r in trainer.history],
            jax.tree.map(np.asarray, state.params),
        )

    losses_1, params_1 = run(make_mesh(jax.devices()[:1]), False)
    losses_sp, params_sp = run(
        make_mesh(model_parallel=2, seq_parallel=2), "ring", shard_vocab=True
    )
    np.testing.assert_allclose(losses_1, losses_sp, rtol=2e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5),
        params_1,
        params_sp,
    )


@pytest.mark.jax
def test_bert4rec_ring_sp_matches_unsharded():
    """The second model body: Bert4Rec's bidirectional attention through the
    ring SP route equals the single-device fit — one rule table, both models."""
    import jax

    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.bert4rec import Bert4Rec

    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")

    def mlm_batch(seed):
        rng = np.random.default_rng(seed)
        items = rng.integers(0, NUM_ITEMS, size=(BATCH, SEQ_LEN)).astype(np.int32)
        mask = np.ones((BATCH, SEQ_LEN), bool)
        token_mask = rng.random((BATCH, SEQ_LEN)) > 0.3
        return {
            "feature_tensors": {"item_id": items},
            "padding_mask": mask,
            "token_mask": token_mask,
            "positive_labels": items[:, :, None],
            "target_padding_mask": (~token_mask)[:, :, None],
        }

    def run(mesh, use_flash):
        model = Bert4Rec(
            schema=make_schema(), embedding_dim=16, num_blocks=2, num_heads=2,
            max_sequence_length=SEQ_LEN, use_flash=use_flash,
        )
        trainer = PROGRAMS.share_init(Trainer(
            model=model, loss=CE(),
            optimizer=OptimizerFactory(name="sgd", learning_rate=0.1),
            mesh=mesh, seed=0,
        ))
        state = trainer.init_state(mlm_batch(0))
        out = []
        for step in range(3):
            state, loss_value = trainer.train_step(state, mlm_batch(step))
            out.append(float(loss_value))
        return out

    base = run(make_mesh(jax.devices()[:1]), False)
    sp = run(make_mesh(model_parallel=2, seq_parallel=2), "ring")
    np.testing.assert_allclose(base, sp, rtol=2e-4)


@pytest.mark.jax
def test_ring_sp_fit_parity_at_the_bf16_band():
    """The precision ladder composes with SP: the bf16 DP×SP ring fit stays
    within the bf16 input-rounding band of the bf16 unsharded fit."""
    import jax

    from replay_tpu.nn import make_mesh

    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")

    def run(mesh, use_flash):
        trainer = make_trainer(mesh, use_flash=use_flash, precision="bf16")
        batches = [make_batch(s) for s in range(3)]
        trainer.fit(batches, epochs=1, log_every=0)
        return [float(r["train_loss"]) for r in trainer.history]

    base = run(make_mesh(jax.devices()[:1]), False)
    sp = run(make_mesh(seq_parallel=4), "ring")
    assert all(np.isfinite(base)) and all(np.isfinite(sp))
    np.testing.assert_allclose(base, sp, rtol=5e-2)


@pytest.mark.jax
def test_ring_attention_op_level_parity_under_scope():
    """Op-level: the MultiHeadAttention ring route under the trainer's scope
    equals the standard einsum route with the SAME params."""
    import jax
    import jax.numpy as jnp

    from replay_tpu.nn import make_mesh
    from replay_tpu.nn.attention import MultiHeadAttention
    from replay_tpu.nn.mask import causal_attention_mask
    from replay_tpu.parallel.sharding import sharding_scope

    mesh = make_mesh(seq_parallel=4)
    rules = ShardingRules.default()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, SEQ_LEN, 16)).astype(np.float32))
    padding = jnp.ones((2, SEQ_LEN), bool)

    standard = MultiHeadAttention(num_heads=2)
    params = standard.init(
        jax.random.PRNGKey(0), x, causal_attention_mask(padding), padding_mask=padding
    )
    want = standard.apply(params, x, causal_attention_mask(padding), padding_mask=padding)
    ring = MultiHeadAttention(num_heads=2, use_flash="ring")
    with sharding_scope(rules, mesh):
        got = jax.jit(
            lambda p, x: ring.apply(p, x, None, padding_mask=padding, causal=True)
        )(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)


@pytest.mark.jax
@pytest.mark.smoke
def test_packed_segments_meet_sp_route_rejected():
    """Satellite: PackedSequenceBatcher segment masks meeting the ring SP
    route must refuse (the flash-route refusal policy), never silently attend
    across packed segment boundaries."""
    import jax

    from replay_tpu.nn import make_mesh

    trainer = make_trainer(make_mesh(seq_parallel=2), use_flash="ring")
    batch = make_batch(0)
    batch["segment_ids"] = np.ones((BATCH, SEQ_LEN), np.int32)
    with pytest.raises(ValueError, match="ring SP route"):
        state = trainer.init_state({k: v for k, v in batch.items() if k != "segment_ids"})
        trainer.train_step(state, batch)


@pytest.mark.jax
def test_seq_parallel_without_ring_route_rejected():
    """A seq>1 mesh under a model that would build [B, 1, L, L] masks is a
    configuration error (XLA would all-gather the sequence), not a silent
    performance cliff."""
    from replay_tpu.nn import make_mesh

    with pytest.raises(ValueError, match="ring"):
        make_trainer(make_mesh(seq_parallel=2), use_flash=False)


@pytest.mark.jax
@pytest.mark.smoke
def test_sp_program_collectives_are_exactly_the_intended_ones():
    """The compiled DP×TP×SP step: ppermute-only ring traffic on the seq axis,
    no item-table-sized all-gather, no full-sequence activation all-gather —
    the rule table produces exactly the intended collectives."""
    import jax

    from replay_tpu.nn import make_mesh
    from replay_tpu.nn.loss import CEFusedTP
    from replay_tpu.parallel.introspect import collective_inventory, sharding_report

    if jax.device_count() < 8:
        pytest.skip("needs the virtual 8-device mesh")
    mesh = make_mesh(model_parallel=2, seq_parallel=2)
    trainer = make_trainer(
        mesh, use_flash="ring", shard_vocab=True,
        loss=CEFusedTP(tile=8, interpret=True),
    )
    # the rule table routes the loss's layout too: catalog over the vocab
    # rule, flattened [B·L] rows over (batch, length)
    batch = make_batch(0)
    state = trainer.init_state(batch)
    state, loss_value = trainer.train_step(state, batch)
    assert np.isfinite(float(loss_value))
    assert trainer.loss.axis_name == "model"
    assert trainer.loss.data_axis == ("data", "seq")

    report = sharding_report(state.params, mesh, rules=trainer.sharding_rules)
    assert report["flags"] == [], report["flags"]
    assert report["sharded_bytes"] > 0

    hlo = trainer.lowered_hlo("train_step")
    inventory = collective_inventory(
        hlo, mesh_shape={axis: int(n) for axis, n in mesh.shape.items()}
    )
    permutes = [e for e in inventory if e["op"] == "collective-permute"]
    assert permutes, "ring attention left no ppermute traffic"
    # ring traffic on the seq axis is ppermute-only at activation scale: an
    # all-gather of a [B_local, L, E] (or bigger) tensor over seq would be
    # the full-sequence materialization SP exists to avoid. Param-sized
    # combines (the replicated positional table's gradient) stay legal.
    full_seq_bytes = (BATCH // 2) * SEQ_LEN * 16 * 4  # [B/dp, L, E] f32
    seq_gathers = [
        e for e in inventory
        if e["op"] == "all-gather"
        and e.get("mesh_axis") == "seq"
        and (e.get("bytes") or 0) >= full_seq_bytes
    ]
    assert not seq_gathers, seq_gathers
    # the item table (16 padded rows × 16 f32 = 1 KiB) must never be gathered
    # to one device over the model axis — only the [rows]-sized lse combine
    # and sub-table-sized resharding traffic may move there
    full_table_bytes = (NUM_ITEMS + 1) * 16 * 4
    table_gathers = [
        e for e in inventory
        if e["op"] == "all-gather"
        and e.get("mesh_axis") == "model"
        and (e.get("bytes") or 0) >= full_table_bytes
    ]
    assert not table_gathers, table_gathers


@pytest.mark.jax
def test_rule_table_report_flags_accidental_replication():
    """sharding_report(rules=...): a table the rules wanted sharded but that
    lowered replicated is flagged — the silent degeneration mode."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from replay_tpu.nn import make_mesh
    from replay_tpu.parallel.introspect import sharding_report

    mesh = make_mesh(model_parallel=2)
    trainer = make_trainer(mesh, shard_vocab=True)
    state = trainer.init_state(make_batch(0))
    # force the vocab table fully replicated behind the rules' back
    broken = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (
            jax.device_put(leaf, NamedSharding(mesh, P()))
            if "embedding_item_id" in jax.tree_util.keystr(path)
            else leaf
        ),
        state.params,
    )
    report = sharding_report(broken, mesh, rules=trainer.sharding_rules)
    assert any("accidental replication" in flag for flag in report["flags"]), report


@pytest.mark.jax
def test_scan_blocks_trains_and_stacks_params():
    """scan-over-blocks: one scanned block body, [layers, ...] params, finite
    losses, and the annotator prepends the layers axis."""
    import jax

    from replay_tpu.nn import make_mesh
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn import OptimizerFactory, Trainer
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.parallel.sharding import logical_axes

    model = SasRec(
        schema=make_schema(), embedding_dim=16, num_blocks=3,
        max_sequence_length=SEQ_LEN, scan_blocks=True,
    )
    trainer = PROGRAMS.share_init(Trainer(
        model=model, loss=CE(),
        optimizer=OptimizerFactory(name="sgd", learning_rate=0.1),
        mesh=make_mesh(jax.devices()[:1]), remat_policy="dots", seed=0,
    ))
    state = trainer.init_state(make_batch(0))
    stacked = [
        (path, leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
        if "blocks" in jax.tree_util.keystr(path)
    ]
    assert stacked and all(leaf.shape[0] == 3 for _, leaf in stacked)
    path, leaf = next(
        (p, l) for p, l in stacked if "kernel" in jax.tree_util.keystr(p)
    )
    assert logical_axes(path, leaf)[0] == "layers"
    state, loss_value = trainer.train_step(state, make_batch(1))
    assert np.isfinite(float(loss_value))


@pytest.mark.jax
def test_remat_policy_is_numerically_invisible():
    """Trainer(remat_policy=...) trades HBM for FLOPs only: losses equal the
    un-rematerialized fit exactly."""
    import jax

    from replay_tpu.nn import make_mesh

    def run(**kwargs):
        trainer = make_trainer(make_mesh(jax.devices()[:1]), **kwargs)
        state = trainer.init_state(make_batch(0))
        losses = []
        for step in range(3):
            state, loss_value = trainer.train_step(state, make_batch(step))
            losses.append(float(loss_value))
        return losses

    np.testing.assert_allclose(run(), run(remat_policy="full"), rtol=1e-6)
    np.testing.assert_allclose(run(), run(remat_policy="dots"), rtol=1e-6)
