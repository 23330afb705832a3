"""Collective + sharding introspection (parallel.introspect), and the
CEFusedTP no-table-gather regression guard.

Core tier parses synthetic HLO text (pure regex, no jax). The jax tier lowers
the real programs on the virtual 8-device mesh: the guard asserts PR 7's core
invariant STATICALLY — ``CEFusedTP``'s lowered program contains no all-gather
of the ``[I/n_tp, E]`` item-table shard, only the ``[rows]``-sized lse/max
combine collectives — so a future lowering/sharding change that silently
regathers the catalog fails CI before any memory graph is eyeballed.
"""

import numpy as np
import pytest

from replay_tpu.parallel.introspect import (
    collective_bytes,
    collective_inventory,
    summarize_collectives,
)

_SYNTHETIC_HLO = """
ENTRY %main {
  %all-gather.1 = f32[2,4]{1,0} all-gather(f32[1,4]{1,0} %slice.1), channel_id=1, replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}, use_global_device_ids=true
  %all-reduce.3 = f32[256,32]{1,0} all-reduce(f32[256,32]{1,0} %dot.9), channel_id=4, replica_groups={{0,2,4,6},{1,3,5,7}}, use_global_device_ids=true, to_apply=%region_25
  %reduce-scatter.1 = f32[1,4]{1,0} reduce-scatter(f32[2,4]{1,0} %fusion.2), channel_id=2, replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}, to_apply=%region_24
  %all-reduce.9 = f32[] all-reduce(f32[] %add.1), channel_id=5, replica_groups=[2,4]<=[4,2]T(1,0), use_global_device_ids=true, to_apply=%region_10
  %ag-start = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %p0), replica_groups={{0,1}}, dimensions={0}
  %ag-done = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ag-start)
  %all-gather.7 = bf16[16,128]{1,0:T(8,128)(2,1)S(1)} all-gather(bf16[8,128]{1,0:T(8,128)(2,1)} %p3), channel_id=9, replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}, use_global_device_ids=true
  %mul.2 = f32[8,8]{1,0} multiply(f32[8,8]{1,0} %p4, f32[8,8]{1,0} %all-gather.1)
  ROOT %dot.1 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %p1, f32[8,8]{1,0} %p2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


@pytest.mark.core
def test_collective_inventory_parses_ops_shapes_and_groups():
    inventory = collective_inventory(_SYNTHETIC_HLO, mesh_shape={"data": 4, "model": 2})
    by_name = {entry["name"]: entry for entry in inventory}
    assert set(by_name) == {
        "all-gather.1", "all-reduce.3", "reduce-scatter.1", "all-reduce.9",
        "ag-start", "all-gather.7",
    }  # -done halves skipped; dot/mul (collective only as OPERAND) excluded
    # TPU-optimized layouts carry tiling/memory-space annotations — the real
    # hardware's as_text() must parse or the guard is inert exactly there
    tpu_layout = by_name["all-gather.7"]
    assert tpu_layout["bytes"] == 16 * 128 * 2
    assert tpu_layout["mesh_axis"] == "model"
    gather = by_name["all-gather.1"]
    assert gather["op"] == "all-gather"
    assert gather["bytes"] == 2 * 4 * 4
    assert gather["group_size"] == 2
    assert gather["mesh_axis"] == "model"  # consecutive-id groups = last axis
    reduce = by_name["all-reduce.3"]
    assert reduce["bytes"] == 256 * 32 * 4
    assert reduce["mesh_axis"] == "data"  # stride == model size = first axis
    iota = by_name["all-reduce.9"]
    assert iota["group_size"] == 4  # [2,4]<=... iota form: 2 groups of 4
    start = by_name["ag-start"]
    assert start["bytes"] == (4 + 8) * 4  # tuple shape sums elements


@pytest.mark.core
def test_collective_summary_and_bytes():
    inventory = collective_inventory(_SYNTHETIC_HLO)
    summary = summarize_collectives(inventory)
    assert summary["count"] == 6
    assert summary["bytes"] == collective_bytes(inventory)
    assert summary["by_op"]["all-reduce"]["count"] == 2
    assert summary["by_op"]["all-gather"]["count"] == 3
    assert summarize_collectives([]) == {"count": 0, "bytes": 0, "by_op": {}}


@pytest.mark.core
def test_collective_inventory_empty_for_collective_free_hlo():
    assert collective_inventory("ENTRY %main { ROOT %x = f32[4]{0} add(%a, %b) }") == []


# --------------------------------------------------------------------------- #
# jax tier: the CEFusedTP no-table-gather guard (8-device DPxTP mesh)
# --------------------------------------------------------------------------- #
def _tp_head_program(num_items, embed, rows, n_tp):
    """value_and_grad of the TP-sharded fused-lse head, lowered on a DPxTP
    mesh — the exact program whose table-locality PR 7 established."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from replay_tpu.nn import make_mesh
    from replay_tpu.parallel.sharded_ce import sharded_fused_lse

    mesh = make_mesh(model_parallel=n_tp)
    rng = np.random.default_rng(0)
    hidden = jax.device_put(
        rng.normal(size=(rows, embed)).astype(np.float32),
        NamedSharding(mesh, P("data", None)),
    )
    table = jax.device_put(
        rng.normal(size=(num_items, embed)).astype(np.float32),
        NamedSharding(mesh, P("model", None)),
    )

    def head(hidden, table):
        return jnp.sum(
            sharded_fused_lse(hidden, table, mesh, tile=8, interpret=True)
        )

    jitted = jax.jit(jax.value_and_grad(head, argnums=(0, 1)))
    return jitted.lower(hidden, table).compile().as_text(), mesh


@pytest.mark.jax
@pytest.mark.smoke
def test_cefused_tp_head_never_gathers_the_table_shard():
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs the virtual multi-device mesh")
    n_tp = 2
    num_items, embed, rows = 4096, 64, 16  # shard table 512 kB >> combine bytes
    hlo, mesh = _tp_head_program(num_items, embed, rows, n_tp)
    inventory = collective_inventory(
        hlo, mesh_shape={axis: int(n) for axis, n in mesh.shape.items()}
    )
    shard_table_bytes = num_items // n_tp * embed * 4
    gathers = [e for e in inventory if e["op"] == "all-gather"]
    oversized = [e for e in gathers if (e.get("bytes") or 0) >= shard_table_bytes]
    assert not oversized, (
        "CEFusedTP's head all-gathers table-shard-sized tensors — the memory "
        f"wall is back: {oversized}"
    )
    # the lse/max combine IS there, and it is [rows]-sized: n_tp scalars per
    # row at most (async gathers report tuple shapes, <= 2x the bound)
    assert gathers, f"expected the lse-combine all-gather in: {inventory}"
    combine_bound = 2 * n_tp * rows * 4
    assert all((e.get("bytes") or 0) <= combine_bound for e in gathers), gathers
    # dW stays shard-local over the model axis: no model-axis reduce touches
    # table-sized tensors either (the data-axis grad psum legitimately does)
    model_reduces = [
        e
        for e in inventory
        if e["op"] in ("all-reduce", "reduce-scatter")
        and e.get("mesh_axis") == "model"
        and (e.get("bytes") or 0) >= shard_table_bytes
    ]
    assert not model_reduces, model_reduces


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


@pytest.mark.jax
def test_full_cefused_tp_train_scan_guard_via_trainer():
    """The same guard through the PRODUCTION program: the dryrun's chunked
    CEFusedTP fit — lowered from the trainer's recorded templates."""
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs the virtual multi-device mesh")
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CEFusedTP
    from replay_tpu.nn.sequential.sasrec import SasRec

    n_tp, num_items, embed, seq_len = 2, 511, 16, 6
    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID, cardinality=num_items,
            embedding_dim=embed,
        )
    )
    model = SasRec(schema=schema, embedding_dim=embed, num_blocks=1, num_heads=1,
                   max_sequence_length=seq_len)
    trainer = PROGRAMS.share_init(Trainer(  # two init_state calls: one jitted init
        model=model, loss=CEFusedTP(tile=8, interpret=True),
        optimizer=OptimizerFactory(learning_rate=1e-2),
        mesh=make_mesh(model_parallel=n_tp), shard_vocab=True,
    ))
    batch_size = 8

    def mk(seed):
        gen = np.random.default_rng(seed)
        items = gen.integers(0, num_items, size=(batch_size, seq_len + 1)).astype(np.int32)
        mask = np.ones((batch_size, seq_len), dtype=bool)
        return {"feature_tensors": {"item_id": items[:, :-1]}, "padding_mask": mask,
                "positive_labels": items[:, 1:, None],
                "target_padding_mask": mask[:, :, None]}

    trainer.fit([mk(i) for i in range(4)], epochs=1, scan_chunk=2, log_every=0)
    mesh_shape = {axis: int(n) for axis, n in trainer.mesh.shape.items()}
    inventory = collective_inventory(trainer.lowered_hlo("train_scan"), mesh_shape)
    # table rows pad to the shard grid: (511 + 1 padding row) / 2 per shard
    shard_table_bytes = (num_items + 1) // n_tp * embed * 4
    oversized = [
        e for e in inventory
        if e["op"] == "all-gather" and (e.get("bytes") or 0) >= shard_table_bytes
    ]
    assert not oversized, oversized

    # sharding introspection: the vocab table IS model-sharded (no flags)
    from replay_tpu.parallel.introspect import sharding_report

    batch = mk(99)
    state = trainer.init_state(batch)
    report = sharding_report(state.params, trainer.mesh, expect_sharded=("embedding_",))
    assert report["flags"] == []
    assert report["sharded_bytes"] > 0
    specs = {row["path"]: row["spec"] for row in report["params"]}
    assert any(
        "embedding_" in path and spec and "model" in spec for path, spec in specs.items()
    ), specs


@pytest.mark.jax
def test_sharding_report_flags_accidental_replication():
    """A vocab-sized table left replicated on a TP mesh is exactly the silent
    failure the flag exists for."""
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs the virtual multi-device mesh")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from replay_tpu.nn import make_mesh
    from replay_tpu.parallel.introspect import sharding_report

    mesh = make_mesh(model_parallel=2)
    params = {
        "embedding_item_id": {
            "embedding": jax.device_put(
                np.zeros((64, 8), np.float32), NamedSharding(mesh, P())
            )
        }
    }
    report = sharding_report(params, mesh, expect_sharded=("embedding_",))
    assert len(report["flags"]) == 1
    assert "accidental replication" in report["flags"][0]
    assert report["replicated_bytes"] == 64 * 8 * 4
