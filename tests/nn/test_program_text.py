"""The train step of every model kind the benchmark's other cells run, held byte for
byte to its text before the looped model came (PR 37): the loop, the sandwich
norms, the q/k-norm switch, recomputation in the layer-pattern stack, the loss's
per-position factoring and the Trainer's plumbing of sown exits and loss counters
are python-static, and their defaults add nothing to a program.

Each case lowers one ``Trainer`` train step (bfloat16 compute, as the cells run)
of a tiny model with an expert cell's layer kinds (lfm2: conv, full attention,
sparse experts; mellum2: sliding and full attention on the fused route, YaRN, an
untied head; moonlight: latent attention, a shared expert) or SASRec's, and
compares a digest of its StableHLO, locations left out, with the one the parent
of PR 37 lowered. A PR that changes one of these programs on purpose puts the new
digest here and says why in CHANGES.md."""

import hashlib
import re

import jax
import numpy as np
import pytest

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential import HybridRec, SasRec

pytestmark = pytest.mark.jax

ITEMS, D, ROWS, LENGTH = 20, 16, 2, 8
SCHEMA = TensorSchema(TensorFeatureInfo(
    "item_id", FeatureType.CATEGORICAL, is_seq=True, feature_hint=FeatureHint.ITEM_ID,
    cardinality=ITEMS, padding_value=ITEMS, embedding_dim=D))
MODELS = {
    "lfm2": (HybridRec, dict(
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1, num_heads=4,
        num_kv_heads=2, dense_dim=24, expert_dim=8, num_experts=8, experts_held=4,
        expert_offset=2, experts_per_token=2)),
    "mellum2": (HybridRec, dict(
        layer_types=("sliding_attention", "full_attention"), num_dense_layers=0, num_heads=4,
        num_kv_heads=2, expert_dim=8, num_experts=8, experts_held=4, expert_offset=2,
        experts_per_token=2, router="softmax", sliding_window=3, fused_attention=True,
        tie_embeddings=False, rope_theta=100.0,
        rope_scaling={"full_attention": {"rope_type": "yarn", "rope_theta": 100, "factor": 4,
                                         "original_max_position_embeddings": 8, "beta_fast": 0.5,
                                         "beta_slow": 0.05, "attention_factor": 1.14},
                      "sliding_attention": {"rope_type": "default", "rope_theta": 100}})),
    "moonlight": (HybridRec, dict(
        layer_types=("latent_attention",) * 2, num_dense_layers=1, num_heads=4, num_kv_heads=4,
        head_dim=8, rope_head_dim=4, value_head_dim=8, kv_latent_dim=12, rope_theta=100.0,
        dense_dim=40, expert_dim=8, shared_expert_dim=12, num_experts=16, experts_held=8,
        expert_offset=4, experts_per_token=6, router="sigmoid", routed_scale=2.446,
        tie_embeddings=False)),
    "sasrec": (SasRec, dict(embedding_dim=D, num_blocks=2, num_heads=2, max_sequence_length=LENGTH)),
}
# sha256 of the parent's lowered text, first 16 hex digits; the three expert
# models' since the expert layer's passes walk a rung of its buffer (nn.moe)
PARENT = {
    "lfm2": "f5ff52c4298a34d0",
    "mellum2": "73641d6fb51c69d5",
    "moonlight": "d481180513621dc4",
    "sasrec": "c39c3e3d12c59659",
}


def batch():
    rng = np.random.default_rng(0)
    padding = np.arange(LENGTH)[None, :] >= np.array([[0], [3]])
    return {
        "feature_tensors": {"item_id": np.where(padding, rng.integers(0, ITEMS, (ROWS, LENGTH)),
                                                ITEMS).astype(np.int32)},
        "padding_mask": padding,
        "positive_labels": rng.integers(0, ITEMS, (ROWS, LENGTH, 1)).astype(np.int32),
        "target_padding_mask": padding[..., None],
        "valid": np.ones(ROWS, bool),
    }


def program_digest(name: str) -> str:
    cls, kwargs = MODELS[name]
    trainer = Trainer(model=cls(schema=SCHEMA, **kwargs), loss=CE(),
                      optimizer=OptimizerFactory(learning_rate=1e-3), precision="bf16",
                      mesh=make_mesh(jax.devices()[:1]), seed=3)
    placed = batch()
    # the text depends on the shapes alone: zeros of the init's shapes, no init program
    shapes = jax.eval_shape(trainer._init_params, placed)
    state = trainer.init_state(placed, params=jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes))
    text = jax.jit(trainer._build_train_step(None)).lower(state, placed).as_text()
    text = re.sub(r"loc\([^\n]*|#loc[^\n]*", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_train_step_lowers_to_the_parents_text(name):
    assert program_digest(name) == PARENT[name]
