import time as _time

_IMPORT_STARTED = _time.perf_counter()  # handed to the start-up log on the last line

from . import loss
from .agg import ConcatAggregator, PositionAwareAggregator, SumAggregator
from .attention import MultiHeadAttention, MultiHeadDifferentialAttention, RMSNorm
from .embedding import (
    CategoricalEmbedding,
    CategoricalListEmbedding,
    IdentityEmbedding,
    NumericalEmbedding,
    SequenceEmbedding,
    xavier_normal_embed_init,
)
from .ffn import PointWiseFeedForward, SwiGLU, SwiGLUEncoder
from .utils import create_activation
from .head import EmbeddingTyingHead
from .mask import (
    DefaultAttentionMask,
    bidirectional_attention_mask,
    causal_attention_mask,
    padding_mask_from_ids,
)
from .postprocess import SeenItemsFilter
from .precision import PARITY_REL_TOL, Precision, fit_parity_record
from .vocabulary import (
    append_item_embeddings,
    get_item_embeddings,
    resize_item_embeddings,
    set_item_embeddings,
    set_item_embeddings_by_size,
    set_item_embeddings_by_tensor,
)
from .train import (
    LRSchedulerFactory,
    OptimizerFactory,
    PreemptionHandler,
    RecoveryPolicy,
    Trainer,
    TrainState,
    make_mesh,
)

# re-exported next to Trainer/RecoveryPolicy for the common attach pattern
# (Trainer(health=HealthConfig(...)), the obs.health diagnostics layer)
from replay_tpu.obs.health import HealthConfig, HealthWatcher

# the ONE sharding-rule table (Trainer(sharding_rules=...)) — re-exported next
# to make_mesh so the DP×TP×SP construction reads as one import
from replay_tpu.parallel.sharding import ShardingRules

__all__ = [
    "create_activation",
    "CategoricalEmbedding",
    "CategoricalListEmbedding",
    "ConcatAggregator",
    "DefaultAttentionMask",
    "EmbeddingTyingHead",
    "HealthConfig",
    "HealthWatcher",
    "IdentityEmbedding",
    "LRSchedulerFactory",
    "MultiHeadAttention",
    "MultiHeadDifferentialAttention",
    "NumericalEmbedding",
    "OptimizerFactory",
    "PARITY_REL_TOL",
    "PointWiseFeedForward",
    "Precision",
    "PositionAwareAggregator",
    "PreemptionHandler",
    "RecoveryPolicy",
    "RMSNorm",
    "SeenItemsFilter",
    "append_item_embeddings",
    "get_item_embeddings",
    "resize_item_embeddings",
    "set_item_embeddings",
    "set_item_embeddings_by_size",
    "set_item_embeddings_by_tensor",
    "SequenceEmbedding",
    "ShardingRules",
    "SumAggregator",
    "SwiGLU",
    "SwiGLUEncoder",
    "TrainState",
    "Trainer",
    "bidirectional_attention_mask",
    "causal_attention_mask",
    "fit_parity_record",
    "loss",
    "make_mesh",
    "padding_mask_from_ids",
]

# the seconds this package's own imports took, as `pkg_import` in the start-up
# log (obs.trace.startup_log)
from replay_tpu.obs.trace import package_imported as _package_imported

_package_imported(__name__, _IMPORT_STARTED)
