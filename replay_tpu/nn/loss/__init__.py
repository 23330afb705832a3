from .base import LossBase, broadcast_negatives, mask_negative_logits, masked_mean
from .bce import BCE, BCESampled, GBCE
from .ce import (
    CE,
    CEFused,
    CEFusedTP,
    CESampled,
    CESampledWeighted,
    CEWeighted,
    ExitWeightedCE,
    exit_distribution,
)
from .login_ce import LogInCE, LogInCESampled
from .logout_ce import LogOutCE, LogOutCEWeighted
from .sce import SCE, ScalableCrossEntropyLoss, SCEParams

# with a sampled negative pool, masking the other positives out of the softmax
# reduces to plain sampled CE — the reference ships the same literal alias
# (replay/nn/loss/__init__.py:7, `LogOutCESampled = CE`)
LogOutCESampled = CESampled
# protocol name used by the reference's typing surface
LossProto = LossBase

__all__ = [
    "BCE",
    "BCESampled",
    "CE",
    "CEFused",
    "CEFusedTP",
    "CESampled",
    "GBCE",
    "CESampledWeighted",
    "CEWeighted",
    "ExitWeightedCE",
    "LogInCE",
    "LogInCESampled",
    "LogOutCE",
    "LogOutCEWeighted",
    "LossBase",
    "LossProto",
    "LogOutCESampled",
    "SCE",
    "SCEParams",
    "ScalableCrossEntropyLoss",
    "broadcast_negatives",
    "exit_distribution",
    "mask_negative_logits",
    "masked_mean",
]
