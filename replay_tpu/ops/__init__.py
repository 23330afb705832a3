from .flash_attention import flash_attention, fused_attention_available, pallas_interpret
from .fused_ce import fused_lse

__all__ = ["flash_attention", "fused_attention_available", "fused_lse", "pallas_interpret"]
