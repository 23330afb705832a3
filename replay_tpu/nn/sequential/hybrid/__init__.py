from .model import HybridRec

__all__ = ["HybridRec"]
