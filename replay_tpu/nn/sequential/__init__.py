from .bert4rec.model import Bert4Rec, Bert4RecBody
from .hybrid.model import HybridRec
from .sasrec.model import SasRec, SasRecBody
from .twotower import FeaturesReader, TwoTower

__all__ = [
    "Bert4Rec", "Bert4RecBody", "FeaturesReader", "HybridRec", "SasRec", "SasRecBody", "TwoTower",
]
