import time as _time

_IMPORT_STARTED = _time.perf_counter()  # handed to the start-up log on the last line

from .schema_builder import TensorSchemaBuilder
from .utils import ensure_pandas, groupby_sequences
from .iterator import (
    DEFAULT_GROUND_TRUTH_PADDING_VALUE,
    DEFAULT_TRAIN_PADDING_VALUE,
    SequenceBatcher,
    TransformedBatches,
    validation_batches,
)
from .module import DataModule
from .packing import PackedSequenceBatcher, first_fit_pack
from .parquet import ParquetBatcher, StreamCursor, write_sequence_parquet
from .partitioning import Partitioning, ReplicasInfo
from .prefetch import DevicePrefetcher, prefetch
from .schema import TensorFeatureInfo, TensorFeatureSource, TensorMap, TensorSchema
from .sequence_tokenizer import SequenceTokenizer
from .sequential_dataset import SequentialDataset

# reference-API aliases, below every import they depend on:
# - the reference names its pandas-backed variant explicitly
#   (replay/data/nn/sequential_dataset.py); ours IS pandas-backed
# - batches are plain mutable dicts; the reference types the two separately
#   (replay/data/nn/schema.py)
PandasSequentialDataset = SequentialDataset
MutableTensorMap = TensorMap

__all__ = [
    "ensure_pandas",
    "groupby_sequences",
    "TensorSchemaBuilder",
    "DataModule",
    "PackedSequenceBatcher",
    "ParquetBatcher",
    "Partitioning",
    "StreamCursor",
    "first_fit_pack",
    "ReplicasInfo",
    "SequenceBatcher",
    "TransformedBatches",
    "DevicePrefetcher",
    "prefetch",
    "SequenceTokenizer",
    "SequentialDataset",
    "TensorFeatureInfo",
    "TensorFeatureSource",
    "TensorMap",
    "MutableTensorMap",
    "PandasSequentialDataset",
    "DEFAULT_GROUND_TRUTH_PADDING_VALUE",
    "DEFAULT_TRAIN_PADDING_VALUE",
    "TensorSchema",
    "validation_batches",
    "write_sequence_parquet",
]

# the seconds this package's own imports took, as `pkg_import` in the start-up
# log (obs.trace.startup_log)
from replay_tpu.obs.trace import package_imported as _package_imported

_package_imported(__name__, _IMPORT_STARTED)
