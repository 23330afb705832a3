"""Per-query sequence container.

Capability parity with replay/data/nn/sequential_dataset.py:18-316: holds one row
per query with array-valued feature columns (the output of the sequence tokenizer),
supports lookup by position or query id, query filtering, alignment of two splits
to their common queries, and parquet save/load.

Host-side by design: this is the boundary between dataframe land and the
fixed-shape batcher (replay_tpu.data.nn.iterator) that feeds the device.
"""

from __future__ import annotations

from pathlib import Path
from typing import Sequence, Tuple

import numpy as np
import pandas as pd

from replay_tpu.data.nn.schema import TensorSchema


class SequentialDataset:
    """Sequences of every tensor-schema feature, one row per query."""

    def __init__(
        self,
        tensor_schema: TensorSchema,
        query_id_column: str,
        item_id_column: str,
        sequences: pd.DataFrame,
    ) -> None:
        if query_id_column not in sequences.columns:
            msg = f"Query id column '{query_id_column}' missing from sequences."
            raise ValueError(msg)
        for name in tensor_schema:
            if name not in sequences.columns:
                msg = f"Tensor feature '{name}' missing from sequences."
                raise ValueError(msg)
        self._schema = tensor_schema
        self._query_id_column = query_id_column
        self._item_id_column = item_id_column
        self._sequences = sequences.reset_index(drop=True)
        self._query_index = pd.Index(self._sequences[query_id_column])

    schema = property(lambda self: self._schema)
    query_id_column = property(lambda self: self._query_id_column)
    item_id_column = property(lambda self: self._item_id_column)

    def __len__(self) -> int:
        return len(self._sequences)

    @property
    def query_ids(self) -> np.ndarray:
        return self._sequences[self._query_id_column].to_numpy()

    def get_all_query_ids(self) -> np.ndarray:
        """Reference-name accessor for :attr:`query_ids`
        (ref data/nn/sequential_dataset.py)."""
        return self.query_ids

    def get_all_sequences(self, feature_name: str) -> np.ndarray:
        """One feature's column as ONE array, a query's value per entry (an
        object array where the values are sequences): for a consumer that walks
        every row (the batcher's flat layout), where :meth:`get_sequence` is a
        pandas lookup a row."""
        return self._sequences[feature_name].to_numpy()

    def get_query_id(self, index: int):
        return self._sequences[self._query_id_column].iloc[index]

    def get_sequence(self, index: int, feature_name: str) -> np.ndarray:
        return np.asarray(self._sequences[feature_name].iloc[index])

    def get_sequence_by_query_id(self, query_id, feature_name: str) -> np.ndarray:
        position = self._query_index.get_loc(query_id)
        return np.asarray(self._sequences[feature_name].iloc[position])

    def get_sequence_length(self, index: int) -> int:
        return len(self.get_sequence(index, self._item_id_column))

    def get_max_sequence_length(self) -> int:
        if not len(self):
            return 0
        return int(self._sequences[self._item_id_column].map(len).max())

    def filter_by_query_id(self, query_ids) -> "SequentialDataset":
        keep = self._sequences[self._query_id_column].isin(np.asarray(query_ids))
        return SequentialDataset(
            self._schema, self._query_id_column, self._item_id_column, self._sequences[keep]
        )

    @staticmethod
    def keep_common_query_ids(
        left: "SequentialDataset", right: "SequentialDataset"
    ) -> Tuple["SequentialDataset", "SequentialDataset"]:
        """Align two splits (e.g. train histories vs validation targets) to the
        queries present in both."""
        common = np.intersect1d(left.query_ids, right.query_ids)
        return left.filter_by_query_id(common), right.filter_by_query_id(common)

    # -- persistence ------------------------------------------------------- #
    def save(self, path: str) -> None:
        target = Path(path).with_suffix(".replay")
        target.mkdir(parents=True, exist_ok=True)
        import json

        (target / "init_args.json").write_text(
            json.dumps(
                {
                    "_class_name": "SequentialDataset",
                    "query_id_column": self._query_id_column,
                    "item_id_column": self._item_id_column,
                }
            )
        )
        (target / "schema.json").write_text(self._schema.to_json())
        frame = self._sequences.copy()
        for name in self._schema:
            if frame[name].map(lambda v: isinstance(v, np.ndarray)).any():
                frame[name] = frame[name].map(lambda v: np.asarray(v).tolist())
        frame.to_parquet(target / "sequences.parquet")

    @classmethod
    def load(cls, path: str) -> "SequentialDataset":
        import json

        source = Path(path).with_suffix(".replay")
        args = json.loads((source / "init_args.json").read_text())
        schema = TensorSchema.from_json((source / "schema.json").read_text())
        frame = pd.read_parquet(source / "sequences.parquet")
        for name in schema:
            if schema[name].is_seq:
                frame[name] = frame[name].map(np.asarray)
        return cls(schema, args["query_id_column"], args["item_id_column"], frame)
