"""Splitter base class: cold-entity dropping and session-boundary handling.

Capability parity with the reference Splitter ABC (replay/splitters/base_splitter.py:25-200):
``split()`` → (train, test), optional dropping of cold users/items from test, optional
session-id integrity (a session crossing the split boundary is moved wholly to train or
test), and ``save``/``load`` of init args into a ``.replay`` directory.

Strategies mark rows with a boolean test mask over the interactions frame and let the
base class materialize train/test — a single seam instead of the reference's
per-backend ``_core_split_*`` triplets.
"""

from __future__ import annotations

import json
from abc import ABC, abstractmethod
from pathlib import Path
from typing import Optional

import numpy as np
import pandas as pd

from replay_tpu.obs.trace import stage

SplitterReturnType = tuple[pd.DataFrame, pd.DataFrame]


class Splitter(ABC):
    """Base class of train/test splitting strategies."""

    _init_arg_names: list[str] = [
        "drop_cold_users",
        "drop_cold_items",
        "query_column",
        "item_column",
        "timestamp_column",
        "session_id_column",
        "session_id_processing_strategy",
    ]

    def __init__(
        self,
        drop_cold_items: bool = False,
        drop_cold_users: bool = False,
        query_column: str = "query_id",
        item_column: Optional[str] = "item_id",
        timestamp_column: Optional[str] = "timestamp",
        session_id_column: Optional[str] = None,
        session_id_processing_strategy: str = "test",
    ) -> None:
        if session_id_processing_strategy not in ("train", "test"):
            msg = "session_id_processing_strategy must be 'train' or 'test'"
            raise ValueError(msg)
        self.drop_cold_items = drop_cold_items
        self.drop_cold_users = drop_cold_users
        self.query_column = query_column
        self.item_column = item_column
        self.timestamp_column = timestamp_column
        self.session_id_column = session_id_column
        self.session_id_processing_strategy = session_id_processing_strategy

    # -- public API -------------------------------------------------------
    def split(self, interactions: pd.DataFrame) -> SplitterReturnType:
        """Split interactions into (train, test)."""
        # a pass over the whole log: a `split` stage (obs.trace.startup_log)
        with stage("split"):
            test_mask = np.asarray(self._test_mask(interactions), dtype=bool)
            if self.session_id_column is not None:
                test_mask = self._recover_sessions(interactions, test_mask)
            train = interactions[~test_mask]
            test = interactions[test_mask]
            return self._drop_cold(train, test)

    @abstractmethod
    def _test_mask(self, interactions: pd.DataFrame) -> np.ndarray:
        """Return a boolean mask marking the test rows."""

    # -- shared mechanics -------------------------------------------------
    def _recover_sessions(self, interactions: pd.DataFrame, test_mask: np.ndarray) -> np.ndarray:
        """Move sessions straddling the boundary wholly to train or test."""
        keys = [self.query_column, self.session_id_column]
        mask = pd.Series(test_mask, index=interactions.index)
        grouped = mask.groupby([interactions[k] for k in keys])
        frac_test = grouped.transform("mean")
        straddling = (frac_test > 0) & (frac_test < 1)
        if self.session_id_processing_strategy == "train":
            mask[straddling] = False
        else:
            mask[straddling] = True
        return mask.to_numpy()

    def _drop_cold(self, train: pd.DataFrame, test: pd.DataFrame) -> SplitterReturnType:
        if self.drop_cold_users:
            test = test[test[self.query_column].isin(set(train[self.query_column].unique()))]
        if self.drop_cold_items and self.item_column is not None:
            test = test[test[self.item_column].isin(set(train[self.item_column].unique()))]
        return train, test

    # -- persistence ------------------------------------------------------
    @property
    def _init_args(self) -> dict:
        return {name: getattr(self, name) for name in self._init_arg_names}

    def save(self, path: str) -> None:
        base = Path(path).with_suffix(".replay").resolve()
        base.mkdir(parents=True, exist_ok=True)
        payload = {"_class_name": str(self), "init_args": self._init_args}
        (base / "init_args.json").write_text(json.dumps(payload, default=str))

    @classmethod
    def load(cls, path: str, **kwargs) -> "Splitter":
        import inspect

        base = Path(path).with_suffix(".replay").resolve()
        payload = json.loads((base / "init_args.json").read_text())
        accepted = set(inspect.signature(cls.__init__).parameters)
        args = {k: v for k, v in payload["init_args"].items() if k in accepted}
        return cls(**{**args, **kwargs})

    def __str__(self) -> str:
        return type(self).__name__
