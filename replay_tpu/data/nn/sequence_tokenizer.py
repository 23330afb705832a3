"""Dataset → per-query padded-sequence tensors.

Capability parity with replay/data/nn/sequence_tokenizer.py:29-921: fit matches the
tensor schema against a :class:`~replay_tpu.data.dataset.Dataset`, fits a
:class:`~replay_tpu.data.dataset_label_encoder.DatasetLabelEncoder` over the
categorical features and assigns cardinalities; transform encodes the dataset,
groups interactions per query (sorted by timestamp) and materializes one array per
(query, feature) into a :class:`SequentialDataset`. ``save``/``load`` round-trip
the schema AND the fitted encoder mappings (ref sequence_tokenizer.py:409-509), so
a deployed model can encode raw ids identically.

Sources supported per feature (via its ``TensorFeatureSource``):
* INTERACTIONS + is_seq — a sequence column (item ids, ratings, …);
* ITEM_FEATURES + is_seq — item-side value looked up for every item of the
  sequence (join-then-group);
* QUERY_FEATURES, non-seq — one scalar per query.

TPU note: ITEM_ID features keep the schema's padding default (``cardinality``, the
LAST embedding row) so tied-weight logits align with item ids — see
replay_tpu/nn/embedding.py.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Optional, Sequence

import numpy as np
import pandas as pd

from replay_tpu.data.dataset import Dataset
from replay_tpu.data.dataset_label_encoder import DatasetLabelEncoder
from replay_tpu.data.nn.schema import TensorFeatureInfo, TensorSchema
from replay_tpu.data.nn.sequential_dataset import SequentialDataset
from replay_tpu.data.schema import FeatureSource
from replay_tpu.obs.trace import stage
from replay_tpu.preprocessing.label_encoder import HandleUnknownStrategies


class SequenceTokenizer:
    """Fit/transform bridge from dataframe land to model tensors."""

    def __init__(
        self,
        tensor_schema: TensorSchema,
        handle_unknown_rule: HandleUnknownStrategies = "error",
        default_value_rule: Optional[int | str] = None,
    ) -> None:
        self._schema = tensor_schema
        self._handle_unknown = handle_unknown_rule
        self._default_value = default_value_rule
        self._encoder = DatasetLabelEncoder(
            handle_unknown_rule=handle_unknown_rule, default_value_rule=default_value_rule
        )
        self._fitted = False

    tensor_schema = property(lambda self: self._schema)

    @property
    def interactions_encoder(self):
        """Encoder over interaction-frame columns (ref sequence_tokenizer.py:130)."""
        return self._encoder.interactions_encoder

    @property
    def query_features_encoder(self):
        return self._encoder.query_features_encoder

    @property
    def item_features_encoder(self):
        return self._encoder.item_features_encoder

    @property
    def query_id_encoder(self):
        return self._encoder.query_id_encoder

    @property
    def item_id_encoder(self):
        return self._encoder.item_id_encoder

    @property
    def query_and_item_id_encoder(self):
        return self._encoder.query_and_item_id_encoder

    def encode(self, dataset: Dataset) -> Dataset:
        """Id-encode a Dataset with the fitted rules WITHOUT sequencing it —
        e.g. to materialize encoded item features for TwoTower's FeaturesReader."""
        if not self._fitted:
            msg = "SequenceTokenizer is not fitted; call fit() first."
            raise RuntimeError(msg)
        return self._encoder.transform(dataset)

    # -- fit ---------------------------------------------------------------- #
    def fit(self, dataset: Dataset) -> "SequenceTokenizer":
        self._check_schema_against(dataset)
        self._encoder.fit(dataset)
        # assign cardinalities from the fitted mappings so padding defaults resolve
        for feature in self._schema.all_features:
            if feature.is_cat and feature.cardinality is None:
                source = feature.feature_source
                if source is not None:
                    rule = self._encoder._encoding_rules.get(source.column)
                    if rule is not None:
                        feature._set_cardinality(len(rule.get_mapping()))
        self._fitted = True
        return self

    def _check_schema_against(self, dataset: Dataset) -> None:
        frames = {
            FeatureSource.INTERACTIONS: dataset.interactions,
            FeatureSource.QUERY_FEATURES: dataset.query_features,
            FeatureSource.ITEM_FEATURES: dataset.item_features,
        }
        for feature in self._schema.all_features:
            source = feature.feature_source
            if source is None:
                continue
            frame = frames.get(source.source)
            if frame is None:
                msg = f"Feature '{feature.name}' sources {source.source}, absent from dataset."
                raise ValueError(msg)
            if source.column not in frame.columns:
                msg = f"Column '{source.column}' for feature '{feature.name}' not found."
                raise ValueError(msg)

    # -- transform ----------------------------------------------------------- #
    def transform(
        self, dataset: Dataset, tensor_features_to_keep: Optional[Sequence[str]] = None
    ) -> SequentialDataset:
        if not self._fitted:
            msg = "SequenceTokenizer is not fitted; call fit() first."
            raise RuntimeError(msg)
        schema = (
            self._schema.subset(tensor_features_to_keep)
            if tensor_features_to_keep is not None
            else self._schema
        )
        encoded = self._encoder.transform(dataset)
        query_col = dataset.feature_schema.query_id_column
        ts_col = dataset.feature_schema.interactions_timestamp_column
        interactions = encoded.interactions
        sort_cols = [query_col] + ([ts_col] if ts_col else [])
        interactions = interactions.sort_values(sort_cols, kind="stable")

        # join item-side sequential features onto the interaction log
        item_seq_features = [
            f
            for f in schema.all_features
            if f.is_seq
            and f.feature_source is not None
            and f.feature_source.source == FeatureSource.ITEM_FEATURES
        ]
        if item_seq_features:
            item_col = dataset.feature_schema.item_id_column
            item_frame = encoded.item_features.set_index(item_col)
            for feature in item_seq_features:
                interactions = interactions.assign(
                    **{
                        f"__item_{feature.name}": interactions[
                            item_col
                        ].map(item_frame[feature.feature_source.column])
                    }
                )

        grouped = interactions.groupby(query_col, sort=True)
        query_order = pd.Index(list(grouped.groups))
        data: dict = {query_col: list(query_order)}

        for feature in schema.all_features:
            source = feature.feature_source
            if feature.is_seq:
                if source is not None and source.source == FeatureSource.ITEM_FEATURES:
                    column = f"__item_{feature.name}"
                else:
                    column = source.column if source else feature.name
                series = grouped[column].apply(lambda s: np.asarray(s.to_numpy()))
                data[feature.name] = series.reindex(query_order).to_list()
            else:
                if source is None or source.source != FeatureSource.QUERY_FEATURES:
                    msg = (
                        f"Non-sequential feature '{feature.name}' must source "
                        "QUERY_FEATURES (one value per query)."
                    )
                    raise ValueError(msg)
                lookup = encoded.query_features.set_index(query_col)[source.column]
                data[feature.name] = lookup.reindex(query_order).to_numpy().tolist()

        frame = pd.DataFrame(data)
        item_feature_name = schema.item_id_feature_name
        return SequentialDataset(
            tensor_schema=schema,
            query_id_column=query_col,
            item_id_column=item_feature_name,
            sequences=frame,
        )

    def fit_transform(
        self, dataset: Dataset, tensor_features_to_keep: Optional[Sequence[str]] = None
    ) -> SequentialDataset:
        with stage("tokenize"):  # obs.trace.startup_log: the encoders and the group-by
            return self.fit(dataset).transform(dataset, tensor_features_to_keep)

    # -- persistence --------------------------------------------------------- #
    def save(self, path: str) -> None:
        target = Path(path).with_suffix(".replay")
        target.mkdir(parents=True, exist_ok=True)
        (target / "init_args.json").write_text(
            json.dumps(
                {
                    "_class_name": "SequenceTokenizer",
                    "handle_unknown_rule": self._handle_unknown,
                    "default_value_rule": self._default_value,
                    "fitted": self._fitted,
                }
            )
        )
        (target / "schema.json").write_text(self._schema.to_json())
        # one serialization format for encoding rules everywhere: the rule's own
        # _as_dict/_from_dict (shared with LabelEncoder.save/load)
        mappings = {
            column: rule._as_dict()
            for column, rule in self._encoder._encoding_rules.items()
        }
        (target / "encoder_mappings.json").write_text(json.dumps(mappings))
        columns = {
            "query": getattr(self._encoder, "_query_column_name", None),
            "item": getattr(self._encoder, "_item_column_name", None),
            # per-source column map backing the sub-encoder views
            "by_source": {
                source.name: cols
                for source, cols in self._encoder._columns_by_source.items()
            },
        }
        (target / "encoder_columns.json").write_text(json.dumps(columns))

    @classmethod
    def load(cls, path: str) -> "SequenceTokenizer":
        from replay_tpu.preprocessing.label_encoder import LabelEncodingRule

        source = Path(path).with_suffix(".replay")
        args = json.loads((source / "init_args.json").read_text())
        schema = TensorSchema.from_json((source / "schema.json").read_text())
        tokenizer = cls(
            schema,
            handle_unknown_rule=args["handle_unknown_rule"],
            default_value_rule=args["default_value_rule"],
        )
        mappings = json.loads((source / "encoder_mappings.json").read_text())
        for column, spec in mappings.items():
            if isinstance(spec, list):  # pre-unification format: [[label, code], ...]
                rule = LabelEncodingRule(
                    column,
                    mapping={label: code for label, code in spec},
                    handle_unknown=args["handle_unknown_rule"],
                    default_value=args["default_value_rule"],
                )
            else:
                rule = LabelEncodingRule._from_dict(spec)
            tokenizer._encoder._encoding_rules[column] = rule
        columns = json.loads((source / "encoder_columns.json").read_text())
        tokenizer._encoder._query_column_name = columns["query"]
        tokenizer._encoder._item_column_name = columns["item"]
        from replay_tpu.data.schema import FeatureSource

        tokenizer._encoder._columns_by_source = {
            FeatureSource[name]: cols
            # absent in artifacts saved before the per-source views existed:
            # the views then report None rather than a wrong grouping
            for name, cols in columns.get("by_source", {}).items()
        }
        tokenizer._fitted = args["fitted"]
        return tokenizer

