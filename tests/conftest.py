"""Test harness: force an 8-device virtual CPU platform BEFORE jax is imported.

Mirrors the reference's trick of faking torch.distributed (SURVEY.md §4): the multi-chip
sharding paths are validated on a host-only mesh, no TPUs required.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402

from replay_tpu.data import Dataset, FeatureHint, FeatureInfo, FeatureSchema, FeatureType  # noqa: E402


@pytest.fixture
def interactions_pandas() -> pd.DataFrame:
    return pd.DataFrame(
        {
            "user_id": [0, 0, 0, 1, 1, 2, 2, 2, 2, 3],
            "item_id": [0, 1, 2, 0, 2, 3, 1, 2, 0, 3],
            "rating": [1.0, 2.0, 3.0, 4.0, 5.0, 1.0, 2.0, 3.0, 4.0, 5.0],
            "timestamp": [0, 1, 2, 3, 4, 5, 6, 7, 8, 9],
        }
    )


@pytest.fixture
def feature_schema() -> FeatureSchema:
    return FeatureSchema(
        [
            FeatureInfo("user_id", FeatureType.CATEGORICAL, FeatureHint.QUERY_ID),
            FeatureInfo("item_id", FeatureType.CATEGORICAL, FeatureHint.ITEM_ID),
            FeatureInfo("rating", FeatureType.NUMERICAL, FeatureHint.RATING),
            FeatureInfo("timestamp", FeatureType.NUMERICAL, FeatureHint.TIMESTAMP),
        ]
    )


@pytest.fixture
def dataset(feature_schema, interactions_pandas) -> Dataset:
    return Dataset(feature_schema=feature_schema, interactions=interactions_pandas)


@pytest.fixture
def rng() -> np.random.Generator:
    return np.random.default_rng(0)


def pytest_collection_modifyitems(config, items):
    """Auto-mark tests: ``jax`` for device-touching paths, ``core`` for the rest.

    Mirrors the reference's core/torch marker split (its CI runs them as separate
    job families) so the fast dataframe tier stays seconds-fast.
    """
    import pytest as _pytest

    jax_paths = ("tests/nn", "tests/parallel", "tests/models/nn", "test_builder", "test_train")
    for item in items:
        if item.get_closest_marker("jax") or item.get_closest_marker("core"):
            continue  # explicitly marked
        path = str(item.fspath)
        if any(fragment in path for fragment in jax_paths):
            item.add_marker(_pytest.mark.jax)
        else:
            item.add_marker(_pytest.mark.core)
