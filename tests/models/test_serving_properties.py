"""Randomized property tests for the serving seams (VERDICT round-4 item 8).

Two invariants that single-case tests cannot pin down:

- ``CompiledInference`` bucket selection: every request size ≤ the largest
  bucket maps to the SMALLEST covering bucket, and the padded execution equals
  the uncompiled forward for every batch size (ref compiled-model contract,
  replay/models/nn/sequential/compiled/base_compiled_model.py:19-55).
- ``MIPSIndex`` shard-merge: mesh-sharded top-k == unsharded top-k for random
  catalogs, ks and query counts — including catalogs that do not divide the
  shard count (padding rows must never win).
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

import jax

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.models import MIPSIndex
from replay_tpu.nn import make_mesh
from replay_tpu.nn.compiled import CompiledInference
from replay_tpu.nn.sequential.sasrec import SasRec

pytestmark = [pytest.mark.jax, pytest.mark.smoke]

NUM_ITEMS, SEQ_LEN = 20, 6


@settings(max_examples=60, deadline=None)
@given(
    buckets=st.lists(st.integers(min_value=1, max_value=512), min_size=1, max_size=6, unique=True),
    data=st.data(),
)
def test_bucket_selection_is_smallest_covering(buckets, data):
    """Pure bucket-routing invariant over random bucket sets and request sizes."""
    chooser = CompiledInference(dict.fromkeys(buckets), SEQ_LEN, "dynamic_batch_size")
    batch = data.draw(st.integers(min_value=1, max_value=max(buckets)))
    got = chooser._bucket_for(batch)
    assert got == min(b for b in buckets if b >= batch)
    oversized = max(buckets) + 1
    with pytest.raises(ValueError, match="largest compiled bucket"):
        chooser._bucket_for(oversized)


@pytest.fixture(scope="module")
def compiled_and_model():
    schema = TensorSchema(
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=NUM_ITEMS,
                          embedding_dim=8)
    )
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, max_sequence_length=SEQ_LEN)
    ids = np.zeros((2, SEQ_LEN), np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), {"item_id": ids},
                                 np.ones((2, SEQ_LEN), bool))["params"]
    compiled = CompiledInference.compile(
        model, params, SEQ_LEN, mode="dynamic_batch_size", dynamic_buckets=(2, 3, 8)
    )
    return compiled, model, params


def test_every_batch_size_matches_uncompiled(compiled_and_model):
    """All sizes 1..max bucket run through padding and equal the plain forward —
    batches with padding rows, ragged masks, exact-bucket hits, everything."""
    compiled, model, params = compiled_and_model
    rng = np.random.default_rng(0)
    uncompiled = jax.jit(  # one program a batch size, not one a primitive
        lambda ids, mask: model.apply({"params": params}, {"item_id": ids}, mask,
                                      method=SasRec.forward_inference)
    )
    for batch in range(1, 9):
        ids = rng.integers(0, NUM_ITEMS, (batch, SEQ_LEN)).astype(np.int32)
        lengths = rng.integers(1, SEQ_LEN + 1, batch)
        mask = np.arange(SEQ_LEN)[None, :] >= (SEQ_LEN - lengths[:, None])
        got = compiled(ids, mask)
        assert got.shape == (batch, NUM_ITEMS)
        want = uncompiled(ids, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6)


@settings(max_examples=8, deadline=None)
@given(
    num_items=st.integers(min_value=9, max_value=70),
    dim=st.integers(min_value=2, max_value=12),
    num_queries=st.integers(min_value=1, max_value=6),
    k=st.integers(min_value=1, max_value=8),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_sharded_topk_equals_unsharded(num_items, dim, num_queries, k, seed):
    """Shard-merge invariant: per-shard top-k + global merge == brute force,
    for catalogs that mostly do NOT divide the 8-device mesh."""
    hypothesis.assume(k <= num_items)
    rng = np.random.default_rng(seed)
    items = rng.normal(size=(num_items, dim)).astype(np.float32)
    queries = rng.normal(size=(num_queries, dim)).astype(np.float32)
    s_scores, s_idx = MIPSIndex(items, mesh=make_mesh()).search(queries, k=k)
    brute = queries @ items.T
    want_idx = np.argsort(-brute, axis=1, kind="stable")[:, :k]
    # continuous gaussians: ties have measure zero, so indices match exactly
    np.testing.assert_array_equal(np.sort(s_idx, axis=1), np.sort(want_idx, axis=1))
    # each score is the dot product of the index returned BESIDE it. Both sides are
    # float32 sums of ``dim`` products in an order of their own: each is within
    # dim * eps * sum|q_i * x_i| of the exact value (the forward error bound of a
    # dot product), so they are within twice that of each other. Where a score
    # does not cancel this is tighter than a relative 1e-5; where it does, a
    # relative bound on the result is more than float32 gives.
    bound = 2 * dim * np.finfo(np.float32).eps * (np.abs(queries) @ np.abs(items).T)
    difference = np.abs(s_scores - np.take_along_axis(brute, s_idx, 1))
    assert (difference <= np.take_along_axis(bound, s_idx, 1)).all()
