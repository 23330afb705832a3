"""The contract of ``nn.transform`` (its module docstring): numpy in, numpy out;
a random draw is one compiled program; the data a key gives does not change.

The oracles are the formulas the transforms had while they worked in eager
``jax.numpy`` (``eager_*`` below): the arrays a pipeline returns for a key are
the ones it returned then, bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn.transform import (
    AdaptiveTrimTransform,
    Compose,
    CopyTransform,
    EqualityMaskTransform,
    GroupTransform,
    InBatchNegativeSamplingTransform,
    MultiClassNegativeSamplingTransform,
    NextTokenTransform,
    RenameTransform,
    SegmentBoundaryMaskTransform,
    SelectTransform,
    SequenceRollTransform,
    TokenMaskTransform,
    TrimTransform,
    UniformNegativeSamplingTransform,
    UnsqueezeTransform,
    make_default_bert4rec_transforms,
    make_default_sasrec_transforms,
)
from replay_tpu.obs.trace import claim_chunk

pytestmark = pytest.mark.jax

BATCH, SEQ_LEN, NUM_ITEMS = 6, 9, 40


def raw_batch(seed=0):
    """A left-padded batch as the batchers emit it: numpy leaves only."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, SEQ_LEN + 1, size=BATCH)
    lengths[0], lengths[1] = SEQ_LEN, 1  # a full row and a row of one
    mask = np.arange(SEQ_LEN)[None, :] >= SEQ_LEN - lengths[:, None]
    items = np.where(mask, rng.integers(0, NUM_ITEMS, (BATCH, SEQ_LEN)), 0).astype(np.int32)
    segments = np.where(mask, 1 + (np.arange(SEQ_LEN)[None, :] > SEQ_LEN // 2), 0).astype(np.int32)
    return {
        "item_id": items,
        "item_id_mask": mask,
        "segment_ids": segments,
        "weight": rng.random((BATCH, SEQ_LEN, 3)).astype(np.float32),
        "valid": np.ones(BATCH, bool),
    }


def on_device(batch):
    return jax.tree.map(jnp.asarray, batch)


def assert_same_tree(left, right):
    assert jax.tree.structure(left) == jax.tree.structure(right)
    for a, b in zip(jax.tree.leaves(left), jax.tree.leaves(right)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def with_target_mask(batch):
    return {**batch, "target_padding_mask": batch["item_id_mask"][:, 1:]}


# (transform, what it is given: a function of the raw batch)
DETERMINISTIC = {
    "next_token": (NextTokenTransform("item_id", apply_to=["item_id", "weight"]), dict),
    "next_token_makes_its_mask": (
        NextTokenTransform("item_id", shift=2),
        lambda raw: {"item_id": raw["item_id"], "valid": raw["valid"]},
    ),
    "segment_boundary": (SegmentBoundaryMaskTransform(), with_target_mask),
    "roll_right": (SequenceRollTransform("item_id", roll=2, padding_value=7), dict),
    "roll_left": (SequenceRollTransform("weight", roll=-3, padding_value=0), dict),
    "roll_past_the_end": (SequenceRollTransform("item_id", roll=SEQ_LEN + 4), dict),
    "trim": (TrimTransform(4, ["item_id", "item_id_mask", "weight"]), dict),
    "equality_and": (EqualityMaskTransform("item_id", "item_id_mask", 0, "and"), dict),
    "equality_or": (EqualityMaskTransform("item_id", "item_id_mask", 3, "or"), dict),
    "equality_xor": (EqualityMaskTransform("segment_ids", "item_id_mask", 1, "xor"), dict),
    "unsqueeze_last": (UnsqueezeTransform("item_id", -1), dict),
    "unsqueeze_middle": (UnsqueezeTransform("weight", 1), dict),
    "in_batch_negatives": (InBatchNegativeSamplingTransform(label_name="weight"), dict),
    "copy": (CopyTransform({"item_id": "positive_labels"}), dict),
    "rename": (RenameTransform({"item_id_mask": "padding_mask"}), dict),
    "select": (SelectTransform(["item_id", "valid"]), dict),
    "group": (GroupTransform({"feature_tensors": ["item_id", "weight"]}), dict),
}


@pytest.mark.parametrize("name", list(DETERMINISTIC) + ["adaptive_trim"])
def test_numpy_in_gives_numpy_out_and_what_jnp_gives(name):
    transform, given = DETERMINISTIC.get(
        name, (AdaptiveTrimTransform(["item_id", "item_id_mask"], "item_id_mask"), None)
    )
    raw = raw_batch()
    if given is None:  # no full row, so something is trimmed
        raw = {key: value[2:] for key, value in raw.items()}
        given = dict
    before = jax.tree.map(np.copy, given(raw))
    host = transform(given(raw))
    assert all(type(leaf) is np.ndarray for leaf in jax.tree.leaves(host)), name
    device = transform(on_device(given(raw)))
    assert all(isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(device)), name
    assert_same_tree(host, device)
    assert_same_tree(given(raw), before)  # a transform writes into nothing it was given


@pytest.mark.parametrize("name", list(DETERMINISTIC))
def test_still_traces_under_jit(name):
    transform, given = DETERMINISTIC[name]
    batch = given(raw_batch())
    assert_same_tree(jax.jit(transform)(batch), transform(batch))


def test_adaptive_trim_is_host_only():
    transform = AdaptiveTrimTransform(["item_id"], "item_id_mask")
    with pytest.raises(jax.errors.ConcretizationTypeError):
        jax.jit(transform)(raw_batch())


@pytest.mark.parametrize("roll", [1, 3, -2, SEQ_LEN, -(SEQ_LEN + 1)])
def test_roll_is_what_roll_and_refill_gave(roll):
    value = raw_batch()["item_id"]
    rolled = jnp.roll(value, roll, axis=1)
    if roll > 0:
        rolled = rolled.at[:, :roll].set(5)
    else:
        rolled = rolled.at[:, roll:].set(5)
    out = SequenceRollTransform("item_id", roll=roll, padding_value=5)({"item_id": value})
    np.testing.assert_array_equal(out["item_id"], np.asarray(rolled))


def eager_token_mask(rng, padding, mask_prob):
    """``TokenMaskTransform.__call__`` as it was: a string of eager programs."""
    uniform = jax.random.uniform(rng, padding.shape)
    keep = (uniform * padding) >= mask_prob
    valid_count = padding.sum(axis=1)
    kept_count = (keep & padding).sum(axis=1)
    all_kept = kept_count == valid_count
    last_valid = padding.shape[1] - 1 - jnp.argmax(padding[:, ::-1], axis=1)
    rows = jnp.arange(padding.shape[0])
    keep = keep.at[rows, last_valid].set(jnp.where(all_kept, False, keep[rows, last_valid]))
    none_kept = (kept_count == 0) & (valid_count > 1)
    before_last = jnp.maximum(last_valid - 1, 0)
    keep = keep.at[rows, before_last].set(jnp.where(none_kept, True, keep[rows, before_last]))
    return keep


@pytest.fixture(scope="module")
def token_mask():
    return TokenMaskTransform("item_id_mask", mask_prob=0.2)


@pytest.mark.parametrize("seed", range(20))
def test_token_mask_kernel_gives_the_eager_mask_bit_for_bit(token_mask, seed):
    padding = raw_batch(seed)["item_id_mask"]
    key = jax.random.PRNGKey(1000003 * seed + 17)
    out = token_mask({"item_id_mask": padding}, key)
    assert out["item_id_mask"] is padding
    np.testing.assert_array_equal(
        np.asarray(out["token_mask"]), np.asarray(eager_token_mask(key, padding, 0.2))
    )


@pytest.mark.parametrize(
    ("case", "mask_prob"),
    # 1e-9: under every draw but 0.0 itself, over the 0.0 of a padded position
    [("nothing_masked", 1e-9), ("everything_masked", 1.0), ("row_of_one", 1e-9), ("row_of_one", 1.0)],
)
def test_token_mask_corner_cases(case, mask_prob):
    padding = raw_batch()["item_id_mask"]
    key = jax.random.PRNGKey(5)
    keep = np.asarray(
        TokenMaskTransform("item_id_mask", mask_prob=mask_prob)({"item_id_mask": padding}, key)[
            "token_mask"
        ]
    )
    np.testing.assert_array_equal(keep, np.asarray(eager_token_mask(key, padding, mask_prob)))
    assert not keep[~padding].any()  # a padded position is never visible
    if case == "row_of_one":
        # its one token is masked either way: nothing before it to keep
        assert padding[1].sum() == 1 and not keep[1].any()
    elif case == "nothing_masked":
        # every row loses exactly its last real token
        expected = padding.copy()
        expected[:, -1] = False
        np.testing.assert_array_equal(keep, expected)
    else:
        # every row of two or more keeps exactly the token before its last
        longer = padding.sum(axis=1) > 1
        assert longer.any()
        expected = np.zeros_like(padding)
        expected[longer, -2] = True
        np.testing.assert_array_equal(keep, expected)


def test_token_mask_compiles_once_for_one_shape(monkeypatch):
    draws = []
    uniform = jax.random.uniform
    monkeypatch.setattr(jax.random, "uniform", lambda *a, **k: draws.append(a) or uniform(*a, **k))
    transform = TokenMaskTransform("item_id_mask", mask_prob=0.2)
    for seed in range(4):
        transform({"item_id_mask": raw_batch(seed)["item_id_mask"]}, jax.random.PRNGKey(seed))
    assert len(draws) == 1  # python ran once: the trace
    transform({"item_id_mask": raw_batch()["item_id_mask"][:, 1:]}, jax.random.PRNGKey(0))
    assert len(draws) == 2  # another shape, another program


def test_token_mask_refuses_a_source_that_is_no_mask():
    with pytest.raises(ValueError, match="boolean"):
        TokenMaskTransform("item_id")(raw_batch(), jax.random.PRNGKey(0))


@pytest.mark.parametrize("weighted", [False, True])
def test_uniform_negatives_are_the_eager_draw(weighted):
    distribution = np.arange(1, NUM_ITEMS + 1, dtype=np.float32) if weighted else None
    transform = UniformNegativeSamplingTransform(NUM_ITEMS, 7, sample_distribution=distribution)
    key = jax.random.PRNGKey(11)
    probs = None if distribution is None else jnp.asarray(distribution) / jnp.sum(distribution)
    expected = jax.random.choice(key, NUM_ITEMS, shape=(7,), replace=False, p=probs)
    out = transform(raw_batch(), key)
    np.testing.assert_array_equal(np.asarray(out["negative_labels"]), np.asarray(expected))
    assert type(out["item_id"]) is np.ndarray


def test_multi_class_negatives_are_the_eager_draw():
    classes = np.arange(NUM_ITEMS) % 3
    transform = MultiClassNegativeSamplingTransform(classes, 5)
    raw, key = raw_batch(), jax.random.PRNGKey(2)
    rows = jnp.asarray(classes)[jnp.clip(raw["item_id"][:, -1], 0, NUM_ITEMS - 1)]
    draws = jax.random.randint(key, (BATCH, 5), 0, jnp.iinfo(jnp.int32).max)
    indices = draws % transform._class_sizes[rows][:, None]
    expected = jnp.take_along_axis(transform._class_items[rows], indices, axis=1)
    negatives = np.asarray(transform(raw, key)["negative_labels"])
    np.testing.assert_array_equal(negatives, np.asarray(expected))
    assert (classes[negatives] == classes[raw["item_id"][:, -1]][:, None]).all()


# --- Compose ---------------------------------------------------------------- #


@pytest.fixture(scope="module")
def schema():
    return TensorSchema(
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=NUM_ITEMS,
                          embedding_dim=8)
    )


def pipeline_batch(seed=0):
    raw = raw_batch(seed)
    return {key: raw[key] for key in ("item_id", "item_id_mask", "valid")}


def eager_sasrec_train(raw):
    """The default SASRec train pipeline as it was: eager ``jnp.expand_dims``."""
    return {
        "feature_tensors": {"item_id": raw["item_id"][:, :-1]},
        "padding_mask": raw["item_id_mask"][:, :-1],
        "positive_labels": jnp.expand_dims(raw["item_id"][:, 1:], -1),
        "target_padding_mask": jnp.expand_dims(raw["item_id_mask"][:, 1:], -1),
        "valid": raw["valid"],
    }


def eager_bert4rec_train(raw, rng, mask_prob):
    """The default BERT4Rec train pipeline as it was: ``Compose`` split the key
    once for its one stochastic transform, eagerly, then ~25 eager programs."""
    _, sub = jax.random.split(rng)
    padding = raw["item_id_mask"]
    keep = eager_token_mask(sub, padding, mask_prob)
    target = jnp.logical_and(padding, keep == False)  # noqa: E712 - the transform's own test
    return {
        "feature_tensors": {"item_id": raw["item_id"]},
        "padding_mask": padding,
        "token_mask": keep,
        "positive_labels": jnp.expand_dims(raw["item_id"], -1),
        "target_padding_mask": jnp.expand_dims(target, -1),
        "valid": raw["valid"],
    }


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_default_sasrec_pipeline_returns_what_it_returned_on_the_host(schema, seed):
    compose = Compose(make_default_sasrec_transforms(schema)["train"])
    raw = pipeline_batch(seed)
    claim_chunk(-1)
    out = compose(raw)
    assert claim_chunk(-2)["transform_device_programs"] == 0
    assert_same_tree(out, eager_sasrec_train(raw))
    assert all(type(leaf) is np.ndarray for leaf in jax.tree.leaves(out))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_default_bert4rec_pipeline_returns_what_it_returned_from_one_program(schema, seed):
    compose = Compose(make_default_bert4rec_transforms(schema, mask_prob=0.3)["train"])
    raw, key = pipeline_batch(seed), jax.random.PRNGKey(77 + seed)
    claim_chunk(-1)
    out = compose(raw, key)
    totals = claim_chunk(-2)
    assert totals["transform_device_programs"] == 1
    assert sum(totals["transform_by_name"].values()) == pytest.approx(totals["transform"])
    assert_same_tree(out, eager_bert4rec_train(raw, key, 0.3))
    # only what the random bits made is on the device
    device = {name for name, leaf in out.items() if isinstance(leaf, jax.Array)}
    assert device == {"token_mask", "target_padding_mask"}
    for name in ("padding_mask", "valid", "positive_labels"):
        assert type(out[name]) is np.ndarray, name
    # what the run did not touch is the very array that went in
    assert out["padding_mask"] is raw["item_id_mask"] and out["valid"] is raw["valid"]
    assert out["feature_tensors"]["item_id"] is raw["item_id"]


class CountingCopy(CopyTransform):
    """Counts how often python runs it (inside the compiled run: once a trace)."""

    def __init__(self, mapping):
        super().__init__(mapping)
        self.calls = 0

    def __call__(self, batch, rng=None):
        self.calls += 1
        return super().__call__(batch)


def test_compiled_run_traces_once_a_shape_and_splits_the_key_per_stochastic_transform():
    counted = CountingCopy({"negative_labels": "pool"})
    compose = Compose([
        UnsqueezeTransform("item_id", -1),
        UniformNegativeSamplingTransform(NUM_ITEMS, 4),
        counted,
        TokenMaskTransform("item_id_mask", mask_prob=0.5),
    ])
    assert compose.needs_rng
    for seed in range(3):
        raw, key = pipeline_batch(seed), jax.random.PRNGKey(seed)
        out = compose(raw, key)
        key, first = jax.random.split(key)
        _, second = jax.random.split(key)
        expected = jax.random.choice(first, NUM_ITEMS, shape=(4,), replace=False)
        np.testing.assert_array_equal(np.asarray(out["negative_labels"]), np.asarray(expected))
        np.testing.assert_array_equal(np.asarray(out["pool"]), np.asarray(expected))
        np.testing.assert_array_equal(
            np.asarray(out["token_mask"]),
            np.asarray(eager_token_mask(second, raw["item_id_mask"], 0.5)),
        )
        assert type(out["item_id"]) is np.ndarray and out["item_id"].shape == (BATCH, SEQ_LEN, 1)
    assert counted.calls == 1
    compose({k: v[:4] for k, v in pipeline_batch().items()}, jax.random.PRNGKey(0))
    assert counted.calls == 2


def test_compose_under_a_callers_jit_gives_the_same_batch(schema):
    compose = Compose(make_default_bert4rec_transforms(schema, mask_prob=0.3)["train"])
    raw, key = pipeline_batch(), jax.random.PRNGKey(4)
    assert_same_tree(jax.jit(compose)(raw, key), compose(raw, key))


def test_compose_names_the_transform_that_lacks_its_key(schema):
    compose = Compose(make_default_bert4rec_transforms(schema)["train"])
    with pytest.raises(ValueError, match="TokenMaskTransform needs an rng key"):
        compose(pipeline_batch())
    assert not Compose(make_default_sasrec_transforms(schema)["train"]).needs_rng
