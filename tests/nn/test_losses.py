from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replay_tpu.nn.loss import (
    BCE,
    CE,
    BCESampled,
    CESampled,
    CESampledWeighted,
    CEWeighted,
    LogInCE,
    LogInCESampled,
    LogOutCE,
    LogOutCEWeighted,
    SCEParams,
    ScalableCrossEntropyLoss,
)

B, L, E, I = 2, 4, 8, 12
RNG = np.random.default_rng(0)
EMB = jnp.asarray(RNG.normal(size=(B, L, E)), dtype=jnp.float32)
ITEMS = jnp.asarray(RNG.normal(size=(I, E)), dtype=jnp.float32)
POS = jnp.asarray(RNG.integers(0, I, size=(B, L, 1)))
NEG = jnp.asarray(RNG.integers(0, I, size=(5,)))
PAD = jnp.asarray([[True] * L, [False, False, True, True]])
TGT = PAD[..., None]


def full_logits_callback(embeddings, ids=None):
    if ids is None:
        return embeddings @ ITEMS.T
    if ids.ndim == 1:
        return embeddings @ ITEMS[ids].T
    return jnp.einsum("...e,...ke->...k", embeddings, ITEMS[ids])


def make(loss):
    loss.logits_callback = full_logits_callback
    return loss


def call(loss, pos=POS, neg=NEG, tgt=TGT):
    return loss(EMB, {}, pos, neg, PAD, tgt)


def test_ce_matches_manual():
    loss = make(CE())
    value = call(loss)
    logits = np.asarray(full_logits_callback(EMB))
    log_probs = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    manual = []
    for b in range(B):
        for t in range(L):
            if bool(PAD[b, t]):
                manual.append(-log_probs[b, t, int(POS[b, t, 0])])
    assert float(value) == pytest.approx(float(np.mean(manual)), rel=1e-4)


def test_ce_multipositive_rejected():
    loss = make(CE())
    with pytest.raises(NotImplementedError):
        call(loss, pos=jnp.zeros((B, L, 2), dtype=jnp.int32), tgt=jnp.ones((B, L, 2), dtype=bool))


def test_ce_weighted_changes_value():
    base = call(make(CE()))
    weights = jnp.ones(I).at[int(POS[0, 0, 0])].set(10.0)
    weighted = call(make(CEWeighted(weights)))
    assert float(base) != pytest.approx(float(weighted))


def test_ce_sampled_all_negative_shapes():
    loss = make(CESampled())
    v1 = call(loss, neg=NEG)  # [N]
    v2 = call(loss, neg=jnp.broadcast_to(NEG, (B, 5)))  # [B, N]
    v3 = call(loss, neg=jnp.broadcast_to(NEG, (B, L, 5)))  # [B, L, N]
    assert float(v1) == pytest.approx(float(v2), rel=1e-5)
    assert float(v1) == pytest.approx(float(v3), rel=1e-5)


def test_ce_sampled_ignore_index():
    loss = make(CESampled())
    padded_negs = jnp.concatenate([NEG, jnp.array([-100, -100])])
    v_padded = call(loss, neg=padded_negs)
    v_plain = call(loss, neg=NEG)
    assert float(v_padded) == pytest.approx(float(v_plain), rel=1e-5)


def test_ce_sampled_multipositive():
    pos2 = jnp.asarray(RNG.integers(0, I, size=(B, L, 3)))
    tgt2 = jnp.broadcast_to(PAD[..., None], (B, L, 3))
    value = call(make(CESampled()), pos=pos2, tgt=tgt2)
    assert np.isfinite(float(value))


def test_ce_sampled_weighted():
    weights = jnp.linspace(0.1, 2.0, I)
    value = call(make(CESampledWeighted(weights)))
    assert np.isfinite(float(value))


def test_bce_losses():
    assert np.isfinite(float(call(make(BCE()))))
    assert np.isfinite(float(call(make(BCESampled()))))


def test_login_ce():
    full = call(make(LogInCE(cardinality=I)))
    sampled = call(make(LogInCESampled()))
    assert np.isfinite(float(full)) and np.isfinite(float(sampled))
    # sampled negatives are a subset of the catalog -> lower or equal denominator
    assert float(sampled) <= float(full) + 1e-4


def test_logout_ce():
    value = call(make(LogOutCE(cardinality=I)))
    assert np.isfinite(float(value))
    weighted = call(make(LogOutCEWeighted(cardinality=I, weight=jnp.ones(I))))
    assert float(weighted) == pytest.approx(float(value), rel=1e-5)


def test_logout_ce_single_positive_close_to_ce():
    # with P=1, logout-CE only removes the positive itself from the negatives pool
    ce = float(call(make(CE())))
    lo = float(call(make(LogOutCE(cardinality=I))))
    # removing the positive from the denominator lowers (or at f32 precision, ties) the loss
    assert lo <= ce + 1e-6


def test_sce_loss():
    sce = ScalableCrossEntropyLoss(SCEParams(n_buckets=4, bucket_size_x=4, bucket_size_y=6))
    value = sce(
        EMB,
        POS[..., 0],
        ITEMS,
        PAD,
        rng=jax.random.PRNGKey(0),
    )
    assert np.isfinite(float(value))
    assert float(value) > 0


def test_missing_callback_raises():
    loss = CE()
    with pytest.raises(AttributeError):
        _ = loss.logits_callback


def plain_ce(hidden, table, labels, target_mask, weight=None):
    """The head as it was before it saved (logits, lse): ``log_softmax`` + ``take_along_axis``."""
    logits = jnp.einsum("...e,ie->...i", hidden, table)
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    labels = jnp.clip(labels[..., 0], 0, logits.shape[-1] - 1)
    nll = -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]
    mask = target_mask[..., 0].astype(nll.dtype)
    if weight is not None:
        mask = mask * weight[labels].astype(nll.dtype)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def head_ce(loss):
    """``loss`` as a function of hidden states and table, through its ``logits_callback``."""

    def apply(hidden, table, labels, target_mask):
        loss.logits_callback = lambda h: jnp.einsum("...e,ie->...i", h, table)
        return loss(hidden, {}, labels, None, PAD, target_mask)

    return apply


CLASS_WEIGHTS = jnp.linspace(0.25, 3.0, I)
HEAD_CASES = {
    # the second row's first two positions are padding: fully masked rows of logits
    "padded_rows": (POS, TGT),
    # no valid position at all: the denominator's clamp decides the value
    "all_padding": (POS, jnp.zeros_like(TGT)),
    # labels outside the catalog (the padding id, a negative id) are clipped into it
    "out_of_range": (POS.at[0, 0, 0].set(I + 5).at[1, 3, 0].set(-3), TGT),
}


@pytest.mark.parametrize("case", sorted(HEAD_CASES))
@pytest.mark.parametrize("hidden_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("weight", [None, CLASS_WEIGHTS], ids=["CE", "CEWeighted"])
def test_ce_head_matches_log_softmax_form(weight, hidden_dtype, case):
    """Value and both gradients of the head that saves (logits, lse) against the
    ``log_softmax`` form, bf16 hidden states against the float32 table included."""
    labels, target_mask = HEAD_CASES[case]
    loss = CE() if weight is None else CEWeighted(weight)
    hidden = EMB.astype(hidden_dtype)
    got, got_grads = jax.value_and_grad(head_ce(loss), argnums=(0, 1))(hidden, ITEMS, labels, target_mask)
    want, want_grads = jax.value_and_grad(partial(plain_ce, weight=weight), argnums=(0, 1))(
        hidden, ITEMS, labels, target_mask
    )
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6, atol=1e-7)
    # a bf16 hidden gradient is the float32 one rounded: a last-bit difference before
    # the cast may fall either side of a bf16 step
    rtol = 1e-5 if hidden_dtype == jnp.float32 else 2.0**-7
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(
            np.asarray(g, dtype=np.float32), np.asarray(w, dtype=np.float32), rtol=rtol, atol=1e-6
        )
    if case == "all_padding":
        assert float(got) == 0.0 and not np.any(np.asarray(got_grads[1]))


def logits_sized_residuals(fn, *args):
    """What ``jax.vjp`` of ``fn`` holds for the way back, of the logits' shape."""
    _, pullback = jax.vjp(fn, *args)
    return [
        leaf for leaf in jax.tree_util.tree_leaves(pullback)
        if getattr(leaf, "shape", None) == (B, L, I) and jnp.issubdtype(leaf.dtype, jnp.floating)
    ]


def test_ce_head_saves_the_logits_and_no_second_tensor():
    """The structural witness: for the way back the head holds ONE float array of
    the logits' shape, the logits themselves; the test fails if the second tensor,
    which ``log_softmax``'s own rule keeps, comes back."""
    args = (EMB, ITEMS, POS, TGT)
    logits = np.asarray(full_logits_callback(EMB))
    for loss in (CE(), CEWeighted(CLASS_WEIGHTS)):
        saved = logits_sized_residuals(head_ce(loss), *args)
        assert len(saved) == 1, [leaf.shape for leaf in saved]
        np.testing.assert_array_equal(np.asarray(saved[0]), logits)
    # what the plain form holds there is a tensor made inside ``log_softmax`` (as
    # traced, exp(logits - max)): one scalar a row away from the logits, at their size
    plain = logits_sized_residuals(plain_ce, *args)
    assert plain and not any(np.array_equal(np.asarray(leaf), logits) for leaf in plain)


@pytest.mark.parametrize("weight", [None, CLASS_WEIGHTS], ids=["CE", "CEWeighted"])
def test_ce_head_under_data_model_mesh(weight):
    """The same loss and gradients inside ``jit`` on a data x model mesh with the
    table's rows over ``model`` (``shard_vocab=True``) as on one device."""
    from jax.sharding import NamedSharding

    from replay_tpu.nn import make_mesh
    from replay_tpu.parallel.sharding import ShardingRules

    loss = CE() if weight is None else CEWeighted(weight)
    step = jax.value_and_grad(head_ce(loss), argnums=(0, 1))
    want, want_grads = step(EMB, ITEMS, POS, TGT)

    mesh = make_mesh(jax.devices()[:4], model_parallel=2)
    rules = ShardingRules.default(shard_vocab=True)
    place = lambda x, *names: jax.device_put(x, NamedSharding(mesh, rules.spec(*names)))  # noqa: E731
    hidden, table = place(EMB, "batch", "length", "embed"), place(ITEMS, "vocab", "embed")
    assert "model" in str(table.sharding.spec) and "data" in str(hidden.sharding.spec)
    got, got_grads = jax.jit(step)(
        hidden, table, place(POS, "batch", "length", None), place(TGT, "batch", "length", None)
    )
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-6)
