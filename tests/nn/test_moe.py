"""The expert layer's two row movements (``nn.moe.dispatch_rows``,
``combine_rows``): their hand-written transposes against what ``jax.grad`` makes
of the plain gathers (the parent's formulation), the mask of the rows no
assignment owns, the float32 sums over a token's ``k`` rows, and the lowered
program of the whole layer (no scatter of a model-wide row). Shapes of a few dozen
rows; only the last test runs the grouped-product kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from replay_tpu.nn import moe
from replay_tpu.nn.moe import SparseExperts, combine_rows, dispatch_rows

TOKENS, HELD, DIM = 12, 3, 20  # DIM differs from every other extent: the scatter tests look for it
ROUTING = ("every_assignment_held", "none_held", "all_on_one_expert", "token_mask")


def plain_dispatch(tokens, order, inverse, here):
    """The parent's formulation: the transpose is what JAX makes of the gather."""
    live = jnp.arange(order.shape[0]) < jnp.sum(here)
    return jnp.where(live[:, None], tokens[order // here.shape[1]], jnp.zeros((), tokens.dtype))


def plain_combine(mixed, share, order, inverse):
    per_choice = mixed[inverse].reshape(*share.shape, mixed.shape[-1])
    out = jnp.sum(per_choice.astype(jnp.float32) * share[..., None], axis=1)
    return out.astype(mixed.dtype)


def plan(routing, k, seed=0):
    """(order, inverse, here) as ``SparseExperts`` makes them, for a selection
    ``local`` [T, k] of local expert ids drawn to fit the case."""
    rng = np.random.default_rng(seed)
    local = rng.integers(0, HELD, (TOKENS, k))
    token_mask = np.ones(TOKENS, bool)
    if routing == "none_held":
        local = local + HELD
    elif routing == "all_on_one_expert":
        local = np.full((TOKENS, k), 1)
    elif routing == "token_mask":
        local = rng.integers(-1, HELD + 2, (TOKENS, k))  # some absent on either side
        token_mask = rng.random(TOKENS) < 0.6
    here = (local >= 0) & (local < HELD) & token_mask[:, None]
    key = np.where(here, local, HELD).reshape(-1)
    order = np.argsort(key, kind="stable").astype(np.int32)
    inverse = np.zeros_like(order)
    inverse[order] = np.arange(order.size, dtype=np.int32)
    return jnp.asarray(order), jnp.asarray(inverse), jnp.asarray(here)


def normal(seed, shape, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape), dtype)


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("routing", ROUTING)
def test_dispatch_gradient_equals_the_plain_gathers(routing, k):
    order, inverse, here = plan(routing, k)
    tokens, weight = normal(1, (TOKENS, DIM)), normal(2, (TOKENS * k, DIM))
    grads = [
        jax.grad(lambda t, f=f: jnp.sum(f(t, order, inverse, here) * weight))(tokens)
        for f in (dispatch_rows, plain_dispatch)
    ]
    np.testing.assert_array_equal(
        dispatch_rows(tokens, order, inverse, here), plain_dispatch(tokens, order, inverse, here)
    )
    np.testing.assert_allclose(grads[0], grads[1], rtol=1e-6, atol=1e-6)
    assert bool(jnp.any(grads[0] != 0)) == bool(jnp.any(here))


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("routing", ROUTING)
def test_combine_gradients_equal_the_plain_gathers(routing, k):
    order, inverse, here = plan(routing, k)
    mixed, weight = normal(3, (TOKENS * k, DIM)), normal(4, (TOKENS, DIM))
    share = jnp.where(here, jnp.abs(normal(5, (TOKENS, k))), 0.0)
    ours, theirs = (
        jax.grad(lambda m, s, f=f: jnp.sum(f(m, s, order, inverse) * weight), argnums=(0, 1))(
            mixed, share
        )
        for f in (combine_rows, plain_combine)
    )
    np.testing.assert_allclose(
        combine_rows(mixed, share, order, inverse), plain_combine(mixed, share, order, inverse),
        rtol=1e-6, atol=1e-6,
    )
    np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-6, atol=1e-6)  # to the buffer
    np.testing.assert_allclose(ours[1], theirs[1], rtol=1e-5, atol=1e-5)  # to the router
    live = int(jnp.sum(here))
    assert not np.asarray(ours[0])[live:].any()  # nothing weighs on a row no assignment owns


@pytest.mark.parametrize("k", [1, 4, 8])
def test_nan_in_the_dead_rows_cotangent_reaches_no_token(k):
    order, inverse, here = plan("token_mask", k)
    live = int(jnp.sum(here))
    assert 0 < live < TOKENS * k
    tokens, cotangent = normal(1, (TOKENS, DIM)), normal(5, (TOKENS * k, DIM))
    pull = jax.vjp(lambda t: dispatch_rows(t, order, inverse, here), tokens)[1]
    (clean,) = pull(cotangent.at[live:].set(0.0))
    (planted,) = pull(cotangent.at[live:].set(jnp.nan))
    assert np.isfinite(np.asarray(planted)).all()
    np.testing.assert_array_equal(planted, clean)


@pytest.mark.parametrize("movement", ["dispatch_transpose", "combine"])
def test_a_tokens_k_rows_are_summed_in_float32(movement):
    k = 8
    order, inverse, here = plan("every_assignment_held", k)
    rows = normal(6, (TOKENS * k, DIM), jnp.bfloat16)
    if movement == "combine":
        got = combine_rows(rows, jnp.ones((TOKENS, k), jnp.float32), order, inverse)
    else:
        (got,) = jax.vjp(
            lambda t: dispatch_rows(t, order, inverse, here), jnp.zeros((TOKENS, DIM), jnp.bfloat16)
        )[1](rows)
    own = np.asarray(rows.astype(jnp.float32))[np.asarray(inverse)].reshape(TOKENS, k, DIM)
    total = np.zeros((TOKENS, DIM), np.float32)
    for j in range(k):
        total += own[:, j]
    np.testing.assert_array_equal(got, jnp.asarray(total).astype(jnp.bfloat16))
    assert got.dtype == jnp.bfloat16
    in_bfloat16 = jnp.zeros((TOKENS, DIM), jnp.bfloat16)
    for j in range(k):
        in_bfloat16 += jnp.asarray(own[:, j], jnp.bfloat16)
    assert (np.asarray(got) != np.asarray(in_bfloat16)).any()  # the coarser sum differs here


def wide_scatters(jaxpr, width):
    """The scatter equations of ``jaxpr`` (nested programs included) whose result's
    last axis is ``width``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            found += [
                f"{eqn.primitive.name} -> {v.aval.str_short()}"
                for v in eqn.outvars if v.aval.shape[-1:] == (width,)
            ]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += wide_scatters(sub, width)
    return found


def layer(router, dtype=jnp.float32):
    return SparseExperts(num_experts=8, experts_held=HELD, expert_offset=2, top_k=2,
                         hidden_dim=8, router=router, dtype=dtype)


def layer_inputs(router, dtype=jnp.float32):
    x = normal(7, (2, TOKENS // 2, DIM), dtype)
    mask = jnp.asarray(np.random.default_rng(8).random((2, TOKENS // 2)) < 0.8)
    shapes = jax.eval_shape(layer(router, dtype).init, jax.random.PRNGKey(0), x, mask)["params"]
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    params = tree.unflatten(
        [0.3 * normal(10 + i, leaf.shape, leaf.dtype) for i, leaf in enumerate(leaves)]
    )
    return params, x, mask


def layer_loss(router, dtype=jnp.float32):
    def loss(params, x, mask):
        out, _ = layer(router, dtype).apply({"params": params}, x, mask, mutable=["counters"])
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return loss


@pytest.mark.parametrize("router", moe.ROUTERS)
def test_the_layers_gradient_holds_no_scatter_of_a_model_wide_row(router):
    params, x, mask = layer_inputs(router, jnp.bfloat16)
    program = jax.make_jaxpr(jax.grad(layer_loss(router, jnp.bfloat16), argnums=(0, 1)))(
        params, x, mask
    )
    assert wide_scatters(program.jaxpr, DIM) == []
    assert "gather" in str(program)  # the rows do move


def test_the_plain_gathers_would_scatter_add_model_wide_rows():
    order, inverse, here = plan("token_mask", 4)
    share = jnp.where(here, 0.5, 0.0)
    cases = (
        (lambda t: plain_dispatch(t, order, inverse, here), (TOKENS, DIM)),
        (lambda m: plain_combine(m, share, order, inverse), (TOKENS * 4, DIM)),
    )
    for f, shape in cases:
        program = jax.make_jaxpr(jax.grad(lambda x, f=f: jnp.sum(f(x).astype(jnp.float32) ** 2)))(
            jnp.zeros(shape, jnp.bfloat16)
        )
        assert len(wide_scatters(program.jaxpr, DIM)) == 1


@pytest.mark.parametrize("router", moe.ROUTERS)
def test_the_layers_gradient_equals_the_parent_formulations(router, monkeypatch):
    params, x, mask = layer_inputs(router)
    grad = lambda: jax.jit(jax.grad(layer_loss(router), argnums=(0, 1)))(params, x, mask)  # noqa: E731
    ours = grad()
    monkeypatch.setattr(moe, "dispatch_rows", plain_dispatch)
    monkeypatch.setattr(moe, "combine_rows", plain_combine)
    theirs = grad()
    assert float(jnp.abs(ours[1]).max()) > 0
    for got, expected in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)
