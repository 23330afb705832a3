"""The expert layer's two row movements (``nn.moe.dispatch_rows``,
``combine_rows``): their hand-written transposes against what ``jax.grad`` makes
of the plain gathers (the parent's formulation), at the whole buffer and at a
rung of the capacity ladder, the select that keeps the rows no assignment owns
from any token, the float32 sums over a token's ``k`` rows, and the lowered
program of the whole layer (no scatter of a model-wide row). Then the ladder
(``nn.moe.routed_experts``): every rung gives the top rung's output and
gradients, whatever the dead rows hold, and the layer counts the rung it took.
Shapes of a few dozen rows; the grouped-product kernel runs, interpreted, only
in the tests of the whole layer and of the ladder."""

from functools import partial

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


def plain_combine(mixed, share, order, inverse, here):
    """The parent's formulation on a buffer of all ``T * k`` rows (a rung's buffer
    is padded with zeros): a product with the weight, 0 where not held."""
    mixed = jnp.pad(mixed, ((0, inverse.shape[0] - mixed.shape[0]), (0, 0)))
    per_choice = mixed[inverse].reshape(*share.shape, mixed.shape[-1])
    out = jnp.sum(per_choice.astype(jnp.float32) * share[..., None], axis=1)
    return out.astype(mixed.dtype)


def plain_experts(ladder, rung, tokens, gate, value, out, share, order, inverse, here, group_sizes):
    """The parent's layer: all ``T * k`` rows, the dead ones zeroed after every
    product, every transpose JAX's own."""
    live = jnp.arange(order.shape[0])[:, None] < jnp.sum(group_sizes)
    product = lambda lhs, rhs: jnp.where(live, moe.grouped_matmul(lhs, rhs, group_sizes), 0)  # noqa: E731
    rows = plain_dispatch(tokens, order, inverse, here)
    hidden = (jax.nn.silu(product(rows, gate)) * product(rows, value)).astype(out.dtype)
    return plain_combine(product(hidden, out), share, order, inverse, here)


def capacities(here):
    """The buffer lengths a test reads at: the smallest multiple of 8 that holds
    the live rows (a rung), and all ``T * k`` rows."""
    live, top = int(jnp.sum(here)), here.size
    return sorted({min(top, max(8, -(-live // 8) * 8)), top})


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


@partial(jax.jit, static_argnums=0)
def dispatched(f, tokens, order, inverse, here, weight):
    """``f``'s rows and the gradient of ``sum(f(tokens) * weight)`` to the tokens,
    as one program."""
    rows, pull = jax.vjp(lambda t: f(t, order, inverse, here), tokens)
    return rows, pull(weight)[0]


@partial(jax.jit, static_argnums=0)
def combined(f, mixed, share, order, inverse, here, weight):
    """``f``'s output and the gradients of ``sum(f(mixed, share) * weight)`` to
    the buffer and to the router's share, as one program."""
    out, pull = jax.vjp(lambda m, s: f(m, s, order, inverse, here), mixed, share)
    return out, pull(weight)


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("routing", ROUTING)
def test_dispatch_gradient_equals_the_plain_gathers(routing, k):
    order, inverse, here = plan(routing, k)
    live = int(jnp.sum(here))
    tokens = normal(1, (TOKENS, DIM))
    for capacity in capacities(here):
        head = order[:capacity]
        weight = normal(2, (capacity, DIM))
        (rows, grad), (plain_rows, plain_grad) = (
            dispatched(f, tokens, head, inverse, here, weight) for f in (dispatch_rows, plain_dispatch)
        )
        np.testing.assert_array_equal(rows[:live], plain_rows[:live])  # nothing reads the others
        np.testing.assert_allclose(grad, plain_grad, rtol=1e-6, atol=1e-6)
        assert bool(jnp.any(grad != 0)) == bool(jnp.any(here))


@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("routing", ROUTING)
def test_combine_gradients_equal_the_plain_gathers(routing, k):
    order, inverse, here = plan(routing, k)
    live = int(jnp.sum(here))
    weight = normal(4, (TOKENS, DIM))
    share = jnp.where(here, jnp.abs(normal(5, (TOKENS, k))), 0.0)
    for capacity in capacities(here):
        head = order[:capacity]
        # the parent's buffer: zeros past the live rows (its router gradient reads them)
        mixed = normal(3, (capacity, DIM)) * (jnp.arange(capacity) < live)[:, None]
        (out, ours), (plain_out, theirs) = (
            combined(f, mixed, share, head, inverse, here, weight) for f in (combine_rows, plain_combine)
        )
        np.testing.assert_allclose(out, plain_out, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(ours[0], theirs[0], rtol=1e-6, atol=1e-6)  # to the buffer
        np.testing.assert_allclose(ours[1], theirs[1], rtol=1e-5, atol=1e-5)  # to the router
        assert not np.asarray(ours[0])[live:].any()  # nothing weighs on a row no assignment owns


@pytest.mark.parametrize("k", [1, 4, 8])
def test_nan_in_the_dead_rows_cotangent_reaches_no_token(k):
    order, inverse, here = plan("token_mask", k)
    live = int(jnp.sum(here))
    assert 0 < live < TOKENS * k
    tokens = normal(1, (TOKENS, DIM))
    for capacity in capacities(here):  # the rung that holds them and the whole buffer
        cotangent = normal(5, (capacity, DIM))
        clean, planted = (
            dispatched(dispatch_rows, tokens, order[:capacity], inverse, here, cotangent.at[live:].set(fill))[1]
            for fill in (0.0, jnp.nan)
        )
        assert np.isfinite(np.asarray(planted)).all()
        np.testing.assert_array_equal(planted, clean)


@pytest.mark.parametrize("movement", ["dispatch_transpose", "combine"])
def test_a_tokens_k_rows_are_summed_in_float32(movement):
    k = 8
    order, inverse, here = plan("every_assignment_held", k)
    rows = normal(6, (TOKENS * k, DIM), jnp.bfloat16)
    if movement == "combine":
        got = combine_rows(rows, jnp.ones((TOKENS, k), jnp.float32), order, inverse, here)
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
def test_the_layers_gradient_holds_no_scatter_of_a_model_wide_row(router, monkeypatch):
    monkeypatch.setattr(moe, "GMM_TILING", (8, 1024, 1024))  # three rungs of 24 rows
    params, x, mask = layer_inputs(router, jnp.bfloat16)
    program = jax.make_jaxpr(jax.grad(layer_loss(router, jnp.bfloat16), argnums=(0, 1)))(
        params, x, mask
    )
    assert wide_scatters(program.jaxpr, DIM) == []  # in every branch of every switch
    assert "gather" in str(program)  # the rows do move
    assert "branches=(" in str(program) and moe.capacity_ladder(TOKENS * 2) == (8, 16, 24)


def test_the_plain_gathers_would_scatter_add_model_wide_rows():
    order, inverse, here = plan("token_mask", 4)
    share = jnp.where(here, 0.5, 0.0)
    cases = (
        (lambda t: plain_dispatch(t, order, inverse, here), (TOKENS, DIM)),
        (lambda m: plain_combine(m, share, order, inverse, here), (TOKENS * 4, DIM)),
    )
    for f, shape in cases:
        program = jax.make_jaxpr(jax.grad(lambda x, f=f: jnp.sum(f(x).astype(jnp.float32) ** 2)))(
            jnp.zeros(shape, jnp.bfloat16)
        )
        assert len(wide_scatters(program.jaxpr, DIM)) == 1


@pytest.mark.parametrize("router", moe.ROUTERS)
def test_the_layers_gradient_equals_the_parent_formulations(router, monkeypatch):
    monkeypatch.setattr(moe, "GMM_TILING", (8, 1024, 1024))  # rungs of 8, 16, 24 rows
    params, x, mask = layer_inputs(router)
    grad = lambda: jax.jit(jax.grad(layer_loss(router), argnums=(0, 1)))(params, x, mask)  # noqa: E731
    ours = grad()
    monkeypatch.setattr(moe, "routed_experts", plain_experts)
    theirs = grad()
    assert float(jnp.abs(ours[1]).max()) > 0
    for got, expected in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


# the ladder at 12 tokens x 4 choices: rungs of 8, 16, 24 and all 48 rows
LADDER_OF_48 = (8, 16, 24, 48)


def assignments(live, k=4, seed=0):
    """(share, order, inverse, here, group_sizes) as ``SparseExperts`` makes them,
    with exactly ``live`` of the ``TOKENS * k`` assignments held here."""
    rng = np.random.default_rng(seed)
    held = np.zeros(TOKENS * k, bool)
    held[rng.permutation(TOKENS * k)[:live]] = True
    local = np.where(held, rng.integers(0, HELD, TOKENS * k), HELD + rng.integers(0, 3, TOKENS * k))
    key = np.where(held, local, HELD)
    order = np.argsort(key, kind="stable").astype(np.int32)
    inverse = np.zeros_like(order)
    inverse[order] = np.arange(order.size, dtype=np.int32)
    sizes = np.array([(key == e).sum() for e in range(HELD)], np.int32)
    here = held.reshape(TOKENS, k)
    share = np.where(here, np.abs(rng.standard_normal((TOKENS, k))), 0.0).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (share, order, inverse, here, sizes))


def ladder_inputs(k=4):
    tokens = normal(20, (TOKENS, DIM))
    gate, value = normal(21, (HELD, DIM, 8)) * 0.3, normal(22, (HELD, DIM, 8)) * 0.3
    return tokens, gate, value, normal(23, (HELD, 8, DIM)) * 0.3


@jax.jit
def value_and_grads(ladder_rung, tokens, gate, value, out, share, order, inverse, here, sizes):
    """The output and the gradients to tokens, weights and ``share`` of
    ``routed_experts`` on the ladder of 48 rows (rung index given) and on its top
    rung alone, for one cotangent."""
    cotangent = normal(24, (TOKENS, DIM))
    results = []
    for ladder, rung in ((LADDER_OF_48, ladder_rung), (LADDER_OF_48[-1:], jnp.int32(0))):
        f = lambda *w, ladder=ladder, rung=rung: moe.routed_experts(  # noqa: E731
            ladder, rung, *w, order, inverse, here, sizes
        )
        result, pull = jax.vjp(f, tokens, gate, value, out, share)
        results.append((result, pull(cotangent)))
    return results


@jax.jit
def plain_value_and_grads(tokens, gate, value, out, share, order, inverse, here, sizes):
    """The same for the parent's layer (``plain_experts``)."""
    result, pull = jax.vjp(
        lambda *w: plain_experts(None, None, *w, order, inverse, here, sizes),
        tokens, gate, value, out, share,
    )
    return result, pull(normal(24, (TOKENS, DIM)))


@pytest.mark.parametrize("live", [0, 8, 9, 16, 17, 24, 25, 48])
def test_every_rung_gives_the_top_rungs_output_and_gradients(live):
    """Exactly a rung's rows, one past it, none held and all held: the rung the
    layer would take gives the top rung's output and gradients, bit for bit (the
    same kernels on the same rows), and both the parent's formulation."""
    share, order, inverse, here, sizes = assignments(live)
    tokens, gate, value, out = ladder_inputs()
    rung = moe.rung_of(LADDER_OF_48, jnp.sum(sizes))
    assert LADDER_OF_48[int(rung)] == min(c for c in LADDER_OF_48 if c >= live)
    (got, got_grads), (top, top_grads) = value_and_grads(
        rung, tokens, gate, value, out, share, order, inverse, here, sizes
    )
    np.testing.assert_array_equal(got, top)
    for ours, expected in zip(got_grads, top_grads):
        np.testing.assert_array_equal(ours, expected)
    plain, plain_grads = plain_value_and_grads(tokens, gate, value, out, share, order, inverse, here, sizes)
    np.testing.assert_allclose(got, plain, rtol=1e-5, atol=1e-6)
    for ours, expected in zip(got_grads, plain_grads):
        np.testing.assert_allclose(ours, expected, rtol=1e-5, atol=1e-6)
    assert bool(jnp.any(got_grads[1] != 0)) == (live > 0)


@pytest.mark.parametrize("live", [9, 24, 40])
def test_nan_in_every_dead_row_of_the_saved_buffers_reaches_no_gradient(live):
    """The rows of the saved buffers past the live ones, those the rung wrote and
    those it left unwritten, may hold anything: NaN planted there changes no
    gradient."""
    share, order, inverse, here, sizes = assignments(live, seed=1)
    tokens, gate, value, out = ladder_inputs()
    rung = moe.rung_of(LADDER_OF_48, jnp.sum(sizes))
    _, saved = jax.jit(moe._routed_experts_fwd, static_argnums=0)(
        LADDER_OF_48, rung, tokens, gate, value, out, share, order, inverse, here, sizes
    )
    planted = jax.tree.map(
        lambda leaf: leaf.at[live:].set(jnp.nan)
        if leaf.shape[:1] == (TOKENS * 4,) and leaf.dtype == jnp.float32 else leaf,
        saved,
    )
    # the buffer, the two products, the hidden rows
    assert sum(not np.isfinite(np.asarray(leaf)).all() for leaf in jax.tree.leaves(planted)) == 4
    cotangent = normal(24, (TOKENS, DIM))
    backward = jax.jit(moe._routed_experts_bwd, static_argnums=0)
    clean = backward(LADDER_OF_48, saved, cotangent)
    dirty = backward(LADDER_OF_48, planted, cotangent)
    for a, b in zip(clean, dirty):
        if a is not None:
            assert np.isfinite(np.asarray(b)).all()
            np.testing.assert_array_equal(a, b)


COUNTING = SparseExperts(num_experts=8, experts_held=4, expert_offset=0, top_k=4, hidden_dim=8)
# one program for every case below, traced at the first, where 8-row tiles are patched in
init_counting = jax.jit(COUNTING.init)
apply_counting = jax.jit(partial(COUNTING.apply, mutable=["counters"]))


@pytest.mark.parametrize("unmasked", [0, 2, 3, 6, 7, 12])
def test_the_layer_counts_the_smallest_rung_that_holds_its_rows(unmasked, monkeypatch):
    """Every choice forced onto a held expert, ``unmasked`` of 12 tokens real: the
    live rows are 4 a token, the rung taken the smallest of 8, 16, 24, 48 rows
    that holds them, and no held assignment is dropped."""
    monkeypatch.setattr(moe, "GMM_TILING", (8, 1024, 1024))
    x = normal(7, (1, TOKENS, DIM))
    mask = jnp.arange(TOKENS)[None] < unmasked
    params = init_counting(jax.random.PRNGKey(0), x, mask)["params"]
    params = {**params, "expert_bias": jnp.where(jnp.arange(8) < 4, 100.0, 0.0)}
    _, counted = apply_counting({"params": params}, x, mask)
    counted = counted["counters"]
    live = 4 * unmasked
    assert int(counted["expert_load"].sum()) == live
    assert moe.capacity_ladder(TOKENS * 4) == LADDER_OF_48
    assert int(counted["expert_rows_capacity"]) == min(c for c in LADDER_OF_48 if c >= live)
    assert int(counted["dropped_assignments"]) == 0
