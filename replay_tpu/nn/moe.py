"""Sparse experts: a routed feed-forward layer that holds a share of the experts.

The layer is told which experts live here (``experts_held`` of ``num_experts``,
starting at ``expert_offset``: one chip's share under expert parallelism). It
routes every token over ALL experts, exactly as the whole layer would, and
computes the part of the result that its own experts give:

    s    = sigmoid(W_g x)                         float32, all num_experts wide
    sel  = top_k(s + b)                           b: the expert bias, a buffer
    w    = s[sel] / (sum s[sel] + 1e-6) * scale   weights from s, not s + b
    out  = sum_{e in sel, e held} w_e * W2_e (silu(W1_e x) * W3_e x)

``router="softmax"`` is the other published form (no bias, no scale):

    p    = softmax(W_g x)                         float32, over all num_experts
    sel  = top_k(p);   w = p[sel] / sum p[sel]

Everything after the selection is one code path. What the absent experts would add is left out (on one chip the layer runs
without its exchange; summing ``out`` over every share gives the whole layer).

Static shapes, no dropped assignment: the ``T * k`` assignments are sorted by
expert (those of absent experts last) and each expert multiplies its own
contiguous group of rows of one buffer (:func:`grouped_matmul`). The held
assignments, ``live = sum(group_sizes)`` of them, are known only on the device
and change from step to step. So the passes over the buffer other than the
products, forward and backward, walk a RUNG of a short ladder of compiled
capacities (:func:`capacity_ladder`: 1/16, 3/16, 1/2 and all of ``T * k``, in
whole row tiles of the product): the device takes the smallest rung that holds
every live row (:func:`rung_of`), and each such pass is one ``lax.switch`` with a
branch a rung that writes the rung's ``C`` rows into the first rows of a buffer
of ``T * k`` rows and leaves the others unwritten. One pass stays whole: the sum
of the buffer's two cotangents, which as the operands of a switch would both be
kept to its end. The products run outside the
switches on those buffers: their kernels visit only the row tiles that hold a
group's rows, so the ladder compiles no kernel twice. The top rung is
``T * k``, so however uneven the routing nothing is dropped. Rows past ``live``
are DEAD: nothing reads them. The kernels leave them as they found them, and each
read of the buffer by a token's own row (``inverse``) is a select on ``here``,
never a product with a zero weight, so whatever a dead row holds reaches no token.

Rows travel by GATHERS in both directions: the sort ``order`` and its inverse
are made once, and each movement has a hand-written transpose
(:func:`dispatch_rows`, :func:`combine_rows`), which is the other movement.
Forward, the buffer reads ``tokens[order[:C] // k]``, and the way back gives
token ``t`` the weighted sum of its own ``k`` buffer rows
``mixed[inverse[t * k + j]]``. Backward, the buffer's cotangent reads
``d_out[order[:C] // k]`` times its weight (a permutation: every row written
once, nothing added), and a token's cotangent is the sum of its own ``k`` rows
of the buffer's cotangent, selected by which assignments are held here. Both
sums over ``k`` are accumulated in float32 and cast once. Left to itself JAX
transposes a gather into a scatter-add, which cannot know that ``inverse`` is a
permutation, serialises over the rows (three times a gather's time on the TPU)
and sums in bfloat16. The whole of dispatch, products and combine is ONE
``jax.custom_vjp`` (:func:`routed_experts`) whose forward and backward switch on
the same rung: its residuals are the buffers of ``T * k`` rows above, as many as
the products' own VJP would keep, whichever rung is taken (JAX's own
linearisation of a switch would keep every rung's residuals at once), and the
backward runs the products' transposes from them without running the products
again.

``expert_load`` (assignments per held expert), ``dropped_assignments`` (held
assignments whose buffer row lies outside their expert's group of rows or past
the rung, so that another expert's kernel, or none, would multiply them: 0
unless the sort, the group sizes, the rung and the way back disagree) and
``expert_rows_capacity`` (the rows of the rung taken) are sown into the
``counters`` collection, which the trainer carries in its step metrics.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

GMM_TILING = (512, 1024, 1024)  # rows, contraction, columns of one kernel tile
LADDER = (1, 3, 8, 16)  # the rungs of the row buffer, in sixteenths of T * k
ROUTERS = ("sigmoid", "softmax")


def capacity_ladder(assignments: int) -> tuple:
    """The buffer lengths compiled for ``assignments`` = ``T * k`` rows: each
    share of ``LADDER`` rounded up to whole row tiles of the grouped product, the
    top one ``assignments`` itself (no assignment can be dropped)."""
    tile = GMM_TILING[0]
    return tuple(sorted({
        min(assignments, -(-assignments * sixteenths // (16 * tile)) * tile) for sixteenths in LADDER
    }))


def rung_of(ladder: tuple, live: jnp.ndarray) -> jnp.ndarray:
    """The index of the smallest rung of ``ladder`` that holds ``live`` rows."""
    return jnp.sum(jnp.asarray(ladder[:-1], jnp.int32) < live, dtype=jnp.int32)


def _tiling(lhs: jnp.ndarray, rhs: jnp.ndarray) -> tuple:
    return tuple(min(t, n) for t, n in zip(GMM_TILING, (lhs.shape[0], lhs.shape[1], rhs.shape[2])))


def grouped_matmul(
    lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """``lhs[rows of group g] @ rhs[g]`` for contiguous groups of rows: ``lhs``
    [M, K], ``rhs`` [G, K, N], ``group_sizes`` [G] int32 summing to at most M.
    The rows past the last group are left as the kernel found them: whatever
    they hold, the caller reads none of them.

    One path: the Pallas grouped product that ships with JAX (``megablox.gmm``),
    compiled on the TPU and interpreted on the CPU, like the repo's other kernels
    (``interpret=None``: ``ops.flash_attention.pallas_interpret`` decides, and any
    other backend raises). The kernel visits only the row tiles that hold a group's
    rows, forward and backward (its own VJP; :func:`grouped_matmul_transpose` is
    the same backward from saved operands), so the products follow the live rows
    and not the buffer's length.
    """
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from replay_tpu.ops.flash_attention import pallas_interpret

    if interpret is None:
        interpret = pallas_interpret()
    return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, _tiling(lhs, rhs), interpret=interpret)


def grouped_matmul_transpose(
    lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray, d_out: jnp.ndarray,
    interpret: Optional[bool] = None,
) -> tuple:
    """(d_lhs, d_rhs) of :func:`grouped_matmul` for its cotangent ``d_out``: the
    two kernels of ``megablox.gmm``'s own VJP, run from the operand the forward
    pass saved. Rows of ``lhs`` and ``d_out`` past the last group weigh nothing in
    ``d_rhs`` (the kernel selects by group); those of ``d_lhs`` are left as the
    kernel found them."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    from replay_tpu.ops.flash_attention import pallas_interpret

    if interpret is None:
        interpret = pallas_interpret()
    tiling = _tiling(lhs, rhs)
    d_lhs = gmm(d_out, rhs, group_sizes, lhs.dtype, tiling, transpose_rhs=True, interpret=interpret)
    # a change of layout, not a pass: the kernel reads the columns it needs
    d_rhs = tgmm(
        lhs.swapaxes(0, 1), d_out, group_sizes, rhs.dtype, tiling,
        num_actual_groups=rhs.shape[0], interpret=interpret,
    )
    return d_lhs, d_rhs


def route(scores: jnp.ndarray, bias: jnp.ndarray, top_k: int, scale: float = 1.0):
    """(selected experts [T, k], their weights [T, k]) from sigmoid scores
    [T, E]: selection by ``scores + bias``, weights from ``scores`` alone,
    normalised over the selected."""
    _, selected = jax.lax.top_k(scores + bias, top_k)
    weights = jnp.take_along_axis(scores, selected, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6) * scale
    return selected, weights


def route_softmax(logits: jnp.ndarray, top_k: int):
    """(selected experts [T, k], their weights [T, k]) from router logits [T, E]:
    a softmax over ALL experts, the ``top_k`` largest, renormalised to sum to 1."""
    weights, selected = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return selected, weights / jnp.sum(weights, axis=-1, keepdims=True)


@jax.custom_vjp
def dispatch_rows(
    tokens: jnp.ndarray, order: jnp.ndarray, inverse: jnp.ndarray, here: jnp.ndarray
) -> jnp.ndarray:
    """The expert buffer [C, d] for ``order`` [C], the first ``C`` rows of the
    sort: row ``r`` holds the token of assignment ``order[r]`` (assignment
    ``t * k + j`` is token ``t``'s ``j``-th choice). The held assignments
    (``here`` [T, k]) sort first; the rows after them are dead. ``inverse`` [T * k]
    is the inverse permutation of the whole sort, used by the transpose:
    ``d_tokens[t] = sum_j d_rows[inverse[t * k + j]] where here[t, j]``, gathers
    and a float32 sum over ``k``. The choice is a select on ``here`` and not the
    cotangent's content, so whatever a dead row's cotangent holds reaches no token."""
    del inverse
    return tokens[order // here.shape[1]]


def _dispatch_rows_fwd(tokens, order, inverse, here):
    return dispatch_rows(tokens, order, inverse, here), (inverse, here)


def _own_rows(rows: jnp.ndarray, inverse: jnp.ndarray, here: jnp.ndarray):
    """``k`` arrays [T, d]: the ``j``-th holds the buffer row of every token's
    ``j``-th choice where that choice is held here, zeros where it is not (a
    select: the row read then, clamped into the buffer, may hold anything). ``k``
    gathers of ``T`` rows and not one of ``T * k`` reshaped to [T, k, d]: on the
    TPU that reshape is a copy of the whole buffer (``k`` lands in a tiled axis)
    and a sum over it a second pass; these fuse with what reads them."""
    row_of = jnp.minimum(inverse.reshape(here.shape), rows.shape[0] - 1)
    return [
        jnp.where(here[:, j, None], rows[row_of[:, j]], jnp.zeros((), rows.dtype))
        for j in range(here.shape[1])
    ]


def _dispatch_rows_bwd(saved, d_rows):
    inverse, here = saved
    d_tokens = jnp.zeros((here.shape[0], d_rows.shape[-1]), jnp.float32)
    for own in _own_rows(d_rows, inverse, here):
        d_tokens += own.astype(jnp.float32)
    return d_tokens.astype(d_rows.dtype), None, None, None


dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def combine_rows(
    mixed: jnp.ndarray, share: jnp.ndarray, order: jnp.ndarray, inverse: jnp.ndarray,
    here: jnp.ndarray,
) -> jnp.ndarray:
    """The way back, [T, d]: ``out[t] = sum_j share[t, j] * mixed[inverse[t * k + j]]``
    over the choices held ``here`` [T, k], with ``share`` [T, k] float32 (0 where
    not held), summed in float32 and cast once; ``mixed`` [C, d] is the buffer of
    ``order`` [C], the first ``C`` rows of the sort. It is the dispatch's
    transpose with weights for a mask, and its own transpose is the dispatch
    again: ``d_mixed[r] = share.flat[order[r]] * d_out[order[r] // k]``, one gather
    of the small [T, d] cotangent, every buffer row written once and nothing
    added; ``d_share[t, j] = <d_out[t], mixed[inverse[t * k + j]]>`` from the rows
    the forward pass gathered."""
    return _combine_rows_fwd(mixed, share, order, inverse, here)[0]


def _combine_rows_fwd(mixed, share, order, inverse, here):
    own = _own_rows(mixed, inverse, here)
    out = jnp.zeros(own[0].shape, jnp.float32)
    for j, rows in enumerate(own):
        out += rows.astype(jnp.float32) * share[:, j, None]
    return out.astype(mixed.dtype), (own, share, order)


def _spread(d_out: jnp.ndarray, share: jnp.ndarray, order: jnp.ndarray) -> jnp.ndarray:
    """The buffer's cotangent [C, d] for ``order`` [C]: ``share.flat[order[r]] *
    d_out[order[r] // k]``, in float32 and cast once."""
    spread = d_out[order // share.shape[1]].astype(jnp.float32)
    return (spread * share.reshape(-1)[order][:, None]).astype(d_out.dtype)


def _share_cotangent(own, d_out: jnp.ndarray) -> jnp.ndarray:
    """[T, k] float32: ``<d_out[t], own[j][t]>``."""
    d_wide = d_out.astype(jnp.float32)
    return jnp.stack([jnp.sum(d_wide * rows.astype(jnp.float32), axis=-1) for rows in own], axis=1)


def _combine_rows_bwd(saved, d_out):
    own, share, order = saved
    return _spread(d_out, share, order), _share_cotangent(own, d_out), None, None, None


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _on_rung(ladder: tuple, rung: jnp.ndarray, pass_, *operands, slot: Optional[int] = None) -> list:
    """The arrays ``pass_(capacity, *operands)`` returns, made at the capacity
    ``ladder[rung]`` (one compiled branch a rung, ``lax.switch``): each of the
    same shape on every rung, or (``slot``) each of ``capacity`` rows, handed back
    as the first rows of a slot of ``slot`` rows whose other rows are left
    unwritten. No pass walks the rows past its rung."""

    def at(capacity):
        def branch(*operands):
            out = []
            for result in pass_(capacity, *operands):
                if slot is not None and result.shape[0] < slot:
                    # a buffer of its own, so that the update writes the rung's rows in place
                    empty = jax.lax.optimization_barrier(
                        jax.lax.empty((slot,) + result.shape[1:], result.dtype)
                    )
                    result = jax.lax.dynamic_update_slice_in_dim(empty, result, 0, axis=0)
                out.append(result)
            return out

        return branch

    return jax.lax.switch(rung, [at(capacity) for capacity in ladder], *operands)


def _swiglu(gated: jnp.ndarray, valued: jnp.ndarray) -> jnp.ndarray:
    return nn.silu(gated) * valued


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def routed_experts(
    ladder: tuple, rung: jnp.ndarray, tokens: jnp.ndarray, gate: jnp.ndarray,
    value: jnp.ndarray, out: jnp.ndarray, share: jnp.ndarray, order: jnp.ndarray,
    inverse: jnp.ndarray, here: jnp.ndarray, group_sizes: jnp.ndarray,
) -> jnp.ndarray:
    """Dispatch, the SwiGLU experts and the way back, [T, d], with the passes over
    the buffer at ``ladder[rung]`` rows (which must hold the ``sum(group_sizes)``
    live ones; see the module docstring): ``tokens`` [T, d]; ``gate``, ``value`` [G, d, h] and ``out``
    [G, h, d] in the compute dtype; ``share``, ``order``, ``inverse``, ``here``,
    ``group_sizes`` as the layer makes them. A ``jax.custom_vjp`` whose forward
    and backward switch on ``rung`` pass by pass; the grouped products run outside
    the switches on buffers of ``T * k`` rows, of which they visit only the live
    row tiles. Integer inputs get no cotangent."""
    return _routed_experts_fwd(
        ladder, rung, tokens, gate, value, out, share, order, inverse, here, group_sizes
    )[0]


def _routed_experts_fwd(ladder, rung, tokens, gate, value, out, share, order, inverse, here, group_sizes):
    top = order.shape[0]
    with jax.named_scope("dispatch"):
        (rows,) = _on_rung(
            ladder, rung, lambda c, t, o, i, h: [dispatch_rows(t, o[:c], i, h)],
            tokens, order, inverse, here, slot=top,
        )
    with jax.named_scope("experts"):
        gated = grouped_matmul(rows, gate, group_sizes)
        valued = grouped_matmul(rows, value, group_sizes)
        (hidden,) = _on_rung(
            ladder, rung, lambda c, g, v: [_swiglu(g[:c], v[:c]).astype(out.dtype)],
            gated, valued, slot=top,
        )
        mixed = grouped_matmul(hidden, out, group_sizes)
    with jax.named_scope("combine"):
        result, (own, _, _) = _combine_rows_fwd(mixed, share, order, inverse, here)
    saved = (rung, rows, gated, valued, hidden, own, gate, value, out, share, order, inverse, here)
    return result, saved + (group_sizes,)


def _routed_experts_bwd(ladder, saved, d_result):
    rung, rows, gated, valued, hidden, own, gate, value, out, share, order, inverse, here, group_sizes = saved
    top = order.shape[0]
    with jax.named_scope("combine"):
        (d_mixed,) = _on_rung(
            ladder, rung, lambda c, d, w, o: [_spread(d, w, o[:c])], d_result, share, order, slot=top
        )
        d_share = _share_cotangent(own, d_result)
    with jax.named_scope("experts"):
        d_hidden, d_out = grouped_matmul_transpose(hidden, out, group_sizes, d_mixed)

        def swiglu_transpose(c, g, v, d_h):
            return jax.vjp(_swiglu, g[:c], v[:c])[1](d_h[:c].astype(g.dtype))

        d_gated, d_valued = _on_rung(
            ladder, rung, swiglu_transpose, gated, valued, d_hidden, slot=top
        )
        d_rows, d_gate = grouped_matmul_transpose(rows, gate, group_sizes, d_gated)
        d_rows_v, d_value = grouped_matmul_transpose(rows, value, group_sizes, d_valued)
    with jax.named_scope("dispatch"):
        # each token's own rows gathered from the rung's rows of the buffer's cotangent:
        # below the top rung an array small enough for the chip's own memory, where a
        # row is read four times as fast as from HBM. The two cotangents are added
        # outside any switch: a switch keeps its operands to its end, and two buffers
        # of T * k rows kept so raised mellum2's step by 0.3 GB
        d_rows = d_rows + d_rows_v
        (d_tokens,) = _on_rung(
            ladder, rung, lambda c, r, i, h: [_dispatch_rows_bwd((i, h), r[:c])[0]],
            d_rows, inverse, here,
        )
    return None, d_tokens, d_gate, d_value, d_out, d_share, None, None, None, None


routed_experts.defvjp(_routed_experts_fwd, _routed_experts_bwd)


def unserved(slot: jnp.ndarray, held_here: jnp.ndarray, row: jnp.ndarray, group_sizes: jnp.ndarray):
    """How many held assignments are fetched from a buffer row OUTSIDE the rows
    their own expert multiplies: ``slot`` [A] the local expert of each
    assignment, ``held_here`` [A] whether it is held here, ``row`` [A] the buffer
    row the way back reads for it, ``group_sizes`` [G] the rows each expert's
    kernel is given, in order from row 0. Read from the way back and not from
    the sizes the groups were made from, so it is 0 only if the sort, the sizes
    and the inverse permutation agree."""
    ends = jnp.cumsum(group_sizes)
    slot = jnp.clip(slot, 0, group_sizes.shape[0] - 1)
    served = (row >= (ends - group_sizes)[slot]) & (row < ends[slot])
    return jnp.sum(held_here & ~served, dtype=jnp.int32)


class SparseExperts(nn.Module):
    """Routed SwiGLU experts, of which ``experts_held`` live here (see the module
    docstring); ``router``: ``"sigmoid"`` (scores + selection bias) or
    ``"softmax"`` (top-k of the softmax, renormalised; no bias parameter).
    ``token_mask`` [...] bool leaves tokens (padding) out of the dispatch: they
    take no row and count in no expert's load."""

    num_experts: int
    experts_held: int
    expert_offset: int
    top_k: int
    hidden_dim: int
    scale: float = 1.0
    dtype: Any = jnp.float32
    router: str = "sigmoid"

    @nn.compact
    def __call__(self, x: jnp.ndarray, token_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        if not 0 <= self.expert_offset <= self.num_experts - self.experts_held:
            msg = (
                f"experts {self.expert_offset}..{self.expert_offset + self.experts_held - 1} "
                f"are not among the layer's {self.num_experts}"
            )
            raise ValueError(msg)
        if self.router not in ROUTERS:
            msg = f"unknown router {self.router!r}; known: {ROUTERS}"
            raise ValueError(msg)
        dim, held, k = x.shape[-1], self.experts_held, self.top_k
        tokens = x.reshape(-1, dim)
        count = tokens.shape[0]
        fan_in = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1)
        gate = self.param("gate", fan_in, (held, dim, self.hidden_dim))
        value = self.param("value", fan_in, (held, dim, self.hidden_dim))
        out_kernel = self.param("out", fan_in, (held, self.hidden_dim, dim))
        if self.router == "sigmoid":
            # the bias steers the selection only; it is a buffer kept with the
            # parameters (checkpoints carry it) and out of the gradient
            bias = jax.lax.stop_gradient(
                self.param("expert_bias", nn.initializers.zeros, (self.num_experts,))
            )

        with jax.named_scope("router"):
            # float32 for real: at the default precision the TPU would round
            # both operands of a float32 product to bfloat16
            logits = nn.Dense(
                self.num_experts, use_bias=False, dtype=jnp.float32,
                precision=jax.lax.Precision.HIGHEST, name="router",
            )(tokens.astype(jnp.float32))
            if self.router == "sigmoid":
                selected, weights = route(jax.nn.sigmoid(logits), bias, k, self.scale)
            else:
                selected, weights = route_softmax(logits, k)

        with jax.named_scope("dispatch"):
            local = selected - self.expert_offset  # [T, k]
            here = (local >= 0) & (local < held)
            if token_mask is not None:
                here = here & token_mask.reshape(-1, 1)
            key = jnp.where(here, local, held).reshape(-1)  # absent experts sort last
            order = jnp.argsort(key, stable=True)
            group_sizes = jnp.sum(
                key[:, None] == jnp.arange(held)[None, :], axis=0, dtype=jnp.int32
            )
            # the way back's permutation, made once: the dispatch's transpose reads by it too
            inverse = jnp.zeros_like(order).at[order].set(jnp.arange(count * k))  # [T * k] rows
            ladder = capacity_ladder(count * k)
            rung = rung_of(ladder, jnp.sum(group_sizes))
            capacity = jnp.asarray(ladder, jnp.int32)[rung]

        # dispatch, experts and combine, each under its own scope, on the rung's rows;
        # the rows of assignments that are not here weigh nothing on the way back
        share = jnp.where(here, weights, 0.0)  # float32, as the router made them
        cast = lambda w: w.astype(self.dtype)  # noqa: E731
        out = routed_experts(
            ladder, rung, tokens, cast(gate), cast(value), cast(out_kernel), share, order,
            inverse, here, group_sizes,
        )

        latest = {"reduce_fn": lambda _, new: new, "init_fn": lambda: None}  # one value a step
        self.sow("counters", "expert_load", group_sizes, **latest)
        read = jnp.where(inverse < capacity, inverse, count * k)  # past the rung: no group's row
        self.sow(
            "counters", "dropped_assignments",
            unserved(local.reshape(-1), here.reshape(-1), read, group_sizes), **latest,
        )
        self.sow("counters", "expert_rows_capacity", capacity, **latest)
        return out.reshape(x.shape)
