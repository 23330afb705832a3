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
expert (those of absent experts last), the rows of the held ones gathered into
one ``[T * k, d]`` buffer, and each expert multiplies its own contiguous group of
rows (:func:`grouped_matmul`). The buffer has room for every assignment, so
however uneven the routing, nothing is dropped. Only the products follow the
rows that are live: the two row movements take all ``T * k`` rows whatever share
of them is held here (PERF.md, section 5). Rows travel by GATHERS in both
directions: the sort ``order`` and its inverse are made once, and each movement
has a hand-written transpose (:func:`dispatch_rows`, :func:`combine_rows`), which
is the other movement. Forward, the buffer reads ``tokens[order // k]``, and the
way back gives token ``t`` the weighted sum of its own ``k`` buffer rows
``mixed[inverse[t * k + j]]``. Backward, the buffer's cotangent reads
``d_out[order // k]`` times its weight (a permutation: every row written once,
nothing added), and a token's cotangent is the sum of its own ``k`` rows of the
buffer's cotangent, masked by which assignments are held here. Both sums over
``k`` are accumulated in float32 and cast once. Left to itself JAX transposes a
gather into a scatter-add, which cannot know that ``inverse`` is a permutation,
serialises over the rows (three times a gather's time on the TPU) and sums in
bfloat16.
``expert_load`` (assignments per held expert) and ``dropped_assignments`` (held
assignments whose buffer row lies outside their expert's group of rows, so that
another expert's kernel, or none, would multiply them: 0 unless the sort, the
group sizes and the way back disagree) are sown into the ``counters``
collection, which the trainer carries in its step metrics.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

GMM_TILING = (512, 1024, 1024)  # rows, contraction, columns of one kernel tile
ROUTERS = ("sigmoid", "softmax")


def grouped_matmul(
    lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """``lhs[rows of group g] @ rhs[g]`` for contiguous groups of rows: ``lhs``
    [M, K], ``rhs`` [G, K, N], ``group_sizes`` [G] int32 summing to at most M.
    Rows past the last group come out as zeros.

    One path: the Pallas grouped product that ships with JAX (``megablox.gmm``),
    compiled on the TPU and interpreted on the CPU, like the repo's other kernels
    (``interpret=None``: ``ops.flash_attention.pallas_interpret`` decides, and any
    other backend raises). The kernel visits only the row tiles that hold a group's
    rows, forward and backward (its own VJP), so the products follow the live rows
    and not the buffer's length.
    """
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from replay_tpu.ops.flash_attention import pallas_interpret

    rows = lhs.shape[0]
    tiling = tuple(min(t, n) for t, n in zip(GMM_TILING, (rows, lhs.shape[1], rhs.shape[2])))
    if interpret is None:
        interpret = pallas_interpret()
    out = megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling, interpret=interpret)
    # the kernel leaves the rows no group owns as it found them
    live = jnp.arange(rows)[:, None] < jnp.sum(group_sizes)
    return jnp.where(live, out, jnp.zeros((), out.dtype))


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
    """The expert buffer [T * k, d]: row ``r`` holds the token of assignment
    ``order[r]`` (assignment ``t * k + j`` is token ``t``'s ``j``-th choice) where
    that assignment is held ``here`` [T, k] (those sort first), zeros after them.
    ``inverse`` is the inverse permutation of ``order``, used by the transpose:
    ``d_tokens[t] = sum_j here[t, j] * d_rows[inverse[t * k + j]]``, gathers and a
    float32 sum over ``k``. The mask is ``here`` and not the cotangent's content, so
    whatever a dead row's cotangent holds reaches no token."""
    del inverse
    live = jnp.arange(order.shape[0]) < jnp.sum(here)
    rows = tokens[order // here.shape[1]]
    return jnp.where(live[:, None], rows, jnp.zeros((), tokens.dtype))


def _dispatch_rows_fwd(tokens, order, inverse, here):
    return dispatch_rows(tokens, order, inverse, here), (inverse, here)


def _own_rows(rows: jnp.ndarray, inverse: jnp.ndarray, k: int):
    """``k`` arrays [T, d]: the ``j``-th holds the buffer row of every token's
    ``j``-th choice. ``k`` gathers of ``T`` rows and not one of ``T * k`` reshaped to
    [T, k, d]: on the TPU that reshape is a copy of the whole buffer (``k`` lands in
    a tiled axis) and a sum over it a second pass; these fuse with what reads them."""
    row_of = inverse.reshape(-1, k)
    return [rows[row_of[:, j]] for j in range(k)]


def _dispatch_rows_bwd(saved, d_rows):
    inverse, here = saved
    d_tokens = jnp.zeros((here.shape[0], d_rows.shape[-1]), jnp.float32)
    for j, own in enumerate(_own_rows(d_rows, inverse, here.shape[1])):
        d_tokens += jnp.where(here[:, j, None], own, jnp.zeros((), own.dtype)).astype(jnp.float32)
    return d_tokens.astype(d_rows.dtype), None, None, None


dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def combine_rows(
    mixed: jnp.ndarray, share: jnp.ndarray, order: jnp.ndarray, inverse: jnp.ndarray
) -> jnp.ndarray:
    """The way back, [T, d]: ``out[t] = sum_j share[t, j] * mixed[inverse[t * k + j]]``
    with ``share`` [T, k] float32 (0 for an assignment that is not here), summed in
    float32 and cast once. It is the dispatch's transpose with weights for a mask,
    and its own transpose is the dispatch again:
    ``d_mixed[r] = share.flat[order[r]] * d_out[order[r] // k]``, one gather of the
    small [T, d] cotangent, every buffer row written once and nothing added;
    ``d_share[t, j] = <d_out[t], mixed[inverse[t * k + j]]>`` from the rows the
    forward pass gathered."""
    return _combine_rows_fwd(mixed, share, order, inverse)[0]


def _combine_rows_fwd(mixed, share, order, inverse):
    own = _own_rows(mixed, inverse, share.shape[1])
    out = jnp.zeros(own[0].shape, jnp.float32)
    for j, rows in enumerate(own):
        out += rows.astype(jnp.float32) * share[:, j, None]
    return out.astype(mixed.dtype), (own, share, order)


def _combine_rows_bwd(saved, d_out):
    own, share, order = saved
    spread = d_out[order // share.shape[1]].astype(jnp.float32)  # [T * k, d]
    d_mixed = (spread * share.reshape(-1)[order][:, None]).astype(d_out.dtype)
    d_wide = d_out.astype(jnp.float32)
    d_share = jnp.stack(
        [jnp.sum(d_wide * rows.astype(jnp.float32), axis=-1) for rows in own], axis=1
    )
    return d_mixed, d_share, None, None


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


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
            rows = dispatch_rows(tokens, order, inverse, here)

        with jax.named_scope("experts"):
            cast = lambda w: w.astype(self.dtype)  # noqa: E731
            hidden = nn.silu(grouped_matmul(rows, cast(gate), group_sizes)) * grouped_matmul(
                rows, cast(value), group_sizes
            )
            mixed = grouped_matmul(hidden.astype(self.dtype), cast(out_kernel), group_sizes)

        with jax.named_scope("combine"):
            # each token's weighted sum of its own k buffer rows, found by the inverse
            # permutation; the rows of assignments that are not here weigh nothing
            share = jnp.where(here, weights, 0.0)  # float32, as the router made them
            out = combine_rows(mixed, share, order, inverse)

        latest = {"reduce_fn": lambda _, new: new, "init_fn": lambda: None}  # one value a step
        self.sow("counters", "expert_load", group_sizes, **latest)
        self.sow(
            "counters", "dropped_assignments",
            unserved(local.reshape(-1), here.reshape(-1), inverse, group_sizes), **latest,
        )
        return out.reshape(x.shape)
