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
rows that are live: the gather into the buffer, the gather back and their
transposes move all ``T * k`` rows whatever share of them is held here, and at
an eighth held they cost several times the products (PERF.md, section 5).
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
            live = jnp.arange(count * k) < jnp.sum(group_sizes)
            rows = jnp.where(live[:, None], tokens[order // k], jnp.zeros((), tokens.dtype))

        with jax.named_scope("experts"):
            cast = lambda w: w.astype(self.dtype)  # noqa: E731
            hidden = nn.silu(grouped_matmul(rows, cast(gate), group_sizes)) * grouped_matmul(
                rows, cast(value), group_sizes
            )
            mixed = grouped_matmul(hidden.astype(self.dtype), cast(out_kernel), group_sizes)

        with jax.named_scope("combine"):
            # back to assignment order by the inverse permutation; the rows of
            # assignments that are not here are zeros and weigh nothing
            inverse = jnp.zeros_like(order).at[order].set(jnp.arange(count * k))  # [T * k] rows
            per_choice = mixed[inverse].reshape(count, k, dim)
            share = jnp.where(here, weights, 0.0)  # float32, as the router made them
            out = jnp.sum(per_choice.astype(jnp.float32) * share[..., None], axis=1)
            out = out.astype(mixed.dtype)

        latest = {"reduce_fn": lambda _, new: new, "init_fn": lambda: None}  # one value a step
        self.sow("counters", "expert_load", group_sizes, **latest)
        self.sow(
            "counters", "dropped_assignments",
            unserved(local.reshape(-1), here.reshape(-1), inverse, group_sizes), **latest,
        )
        return out.reshape(x.shape)
