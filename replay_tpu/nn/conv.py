"""The gated short convolution: a sequence mixer with a fixed, tiny receptive field.

    [b, c, u] = split(W_in x)        W_in: d -> 3d, no bias
    v         = b * u
    conv_t    = sum_{j < K} kernel[j] * v_{t-j}     depth-wise, causal, no bias
    out       = W_out (c * conv)

No activation, no state beyond the last ``K - 1`` positions. The convolution is
``K`` shifted element-wise products (K = 3): XLA fuses them with the two gates
into one pass over ``[B, L, d]``, so the layer's cost is its two projections.
``kernel[j]`` weighs the input ``j`` positions back (a framework that stores a
``[d, 1, K]`` cross-correlation filter holds the same numbers in reverse order).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp


def causal_depthwise_conv(v: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    """``sum_j kernel[j] * v[..., t - j, :]`` with zeros before the window:
    ``v`` [..., L, d], ``kernel`` [K, d]."""
    out = v * kernel[0]
    for j in range(1, kernel.shape[0]):
        shifted = jnp.pad(v, [(0, 0)] * (v.ndim - 2) + [(j, 0), (0, 0)])[..., : v.shape[-2], :]
        out = out + shifted * kernel[j]
    return out


class GatedShortConv(nn.Module):
    """Input-gated, output-gated depth-wise causal convolution of width
    ``kernel_size`` (see the module docstring). Positions the caller zeroed
    (padding) contribute nothing to the positions after them."""

    kernel_size: int = 3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        dim = x.shape[-1]
        gates = nn.Dense(3 * dim, use_bias=False, dtype=self.dtype, name="in_proj")(x)
        b, c, u = jnp.split(gates, 3, axis=-1)
        kernel = self.param(
            "kernel", nn.initializers.normal(stddev=self.kernel_size**-0.5),
            (self.kernel_size, dim),
        )
        conv = causal_depthwise_conv(b * u, kernel.astype(gates.dtype))
        return nn.Dense(dim, use_bias=False, dtype=self.dtype, name="out_proj")(c * conv)
