"""Device milliseconds per step of the ops under the ``latent_attention`` scope and
its transpose in the traced slice (every latent-attention layer: the four
projections, the latent norm, rotary, the broadcast of the rotary key, the fused
192 / 128 route forward and backward)."""

from benchmark import counts_latent


def read(context):
    return counts_latent.scope_ms_per_step(context, counts_latent.SCOPE)
