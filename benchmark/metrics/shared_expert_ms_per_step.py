"""Device milliseconds per step of the ops under the ``shared_expert`` scope and its
transpose in the traced slice (the dense SwiGLU beside the routed share of every
sparse layer, forward and backward; a sibling of ``moe``, so in neither
``moe_ms_per_step`` nor ``moe_roofline_pct``)."""

from benchmark import counts_latent


def read(context):
    return counts_latent.scope_ms_per_step(context, "shared_expert")
