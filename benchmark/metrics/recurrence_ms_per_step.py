"""Device milliseconds per step of the looped stack: the ops under the
``recurrence`` scope and its transpose in the traced slice, the layer kinds nested
in it (``attention``, ``dense_ffn``) included: all T passes, forward, backward and
the recomputed forward."""

from benchmark import counts_latent


def read(context):
    return counts_latent.scope_ms_per_step(context, "recurrence")
