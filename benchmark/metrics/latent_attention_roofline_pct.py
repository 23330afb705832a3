"""The latent-attention layers' share of their roofline: the least time the chip
could take for their required work of one step
(``counts_latent.attention_least_seconds``: the q, kv_a, kv_b and o projections and
the causal half square's pairs at 192 + 128 a head, the larger of FLOPs over peak
FLOP/s and least bytes over peak bytes/s; compute-bound at these shapes) over the
device time per step of the ops under the ``latent_attention`` scope and its
transpose. The work is counted from shapes: blocks above the diagonal that a route
multiplies, and keys it broadcasts, are owed nothing."""

from benchmark import counts, counts_latent


def read(context):
    model = context["model_sizes"]
    device_ms = counts_latent.scope_ms_per_step(context, counts_latent.SCOPE)
    if counts_latent.SCOPE not in model or device_ms is None:
        return None  # not a latent-attention cell, or no op under the scope: nothing to read
    peaks = counts.load_peaks(context["device_kind"])
    per_chip_batch = context["batch_size"] // context["chips"]
    least_s, _ = counts_latent.attention_least_seconds(model, per_chip_batch, peaks)
    return 100.0 * least_s / (device_ms / 1e3)
