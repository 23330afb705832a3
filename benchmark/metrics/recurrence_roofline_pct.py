"""The looped stack's share of its roofline: the least time the chip could take for
its required work of one step (``counts_loop.recurrence_least_seconds``: T x N
layer applications of the projections, the SwiGLU and the causal half square's
pairs, forward x3; compute-bound at these shapes) over the device time per step
under the ``recurrence`` scope and its transpose. The count owes nothing for
recomputation, which the time holds: under full recomputation (a fourth pass of the
forward) 75% is this share's ceiling."""

from benchmark import counts, counts_latent, counts_loop


def read(context):
    model = context["model_sizes"]
    device_ms = counts_latent.scope_ms_per_step(context, "recurrence")
    if counts_loop.GROUP not in model or device_ms is None:
        return None  # not a looped cell, or no op under the scope: nothing to read
    peaks = counts.load_peaks(context["device_kind"])
    per_chip_batch = context["batch_size"] // context["chips"]
    least_s, _ = counts_loop.recurrence_least_seconds(model, per_chip_batch, peaks)
    return 100.0 * least_s / (device_ms / 1e3)
