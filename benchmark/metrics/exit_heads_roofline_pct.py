"""The exits' share of their roofline: the least time the chip could take for the
T exit heads and gates of one step (``counts_loop.exit_heads_least_seconds``:
T x 2*P*d*I and the gates, forward x3; compute-bound at these shapes) over the
device time per step under the ``loss`` scope and its transpose (every
``exit_head`` lies in it). ``head_roofline_pct`` counts ONE head and would read a
quarter of this at T = 4."""

from benchmark import counts, counts_latent, counts_loop


def read(context):
    model = context["model_sizes"]
    device_ms = counts_latent.scope_ms_per_step(context, "loss")
    if counts_loop.GROUP not in model or device_ms is None:
        return None  # not a looped cell, or no op under the scope: nothing to read
    peaks = counts.load_peaks(context["device_kind"])
    per_chip_batch = context["batch_size"] // context["chips"]
    least_s, _ = counts_loop.exit_heads_least_seconds(model, per_chip_batch, peaks)
    return 100.0 * least_s / (device_ms / 1e3)
