"""The whole step's share of the chip's bf16 peak for the looped layer-pattern
model: FLOPs a step REQUIRES (``counts_loop.step_train_flops``: every layer
application of the T passes and the T exits' heads and gates, forward x3; no
recomputation) times the steps of the window, over the window's length and
chips x peak."""

from benchmark import counts, counts_loop


def read(context):
    model = context["model_sizes"]
    if counts_loop.GROUP not in model:
        return None  # not a looped cell: nothing to read
    peaks = counts.load_peaks(context["device_kind"])
    flops = counts_loop.step_train_flops(model, context["batch_size"])
    achieved = flops * context["steps"] / context["window_s"]
    return 100.0 * achieved / (context["chips"] * peaks["bf16_flops_per_s"])
