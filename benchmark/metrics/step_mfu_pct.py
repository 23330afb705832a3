"""The whole step's share of the chip's bf16 peak: FLOPs a step REQUIRES (shape
counts, ``counts.step_train_flops``; no recomputation) times the steps of the
window, over the window's length and chips x peak."""

from benchmark import counts


def read(context):
    peaks = counts.load_peaks(context["device_kind"])
    flops = counts.step_train_flops(context["model_sizes"], context["batch_size"])
    achieved = flops * context["steps"] / context["window_s"]
    return 100.0 * achieved / (context["chips"] * peaks["bf16_flops_per_s"])
