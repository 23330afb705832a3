"""The whole step's share of the chip's bf16 peak for the layer-pattern model:
FLOPs a step REQUIRES (``counts_hybrid.step_train_flops``: every layer kind and
the head, no recomputation; the experts' products at the assignments the program
counted over the window, ``counters.expert_load`` in the chunk stage log, or at
the even-routing expectation where it counts nothing) times the steps of the
window, over the window's length and chips x peak."""

from benchmark import counts, counts_hybrid, stages


def read(context):
    model = context["model_sizes"]
    if "layers" not in model:
        return None  # not a layer-pattern cell: nothing to read
    peaks = counts.load_peaks(context["device_kind"])
    sent = counts_hybrid.measured_assignments(stages.records())
    flops = counts_hybrid.step_train_flops(model, context["batch_size"], sent)
    achieved = flops * context["steps"] / context["window_s"]
    return 100.0 * achieved / (context["chips"] * peaks["bf16_flops_per_s"])
