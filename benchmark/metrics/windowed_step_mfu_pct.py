"""The whole step's share of the chip's bf16 peak for the window-and-full
layer-pattern model: FLOPs a step REQUIRES (``counts_windowed.step_train_flops``:
projections at the true head counts, the band's pairs in the sliding layers and
the causal half square in the full ones, the experts' products at the assignments
the program counted over the window, ``counters.expert_load`` in the chunk stage
log, the head; no recomputation) times the steps of the window, over the window's
length and chips x peak."""

from benchmark import counts, counts_hybrid, counts_windowed, stages


def read(context):
    model = context["model_sizes"]
    if "sliding_window" not in model.get("attention", {}):
        return None  # not a window-and-full cell: nothing to read
    peaks = counts.load_peaks(context["device_kind"])
    sent = counts_hybrid.measured_assignments(stages.records())
    flops = counts_windowed.step_train_flops(model, context["batch_size"], sent)
    achieved = flops * context["steps"] / context["window_s"]
    return 100.0 * achieved / (context["chips"] * peaks["bf16_flops_per_s"])
