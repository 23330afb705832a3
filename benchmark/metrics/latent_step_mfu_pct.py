"""The whole step's share of the chip's bf16 peak for the latent-attention
layer-pattern model: FLOPs a step REQUIRES (``counts_latent.step_train_flops``: the
four projections and the causal half square of every latent-attention layer, the
shared experts at the positions the program counted (``counters.shared_expert_tokens``
in the chunk stage log), the routed experts at the assignments it counted
(``counters.expert_load``), the dense feed-forward, the head; no recomputation)
times the steps of the window, over the window's length and chips x peak."""

from benchmark import counts, counts_hybrid, counts_latent, stages


def read(context):
    model = context["model_sizes"]
    if counts_latent.SCOPE not in model:
        return None  # not a latent-attention cell: nothing to read
    peaks = counts.load_peaks(context["device_kind"])
    records = stages.records()
    flops = counts_latent.step_train_flops(
        model, context["batch_size"], counts_hybrid.measured_assignments(records),
        counts_latent.measured_shared_tokens(records),
    )
    achieved = flops * context["steps"] / context["window_s"]
    return 100.0 * achieved / (context["chips"] * peaks["bf16_flops_per_s"])
