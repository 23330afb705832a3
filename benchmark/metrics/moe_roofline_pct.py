"""The expert layers' share of their roofline: the least time the chip could take
for the routers' and the held experts' products of one step
(``counts_hybrid.moe_least_seconds``: the larger of FLOPs over peak FLOP/s and
least bytes over peak bytes/s) over the device time per step of the ops under the
``moe`` scope and its transpose (router, dispatch, the grouped products, combine).
The products are counted at the assignments the program COUNTED in the traced
chunks (``counters.expert_load`` in the last records of the chunk stage log), not
at the even-routing expectation: a router that has collapsed onto an expert held
here sends up to 8x the expectation, one that has left sends none. Where the
program counts nothing, the expectation stands in. The work is counted from
shapes, whatever implements the layer."""

from benchmark import counts, counts_hybrid, stages


def read(context):
    traced = context["traced"]
    device_s = traced["scope_s"].get("moe", 0.0)
    if device_s <= 0 or traced["steps"] <= 0 or "experts" not in context["model_sizes"]:
        return None
    peaks = counts.load_peaks(context["device_kind"])
    per_chip_batch = context["batch_size"] // context["chips"]
    records, runs = stages.records(), int(traced.get("runs", 0))
    sent = counts_hybrid.measured_assignments(records[-runs:] if runs else records)
    least_s, _ = counts_hybrid.moe_least_seconds(
        context["model_sizes"], per_chip_batch, peaks, sent
    )
    return 100.0 * least_s / (device_s / traced["steps"])
