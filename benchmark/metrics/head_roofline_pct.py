"""The loss head's share of its roofline: the least time the chip could take for
the head's work of one step (``counts.head_least_seconds``: the larger of FLOPs
over peak FLOP/s and least bytes over peak bytes/s; compute-bound at these
shapes) over the device time per step of the ops under the ``loss`` scope and its
transpose in the traced slice. The work is counted from shapes, whatever
implements the head."""

from benchmark import counts


def read(context):
    traced = context["traced"]
    device_s = traced["scope_s"].get("loss", 0.0)
    if device_s <= 0 or traced["steps"] <= 0:
        return None  # no op under the scope in the capture: nothing to read
    peaks = counts.load_peaks(context["device_kind"])
    per_chip_batch = context["batch_size"] // context["chips"]
    least_s, _ = counts.head_least_seconds(context["model_sizes"], per_chip_batch, peaks)
    return 100.0 * least_s / (device_s / traced["steps"])
