"""Device milliseconds per step of the ops under the ``window_attention`` scope and
its transpose in the traced slice (every sliding layer: projections, rotary, the
banded route forward and backward)."""


def read(context):
    traced = context["traced"]
    device_s = traced["scope_s"].get("window_attention", 0.0)
    if device_s <= 0 or traced["steps"] <= 0:
        return None  # no op under the scope in the capture: nothing to read
    return 1e3 * device_s / traced["steps"]
