"""Device milliseconds per step of the model blocks: ops under the ``forward``
scope and its transpose in the traced slice (embedding, every block, final norm,
forward and backward). The loss head is a sibling scope and is not in it."""


def read(context):
    traced = context["traced"]
    device_s = traced["scope_s"].get("forward", 0.0)
    if device_s <= 0 or traced["steps"] <= 0:
        return None
    return 1e3 * device_s / traced["steps"]
