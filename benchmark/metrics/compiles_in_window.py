"""Programs traced (and so compiled or fetched from the cache) between the
window's opening and its close, from ``Trainer.compile_tracker``. Anything but 0
is a finding: something warmed up inside the measured window."""


def read(context):
    return float(context["compiles_in_window"])
