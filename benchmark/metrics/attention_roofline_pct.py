"""The full layers' share of their roofline in the window-and-full model: the least
time for their required work of one step (``counts_windowed.attention_least_seconds``:
projections at the true head counts and the causal half square) over the device time
per step under the ``attention`` scope and its transpose. Nothing for a model
without a window (its attention count is ``counts_hybrid``'s)."""

from benchmark import counts_windowed


def read(context):
    return counts_windowed.attention_roofline_pct(context, "full_attention")
