"""The sliding layers' share of their roofline: the least time the chip could take
for their required work of one step (``counts_windowed.attention_least_seconds``:
the projections at the true head counts and the BAND's pairs, the larger of FLOPs
over peak FLOP/s and least bytes over peak bytes/s; compute-bound at these shapes)
over the device time per step of the ops under the ``window_attention`` scope and
its transpose. The work is counted from shapes: a route that multiplies blocks
outside the band is owed nothing for them."""

from benchmark import counts_windowed


def read(context):
    return counts_windowed.attention_roofline_pct(context, "sliding_attention")
