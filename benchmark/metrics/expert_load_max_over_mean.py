"""How uneven the routing is: per optimizer step, the largest held expert's
assignments over the mean of the held experts', in the expert layer where that
ratio is largest; the median over the steps of the chunks in the program's chunk
stage log (``counters.expert_load``: [steps, expert layers, held experts], which
the model counts and the trainer carries in its step metrics). 1 is even."""

import statistics

from benchmark import stages


def read(context):
    ratios = []
    for record in stages.records():
        for step in record.get("counters", {}).get("expert_load", ()):
            per_layer = [max(load) * len(load) / sum(load) for load in step if sum(load) > 0]
            if per_layer:
                ratios.append(max(per_layer))
    if len(ratios) < stages.FEWEST_RECORDS:
        return None
    return statistics.median(ratios)
