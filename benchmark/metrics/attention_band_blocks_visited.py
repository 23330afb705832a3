"""How much of the square the sliding layers' route computes, over the band: the
kv-block products it runs, forward and backward, over the blocks the band's pairs
alone would fill, from the program's own counts
(``counters.attention_blocks_visited`` [layers on the fused route, (forward,
backward)] and ``counters.attention_blocks_needed`` in the chunk stage log, which
the attention layer sows from the schedule its kernel's grid and its backward's
scan are built from). 1 is the band alone; block rounding reads 1.25 at 256-row
blocks and 1.5 at 512; a route that masks the window instead of skipping reads
4.4 (the causal half square) or 8.5 (the square). The median over the log's steps."""

import statistics

from benchmark import stages


def read(context):
    # a row of the counters per attention layer, in layer order (every one on the fused route)
    kinds = context["model_sizes"].get("layers", {}).get("layer_types", ())
    attention = [kind for kind in kinds if kind.endswith("_attention")]
    sliding = [i for i, kind in enumerate(attention) if kind == "sliding_attention"]
    ratios = []
    for record in stages.records() if sliding else ():
        counted = record.get("counters", {})
        visited, needed = (counted.get(f"attention_blocks_{k}", ()) for k in ("visited", "needed"))
        for step_visited, step_needed in zip(visited, needed):
            if len(step_visited) == len(attention):
                products = sum(sum(step_visited[i]) for i in sliding)
                ratios.append(products / (2.0 * sum(step_needed[i] for i in sliding)))
    if len(ratios) < stages.FEWEST_RECORDS:
        return None
    return statistics.median(ratios)
