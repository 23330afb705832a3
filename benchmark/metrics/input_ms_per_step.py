"""Host milliseconds the input pipeline (batcher + transforms) took to produce a
batch, over the batches handed over inside the window. It runs on the feeder
thread beside the step: the run is input-bound when this nears the step time."""


def read(context):
    if not context["produce_seconds"]:
        return None
    return 1e3 * sum(context["produce_seconds"]) / len(context["produce_seconds"])
