"""90th percentile of the host time between successive chunk completions, over
all chunks of the window (their count is ``notes.chunks`` of the result line).
The tail that the window's rate averages away: a stall of the feed or the host
shows here first."""

import numpy as np


def read(context):
    gaps = context["chunk_gaps_s"]
    if len(gaps) < 10:  # a 90th percentile wants samples beyond it
        return None
    return 1e3 * float(np.percentile(gaps, 90))
