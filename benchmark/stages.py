"""What the readers of the program's chunk stage log share.

``replay_tpu.obs.trace.chunk_stage_log()`` holds one record per scan chunk of
every ``fit`` of this process, written by the program itself (the stage spans of
``Trainer.fit``'s chunked path, on the fit thread and on the feeder). A reader
takes the records of the process's LAST ``fit`` call in which nothing compiled
and reports the median over chunks, or nothing when there are fewer than 10.

In a run of the ``fit`` driver that set is the window's chunks plus 2 warm-up
chunks and, with ``--trace 1``, the 4 traced ones; one warm-up chunk holds the
driver's 0.5 s settle pause in ``account`` (a logger's time is the fit
thread's). The median is of the window all the same: 6 records beside 78 or more.

A program that has no such log (the parent of the PR that brought it) gives
nothing to read, and the metric is left out of the line.
"""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, List, Optional

FEWEST_RECORDS = 10


def records() -> List[Dict[str, Any]]:
    try:
        from replay_tpu.obs.trace import chunk_stage_log
    except ImportError:
        return []
    log = chunk_stage_log()
    if not log:
        return []
    last_fit = log[-1]["fit"]
    return [r for r in log if r["fit"] == last_fit and not r["compiled"]]


def median(value: Callable[[Dict[str, Any]], Optional[float]], scale: float) -> Optional[float]:
    """``scale`` times the median over the chunks of ``value(record)`` (1e3: the
    seconds of a stage in milliseconds); a record it gives ``None`` for is left out."""
    values = [v for v in map(value, records()) if v is not None]
    if len(values) < FEWEST_RECORDS:
        return None
    return scale * statistics.median(values)
