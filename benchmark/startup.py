"""What the readers of the program's start-up log share.

``replay_tpu.obs.trace.startup_log()`` holds one record per ``fit`` call of this
process, written by the program itself: the seconds of every stage that ran on
the fit's thread outside any chunk before the call's first chunk (the set-up
spans ``pkg_import``, ``split``, ``tokenize``, ``batcher_init``, ``init_state``)
and what ``jax.monitoring`` said was built there (the seven ``compile_*``
counters); its last record is what ran after the last ``fit``. The chunk stage
log (``chunk_stage_log()``) holds the same counters per chunk, from both threads.

In a run of the ``fit`` driver the process calls ``fit`` twice (the first chunk;
warm-up, window and traced slice), so set-up is the two calls' start-up records
and the one chunk in which ``train_scan`` compiled. What follows the last ``fit``
is the benchmark's own (the program's HLO text for the capture, the plain
reference, which compiles): a span is summed over the whole log, the counters
over the ``fit`` calls' records only.

A program that has no such log (the parent of the PR that brought it) gives
nothing to read, and the metric is left out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional


def log() -> Optional[List[Dict[str, Any]]]:
    try:
        from replay_tpu.obs.trace import startup_log
    except ImportError:
        return None
    return startup_log()


def spans(*names: str) -> Optional[float]:
    """Seconds of the named stages over the whole start-up log."""
    records = log()
    if records is None:
        return None
    return float(sum(record.get(name, 0.0) for record in records for name in names))


def built(*counters: str) -> Optional[float]:
    """The named counters over set-up: the start-up record of every ``fit`` call
    and every chunk in which one of the trainer's programs compiled."""
    records = log()
    if records is None:
        return None
    from replay_tpu.obs.trace import chunk_stage_log

    records = [r for r in records if r["fit"] is not None]
    records += [r for r in chunk_stage_log() if r["compiled"]]
    return float(sum(record.get(name, 0) for record in records for name in counters))
