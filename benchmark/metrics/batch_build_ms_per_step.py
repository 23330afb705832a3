"""Milliseconds a step of ``SequenceBatcher._make_batch`` (gather and pad):
the chunk's ``batch_build`` stages over its steps.

Median over the chunks of the process's last ``fit`` call in which nothing
compiled: the window's chunks, 2 warm-up chunks (one holds the driver's 0.5 s
settle pause in ``account``) and the 4 traced ones; nothing under 10 records
(``benchmark/stages.py``)."""

from benchmark import stages


def read(context):
    return stages.median(lambda r: r["batch_build"] / r["steps"], 1e3)
