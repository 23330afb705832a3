"""Milliseconds a step of the transforms inside ``Compose.__call__``: the
chunk's ``transform`` stages, all transforms together, over its steps. Which
transform holds them is ``transform_by_name`` of the stage log.

Median over the chunks of the process's last ``fit`` call in which nothing
compiled: the window's chunks, 2 warm-up chunks (one holds the driver's 0.5 s
settle pause in ``account``) and the 4 traced ones; nothing under 10 records
(``benchmark/stages.py``)."""

from benchmark import stages


def read(context):
    return stages.median(lambda r: r["transform"] / r["steps"], 1e3)
