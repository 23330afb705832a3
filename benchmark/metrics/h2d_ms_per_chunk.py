"""Milliseconds of the ``h2d`` stage: ``Trainer._put_stacked`` and its fence,
the chunk's host-to-device copy alone.

Median over the chunks of the process's last ``fit`` call in which nothing
compiled: the window's chunks, 2 warm-up chunks (one holds the driver's 0.5 s
settle pause in ``account``) and the 4 traced ones; nothing under 10 records
(``benchmark/stages.py``)."""

from benchmark import stages


def read(context):
    return stages.median(lambda r: r["h2d"], 1e3)
