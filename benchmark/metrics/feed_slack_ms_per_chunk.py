"""Milliseconds the feeder was blocked putting a finished chunk into the full
queue (``feed_full`` in ``prefetch._pipeline``): how long the input side had
nothing to do. Nought is an input-bound run.

Median over the chunks of the process's last ``fit`` call in which nothing
compiled: the window's chunks, 2 warm-up chunks (one holds the driver's 0.5 s
settle pause in ``account``) and the 4 traced ones; nothing under 10 records
(``benchmark/stages.py``)."""

from benchmark import stages


def read(context):
    return stages.median(lambda r: r["feed_full"], 1e3)
