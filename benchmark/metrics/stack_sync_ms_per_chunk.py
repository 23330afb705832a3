"""Milliseconds of the ``stack`` stage: ``Trainer._stack_chunk`` alone, the
host stacking of a chunk's batches and, for each leaf that arrived as a device
array (``device_leaves``), a device-to-host read that waits for what the device
is running.

Median over the chunks of the process's last ``fit`` call in which nothing
compiled: the window's chunks, 2 warm-up chunks (one holds the driver's 0.5 s
settle pause in ``account``) and the 4 traced ones; nothing under 10 records
(``benchmark/stages.py``)."""

from benchmark import stages


def read(context):
    return stages.median(lambda r: r["stack"], 1e3)
