"""Share of a chunk's done-to-done period in which the fit thread had no
``train_scan`` waiting on the device: (``data_wait`` + ``dispatch`` + ``account``)
over the period between two chunk syncs, in percent. What is left is
``device_wait``. The feeder's eager programs can keep the device busy inside
this share, so it is an upper limit on the idle share the fit thread causes.

Median over the chunks of the process's last ``fit`` call in which nothing
compiled: the window's chunks, 2 warm-up chunks (one holds the driver's 0.5 s
settle pause in ``account``) and the 4 traced ones; nothing under 10 records
(``benchmark/stages.py``)."""

from benchmark import stages


def _share(record):
    if not record.get("period"):
        return None  # the fit's first chunk has no earlier sync to count from
    return (record["data_wait"] + record["dispatch"] + record["account"]) / record["period"]


def read(context):
    return stages.median(_share, 100.0)
