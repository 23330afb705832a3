"""Milliseconds of the fit thread's ``account`` stage: from a chunk's one host
sync to the next pull on the feed (``account_step`` for each of the chunk's
steps, every logger's ``log_event``, the memory sample, checkpoint and
preemption checks).

Median over the chunks of the process's last ``fit`` call in which nothing
compiled: the window's chunks, 2 warm-up chunks (one holds the driver's 0.5 s
settle pause in ``account``) and the 4 traced ones; nothing under 10 records
(``benchmark/stages.py``)."""

from benchmark import stages


def read(context):
    return stages.median(lambda r: r["account"], 1e3)
