"""Milliseconds the fit thread waited for the feed to hand it a chunk: the
program's ``data_wait`` stage (``obs.trace.ChunkStages.feed``, the pull in
``Trainer.fit``'s scan branch). Near nought the feed is ahead of the device; a
whole stack + copy (20 ms and more) is the unfilled feed state; hundreds of
milliseconds is an input-bound run.

Median over the chunks of the process's last ``fit`` call in which nothing
compiled: the window's chunks, 2 warm-up chunks (one holds the driver's 0.5 s
settle pause in ``account``) and the 4 traced ones; nothing under 10 records
(``benchmark/stages.py``)."""

from benchmark import stages


def read(context):
    return stages.median(lambda r: r["data_wait"], 1e3)
