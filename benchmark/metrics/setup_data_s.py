"""Seconds of set-up the program's data path took: the ``split``
(``LastNSplitter.split``), ``tokenize`` (``SequenceTokenizer.fit_transform``) and
``batcher_init`` (``SequenceBatcher.__init__``: the index arrays a batch is
gathered from) spans of the start-up log, over the whole process. The synthetic
log itself is the benchmark's and is in none of them (``benchmark/startup.py``)."""

from benchmark import startup


def read(context):
    return startup.spans("split", "tokenize", "batcher_init")
