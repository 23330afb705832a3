"""Executables set-up built or fetched (``compile_programs``: one per
``backend_compile_duration`` event of ``jax.monitoring``, which wraps the look into
the persistent cache too), on the fit thread and the feeder, over the same
records as ``setup_trace_lower_s``: ``train_scan`` is one; the rest are the eager
ops of ``init_state``, of the transforms' first batch and of the benchmark's own
set-up, each a dispatch and a cache lookup of its own (``benchmark/startup.py``)."""

from benchmark import startup


def read(context):
    return startup.built("compile_programs")
