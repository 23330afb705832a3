"""Seconds of set-up inside jax's backend compile (``compile_backend_s``, from
``jax.monitoring``'s ``backend_compile_duration``: XLA compiling an executable, or
the persistent cache loading it), on any thread, over the same records as
``setup_trace_lower_s``. Tens of seconds on the first run of a checkout, the
cache's loads on every later one (``benchmark/startup.py``)."""

from benchmark import startup


def read(context):
    return startup.built("compile_backend_s")
