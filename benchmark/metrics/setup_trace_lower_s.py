"""Seconds of set-up that jax spent tracing python to jaxprs and lowering them to
MLIR (``compile_trace_s`` + ``compile_lower_s``, from ``jax.monitoring``), on any
thread: over the start-up record of every ``fit`` call and every chunk in which
``train_scan`` compiled. No compilation cache removes this part: it is paid on
every start, warm or cold (``benchmark/startup.py``)."""

from benchmark import startup


def read(context):
    return startup.built("compile_trace_s", "compile_lower_s")
