"""Executables set-up had XLA compile because the persistent cache did not hold
them: ``compile_programs`` less ``compile_cache_hits`` (``jax.monitoring``), over
the same records as ``setup_trace_lower_s``. 0 says the start was warm, anything
else how much of ``setup_programs_built`` was compiled. Not jax's own
``cache_misses`` event (``compile_cache_misses``), which fires only when a compiled
executable is WRITTEN: never with no cache directory or under jax's least
compile time and entry size, where a cold start would read 0
(``benchmark/startup.py``)."""

from benchmark import startup


def read(context):
    built, fetched = startup.built("compile_programs"), startup.built("compile_cache_hits")
    return None if built is None else built - fetched
