"""Executables built or fetched (``compile_programs``, ``jax.monitoring``) on the
fit thread or the feeder during the chunks of the process's last ``fit`` call in
which ``train_scan`` did not compile: warm-up, window and traced slice
(``benchmark/stages.records``). 0, or something met a new shape in steady state:
a transform's program, an eager op, a kernel. ``compiles_in_window`` sees the
trainer's own three programs only."""

from benchmark import stages, startup


def read(context):
    records = stages.records()
    if startup.log() is None or not records:
        return None
    return float(sum(record.get("compile_programs", 0) for record in records))
