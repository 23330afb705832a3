"""Seconds in ``Trainer.init_state``: the ``init_state`` span of the start-up log
(the flax init where no weights are handed in, the placement of every leaf on
the mesh, the optimizer's state), every call of the process. The programs it
dispatches one by one are counted in ``setup_programs_built``, their seconds in
``setup_trace_lower_s`` and ``setup_backend_compile_s``, which lie INSIDE this
span where they happened in it (``benchmark/startup.py``)."""

from benchmark import startup


def read(context):
    return startup.spans("init_state")
