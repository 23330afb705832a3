"""Seconds the process spent importing the program's packages: the ``pkg_import``
span of the start-up log (``replay_tpu/nn/__init__.py`` and
``replay_tpu/data/nn/__init__.py`` time themselves, top to bottom, each less what
the other took inside it), over the whole process. The benchmark imports jax
first, so this is flax, optax and the packages' own modules; python's start,
``import jax`` and ``jax.devices()`` come before it and are in no span
(``benchmark/startup.py``)."""

from benchmark import startup


def read(context):
    return startup.spans("pkg_import")
