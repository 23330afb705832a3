"""Driver of the traffic kind ``fit_windowed``: the ``fit_hybrid`` driver for a
layer pattern of window and full attention layers over sparse experts (no
convolution, no dense feed-forward).

Everything that is timed, warmed up, probed and compared is ``fit_hybrid.py``'s,
and through it ``fit.py``'s, reused by import: the same ``Stream``, ``ChunkClock``,
step-1 probe chunk, ``grad_step1_gap``, ``grad_step1_leaf_gap`` and
``expert_load_step1_gap``. This file loads a PRIVATE instance of ``fit_hybrid.py``
(which loads its own private ``fit.py``: the ``fit`` and ``fit_hybrid`` cells run
the code they always ran) and sets the two tuples that name this model:

``GROUPS``        what the plain reference and the shape counts need besides the
                  nine keys of the ``fit`` kind: the configuration's ``layers`` /
                  ``experts`` / ``attention`` groups and ``norm_eps``.
``LAYER_SCOPES``  the traced slice is reduced under ``loss``, ``moe``,
                  ``window_attention``, ``attention``, ``forward``, in that order:
                  a sliding layer's ops carry both ``window_attention`` (its scope)
                  and ``attention`` (its module's name) in their path, and the
                  first listed wins.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path


def _private_hybrid():
    path = Path(__file__).with_name("fit_hybrid.py")
    spec = importlib.util.spec_from_file_location("benchmark.drivers._hybrid_for_windowed", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_hybrid = _private_hybrid()
_hybrid.GROUPS = ("layers", "experts", "attention", "norm_eps")
_hybrid.LAYER_SCOPES = ("moe", "window_attention", "attention")

# what run.py, readings.py and the tests ask of a driver
run, build, Stream, compare = _hybrid.run, _hybrid.build, _hybrid.Stream, _hybrid.compare
read_capture, reference_model = _hybrid.read_capture, _hybrid.reference_model
drive_first_chunk, follow_reference = _hybrid.drive_first_chunk, _hybrid.follow_reference
reference_step1, numbers = _hybrid.reference_step1, _hybrid.numbers
