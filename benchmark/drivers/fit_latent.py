"""Driver of the traffic kind ``fit_latent``: the ``fit_hybrid`` driver for a layer
pattern of latent-attention layers over one leading dense feed-forward and sparse
experts with a shared expert beside them (no convolution, no grouped-query layer).

Everything that is timed, warmed up, probed and compared is ``fit_hybrid.py``'s,
and through it ``fit.py``'s, reused by import: the same ``Stream``, ``ChunkClock``,
step-1 probe chunk, ``grad_step1_gap``, ``grad_step1_leaf_gap`` and
``expert_load_step1_gap``. This file loads a PRIVATE instance of ``fit_hybrid.py``
(which loads its own private ``fit.py``: the ``fit``, ``fit_hybrid`` and
``fit_windowed`` cells run the code they always ran) and sets the two tuples that
name this model:

``GROUPS``        what the plain reference and the shape counts need besides the
                  nine keys of the ``fit`` kind: the configuration's ``layers`` /
                  ``experts`` / ``shared_experts`` / ``latent_attention`` groups
                  and ``norm_eps``; and an ``attention`` group (heads of 128 + 64)
                  that only ``counts_hybrid.forward_flops_by_kind`` reads, on its
                  way to the ``moe`` entry ``moe_roofline_pct`` takes from it.
``LAYER_SCOPES``  the traced slice is reduced under ``loss``, ``moe``,
                  ``shared_expert``, ``latent_attention``, ``dense_ffn``,
                  ``forward``, in that order. ``shared_expert`` is a sibling of
                  ``moe``, so ``moe`` stays routers + held experts; a scope is
                  matched as a whole segment of an op's path, so the mixer's
                  module name ``attention`` inside ``latent_attention`` claims
                  nothing here (no scope of this list is named so).
"""

from __future__ import annotations

import importlib.util
from pathlib import Path


def _private_hybrid():
    path = Path(__file__).with_name("fit_hybrid.py")
    spec = importlib.util.spec_from_file_location("benchmark.drivers._hybrid_for_latent", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_hybrid = _private_hybrid()
_hybrid.GROUPS = ("layers", "experts", "shared_experts", "latent_attention", "attention", "norm_eps")
_hybrid.LAYER_SCOPES = ("moe", "shared_expert", "latent_attention", "dense_ffn")

# what run.py, readings.py and the tests ask of a driver
run, build, Stream, compare = _hybrid.run, _hybrid.build, _hybrid.Stream, _hybrid.compare
read_capture, reference_model = _hybrid.read_capture, _hybrid.reference_model
drive_first_chunk, follow_reference = _hybrid.drive_first_chunk, _hybrid.follow_reference
reference_step1, numbers = _hybrid.reference_step1, _hybrid.numbers
