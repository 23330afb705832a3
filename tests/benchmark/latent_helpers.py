"""The toy cells of the latent-attention layer-pattern model, added to
``bench_helpers``' checkout the way it adds its own (and a later PR adds a cell):
files copied in, entries appended, nothing edited."""

from __future__ import annotations

from pathlib import Path
from unittest import mock

import bench_helpers

LATENT_CELLS = (
    ("tiny_moonlight", "tiny_fit_latent", 1), ("tiny_moonlight_f32", "tiny_fit_latent", 1),
)


def make_checkout(tmp_path: Path) -> Path:
    cells = bench_helpers.TOY_CELLS + LATENT_CELLS
    with mock.patch.object(bench_helpers, "TOY_CELLS", cells):
        return bench_helpers.make_checkout(tmp_path)
