"""The toy cells of the window-and-full layer-pattern model, added to
``bench_helpers``' checkout the way it adds its own (and a later PR adds a cell):
files copied in, entries appended, nothing edited."""

from __future__ import annotations

from pathlib import Path
from unittest import mock

import bench_helpers

WINDOWED_CELLS = (
    ("tiny_mellum2", "tiny_fit_windowed", 1), ("tiny_mellum2_f32", "tiny_fit_windowed", 1),
)


def make_checkout(tmp_path: Path) -> Path:
    cells = bench_helpers.TOY_CELLS + WINDOWED_CELLS
    with mock.patch.object(bench_helpers, "TOY_CELLS", cells):
        return bench_helpers.make_checkout(tmp_path)
