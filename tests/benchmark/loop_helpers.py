"""The toy cells of the looped layer-pattern model, added to ``bench_helpers``'
checkout the way it adds its own (and a later PR adds a cell): files copied in,
entries appended, nothing edited."""

from __future__ import annotations

from pathlib import Path
from unittest import mock

import bench_helpers

LOOP_CELLS = (("tiny_ouro", "tiny_fit_loop", 1), ("tiny_ouro_f32", "tiny_fit_loop", 1))


def make_checkout(tmp_path: Path) -> Path:
    cells = bench_helpers.TOY_CELLS + LOOP_CELLS
    with mock.patch.object(bench_helpers, "TOY_CELLS", cells):
        return bench_helpers.make_checkout(tmp_path)
