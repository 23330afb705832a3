"""The comparison that decides ``correct`` for a training cell.

The program's first chunk of optimizer steps against the plain reference's, on
the same weights, rows and dropout keys. Three kinds of number, each with a
limit of its own (``limits/<cell>.json``; PERF.md gives the readings each was
set from):

``loss_step<k>``       |program - reference| / |reference| of step k's loss.
``grad_norm_gap``      the gradients as the optimizer holds them after the chunk
                       (Adam's first moment): per leaf, the gap between the
                       program's norm and the reference's, over the reference's
                       norm of that leaf or of the median leaf, whichever is
                       larger; the worst leaf.
``update_norm_gap``    the same measure on the parameters' change over the
                       chunk. Leaves whose reference gradient is under a
                       thousandth of the median leaf's are left out: under Adam
                       they move by round-off alone.

A state left unchanged reads 1 on both norms by this measure.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Sequence

import numpy as np

SMALL_GRADIENT = 1e-3  # of the median leaf's gradient norm, in the reference


def _norms(leaves: Mapping[str, Any]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in leaves.items()}


def norm_gaps(program: Mapping[str, float], reference: Mapping[str, float], names=None):
    """{leaf: |program - reference| / max(reference, median reference)}."""
    names = sorted(reference) if names is None else names
    floor = float(np.median([reference[k] for k in sorted(reference)]))
    return {
        k: abs(program[k] - reference[k]) / max(reference[k], floor, 1e-30) for k in names
    }


def training_numbers(
    program_losses: Sequence[float],
    reference_losses: Sequence[float],
    program_moment: Mapping[str, Any],
    reference_moment: Mapping[str, Any],
    program_change: Mapping[str, Any],
    reference_change: Mapping[str, Any],
) -> Dict[str, Any]:
    """The numbers compared, by name, and the leaf each worst gap sits on."""
    if len(program_losses) != len(reference_losses):
        raise ValueError("the program and the reference followed different numbers of steps")
    numbers = {
        f"loss_step{i + 1}": abs(p - r) / abs(r)
        for i, (p, r) in enumerate(zip(program_losses, reference_losses))
    }
    ref_moment = _norms(reference_moment)
    moment_gaps = norm_gaps(_norms(program_moment), ref_moment)
    median_gradient = float(np.median(list(ref_moment.values())))
    moved = [k for k in sorted(ref_moment) if ref_moment[k] >= SMALL_GRADIENT * median_gradient]
    change_gaps = norm_gaps(_norms(program_change), _norms(reference_change), moved)
    worst_moment = max(moment_gaps, key=moment_gaps.get)
    worst_change = max(change_gaps, key=change_gaps.get)
    numbers["grad_norm_gap"] = moment_gaps[worst_moment]
    numbers["update_norm_gap"] = change_gaps[worst_change]
    detail = {
        "grad_norm_gap_leaf": worst_moment,
        "update_norm_gap_leaf": worst_change,
        "leaves_left_out": sorted(set(ref_moment) - set(moved)),
    }
    return {"numbers": numbers, "detail": detail}


def judge(numbers: Mapping[str, float], limits: Mapping[str, float]) -> Dict[str, Any]:
    """Each number beside its limit; ``correct`` only if every one is finite and
    within it. ``limits`` may give one limit for a family (``loss_step``)."""
    checks = {}
    for name, value in numbers.items():
        family = name.rstrip("0123456789")
        if name not in limits and family not in limits:
            raise KeyError(f"no limit for {name!r} in the cell's limits file")
        limit = limits[name] if name in limits else limits[family]
        checks[name] = {"value": float(value), "limit": float(limit)}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return {"correct": bool(correct), "checks": checks}
