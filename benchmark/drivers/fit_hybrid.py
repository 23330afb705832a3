"""Driver of the traffic kind ``fit_hybrid``: the ``fit`` driver for a model whose
blocks are a layer pattern (mixers of several kinds, dense and sparse
feed-forwards) instead of SASRec's.

Everything that is timed, warmed up and reported is ``drivers/fit.py``'s, reused by
import: the same ``Stream``, ``ChunkClock``, first chunk, warm-up, window and
result. This file loads a PRIVATE instance of that module (the copy in
``sys.modules``, if any, is left alone, so the ``fit`` cells run the code they
always ran) and sets five names on it:

``reference_model``   the sizes the plain reference and the shape counts need: the
                      nine keys of the ``fit`` kind with their true values (the
                      head's counts read them) plus the configuration's
                      ``layers`` / ``experts`` / ``attention`` / ``conv`` groups
                      and ``norm_eps``.
``read_capture``      the traced slice reduced under this model's scopes: each
                      layer kind (``moe``, ``conv``, ``attention``, ``dense_ffn``)
                      besides ``loss`` and ``forward``. ``forward`` stays what it
                      is for the other cells (everything under it, the layer
                      kinds included); ``forward_rest`` is what no kind claims.
``drive_first_chunk`` ``follow_reference`` ``numbers``
                      the comparison that decides ``correct`` reads STEP 1 ALONE
                      besides the chunk's trajectory. A router that picks experts
                      makes the trajectory a poor witness: a near-tie that falls
                      the other way sends a token elsewhere, eight Adam steps at
                      1e-3 amplify it, and from the fourth step on float8 and
                      bfloat16 read alike (PERF.md, section 2). At step 1 program
                      and reference hold the same weights, so only the arithmetic
                      separates them.

Step 1 is read from the timed program itself, not from a second one. Before the
first chunk, set-up drives the trainer's compiled ``train_scan`` through one PROBE
chunk from the seed's weights: its first step is the first chunk's first batch, its
other steps are that batch with no valid row, whose loss and gradient are zero.
Adam's first moment after such a chunk is ``(1 - b1) * b1 ** (K - 1)`` times the
first gradient, exactly; the first step's ``expert_load`` counter is the program's
selection at the seed's weights. The state is then made again from the same
weights and the first chunk, warm-up and window go on as in ``fit.py``. The numbers
this kind compares:

``loss_step<k>``            as ``compare.py`` has them.
``update_norm_gap``         as ``compare.py`` has it, over the whole chunk.
``grad_step1_gap``          ||g_program - g_reference|| / ||g_reference|| over all
                            leaves of the first gradient: element by element, so
                            it sees rounding that a gap between two norms hides.
``grad_step1_leaf_gap``     the same per leaf, over the reference's norm of that
                            leaf or of the median leaf; the worst leaf.
``expert_load_step1_gap``   sum |load_program - load_reference| over the held
                            experts of every expert layer / the reference's
                            assignments: the selection itself (its bias too).

``grad_norm_gap`` (the gap of the norms of Adam's first moment after the chunk) is
NOT compared here: two thirds of that moment are the gradients of steps 4 to 8,
taken at weights that have already parted, and the first gradient proper takes its
place.
"""

from __future__ import annotations

import importlib.util
import shutil
from pathlib import Path
from typing import Any, Dict, Mapping

import numpy as np

GROUPS = ("layers", "experts", "attention", "conv", "norm_eps")
LAYER_SCOPES = ("moe", "conv", "attention", "dense_ffn")


def _private_fit():
    # run.load_module's three lines, not imported: run.py is `__main__` in a run,
    # and importing it by name would execute it a second time
    path = Path(__file__).with_name("fit.py")
    spec = importlib.util.spec_from_file_location("benchmark.drivers._fit_for_hybrid", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_fit = _private_fit()
_nine_keys = _fit.reference_model
_first_chunk, _trajectory, _trajectory_numbers = (
    _fit.drive_first_chunk, _fit.follow_reference, _fit.numbers,
)


def reference_model(config: Mapping[str, Any]) -> Dict[str, Any]:
    return {**_nine_keys(config), **{key: config[key] for key in GROUPS}}


def read_capture(cell, context) -> Dict[str, Any]:
    """The traced slice reduced to device numbers; the capture is then removed."""
    from benchmark import tracing

    events = tracing.load_events(tracing.find_xplane(context["capture_dir"]))
    traced = tracing.reduce_capture(
        events, context["scan_program"], ("loss",) + LAYER_SCOPES + ("forward",), cell.chips,
        tracing.op_paths_from_hlo(context["hlo_text"]),
    )
    scope_s = traced["scope_s"]
    scope_s["forward_rest"] = scope_s["forward"]
    scope_s["forward"] += sum(scope_s[kind] for kind in LAYER_SCOPES)
    traced["steps"] = traced["runs"] * context["scan_chunk"]
    if not cell.keep_capture:
        shutil.rmtree(context["capture_dir"], ignore_errors=True)
    return traced


class _Pulled:
    """The first chunk's batches, already pulled from the stream (the probe read
    the first of them), in ``drive_first_chunk``'s terms."""

    def __init__(self, stream, batches):
        self.scan_chunk, self.batches = stream.scan_chunk, batches

    def first_chunk(self):
        return iter(self.batches)


class _FirstLoads:
    """A second sink beside ``fit.py``'s: the first step's ``expert_load``."""

    def __init__(self):
        self.loads = None

    def log_event(self, event) -> None:
        if event.event == "on_train_step" and self.loads is None:
            self.loads = np.asarray(event.payload["counters"]["expert_load"])


def probe_step1(cell, built, batch, fit, steps: int) -> Dict[str, Any]:
    """The program's first gradient and first selection, from its own
    ``train_scan`` (module docstring): one chunk from the seed's weights whose
    steps after the first have no valid row."""
    import jax

    trainer, paths = built["trainer"], built["param_paths"]
    weights = built["make_weights"](built["weights_key"])
    state = trainer.init_state(batch, params=_fit.to_program_tree(weights, paths))
    del weights
    empty = {**batch, "valid": np.zeros_like(np.asarray(batch["valid"]))}
    listener = _FirstLoads()
    state = fit(iter([batch] + [empty] * (steps - 1)), state=state, loggers=listener)
    moment = jax.device_get(_fit.from_program_tree(_fit.first_moment(state.opt_state), paths))
    b1 = cell.config["optimizer"]["b1"]
    scale = (1.0 - b1) * b1 ** (steps - 1)
    return {
        "first_gradient": {k: np.asarray(v) / scale for k, v in moment.items()},
        "first_loads": listener.loads,
    }


def drive_first_chunk(cell, built, stream, fit):
    batches = list(stream.first_chunk())
    if "valid" not in batches[0]:
        raise RuntimeError("the batcher marks no valid rows: the probe chunk needs the mask")
    probe = probe_step1(cell, built, batches[0], fit, stream.scan_chunk)
    state, program = _first_chunk(cell, built, _Pulled(stream, batches), fit)
    return state, {**program, **probe}


def reference_step1(cell, built, kept, precision="f32", fault=None) -> Dict[str, Any]:
    """The reference's step 1 alone: its first gradient and first selection."""
    import jax

    config = cell.config
    weights = built["make_weights"](built["weights_key"])
    batch = _fit.reference_batch(kept[0], config["program"]["item_feature"])
    _, gradient, loads = built["reference"].first_step(
        weights, batch, built["model_sizes"], config["reference"]["head_row_blocks"],
        precision=precision, fault=fault,
    )
    return {"first_gradient": jax.device_get(gradient), "first_loads": np.asarray(loads)}


def follow_reference(cell, built, kept, seed: int, precision="f32", fault=None):
    """``fit.py``'s trajectory, and the reference's step 1 alone."""
    out = _trajectory(cell, built, kept, seed, precision, fault)
    return {**out, **reference_step1(cell, built, kept, precision, fault)}


def _norm(leaf) -> float:
    return float(np.linalg.norm(np.asarray(leaf, np.float64)))


def numbers(program: Mapping[str, Any], reference: Mapping[str, Any]) -> Dict[str, Any]:
    compared = _trajectory_numbers(program, reference)
    found, detail = compared["numbers"], compared["detail"]
    del found["grad_norm_gap"], detail["grad_norm_gap_leaf"]  # module docstring
    ours, theirs = program["first_gradient"], reference["first_gradient"]
    norms = {k: _norm(v) for k, v in theirs.items()}
    apart = {k: _norm(np.asarray(ours[k], np.float64) - np.asarray(theirs[k])) for k in theirs}
    floor = float(np.median(list(norms.values())))
    by_leaf = {k: apart[k] / max(norms[k], floor, 1e-30) for k in sorted(theirs)}
    worst = max(by_leaf, key=by_leaf.get)
    whole = sum(v * v for v in norms.values()) ** 0.5
    found["grad_step1_gap"] = sum(v * v for v in apart.values()) ** 0.5 / max(whole, 1e-30)
    found["grad_step1_leaf_gap"] = by_leaf[worst]
    detail["grad_step1_leaf"] = worst
    loads = np.asarray(reference["first_loads"], np.int64)
    found["expert_load_step1_gap"] = float(
        np.abs(np.asarray(program["first_loads"], np.int64) - loads).sum() / max(loads.sum(), 1)
    )
    detail["first_loads"] = {
        "program": np.asarray(program["first_loads"]).tolist(), "reference": loads.tolist(),
    }
    return compared


for _name in ("reference_model", "read_capture", "drive_first_chunk", "follow_reference", "numbers"):
    setattr(_fit, _name, globals()[_name])

# what run.py and readings.py ask of a driver
run = _fit.run
build, Stream, compare = _fit.build, _fit.Stream, _fit.compare
