"""Driver of the traffic kind ``fit_loop``: the ``fit_hybrid`` driver for a looped
layer pattern (one stack of dense full-attention layers run ``total_ut_steps``
times over one set of weights, an exit gate and a loss at every exit; no expert).

Everything that is timed, warmed up, probed and compared is ``fit_hybrid.py``'s,
and through it ``fit.py``'s, reused by import: the same ``Stream``, ``ChunkClock``,
step-1 probe chunk, ``grad_step1_gap`` and ``grad_step1_leaf_gap``. This file
loads a PRIVATE instance of ``fit_hybrid.py`` (which loads its own private
``fit.py``: the other kinds' cells run the code they always ran) and sets on it
what names this model:

``GROUPS``          what the plain reference and the shape counts need besides the
                    nine keys of the ``fit`` kind: the configuration's ``layers`` /
                    ``attention`` / ``loop`` groups and ``norm_eps``.
``build``           ``fit.py``'s, with the trainer made again with
                    ``Trainer(remat_policy=program.remat_policy)``: each block
                    application is recomputed from its input on the way back. A
                    program whose model lacks a keyword of ``model_kwargs`` or
                    whose loss is not there is refused before the data is made.
``_FirstLoads``     the probe's listener reads the first step's ``exit_mass``
                    counter ([T]: the mean over the valid targets of the chance
                    that the loop stops after each step) where the expert kinds
                    read ``expert_load``; it is reported, not compared.
``probe_step1``     ``fit_hybrid``'s probe, and before it the exit distribution
                    p [T, B, L] at every position of the probe's batch, from the
                    gate logits the trainer's model sows in ``exits`` (its
                    forward alone, at the seed's weights and the trainer's
                    precision: one program, compiled in set-up), 0 where no
                    valid target is.
``numbers``         ``fit_hybrid``'s without ``expert_load_step1_gap``, and with
                    ``exit_mass_step1_gap`` = sum_t sum_pos |p_program(t, pos) -
                    p_reference(t, pos)| / valid targets (the gate's own witness
                    at step 1, per position, so that errors of opposite sign do
                    not cancel; a reference with fewer steps reads 0 at the
                    steps it lacks).

``read_capture`` reduces the traced slice under ``loss``, ``attention``,
``dense_ffn``, ``exit_gate``, ``recurrence``, ``forward``, in that order, then folds
the two layer kinds into ``recurrence`` (every op of the T passes of the stack, the
recomputed ones included) and ``recurrence`` and ``exit_gate`` into ``forward``
(``forward_rest``: what no scope claims; the embedding).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import shutil
from pathlib import Path
from typing import Any, Dict, Mapping

import numpy as np

LAYER_SCOPES = ("attention", "dense_ffn", "exit_gate", "recurrence")
STACK_SCOPES = ("attention", "dense_ffn")  # nested in `recurrence`


def _private_hybrid():
    path = Path(__file__).with_name("fit_hybrid.py")
    spec = importlib.util.spec_from_file_location("benchmark.drivers._hybrid_for_loop", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_hybrid = _private_hybrid()
_hybrid.GROUPS = ("layers", "attention", "loop", "norm_eps")
_hybrid.LAYER_SCOPES = LAYER_SCOPES
_fit = _hybrid._fit
_build, _hybrid_numbers, _probe_step1 = _fit.build, _hybrid.numbers, _hybrid.probe_step1


def build(cell, seed: int):
    program = cell.config["program"]
    # a program without the looped model (the parent of the PR that brought this
    # kind) is refused here, before the data is made
    _fit.resolve(program["loss"])
    fields = {f.name for f in dataclasses.fields(_fit.resolve(program["model"]))}
    if set(program["model_kwargs"]) - fields:
        raise SystemExit(f"benchmark: the model takes no {sorted(set(program['model_kwargs']) - fields)}")
    built = _build(cell, seed)
    policy = program.get("remat_policy")
    if policy is not None:
        built["trainer"] = dataclasses.replace(built["trainer"], remat_policy=policy)
    return built


def probe_step1(cell, built, batch, fit, steps: int) -> Dict[str, Any]:
    """``fit_hybrid``'s probe, with the program's p per position (module docstring)."""
    import jax

    from replay_tpu.nn.loss import exit_distribution

    trainer = built["trainer"]
    params = _fit.to_program_tree(built["make_weights"](built["weights_key"]), built["param_paths"])

    def gate_logits(params, inputs):
        _, sown = trainer.model.apply({"params": params}, mutable=["exits"], **inputs)
        return sown["exits"]["gate_logits"]

    logits = jax.jit(trainer._scoped(gate_logits))(params, trainer._forward_kwargs(batch))
    p = np.asarray(jax.device_get(exit_distribution(logits)), np.float64)
    del params, logits
    targets = _fit.reference_batch(batch, cell.config["program"]["item_feature"])
    valid = targets["target_mask"] & targets["valid"][:, None]
    probe = _probe_step1(cell, built, batch, fit, steps)
    return {**probe, "first_mass": probe["first_loads"], "first_loads": p * valid}


class _FirstMass:
    """The probe's sink: the first step's ``exit_mass`` (kept as ``loads``, the
    name ``fit_hybrid.probe_step1`` reads)."""

    def __init__(self):
        self.loads = None

    def log_event(self, event) -> None:
        if event.event == "on_train_step" and self.loads is None:
            self.loads = np.asarray(event.payload["counters"]["exit_mass"], np.float64)


def numbers(program: Mapping[str, Any], reference: Mapping[str, Any]) -> Dict[str, Any]:
    # p [T, B, L] at the valid targets, 0 elsewhere: it sums to their number
    p = {who: np.asarray(side["first_loads"], np.float64) for who, side in
         (("program", program), ("reference", reference))}
    none = np.zeros(1, np.int64)
    compared = _hybrid_numbers({**program, "first_loads": none}, {**reference, "first_loads": none})
    del compared["numbers"]["expert_load_step1_gap"], compared["detail"]["first_loads"]
    steps = max(len(m) for m in p.values())
    padded = {who: np.pad(m, [(0, steps - len(m))] + [(0, 0)] * (m.ndim - 1)) for who, m in p.items()}
    targets = max(float(p["reference"].sum()), 1.0)
    compared["numbers"]["exit_mass_step1_gap"] = float(
        np.abs(padded["program"] - padded["reference"]).sum() / targets
    )
    mass = {who: m.sum(axis=(1, 2)) / targets for who, m in p.items()}
    if "first_mass" in program:  # the timed program's own counter
        mass["program"] = np.asarray(program["first_mass"], np.float64)
    compared["detail"]["first_exit_mass"] = {who: m.tolist() for who, m in mass.items()}
    return compared


def read_capture(cell, context) -> Dict[str, Any]:
    """The traced slice reduced to device numbers; the capture is then removed."""
    from benchmark import tracing

    events = tracing.load_events(tracing.find_xplane(context["capture_dir"]))
    traced = tracing.reduce_capture(
        events, context["scan_program"], ("loss",) + LAYER_SCOPES + ("forward",), cell.chips,
        tracing.op_paths_from_hlo(context["hlo_text"]),
    )
    scope_s = traced["scope_s"]
    scope_s["recurrence"] += sum(scope_s[kind] for kind in STACK_SCOPES)
    scope_s["forward_rest"] = scope_s["forward"]
    scope_s["forward"] += scope_s["recurrence"] + scope_s["exit_gate"]
    traced["steps"] = traced["runs"] * context["scan_chunk"]
    if not cell.keep_capture:
        shutil.rmtree(context["capture_dir"], ignore_errors=True)
    return traced


_hybrid._FirstLoads, _hybrid.probe_step1 = _FirstMass, probe_step1
for _name in ("build", "numbers", "read_capture"):
    setattr(_fit, _name, globals()[_name])
_hybrid.numbers = numbers

# what run.py, readings.py and the tests ask of a driver
run, Stream, compare = _fit.run, _fit.Stream, _fit.compare
reference_model = _hybrid.reference_model
drive_first_chunk, follow_reference = _hybrid.drive_first_chunk, _hybrid.follow_reference
reference_step1 = _hybrid.reference_step1
