"""The precision ladder: mixed-precision policies for the production fit.

Beyond-parity (ROADMAP item 5): the reference trains f32 end-to-end; every
roofline row PR 8 produced classifies the big heads memory-bound, and the cure
for bandwidth-bound is fewer bytes ("Demystifying BERT"'s accelerator/precision
analysis, TurboGR's reduced-precision training-acceleration framing —
PAPERS.md). This module makes reduced precision a sanctioned, *tested* config
instead of a folk remedy:

* **bf16 rung** — bfloat16 activations and compute, float32 master parameters
  and optimizer state (flax's ``param_dtype`` default), float32 loss/metric
  accumulation. bf16 shares f32's exponent range, so the policy is
  LOSS-SCALE-FREE on TPU (no GradScaler analog — a deliberate non-feature).
  Gradients are taken with respect to the f32 master params, so the optimizer
  state and the non-finite sentinel's arithmetic stay f32 untouched.
* **f32 rung** — the identity policy; applying it never changes a program.

The policy is applied through the models' existing ``dtype`` fields
(``replay_tpu/nn/embedding.py`` / attention / ffn — flax compute-dtype
convention): :meth:`Precision.apply_to_model` clones the module with
``dtype=compute_dtype``; parameters stay ``float32`` because ``param_dtype``
is never touched. The trainer additionally wraps the loss's
``logits_callback`` so candidate-shaped logits (a bf16 × bf16 einsum that
would otherwise stay bf16) are accumulated in ``accum_dtype`` — full-catalog
logits already promote to f32 through the f32 item table, and ``CEFused`` /
``CEFusedTP`` accumulate f32 inside the kernel (the sanctioned
bf16-compute/f32-param split their dtype check names).

Parity is gated, never assumed: :func:`fit_parity_record` compares an f32 and
a reduced-precision fit of the SAME data/seed at the PARITY_REPORT-style
relative threshold (the committed cross-framework gate runs at 10% on the
final eval metric; see PARITY_REPORT.md) and keeps both loss curves in the
record. bf16-vs-f32 parity is a tolerance claim, NEVER a bitwise one.

The serving rung of the ladder (int8 post-training quantization of the item
table for MIPS retrieval) lives in :mod:`replay_tpu.serve.quant`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Mapping, Optional, Sequence

__all__ = [
    "PARITY_REL_TOL",
    "Precision",
    "fit_parity_record",
]

# the PARITY_REPORT-style relative tolerance on the gated eval metric: the
# committed cross-framework parity gate runs at 10% relative on final ndcg@10
# (PARITY_REPORT.md; examples/reference_parity.py --tolerance 0.10). The
# bf16-vs-f32 gate reuses the same yardstick — in practice the observed gap is
# far smaller, but the CLAIM is tolerance-parity, never bitwise.
PARITY_REL_TOL = 0.10


@dataclass(frozen=True)
class Precision:
    """One rung of the precision ladder: compute/param/accumulation dtypes.

    ``compute_dtype`` flows into the models' flax ``dtype`` fields
    (activations, attention, ffn compute); ``param_dtype`` is the master-
    parameter dtype (always f32 here — flax's default ``param_dtype`` is never
    overridden, so optimizer moments stay f32 too); ``accum_dtype`` is what
    loss terms and epoch metrics accumulate in. Resolve by name via
    :meth:`resolve` (``Trainer(precision="bf16")``) or construct directly.
    ``None`` dtype fields default to float32 at construction (lazy jax
    import: drivers may import this module before deciding whether jax may be
    imported at all).
    """

    name: str = "f32"
    compute_dtype: Any = None
    param_dtype: Any = None
    accum_dtype: Any = None

    def __post_init__(self) -> None:
        import jax.numpy as jnp

        for attr in ("compute_dtype", "param_dtype", "accum_dtype"):
            if getattr(self, attr) is None:
                object.__setattr__(self, attr, jnp.float32)

    @classmethod
    def f32(cls) -> "Precision":
        return cls(name="f32")

    @classmethod
    def bf16(cls) -> "Precision":
        import jax.numpy as jnp

        return cls(name="bf16", compute_dtype=jnp.bfloat16)

    @classmethod
    def resolve(cls, spec: Any) -> Optional["Precision"]:
        """``None`` | ``"f32"`` | ``"bf16"`` | a :class:`Precision` → policy.

        ``None`` stays ``None`` (the trainer then touches nothing — the
        pre-precision programs lower byte-identical).
        """
        if spec is None or isinstance(spec, cls):
            return spec
        if isinstance(spec, str):
            by_name = {"f32": cls.f32, "float32": cls.f32, "bf16": cls.bf16,
                       "bfloat16": cls.bf16}
            if spec.lower() in by_name:
                return by_name[spec.lower()]()
            msg = (
                f"Unknown precision {spec!r}; use one of "
                f"{sorted(set(by_name))} or pass a Precision instance"
            )
            raise ValueError(msg)
        msg = f"precision must be None, a name string or a Precision, got {type(spec).__name__}"
        raise TypeError(msg)

    # -- model application -------------------------------------------------- #
    @property
    def is_identity(self) -> bool:
        import jax.numpy as jnp

        return (
            jnp.dtype(self.compute_dtype) == jnp.dtype(jnp.float32)
            and jnp.dtype(self.param_dtype) == jnp.dtype(jnp.float32)
        )

    def apply_to_model(self, model: Any) -> Any:
        """Clone ``model`` with its flax compute ``dtype`` set to this rung.

        The identity rung returns the model unchanged (no clone, no retrace
        risk). A non-identity rung applied to a module without a ``dtype``
        field is an error at construction time, not a silent f32 run.
        """
        import jax.numpy as jnp

        if self.is_identity:
            return model
        if not hasattr(model, "dtype"):
            msg = (
                f"Precision('{self.name}') needs a flax compute-dtype knob, but "
                f"{type(model).__name__} defines no `dtype` field. Add one "
                "(the SasRec/Bert4Rec/TwoTower convention: activations in "
                "`dtype`, params in float32) or drop the precision policy."
            )
            raise ValueError(msg)
        if jnp.dtype(model.dtype) == jnp.dtype(self.compute_dtype):
            return model
        return model.clone(dtype=self.compute_dtype)

    # -- loss-side accumulation --------------------------------------------- #
    @property
    def casts_logits(self) -> bool:
        """Whether loss-consumed logits need an explicit up-cast: candidate-
        shaped logits are a narrow × narrow einsum under a narrow compute
        dtype and would otherwise accumulate in bf16."""
        import jax.numpy as jnp

        return jnp.dtype(self.compute_dtype) != jnp.dtype(self.accum_dtype)

    def wrap_logits_callback(self, callback: Callable) -> Callable:
        """``logits_callback`` → same callback with outputs cast to
        ``accum_dtype`` (an identity no-op for already-f32 logits, e.g. the
        full-catalog path promoted through the f32 item table)."""
        accum = self.accum_dtype

        def cast_logits(*args, **kwargs):
            return callback(*args, **kwargs).astype(accum)

        return cast_logits

    def describe(self) -> Dict[str, str]:
        """Flat record for events."""
        import jax.numpy as jnp

        return {
            "precision": self.name,
            "compute_dtype": jnp.dtype(self.compute_dtype).name,
            "param_dtype": jnp.dtype(self.param_dtype).name,
            "accum_dtype": jnp.dtype(self.accum_dtype).name,
        }


def _metric_series(history: Sequence[Mapping[str, Any]], metric: str):
    return [
        float(record[metric])
        for record in history
        if metric in record and isinstance(record[metric], (int, float))
    ]


def fit_parity_record(
    baseline_history: Sequence[Mapping[str, Any]],
    candidate_history: Sequence[Mapping[str, Any]],
    metric: str = "ndcg@10",
    rel_tol: float = PARITY_REL_TOL,
    baseline_name: str = "f32",
    candidate_name: str = "bf16",
) -> Dict[str, Any]:
    """The fit-parity gate record: candidate vs baseline ``Trainer.history``.

    Same data, same seed, two precisions: the gate passes when the FINAL
    ``metric`` value agrees within ``rel_tol`` relative (the PARITY_REPORT
    yardstick) and both values are finite. Loss curves (``train_loss`` per
    epoch) ride the record for forensics — tracked, never gated bitwise.
    Raises ``KeyError`` when the metric never appears (a gate that silently
    passes on a missing metric would be worse than no gate).
    """
    base_series = _metric_series(baseline_history, metric)
    cand_series = _metric_series(candidate_history, metric)
    if not base_series or not cand_series:
        msg = (
            f"fit_parity_record: metric {metric!r} absent from "
            f"{'baseline' if not base_series else 'candidate'} history"
        )
        raise KeyError(msg)
    base_final, cand_final = base_series[-1], cand_series[-1]
    finite = math.isfinite(base_final) and math.isfinite(cand_final)
    denom = max(abs(base_final), 1e-12)
    rel_gap = abs(cand_final - base_final) / denom
    return {
        "metric": metric,
        baseline_name: base_final,
        candidate_name: cand_final,
        "rel_gap": rel_gap,
        "tolerance": rel_tol,
        "passed": bool(finite and rel_gap <= rel_tol),
        f"loss_curve_{baseline_name}": _metric_series(baseline_history, "train_loss"),
        f"loss_curve_{candidate_name}": _metric_series(candidate_history, "train_loss"),
    }
