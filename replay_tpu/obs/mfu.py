"""Model-FLOPs-utilization: the XLA cost model and the peak-TFLOPs table.

The single home of the peak dense-bf16 throughput table and the cost-model
FLOPs extraction ("Demystifying BERT" argues MFU belongs in every run record,
not in one-off scripts — PAPERS.md). Import-light on purpose (no jax at import).
"""

from __future__ import annotations

from typing import Any, Optional

# peak dense bf16 TFLOP/s per chip, keyed by substring of jax Device.device_kind
PEAK_BF16_TFLOPS = {
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
    "v4": 275.0,
    "v3": 123.0,
    "v2": 46.0,
}


def peak_tflops(device_kind: str) -> Optional[float]:
    """Peak dense bf16 TFLOP/s for a ``jax.Device.device_kind`` string, or
    None for kinds without a table entry (CPU hosts, unknown chips)."""
    kind = (device_kind or "").lower()
    for key, peak in PEAK_BF16_TFLOPS.items():
        if key in kind:
            return peak
    return None


def cost_analysis(jitted_fn: Any, *args, **kwargs) -> Optional[dict]:
    """XLA's cost analysis of ``jitted_fn`` compiled for ``args`` (a dict), or
    None when the backend offers no analysis."""
    try:
        return jitted_fn.lower(*args, **kwargs).compile().cost_analysis()
    except Exception:  # best-effort across backends
        return None


def program_costs(jitted_fn: Any, *args, **kwargs) -> Optional[dict]:
    """Everything the static analyses say about one compiled program, from a
    single ``lower().compile()``: the cost model's ``flops`` / ``bytes
    accessed`` / ``transcendentals``, ``memory_analysis()``'s argument/output/
    temp/code byte sizes, and the optimized HLO text (the input to the
    collective inventory, :mod:`replay_tpu.parallel.introspect`). Fields
    degrade to absence where a backend offers no analysis; returns None only
    when compilation itself is unavailable. ``obs.roofline.analyze_program``
    builds the bound-ness classification on top of this record.
    """
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
    except Exception:  # best-effort across backends
        return None
    return compiled_costs(compiled)


def compiled_costs(compiled: Any) -> Optional[dict]:
    """:func:`program_costs` for an ALREADY-compiled ``jax.stages.Compiled``
    (AOT executables like CompiledInference buckets — no re-lowering)."""
    record: dict = {}
    try:
        analysis = compiled.cost_analysis()
        if analysis:
            record["flops"] = float(analysis.get("flops", 0.0)) or None
            record["bytes_accessed"] = float(analysis.get("bytes accessed", 0.0)) or None
            if "transcendentals" in analysis:
                record["transcendentals"] = float(analysis["transcendentals"])
    except Exception:
        pass
    try:
        memory = compiled.memory_analysis()
        if memory is not None:
            record["memory"] = {
                "argument_bytes": int(getattr(memory, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(memory, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(memory, "temp_size_in_bytes", 0)),
                "alias_bytes": int(getattr(memory, "alias_size_in_bytes", 0)),
                "generated_code_bytes": int(
                    getattr(memory, "generated_code_size_in_bytes", 0)
                ),
            }
    except Exception:
        pass
    try:
        record["hlo_text"] = compiled.as_text()
    except Exception:
        pass
    return record or None


def flops_per_step(jitted_fn: Any, *args, extra_flops: float = 0.0, **kwargs) -> Optional[float]:
    """Per-call FLOPs of a compiled step from the XLA cost model.

    ``extra_flops`` adds work the cost model cannot see — pallas custom calls
    are opaque to it, so callers add the analytic FLOPs of the kernel they
    fused (e.g. the CEFused head: fwd 2NEI + bwd 2·2NEI).
    """
    analysis = cost_analysis(jitted_fn, *args, **kwargs)
    if not analysis or "flops" not in analysis:
        return None
    flops = float(analysis["flops"])
    if flops <= 0:
        return None
    return flops + float(extra_flops)


def mfu(tflops_per_sec: float, device_kind: str, device_count: int = 1) -> Optional[float]:
    """Achieved ÷ peak TFLOP/s over ``device_count`` chips, or None when the
    chip kind has no peak entry (an MFU against an unknown peak is noise)."""
    peak = peak_tflops(device_kind)
    if not peak or device_count < 1:
        return None
    return float(tflops_per_sec) / (peak * device_count)
