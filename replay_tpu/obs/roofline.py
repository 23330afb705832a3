"""Static roofline analysis of compiled programs: memory- vs compute-bound.

MFU alone lies about fused/memory-bound programs: a head that is hard against
the HBM bandwidth wall can never reach the MXU peak, so "6.9% MFU" reads as
failure when it may be 90% of what the chip can physically deliver for that
program. The roofline model (flops ÷ bytes = arithmetic intensity, ceiling =
min(peak FLOPs, intensity × peak bandwidth)) turns the same two cost-model
numbers into the *honest* target: "achieved X% of the roofline-predicted
ceiling". PR 7's memory-wall fix was diagnosed by hand from exactly this
arithmetic by hand; this module makes the framework do it
for every compiled program — per-step fit, scan chunk, CompiledInference
buckets, the CEFused/CEFusedTP heads — from XLA's own ``cost_analysis()``
(flops, bytes accessed) and ``memory_analysis()`` (argument/output/temp
bytes), no execution required.

Import-light like :mod:`.mfu` (jax only inside :func:`analyze_program`):
drivers consult the peak tables before deciding whether jax may be imported.
The bandwidth table mirrors :data:`.mfu.PEAK_BF16_TFLOPS`; on hosts without a
table entry (CPU CI), ``REPLAY_TPU_ROOFLINE_ASSUME_KIND`` classifies against an
assumed chip and the record carries ``peak_assumed`` so arithmetic can never
read as measurement.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Mapping, Optional

from .mfu import peak_tflops, program_costs

__all__ = [
    "PEAK_HBM_GBPS",
    "analyze_costs",
    "analyze_program",
    "assumed_device_kind",
    "classify",
    "of_ceiling",
    "peak_bandwidth",
]

# peak HBM bandwidth in GB/s per chip, keyed like mfu.PEAK_BF16_TFLOPS
# (substring of jax Device.device_kind)
PEAK_HBM_GBPS = {
    "v5 lite": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
    "v4": 1228.0,
    "v3": 900.0,
    "v2": 700.0,
}


def peak_bandwidth(device_kind: str) -> Optional[float]:
    """Peak HBM GB/s for a ``jax.Device.device_kind`` string, or None for
    kinds without a table entry (CPU hosts, unknown chips)."""
    kind = (device_kind or "").lower()
    for key, peak in PEAK_HBM_GBPS.items():
        if key in kind:
            return peak
    return None


def assumed_device_kind() -> Optional[str]:
    """The chip kind CPU-smoke runs classify against (arithmetic, not
    measurement): ``REPLAY_TPU_ROOFLINE_ASSUME_KIND``."""
    return os.environ.get("REPLAY_TPU_ROOFLINE_ASSUME_KIND")


def classify(
    flops: float,
    bytes_accessed: float,
    device_kind: str,
    allow_assumed: bool = True,
) -> Optional[Dict[str, Any]]:
    """Roofline classification of one program against one chip's peaks.

    ``critical_intensity`` (flops/byte) is where the roofline's slanted and
    flat parts meet: a program below it is ``"memory"``-bound (its ceiling is
    ``intensity × bandwidth``), above it ``"compute"``-bound (ceiling = MXU
    peak). Returns None when neither the real ``device_kind`` nor an assumed
    kind has table entries, or the cost-model inputs are degenerate — an
    unclassifiable program must stay visibly unclassified, not default to a
    bound.
    """
    flops = float(flops or 0.0)
    bytes_accessed = float(bytes_accessed or 0.0)
    if flops <= 0.0 or bytes_accessed <= 0.0:
        return None
    peak_flops = peak_tflops(device_kind)
    peak_gbps = peak_bandwidth(device_kind)
    assumed = None
    if (peak_flops is None or peak_gbps is None) and allow_assumed:
        assumed = assumed_device_kind()
        if assumed:
            peak_flops = peak_tflops(assumed)
            peak_gbps = peak_bandwidth(assumed)
    if not peak_flops or not peak_gbps:
        return None
    intensity = flops / bytes_accessed
    critical = (peak_flops * 1e12) / (peak_gbps * 1e9)
    bandwidth_ceiling_tflops = intensity * peak_gbps * 1e9 / 1e12
    ceiling = min(peak_flops, bandwidth_ceiling_tflops)
    record = {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "arithmetic_intensity": intensity,
        "critical_intensity": critical,
        "bound": "memory" if intensity < critical else "compute",
        "ceiling_tflops": ceiling,
        "peak_tflops": peak_flops,
        "peak_hbm_gbps": peak_gbps,
        # the bandwidth-side step-time floor: bytes / peak bandwidth (the
        # compute-side floor is flops / peak flops; the max binds)
        "min_step_seconds": max(
            bytes_accessed / (peak_gbps * 1e9), flops / (peak_flops * 1e12)
        ),
    }
    if assumed:
        record["peak_assumed"] = assumed
    return record


def of_ceiling(tflops_per_sec: Optional[float], record: Optional[Mapping[str, Any]]) -> Optional[float]:
    """Achieved ÷ roofline-predicted ceiling — the honest MFU for programs
    whose ceiling is the bandwidth roof, not the MXU peak."""
    if record is None or tflops_per_sec is None:
        return None
    ceiling = record.get("ceiling_tflops")
    if not ceiling:
        return None
    return float(tflops_per_sec) / float(ceiling)


def analyze_program(
    jitted_fn: Any,
    *args,
    device_kind: Optional[str] = None,
    extra_flops: float = 0.0,
    extra_bytes: float = 0.0,
    mesh_shape: Optional[Mapping[str, int]] = None,
    **kwargs,
) -> Optional[Dict[str, Any]]:
    """The full static record for one compiled program: roofline + memory +
    collectives — one ``lower().compile()``, no execution.

    ``extra_flops`` / ``extra_bytes`` add work opaque to the XLA cost model
    (pallas custom calls: the CEFused head's analytic FLOPs, fwd 2·N·E·I +
    bwd 2 × 2·N·E·I, and its ``rows×items`` logits traffic that
    the kernel keeps OUT of HBM — pass the bytes it actually touches, i.e.
    the table + hidden sweeps). Returns None when the backend offers no
    analysis; partial records (memory without a roofline) degrade per-field.

    The record::

        {"roofline": classify(...) | None,
         "hbm_peak_bytes", "argument_bytes", "output_bytes", "temp_bytes",
         "collectives": {"count", "bytes", "by_op"},
         "collective_bytes"}
    """
    costs = program_costs(jitted_fn, *args, **kwargs)
    return analyze_costs(
        costs,
        device_kind=device_kind,
        extra_flops=extra_flops,
        extra_bytes=extra_bytes,
        mesh_shape=mesh_shape,
    )


def analyze_costs(
    costs: Optional[Mapping[str, Any]],
    device_kind: Optional[str] = None,
    extra_flops: float = 0.0,
    extra_bytes: float = 0.0,
    mesh_shape: Optional[Mapping[str, int]] = None,
) -> Optional[Dict[str, Any]]:
    """:func:`analyze_program` on an already-extracted
    :func:`.mfu.program_costs` / :func:`.mfu.compiled_costs` record — lets a
    caller reuse ONE compile for both the roofline and the device-time
    attribution's HLO text."""
    if costs is None:
        return None
    if device_kind is None:
        try:
            import jax

            device_kind = jax.devices()[0].device_kind
        except Exception:
            device_kind = ""
    flops = (costs.get("flops") or 0.0) + float(extra_flops)
    bytes_accessed = (costs.get("bytes_accessed") or 0.0) + float(extra_bytes)
    record: Dict[str, Any] = {
        "roofline": classify(flops, bytes_accessed, device_kind or ""),
        "flops": flops,
        "bytes_accessed": bytes_accessed,
    }
    memory = costs.get("memory") or {}
    for key in (
        "argument_bytes", "output_bytes", "temp_bytes", "generated_code_bytes",
        "alias_bytes",
    ):
        if key in memory:
            record[key] = memory[key]
    if memory:
        # the static peak estimate: everything the executable holds resident
        # at once (arguments + outputs + scratch + code). Donated/aliased
        # buffers appear in BOTH argument and output totals with the overlap
        # reported as alias bytes — subtract it or the donated train state
        # (params + optimizer moments, the bulk of a fit's footprint) counts
        # twice.
        record["hbm_peak_bytes"] = max(
            int(
                (memory.get("argument_bytes") or 0)
                + (memory.get("output_bytes") or 0)
                + (memory.get("temp_bytes") or 0)
                + (memory.get("generated_code_bytes") or 0)
                - (memory.get("alias_bytes") or 0)
            ),
            0,
        )
    hlo_text = costs.get("hlo_text")
    if hlo_text:
        from replay_tpu.parallel.introspect import (
            collective_inventory,
            summarize_collectives,
        )

        inventory = collective_inventory(hlo_text, mesh_shape=mesh_shape)
        record["collectives"] = summarize_collectives(inventory)
        record["collective_bytes"] = record["collectives"]["bytes"]
    return record
