"""Roofline analysis (obs.roofline): peak tables, classification math, the
compiled-program record, the MemoryMonitor chunk-boundary sampling hook, and
the profiled fit (``fit(profile_steps=...)``: the capture it writes and the
``roofline`` payload on ``on_fit_end`` — the run_logs/profile_smoke artifact
CI renders and uploads).

Core tier is pure arithmetic (no jax): bandwidth lookups, memory- vs
compute-bound classification against real and assumed chips, the ceiling
formula, degradation to None for unclassifiable inputs. The jax tier runs
``analyze_program`` / ``Trainer.analyze_programs`` on real compiled programs
and checks the memory/collective fields, and verifies the scan-chunked fit
samples device memory at chunk boundaries (CPU-safe no-op).
"""

import glob
import json
import os

import numpy as np
import pytest

from replay_tpu.obs import MemoryMonitor
from replay_tpu.obs.mfu import peak_tflops
from replay_tpu.obs.roofline import (
    PEAK_HBM_GBPS,
    classify,
    of_ceiling,
    peak_bandwidth,
)


# --------------------------------------------------------------------------- #
# core: tables + classification arithmetic
# --------------------------------------------------------------------------- #
@pytest.mark.core
def test_peak_bandwidth_table_mirrors_flops_table_keys():
    from replay_tpu.obs.mfu import PEAK_BF16_TFLOPS

    assert set(PEAK_HBM_GBPS) == set(PEAK_BF16_TFLOPS)
    assert peak_bandwidth("TPU v5 lite") == 819.0
    assert peak_bandwidth("TPU v5p chip") == 2765.0
    assert peak_bandwidth("cpu") is None
    assert peak_bandwidth("") is None


@pytest.mark.core
def test_classify_memory_vs_compute_bound():
    # v5e: critical intensity = 197e12 / 819e9 ~ 240.5 flops/byte
    low = classify(flops=1e9, bytes_accessed=1e9, device_kind="TPU v5e")  # 1 flop/B
    assert low["bound"] == "memory"
    assert low["ceiling_tflops"] == pytest.approx(819e9 * 1.0 / 1e12)
    assert low["min_step_seconds"] == pytest.approx(1e9 / 819e9)

    high = classify(flops=1000e9, bytes_accessed=1e9, device_kind="TPU v5e")
    assert high["bound"] == "compute"
    assert high["ceiling_tflops"] == pytest.approx(197.0)
    assert high["critical_intensity"] == pytest.approx(197e12 / 819e9)


@pytest.mark.core
def test_classify_unknown_chip_uses_assumed_kind_and_flags_it(monkeypatch):
    monkeypatch.delenv("REPLAY_TPU_ROOFLINE_ASSUME_KIND", raising=False)
    assert classify(1e9, 1e9, "cpu") is None  # no peaks, no assumption -> None
    monkeypatch.setenv("REPLAY_TPU_ROOFLINE_ASSUME_KIND", "v5e")
    record = classify(1e9, 1e9, "cpu")
    assert record["bound"] == "memory"
    assert record["peak_assumed"] == "v5e"
    # a REAL chip kind never carries the assumed flag
    real = classify(1e9, 1e9, "TPU v4")
    assert "peak_assumed" not in real
    assert real["peak_tflops"] == peak_tflops("TPU v4")


@pytest.mark.core
def test_classify_degenerate_inputs_return_none():
    assert classify(0.0, 1e9, "TPU v5e") is None
    assert classify(1e9, 0.0, "TPU v5e") is None
    assert classify(None, None, "TPU v5e") is None


@pytest.mark.core
def test_of_ceiling():
    record = classify(1e9, 1e9, "TPU v5e")
    assert of_ceiling(record["ceiling_tflops"] / 2, record) == pytest.approx(0.5)
    assert of_ceiling(None, record) is None
    assert of_ceiling(1.0, None) is None


# --------------------------------------------------------------------------- #
# core: MemoryMonitor chunk-boundary sampling (fake devices, no jax)
# --------------------------------------------------------------------------- #
class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats

    def __str__(self):
        return f"fake:{id(self)}"


@pytest.mark.core
def test_memory_monitor_observe_tracks_windowed_peak():
    device = _FakeDevice({"peak_bytes_in_use": 100, "bytes_in_use": 50})
    monitor = MemoryMonitor(devices=[device])
    assert monitor.observe() == 100
    device._stats = {"peak_bytes_in_use": 300}
    assert monitor.observe() == 300
    device._stats = {"peak_bytes_in_use": 200}  # peak never regresses
    assert monitor.observe() == 200
    assert monitor.observed_peak_bytes == 300
    assert monitor.observed_samples == 3


@pytest.mark.core
def test_memory_monitor_observe_is_a_noop_without_allocator_stats():
    monitor = MemoryMonitor(devices=[_FakeDevice(None)])
    assert monitor.observe() is None
    assert monitor.observed_peak_bytes is None
    assert monitor.observed_samples == 0


# --------------------------------------------------------------------------- #
# jax tier: compiled-program records + the fit sampling hook
# --------------------------------------------------------------------------- #
PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def _tiny_trainer(own_programs=False, num_items=50, seq_len=8, dim=16):
    """The module's one tiny trainer; all but ``own_programs`` share their two
    programs (traced and lowered once a module)."""
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.sasrec import SasRec

    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID, cardinality=num_items,
            embedding_dim=dim,
        )
    )
    model = SasRec(schema=schema, embedding_dim=dim, num_blocks=1, num_heads=1,
                   max_sequence_length=seq_len)
    trainer = Trainer(model=model, loss=CE(),
                      optimizer=OptimizerFactory(learning_rate=1e-2), mesh=make_mesh())
    return PROGRAMS.share_init(trainer) if own_programs else PROGRAMS.adopt(trainer)


def _tiny_batches(n, num_items=50, seq_len=8, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        items = rng.integers(0, num_items, size=(batch, seq_len + 1)).astype(np.int32)
        mask = np.ones((batch, seq_len), dtype=bool)
        out.append({
            "feature_tensors": {"item_id": items[:, :-1]},
            "padding_mask": mask,
            "positive_labels": items[:, 1:, None],
            "target_padding_mask": mask[:, :, None],
        })
    return out


@pytest.mark.jax
def test_analyze_program_on_compiled_matmul(monkeypatch):
    import jax
    import jax.numpy as jnp

    from replay_tpu.obs.roofline import analyze_program

    monkeypatch.setenv("REPLAY_TPU_ROOFLINE_ASSUME_KIND", "v5e")
    jitted = jax.jit(lambda a, b: jnp.tanh(a @ b).sum())
    record = analyze_program(jitted, jnp.ones((64, 32)), jnp.ones((32, 32)))
    assert record is not None
    assert record["hbm_peak_bytes"] >= record["argument_bytes"]
    assert record["collective_bytes"] == 0  # single-program, no mesh
    classification = record["roofline"]
    assert classification is not None and classification["bound"] in ("memory", "compute")
    # extra_flops shifts the intensity (the pallas-opacity compensation path)
    boosted = analyze_program(
        jitted, jnp.ones((64, 32)), jnp.ones((32, 32)), extra_flops=1e12
    )
    assert (
        boosted["roofline"]["arithmetic_intensity"]
        > classification["arithmetic_intensity"]
    )


@pytest.mark.jax
@pytest.mark.smoke
def test_chunked_fit_samples_memory_at_chunk_boundaries(monkeypatch):
    """The scan fit path calls MemoryMonitor.observe() once per chunk —
    verified through a recording stand-in (CPU reports no allocator stats, so
    the real observe is a no-op there by design)."""
    import replay_tpu.nn.train as train_module

    observed = []

    class RecordingMonitor(MemoryMonitor):
        def observe(self):
            observed.append(True)
            return super().observe()

    monkeypatch.setattr(train_module, "MemoryMonitor", RecordingMonitor)
    trainer = _tiny_trainer()
    trainer.fit(_tiny_batches(5), epochs=1, log_every=0, scan_chunk=2)
    # 5 batches at K=2 -> two scan chunks (the tail runs per-step)
    assert len(observed) == 2


@pytest.mark.jax
def test_compiled_inference_roofline_per_bucket(monkeypatch):
    from replay_tpu.nn.compiled import CompiledInference

    monkeypatch.setenv("REPLAY_TPU_ROOFLINE_ASSUME_KIND", "v5e")
    trainer = _tiny_trainer()
    batch = _tiny_batches(1)[0]
    state = trainer.init_state(batch)
    compiled = CompiledInference.compile(
        trainer.model, state.params, max_sequence_length=8,
        mode="dynamic_batch_size", dynamic_buckets=(1, 8),
    )
    records = compiled.roofline()
    assert set(records) == {1, 8}
    for record in records.values():
        assert record["hbm_peak_bytes"] > 0
        assert record["roofline"]["bound"] in ("memory", "compute")


# --------------------------------------------------------------------------- #
# the profiled fit end-to-end (CI's profile_smoke artifact)
# --------------------------------------------------------------------------- #
def _captures(profile_dir):
    """The ``*.xplane.pb`` files ``jax.profiler`` wrote under ``profile_dir``."""
    return glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb"))


@pytest.mark.jax
@pytest.mark.smoke
def test_profiled_fit_writes_capture_and_roofline(tmp_path, monkeypatch):
    from replay_tpu.obs import JsonlLogger

    # classify against an assumed chip on the CPU mesh (arithmetic, flagged)
    monkeypatch.setenv("REPLAY_TPU_ROOFLINE_ASSUME_KIND", "v5e")
    trainer = _tiny_trainer()
    batches = _tiny_batches(5)
    base = os.environ.get("REPLAY_TPU_RUN_DIR")
    run_dir = os.path.join(base, "profile_smoke") if base else str(tmp_path / "profile_smoke")
    # mode="w": REPLAY_TPU_RUN_DIR is a fixed path in CI — re-runs must not append
    with JsonlLogger(run_dir, mode="w") as sink:
        trainer.fit(batches, epochs=1, loggers=sink, log_every=0,
                    profile_steps=(1, 4), scan_chunk=2)

    assert _captures(os.path.join(run_dir, "profile")), "no capture under run_dir/profile"

    events = [json.loads(line) for line in open(os.path.join(run_dir, "events.jsonl"))]
    fit_end = [e for e in events if e["event"] == "on_fit_end"][-1]
    # the roofline payload rides the terminal event: both dispatched programs
    # classified, the full-CE step memory-bound under the assumed v5e peaks
    roofline = fit_end["roofline"]
    assert {"train_step", "train_scan"} <= set(roofline)
    for record in roofline.values():
        assert record["hbm_peak_bytes"] > 0
        classification = record["roofline"]
        assert classification["bound"] == "memory"
        assert classification["peak_assumed"] == "v5e"
        assert 0.0 < classification["ceiling_tflops"] <= classification["peak_tflops"]


@pytest.mark.jax
def test_profiled_per_step_fit_window(tmp_path):
    """The per-step (unchunked) path: window [1, 3) opens/closes inside the
    fit and leaves a capture; a window that is not [start, stop) raises."""
    trainer = _tiny_trainer()
    batches = _tiny_batches(4)
    profile_dir = str(tmp_path / "prof")
    trainer.fit(batches, epochs=1, log_every=0, profile_steps=(1, 3),
                profile_dir=profile_dir)
    assert _captures(profile_dir)
    with pytest.raises(ValueError, match=r"valid \[start, stop\) window"):
        trainer.fit(batches, epochs=1, log_every=0, profile_steps=(3, 1),
                    profile_dir=profile_dir)


@pytest.mark.jax
def test_analyze_programs_and_lowered_hlo_roundtrip():
    trainer = _tiny_trainer(own_programs=True)  # what IT dispatched is asserted
    batches = _tiny_batches(1)
    state = trainer.init_state(batches[0])
    trainer.train_step(state, batches[0])
    hlo = trainer.lowered_hlo("train_step")
    assert "op_name" in hlo  # the scope metadata survives compilation
    with pytest.raises(KeyError):
        trainer.lowered_hlo("train_scan")  # never dispatched
    records = trainer.analyze_programs()
    assert "train_step" in records
    assert records["train_step"]["hbm_peak_bytes"] > 0
    assert records["train_step"]["collectives"]["count"] >= 0
