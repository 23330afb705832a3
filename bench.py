"""Headline benchmark: SASRec training throughput on the available accelerator.

Matches BASELINE.md's reference point — the new-stack SASRec of notebook 09
(batch 512, max_sequence_length 50, hidden 64, 2 blocks, full-softmax CE over an
ML-1M-sized catalog) which sustains 11.07 it/s × 512 ≈ 5668 sequences/sec on the
reference's CPU box. Prints ONE JSON line:

    {"metric": "sasrec_train_samples_per_sec", "value": ..., "unit": "samples/sec",
     "vs_baseline": ..., "backend": "tpu", "mfu": ..., "compile_seconds": ...,
     "peak_memory_bytes": ...}

The metric/value/vs_baseline schema is frozen; observability fields are
additive (``compile_seconds`` from the trainer's CompileTracker,
``peak_memory_bytes`` from obs.MemoryMonitor — null where the backend has no
allocator stats). ``fit_samples_per_sec`` / ``fit_step_ms`` measure the real
``Trainer.fit(scan_chunk=..., device_feed=...)`` loop end-to-end (batch
stacking + H2D on the feeder thread included) and ``dispatch_gap_closed``
reports how much of the microbench-vs-dispatch gap it recovers; the
``fit_scan_chunk`` / ``fit_device_feed`` flags mark variant runs
(``REPLAY_TPU_BENCH_FIT_CHUNK`` / ``REPLAY_TPU_BENCH_DEVICE_FEED=0``) so they
cannot masquerade as the baseline. The MFU math and the peak-TFLOPs table live in
``replay_tpu.obs.mfu`` (shared with bench_suite.py and Trainer.fit telemetry).
``REPLAY_TPU_BENCH_BATCH`` / ``_SEQ_LEN`` / ``_NUM_ITEMS`` / ``_EMBEDDING_DIM``
/ ``_NUM_BLOCKS`` shrink the shape for CI smoke runs (flagged
``shape_override``).

Backend policy: one process, on the backend JAX gives it; the record carries
``platform`` / ``device_kind`` / ``device_count``. On the CPU the model runs in
float32 (bf16 is MXU-native and CPU-hostile, so a bf16 CPU number would measure
dtype emulation, not the code) and the metric is renamed
``sasrec_train_samples_per_sec_cpu_fallback``, so a CPU run can never pass for
a device number.

TPU notes: bfloat16 compute dtype (MXU-native), one jitted donated-buffer train
step reused across iterations (no retracing), device timings via
block_until_ready, MFU = achieved TFLOP/s (XLA cost model) ÷ chip bf16 peak.
"""

import json
import os
import time

import numpy as np

# the peak-TFLOPs table and cost-model FLOPs live in obs.mfu, shared with
# bench_suite.py and Trainer.fit's telemetry; obs.roofline adds the
# peak-bandwidth table and the memory/compute-bound classification
from replay_tpu.obs import MemoryMonitor
from replay_tpu.obs.mfu import mfu as _mfu, program_costs
from replay_tpu.obs.roofline import analyze_costs, bench_fields
from replay_tpu.utils.compile_cache import enable_compile_cache

_DEFAULTS = {"BATCH": 512, "SEQ_LEN": 50, "NUM_ITEMS": 3706, "EMBEDDING_DIM": 64, "NUM_BLOCKS": 2}


def _shape(name: str) -> int:
    """REPLAY_TPU_BENCH_<name> overrides the headline shape (CI smoke runs tiny
    configs); any override marks the record."""
    return int(os.environ.get(f"REPLAY_TPU_BENCH_{name}", _DEFAULTS[name]))


BATCH = _shape("BATCH")
SEQ_LEN = _shape("SEQ_LEN")
NUM_ITEMS = _shape("NUM_ITEMS")  # default: ML-1M catalog size
EMBEDDING_DIM = _shape("EMBEDDING_DIM")
NUM_BLOCKS = _shape("NUM_BLOCKS")
SHAPE_OVERRIDE = any(_shape(k) != v for k, v in _DEFAULTS.items())
BASELINE_SAMPLES_PER_SEC = 11.07 * 512  # notebook 09 cell 28 (reference CPU box)


def main() -> None:
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE, CEFused
    from replay_tpu.nn.sequential.sasrec import SasRec
    on_cpu = jax.default_backend() == "cpu"
    use_flash = os.environ.get("REPLAY_TPU_BENCH_FLASH") == "1" and not on_cpu
    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id",
            FeatureType.CATEGORICAL,
            is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            cardinality=NUM_ITEMS,
            embedding_dim=EMBEDDING_DIM,
        )
    )
    model = SasRec(
        schema=schema,
        embedding_dim=EMBEDDING_DIM,
        num_blocks=NUM_BLOCKS,
        num_heads=1,
        max_sequence_length=SEQ_LEN,
        dropout_rate=0.0,
        # REPLAY_TPU_BENCH_FLASH=1 A/Bs the pallas fused attention (TPU only)
        use_flash=use_flash,
        # f32 on CPU: a bf16 number there measures emulation, not the framework
        dtype=jnp.float32 if on_cpu else jnp.bfloat16,
    )
    # REPLAY_TPU_BENCH_FUSED_CE=1 A/Bs the pallas fused-logsumexp head
    # (ops/fused_ce.py): same math, no [B, L, I] logits in HBM
    use_fused_ce = os.environ.get("REPLAY_TPU_BENCH_FUSED_CE") == "1" and not on_cpu
    trainer = Trainer(
        model=model,
        loss=CEFused() if use_fused_ce else CE(),
        optimizer=OptimizerFactory(name="adam", learning_rate=1e-3),
        mesh=make_mesh(),
    )

    rng = np.random.default_rng(0)
    items = rng.integers(0, NUM_ITEMS, size=(BATCH, SEQ_LEN + 1)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), dtype=bool)
    batch = {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }

    state = trainer.init_state(batch)
    # warmup: compile + settle caches
    for _ in range(3):
        state, loss_value = trainer.train_step(state, batch)
    jax.block_until_ready(loss_value)

    # per-step dispatch+transfer timing (diagnostic: includes the per-step
    # host->device batch copy)
    probe_start = time.perf_counter()
    state, loss_value = trainer.train_step(state, batch)
    jax.block_until_ready(loss_value)
    probe_step = time.perf_counter() - probe_start
    dispatch_steps = max(3, min(30, int(10.0 / max(probe_step, 1e-6))))
    start = time.perf_counter()
    for _ in range(dispatch_steps):
        state, loss_value = trainer.train_step(state, batch)
    jax.block_until_ready(loss_value)
    dispatch_step_ms = (time.perf_counter() - start) / dispatch_steps * 1000

    # per-step FLOPs from XLA's own cost model of the compiled train step;
    # the pallas custom call is opaque to the cost model, so the fused head
    # adds back the analytic FLOPs it replaced (fwd 2NEI + bwd 2*2NEI).
    # The same compile feeds the static roofline (obs.roofline): memory- vs
    # compute-bound with the predicted ceiling, HBM footprint, collective
    # bytes — "achieved X% of the roofline ceiling" is the honest MFU for
    # bandwidth-bound heads.
    extra_flops = 6.0 * BATCH * SEQ_LEN * EMBEDDING_DIM * NUM_ITEMS if use_fused_ce else 0.0
    step_costs = program_costs(trainer._train_step, state, trainer._put_batch(batch))
    step_flops = None
    if step_costs and step_costs.get("flops"):
        step_flops = float(step_costs["flops"]) + extra_flops
    static_record = analyze_costs(
        step_costs,
        device_kind=jax.devices()[0].device_kind,
        extra_flops=extra_flops,
        mesh_shape={axis: int(n) for axis, n in trainer.mesh.shape.items()},
    )

    # headline: K optimizer steps per XLA dispatch (Trainer.train_steps lax.scan
    # path, same math as train_step) with the input chunk already resident on
    # device — in production the prefetcher overlaps the copy with compute
    scan_k = int(os.environ.get("REPLAY_TPU_BENCH_SCAN_K", "32"))
    chunk = [batch] * scan_k
    state, scan_losses = trainer.train_steps(state, chunk)  # compile + warmup
    stacked = jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]), *chunk)
    placed = trainer._put_stacked(stacked)
    jax.block_until_ready(placed)
    scan_fn = trainer._train_scan
    probe_start = time.perf_counter()
    state, scan_losses = scan_fn(state, placed)
    jax.block_until_ready(scan_losses)
    chunk_time = time.perf_counter() - probe_start
    n_chunks = max(2, min(20, int(20.0 / max(chunk_time, 1e-6))))
    start = time.perf_counter()
    for _ in range(n_chunks):
        state, scan_losses = scan_fn(state, placed)
    jax.block_until_ready(scan_losses)
    elapsed = time.perf_counter() - start
    steps = n_chunks * scan_k

    # end-to-end fit loop: the PRODUCTION path (Trainer.fit with scan_chunk +
    # the device-feed stage), not the hand-rolled chunk loop above — this is
    # the number that certifies the dispatch gap is closed where training
    # actually runs. Stacking + H2D happen per chunk on the feeder thread,
    # exactly as a real run pays them. REPLAY_TPU_BENCH_FIT_CHUNK /
    # _DEVICE_FEED=0 A/B the chunk size and the feed; the flags are carried in
    # the record so a variant run can never masquerade as the baseline.
    fit_chunk = int(os.environ.get("REPLAY_TPU_BENCH_FIT_CHUNK", str(scan_k)))
    use_device_feed = os.environ.get("REPLAY_TPU_BENCH_DEVICE_FEED", "1") != "0"
    # size the run from PER-STEP time (chunk_time measured a scan_k-step
    # chunk), so an overridden fit_chunk keeps the ~10s target instead of
    # scaling the timed section with the chunk size
    fit_chunk_time = chunk_time / scan_k * fit_chunk
    fit_chunks = max(2, min(10, int(10.0 / max(fit_chunk_time, 1e-6))))
    fit_steps = fit_chunks * fit_chunk
    fit_batches = [batch] * fit_steps
    # warmup pass: the scan/step programs are already compiled (same shapes);
    # this settles the feeder thread + queue path before timing
    state = trainer.fit(
        fit_batches, epochs=1, state=state, scan_chunk=fit_chunk,
        device_feed=use_device_feed, log_every=0,
    )
    start = time.perf_counter()
    state = trainer.fit(
        fit_batches, epochs=1, state=state, scan_chunk=fit_chunk,
        device_feed=use_device_feed, log_every=0,
    )
    # fit's epoch-end loss fetch already fenced the last chunk
    fit_elapsed = time.perf_counter() - start
    fit_samples_per_sec = fit_steps * BATCH / fit_elapsed
    fit_step_ms = fit_elapsed / fit_steps * 1000

    samples_per_sec = steps * BATCH / elapsed
    metric = "sasrec_train_samples_per_sec"
    if on_cpu:
        metric += "_cpu_fallback"
    record = {
        "metric": metric,
        "value": round(samples_per_sec, 1),
        "unit": "samples/sec",
        "vs_baseline": round(samples_per_sec / BASELINE_SAMPLES_PER_SEC, 3),
        "backend": jax.default_backend(),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "step_ms": round(elapsed / steps * 1000, 2),
        "dispatch_step_ms": round(dispatch_step_ms, 2),
        "scan_k": scan_k,
        # end-to-end Trainer.fit(scan_chunk=...) loop — how much of the
        # microbench-vs-dispatch gap the production loop actually closes
        # (1.0 = fit runs at the scan-path rate, 0.0 = at the per-step
        # dispatch rate; the flags distinguish variant runs from baseline)
        "fit_samples_per_sec": round(fit_samples_per_sec, 1),
        "fit_step_ms": round(fit_step_ms, 2),
        "fit_scan_chunk": fit_chunk,
        "fit_device_feed": use_device_feed,
        "dispatch_gap_closed": (
            round(
                (dispatch_step_ms - fit_step_ms)
                / (dispatch_step_ms - elapsed / steps * 1000),
                3,
            )
            if dispatch_step_ms > elapsed / steps * 1000
            else None
        ),
        # which head variants produced this number — a fused A/B run must be
        # distinguishable from the baseline
        "fused_ce": use_fused_ce,
        "flash_attention": use_flash,
        # additive observability fields (obs collectors): how long XLA spent
        # building the step/scan programs, and the per-device HBM peak
        # (null on hosts whose backend exposes no allocator stats)
        "compile_seconds": round(trainer.compile_tracker.total_compile_seconds, 2),
        "peak_memory_bytes": MemoryMonitor().peak_bytes(),
    }
    if SHAPE_OVERRIDE:
        record["shape_override"] = {
            "B": BATCH, "L": SEQ_LEN, "items": NUM_ITEMS,
            "d": EMBEDDING_DIM, "blocks": NUM_BLOCKS,
        }
    device_kind = record["device_kind"]
    tflops = None
    if step_flops:
        tflops = step_flops * steps / elapsed / 1e12
        record["tflops_per_sec"] = round(tflops, 3)
        # the cost model aggregates the whole sharded program: normalize the
        # peak by the chip count or multi-chip slices report >1.0 MFU
        utilization = _mfu(tflops, device_kind, device_count=jax.device_count())
        if utilization is not None and not on_cpu:
            record["mfu"] = round(utilization, 4)
    # static program analyses (one shaping shared with bench_suite rows):
    # HBM footprint + collective traffic + the roofline classification, and
    # achieved ÷ per-chip roofline ceiling when the rate was measured
    record.update(bench_fields(static_record, tflops, jax.device_count()))
    print(json.dumps(record))


if __name__ == "__main__":
    main()
