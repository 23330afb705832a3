"""North-star benchmark suite: every BASELINE.json config on the live backend.

The one-row headline lives in ``bench.py`` (the driver contract). This suite
produces the full measurement batch the round-4 verdict asked for:

- ``sasrec_ref``       — notebook-09 config (B512 L50 d64, 3706 items), CE.
- ``sasrec_ref_fused`` — same with the pallas fused-logsumexp head (A/B).
- ``sasrec_27k``       — ML-20M-scale catalog (27k items, d128), CE.
- ``sasrec_27k_fused`` — fused head at 27k (where tile-wise logsumexp earns it).
- ``sasrec_100k``      — 100k-item catalog; plain CE materializes a [25600,
  100k] logits tensor (~5 GB bf16 + backward) and may legitimately OOM — that
  outcome is recorded, it is the fused head's reason to exist.
- ``sasrec_100k_fused``
- ``sasrec_100k_sce``  — SCE (bucketed hard-negative mining, the reference's
  scalable loss) at the 100k catalog: the approximate-loss alternative to
  CEFused's exact logsumexp (not numerically comparable to the CE rows).
- ``bert4rec``         — notebook-10 config (L100 d300 h4, MLM masking).
- ``twotower``         — notebook-15 config (d64 L50, in-batch negatives), at
  B512 (the notebook's B32 is a CPU-host artifact; recorded in the row).
- ``pipeline_e2e``     — parquet on disk → ParquetBatcher → transforms →
  prefetch → chunked ``train_steps``: the production input path, measured
  end-to-end against the device-resident number (ref thread-tuning note,
  replay/data/nn/parquet/parquet_dataset.py:49-52).
- ``stream_{inmem,parquet,packed}`` — the streaming-input family
  (docs/performance.md "Feeding the beast"): the same ragged data through the
  fixed-shape in-memory batcher, the row-group-sharded out-of-core parquet
  reader (read-ahead + memory budget), and first-fit sequence packing with
  segment masks. Rows report ``effective_tokens_per_sec`` (real tokens/s) and
  ``padding_fraction``; ``obs.report --compare`` gates packed ≥ unpacked.
- ``attention_long``   — tiled flash kernel (ops/flash_tiled.py) vs XLA full
  attention at L=4096, fwd+bwd: the single-chip long-context A/B.
- ``attention_long_sp`` — ring attention (sequence sharded over all chips,
  ppermute KV rotation) vs single-device full attention at L=4096: the
  multi-chip half of the long-context A/B, with the exactness check inline.
- ``sasrec_l1024`` / ``sasrec_l1024_tiled`` — the full MODEL at L=1024
  (fused-CE head): default attention vs use_flash='tiled' end-to-end.
- ``sasrec_l1024_sp_remat_{off,on}`` — the full MODEL at L=1024 through the
  DP×TP×SP production fit (ONE rule table: ring attention over ``seq``,
  CEFusedTP catalog over ``model``, rows over ``data``), A/B'ing
  ``Trainer(remat_policy="dots")``. The claim: remat-on strictly lowers
  ``hbm_peak_bytes`` at held math; ``obs.report`` renders the pair and
  ``--compare`` gates it lower-better.
- ``prec_{f32,bf16}_{ce,fused,tp}`` — the precision-ladder family
  (docs/performance.md "The precision ladder"): the SAME 27k-catalog shape per
  head, f32 vs the sanctioned ``Trainer(precision="bf16")`` policy (bf16
  compute, f32 master params/optimizer/loss accumulation). The claim each
  pair must support: strictly lower ``hbm_peak_bytes`` and a moved roofline
  (``of_roofline_ceiling``), not just step_ms — ``obs.report --compare``
  gates the ``prec_*`` rows' ``hbm_peak_bytes`` lower-better.
- ``scale_{27k,100k,1m}_{ce,fused,tp,sce,gbce}`` — the catalog-scaling family
  (docs/performance.md "Breaking the memory wall"): step time vs catalog size
  at 27,278 / 100,000 / 1,000,000 items for plain CE (the memory wall — the
  1M row is EXPECTED to OOM and record the error), the fused-logsumexp head,
  the TP vocab-sharded fused head, SCE and gBCE. Each fused/TP row adds the
  head's analytic FLOPs (obs.mfu.fused_ce_flops — pallas calls are opaque to
  the XLA cost model) so the per-variant MFU stays an honest cross-variant
  number. The memory-wall claim is "near-flat step time 27k → 1M" for the
  fused/TP/SCE/gBCE heads.

Usage (on the backend JAX gives it):
    python bench_suite.py [--rows row1,row2] [--quick] [--out BENCH_SUITE.json]

``--quick`` shrinks every row to toy shapes on CPU — a script-correctness
smoke, not a measurement. ``REPLAY_TPU_BENCH_ASSUME_KIND=v5e`` additionally
computes the MFU arithmetic against that chip's peak on CPU quick runs (CI
exercises the accounting path; the record carries ``mfu_peak_assumed`` so it
can never be mistaken for a measurement).
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

from bench import _git_rev
from replay_tpu.obs import JsonlLogger, MemoryMonitor
from replay_tpu.obs.mfu import mfu as _mfu, program_costs
from replay_tpu.obs.roofline import analyze_costs, bench_fields

REPO = os.path.dirname(os.path.abspath(__file__))


# --------------------------------------------------------------------------- #
# shared measurement core
# --------------------------------------------------------------------------- #
def measure(trainer, batch, label, scan_k=16, extra_flops_per_step=0.0, meta=None):
    """Warm up, then time K-step scan chunks with device-resident inputs.

    Returns the record dict (never raises: an OOM/compile failure becomes a
    ``{"error": ...}`` row — for the 100k plain-CE case that IS the result).
    """
    import jax

    # device peak_bytes_in_use is a process-lifetime high-water mark and the
    # suite runs rows sequentially: only report it for rows that RAISED it,
    # so no row inherits a bigger predecessor's peak
    monitor = MemoryMonitor()
    peak_before = monitor.peak_bytes()
    try:
        state = trainer.init_state(batch)
        for _ in range(2):
            state, loss_value = trainer.train_step(state, batch)
        jax.block_until_ready(loss_value)

        t0 = time.perf_counter()
        state, loss_value = trainer.train_step(state, batch)
        jax.block_until_ready(loss_value)
        dispatch_step = time.perf_counter() - t0

        # one lower+compile feeds the per-step FLOPs AND the static roofline
        # (obs.roofline): bound-ness, predicted ceiling, HBM footprint and
        # collective bytes ride every row next to the measured rates
        step_costs = program_costs(trainer._train_step, state, trainer._put_batch(batch))
        step_flops = None
        if step_costs and step_costs.get("flops"):
            step_flops = float(step_costs["flops"]) + float(extra_flops_per_step)
        static_record = analyze_costs(
            step_costs,
            device_kind=jax.devices()[0].device_kind,
            extra_flops=extra_flops_per_step,
            mesh_shape={axis: int(n) for axis, n in trainer.mesh.shape.items()},
        )

        chunk = [batch] * scan_k
        state, _ = trainer.train_steps(state, chunk)  # compile + warm
        stacked = jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]), *chunk)
        placed = trainer._put_stacked(stacked)
        jax.block_until_ready(placed)
        # the raw scan program returns (state, {loss/good/grad_norm: [K]});
        # time it directly but read the losses out of the metrics pytree
        scan_fn = trainer._train_scan
        t0 = time.perf_counter()
        state, chunk_metrics = scan_fn(state, placed)
        losses = chunk_metrics["loss"]
        jax.block_until_ready(losses)
        chunk_time = time.perf_counter() - t0
        n_chunks = max(2, min(12, int(15.0 / max(chunk_time, 1e-6))))
        t0 = time.perf_counter()
        for _ in range(n_chunks):
            state, chunk_metrics = scan_fn(state, placed)
        losses = chunk_metrics["loss"]
        jax.block_until_ready(losses)
        elapsed = time.perf_counter() - t0
        steps = n_chunks * scan_k

        batch_size = np.asarray(batch["padding_mask"]).shape[0]
        record = {
            "row": label,
            "samples_per_sec": round(steps * batch_size / elapsed, 1),
            "step_ms": round(elapsed / steps * 1000, 3),
            "dispatch_step_ms": round(dispatch_step * 1000, 3),
            "scan_k": scan_k,
            "final_loss": round(float(np.asarray(losses)[-1]), 4),
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "compile_seconds": round(trainer.compile_tracker.total_compile_seconds, 2),
            "peak_memory_bytes": (
                peak_after
                if (peak_after := monitor.peak_bytes()) is not None
                and peak_after != peak_before
                else None
            ),
            **(meta or {}),
        }
        tflops = None
        if step_flops:
            tflops = step_flops * steps / elapsed / 1e12
            record["tflops_per_sec"] = round(tflops, 3)
        # one shaping shared with bench.py (obs.roofline.bench_fields):
        # bound-ness + ceiling + HBM/collective bytes, and achieved ÷ per-chip
        # roofline ceiling — the honest utilization for memory-bound heads
        # (CPU rows: arithmetic against the assumed peak, flagged via
        # roofline_peak_assumed)
        record.update(bench_fields(static_record, tflops, jax.device_count()))
        if step_flops:
            utilization = _mfu(tflops, record["device_kind"], device_count=jax.device_count())
            if utilization is not None and record["backend"] != "cpu":
                record["mfu"] = round(utilization, 4)
            elif record["backend"] == "cpu" and os.environ.get("REPLAY_TPU_BENCH_ASSUME_KIND"):
                # CI quick mode: exercise the MFU accounting arithmetic against
                # an ASSUMED chip peak — mfu_peak_assumed marks the record so a
                # CPU smoke can never read as a measurement
                assumed = os.environ["REPLAY_TPU_BENCH_ASSUME_KIND"]
                utilization = _mfu(tflops, assumed, device_count=jax.device_count())
                if utilization is not None:
                    record["mfu"] = round(utilization, 10)
                    record["mfu_peak_assumed"] = assumed
        return record
    except Exception as exc:  # OOM / compile failure is a result, not a crash
        return {"row": label, "error": f"{type(exc).__name__}: {str(exc)[:400]}",
                **(meta or {})}


def item_schema(num_items, dim):
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema

    return TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID, cardinality=num_items,
            embedding_dim=dim,
        )
    )


def sasrec_batch(num_items, batch, seq_len, seed=0, negatives=0):
    rng = np.random.default_rng(seed)
    items = rng.integers(0, num_items, size=(batch, seq_len + 1)).astype(np.int32)
    mask = np.ones((batch, seq_len), dtype=bool)
    record = {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }
    if negatives:  # a shared sampled-negative pool (the BCESampled/GBCE shape)
        record["negative_labels"] = rng.integers(0, num_items, size=(negatives,)).astype(np.int32)
    return record


# --------------------------------------------------------------------------- #
# rows
# --------------------------------------------------------------------------- #
def _sasrec_loss(loss_kind, num_items, quick):
    """(loss, model_parallel, negatives, loss_label) for one scaling variant."""
    from replay_tpu.nn.loss import CE, CEFused, CEFusedTP, GBCE, SCE, SCEParams

    if loss_kind == "ce":
        return CE(), 1, 0, "CE"
    if loss_kind == "fused":
        return CEFused(), 1, 0, "CEFused"
    if loss_kind == "tp":
        import jax

        # shard the catalog over as much of the slice as divides it; a single
        # chip degenerates to n_tp=1 (recorded in the row meta)
        n = jax.device_count()
        mp = max(d for d in (8, 4, 2, 1) if n % d == 0 and d <= n)
        return CEFusedTP(), mp, 0, f"CEFusedTP(n_tp={mp})"
    if loss_kind == "sce":
        size = 8 if quick else 256
        n_buckets = 8 if quick else 128
        return (
            SCE(SCEParams(n_buckets=n_buckets, bucket_size_x=size, bucket_size_y=size)),
            1, 0, f"SCE(nb={n_buckets},bx={size},by={size})",
        )
    if loss_kind == "gbce":
        negatives = 16 if quick else 256
        return GBCE(catalog_size=num_items, t=0.75), 1, negatives, f"GBCE(t=0.75,k={negatives})"
    msg = f"unknown loss_kind {loss_kind!r}"
    raise ValueError(msg)


def run_sasrec(num_items, dim, batch, seq_len, blocks, heads, loss_kind, label, dtype,
               quick=False, precision=None):
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.obs.mfu import fused_ce_flops

    loss, model_parallel, negatives, loss_label = _sasrec_loss(loss_kind, num_items, quick)
    model = SasRec(
        schema=item_schema(num_items, dim), embedding_dim=dim, num_blocks=blocks,
        num_heads=heads, max_sequence_length=seq_len, dropout_rate=0.0, dtype=dtype,
    )
    trainer = Trainer(
        model=model, loss=loss,
        optimizer=OptimizerFactory(name="adam", learning_rate=1e-3),
        mesh=make_mesh(model_parallel=model_parallel),
        shard_vocab=model_parallel > 1,
        # the precision-ladder rows go through the sanctioned policy (model
        # compute dtype + f32 master params + f32 loss accumulation), not a
        # hand-set model dtype — the bench measures what fit() would run
        precision=precision,
    )
    # the pallas head is opaque to the XLA cost model: add its analytic FLOPs
    # back so the fused/TP MFU stays honest next to the plain-CE rows
    extra = (
        fused_ce_flops(batch * seq_len, dim, num_items)
        if loss_kind in ("fused", "tp")
        else 0.0
    )
    meta = {"num_items": num_items, "d": dim, "B": batch, "L": seq_len,
            "loss": loss_label}
    if precision is not None:
        meta["precision"] = precision
    if model_parallel > 1:
        meta["model_parallel"] = model_parallel
    if loss_kind == "sce":
        meta["note"] = ("approximate loss (hard-negative buckets): scalability "
                        "row, not numerically comparable to CE rows")
    if loss_kind == "gbce":
        meta["note"] = ("sampled calibrated loss (gBCE): scalability row, not "
                        "numerically comparable to CE rows")
    return measure(
        trainer, sasrec_batch(num_items, batch, seq_len, negatives=negatives), label,
        extra_flops_per_step=extra, meta=meta,
    )


def run_sasrec_sce(num_items, dim, batch, seq_len, label, dtype, quick):
    """SCE (bucketed hard-negative mining) — the reference's scalable-loss
    answer to huge catalogs, vs CEFused's exact tile-wise logsumexp."""
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import SCE, SCEParams
    from replay_tpu.nn.sequential.sasrec import SasRec

    tokens = batch * seq_len
    n_buckets = max(4, int(round(tokens ** 0.5 / 16)) * 16)
    size = 8 if quick else 256
    model = SasRec(
        schema=item_schema(num_items, dim), embedding_dim=dim, num_blocks=2,
        num_heads=2, max_sequence_length=seq_len, dropout_rate=0.0, dtype=dtype,
    )
    trainer = Trainer(
        model=model,
        loss=SCE(SCEParams(n_buckets=n_buckets, bucket_size_x=size, bucket_size_y=size)),
        optimizer=OptimizerFactory(name="adam", learning_rate=1e-3), mesh=make_mesh(),
    )
    return measure(
        trainer, sasrec_batch(num_items, batch, seq_len), label,
        meta={"num_items": num_items, "d": dim, "B": batch, "L": seq_len,
              "loss": f"SCE(nb={n_buckets},bx={size},by={size})",
              "note": "approximate loss (hard-negative buckets): scalability row, "
                      "not numerically comparable to CE rows"},
    )


def run_bert4rec(num_items, dim, batch, seq_len, heads, dtype):
    import jax

    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.bert4rec import Bert4Rec
    from replay_tpu.nn.transform import Compose
    from replay_tpu.nn.transform.template import make_default_bert4rec_transforms

    schema = item_schema(num_items, dim)
    model = Bert4Rec(schema=schema, embedding_dim=dim, num_blocks=2, num_heads=heads,
                     max_sequence_length=seq_len, dropout_rate=0.0, dtype=dtype)
    trainer = Trainer(model=model, loss=CE(),
                      optimizer=OptimizerFactory(name="adam", learning_rate=1e-3),
                      mesh=make_mesh())
    rng = np.random.default_rng(0)
    items = rng.integers(0, num_items, size=(batch, seq_len)).astype(np.int32)
    raw = {"item_id": items, "item_id_mask": np.ones((batch, seq_len), bool)}
    pipeline = Compose(make_default_bert4rec_transforms(schema, mask_prob=0.2)["train"])
    mlm_batch = pipeline(raw, jax.random.PRNGKey(0))
    # notebook-10 parity point: L=100, hidden 300, heads 4, blocks 2
    return measure(trainer, mlm_batch, "bert4rec",
                   meta={"num_items": num_items, "d": dim, "B": batch, "L": seq_len,
                         "config": "10_bert4rec_example.ipynb (hidden 300, h4, bl2)"})


def run_twotower(num_items, dim, batch, seq_len, dtype):
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CESampled
    from replay_tpu.nn.sequential.twotower import TwoTower
    from replay_tpu.nn.transform import Compose
    from replay_tpu.nn.transform.template import make_default_twotower_transforms

    schema = item_schema(num_items, dim)
    model = TwoTower(schema=schema, embedding_dim=dim, num_blocks=2, num_heads=2,
                     max_sequence_length=seq_len, dropout_rate=0.0, dtype=dtype)
    trainer = Trainer(model=model, loss=CESampled(),
                      optimizer=OptimizerFactory(name="adam", learning_rate=1e-3),
                      mesh=make_mesh())
    rng = np.random.default_rng(0)
    items = rng.integers(0, num_items, size=(batch, seq_len + 1)).astype(np.int32)
    raw = {"item_id": items, "item_id_mask": np.ones((batch, seq_len + 1), bool)}
    tt_batch = Compose(make_default_twotower_transforms(schema)["train"])(raw)
    return measure(trainer, tt_batch, "twotower",
                   meta={"num_items": num_items, "d": dim, "B": batch, "L": seq_len,
                         "config": "15_twotower_example.ipynb (in-batch negatives; "
                                   "B512 vs the notebook's CPU-host B32)"})


def _longseq_mesh_layout():
    """The DP×TP×SP grid the long-sequence sharded rows run on: 2×2×2 on an
    8-chip slice, degrading gracefully toward 1×1×1 on smaller ones (the row
    meta records the actual grid so cross-run compares stay like-for-like)."""
    import jax

    n = jax.device_count()
    seq = 2 if n % 2 == 0 else 1
    tp = 2 if n % 4 == 0 else 1
    dp = n // (seq * tp)
    return dp, tp, seq


def run_sasrec_longseq(length, dim, batch, fused, tiled, label, dtype, quick,
                       sharded=False, remat=None):
    """SASRec at long L — the regime the reference cannot reach on one device
    (its torch attention materializes [B, H, L, L]). A/B: default attention vs
    use_flash='tiled', with CEFused keeping the head off the critical path.

    ``sharded=True`` runs the FULL DP×TP×SP production fit instead of the
    single-chip model: the rule table places batch rows over ``data``, the
    vocab table over ``model`` (CEFusedTP head) and the sequence over ``seq``
    with ring attention — the ROADMAP-2 long-context path end-to-end.
    ``remat`` ("on"/"off") A/Bs activation checkpointing over the blocks;
    ``obs.report --compare`` gates the pair on ``hbm_peak_bytes``
    lower-better.
    """
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE, CEFused, CEFusedTP
    from replay_tpu.nn.sequential.sasrec import SasRec

    num_items = 64 if quick else 3706
    if sharded:
        dp, tp, seq = _longseq_mesh_layout()
        if tp > 1:
            # the vocab rule shards TABLE ROWS (cardinality + padding row):
            # keep them divisible by the model axis or the placement warns
            # and replicates (the satellite-1 loud fallback)
            num_items -= (num_items + 1) % tp
        mesh = make_mesh(model_parallel=tp, seq_parallel=seq)
        use_flash = "ring" if seq > 1 else False
        loss = CEFusedTP(tile=8 if quick else 256) if tp > 1 else (
            CEFused(tile=8 if quick else 256) if fused else CE()
        )
        loss_label = type(loss).__name__ + (f"(n_tp={tp})" if tp > 1 else "")
    else:
        mesh = make_mesh()
        use_flash = "tiled" if tiled else False
        loss = CEFused() if fused else CE()
        loss_label = type(loss).__name__
    model = SasRec(
        schema=item_schema(num_items, dim), embedding_dim=dim, num_blocks=2,
        num_heads=2, max_sequence_length=length, dropout_rate=0.0, dtype=dtype,
        use_flash=use_flash,
    )
    trainer = Trainer(
        model=model, loss=loss,
        optimizer=OptimizerFactory(name="adam", learning_rate=1e-3), mesh=mesh,
        shard_vocab=sharded and tp > 1,
        remat_policy="dots" if remat == "on" else None,
    )
    meta = {"num_items": num_items, "d": dim, "B": batch, "L": length,
            "attention": ("ring" if use_flash == "ring" else
                          "flash_tiled" if tiled else "xla_full"),
            "loss": loss_label}
    if sharded:
        meta["mesh"] = {"data": dp, "model": tp, "seq": seq}
    if remat is not None:
        meta["remat"] = remat
    return measure(
        trainer, sasrec_batch(num_items, batch, length), label, scan_k=4,
        meta=meta,
    )


def run_attention_long(length, quick):
    """Tiled flash kernel vs XLA full attention at long L, fwd+bwd — the
    single-chip long-context A/B (ops/flash_tiled.py; the single-block kernel
    OOMs here, the 2026-07-29 round-3 chip reading)."""
    import jax
    import jax.numpy as jnp

    from replay_tpu.ops.flash_tiled import flash_attention_tiled, padding_mask_bias

    on_cpu = jax.default_backend() == "cpu"
    batch, heads, dim = (1, 1, 8) if quick else (4, 4, 64)
    block = 16 if quick else 512
    rng = np.random.default_rng(0)
    shape = (batch, heads, length, dim)
    q = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    mask = jnp.ones((batch, length), bool)
    bias = padding_mask_bias(mask)

    def xla_loss(q):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, q) / np.sqrt(dim)
        tri = jnp.tril(jnp.ones((length, length), bool))
        s = jnp.where(tri[None, None], s, -1e30)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), q) ** 2)

    def tiled_loss(q):
        return jnp.sum(
            flash_attention_tiled(q, q, q, bias, True, block, block, on_cpu) ** 2
        )

    record = {"row": "attention_long", "B": batch, "H": heads, "L": length, "D": dim,
              "block": block, "backend": jax.default_backend(),
              "device_kind": jax.devices()[0].device_kind}
    for name, fn in (("xla_full", xla_loss), ("flash_tiled", tiled_loss)):
        try:
            grad = jax.jit(jax.grad(fn))
            out = grad(q)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            reps = 2 if quick else 10
            for _ in range(reps):
                out = grad(q)
            jax.block_until_ready(out)
            record[f"{name}_ms"] = round((time.perf_counter() - t0) / reps * 1000, 2)
        except Exception as exc:  # XLA full attention MAY OOM at long L: a result
            record[f"{name}_error"] = f"{type(exc).__name__}: {str(exc)[:200]}"
    return record


def run_attention_long_sp(length, quick):
    """Ring attention (sequence sharded over every device) vs single-device
    full attention at long L, fwd+bwd — the multi-chip half of the
    ``attention_long`` A/B: per-chip memory is O((L/n_sp)·L-block) and the only
    sequence traffic is the ppermute KV rotation (arXiv 2310.01889)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from replay_tpu.parallel import full_attention_reference, ring_attention

    n_sp = jax.device_count()
    batch, heads, dim = (1, 1, 8) if quick else (4, 4, 64)
    length = length - (length % n_sp) or n_sp
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(batch, length, heads, dim)).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()), ("sp",))

    record = {"row": "attention_long_sp", "B": batch, "H": heads, "L": length,
              "D": dim, "sp": n_sp, "backend": jax.default_backend(),
              "device_kind": jax.devices()[0].device_kind}

    def ring_loss(q):
        return jnp.sum(ring_attention(q, q, q, mesh, axis_name="sp", causal=True) ** 2)

    def full_loss(q):
        return jnp.sum(full_attention_reference(q, q, q, causal=True) ** 2)

    for name, fn in (("xla_full", full_loss), ("ring_sp", ring_loss)):
        try:
            grad = jax.jit(jax.grad(fn))
            out = grad(q)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            reps = 2 if quick else 10
            for _ in range(reps):
                out = grad(q)
            jax.block_until_ready(out)
            record[f"{name}_ms"] = round((time.perf_counter() - t0) / reps * 1000, 2)
        except Exception as exc:  # full attention MAY OOM at long L: a result
            record[f"{name}_error"] = f"{type(exc).__name__}: {str(exc)[:200]}"
    if "xla_full_error" not in record and "ring_sp_error" not in record:
        err = float(
            jnp.max(jnp.abs(
                ring_attention(q, q, q, mesh, axis_name="sp", causal=True)
                - full_attention_reference(q, q, q, causal=True)
            ))
        )
        record["ring_max_err"] = round(err, 8)
    return record


def run_pipeline_e2e(num_items, dim, batch, seq_len, quick, dtype):
    """parquet → ParquetBatcher → transforms → prefetch → chunked train_steps."""
    import jax

    from replay_tpu.data.nn import ParquetBatcher, prefetch
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.nn.transform import Compose
    from replay_tpu.nn.transform.template import make_default_sasrec_transforms

    schema = item_schema(num_items, dim)
    num_rows = batch * (8 if quick else 64)
    rng = np.random.default_rng(0)

    with tempfile.TemporaryDirectory(prefix="bench_e2e_") as tmp:
        path = os.path.join(tmp, "seqs.parquet")
        import pyarrow as pa
        import pyarrow.parquet as pq

        lengths = rng.integers(max(2, seq_len // 3), seq_len + 2, size=num_rows)
        table = pa.table({
            "query_id": pa.array(np.arange(num_rows)),
            "item_id": pa.array(
                [rng.integers(0, num_items, n).tolist() for n in lengths]
            ),
        })
        pq.write_table(table, path)

        model = SasRec(schema=schema, embedding_dim=dim, num_blocks=2, num_heads=1,
                       max_sequence_length=seq_len, dropout_rate=0.0, dtype=dtype)
        trainer = Trainer(model=model, loss=CE(),
                          optimizer=OptimizerFactory(name="adam", learning_rate=1e-3),
                          mesh=make_mesh())
        pipeline = Compose(make_default_sasrec_transforms(schema)["train"])
        scan_k = 4 if quick else 8

        def batches(epoch):
            batcher = ParquetBatcher(
                path, batch_size=batch, shuffle=True, seed=0,
                metadata={"item_id": {"shape": seq_len + 1, "padding": num_items}},
            )
            batcher.set_epoch(epoch)
            for raw in batcher:
                yield pipeline({"item_id": raw["item_id"],
                                "item_id_mask": raw["item_id_mask"]})

        def chunks(epoch):
            buf = []
            for b in batches(epoch):
                buf.append(b)
                if len(buf) == scan_k:
                    yield buf
                    buf = []

        state = None
        for chunk in prefetch(chunks(0), depth=2):  # warmup epoch: compile
            if state is None:
                state = trainer.init_state(chunk[0])
            state, losses = trainer.train_steps(state, chunk)
        jax.block_until_ready(losses)

        steps = 0
        t0 = time.perf_counter()
        for chunk in prefetch(chunks(1), depth=2):
            state, losses = trainer.train_steps(state, chunk)
            steps += len(chunk)
        jax.block_until_ready(losses)
        elapsed = time.perf_counter() - t0

        return {
            "row": "pipeline_e2e",
            "samples_per_sec": round(steps * batch / elapsed, 1),
            "step_ms": round(elapsed / max(steps, 1) * 1000, 3),
            "scan_k": scan_k,
            "rows_on_disk": num_rows,
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "num_items": num_items, "d": dim, "B": batch, "L": seq_len,
            "note": "parquet->ParquetBatcher->transforms->prefetch->train_steps, "
                    "host time included",
        }


def run_stream(kind, num_items, dim, batch, seq_len, quick, dtype):
    """Streaming-input family (docs/performance.md "Feeding the beast"):
    the SAME ragged synthetic interaction data through three input stages —

    - ``stream_inmem``:   SequenceBatcher (fixed [B, L], padding waste as-is)
    - ``stream_parquet``: row-group-sharded ParquetBatcher with read-ahead +
                          a memory budget (the out-of-core path)
    - ``stream_packed``:  PackedSequenceBatcher (first-fit packing + segment
                          masks — the padding-waste cure)

    each feeding chunked ``train_steps``. Rows report the feed-efficiency
    numbers: ``effective_tokens_per_sec`` (REAL tokens/s through the device)
    and ``padding_fraction``; ``obs.report --compare`` gates packed ≥ unpacked
    effective tokens/s whenever both rows are present.
    """
    import jax
    import pandas as pd

    from replay_tpu.data.nn import (
        PackedSequenceBatcher,
        ParquetBatcher,
        SequenceBatcher,
        SequentialDataset,
        TensorFeatureInfo,
        TensorSchema,
        TransformedBatches,
        prefetch,
        write_sequence_parquet,
    )
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.nn.transform import Compose
    from replay_tpu.nn.transform.template import (
        make_default_sasrec_transforms,
        make_packed_sasrec_transforms,
    )

    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID, cardinality=num_items,
            embedding_dim=dim,
        )
    )
    rng = np.random.default_rng(0)
    num_rows = batch * (32 if quick else 48)
    # short sequences (mean ~L/4): the padding-waste regime packing targets
    lengths = rng.integers(2, max(3, seq_len // 2), size=num_rows)
    frame = pd.DataFrame({
        "query_id": np.arange(num_rows),
        "item_id": [rng.integers(1, num_items, n).astype(np.int64) for n in lengths],
    })
    dataset = SequentialDataset(schema, "query_id", "item_id", frame)

    model = SasRec(schema=schema, embedding_dim=dim, num_blocks=2, num_heads=1,
                   max_sequence_length=seq_len, dropout_rate=0.0, dtype=dtype)
    trainer = Trainer(model=model, loss=CE(),
                      optimizer=OptimizerFactory(name="adam", learning_rate=1e-3),
                      mesh=make_mesh())
    scan_k = 4 if quick else 8
    tmp_ctx = tempfile.TemporaryDirectory(prefix="bench_stream_")
    extra_meta = {}
    with tmp_ctx:
        if kind == "packed":
            pipeline = Compose(make_packed_sasrec_transforms(schema)["train"])
            batcher = PackedSequenceBatcher(
                dataset, batch_size=batch, max_sequence_length=seq_len + 1,
                shuffle=True, seed=0,
            )
            extra_meta = {
                "segments_per_row": round(
                    batcher.packing_summary()["segments_per_row"], 3
                )
            }
        elif kind == "parquet":
            pipeline = Compose(make_default_sasrec_transforms(schema)["train"])
            path = os.path.join(tmp_ctx.name, "stream.parquet")
            write_sequence_parquet(path, dataset, rows_per_chunk=max(batch, 64))
            batcher = ParquetBatcher(
                path, batch_size=batch, shuffle=True, seed=0,
                shard="row_groups", read_ahead=2,
                memory_budget_bytes=8 << 20,
                metadata={"item_id": {"shape": seq_len + 1, "padding": 0}},
            )
            extra_meta = {"rows_on_disk": num_rows, "shard": "row_groups"}
        elif kind == "inmem":
            pipeline = Compose(make_default_sasrec_transforms(schema)["train"])
            batcher = SequenceBatcher(
                dataset, batch_size=batch, max_sequence_length=seq_len + 1,
                shuffle=True, seed=0,
            )
        else:
            msg = f"unknown stream kind {kind!r}"
            raise ValueError(msg)
        stream = TransformedBatches(batcher, pipeline)

        def chunks(epoch):
            # FULL chunks only: packing can shift the epoch's batch count by
            # one, and a differently-sized tail chunk would recompile inside
            # the measured window — the bench times one steady program
            stream.set_epoch(epoch)
            buf = []
            for b in stream:
                buf.append(b)
                if len(buf) == scan_k:
                    yield buf
                    buf = []

        state = None
        for chunk in prefetch(chunks(0), depth=2):  # warmup epoch: compile
            if state is None:
                state = trainer.init_state(chunk[0])
            state, losses = trainer.train_steps(state, chunk)
        jax.block_until_ready(losses)

        steps = 0
        tokens_real = 0
        tokens_grid = 0
        sequences = 0
        t0 = time.perf_counter()
        for chunk in prefetch(chunks(1), depth=2):
            state, losses = trainer.train_steps(state, chunk)
            steps += len(chunk)
            for b in chunk:
                mask = np.asarray(b["padding_mask"])
                valid = np.asarray(b["valid"])
                tokens_real += int(mask[valid].sum())
                tokens_grid += mask.size
                if "segment_ids" in b:
                    seg = np.asarray(b["segment_ids"])[valid]
                    sequences += int((np.diff(seg, prepend=0) > 0).sum())
                else:
                    sequences += int(valid.sum())
        jax.block_until_ready(losses)
        elapsed = time.perf_counter() - t0

    return {
        "row": f"stream_{kind}",
        # samples/sec = USER SEQUENCES per second (packed rows hold several),
        # so the three rows compare like for like
        "samples_per_sec": round(sequences / elapsed, 1),
        "step_ms": round(elapsed / max(steps, 1) * 1000, 3),
        "effective_tokens_per_sec": round(tokens_real / elapsed, 1),
        "padding_fraction": round(1.0 - tokens_real / tokens_grid, 4) if tokens_grid else None,
        "scan_k": scan_k,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "num_items": num_items, "d": dim, "B": batch, "L": seq_len,
        "note": "stream family: same ragged data, three input stages; "
                "host time included",
        **extra_meta,
    }


# --------------------------------------------------------------------------- #
def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rows", default="all")
    parser.add_argument("--quick", action="store_true", help="toy shapes (CPU smoke)")
    parser.add_argument("--out", default=None)
    parser.add_argument(
        "--run-dir",
        default=os.environ.get("REPLAY_TPU_RUN_DIR"),
        help="also append each row as a JSON line to RUN_DIR/events.jsonl "
             "(the shared obs artifact shape; default: $REPLAY_TPU_RUN_DIR)",
    )
    args = parser.parse_args()
    run_log = JsonlLogger(args.run_dir) if args.run_dir else None

    from replay_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp
    import jax

    on_cpu = jax.default_backend() == "cpu"
    dtype = jnp.float32 if on_cpu else jnp.bfloat16

    q = args.quick
    B, L = (8, 8) if q else (512, 50)
    rows = {
        "sasrec_ref": lambda: run_sasrec(3706 if not q else 50, 64, B, L, 2, 1, "ce", "sasrec_ref", dtype, q),
        "sasrec_ref_fused": lambda: run_sasrec(3706 if not q else 50, 64, B, L, 2, 1, "fused", "sasrec_ref_fused", dtype, q),
        "sasrec_27k": lambda: run_sasrec(27278 if not q else 96, 128 if not q else 16, B, L, 2, 2, "ce", "sasrec_27k", dtype, q),
        "sasrec_27k_fused": lambda: run_sasrec(27278 if not q else 96, 128 if not q else 16, B, L, 2, 2, "fused", "sasrec_27k_fused", dtype, q),
        "sasrec_100k": lambda: run_sasrec(100000 if not q else 128, 128 if not q else 16, B, L, 2, 2, "ce", "sasrec_100k", dtype, q),
        "sasrec_100k_fused": lambda: run_sasrec(100000 if not q else 128, 128 if not q else 16, B, L, 2, 2, "fused", "sasrec_100k_fused", dtype, q),
        "sasrec_100k_sce": lambda: run_sasrec_sce(100000 if not q else 128, 128 if not q else 16, B, L, "sasrec_100k_sce", dtype, q),
        "bert4rec": lambda: run_bert4rec(27278 if not q else 96, 300 if not q else 16, B, 100 if not q else L, 4 if not q else 2, dtype),
        "twotower": lambda: run_twotower(27278 if not q else 96, 64 if not q else 16, B, L, dtype),
        "pipeline_e2e": lambda: run_pipeline_e2e(3706 if not q else 50, 64 if not q else 16, B, L, q, dtype),
        # the streaming-input family ("Feeding the beast"): padding waste vs
        # effective tokens/s across the three input stages; --compare gates
        # packed >= unpacked effective tokens/s
        "stream_inmem": lambda: run_stream("inmem", 3706 if not q else 50, 64 if not q else 16, B, L, q, dtype),
        "stream_parquet": lambda: run_stream("parquet", 3706 if not q else 50, 64 if not q else 16, B, L, q, dtype),
        "stream_packed": lambda: run_stream("packed", 3706 if not q else 50, 64 if not q else 16, B, L, q, dtype),
        "attention_long": lambda: run_attention_long(4096 if not q else 32, q),
        "attention_long_sp": lambda: run_attention_long_sp(4096 if not q else 32, q),
        "sasrec_l1024": lambda: run_sasrec_longseq(1024 if not q else 16, 128 if not q else 8, 32 if not q else 4, not q, False, "sasrec_l1024", dtype, q),
        "sasrec_l1024_tiled": lambda: run_sasrec_longseq(1024 if not q else 16, 128 if not q else 8, 32 if not q else 4, not q, True, "sasrec_l1024_tiled", dtype, q),
        # the DP×TP×SP long-context family (ROADMAP 2): the FULL sharded fit —
        # ring attention over the seq axis, CEFusedTP over the model axis,
        # batch rows over data, all from ONE rule table — with a remat on/off
        # A/B pair; obs.report renders the pair and --compare gates
        # hbm_peak_bytes lower-better (remat exists to move bytes)
        "sasrec_l1024_sp_remat_off": lambda: run_sasrec_longseq(1024 if not q else 16, 128 if not q else 8, 32 if not q else 4, True, False, "sasrec_l1024_sp_remat_off", dtype, q, sharded=True, remat="off"),
        "sasrec_l1024_sp_remat_on": lambda: run_sasrec_longseq(1024 if not q else 16, 128 if not q else 8, 32 if not q else 4, True, False, "sasrec_l1024_sp_remat_on", dtype, q, sharded=True, remat="on"),
    }
    # the catalog-scaling family ("Breaking the memory wall"): one row per
    # (catalog size, head) — near-flat step time 27k → 1M is the claim for
    # every head except plain CE, whose 1M row records the OOM that motivates
    # the rest. d=128 B=512 L=50 held constant so only the catalog moves.
    scale_sizes = {"27k": 96, "100k": 128, "1m": 192} if q else {
        "27k": 27278, "100k": 100000, "1m": 1000000,
    }
    scale_dim = 16 if q else 128
    for size_tag, size_items in scale_sizes.items():
        for kind in ("ce", "fused", "tp", "sce", "gbce"):
            name = f"scale_{size_tag}_{kind}"
            rows[name] = (
                lambda n=size_items, k=kind, lbl=name: run_sasrec(
                    n, scale_dim, B, L, 2, 2, k, lbl, dtype, q
                )
            )
    # the precision-ladder family (docs/performance.md "The precision
    # ladder"): f32 vs bf16 through the SANCTIONED Trainer(precision=...)
    # policy at the 27k catalog shape, per head. The claim is per-pair:
    # the bf16 row must carry strictly lower hbm_peak_bytes and a moved
    # roofline (the of_roofline_ceiling honesty check), not just step_ms on a
    # toy shape — obs.report --compare gates prec_* rows' hbm_peak_bytes
    # lower-better. Model dtype is pinned f32 here so ONLY the policy differs
    # between the two rows of a pair.
    prec_items = 96 if q else 27278
    prec_dim = 16 if q else 128
    for prec_tag in ("f32", "bf16"):
        for kind in ("ce", "fused", "tp"):
            name = f"prec_{prec_tag}_{kind}"
            rows[name] = (
                lambda k=kind, lbl=name, tag=prec_tag: run_sasrec(
                    prec_items, prec_dim, B, L, 2, 2, k, lbl, jnp.float32, q,
                    precision=tag,
                )
            )
    selected = list(rows) if args.rows == "all" else args.rows.split(",")
    unknown = [name for name in selected if name not in rows]
    if unknown:
        parser.error(f"unknown rows: {unknown}; choose from {list(rows)}")

    results = []
    for name in selected:
        print(f"--- {name} ...", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        try:
            record = rows[name]()
        except Exception as exc:  # a crashed row must not lose the session
            record = {"row": name, "error": f"{type(exc).__name__}: {str(exc)[:400]}"}
        record["wall_s"] = round(time.perf_counter() - t0, 1)
        record["git_rev"] = _git_rev()
        record["captured_unix"] = int(time.time())
        results.append(record)
        print(json.dumps(record), flush=True)
        if run_log is not None:  # same artifact shape as training runs / dryruns
            run_log.log_record({"event": "bench_row", **record})
        if args.out:  # write-through: completed rows survive a later crash
            with open(args.out, "w") as fh:
                json.dump(results, fh, indent=1)
    if run_log is not None:
        run_log.close()
    if args.out:
        print(f"wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
