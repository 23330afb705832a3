"""Chip smoke: fit -> predict -> serve on ONE TPU v5e chip, in one process.

The quickest proof that the system still starts on the chip. It drives the main
path once through the entry points a user calls, at the full width of the
reference's notebook-09 SASRec on the ML-20M catalog (``embedding_dim=64``, 2
blocks, 2 heads, ``max_sequence_length=50``, batch 512, 27,278 items, bf16
compute), on synthetic interactions made from ``--seed``. Every phase prints one
JSON line ``{"phase": ..., "seconds": ...}``; the first failing check raises, so
the exit code is non-zero and no result line is printed. The last line of
stdout is the contract's::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (one chip): ``device``, ``fit``, ``fit_fused``, ``attention_tiled``,
``eval_predict``, ``serve``. Every seconds / samples-per-second figure printed
here is a SMOKE READING (compile and host work included), not a benchmark number.

``--chips 4`` runs ONLY the sharded path and what it is compared with: the same
model on (a) one chip, (b) DP4, (c) DP2xTP2 with the vocab-sharded fused head,
(d) SP4 ring attention at L=4096 — losses against the one-chip leg, placement
asserted on the real devices. The driver never passes it.

``--rehearse`` shrinks every size and accepts the CPU (Pallas interpreted, four
virtual devices with ``--chips 4``): it finds wrong paths and arguments before a
chip call, always ends on ``{"ok": false, "rehearsal": true, ...}`` and exits
with code 4, so a rehearsal can never be read as a pass.

One process holds the chip: nothing here starts a child that imports JAX. The
copy this runs from has no network and is not a git repository.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from importlib import metadata
from pathlib import Path

REHEARSAL_EXIT_CODE = 4


@dataclass(frozen=True)
class Sizes:
    """Everything a rehearsal shrinks; the model's widths are the first block."""

    num_items: int = 27_278  # ML-20M catalog (BASELINE.json; examples/ml1m_parity.py)
    embedding_dim: int = 64
    num_blocks: int = 2
    num_heads: int = 2
    seq_len: int = 50
    dropout: float = 0.3  # notebook 09
    batch: int = 512
    users: int = 12_000  # ~50 windowed batches an epoch
    min_steps_per_epoch: int = 40
    min_events: int = 20
    max_events: int = 150
    scan_chunk: int = 8
    fused_chunks: int = 3
    attention: tuple = (8, 2, 4096, 64)  # (B, H, L, D) of the tiled-kernel phase
    predict_batches: int = 3
    serve_users: int = 8
    mesh_steps: int = 6
    sp_shape: tuple = (4, 4096)  # (B, L) of the ring-attention leg


REHEARSAL = Sizes(
    num_items=302, embedding_dim=16, seq_len=12, batch=32, users=400, min_events=10,
    max_events=40, min_steps_per_epoch=1, scan_chunk=4, fused_chunks=1, attention=(2, 2, 320, 16),
    predict_batches=2, serve_users=4, mesh_steps=3, sp_shape=(4, 64),
)


def emit(phase: str, started: float, **fields) -> None:
    record = {"phase": phase, "seconds": round(time.perf_counter() - started, 3), **fields}
    print(json.dumps(record), flush=True)


def check(condition, message: str) -> None:
    """A failed check ends the run (``assert`` would vanish under ``-O``)."""
    if not condition:
        raise SystemExit(f"chip_smoke: FAILED: {message}")


def parse_args() -> argparse.Namespace:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0, help="seeds data, weights and dropout")
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = only the sharded legs and their one-chip reference",
    )
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes, CPU accepted; always ends ok=false with exit code 4",
    )
    return parser.parse_args()


# --------------------------------------------------------------------------- #
# data: a seeded synthetic log, then the README quick-start pipeline
# --------------------------------------------------------------------------- #
def synthetic_log(sizes: Sizes, seed: int):
    """Per-user walks over the catalog with Zipf-popular restarts: learnable
    (next = previous + 1 most of the time) and covering EVERY item, so the
    tokenizer's catalog is exactly ``num_items``. Built in bulk with numpy."""
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(seed)
    n = sizes.num_items
    lengths = rng.integers(sizes.min_events, sizes.max_events + 1, size=sizes.users)
    total = int(lengths.sum())
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    user = np.repeat(np.arange(sizes.users), lengths)
    position = np.arange(total) - starts[user]

    popularity = 1.0 / (np.arange(n) + 10.0)
    jump = rng.random(total) < 0.2
    # the first `stride` events of user u walk from u * stride without a jump:
    # users * stride >= num_items, so together they visit the whole catalog
    stride = -(-n // sizes.users)
    check(sizes.min_events > stride + 1, "histories too short to cover the catalog")
    jump[position < stride] = False
    jump[position == 0] = True
    target = rng.choice(n, size=total, p=popularity / popularity.sum())
    target[position == 0] = (np.arange(sizes.users) * stride) % n
    last_jump = np.maximum.accumulate(np.where(jump, np.arange(total), 0))
    item = (target[last_jump] + np.arange(total) - last_jump) % n
    return pd.DataFrame({"user_id": user, "item_id": item, "timestamp": position})


def prepare_data(sizes: Sizes, seed: int) -> dict:
    """pandas log -> LastNSplitter -> Dataset -> SequenceTokenizer (README)."""
    from replay_tpu.data import Dataset, FeatureHint, FeatureInfo, FeatureSchema, FeatureType
    from replay_tpu.data.nn import (
        SequenceTokenizer, TensorFeatureInfo, TensorFeatureSource, TensorSchema,
    )
    from replay_tpu.data.schema import FeatureSource
    from replay_tpu.nn.transform import Compose
    from replay_tpu.nn.transform.template import make_default_sasrec_transforms
    from replay_tpu.splitters import LastNSplitter

    log = synthetic_log(sizes, seed)
    train_log, val_log = LastNSplitter(
        N=1, divide_column="user_id", query_column="user_id"
    ).split(log)
    schema = FeatureSchema(
        [
            FeatureInfo("user_id", FeatureType.CATEGORICAL, FeatureHint.QUERY_ID),
            FeatureInfo("item_id", FeatureType.CATEGORICAL, FeatureHint.ITEM_ID),
            FeatureInfo("timestamp", FeatureType.NUMERICAL, FeatureHint.TIMESTAMP),
        ]
    )
    tensor_schema = TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            feature_sources=[TensorFeatureSource(FeatureSource.INTERACTIONS, "item_id")],
            embedding_dim=sizes.embedding_dim,
        )
    )
    tokenizer = SequenceTokenizer(tensor_schema, handle_unknown_rule="drop")
    train_seq = tokenizer.fit_transform(Dataset(feature_schema=schema, interactions=train_log))
    val_seq = tokenizer.transform(Dataset(feature_schema=schema, interactions=val_log))
    check(
        tensor_schema["item_id"].cardinality == sizes.num_items,
        f"catalog is {tensor_schema['item_id'].cardinality} items, wanted {sizes.num_items}",
    )
    pipes = {k: Compose(v) for k, v in make_default_sasrec_transforms(tensor_schema).items()}
    return {
        "rows": len(log), "tensor_schema": tensor_schema, "train_seq": train_seq,
        "val_seq": val_seq, "pipes": pipes,
    }


def fixed_schema(sizes: Sizes, num_items: int):
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema

    return TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID, cardinality=num_items,
            embedding_dim=sizes.embedding_dim,
        )
    )


def make_trainer(
    sizes: Sizes, tensor_schema, loss, seed: int, seq_len=None, use_flash=False, **trainer_kwargs
):
    """The one trainer recipe every phase and leg shares (notebook 09, bf16)."""
    from replay_tpu.nn import OptimizerFactory, Trainer
    from replay_tpu.nn.sequential import SasRec

    model = SasRec(
        schema=tensor_schema, embedding_dim=sizes.embedding_dim,
        num_blocks=sizes.num_blocks, num_heads=sizes.num_heads,
        dropout_rate=sizes.dropout, max_sequence_length=seq_len or sizes.seq_len,
        use_flash=use_flash,
    )
    return Trainer(
        model=model, loss=loss, optimizer=OptimizerFactory(name="adam", learning_rate=1e-3),
        precision="bf16", seed=seed, **trainer_kwargs,
    )


# --------------------------------------------------------------------------- #
# one-chip phases
# --------------------------------------------------------------------------- #
class EpochWatch:
    """A RunLogger sink: at each epoch end, the clock and the compile counts."""

    def __init__(self, trainer) -> None:
        self.trainer = trainer
        self.epochs = []

    def log_event(self, event) -> None:
        if event.event == "on_epoch_end":
            self.epochs.append(
                (time.perf_counter(), dict(self.trainer.compile_tracker.traces))
            )


def phase_fit(sizes: Sizes, data: dict, seed: int, started_process: float):
    import math

    from replay_tpu.data.nn import SequenceBatcher, TransformedBatches
    from replay_tpu.native import native_artifact
    from replay_tpu.nn.loss import CE

    started = time.perf_counter()
    trainer = make_trainer(sizes, data["tensor_schema"], CE(), seed)
    batcher = SequenceBatcher(
        data["train_seq"], batch_size=sizes.batch, max_sequence_length=sizes.seq_len + 1,
        windows=True, shuffle=True, seed=seed,
    )
    source = TransformedBatches(batcher, data["pipes"]["train"])
    steps_per_epoch = len(batcher)
    check(
        steps_per_epoch >= sizes.min_steps_per_epoch, f"only {steps_per_epoch} batches an epoch"
    )
    watch = EpochWatch(trainer)
    fit_started = time.perf_counter()
    state = trainer.fit(
        source, epochs=2, scan_chunk=sizes.scan_chunk, device_feed=True,
        loggers=watch, log_every=0,
    )
    losses = [record["train_loss"] for record in trainer.history]
    check(len(losses) == 2 and all(math.isfinite(x) for x in losses), f"epoch losses {losses}")
    check(losses[1] < losses[0], f"second-epoch loss {losses[1]} is not below the first {losses[0]}")
    # the in-jit sentinel keeps the old params on a non-finite step and carries
    # on; on this path that is a failure, not a recovery
    check(int(state.bad_steps) == 0, f"{int(state.bad_steps)} non-finite steps were skipped")
    traces = trainer.compile_tracker.traces
    # PR 5: the scan chunk and the per-step tail, nothing else, nothing late
    check(
        set(traces) <= {"train_scan", "train_step"} and sum(traces.values()) <= 2,
        f"more programs than the scan chunk and the per-step tail: {traces}",
    )
    check(watch.epochs[0][1] == traces, f"compiled during epoch 2: {watch.epochs[0][1]} -> {traces}")
    artifact = native_artifact()
    check(artifact is not None, "the native ragged kernel did not build (numpy fallback in use)")
    check(
        artifact.stat().st_mtime >= started_process,
        f"{artifact.name} predates this run: it was not built from this checkout's ragged.cpp",
    )
    epoch2_seconds = watch.epochs[1][0] - watch.epochs[0][0]
    emit(
        "fit", started,
        rows=data["rows"], steps_per_epoch=steps_per_epoch, epoch_losses=losses,
        bad_steps=0, programs=traces,
        compile_seconds=round(trainer.compile_tracker.total_compile_seconds, 3),
        epoch1_seconds=round(watch.epochs[0][0] - fit_started, 3),
        epoch2_seconds=round(epoch2_seconds, 3),
        smoke_samples_per_sec_epoch2=round(steps_per_epoch * sizes.batch / epoch2_seconds, 1),
        native_artifact=artifact.name,
    )
    return trainer, state, source


def loss_of_one_step(trainer, params, batch) -> float:
    """The loss ``train_step`` reports for ``batch`` at ``params`` (a fresh
    state from the trainer's seed: same dropout key for every trainer)."""
    _, loss = trainer.train_step(trainer.init_state(batch, params=params), batch)
    return float(loss)


def phase_fit_fused(sizes: Sizes, data: dict, seed: int, ce_trainer, state, source, interpret_ok):
    import math

    from replay_tpu.nn.loss import CEFused

    started = time.perf_counter()
    loss = CEFused()
    check(
        loss._resolve_interpret() is interpret_ok,
        f"CEFused would run interpret={loss._resolve_interpret()} on this backend",
    )
    trainer = make_trainer(sizes, data["tensor_schema"], loss, seed)
    batches = list(itertools.islice(iter(source), sizes.fused_chunks * sizes.scan_chunk))
    trainer.fit(batches, epochs=1, scan_chunk=sizes.scan_chunk, device_feed=True, log_every=0)
    fused_loss = trainer.history[-1]["train_loss"]
    check(math.isfinite(fused_loss), f"fused fit loss {fused_loss}")
    mosaic = "tpu_custom_call" in trainer.lowered_hlo("train_scan")
    check(mosaic or interpret_ok, "no tpu_custom_call in the fused scan program")

    # first numerical check of the Mosaic-compiled head against the XLA head on
    # silicon: one batch, the params phase `fit` trained, the same dropout key
    ce = loss_of_one_step(ce_trainer, state.params, batches[0])
    fused = loss_of_one_step(trainer, state.params, batches[0])
    # both heads see the same bf16 hidden states and accumulate in f32; the XLA
    # head feeds the MXU the f32 table at default precision (one bf16 pass,
    # 2^-9 relative per product) where the kernel multiplies in f32. Per-row
    # errors are zero-mean and the loss averages ~25k rows: the chip measured
    # 3e-5 relative (PR 21); 1e-3 leaves 30x and is far below what a wrong mask
    # or a dropped catalog tile produces.
    tolerance = 1e-3
    check(
        abs(ce - fused) <= tolerance * abs(ce),
        f"CEFused {fused} vs CE {ce}: relative {abs(ce - fused) / abs(ce):.2e} > {tolerance}",
    )
    emit(
        "fit_fused", started,
        steps=len(batches), train_loss=fused_loss, interpret=loss._resolve_interpret(),
        tpu_custom_call=mosaic, ce_loss=ce, cefused_loss=fused,
        relative_difference=abs(ce - fused) / abs(ce), tolerance=tolerance,
        compile_seconds=round(trainer.compile_tracker.total_compile_seconds, 3),
    )


def phase_attention_tiled(sizes: Sizes, seed: int, interpret_ok: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from replay_tpu.nn.attention import dot_product_attention
    from replay_tpu.nn.mask import causal_attention_mask

    started = time.perf_counter()
    batch, heads, length, dim = sizes.attention
    rng = np.random.default_rng(seed)
    q, k, v, g = (
        jnp.asarray(rng.standard_normal((batch, heads, length, dim)), jnp.bfloat16)
        for _ in range(4)
    )
    # ragged, LEFT-padded rows as SequenceBatcher makes them
    lengths = rng.integers(length // 4, length + 1, size=batch)
    lengths[0] = length
    padding_mask = jnp.asarray(np.arange(length)[None, :] >= (length - lengths)[:, None])
    # padded QUERY rows differ by construction (the dense mask's diagonal rescue
    # vs zeros) and the models drop them: compare, and back-propagate, valid rows
    valid = padding_mask[:, None, :, None]
    g = jnp.where(valid, g, 0)

    def objective(route):
        def fn(q, k, v):
            out = route(q, k, v)
            out = jnp.where(valid, out, 0)
            return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True))

    def tiled(q, k, v):
        return dot_product_attention(
            q, k, v, None, use_flash="tiled", padding_mask=padding_mask, causal=True
        )

    def xla(q, k, v):
        mask = causal_attention_mask(padding_mask, deterministic=True, dtype=q.dtype)
        return dot_product_attention(q, k, v, mask)

    tiled_fn = objective(tiled).lower(q, k, v).compile()
    mosaic = "tpu_custom_call" in tiled_fn.as_text()
    check(mosaic or interpret_ok, "no tpu_custom_call on the use_flash='tiled' route")
    (_, out_tiled), grads_tiled = tiled_fn(q, k, v)
    # the reference is the XLA route on the SAME values in float32: run in bf16
    # it rounds the softmax over L keys to 8 bits itself, which is printed below
    # as `xla_bf16_error` for scale and is not what the kernel is held to
    as_f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        (_, out_ref), grads_ref = objective(xla)(*as_f32)
    (_, out_bf16), _ = objective(xla)(q, k, v)

    def error(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    errors = {"out": error(out_tiled, out_ref)}
    errors.update({f"d{n}": error(a, b) for n, a, b in zip("qkv", grads_tiled, grads_ref)})
    check(all(np.isfinite(np.asarray(x, np.float32)).all() for x in (out_tiled, *grads_tiled)),
          "non-finite values from the tiled route")
    # the kernel computes in f32 and returns bf16: one rounding of the result
    # (2^-8 relative) plus f32 reassociation across kv blocks. 2e-2 of the
    # largest reference magnitude leaves ~5x head-room and is an order of
    # magnitude below what a wrong mask or a dropped block produces.
    tolerance = 2e-2
    check(max(errors.values()) <= tolerance, f"tiled vs XLA {errors} > {tolerance}")
    emit(
        "attention_tiled", started, shape=list(sizes.attention), dtype="bfloat16",
        tpu_custom_call=mosaic, max_error_over_max_magnitude=errors, tolerance=tolerance,
        xla_bf16_error=error(out_bf16, out_ref),
        valid_fraction=float(np.mean(np.asarray(padding_mask))),
    )


def phase_eval_predict(sizes: Sizes, data: dict, trainer, state):
    import numpy as np

    from replay_tpu.data.nn import SequenceBatcher, validation_batches

    started = time.perf_counter()
    pipes = data["pipes"]
    metrics = trainer.validate(
        state,
        (pipes["validate"](b) for b in validation_batches(
            data["train_seq"], data["val_seq"], sizes.batch, sizes.seq_len)),
        metrics=("ndcg", "recall"), top_k=(10,),
    )
    check(set(metrics) == {"ndcg@10", "recall@10"}, f"metrics {sorted(metrics)}")
    check(
        all(np.isfinite(x) and 0.0 <= x <= 1.0 for x in metrics.values()),
        f"metrics outside [0, 1]: {metrics}",
    )
    predict = SequenceBatcher(data["train_seq"], sizes.batch, sizes.seq_len)
    queries, items, scores = trainer.predict_top_k(
        state,
        (pipes["predict"](b) for b in itertools.islice(iter(predict), sizes.predict_batches)),
        k=10,
    )
    rows = sizes.predict_batches * sizes.batch
    check(items.shape == (rows, 10) and scores.shape == (rows, 10), f"top-k shapes {items.shape}")
    check(queries.shape == (rows,), f"query ids {queries.shape}")
    check(items.min() >= 0 and items.max() < sizes.num_items, "item ids outside the catalog")
    check(np.isfinite(scores).all(), "non-finite scores")
    check((np.diff(scores, axis=1) <= 0).all(), "top-k scores are not sorted")
    emit("eval_predict", started, metrics={k: float(v) for k, v in metrics.items()},
         predicted_rows=rows)


def phase_serve(sizes: Sizes, data: dict, trainer, state, seed: int) -> None:
    import numpy as np

    from replay_tpu.serve import ScoringService, make_window, top_k_cut

    started = time.perf_counter()
    rng = np.random.default_rng(seed)
    train_seq = data["train_seq"]
    users = [int(u) for u in rng.choice(len(train_seq), sizes.serve_users, replace=False)]
    histories = {u: [int(i) for i in train_seq.get_sequence(u, "item_id")] for u in users}
    fresh = {u: [int(i) for i in rng.integers(0, sizes.num_items, 2)] for u in users}

    # default buckets, the default breaker and NO fallback scorer: a request the
    # device path cannot answer fails here instead of being papered over
    service = ScoringService(trainer.model, state.params).start()
    construct_seconds = time.perf_counter() - started
    try:
        def session(user):
            """cold (full history) -> two advances -> three pure hits."""
            waves = [
                [{"history": histories[user], "k": 10}],
                [{"new_items": fresh[user][:1], "k": 10}],
                [{"new_items": fresh[user][1:], "k": 10}],
                [{}, {"k": 10}, {"k": 10}],  # {} = full-catalog scores
            ]
            responses = []
            for wave in waves:
                futures = [service.submit(user, **request) for request in wave]
                responses.extend(f.result(timeout=120) for f in futures)
            return user, responses

        with ThreadPoolExecutor(max_workers=len(users)) as pool:
            sessions = dict(pool.map(session, users))
        stats = service.stats()
    finally:
        service.close()

    served_from = stats["served_from"]
    requests = 6 * len(users)
    check(stats["requests"] == requests and stats["answered"] == requests,
          f"{stats['answered']} of {stats['requests']} requests answered, sent {requests}")
    check(
        stats["errors"] == stats["shed"] == stats["deadline_misses"]
        == stats["circuit_refusals"] == stats["degraded"] == 0,
        f"errors/sheds/misses/refusals/degraded: {stats}",
    )
    check(
        served_from == {"cold": len(users), "advance": 2 * len(users),
                        "hit": 3 * len(users), "fallback": 0},
        f"served_from {served_from}",
    )
    for user, responses in sessions.items():
        kinds = [r.served_from for r in responses]
        check(kinds == ["cold", "advance", "advance", "hit", "hit", "hit"], f"user {user}: {kinds}")
        check(all(r.served_by == "primary" for r in responses), f"user {user} was degraded")

    # three users: what the service answers from its cached state is what the
    # trainer predicts from the same history
    compared = users[:3]
    windows = [make_window(histories[u] + fresh[u], sizes.seq_len) for u in compared]
    batch = {
        "feature_tensors": {"item_id": np.stack([w[0] for w in windows])},
        "padding_mask": np.stack([w[1] for w in windows]),
    }
    _, want_ids, want_scores = trainer.predict_top_k(state, batch, k=10)
    # the service encodes at batch bucket 8 and the trainer at batch 3, both in
    # bf16: XLA may round the same logit differently in the two programs (8-bit
    # mantissa, 2^-8 relative), so near-ties may swap. Held to: the same score
    # for the same item, and every predicted item inside the served top-10 up
    # to such a tie.
    tolerance = 2.0 ** -6
    exact = 0
    worst = 0.0
    for row, user in enumerate(compared):
        full, cut = sessions[user][3], sessions[user][4]
        served_ids, _ = top_k_cut(cut, 10)
        check(full.item_ids is None and full.scores.shape[0] >= sizes.num_items,
              f"full-catalog response has {full.scores.shape} scores")
        scale = float(np.max(np.abs(want_scores[row])))
        difference = float(np.max(np.abs(full.scores[want_ids[row]] - want_scores[row])))
        worst = max(worst, difference / scale)
        kth = np.sort(full.scores[: sizes.num_items])[-10]
        check(difference <= tolerance * scale, f"user {user}: served scores differ by {difference}")
        check(
            (full.scores[want_ids[row]] >= kth - tolerance * scale).all(),
            f"user {user}: predicted top-10 {want_ids[row]} is not the served {served_ids}",
        )
        exact += int(list(served_ids) == list(want_ids[row]))
    leftover = [t.name for t in threading.enumerate()
                if t is not threading.main_thread() and not t.daemon]
    check(not leftover, f"threads still alive after close(): {leftover}")
    engine = stats["engine"]
    emit(
        "serve", started, construct_seconds=round(construct_seconds, 3),
        requests=requests, served_from=served_from,
        engine={k: engine[k] for k in sorted(engine) if isinstance(engine[k], (int, float))},
        batch_fill_ratio=stats["batch_fill_ratio"],
        queue_wait_ms_mean=stats["queue_wait_ms_mean"],
        top10_identical_users=exact, top10_compared_users=len(compared),
        max_score_difference_over_scale=worst, tolerance=tolerance,
    )


def run_one_chip(sizes: Sizes, seed: int, interpret_ok: bool, started_process: float) -> None:
    started = time.perf_counter()
    data = prepare_data(sizes, seed)
    emit("data", started, rows=data["rows"], users=len(data["train_seq"]), items=sizes.num_items)
    trainer, state, source = phase_fit(sizes, data, seed, started_process)
    phase_fit_fused(sizes, data, seed, trainer, state, source, interpret_ok)
    phase_attention_tiled(sizes, seed, interpret_ok)
    phase_eval_predict(sizes, data, trainer, state)
    phase_serve(sizes, data, trainer, state, seed)


# --------------------------------------------------------------------------- #
# --chips 4: the sharded legs and the one-chip leg they are compared with
# --------------------------------------------------------------------------- #
def seeded_batches(sizes: Sizes, num_items: int, seed: int, batch: int, seq_len: int, steps: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        items = rng.integers(0, num_items, size=(batch, seq_len + 1)).astype(np.int32)
        lengths = rng.integers(seq_len // 2, seq_len + 1, size=batch)
        mask = np.arange(seq_len)[None, :] >= (seq_len - lengths)[:, None]  # left-padded
        out.append({
            "feature_tensors": {"item_id": items[:, :-1]},
            "padding_mask": mask,
            "positive_labels": items[:, 1:, None],
            "target_padding_mask": mask[:, :, None],
        })
    return out


def shard_shapes(array) -> dict:
    """{device id: shape held} of a placed array, from its addressable shards."""
    return {shard.device.id: tuple(shard.data.shape) for shard in array.addressable_shards}


def run_leg(name, trainer, batches, expect_mask_shard, expect_table_rows=None):
    """A few ``train_step``s on seeded global batches; placement asserted on
    the devices the mesh names, not on what the rule table says it wanted.
    ``expect_mask_shard`` is the ``[rows, positions]`` of the batch that each
    device must hold."""
    import jax
    import numpy as np

    from replay_tpu.parallel.introspect import sharding_report

    started = time.perf_counter()
    mesh_devices = {d.id for d in np.asarray(trainer.mesh.devices).reshape(-1)}
    state = trainer.init_state(batches[0])
    # the trainer's own placement of a host batch (what train_step does first)
    placed = trainer._put_batch(batches[0])
    batch_shards = shard_shapes(placed["padding_mask"])
    check(
        set(batch_shards) == mesh_devices and set(batch_shards.values()) == {expect_mask_shard},
        f"{name}: batch shard per device {batch_shards}, wanted {expect_mask_shard} on {mesh_devices}",
    )
    tables = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]:
        held = {shard.device.id for shard in leaf.addressable_shards}
        check(held == mesh_devices, f"{name}: {jax.tree_util.keystr(path)} lives on {held} only")
        if "embedding_" in jax.tree_util.keystr(path) and leaf.ndim == 2:
            tables[jax.tree_util.keystr(path)] = sorted(
                {shape[0] for shape in shard_shapes(leaf).values()}
            )
    if expect_table_rows is not None:
        check(
            tables and all(rows == [expect_table_rows] for rows in tables.values()),
            f"{name}: item-table rows per shard {tables}, wanted {expect_table_rows}",
        )
    report = sharding_report(state.params, mesh=trainer.mesh, rules=trainer.sharding_rules)
    check(not report["flags"], f"{name}: accidental replication {report['flags']}")
    losses = []
    for batch in batches:
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    check(np.isfinite(losses).all() and int(state.bad_steps) == 0, f"{name}: losses {losses}")
    emit(
        f"mesh_{name}", started, mesh={k: int(v) for k, v in trainer.mesh.shape.items()},
        losses=losses, batch_shard_per_device=batch_shards, table_rows_per_shard=tables,
        sharded_bytes=report["sharded_bytes"], replicated_bytes=report["replicated_bytes"],
        compile_seconds=round(trainer.compile_tracker.total_compile_seconds, 3),
    )
    return losses


def check_losses(name: str, got, want, rtol: float) -> float:
    import numpy as np

    worst = float(np.max(np.abs(np.asarray(got) - np.asarray(want)) / np.abs(np.asarray(want))))
    check(worst <= rtol, f"{name}: losses {got} vs one chip {want}: relative {worst:.2e} > {rtol}")
    return worst


def run_four_chips(sizes: Sizes, seed: int, interpret_ok: bool) -> None:
    import jax

    from replay_tpu.nn import make_mesh
    from replay_tpu.nn.loss import CE, CEFusedTP
    from replay_tpu.parallel.introspect import collective_inventory, summarize_collectives

    started = time.perf_counter()
    # The embedding table has cardinality + 1 rows (the padding row). ML-20M's
    # 27,278 items make 27,279 rows, which no even `model` axis divides: the
    # rule table then warns once and REPLICATES the table (PERF.md Findings).
    # These legs hold one item fewer, so the table is 27,278 rows and each of
    # the two `model` shards holds 13,639.
    num_items = sizes.num_items - 1
    table_rows = num_items + 1
    schema = fixed_schema(sizes, num_items)
    batches = seeded_batches(sizes, num_items, seed, sizes.batch, sizes.seq_len, sizes.mesh_steps)

    one = run_leg(
        "one_chip", make_trainer(sizes, schema, CE(), seed, mesh=make_mesh(jax.devices()[:1])),
        batches, (sizes.batch, sizes.seq_len),
    )
    dp4 = run_leg(
        "dp4", make_trainer(sizes, schema, CE(), seed, mesh=make_mesh()),
        batches, (sizes.batch // 4, sizes.seq_len),
    )
    tp_trainer = make_trainer(
        sizes, schema, CEFusedTP(), seed, mesh=make_mesh(model_parallel=2), shard_vocab=True
    )
    dp2tp2 = run_leg(
        "dp2_tp2", tp_trainer, batches, (sizes.batch // 2, sizes.seq_len), table_rows // 2
    )

    hlo = tp_trainer.lowered_hlo("train_step")
    check("tpu_custom_call" in hlo or interpret_ok, "no tpu_custom_call in the DP2xTP2 step")
    inventory = collective_inventory(hlo, {k: int(v) for k, v in tp_trainer.mesh.shape.items()})
    shard_bytes = table_rows // 2 * sizes.embedding_dim * 4
    gathers = [e for e in inventory if e["op"] == "all-gather" and (e["bytes"] or 0) >= shard_bytes]
    check(not gathers, f"DP2xTP2 step all-gathers table-shard-sized tensors: {gathers}")

    # tests/parallel/test_mesh_training.py holds f32 SGD losses to rtol 2e-4.
    # Here compute is bf16 (2^-8 per rounding) under Adam, and a different mesh
    # changes the order of every cross-device sum, so the band is widened to
    # 1e-3: the four chips measured 1.1e-5 to 1.3e-5 (PR 21), and a leg that
    # drops or double-counts a shard is off by far more than that.
    rtol = 1e-3
    emit(
        "mesh_compare", started, rtol=rtol,
        dp4_vs_one_chip=check_losses("dp4", dp4, one, rtol),
        dp2_tp2_vs_one_chip=check_losses("dp2_tp2", dp2tp2, one, rtol),
        table_shard_gathers=len(gathers),
        collectives=summarize_collectives(inventory)["by_op"],
    )
    run_ring_leg(sizes, schema, num_items, seed, rtol)


def run_ring_leg(sizes: Sizes, schema, num_items: int, seed: int, rtol: float) -> None:
    """(d) SP4: the sequence sharded over four chips, attention through the
    ring, against the dense XLA route on one chip."""
    import jax

    from replay_tpu.nn import make_mesh
    from replay_tpu.nn.loss import CE

    started = time.perf_counter()
    batch, seq_len = sizes.sp_shape
    batches = seeded_batches(sizes, num_items, seed + 1, batch, seq_len, 2)
    dense = run_leg(
        "sp_one_chip",
        make_trainer(sizes, schema, CE(), seed, seq_len=seq_len, mesh=make_mesh(jax.devices()[:1])),
        batches, (batch, seq_len),
    )
    ring = run_leg(
        "sp4_ring",
        make_trainer(sizes, schema, CE(), seed, seq_len=seq_len, use_flash="ring",
                     mesh=make_mesh(seq_parallel=4)),
        batches, (batch, seq_len // 4),
    )
    emit("mesh_compare_ring", started, rtol=rtol, seq_len=seq_len,
         sp4_ring_vs_one_chip=check_losses("sp4_ring", ring, dense, rtol))


def main() -> int:
    started_process = time.time()
    args = parse_args()
    if args.rehearse and args.chips > 1:
        # virtual CPU devices must be asked for before jax is imported
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={args.chips}".strip()
            )
    started = time.perf_counter()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not (args.rehearse and platform == "cpu"):
        print(
            f"chip_smoke: JAX found no TPU (platform {platform!r}, {len(devices)} device(s)); "
            "this script has no CPU path — run it through the chip tool",
            file=sys.stderr,
        )
        return 1
    if len(devices) != args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs exactly {args.chips} device(s), "
            f"JAX reports {len(devices)}",
            file=sys.stderr,
        )
        return 1

    import jaxlib

    # generated code never travels: whatever native artifact the tree holds is
    # removed, so the one phase `fit` loads was built from this ragged.cpp
    for stale in (Path(__file__).resolve().parent / "replay_tpu" / "native").glob("_ragged*.so"):
        stale.unlink()

    from replay_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    device = {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}
    emit(
        "device", started, **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, compile_cache_dir=cache_dir,
        compile_cache_entries=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
        rehearsal=args.rehearse, seed=args.seed,
    )

    sizes = REHEARSAL if args.rehearse else Sizes()
    interpret_ok = platform == "cpu"  # only a rehearsal may interpret the kernels
    if args.chips == 4:
        run_four_chips(sizes, args.seed, interpret_ok)
    else:
        run_one_chip(sizes, args.seed, interpret_ok, started_process)

    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}), flush=True)
        return REHEARSAL_EXIT_CODE
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
