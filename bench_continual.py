"""Continual-training replay harness: fine-tune-on-the-tail vs full retrain.

The training-quality half of the promotion loop (docs/robustness.md
"Zero-downtime swaps and canary promotion"; the serving half — swap-under-load
— lives in ``bench_serve.py``'s ``REPLAY_TPU_SERVE_SWAPS`` phase). Simulates
``DAYS`` days of interactions over a catalog that GROWS mid-stream (new items
appear on a schedule, the production shape vocab surgery exists for), then
replays the stream time-sliced:

* **continual** — ONE model rides the whole stream: each day it fine-tunes on
  just that day's interaction tail via ``Trainer.finetune`` (optimizer-state-
  safe catalog growth with xavier cold rows, Adam moments carried), exactly
  what the promotion driver ships to the serving canary;
* **full retrain** — the baseline: every day a FRESH model trains from
  scratch on all interactions seen so far.

Both are scored on the NEXT day's held-out events (NDCG@K / recall@K against
each user's true next item), so the comparison is honestly prequential: no
model ever sees its evaluation day. Prints ONE JSON line in bench.py's
record format::

    {"metric": "continual_vs_retrain_ndcg", "value": <ratio>,
     "continual_ndcg": ..., "retrain_ndcg": ..., "continual_fit_seconds": ...,
     "retrain_fit_seconds": ..., "days": ..., "catalog_start": ...,
     "catalog_end": ..., "per_day": [...], "backend": ...}

``value`` is mean(continual NDCG) / mean(retrain NDCG): ≈1.0 means the cheap
tail fine-tune holds the full retrain's quality; the record also carries the
fit-time ratio (the whole point — continual spends a fraction of the compute).
``REPLAY_TPU_CONTINUAL_*`` env vars override every knob (CI runs tiny
shapes); events land in ``runs/bench_continual/`` for ``obs.report``.

Backend policy mirrors bench.py: one process on the backend JAX gives it, the
record stamped with ``platform`` / ``device_kind`` / ``device_count``; on the
CPU the metric is renamed ``*_cpu_fallback``.
"""

import json
import os
import time

import numpy as np

from replay_tpu.utils.compile_cache import enable_compile_cache

_DEFAULTS = {
    "DAYS": 5,
    "USERS": 96,
    "EVENTS_PER_DAY": 6,  # interactions per user per day
    "ITEMS": 60,  # starting catalog
    "GROW_ITEMS": 12,  # new items introduced at each growth day
    "GROW_EVERY": 2,  # a growth every N days
    "SEQ_LEN": 16,
    "EMBEDDING_DIM": 16,
    "NUM_BLOCKS": 1,
    "BATCH": 32,
    "TAIL_EPOCHS": 2,  # continual: epochs over ONE day's tail
    "RETRAIN_EPOCHS": 2,  # baseline: epochs over the FULL history
    "TOPK": 10,
}


def _knob(name: str) -> int:
    return int(os.environ.get(f"REPLAY_TPU_CONTINUAL_{name}", _DEFAULTS[name]))


DAYS = _knob("DAYS")
USERS = _knob("USERS")
EVENTS_PER_DAY = _knob("EVENTS_PER_DAY")
ITEMS = _knob("ITEMS")
GROW_ITEMS = _knob("GROW_ITEMS")
GROW_EVERY = _knob("GROW_EVERY")
SEQ_LEN = _knob("SEQ_LEN")
EMBEDDING_DIM = _knob("EMBEDDING_DIM")
NUM_BLOCKS = _knob("NUM_BLOCKS")
BATCH = _knob("BATCH")
TAIL_EPOCHS = _knob("TAIL_EPOCHS")
RETRAIN_EPOCHS = _knob("RETRAIN_EPOCHS")
TOPK = _knob("TOPK")
SHAPE_OVERRIDE = any(_knob(k) != v for k, v in _DEFAULTS.items())

RUN_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "runs", "bench_continual"
)


def simulate_stream(rng):
    """Per-user, per-day interaction lists over a GROWING catalog.

    The behavior has learnable structure (a noisy successor pattern over the
    catalog available that day) so NDCG separates trained from untrained —
    and new items enter the pattern the day they appear, which is exactly the
    cold-start the xavier warm-start rows must absorb."""
    catalog = ITEMS
    events = []  # events[day][user] -> list[int]
    catalog_by_day = []
    state = rng.integers(0, ITEMS, size=USERS)
    for day in range(DAYS):
        if day > 0 and GROW_EVERY > 0 and day % GROW_EVERY == 0:
            catalog += GROW_ITEMS
        catalog_by_day.append(catalog)
        day_events = []
        for user in range(USERS):
            items = []
            current = int(state[user])
            for _ in range(EVENTS_PER_DAY):
                if rng.random() < 0.2:
                    current = int(rng.integers(0, catalog))
                else:
                    current = (current * 3 + 7) % catalog
                items.append(current)
            state[user] = current
            day_events.append(items)
        events.append(day_events)
    return events, catalog_by_day


def _window(items, length):
    window = np.zeros(length, np.int32)
    count = min(len(items), length)
    if count:
        window[length - count:] = np.asarray(items[-count:], np.int32)
    mask = np.zeros(length, bool)
    mask[length - count:] = True
    return window, mask


def train_batches(histories, rng):
    """Fixed-shape [B, L] next-item training batches from per-user histories
    (right-aligned windows, shifted-label CE like SequenceBatcher's)."""
    users = [u for u, h in enumerate(histories) if len(h) >= 2]
    rng.shuffle(users)
    batches = []
    for start in range(0, len(users), BATCH):
        chunk = users[start:start + BATCH]
        rows_ids, rows_mask = [], []
        for user in chunk:
            window, mask = _window(histories[user], SEQ_LEN + 1)
            rows_ids.append(window)
            rows_mask.append(mask)
        ids = np.stack(rows_ids)
        mask = np.stack(rows_mask)
        valid = np.zeros(BATCH, bool)
        valid[: len(chunk)] = True
        if len(chunk) < BATCH:  # static shapes: pad the final batch, mask rows
            pad = BATCH - len(chunk)
            ids = np.concatenate([ids, np.repeat(ids[:1], pad, 0)])
            mask = np.concatenate([mask, np.zeros((pad, SEQ_LEN + 1), bool)])
        batches.append(
            {
                "feature_tensors": {"item_id": ids[:, :-1]},
                "padding_mask": mask[:, :-1],
                "positive_labels": ids[:, 1:, None],
                "target_padding_mask": (mask[:, :-1] & mask[:, 1:])[:, :, None],
                "valid": valid,
            }
        )
    return batches


def eval_batches(histories, next_day_events):
    """Prequential eval: each user's history window vs their TRUE first
    interaction of the next day."""
    rows_ids, rows_mask, truths = [], [], []
    for user, history in enumerate(histories):
        if not history or not next_day_events[user]:
            continue
        window, mask = _window(history, SEQ_LEN)
        rows_ids.append(window)
        rows_mask.append(mask)
        truths.append(next_day_events[user][0])
    batches = []
    for start in range(0, len(rows_ids), BATCH):
        ids = np.stack(rows_ids[start:start + BATCH])
        mask = np.stack(rows_mask[start:start + BATCH])
        gt = np.asarray(truths[start:start + BATCH], np.int32)[:, None]
        rows = ids.shape[0]
        valid = np.zeros(BATCH, bool)
        valid[:rows] = True
        if rows < BATCH:
            pad = BATCH - rows
            ids = np.concatenate([ids, np.repeat(ids[:1], pad, 0)])
            mask = np.concatenate([mask, np.repeat(mask[:1], pad, 0)])
            gt = np.concatenate([gt, np.repeat(gt[:1], pad, 0)])
        batches.append(
            {
                "feature_tensors": {"item_id": ids},
                "padding_mask": mask,
                "ground_truth": gt,
                "valid": valid,
            }
        )
    return batches


def main() -> None:
    enable_compile_cache()
    import jax

    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn import OptimizerFactory, Trainer
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.obs import JsonlLogger

    rng = np.random.default_rng(0)
    events, catalog_by_day = simulate_stream(rng)

    def make_trainer(cardinality):
        schema = TensorSchema(
            TensorFeatureInfo(
                "item_id", FeatureType.CATEGORICAL, is_seq=True,
                feature_hint=FeatureHint.ITEM_ID, cardinality=cardinality,
                embedding_dim=EMBEDDING_DIM,
            )
        )
        model = SasRec(
            schema=schema, embedding_dim=EMBEDDING_DIM, num_blocks=NUM_BLOCKS,
            num_heads=1, max_sequence_length=SEQ_LEN, dropout_rate=0.0,
        )
        return Trainer(
            model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2)
        )

    logger = JsonlLogger(RUN_DIR, mode="w")
    continual_trainer = make_trainer(catalog_by_day[0])
    continual_state = None
    continual_fit_seconds = 0.0
    retrain_fit_seconds = 0.0
    per_day = []
    histories = [[] for _ in range(USERS)]

    for day in range(DAYS - 1):
        tail = [list(day_user) for day_user in events[day]]
        for user in range(USERS):
            histories[user].extend(tail[user])
        catalog = catalog_by_day[day]
        metric_names = ("ndcg", "recall")

        # ---- continual: fine-tune the ONE model on the fresh tail --------- #
        started = time.perf_counter()
        tail_batches = train_batches(
            [h[-(SEQ_LEN + 1):] for h in histories], np.random.default_rng(100 + day)
        )
        if continual_state is None:
            continual_state = continual_trainer.fit(tail_batches, epochs=TAIL_EPOCHS)
        else:
            continual_state = continual_trainer.finetune(
                continual_state, tail_batches,
                new_cardinality=(
                    catalog
                    if catalog > continual_trainer.model.schema["item_id"].cardinality
                    else None
                ),
                epochs=TAIL_EPOCHS,
            )
        continual_fit_seconds += time.perf_counter() - started

        # ---- baseline: a fresh model over the FULL history ---------------- #
        started = time.perf_counter()
        retrain_trainer = make_trainer(catalog)
        full_batches = train_batches(histories, np.random.default_rng(200 + day))
        retrain_state = retrain_trainer.fit(full_batches, epochs=RETRAIN_EPOCHS)
        retrain_fit_seconds += time.perf_counter() - started

        # ---- prequential eval on the NEXT day ----------------------------- #
        evals = eval_batches(histories, events[day + 1])
        continual_metrics = continual_trainer.validate(
            continual_state, evals, metrics=metric_names, top_k=(TOPK,)
        )
        retrain_metrics = retrain_trainer.validate(
            retrain_state, evals, metrics=metric_names, top_k=(TOPK,)
        )
        day_record = {
            "event": "continual_day",
            "day": day,
            "catalog": catalog,
            "continual_ndcg": float(continual_metrics[f"ndcg@{TOPK}"]),
            "retrain_ndcg": float(retrain_metrics[f"ndcg@{TOPK}"]),
            "continual_recall": float(continual_metrics[f"recall@{TOPK}"]),
            "retrain_recall": float(retrain_metrics[f"recall@{TOPK}"]),
        }
        per_day.append(day_record)
        logger.log_record(day_record)

    continual_ndcg = float(np.mean([d["continual_ndcg"] for d in per_day]))
    retrain_ndcg = float(np.mean([d["retrain_ndcg"] for d in per_day]))
    metric = "continual_vs_retrain_ndcg"
    if jax.default_backend() == "cpu":
        metric += "_cpu_fallback"
    record = {
        "metric": metric,
        "value": round(continual_ndcg / retrain_ndcg, 4) if retrain_ndcg else None,
        "unit": "ratio",
        "continual_ndcg": round(continual_ndcg, 4),
        "retrain_ndcg": round(retrain_ndcg, 4),
        "continual_fit_seconds": round(continual_fit_seconds, 2),
        "retrain_fit_seconds": round(retrain_fit_seconds, 2),
        "fit_time_ratio": (
            round(continual_fit_seconds / retrain_fit_seconds, 4)
            if retrain_fit_seconds
            else None
        ),
        "days": DAYS,
        "users": USERS,
        "catalog_start": catalog_by_day[0],
        "catalog_end": catalog_by_day[-1],
        "topk": TOPK,
        "per_day": per_day,
        "backend": jax.default_backend(),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
    }
    if SHAPE_OVERRIDE:
        record["shape_override"] = {
            "days": DAYS, "users": USERS, "items": ITEMS, "L": SEQ_LEN,
            "d": EMBEDDING_DIM,
        }
    logger.log_record(record)
    logger.close()
    print(json.dumps(record))


if __name__ == "__main__":
    main()
