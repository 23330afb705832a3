"""Traffic generation for the ``fit`` kind: a seeded synthetic interaction log.

One general generator; a traffic file's ``history`` group gives its parameters.
Per-user walks over the catalog with Zipf-popular restarts: learnable (the next
item is the previous + 1 most of the time) and covering EVERY item, so that the
tokenizer's catalog is exactly ``num_items``. Every seed gets the SAME multiset
of history lengths (evenly spread over ``min_events..max_events``) in another
order, so the rows and events of an epoch do not change with the seed.
Copied from ``chip_smoke.synthetic_log`` (PR 21), which drew the lengths.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np
import pandas as pd


def history_lengths(users: int, history: Mapping[str, Any], rng) -> np.ndarray:
    if history.get("distribution", "uniform") != "uniform":
        raise ValueError(f"unknown history distribution {history['distribution']!r}")
    low, high = int(history["min_events"]), int(history["max_events"])
    spread = low + (np.arange(users) * (high - low + 1)) // users
    return rng.permutation(spread)


def synthetic_log(num_items: int, users: int, history: Mapping[str, Any], seed: int):
    rng = np.random.default_rng(seed)
    lengths = history_lengths(users, history, rng)
    total = int(lengths.sum())
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    user = np.repeat(np.arange(users), lengths)
    position = np.arange(total) - starts[user]

    popularity = 1.0 / (np.arange(num_items) + float(history.get("popularity_offset", 10)))
    jump = rng.random(total) < float(history.get("jump_prob", 0.2))
    # the first `stride` events of user u walk from u * stride without a jump:
    # users * stride >= num_items, so together they visit the whole catalog
    stride = -(-num_items // users)
    if int(history["min_events"]) <= stride + 1:
        raise ValueError("histories too short to cover the catalog")
    jump[position < stride] = False
    jump[position == 0] = True
    target = rng.choice(num_items, size=total, p=popularity / popularity.sum())
    target[position == 0] = (np.arange(users) * stride) % num_items
    last_jump = np.maximum.accumulate(np.where(jump, np.arange(total), 0))
    item = (target[last_jump] + np.arange(total) - last_jump) % num_items
    return pd.DataFrame({"user_id": user, "item_id": item, "timestamp": position})
