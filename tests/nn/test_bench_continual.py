"""bench_continual.py emits one parseable JSON record: continual (tail
fine-tune with mid-stream catalog growth) vs full-retrain NDCG, prequentially
scored on the next day's events."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_bench_continual_one_json_line(tmp_path):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "REPLAY_TPU_CONTINUAL_DAYS": "3",
        "REPLAY_TPU_CONTINUAL_USERS": "24",
        "REPLAY_TPU_CONTINUAL_ITEMS": "24",
        "REPLAY_TPU_CONTINUAL_GROW_ITEMS": "8",
        "REPLAY_TPU_CONTINUAL_GROW_EVERY": "2",
        "REPLAY_TPU_CONTINUAL_SEQ_LEN": "8",
        "REPLAY_TPU_CONTINUAL_EMBEDDING_DIM": "8",
        "REPLAY_TPU_CONTINUAL_BATCH": "16",
        "REPLAY_TPU_CONTINUAL_TAIL_EPOCHS": "1",
        "REPLAY_TPU_CONTINUAL_RETRAIN_EPOCHS": "1",
    }
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench_continual.py")],
        capture_output=True,
        timeout=300,
        env=env,
        cwd=str(tmp_path),
        check=False,
    )
    assert out.returncode == 0, out.stderr.decode()
    record = json.loads(out.stdout.decode().strip().splitlines()[-1])
    assert record["metric"] == "continual_vs_retrain_ndcg_cpu_fallback"
    assert record["unit"] == "ratio"
    assert record["value"] is not None and record["value"] > 0
    for key in ("continual_ndcg", "retrain_ndcg"):
        assert 0.0 <= record[key] <= 1.0, key
    assert record["continual_fit_seconds"] > 0
    assert record["retrain_fit_seconds"] > 0
    # the catalog actually GREW mid-stream (day 2 is a growth day) and the
    # continual model absorbed it via optimizer-state-safe surgery
    assert record["catalog_end"] > record["catalog_start"]
    assert len(record["per_day"]) == 2
    assert record["shape_override"]["days"] == 3
