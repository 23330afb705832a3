"""bench_serve.py emits one parseable JSON record with finite serving metrics —
and, with overload + chaos enabled, the resilience accounting the acceptance
criteria gate on (bounded p99 with nonzero shed, zero hung futures, a breaker
that opens and recovers)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_bench_serve_one_json_line(tmp_path):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "REPLAY_TPU_SERVE_SEQ_LEN": "8",
        "REPLAY_TPU_SERVE_NUM_ITEMS": "30",
        "REPLAY_TPU_SERVE_EMBEDDING_DIM": "8",
        "REPLAY_TPU_SERVE_NUM_BLOCKS": "1",
        "REPLAY_TPU_SERVE_USERS": "12",
        "REPLAY_TPU_SERVE_CLIENTS": "2",
        "REPLAY_TPU_SERVE_CLOSED_REQUESTS": "8",
        "REPLAY_TPU_SERVE_RATE": "200",
        "REPLAY_TPU_SERVE_SECONDS": "1",
        "REPLAY_TPU_SERVE_CANDIDATES": "10",
        "REPLAY_TPU_SERVE_TOPK": "3",
        "REPLAY_TPU_SERVE_BATCH_BUCKETS": "1,4",
        # resilience phases: open-loop overload at 4x measured capacity with
        # per-request deadlines, then deterministic chaos injection; the swap
        # phase runs BEFORE them (its zero-error claim must stay unpolluted)
        "REPLAY_TPU_SERVE_CHAOS": "1",
        "REPLAY_TPU_SERVE_OVERLOAD_SECONDS": "1",
        "REPLAY_TPU_SERVE_SWAPS": "2",
        "REPLAY_TPU_SERVE_SWAP_GAP_MS": "100",
        # the tiny CPU model outruns a single open-loop generator thread, so
        # admission control must be made reachable: tight lanes + a high
        # factor (the default 4x/auto-depth shape is for real configs)
        "REPLAY_TPU_SERVE_MAX_DEPTH": "4",
        "REPLAY_TPU_SERVE_OVERLOAD_FACTOR": "16",
        "REPLAY_TPU_SERVE_DEADLINE_MS": "150",
        "REPLAY_TPU_SERVE_BREAKER_THRESHOLD": "3",
        "REPLAY_TPU_SERVE_BREAKER_RESET_MS": "100",
    }
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench_serve.py")],
        capture_output=True,
        timeout=300,
        env=env,
        cwd=str(tmp_path),  # run dir artifacts land under the repo, record on stdout
        check=False,
    )
    assert out.returncode == 0, out.stderr.decode()
    record = json.loads(out.stdout.decode().strip().splitlines()[-1])
    assert record["metric"] == "serve_qps_cpu_fallback"
    assert record["unit"] == "req/s"
    for key in ("qps", "p50_ms", "p95_ms", "p99_ms", "closed_loop_qps"):
        assert isinstance(record[key], (int, float)) and record[key] > 0, key
    assert record["p50_ms"] <= record["p95_ms"] <= record["p99_ms"]
    assert 0.0 < record["batch_fill_ratio"] <= 1.0
    assert 0.0 <= record["cache_hit_rate"] <= 1.0
    assert record["request_errors"] == 0
    assert record["mode"] == "retrieval"
    assert record["shape_override"]["L"] == 8

    # run-wide resilience rates (the --compare gate inputs) are present/finite
    for key in ("serve_shed_rate", "serve_deadline_miss_rate", "serve_error_rate"):
        assert 0.0 <= record[key] <= 1.0, key
    assert record["hung_requests"] == 0

    # overload: arrivals ≫ capacity, bounded lanes must shed or drop expired
    # waiters — and p99 of COMPLETED requests stays bounded (nothing can queue
    # past its deadline, so latency is capped near deadline + one dispatch)
    overload = record["overload"]
    refused = (
        overload["shed"] + overload["deadline_missed"] + overload["circuit_refused"]
    )
    assert refused > 0, overload
    assert overload["submitted"] > overload["completed"]
    assert overload["hung_requests"] == 0
    assert overload["p99_ms"] <= 150 + 1000, overload  # deadline + slack, not ∞
    assert overload["errors"] == 0

    # swap under load (serve.promote): N hot swaps completed with ZERO request
    # errors, every swap a zero-recompile pointer move, p99 bounded/finite,
    # and the generation tags observed prove both sides of each swap served
    swap = record["swap"]
    assert swap["swaps"] == 2
    assert swap["errors"] == 0, swap["first_error"]
    assert swap["recompiled_swaps"] == 0  # same shapes: never recompiled
    assert swap["answered"] > 0
    assert swap["p99_ms"] > 0 and swap["p99_ms"] < 120_000
    assert swap["generations_seen"] >= 1
    assert swap["final_generation"] == 2
    assert swap["swap_apply_ms_max"] > 0

    # chaos: injected engine faults tripped the breaker, degraded traffic is
    # tagged, the breaker re-closed, and no future was left hanging
    chaos = record["chaos"]
    assert chaos["injected_engine_errors"] == 3
    assert chaos["breaker_opens"] >= 1
    assert chaos["breaker_state_after_trip"] == "open"
    assert chaos["recovered"] is True
    assert chaos["breaker_state_final"] == "closed"
    assert chaos["served_by_seen"]["advance_while_open"] == "cache_only"
    assert chaos["served_by_seen"]["cold_while_open"] == "fallback"
    assert chaos["client_abandoned"] == 1
    assert chaos["storm_deadline_missed"] > 0
    assert chaos["hung_requests"] == 0
    assert record["breaker"]["opens"] >= 1
