"""Run-report CLI (obs.report): summaries, compare mode, exit codes.

Core tier, no jax: the CLI is import-light by contract. Fixtures mimic the
three artifact shapes it must digest — a fit run's ``events.jsonl`` (+
``trace.json``), a ``dryrun_multichip`` record, and a single-record bench
JSON — plus a malformed stream that must fail loudly (CI's "our artifacts
still parse" gate).
"""

import json
import os
import subprocess
import sys

import pytest

from replay_tpu.obs.report import compare_runs, load_events, main, summarize_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _write_fit_run(path, samples_per_sec=1000.0, retraces=0, goodput_train=0.8):
    os.makedirs(path, exist_ok=True)
    spans = {
        "data_wait": 0.05,
        "h2d": 0.02,
        "compile": 0.05,
        "train_step": goodput_train,
        "validation": 0.0,
        "checkpoint": 0.0,
        "recovery": 0.0,
    }
    goodput = {
        "wall_seconds": 1.0,
        "fractions": {**spans, "other": 1.0 - sum(spans.values())},
        "input_starvation": 0.05,
    }
    events = [
        {"event": "on_fit_start", "time": 1.0, "epoch": 0, "epochs": 1},
        *(
            {
                "event": "on_train_step", "time": 1.0 + i, "step": i + 1, "epoch": 0,
                "loss": 2.5 - 0.1 * i, "lr": 1e-3,
                "samples_per_sec": samples_per_sec, "steps_per_sec": samples_per_sec / 8,
                "step_seconds": 8 / samples_per_sec,
            }
            for i in range(3)
        ),
        {"event": "on_anomaly", "time": 4.5, "step": 3, "epoch": 0, "loss": None,
         "grad_norm": None, "consecutive_bad": 1},
        {"event": "on_epoch_end", "time": 5.0, "step": 3, "epoch": 0,
         "record": {"epoch": 0, "train_loss": 2.31}, "goodput": goodput},
        {"event": "on_fit_end", "time": 6.0, "step": 3,
         "telemetry": {"steps": 2.0, "elapsed_seconds": 0.5,
                       "steps_per_sec": samples_per_sec / 8,
                       "samples_per_sec": samples_per_sec},
         "compile": {"train_step": {"traces": 1 + retraces, "compile_seconds": 0.9}},
         "peak_memory_bytes": None, "history_len": 1, "bad_steps": 1,
         "goodput": goodput},
    ]
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        for event in events:
            fh.write(json.dumps(event) + "\n")
    return path


def _write_trace(path, names=("data_wait", "train_step")):
    payload = {
        "traceEvents": [
            {"name": name, "cat": "host", "ph": "X", "ts": 10.0 * i, "dur": 5.0,
             "pid": 1, "tid": 1}
            for i, name in enumerate(names)
        ],
        "displayTimeUnit": "ms",
    }
    with open(os.path.join(path, "trace.json"), "w") as fh:
        json.dump(payload, fh)


# --------------------------------------------------------------------------- #
# summaries
# --------------------------------------------------------------------------- #
def test_summarize_fit_run(tmp_path):
    run = _write_fit_run(str(tmp_path / "run"))
    _write_trace(run)
    summary = summarize_run(run)
    assert summary["kind"] == "fit"
    assert summary["samples_per_sec"] == pytest.approx(1000.0)
    assert summary["throughput_source"] == "telemetry"
    assert summary["final_train_loss"] == pytest.approx(2.31)
    assert summary["retraces"] == 0 and summary["bad_steps"] == 1
    assert summary["anomalies"] == 1
    assert summary["goodput"]["fractions"]["train_step"] == pytest.approx(0.8)
    assert summary["trace"]["train_step"]["count"] == 1


def test_report_cli_renders_fit_run(tmp_path, capsys):
    run = _write_fit_run(str(tmp_path / "run"))
    _write_trace(run)
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "throughput" in out and "1000.0 samples/sec" in out
    assert "goodput" in out and "input starvation" in out
    assert "trace.json" in out


def test_report_cli_renders_dryrun_record(tmp_path, capsys):
    run = tmp_path / "dry"
    run.mkdir()
    record = {
        "event": "dryrun_multichip", "time": 1.0, "backend": "cpu",
        "mesh": {"data": 4, "model": 2}, "losses": [3.9, 3.7], "psum": 28.0,
        "sp_ring_err": 3.6e-07,
        "compile": {"train_step": {"traces": 1, "compile_seconds": 0.77}},
        "peak_memory_bytes": None,
        "spans": {"train_step": {"count": 2, "seconds": 1.4, "self_seconds": 0.5}},
    }
    (run / "events.jsonl").write_text(json.dumps(record) + "\n")
    assert main([str(run)]) == 0
    out = capsys.readouterr().out
    assert "dryrun_multichip" in out and "mesh={'data': 4, 'model': 2}" in out
    assert "dryrun spans" in out


def test_report_cli_reads_bench_json(tmp_path, capsys):
    bench = tmp_path / "BENCH.json"
    bench.write_text(json.dumps({
        "metric": "sasrec_train_samples_per_sec", "value": 5668.0,
        "unit": "samples/sec", "vs_baseline": 1.0, "backend": "tpu",
        "mfu": 0.41, "compile_seconds": 12.0, "device_kind": "TPU v5e",
    }))
    assert main([str(bench)]) == 0
    out = capsys.readouterr().out
    assert "5668.0 samples/sec" in out and "[bench]" in out
    assert "MFU 0.410" in out


def test_report_json_flag_emits_json(tmp_path, capsys):
    run = _write_fit_run(str(tmp_path / "run"))
    assert main([run, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["samples_per_sec"] == pytest.approx(1000.0)


# --------------------------------------------------------------------------- #
# failure modes: a report that cannot parse its own artifacts must exit non-zero
# --------------------------------------------------------------------------- #
def test_report_malformed_events_fails(tmp_path, capsys):
    run = tmp_path / "bad"
    run.mkdir()
    (run / "events.jsonl").write_text('{"event": "on_fit_start"}\nnot json{{{\n')
    assert main([str(run)]) == 1
    assert "cannot parse" in capsys.readouterr().err


def test_report_missing_run_fails(tmp_path, capsys):
    assert main([str(tmp_path / "nope")]) == 1


def test_report_invalid_trace_fails(tmp_path, capsys):
    run = _write_fit_run(str(tmp_path / "run"))
    with open(os.path.join(run, "trace.json"), "w") as fh:
        json.dump({"traceEvents": [{"ph": "X", "ts": 0}]}, fh)  # no name
    assert main([run]) == 1
    assert "name/ph/ts" in capsys.readouterr().err


def test_load_events_rejects_empty(tmp_path):
    path = tmp_path / "empty.jsonl"
    path.write_text("\n\n")
    with pytest.raises(ValueError, match="no records"):
        load_events(str(path))


# --------------------------------------------------------------------------- #
# compare mode
# --------------------------------------------------------------------------- #
def test_compare_flags_throughput_regression(tmp_path, capsys):
    baseline = _write_fit_run(str(tmp_path / "base"), samples_per_sec=1000.0)
    candidate = _write_fit_run(str(tmp_path / "cand"), samples_per_sec=700.0)
    rc = main([candidate, "--compare", baseline])
    captured = capsys.readouterr()
    assert rc != 0  # ≥20% throughput regression must fail the invocation
    assert "REGRESSION" in captured.err and "samples_per_sec" in captured.err


def test_compare_passes_within_threshold(tmp_path, capsys):
    baseline = _write_fit_run(str(tmp_path / "base"), samples_per_sec=1000.0)
    candidate = _write_fit_run(str(tmp_path / "cand"), samples_per_sec=950.0)
    assert main([candidate, "--compare", baseline]) == 0


def test_compare_threshold_is_tunable(tmp_path):
    baseline = _write_fit_run(str(tmp_path / "base"), samples_per_sec=1000.0)
    candidate = _write_fit_run(str(tmp_path / "cand"), samples_per_sec=700.0)
    assert main([candidate, "--compare", baseline, "--threshold", "0.5"]) == 0


def test_compare_improvement_passes(tmp_path):
    baseline = _write_fit_run(str(tmp_path / "base"), samples_per_sec=700.0)
    candidate = _write_fit_run(str(tmp_path / "cand"), samples_per_sec=1000.0)
    assert main([candidate, "--compare", baseline]) == 0


def test_compare_flags_new_retraces(tmp_path, capsys):
    baseline = _write_fit_run(str(tmp_path / "base"))
    candidate = _write_fit_run(str(tmp_path / "cand"), retraces=3)
    rc = main([candidate, "--compare", baseline])
    assert rc != 0
    assert "retraces increased" in capsys.readouterr().err


def test_compare_against_bench_json(tmp_path):
    """The --compare operand may be a bench record, not a run directory."""
    candidate = _write_fit_run(str(tmp_path / "cand"), samples_per_sec=700.0)
    bench = tmp_path / "BENCH.json"
    bench.write_text(json.dumps({
        "metric": "sasrec_train_samples_per_sec", "value": 1000.0,
        "unit": "samples/sec", "vs_baseline": 0.18, "backend": "cpu",
    }))
    assert main([candidate, "--compare", str(bench)]) != 0


def test_compare_runs_api_reports_goodput_shift(tmp_path):
    baseline = summarize_run(_write_fit_run(str(tmp_path / "base"), goodput_train=0.8))
    candidate = summarize_run(_write_fit_run(str(tmp_path / "cand"), goodput_train=0.5))
    lines, regressions = compare_runs(candidate, baseline)
    assert any("goodput/train_step" in line for line in lines)
    assert regressions == []  # goodput shifts inform; throughput/mfu/retraces gate


# --------------------------------------------------------------------------- #
# module entrypoint
# --------------------------------------------------------------------------- #
def test_python_dash_m_entrypoint(tmp_path):
    run = _write_fit_run(str(tmp_path / "run"))
    proc = subprocess.run(
        [sys.executable, "-m", "replay_tpu.obs.report", run],
        capture_output=True, text=True, timeout=120, cwd=REPO, check=False,
    )
    assert proc.returncode == 0, proc.stderr
    assert "Run report" in proc.stdout


# --------------------------------------------------------------------------- #
# fit-loop bench fields (scan-chunked fit, docs/performance.md "Closing the
# dispatch gap") + h2d-overlap surfacing
# --------------------------------------------------------------------------- #
def _bench_record(**extra):
    return {
        "metric": "sasrec_train_samples_per_sec", "value": 5668.0,
        "unit": "samples/sec", "vs_baseline": 1.0, "backend": "tpu",
        "step_ms": 4.1, "dispatch_step_ms": 10.5, "scan_k": 32,
        **extra,
    }


def _fit_fields(samples=5000.0, chunk=32, feed=True):
    return {
        "fit_samples_per_sec": samples, "fit_step_ms": 5.0,
        "fit_scan_chunk": chunk, "fit_device_feed": feed,
        "dispatch_gap_closed": 0.86,
    }


def test_bench_fit_loop_fields_summarize_and_render(tmp_path, capsys):
    bench = tmp_path / "BENCH.json"
    bench.write_text(json.dumps(_bench_record(**_fit_fields())))
    summary = summarize_run(str(bench))
    assert summary["fit_samples_per_sec"] == pytest.approx(5000.0)
    assert summary["bench"]["fit_scan_chunk"] == 32
    assert summary["bench"]["fit_device_feed"] is True
    assert main([str(bench)]) == 0
    out = capsys.readouterr().out
    assert "fit loop: 5000.0 samples/sec" in out
    assert "scan_chunk=32" in out and "device_feed=True" in out
    assert "dispatch gap closed 86%" in out


def test_compare_gates_on_end_to_end_fit_throughput(tmp_path, capsys):
    cand = tmp_path / "cand.json"
    base = tmp_path / "base.json"
    # microbench value holds; only the PRODUCTION fit loop regressed
    cand.write_text(json.dumps(_bench_record(**_fit_fields(samples=2000.0))))
    base.write_text(json.dumps(_bench_record(**_fit_fields(samples=5000.0))))
    assert main([str(cand), "--compare", str(base)]) == 2
    err = capsys.readouterr().err
    assert "fit_samples_per_sec" in err


def test_compare_skips_fit_gate_across_variants(tmp_path, capsys):
    cand = tmp_path / "cand.json"
    base = tmp_path / "base.json"
    # a different chunk size is a VARIANT run: its fit number must neither
    # gate nor masquerade as the baseline
    cand.write_text(json.dumps(_bench_record(**_fit_fields(samples=2000.0, chunk=4))))
    base.write_text(json.dumps(_bench_record(**_fit_fields(samples=5000.0, chunk=32))))
    assert main([str(cand), "--compare", str(base)]) == 0
    out = capsys.readouterr().out
    assert "variant flags differ" in out


def test_h2d_overlap_surfaced_from_trace(tmp_path, capsys):
    run = _write_fit_run(str(tmp_path / "run"))
    _write_trace(run, names=("data_wait", "train_step", "h2d", "h2d"))
    summary = summarize_run(run)
    assert summary["h2d_seconds"] == pytest.approx(2 * 5.0 / 1e6)
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "h2d:" in out and "overlapped" in out and "input starvation" in out


# --------------------------------------------------------------------------- #
# serving summaries (replay_tpu.serve)
# --------------------------------------------------------------------------- #
def _write_serve_run(path, qps=250.0, p99_ms=4.5, fill=0.8, hit_rate=0.9,
                     with_bench_record=True):
    os.makedirs(path, exist_ok=True)
    serve_goodput = {
        "wall_seconds": 2.0,
        "fractions": {"queue_wait": 0.5, "batch_build": 0.05, "score": 0.2,
                      "retrieve": 0.1, "rerank": 0.1, "other": 0.05},
        "input_starvation": None,
    }
    events = [
        {"event": "on_serve_start", "time": 1.0, "mode": "retrieval",
         "length_buckets": [8], "batch_buckets": [1, 4], "max_wait_ms": 2.0,
         "cache_capacity": 100},
        {"event": "on_serve_batch", "time": 1.1, "lane": "encode:L=8", "rows": 3,
         "bucket": 4, "fill": 0.75, "queue_wait_ms_max": 2.2},
        {"event": "on_serve_batch", "time": 1.2, "lane": "hit", "rows": 4,
         "bucket": 4, "fill": 1.0, "queue_wait_ms_max": 1.1},
        {"event": "on_serve_end", "time": 3.0, "mode": "retrieval", "requests": 7,
         "answered": 7, "errors": 0, "cache_hit_rate": hit_rate,
         "pure_hit_rate": 0.5, "batch_fill_ratio": fill,
         "queue_wait_ms_mean": 1.4, "queue_wait_ms_max": 2.2,
         "served_from": {"hit": 4, "advance": 1, "cold": 2},
         "goodput": serve_goodput},
    ]
    if with_bench_record:
        events.append(
            {"metric": "serve_qps", "value": qps, "unit": "req/s", "qps": qps,
             "p50_ms": 1.2, "p95_ms": 3.1, "p99_ms": p99_ms,
             "batch_fill_ratio": fill, "cache_hit_rate": hit_rate,
             "closed_loop_qps": qps * 1.1, "mode": "retrieval", "backend": "cpu"}
        )
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        for event in events:
            fh.write(json.dumps(event) + "\n")
    return path


def test_serve_run_summarizes_and_renders(tmp_path, capsys):
    run = _write_serve_run(str(tmp_path / "serve"))
    summary = summarize_run(run)
    assert summary["serve"]["qps"] == 250.0
    assert summary["serve"]["p99_ms"] == 4.5
    assert summary["serve"]["requests"] == 7
    assert summary["serve"]["batches"] == 2
    assert summary["serve"]["cache_hit_rate"] == 0.9
    # the serve goodput is picked up by the generic goodput scan
    assert summary["goodput"]["fractions"]["queue_wait"] == 0.5
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "serving [retrieval]:" in out
    assert "250.0 qps" in out
    assert "latency p50/p95/p99" in out
    assert "batch fill 80%" in out
    assert "cache hits 90%" in out
    assert "queue_wait 50.0%" in out  # serve-span fractions render too
    assert "input starvation" not in out  # meaningless for a serve run


def test_serve_events_only_still_renders_section(tmp_path, capsys):
    run = _write_serve_run(str(tmp_path / "serve"), with_bench_record=False)
    summary = summarize_run(run)
    assert summary["kind"] == "serve"
    assert "qps" not in summary["serve"]  # no bench record in this run
    assert summary["serve"]["requests"] == 7
    assert main([run]) == 0
    assert "serving" in capsys.readouterr().out


def test_compare_flags_serve_qps_regression(tmp_path, capsys):
    baseline = _write_serve_run(str(tmp_path / "base"), qps=250.0)
    candidate = _write_serve_run(str(tmp_path / "cand"), qps=150.0)
    assert main([candidate, "--compare", baseline]) == 2
    assert "serve_qps regressed" in capsys.readouterr().err


def test_compare_flags_serve_p99_regression_latency_is_lower_better(tmp_path, capsys):
    baseline = _write_serve_run(str(tmp_path / "base"), p99_ms=4.0)
    candidate = _write_serve_run(str(tmp_path / "cand"), p99_ms=9.0)
    assert main([candidate, "--compare", baseline]) == 2
    assert "serve_p99_ms regressed" in capsys.readouterr().err


def test_compare_serve_improvement_passes(tmp_path):
    baseline = _write_serve_run(str(tmp_path / "base"), qps=200.0, p99_ms=5.0)
    candidate = _write_serve_run(str(tmp_path / "cand"), qps=260.0, p99_ms=3.0)
    assert main([candidate, "--compare", baseline]) == 0


def test_compare_serve_within_threshold_passes(tmp_path):
    baseline = _write_serve_run(str(tmp_path / "base"), qps=250.0, p99_ms=4.0)
    candidate = _write_serve_run(str(tmp_path / "cand"), qps=240.0, p99_ms=4.3)
    assert main([candidate, "--compare", baseline]) == 0


# --------------------------------------------------------------------------- #
# serving resilience: shed / deadline-miss / error rates, breaker, chaos
# --------------------------------------------------------------------------- #
def _write_resilient_serve_run(path, error_rate=0.0, deadline_miss_rate=0.0,
                               shed_rate=0.0, overload=True, chaos=True):
    os.makedirs(path, exist_ok=True)
    events = [
        {"event": "on_serve_start", "time": 1.0, "mode": "retrieval",
         "max_queue_depth": 64, "default_deadline_ms": 250.0, "fallback": True},
        {"event": "on_shed", "time": 1.2, "lane": "encode:L=8", "depth": 64,
         "max_depth": 64, "retry_after_s": 0.05, "count": 17},
        {"event": "on_breaker", "time": 1.3, "from": "closed", "to": "open",
         "consecutive_failures": 5, "opens": 1},
        {"event": "on_degrade", "time": 1.35, "to": "cache_only",
         "reason": "breaker_open", "count": 3},
        {"event": "on_breaker", "time": 1.6, "from": "open", "to": "half_open",
         "consecutive_failures": 5, "opens": 1},
        {"event": "on_breaker", "time": 1.7, "from": "half_open", "to": "closed",
         "consecutive_failures": 0, "opens": 1},
        {"event": "on_serve_end", "time": 3.0, "mode": "retrieval",
         "requests": 100, "answered": 80, "errors": int(error_rate * 100),
         "cache_hit_rate": 0.9, "batch_fill_ratio": 0.8,
         "served_from": {"hit": 60, "advance": 10, "cold": 10},
         "served_by": {"primary": 70, "cache_only": 8, "fallback": 2},
         "shed": int(shed_rate * 100), "deadline_misses": 4, "cancelled": 1,
         "circuit_refusals": 2, "degraded": 10,
         "shed_rate": shed_rate, "deadline_miss_rate": deadline_miss_rate,
         "error_rate": error_rate},
    ]
    record = {
        "metric": "serve_qps", "value": 200.0, "unit": "req/s", "qps": 200.0,
        "p50_ms": 1.2, "p95_ms": 3.1, "p99_ms": 4.5, "batch_fill_ratio": 0.8,
        "cache_hit_rate": 0.9, "mode": "retrieval", "backend": "cpu",
        "serve_shed_rate": shed_rate,
        "serve_deadline_miss_rate": deadline_miss_rate,
        "serve_error_rate": error_rate,
        "served_by": {"primary": 70, "cache_only": 8, "fallback": 2},
        "breaker": {"state": "closed", "opens": 1, "closes": 1},
        "hung_requests": 0,
    }
    if overload:
        record["overload"] = {
            "rate": 800.0, "p99_ms": 40.0, "shed_rate": shed_rate,
            "deadline_miss_rate": deadline_miss_rate, "hung_requests": 0,
        }
    if chaos:
        record["chaos"] = {
            "injected_engine_errors": 5, "breaker_opens": 1,
            "breaker_state_final": "closed", "recovered": True,
            "hung_requests": 0, "storm_deadline_missed": 12,
        }
    events.append(record)
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        for event in events:
            fh.write(json.dumps(event) + "\n")
    return path


def test_serve_resilience_summarizes_and_renders(tmp_path, capsys):
    run = _write_resilient_serve_run(
        str(tmp_path / "serve"), error_rate=0.01, deadline_miss_rate=0.04,
        shed_rate=0.2,
    )
    summary = summarize_run(run)
    serve = summary["serve"]
    assert serve["shed_rate"] == 0.2
    assert serve["deadline_miss_rate"] == 0.04
    assert serve["error_rate"] == 0.01
    assert serve["served_by"] == {"primary": 70, "cache_only": 8, "fallback": 2}
    assert serve["breaker"]["opens"] == 1
    assert serve["shed_events"] == 1
    assert serve["breaker_events"] == 3
    assert serve["degrade_events"] == 1
    assert serve["overload"] is True
    assert serve["overload_p99_ms"] == 40.0
    assert serve["chaos"]["breaker_opens"] == 1
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "serving resilience:" in out
    assert "shed rate 20.00%" in out
    assert "deadline-miss rate 4.00%" in out
    assert "error rate 1.00%" in out
    assert "degraded 10 (cache_only:8/fallback:2)" in out
    assert "breaker closed (1 open(s))" in out
    assert "hung 0" in out
    assert "serving overload:" in out
    assert "serving chaos:" in out
    assert "5 injected error(s)" in out


def test_compare_gates_on_serve_error_rate_rise(tmp_path, capsys):
    baseline = _write_resilient_serve_run(str(tmp_path / "base"), error_rate=0.0)
    candidate = _write_resilient_serve_run(str(tmp_path / "cand"), error_rate=0.05)
    # the absolute floor matters: relative-only would never fire on 0 -> 0.05
    assert main([candidate, "--compare", baseline]) == 2
    assert "serve_error_rate regressed" in capsys.readouterr().err


def test_compare_gates_on_serve_deadline_miss_rate_rise(tmp_path, capsys):
    baseline = _write_resilient_serve_run(
        str(tmp_path / "base"), deadline_miss_rate=0.01
    )
    candidate = _write_resilient_serve_run(
        str(tmp_path / "cand"), deadline_miss_rate=0.10
    )
    assert main([candidate, "--compare", baseline]) == 2
    assert "serve_deadline_miss_rate regressed" in capsys.readouterr().err


def test_compare_gates_shed_rate_only_when_both_ran_overload(tmp_path, capsys):
    baseline = _write_resilient_serve_run(
        str(tmp_path / "base"), shed_rate=0.1, overload=True
    )
    worse = _write_resilient_serve_run(
        str(tmp_path / "cand"), shed_rate=0.5, overload=True
    )
    assert main([worse, "--compare", baseline]) == 2
    assert "serve_shed_rate regressed" in capsys.readouterr().err
    # candidate without the overload phase: surfaced, NOT gated
    no_overload = _write_resilient_serve_run(
        str(tmp_path / "cand2"), shed_rate=0.5, overload=False
    )
    assert main([no_overload, "--compare", baseline]) == 0
    assert "not gated: both sides must run overload" in capsys.readouterr().out


def test_compare_skips_rate_gates_when_phases_mismatch(tmp_path, capsys):
    """The run-wide rates are dominated by the opt-in phases: a chaos run's
    injected errors (or an overload run's designed deadline misses) must not
    gate against a baseline that never ran the phase."""
    baseline = _write_resilient_serve_run(
        str(tmp_path / "base"), error_rate=0.0, deadline_miss_rate=0.0,
        overload=False, chaos=False,
    )
    candidate = _write_resilient_serve_run(
        str(tmp_path / "cand"), error_rate=0.03, deadline_miss_rate=0.08,
        overload=True, chaos=True,
    )
    assert main([candidate, "--compare", baseline]) == 0
    out = capsys.readouterr().out
    assert "serve_error_rate" in out and "chaos phase ran on one side only" in out
    assert "overload phase ran on one side only" in out


def test_compare_resilience_rates_within_floor_pass(tmp_path):
    baseline = _write_resilient_serve_run(
        str(tmp_path / "base"), error_rate=0.0, deadline_miss_rate=0.01,
        shed_rate=0.1,
    )
    candidate = _write_resilient_serve_run(
        str(tmp_path / "cand"), error_rate=0.004, deadline_miss_rate=0.012,
        shed_rate=0.1,
    )
    assert main([candidate, "--compare", baseline]) == 0


def test_compare_resilience_improvement_passes(tmp_path):
    baseline = _write_resilient_serve_run(
        str(tmp_path / "base"), error_rate=0.05, deadline_miss_rate=0.1,
        shed_rate=0.4,
    )
    candidate = _write_resilient_serve_run(
        str(tmp_path / "cand"), error_rate=0.0, deadline_miss_rate=0.0,
        shed_rate=0.1,
    )
    assert main([candidate, "--compare", baseline]) == 0


# --------------------------------------------------------------------------- #
# resource gates: peak memory + compile time (lower-better), bench-row skips
# --------------------------------------------------------------------------- #
def _write_resource_run(path, peak_memory=1_000_000, compile_seconds=2.0):
    os.makedirs(path, exist_ok=True)
    events = [
        {"event": "on_fit_start", "time": 1.0, "epoch": 0, "epochs": 1},
        {"event": "on_train_step", "time": 2.0, "step": 1, "epoch": 0, "loss": 1.0,
         "samples_per_sec": 500.0, "steps_per_sec": 62.5},
        {"event": "on_fit_end", "time": 3.0, "step": 1,
         "telemetry": {"steps": 1.0, "elapsed_seconds": 0.1,
                       "steps_per_sec": 62.5, "samples_per_sec": 500.0},
         "compile": {"train_step": {"traces": 1, "compile_seconds": compile_seconds}},
         "peak_memory_bytes": peak_memory, "history_len": 1, "bad_steps": 0},
    ]
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        for event in events:
            fh.write(json.dumps(event) + "\n")
    return path


def test_compare_gates_on_peak_memory_growth(tmp_path, capsys):
    baseline = _write_resource_run(str(tmp_path / "base"), peak_memory=1_000_000)
    candidate = _write_resource_run(str(tmp_path / "cand"), peak_memory=1_300_000)
    assert main([candidate, "--compare", baseline]) == 2
    assert "peak_memory_bytes regressed" in capsys.readouterr().err


def test_compare_peak_memory_within_threshold_passes(tmp_path):
    baseline = _write_resource_run(str(tmp_path / "base"), peak_memory=1_000_000)
    candidate = _write_resource_run(str(tmp_path / "cand"), peak_memory=1_050_000)
    assert main([candidate, "--compare", baseline]) == 0


def test_compare_memory_threshold_is_tunable(tmp_path):
    baseline = _write_resource_run(str(tmp_path / "base"), peak_memory=1_000_000)
    candidate = _write_resource_run(str(tmp_path / "cand"), peak_memory=1_300_000)
    assert main([candidate, "--compare", baseline, "--memory-threshold", "0.5"]) == 0


def test_compare_gates_on_compile_time_growth(tmp_path, capsys):
    baseline = _write_resource_run(str(tmp_path / "base"), compile_seconds=2.0)
    # compile gate defaults to max(threshold, 0.5): +60% trips it
    candidate = _write_resource_run(str(tmp_path / "cand"), compile_seconds=3.2)
    assert main([candidate, "--compare", baseline]) == 2
    assert "compile_seconds regressed" in capsys.readouterr().err


def test_compare_compile_noise_within_default_threshold_passes(tmp_path):
    baseline = _write_resource_run(str(tmp_path / "base"), compile_seconds=2.0)
    candidate = _write_resource_run(str(tmp_path / "cand"), compile_seconds=2.8)
    assert main([candidate, "--compare", baseline]) == 0


def test_compare_memory_shrink_and_missing_are_fine(tmp_path):
    baseline = _write_resource_run(str(tmp_path / "base"), peak_memory=2_000_000)
    candidate = _write_resource_run(str(tmp_path / "cand"), peak_memory=1_000_000)
    assert main([candidate, "--compare", baseline]) == 0
    # null peaks (CPU fits) stay "not comparable", never a regression
    base2 = _write_resource_run(str(tmp_path / "b2"), peak_memory=None)
    cand2 = _write_resource_run(str(tmp_path / "c2"), peak_memory=None)
    assert main([cand2, "--compare", base2]) == 0


def _write_suite_run(path, rows):
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        for row in rows:
            fh.write(json.dumps({"event": "bench_row", "time": 1.0, **row}) + "\n")
    return path


def test_compare_skips_error_bench_rows(tmp_path, capsys):
    """The by-design 1M plain-CE OOM row must not trip the gate — on either
    side — while measured rows still gate per name."""
    baseline = _write_suite_run(str(tmp_path / "base"), [
        {"row": "scale_1m_ce", "error": "RESOURCE_EXHAUSTED: oom"},
        {"row": "scale_1m_fused", "samples_per_sec": 1000.0},
    ])
    candidate = _write_suite_run(str(tmp_path / "cand"), [
        {"row": "scale_1m_ce", "error": "RESOURCE_EXHAUSTED: oom"},
        {"row": "scale_1m_fused", "samples_per_sec": 980.0},
    ])
    assert main([candidate, "--compare", baseline]) == 0
    out = capsys.readouterr().out
    assert "skipped (baseline error row)" in out
    assert "bench_row[scale_1m_fused].samples_per_sec" in out


def test_compare_flags_bench_row_regression_and_new_errors(tmp_path, capsys):
    baseline = _write_suite_run(str(tmp_path / "base"), [
        {"row": "scale_1m_fused", "samples_per_sec": 1000.0},
        {"row": "scale_27k_tp", "samples_per_sec": 500.0},
    ])
    candidate = _write_suite_run(str(tmp_path / "cand"), [
        {"row": "scale_1m_fused", "samples_per_sec": 500.0},  # -50%: regression
        {"row": "scale_27k_tp", "error": "XlaRuntimeError: boom"},  # NEW error
    ])
    assert main([candidate, "--compare", baseline]) == 2
    err = capsys.readouterr().err
    assert "bench_row[scale_1m_fused].samples_per_sec regressed" in err
    assert "errored in the candidate" in err


# --------------------------------------------------------------------------- #
# roofline section
# --------------------------------------------------------------------------- #
def _write_profiled_run(path):
    os.makedirs(path, exist_ok=True)
    roofline = {
        "train_step": {
            "roofline": {
                "flops": 1e9, "bytes_accessed": 1e8,
                "arithmetic_intensity": 10.0, "critical_intensity": 240.5,
                "bound": "memory", "ceiling_tflops": 8.19,
                "peak_tflops": 197.0, "peak_hbm_gbps": 819.0,
                "min_step_seconds": 1.2e-4, "peak_assumed": "v5e",
            },
            "hbm_peak_bytes": 50_000_000, "collective_bytes": 1_000_000,
        }
    }
    events = [
        {"event": "on_fit_start", "time": 1.0, "epoch": 0, "epochs": 1},
        {"event": "on_fit_end", "time": 2.0, "step": 3,
         "telemetry": {"steps": 3.0, "elapsed_seconds": 0.3,
                       "steps_per_sec": 10.0, "samples_per_sec": 80.0},
         "compile": {"train_step": {"traces": 1, "compile_seconds": 1.0}},
         "peak_memory_bytes": None, "history_len": 1, "bad_steps": 0,
         "roofline": roofline},
    ]
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        for event in events:
            fh.write(json.dumps(event) + "\n")
    return path


def test_roofline_section_renders(tmp_path, capsys):
    run = _write_profiled_run(str(tmp_path / "run"))
    summary = summarize_run(run)
    assert summary["roofline"]["train_step"]["roofline"]["bound"] == "memory"
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "roofline:" in out
    assert "memory-bound (assumed v5e peaks)" in out
    assert "ceiling 8.19 TFLOP/s" in out
    assert "peak HBM 50.0 MB" in out


def test_bench_rows_render_roofline_fields(tmp_path, capsys):
    run = _write_suite_run(str(tmp_path / "suite"), [
        {"row": "scale_27k_fused", "samples_per_sec": 900.0, "step_ms": 2.0,
         "num_items": 27278, "loss": "CEFused", "roofline_bound": "memory",
         "of_roofline_ceiling": 0.42, "hbm_peak_bytes": 64_000_000,
         "collective_bytes": 2_000_000},
    ])
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "memory-bound (42% of ceiling)" in out
    assert "HBM 64.0 MB" in out and "coll 2.00 MB" in out


# --------------------------------------------------------------------------- #
# the precision ladder (prec_* bench rows + serving quant block)
# --------------------------------------------------------------------------- #
def test_prec_rows_gate_hbm_lower_better(tmp_path, capsys):
    """A prec_* row whose hbm_peak_bytes grew past --memory-threshold fails
    even at held throughput — the precision regression that only moves bytes;
    non-prec rows keep their throughput-only gate."""
    baseline = _write_suite_run(str(tmp_path / "base"), [
        {"row": "prec_bf16_fused", "samples_per_sec": 900.0,
         "hbm_peak_bytes": 50_000_000, "precision": "bf16"},
        {"row": "scale_27k_fused", "samples_per_sec": 900.0,
         "hbm_peak_bytes": 50_000_000},
    ])
    candidate = _write_suite_run(str(tmp_path / "cand"), [
        {"row": "prec_bf16_fused", "samples_per_sec": 910.0,
         "hbm_peak_bytes": 80_000_000, "precision": "bf16"},
        # same 60% HBM growth on a NON-prec row: surfaced, not gated
        {"row": "scale_27k_fused", "samples_per_sec": 910.0,
         "hbm_peak_bytes": 80_000_000},
    ])
    rc = main([candidate, "--compare", baseline])
    err = capsys.readouterr().err
    assert rc != 0
    assert "bench_row[prec_bf16_fused].hbm_peak_bytes" in err
    assert "scale_27k_fused].hbm_peak_bytes" not in err


def test_prec_rows_hbm_gate_respects_memory_threshold(tmp_path):
    baseline = _write_suite_run(str(tmp_path / "base"), [
        {"row": "prec_bf16_ce", "samples_per_sec": 900.0,
         "hbm_peak_bytes": 50_000_000},
    ])
    candidate = _write_suite_run(str(tmp_path / "cand"), [
        {"row": "prec_bf16_ce", "samples_per_sec": 900.0,
         "hbm_peak_bytes": 56_000_000},
    ])
    # 12% growth: fails the default 10% memory threshold, passes at 20%
    assert main([candidate, "--compare", baseline]) != 0
    assert main([candidate, "--compare", baseline, "--memory-threshold", "0.2"]) == 0


def test_precision_pairs_summarize_and_render(tmp_path, capsys):
    run = _write_suite_run(str(tmp_path / "suite"), [
        {"row": "prec_f32_fused", "samples_per_sec": 900.0, "step_ms": 4.0,
         "precision": "f32", "hbm_peak_bytes": 100_000_000, "backend": "tpu"},
        {"row": "prec_bf16_fused", "samples_per_sec": 1200.0, "step_ms": 3.0,
         "precision": "bf16", "hbm_peak_bytes": 60_000_000, "backend": "tpu"},
    ])
    summary = summarize_run(run)
    pair = summary["precision_pairs"]["fused"]
    assert pair["f32_hbm_peak_bytes"] == 100_000_000
    assert pair["bf16_hbm_peak_bytes"] == 60_000_000
    assert pair["hbm_saved_fraction"] == pytest.approx(0.4)
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "precision ladder [fused]" in out
    assert "HBM 100.0→60.0 MB" in out and "+40.0% saved" in out
    assert "prec bf16" in out  # the per-row precision tag renders too


def _write_quant_serve_run(path, recall=0.996, topk_match=1.0):
    os.makedirs(path, exist_ok=True)
    record = {
        "metric": "serve_qps", "value": 250.0, "unit": "req/s", "qps": 250.0,
        "p50_ms": 2.0, "p95_ms": 3.5, "p99_ms": 4.5, "batch_fill_ratio": 0.8,
        "cache_hit_rate": 0.9, "requests": 512, "mode": "retrieval",
        "quant": {
            "candidates": 100, "top_k": 10,
            "recall_at_candidates": recall, "topk_match_rate": topk_match,
            "f32_rank_ms": 0.9, "int8_rank_ms": 0.7,
            "int8_table_bytes": 4000, "f32_table_bytes": 12800,
            "bytes_ratio": 0.3125,
        },
    }
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        fh.write(json.dumps(record) + "\n")
    return path


def test_serve_quant_summarizes_and_renders(tmp_path, capsys):
    run = _write_quant_serve_run(str(tmp_path / "serve"))
    summary = summarize_run(run)
    quant = summary["serve"]["quant"]
    assert quant["recall_at_candidates"] == pytest.approx(0.996)
    assert quant["bytes_ratio"] == pytest.approx(0.3125)
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "serving quant (int8 retrieval)" in out
    assert "int8 recall@100 0.9960" in out and "table bytes" in out


def test_serve_quant_recall_gates_higher_better(tmp_path, capsys):
    baseline = _write_quant_serve_run(str(tmp_path / "base"), recall=0.996)
    candidate = _write_quant_serve_run(str(tmp_path / "cand"), recall=0.95)
    rc = main([candidate, "--compare", baseline])
    assert rc != 0
    assert "serve_quant_recall_at_candidates" in capsys.readouterr().err
    # within the absolute 0.005 band: measurement noise, not a regression
    near = _write_quant_serve_run(str(tmp_path / "near"), recall=0.993)
    assert main([near, "--compare", baseline]) == 0


def test_serve_quant_topk_match_gates(tmp_path, capsys):
    baseline = _write_quant_serve_run(str(tmp_path / "base"), topk_match=1.0)
    candidate = _write_quant_serve_run(str(tmp_path / "cand"), topk_match=0.9)
    assert main([candidate, "--compare", baseline]) != 0
    assert "serve_quant_topk_match_rate" in capsys.readouterr().err


def _write_ann_serve_run(path, recall=0.995, agreement=1.0, ivf_qps=2500.0):
    os.makedirs(path, exist_ok=True)
    record = {
        "metric": "serve_qps", "value": 250.0, "unit": "req/s", "qps": 250.0,
        "p50_ms": 2.0, "p95_ms": 3.5, "p99_ms": 4.5, "batch_fill_ratio": 0.8,
        "cache_hit_rate": 0.9, "requests": 512, "mode": "retrieval",
        "ann": {
            "items": 10_000_000, "dim": 64, "nlist": 4096, "nprobe": 16,
            "cmax": 4688, "scanned_fraction": 0.0075,
            "recall_at_100": recall, "topk_agreement": agreement,
            "brute_qps": 180.0, "ivf_qps": ivf_qps,
            "speedup": ivf_qps / 180.0, "build_s": 310.0,
            "recall_at_100_int8": 0.994, "recall_at_100_pq": 0.993,
            "index_total_bytes": 2_900_000_000,
        },
    }
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        fh.write(json.dumps(record) + "\n")
    return path


def test_serve_ann_summarizes_and_renders(tmp_path, capsys):
    run = _write_ann_serve_run(str(tmp_path / "serve"))
    summary = summarize_run(run)
    ann = summary["serve"]["ann"]
    assert ann["recall_at_100"] == pytest.approx(0.995)
    assert ann["nlist"] == 4096 and ann["nprobe"] == 16
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "serving ann (ivf retrieval)" in out
    assert "recall@100 0.9950" in out
    assert "vs IVF" in out  # the brute-vs-IVF speedup line


def test_serve_ann_recall_gates_higher_better(tmp_path, capsys):
    baseline = _write_ann_serve_run(str(tmp_path / "base"), recall=0.995)
    candidate = _write_ann_serve_run(str(tmp_path / "cand"), recall=0.95)
    assert main([candidate, "--compare", baseline]) != 0
    assert "serve_ann_recall_at_100" in capsys.readouterr().err
    # within the absolute 0.005 band: measurement noise, not a regression
    near = _write_ann_serve_run(str(tmp_path / "near"), recall=0.992)
    assert main([near, "--compare", baseline]) == 0


def test_serve_ann_agreement_and_qps_gate(tmp_path, capsys):
    baseline = _write_ann_serve_run(str(tmp_path / "base"), agreement=1.0)
    candidate = _write_ann_serve_run(str(tmp_path / "cand"), agreement=0.9)
    assert main([candidate, "--compare", baseline]) != 0
    assert "serve_ann_topk_agreement" in capsys.readouterr().err
    slow = _write_ann_serve_run(str(tmp_path / "slow"), ivf_qps=1000.0)
    fast = _write_ann_serve_run(str(tmp_path / "fast"), ivf_qps=2500.0)
    assert main([slow, "--compare", fast]) != 0
    assert "serve_ann_qps" in capsys.readouterr().err


# --------------------------------------------------------------------------- #
# promotion: canary lifecycle summary, rollback + swap_p99_ms compare gates
# --------------------------------------------------------------------------- #
def _write_promotion_run(path, rollbacks=0, promotions=1, swap_p99_ms=None,
                         qps=250.0):
    os.makedirs(path, exist_ok=True)
    events = [
        {"event": "on_serve_start", "time": 1.0, "mode": "full",
         "length_buckets": [8], "batch_buckets": [1, 4], "max_wait_ms": 2.0,
         "cache_capacity": 100},
        {"event": "on_publish", "time": 1.1, "generation": 1,
         "label": "candidate-a", "recompiled": False, "recompile_reason": None},
        {"event": "on_canary_start", "time": 1.2, "generation": 1, "fraction": 0.1},
        {"event": "on_canary_eval", "time": 1.3, "stage": "canary",
         "generation": 1, "action": None, "error_rate": 0.0,
         "window": {"requests": 8.0, "answered": 8.0, "errors": 0.0, "shed": 0.0},
         "clean_evals": 1, "evals": 1, "breached_rules": []},
    ]
    for _ in range(promotions):
        events += [
            {"event": "on_promotion", "time": 1.4, "generation": 1,
             "from_generation": 0, "clean_evals": 2, "evals": 2},
            {"event": "on_swap", "time": 1.4, "reason": "promote",
             "from_generation": 0, "to_generation": 1, "recompiled": False},
        ]
    for _ in range(rollbacks):
        events += [
            {"event": "on_rollback", "time": 1.5, "generation": 2,
             "restored_generation": 1, "rules": ["canary_error_rate"], "evals": 3},
            {"event": "on_swap", "time": 1.5, "reason": "rollback",
             "from_generation": 2, "to_generation": 1, "recompiled": False},
        ]
    events.append(
        {"event": "on_serve_end", "time": 3.0, "mode": "full", "requests": 20,
         "answered": 20, "errors": 0, "cache_hit_rate": 0.5,
         "batch_fill_ratio": 0.8, "queue_wait_ms_mean": 1.0,
         "queue_wait_ms_max": 2.0,
         "served_from": {"hit": 10, "advance": 5, "cold": 5}},
    )
    record = {"metric": "serve_qps", "value": qps, "unit": "req/s", "qps": qps,
              "p50_ms": 1.2, "p95_ms": 3.1, "p99_ms": 4.0,
              "batch_fill_ratio": 0.8, "cache_hit_rate": 0.5, "mode": "full",
              "backend": "cpu"}
    if swap_p99_ms is not None:
        record["swap"] = {"swaps": 3, "p99_ms": swap_p99_ms, "errors": 0,
                          "generations_seen": 4, "recompiled_swaps": 1}
    events.append(record)
    with open(os.path.join(path, "events.jsonl"), "w") as fh:
        for event in events:
            fh.write(json.dumps(event) + "\n")
    return path


def test_promotion_summary_and_render(tmp_path, capsys):
    run = _write_promotion_run(str(tmp_path / "promo"), rollbacks=1,
                               swap_p99_ms=6.5)
    summary = summarize_run(run)
    assert summary["rollbacks"] == 1
    assert summary["promotions"] == 1
    assert summary["swaps"] == 2
    promotion = summary["promotion"]
    assert promotion["publishes"] == 1
    assert promotion["canaries"] == 1
    assert promotion["canary_evals"] == 1
    assert promotion["rollback_rules"] == ["canary_error_rate"]
    assert summary["serve"]["swap"] is True
    assert summary["serve"]["swap_p99_ms"] == 6.5
    assert main([run]) == 0
    out = capsys.readouterr().out
    assert "promotion:" in out
    assert "1 rolled back" in out
    assert "serving swap:" in out
    assert "rollback rule(s): canary_error_rate" in out


def test_compare_gates_on_rollback_increase(tmp_path, capsys):
    baseline = _write_promotion_run(str(tmp_path / "base"), rollbacks=0)
    candidate = _write_promotion_run(str(tmp_path / "cand"), rollbacks=1)
    assert main([candidate, "--compare", baseline]) == 2
    assert "rollbacks increased" in capsys.readouterr().err


def test_compare_rollbacks_equal_passes(tmp_path):
    baseline = _write_promotion_run(str(tmp_path / "base"), rollbacks=1)
    candidate = _write_promotion_run(str(tmp_path / "cand"), rollbacks=1)
    assert main([candidate, "--compare", baseline]) == 0


def test_compare_gates_swap_p99_when_both_ran_swaps(tmp_path, capsys):
    baseline = _write_promotion_run(str(tmp_path / "base"), swap_p99_ms=5.0)
    candidate = _write_promotion_run(str(tmp_path / "cand"), swap_p99_ms=9.0)
    assert main([candidate, "--compare", baseline]) == 2
    assert "swap_p99_ms regressed" in capsys.readouterr().err


def test_compare_surfaces_swap_p99_ungated_on_phase_mismatch(tmp_path, capsys):
    baseline = _write_promotion_run(str(tmp_path / "base"), swap_p99_ms=None)
    candidate = _write_promotion_run(str(tmp_path / "cand"), swap_p99_ms=50.0)
    assert main([candidate, "--compare", baseline]) == 0
    out = capsys.readouterr().out
    assert "swap_p99_ms" in out and "not gated" in out


def test_compare_swap_p99_improvement_passes(tmp_path):
    baseline = _write_promotion_run(str(tmp_path / "base"), swap_p99_ms=9.0)
    candidate = _write_promotion_run(str(tmp_path / "cand"), swap_p99_ms=5.0)
    assert main([candidate, "--compare", baseline]) == 0
