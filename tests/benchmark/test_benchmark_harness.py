"""The harness end to end at toy size on the CPU: cells added as DATA run, the
result line has the contract's keys, a run without a TPU fails, the plain
reference agrees with the models, the control and every planted fault come out
as not correct, and the ``fit`` driver runs on a ``data=4`` mesh."""

import json

import jax
import pytest

import bench_helpers
from benchmark import run as bench_run


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return bench_helpers.make_checkout(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def toy_results(checkout):
    """One in-process run of each toy cell (they were added as files and entries,
    with no edit to run.py or to any file the benchmark had)."""
    results = {}
    for config, traffic, chips in bench_helpers.TOY_CELLS:
        cell = bench_helpers.toy_cell(checkout, f"{config}.{traffic}", jax.devices()[:chips])
        results[cell.name] = bench_run.run_cell(cell)
    return results


@pytest.mark.parametrize("name", [f"{c}.{t}" for c, t, _ in bench_helpers.TOY_CELLS])
def test_a_cell_added_as_files_runs_and_is_correct(toy_results, name):
    result = toy_results[name]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["fit_samples_per_s"]["value"] > 0
    assert result["metrics"]["fit_tokens_per_s"]["value"] > result["metrics"]["fit_samples_per_s"]["value"]


def test_result_line_has_the_contracts_keys(toy_results, capsys):
    result = toy_results["tiny_sasrec.tiny_fit"]
    bench_run.report(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "checks"  # each number compared beside its limit comes last
    assert set(line["metrics"]) == {"setup_s", "fit_samples_per_s", "fit_tokens_per_s"}
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())
    assert err.strip().splitlines()[-1] == "correct: True"
    assert "check grad_norm_gap:" in err and "check loss_step1:" in err


def test_the_dp4_cell_ran_on_a_data_4_mesh(toy_results):
    result = toy_results["tiny_sasrec_f32.tiny_fit_dp4"]
    one = toy_results["tiny_sasrec.tiny_fit"]
    assert result["correct"] is True
    assert result["attempted"] and result["notes"]["steps"] % 2 == 0  # whole chunks of 2
    # 4x the global batch in each step
    rows_per_step = result["metrics"]["fit_samples_per_s"]["value"] * result["notes"]["window_s"] / result["notes"]["steps"]
    rows_one = one["metrics"]["fit_samples_per_s"]["value"] * one["notes"]["window_s"] / one["notes"]["steps"]
    assert rows_per_step > 3 * rows_one


def test_without_a_tpu_the_command_fails_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as raised:
        bench_run.main(["--workload", "sasrec_ml20m.fit", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert raised.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_no_window_compiles_and_metric_readers_read_the_context(toy_results, checkout):
    context = {
        "produce_seconds": [0.001, 0.003], "chunk_gaps_s": [0.1] * 9 + [0.5],
        "compiles_in_window": 0, "memory_peak_bytes": 5_000_000_000,
        "device_kind": "TPU v5 lite", "batch_size": 512, "chips": 1, "steps": 100,
        "window_s": 3.45, "scan_chunk": 8,
        "model_sizes": dict(embedding_dim=64, num_blocks=2, num_heads=2, max_sequence_length=50,
                            ffn_dim=256, num_items=27278),
        "traced": {"busy_s": 0.9, "window_s": 1.0, "steps": 32,
                   "scope_s": {"loss": 0.8, "forward": 0.064, "other": 0.036}},
    }
    read = lambda name: bench_run.load_module(checkout, f"benchmark/metrics/{name}.py").read(context)  # noqa: E731
    assert read("input_ms_per_step") == pytest.approx(2.0)
    assert read("chunk_gap_p90_ms") == pytest.approx(140.0)
    assert read("compiles_in_window") == 0
    assert read("peak_hbm_gb") == pytest.approx(5.0)
    assert read("device_idle_pct") == pytest.approx(10.0)
    assert read("forward_ms_per_step") == pytest.approx(2.0)
    # 285.2 GFLOP a step * 100 steps / 3.45 s / 197 TFLOP/s
    assert read("step_mfu_pct") == pytest.approx(100 * 285_219_225_600 * 100 / 3.45 / 197e12)
    # least 1.361 ms over 25 ms of loss-scope device time a step
    assert read("head_roofline_pct") == pytest.approx(100 * (3 * 89_384_550_400 / 197e12) / 0.025)
    context["traced"]["scope_s"]["loss"] = 0.0
    assert read("head_roofline_pct") is None  # nothing to read is nothing, never 0
