"""The window-and-full layer-pattern model through the benchmark at toy size on
the CPU: its cells are added as DATA plus one driver kind, run through
``drivers/fit_windowed.py`` and are judged ``correct``; the lower-precision control
and every planted fault come out as not correct; the band's closed form agrees with
a brute-force count, and each new reader reads a hand-made context.

Tolerances of the toy cells (``limits/`` beside this file): the float32 cell holds
program and reference to 1e-5 on the losses and 1e-4 on update and first gradient,
every selection the same. The bfloat16 cell's limits (losses 5e-3, first gradient
0.12, worst leaf 0.27, loads 0.05) fail the float8 control and every fault: half the
batch, no experts, the window dropped, YaRN dropped, the renormalisation dropped.
"""

import json

import jax
import numpy as np
import pytest

import bench_helpers
import windowed_helpers
from benchmark import counts_windowed
from benchmark import run as bench_run

CELLS = [f"{config}.{traffic}" for config, traffic, _ in windowed_helpers.WINDOWED_CELLS]
REAL = json.loads((bench_helpers.REPO / "benchmark/configs/mellum2_12b_a2b_ep8.json").read_text())
SIZES = {key: REAL[key] for key in (
    "embedding_dim", "max_sequence_length", "ffn_dim", "num_items", "layers", "experts", "attention",
)}
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return windowed_helpers.make_checkout(tmp_path_factory.mktemp("bench_windowed"))


def test_a_windowed_cell_runs_through_its_driver_and_is_correct(checkout):
    cell = bench_helpers.toy_cell(checkout, CELLS[1], jax.devices()[:1], seed=2147483659)
    result = bench_run.run_cell(cell)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["fit_samples_per_s"]["value"] > 0
    assert set(result["checks"]) == {
        "loss_step1", "loss_step2", "update_norm_gap", "grad_step1_gap", "grad_step1_leaf_gap",
        "expert_load_step1_gap", "bad_steps",
    }
    loads = result["notes"]["comparison"]["first_loads"]
    assert loads["program"] == loads["reference"] and sum(map(sum, loads["program"])) > 0
    assert result["notes"]["comparison"]["leaves_left_out"] == []  # every leaf trains: no bias buffer
    # the counters of both layer kinds ride the chunk stage log
    from benchmark import stages

    counted = stages.records()[-1]["counters"]
    assert np.asarray(counted["expert_load"]).shape == (2, 3, 4)  # [steps, expert layers, held]
    assert np.asarray(counted["attention_blocks_visited"]).shape == (2, 3, 2)  # [steps, layers, (fwd, bwd)]
    assert np.asarray(counted["attention_blocks_needed"]).shape == (2, 3)
    assert counted["dropped_assignments"] == [[0, 0, 0]] * 2


def test_the_driver_names_this_models_groups_and_scopes_and_leaves_the_others_alone(checkout):
    windowed = bench_run.load_module(checkout, "benchmark/drivers/fit_windowed.py")
    hybrid = bench_run.load_module(checkout, "benchmark/drivers/fit_hybrid.py")
    config = json.loads((checkout / "benchmark/configs/tiny_mellum2.json").read_text())
    sizes = windowed.reference_model(config)
    assert set(sizes) - {"layers", "experts", "attention", "norm_eps"} == {
        "embedding_dim", "num_blocks", "num_heads", "max_sequence_length", "ffn_dim",
        "activation", "dropout", "causal", "num_items",
    }
    assert "conv" in hybrid.GROUPS and hybrid.LAYER_SCOPES == ("moe", "conv", "attention", "dense_ffn")
    assert windowed.run is not hybrid.run and windowed.read_capture is not hybrid.read_capture


@pytest.fixture(scope="module")
def followed(checkout):
    """The bf16 toy cell's first chunk through ``fit``, and the float32 reference's
    trajectory over the same batches."""
    from functools import partial

    cell = bench_helpers.toy_cell(checkout, CELLS[0], jax.devices()[:1], seed=2147483659)
    driver = bench_run.load_module(checkout, "benchmark/drivers/fit_windowed.py")
    built = driver.build(cell, cell.seed)
    stream = driver.Stream(built["batcher"], built["transform"], False, cell.seed,
                           cell.traffic["scan_chunk"])
    fit = partial(built["trainer"].fit, epochs=1, scan_chunk=cell.traffic["scan_chunk"],
                  device_feed=True, log_every=0)
    _, program = driver.drive_first_chunk(cell, built, stream, fit)
    reference = driver.follow_reference(cell, built, stream.kept, cell.seed)
    return cell, driver, built, stream.kept, reference, program


def test_the_bfloat16_program_is_within_its_cells_limits(followed):
    cell, driver, _, _, reference, program = followed
    numbers = driver.numbers(program, reference)["numbers"]
    numbers["bad_steps"] = 0.0
    verdict = driver.compare.judge(numbers, cell.limits)
    assert verdict["correct"] is True, verdict["checks"]
    assert numbers["loss_step1"] > 1e-6  # bfloat16 did run: float32 reads 1e-7
    assert set(program["first_gradient"]) == set(reference["first_gradient"])
    assert "output_table" in program["first_gradient"]


@pytest.mark.parametrize(
    "how",
    [{"precision": "fp8"}, {"fault": "half_batch"}, {"fault": "no_experts"}, {"fault": "no_window"},
     {"fault": "no_yarn"}, {"fault": "no_renorm"}],
    ids=lambda how: next(iter(how.values())),
)
def test_the_float8_control_and_each_planted_fault_fail_the_cells_own_limits(followed, how):
    """Held to the bfloat16 toy cell's OWN limits, which its program is within, and
    by step 1 alone: the trajectory is the float32 reference's own."""
    cell, driver, built, kept, reference, _ = followed
    other = {**reference, **driver.reference_step1(cell, built, kept, **how)}
    numbers = driver.numbers(other, reference)["numbers"]
    numbers["bad_steps"] = 0.0
    verdict = driver.compare.judge(numbers, cell.limits)
    assert verdict["correct"] is False, (how, verdict["checks"])
    worst = max(("grad_step1_gap", "grad_step1_leaf_gap"), key=lambda n: numbers[n] / cell.limits[n])
    assert numbers[worst] > 1.5 * cell.limits[worst], (how, verdict["checks"])  # over, not just over


@pytest.mark.parametrize("length,window", [(1, 1), (7, 3), (16, 16), (16, 40), (61, 13), (50, None)])
def test_band_pairs_closed_form_against_a_brute_force_count(length, window):
    distance = np.arange(length)[:, None] - np.arange(length)[None, :]
    seen = (distance >= 0) & (distance < (length if window is None else window))
    assert counts_windowed.band_pairs(length, window) == seen.sum()
    from replay_tpu.ops.flash_tiled import block_counts

    assert block_counts(length, 8, 8, True, window)["needed"] == seen.sum()  # the program's own figure


def test_counts_against_numbers_counted_by_hand():
    """The published widths at 1 x 8,192 positions (ISSUE 31's arithmetic)."""
    assert counts_windowed.band_pairs(8192, 1024) == 7_864_832
    assert counts_windowed.band_pairs(8192, None) == 33_558_528
    by_scope = counts_windowed.forward_flops_by_scope(SIZES, 1)
    projections = 2 * 8192 * 2304 * (4096 + 512 + 512 + 4096)  # q, k, v, o at 32 / 4 heads of 128
    assert by_scope["window_attention"] == 3 * (projections + 4 * 7_864_832 * 4096)
    assert by_scope["attention"] == projections + 4 * 33_558_528 * 4096
    # 8 of 64 routed, 8 of 64 held: one expert a position, in each of 4 layers
    assert by_scope["moe"] == 4 * (2 * 8192 * 2304 * 64 + 3 * 2 * 8192 * 2304 * 896)
    assert by_scope["head"] == 2 * 8192 * 2304 * 12288
    total = counts_windowed.step_train_flops(SIZES, 1)
    assert total == pytest.approx(9.6e12, rel=0.01)
    attention = 3 * (by_scope["window_attention"] + by_scope["attention"])
    assert attention / total == pytest.approx(0.73, abs=0.01)
    # a route that masks the window is owed the band all the same: 3.8 T of products it would add
    assert 3 * 3 * 4 * (33_558_528 - 7_864_832) * 4096 == pytest.approx(3.8e12, rel=0.01)
    seconds, bound = counts_windowed.attention_least_seconds(SIZES, 1, V5E, "sliding_attention")
    assert bound == "compute" and seconds == pytest.approx(3 * by_scope["window_attention"] / 197e12)
    weights = 2 * 2304 * (32 + 4) * 128
    assert counts_windowed.attention_train_bytes(SIZES, 1, "full_attention") == (
        2 * 8192 * 2304 * 2 + 2 * weights * 4)
    assert counts_windowed.step_train_flops(SIZES, 1, 0.0) == pytest.approx(total - 3 * 4 * 3 * 2 * 8192 * 2304 * 896)


def test_the_new_readers_read_a_hand_made_context(checkout, monkeypatch):
    from benchmark import stages

    counted = {"expert_load": [[[1024] * 8] * 4] * 8,
               "attention_blocks_visited": [[[150, 150]] * 3 + [[528, 528]]] * 8,
               "attention_blocks_needed": [[7_864_832 / 65536] * 3 + [33_558_528 / 65536]] * 8}
    log = [{"counters": counted}] * 3
    monkeypatch.setattr(stages, "records", lambda: log)
    context = {
        "device_kind": "TPU v5 lite", "batch_size": 1, "chips": 1, "steps": 200, "window_s": 40.0,
        "model_sizes": SIZES,
        "traced": {"steps": 16, "scope_s": {"window_attention": 0.64, "attention": 0.32, "moe": 0.4}},
    }
    read = lambda name, ctx=context: bench_run.load_module(  # noqa: E731
        checkout, f"benchmark/metrics/{name}.py").read(ctx)
    assert read("window_attention_ms_per_step") == pytest.approx(40.0)
    flops = counts_windowed.step_train_flops(SIZES, 1, 8192)
    assert read("windowed_step_mfu_pct") == pytest.approx(100 * flops * 200 / 40.0 / 197e12)
    for name, kind, device_s in (("window_attention_roofline_pct", "sliding_attention", 0.04),
                                 ("attention_roofline_pct", "full_attention", 0.02)):
        least, _ = counts_windowed.attention_least_seconds(SIZES, 1, V5E, kind)
        assert read(name) == pytest.approx(100 * least / device_s)
        assert 0 < read(name) < 100
    assert read("attention_band_blocks_visited") == pytest.approx(150 * 65536 / 7_864_832)  # 1.25
    # a route that masked the window would have visited the causal half square: 4.4
    masked = {**counted, "attention_blocks_visited": [[[528, 528]] * 4] * 8}
    monkeypatch.setattr(stages, "records", lambda: [{"counters": masked}] * 3)
    assert read("attention_band_blocks_visited") == pytest.approx(4.4, abs=0.01)
    # where the program counts nothing (the parent), or the cell is another model's: nothing, no error
    monkeypatch.setattr(stages, "records", lambda: [{"steps": 8}] * 20)
    assert read("attention_band_blocks_visited") is None
    lfm2 = json.loads((bench_helpers.REPO / "benchmark/configs/lfm2_24b_a2b_ep8.json").read_text())
    other = {**context, "model_sizes": {k: lfm2[k] for k in ("embedding_dim", "layers", "experts", "attention")},
             "traced": {"steps": 16, "scope_s": {"attention": 0.1, "loss": 1.0}}}
    plain = {**context, "model_sizes": {"embedding_dim": 64}, "traced": {"steps": 16, "scope_s": {"loss": 1.0}}}
    for name in ("windowed_step_mfu_pct", "window_attention_ms_per_step", "window_attention_roofline_pct",
                 "attention_roofline_pct", "attention_band_blocks_visited"):
        assert read(name, other) is None and read(name, plain) is None
