"""The looped layer-pattern model through the benchmark at toy size on the CPU: its
cells are added as DATA plus one driver kind, run through ``drivers/fit_loop.py``
and are judged ``correct``; the lower-precision control and every planted fault
come out as not correct; the counts agree with numbers counted by hand, and each
new reader reads a hand-made context (the four readers are not listed in
``BENCHMARK.json`` yet: ``test_benchmark_startup_metrics.py`` pins the last eight
entries of ``per_layer``, PERF.md §7). The toy cells run two steps over two layers
on the standard attention route (the published cell: four steps, the fused route).

Tolerances of the toy cells (``limits/`` beside this file): the float32 cell holds
program and reference to 1e-5 on the losses and 1e-4 on update and first gradient,
the exit mass to 1e-5. The bfloat16 cell's limits (losses 5e-3, first gradient
0.05 and worst leaf 0.07, exit mass 0.01) fail the float8 control and every fault:
half the batch, one step fewer, no sandwich norms, the last exit alone, no entropy
term, the un-normed stream carried.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

import bench_helpers
import loop_helpers
from benchmark import counts_loop
from benchmark import run as bench_run

CELLS = [f"{config}.{traffic}" for config, traffic, _ in loop_helpers.LOOP_CELLS]
REAL = json.loads((bench_helpers.REPO / "benchmark/configs/ouro_2_6b_pp8.json").read_text())
SIZES = {key: REAL[key] for key in (
    "embedding_dim", "max_sequence_length", "ffn_dim", "num_items", "layers", "attention", "loop",
)}
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("loop_step_mfu_pct", "recurrence_ms_per_step", "recurrence_roofline_pct",
               "exit_heads_roofline_pct")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return loop_helpers.make_checkout(tmp_path_factory.mktemp("bench_loop"))


def test_a_loop_cell_runs_through_its_driver_and_is_correct(checkout):
    cell = bench_helpers.toy_cell(checkout, CELLS[1], jax.devices()[:1], seed=2147483659)
    result = bench_run.run_cell(cell)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["fit_samples_per_s"]["value"] > 0
    assert set(result["checks"]) == {
        "loss_step1", "loss_step2", "update_norm_gap", "grad_step1_gap", "grad_step1_leaf_gap",
        "exit_mass_step1_gap", "bad_steps",
    }
    mass = result["notes"]["comparison"]["first_exit_mass"]
    np.testing.assert_allclose(mass["program"], mass["reference"], rtol=1e-5)
    assert sum(mass["program"]) == pytest.approx(1.0, rel=1e-5)
    # what the loop and its loss count ride the step metrics into the chunk stage log
    from benchmark import stages

    counted = stages.records()[-1]["counters"]
    assert counted["loop_layer_applications"] == [[4], [4]]  # [steps, 1]: 2 passes x 2 layers
    assert np.asarray(counted["exit_mass"]).shape == (2, 2)  # [steps, T]
    np.testing.assert_allclose(np.sum(counted["exit_mass"], axis=1), 1.0, rtol=1e-5)
    assert np.asarray(counted["exit_loss"]).shape == (2, 2)


def test_the_driver_names_this_models_groups_and_scopes_and_leaves_the_others_alone(checkout):
    loop = bench_run.load_module(checkout, "benchmark/drivers/fit_loop.py")
    hybrid = bench_run.load_module(checkout, "benchmark/drivers/fit_hybrid.py")
    config = json.loads((checkout / "benchmark/configs/tiny_ouro.json").read_text())
    assert set(loop.reference_model(config)) - {"layers", "attention", "loop", "norm_eps"} == {
        "embedding_dim", "num_blocks", "num_heads", "max_sequence_length", "ffn_dim",
        "activation", "dropout", "causal", "num_items",
    }
    assert hybrid.LAYER_SCOPES == ("moe", "conv", "attention", "dense_ffn") and "experts" in hybrid.GROUPS
    assert loop.run is not hybrid.run and hybrid._FirstLoads is not loop._FirstMass
    from benchmark import tracing

    scopes = ("loss",) + loop.LAYER_SCOPES + ("forward",)
    step = "jit(f)/while/body/transpose(jvp(forward))/encoder/HybridRec._loop/while/body/HybridRec.one_step/{}"
    assert tracing.scope_of(step.format("recurrence/encoder/checkpoint/layer_1/add"), scopes) == "recurrence"
    assert tracing.scope_of(step.format("recurrence/encoder/layer_0/attention/attention/out"), scopes) == "attention"
    assert tracing.scope_of(step.format("recurrence/encoder/layer_0/dense_ffn/dense_ffn/gate"), scopes) == "dense_ffn"
    assert tracing.scope_of(step.format("exit_gate/exit_gate/dot_general"), scopes) == "exit_gate"
    assert tracing.scope_of("jit(f)/while/body/transpose(jvp(loss))/exit_head/get_logits", scopes) == "loss"
    # the parent's program has no looped model: refused before any data is made
    cell = bench_helpers.toy_cell(checkout, CELLS[0], jax.devices()[:1])
    program = {**cell.config["program"], "model_kwargs": {**cell.config["program"]["model_kwargs"],
                                                          "loop_period": "total_ut_steps"}}
    cell = dataclasses.replace(cell, config={**cell.config, "program": program})
    with pytest.raises(SystemExit, match="loop_period"):
        loop.build(cell, 1)


@pytest.fixture(scope="module")
def followed(checkout):
    """The bf16 toy cell's first chunk through ``fit``, and the float32 reference's
    trajectory over the same batches."""
    from functools import partial

    cell = bench_helpers.toy_cell(checkout, CELLS[0], jax.devices()[:1], seed=2147483659)
    driver = bench_run.load_module(checkout, "benchmark/drivers/fit_loop.py")
    built = driver.build(cell, cell.seed)
    assert built["trainer"].model.remat and built["trainer"].remat_policy == "full"
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
    assert {"gate.w", "gate.b", "layers.1.mixer_post_norm.scale"} <= set(program["first_gradient"])


@pytest.mark.parametrize(
    "how", [{"precision": "fp8"}] + [{"fault": fault} for fault in (
        "half_batch", "loop_3", "no_sandwich", "last_exit_only", "no_entropy", "unnormed_carry")],
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


def test_counts_against_numbers_counted_by_hand():
    """The published widths at 1 x 4,096 positions (ISSUE 37's arithmetic)."""
    assert counts_loop.applications(SIZES) == 24
    assert counts_loop.layer_weights(SIZES) == 4 * 2048**2 + 3 * 2048 * 5632  # 51.38M a layer
    application = counts_loop.application_forward_flops(SIZES, 1)
    assert application == 2 * 4096 * 51_380_224 + 8_390_656 * 16 * 2 * 256
    assert application == pytest.approx(489.6e9, rel=0.001)
    parts = counts_loop.forward_flops_by_part(SIZES, 1)
    assert parts["recurrence"] == pytest.approx(11.75e12, rel=0.001)
    assert parts["exits"] == 4 * (2 * 4096 * 2048 * 49152 + 2 * 4096 * 2048)
    total = counts_loop.step_train_flops(SIZES, 1)
    assert total == pytest.approx(45.2e12, rel=0.002)
    loop_share = (3 * 18 * application + 3 * 3 * parts["exits"] / 4) / total  # passes 2-4, exits 1-3
    assert loop_share == pytest.approx(0.75, abs=0.002)
    seconds, bound = counts_loop.recurrence_least_seconds(SIZES, 1, V5E)
    assert bound == "compute" and seconds == pytest.approx(3 * parts["recurrence"] / 197e12)
    seconds, bound = counts_loop.exit_heads_least_seconds(SIZES, 1, V5E)
    assert bound == "compute" and seconds == pytest.approx(3 * parts["exits"] / 197e12)


def test_the_new_readers_read_a_hand_made_context(checkout):
    context = {
        "device_kind": "TPU v5 lite", "batch_size": 1, "chips": 1, "steps": 48, "window_s": 40.0,
        "model_sizes": SIZES,
        "traced": {"steps": 8, "runs": 2, "scope_s": {"recurrence": 4.0, "loss": 0.8, "forward": 4.2}},
    }
    read = lambda name, ctx=context: bench_run.load_module(  # noqa: E731
        checkout, f"benchmark/metrics/{name}.py").read(ctx)
    assert read("recurrence_ms_per_step") == pytest.approx(500.0)
    assert read("loop_step_mfu_pct") == pytest.approx(
        100 * counts_loop.step_train_flops(SIZES, 1) * 48 / 40.0 / 197e12)
    least, _ = counts_loop.recurrence_least_seconds(SIZES, 1, V5E)
    assert read("recurrence_roofline_pct") == pytest.approx(100 * least / 0.5)
    heads, _ = counts_loop.exit_heads_least_seconds(SIZES, 1, V5E)
    assert read("exit_heads_roofline_pct") == pytest.approx(100 * heads / 0.1)
    assert 0 < read("recurrence_roofline_pct") < 100 and 0 < read("exit_heads_roofline_pct") < 100
    # the parent's program has no `recurrence` scope, another model's cell no loop group: nothing, no error
    bare = {**context, "traced": {"steps": 8, "scope_s": {"loss": 0.8, "forward": 4.2}}}
    plain = {**context, "model_sizes": {"embedding_dim": 64}, "traced": {"steps": 8, "scope_s": {"loss": 1.0}}}
    for name in NEW_READERS:
        assert read(name, plain) is None
        if name not in ("loop_step_mfu_pct", "exit_heads_roofline_pct"):
            assert read(name, bare) is None
