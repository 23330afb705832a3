"""The layer-pattern model through the benchmark at toy size on the CPU: its cells
are added as DATA plus one driver kind, run through ``drivers/fit_hybrid.py`` and
are judged ``correct``; the lower-precision control and every planted fault come
out as not correct; the shape counts agree with counts made by hand.

Tolerances of the toy cells (``limits/`` beside this file): float32 program
against the float32 reference reads 1e-7 on the losses, 1e-5 on the update and
1.2e-6 on the first gradient, element by element, with every selection the same
(limits 1e-5, 1e-4, 1e-4 and 0): the probe chunk does yield the first gradient. The
bfloat16 program reads up to 2e-3 on a loss, 0.04..0.1 on the first gradient (one
router near-tie that falls the other way moves an expert's gradient by a tenth at
these widths) and up to 0.026 on the loads; float8 reads 0.39 on the first gradient,
half the batch 0.97, no experts 0.63: the bfloat16 cell's own limits (5e-3, 0.2,
0.3, 0.05) fail each. The dropped selection bias moves 7% of 57 assignments, which
only the float32 cell's limit on the loads (0: every selection the same) can hold.
"""

import json

import jax
import pytest

import bench_helpers
import hybrid_helpers
from benchmark import counts_hybrid
from benchmark import run as bench_run
from replay_tpu.nn.train import Trainer

CELLS = [f"{config}.{traffic}" for config, traffic, _ in hybrid_helpers.HYBRID_CELLS]
REAL = json.loads((bench_helpers.REPO / "benchmark/configs/lfm2_24b_a2b_ep8.json").read_text())


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return hybrid_helpers.make_checkout(tmp_path_factory.mktemp("bench_hybrid"))


@pytest.fixture(scope="module")
def toy_results(checkout):
    events = []

    class Sink:
        def log_event(self, event):
            if event.event == "on_train_step":
                events.append(event)

    results = {}
    fit = Trainer.fit

    def fit_and_listen(self, *args, loggers=None, **kwargs):
        return fit(self, *args, loggers=[loggers, Sink()], **kwargs)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(Trainer, "fit", fit_and_listen)
        # the float32 cell goes through a whole run; the bfloat16 one is held to
        # its limits below, where the control and the faults are read against it
        cell = bench_helpers.toy_cell(checkout, CELLS[1], jax.devices()[:1], seed=2147483659)
        results[CELLS[1]] = bench_run.run_cell(cell)
    results["events"] = events
    return results


def test_a_layer_pattern_cell_runs_through_its_driver_and_is_correct(toy_results):
    result = toy_results[CELLS[1]]
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["fit_samples_per_s"]["value"] > 0
    assert set(result["checks"]) == {
        "loss_step1", "loss_step2", "update_norm_gap", "grad_step1_gap", "grad_step1_leaf_gap",
        "expert_load_step1_gap", "bad_steps",
    }
    loads = result["notes"]["comparison"]["first_loads"]
    assert loads["program"] == loads["reference"] and sum(map(sum, loads["program"])) > 0
    # the untrained selection bias is the one leaf without a gradient
    assert result["notes"]["comparison"]["leaves_left_out"] == ["layers.1.moe.bias", "layers.2.moe.bias"]


def test_the_counters_ride_every_step_event(toy_results):
    events = toy_results["events"]
    assert events and all("counters" in e.payload for e in events)
    counted = events[-1].payload["counters"]
    load = counted["expert_load"]  # [expert layers][held experts]
    assert len(load) == 2 and all(len(layer) == 4 for layer in load)
    assert counted["dropped_assignments"] == [0, 0]
    assert all(sum(layer) <= 4 * 16 * 2 for layer in load)  # at most k picks of B*L tokens


def test_the_fit_cells_still_run_the_fit_drivers_own_names(checkout):
    """The private instance carries this kind's names; the module the ``fit`` kind
    loads is untouched."""
    hybrid = bench_run.load_module(checkout, "benchmark/drivers/fit_hybrid.py")
    plain = bench_run.load_module(checkout, "benchmark/drivers/fit.py")
    config = json.loads((checkout / "benchmark/configs/tiny_lfm2.json").read_text())
    assert set(plain.reference_model(config)) == {
        "embedding_dim", "num_blocks", "num_heads", "max_sequence_length", "ffn_dim",
        "activation", "dropout", "causal", "num_items",
    }
    sizes = hybrid.reference_model(config)
    assert set(sizes) == set(plain.reference_model(config)) | set(hybrid.GROUPS)
    assert plain.read_capture is not hybrid.read_capture and hybrid.run is not plain.run
    for name in ("drive_first_chunk", "follow_reference", "numbers"):
        assert getattr(plain, name).__module__ == plain.__name__  # fit.py's own
        assert getattr(hybrid, name).__module__ == hybrid.__name__


@pytest.fixture(scope="module")
def followed(checkout):
    """The bf16 toy cell's first chunk through ``fit``, and the float32 reference's
    trajectory over the same batches."""
    from functools import partial

    cell = bench_helpers.toy_cell(checkout, CELLS[0], jax.devices()[:1], seed=2147483659)
    driver = bench_run.load_module(checkout, "benchmark/drivers/fit_hybrid.py")
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


def test_the_probe_chunk_reads_the_first_gradient_and_the_first_selection(followed):
    """One real step, then steps with no valid row: Adam's first moment is the
    first gradient times a constant, and the first step's counter the selection."""
    cell, driver, _, _, reference, program = followed
    ours, theirs = program["first_gradient"], reference["first_gradient"]
    assert set(ours) == set(theirs) and ours["item_table"].shape == theirs["item_table"].shape
    numbers = driver.numbers(program, reference)
    assert 1e-4 < numbers["numbers"]["grad_step1_gap"] < 0.2  # bfloat16 did run; the scale is right
    assert "grad_norm_gap" not in numbers["numbers"]
    assert program["first_loads"].shape == reference["first_loads"].shape == (2, 4)
    # the untrained bias has no gradient on either side
    assert not ours["layers.1.moe.bias"].any() and not theirs["layers.1.moe.bias"].any()


@pytest.mark.parametrize(
    "how,fails",
    [
        ({"precision": "fp8"}, "grad_step1_gap"),
        ({"fault": "half_batch"}, "grad_step1_gap"),
        ({"fault": "no_experts"}, "grad_step1_leaf_gap"),
    ],
    ids=lambda how: next(iter(how.values())) if isinstance(how, dict) else how,
)
def test_the_float8_control_and_each_planted_fault_fail_the_cells_own_limits(followed, how, fails):
    """Held to the bfloat16 toy cell's OWN limits, which its program is within, and
    by step 1 alone: the trajectory is the float32 reference's own."""
    cell, driver, built, kept, reference, _ = followed
    other = {**reference, **driver.reference_step1(cell, built, kept, **how)}
    numbers = driver.numbers(other, reference)["numbers"]
    numbers["bad_steps"] = 0.0
    verdict = driver.compare.judge(numbers, cell.limits)
    assert verdict["correct"] is False, (how, verdict["checks"])
    assert numbers[fails] > 1.5 * cell.limits[fails], (how, verdict["checks"])  # over, not just over


def test_a_fault_shows_in_the_trajectory_too(followed):
    """The whole of ``follow_reference`` with the held experts left out: the
    update over the chunk reads 1 on an expert leaf."""
    cell, driver, built, kept, reference, _ = followed
    other = driver.follow_reference(cell, built, kept, cell.seed, fault="no_experts")
    numbers = driver.numbers(other, reference)["numbers"]
    assert numbers["update_norm_gap"] > 0.9 and numbers["grad_step1_leaf_gap"] > 0.9
    assert max(numbers["loss_step1"], numbers["loss_step2"]) > 1e-3


def test_a_selection_that_forgets_the_bias_fails_on_the_loads(followed, checkout):
    """The bias steers the selection only, so the first step's loads are where a
    program that drops it shows; a toy's 57 held assignments need the float32
    cell's limit (every selection the same)."""
    cell, driver, built, kept, reference, _ = followed
    other = {**reference, **driver.reference_step1(cell, built, kept, fault="no_bias")}
    numbers = driver.numbers(other, reference)["numbers"]
    strict = json.loads((checkout / f"benchmark/limits/{CELLS[1]}.json").read_text())
    assert numbers["expert_load_step1_gap"] > 0.05 > strict["expert_load_step1_gap"]
    assert driver.compare.judge({**numbers, "bad_steps": 0.0}, strict)["correct"] is False


def test_counts_against_numbers_counted_by_hand():
    """The published widths at 8 x 1024 positions; forward FLOPs per position."""
    sizes = {key: REAL[key] for key in (
        "embedding_dim", "max_sequence_length", "ffn_dim", "num_items", "layers", "experts",
        "attention", "conv",
    )}
    per_position = {k: v / 8192 for k, v in counts_hybrid.forward_flops_by_kind(sizes, 8).items()}
    conv_mixer = 2 * 2048 * 6144 + 2 * 2048 * 2048  # 33,554,432
    assert per_position["conv"] == 4 * conv_mixer
    assert per_position["dense_ffn"] == 3 * 2 * 2048 * 11776  # 144,703,488
    # q, o at 2048 wide; k, v at 8 x 64; the causal half of a 1024 x 1024 square
    assert per_position["attention"] == 2 * 2 * 2048 * 2048 + 2 * 2 * 2048 * 512 + 2 * 2 * 512 * 2048
    # 4 of 64 routed, 8 of 64 held: half an expert a position, in each of 4 layers
    one_expert = 3 * 2 * 2048 * 1536  # 18,874,368
    assert per_position["moe"] == 4 * (2 * 2048 * 64 + one_expert / 2)
    assert per_position["head"] == 2 * 2048 * 8192
    assert counts_hybrid.expected_assignments(sizes, 8) == 4096
    total = counts_hybrid.step_train_flops(sizes, 8)
    assert total == pytest.approx(3 * 8192 * sum(per_position.values()))
    assert total == pytest.approx(9.26e12, rel=0.01)
    assert per_position["head"] / sum(per_position.values()) == pytest.approx(0.089, abs=0.002)
    # least bytes: 4 layers x (hidden states in and out at 2 bytes, float32 kernels and gradients)
    kernels = 2048 * 64 + 3 * 8 * 2048 * 1536
    assert counts_hybrid.moe_train_bytes(sizes, 8) == 4 * (2 * 8192 * 2048 * 2 + 2 * kernels * 4)
    seconds, bound = counts_hybrid.moe_least_seconds(
        sizes, 8, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    )
    # 4.84 ms of products against 3.28 ms of bytes: the count says compute-bound
    assert bound == "compute" and seconds == pytest.approx(3 * 8192 * 38_797_312 / 197e12)
    assert 4 * (67_108_864 + 605_028_352) / 819e9 < seconds


def test_the_new_readers_read_the_context_and_find_nothing_where_nothing_is(checkout, monkeypatch):
    from benchmark import stages

    monkeypatch.setattr(stages, "records", lambda: [])  # a program that counts nothing: the expectation
    sizes = {key: REAL[key] for key in (
        "embedding_dim", "max_sequence_length", "ffn_dim", "num_items", "layers", "experts",
        "attention", "conv",
    )}
    context = {
        "device_kind": "TPU v5 lite", "batch_size": 8, "chips": 1, "steps": 200,
        "window_s": 40.0, "model_sizes": sizes,
        "traced": {"steps": 16, "scope_s": {"moe": 0.32, "conv": 0.16, "attention": 0.08}},
    }
    read = lambda name, ctx=context: bench_run.load_module(  # noqa: E731
        checkout, f"benchmark/metrics/{name}.py").read(ctx)
    assert read("moe_ms_per_step") == pytest.approx(20.0)
    assert read("conv_ms_per_step") == pytest.approx(10.0)
    assert read("attention_ms_per_step") == pytest.approx(5.0)
    flops = counts_hybrid.step_train_flops(sizes, 8)
    assert read("hybrid_step_mfu_pct") == pytest.approx(100 * flops * 200 / 40.0 / 197e12)
    least, _ = counts_hybrid.moe_least_seconds(sizes, 8, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert read("moe_roofline_pct") == pytest.approx(100 * least / 0.02)
    # where the program counted its assignments, the shares are of that work
    twice = [{"counters": {"expert_load": [[[2048] * 8] * 4] * 8}}] * 3  # 16,384 a layer a step
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(stages, "records", lambda: twice)
        assert counts_hybrid.measured_assignments(twice) == 16384
        counted = counts_hybrid.step_train_flops(sizes, 8, 16384)
        assert counted - flops == pytest.approx(3 * 4 * 3 * 2 * (16384 - 4096) * 2048 * 1536)
        assert read("hybrid_step_mfu_pct") == pytest.approx(100 * counted * 200 / 40.0 / 197e12)
        more, _ = counts_hybrid.moe_least_seconds(
            sizes, 8, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, 16384
        )
        assert read("moe_roofline_pct") == pytest.approx(100 * more / 0.02) and more > 3 * least
    assert counts_hybrid.measured_assignments([{"steps": 8}]) is None
    # a SASRec cell's context: no layer groups, no such scope
    plain = {**context, "model_sizes": {"embedding_dim": 64}, "traced": {"steps": 16, "scope_s": {"loss": 1.0}}}
    for name in ("hybrid_step_mfu_pct", "moe_ms_per_step", "conv_ms_per_step",
                 "attention_ms_per_step", "moe_roofline_pct"):
        assert read(name, plain) is None


def test_expert_load_reader_takes_the_worst_layers_ratio_and_the_median_over_steps(checkout, monkeypatch):
    from benchmark import stages

    even, skewed = [[4, 4, 4, 4], [4, 4, 4, 4]], [[4, 4, 4, 4], [10, 2, 2, 2]]
    log = [{"counters": {"expert_load": [even, skewed, skewed]}} for _ in range(4)]
    monkeypatch.setattr(stages, "records", lambda: log)
    reader = bench_run.load_module(checkout, "benchmark/metrics/expert_load_max_over_mean.py")
    assert reader.read({}) == pytest.approx(2.5)  # 10 over a mean of 4, in 8 of 12 steps
    monkeypatch.setattr(stages, "records", lambda: log[:1])
    assert reader.read({}) is None  # under 10 steps: nothing to read
    monkeypatch.setattr(stages, "records", lambda: [{"steps": 8}] * 20)
    assert reader.read({}) is None  # a program that counts nothing
