"""The latent-attention layer-pattern model through the benchmark at toy size on
the CPU: its cells are added as DATA plus one driver kind, run through
``drivers/fit_latent.py`` and are judged ``correct``; the lower-precision control
and every planted fault come out as not correct; the counts agree with numbers
counted by hand, and each new reader reads a hand-made context.

Tolerances of the toy cells (``limits/`` beside this file): the float32 cell holds
program and reference to 1e-5 on the losses and 1e-4 on update and first gradient,
every selection the same. The bfloat16 cell's limits (losses 5e-3, first gradient
0.05 and worst leaf 0.07: the program reads 0.014 and 0.018 there, the float8 control
0.15 and 0.20; loads 0.05) fail the float8 control and every fault: half the
batch, no routed experts, no shared expert, the rotary key dropped, the latent norm
dropped, scores over sqrt(8) instead of sqrt(12), the routed scale dropped.
"""

import json

import jax
import numpy as np
import pytest

import bench_helpers
import latent_helpers
from benchmark import counts_latent
from benchmark import run as bench_run

CELLS = [f"{config}.{traffic}" for config, traffic, _ in latent_helpers.LATENT_CELLS]
REAL = json.loads((bench_helpers.REPO / "benchmark/configs/moonlight_16b_a3b_ep8.json").read_text())
SIZES = {key: REAL[key] for key in (
    "embedding_dim", "max_sequence_length", "ffn_dim", "num_items", "layers", "experts",
    "shared_experts", "latent_attention", "attention",
)}
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("latent_step_mfu_pct", "latent_attention_ms_per_step", "latent_attention_roofline_pct",
               "shared_expert_ms_per_step")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return latent_helpers.make_checkout(tmp_path_factory.mktemp("bench_latent"))


def test_a_latent_cell_runs_through_its_driver_and_is_correct(checkout):
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
    # the selection bias is a buffer: the only leaves the optimizer leaves alone
    assert result["notes"]["comparison"]["leaves_left_out"] == ["layers.1.moe.bias"]
    # the counters of every layer kind ride the step metrics into the chunk stage log
    from benchmark import stages

    counted = stages.records()[-1]["counters"]
    assert np.asarray(counted["expert_load"]).shape == (2, 1, 4)  # [steps, sparse layers, held]
    assert np.asarray(counted["attention_blocks_visited"]).shape == (2, 2, 2)  # [steps, layers, (fwd, bwd)]
    assert np.asarray(counted["attention_blocks_needed"]).shape == (2, 2)
    tokens = np.asarray(counted["shared_expert_tokens"])  # [steps, sparse layers]: the real positions
    assert tokens.shape == (2, 1) and 0 < tokens.min() <= tokens.max() <= 4 * 16
    assert counts_latent.measured_shared_tokens(stages.records()) == pytest.approx(
        np.mean([r["counters"]["shared_expert_tokens"] for r in stages.records()]))
    assert counted["dropped_assignments"] == [[0]] * 2


def test_the_driver_names_this_models_groups_and_scopes_and_leaves_the_others_alone(checkout):
    latent = bench_run.load_module(checkout, "benchmark/drivers/fit_latent.py")
    windowed = bench_run.load_module(checkout, "benchmark/drivers/fit_windowed.py")
    hybrid = bench_run.load_module(checkout, "benchmark/drivers/fit_hybrid.py")
    config = json.loads((checkout / "benchmark/configs/tiny_moonlight.json").read_text())
    sizes = latent.reference_model(config)
    assert set(sizes) - {"layers", "experts", "shared_experts", "latent_attention", "attention", "norm_eps"} == {
        "embedding_dim", "num_blocks", "num_heads", "max_sequence_length", "ffn_dim",
        "activation", "dropout", "causal", "num_items",
    }
    assert hybrid.LAYER_SCOPES == ("moe", "conv", "attention", "dense_ffn") and "conv" in hybrid.GROUPS
    assert latent.run is not hybrid.run and latent.run is not windowed.run
    assert latent.read_capture is not windowed.read_capture
    # the shared expert's ops lie under no `moe` segment, the mixer's under `latent_attention` first
    from benchmark import tracing

    scopes = ("loss", "moe", "shared_expert", "latent_attention", "dense_ffn", "forward")
    path = "jit(f)/while/body/jvp(forward)/encoder/layer_1/{}/dot_general"
    assert tracing.scope_of(path.format("shared_expert/shared_expert/gate"), scopes) == "shared_expert"
    assert tracing.scope_of(path.format("moe/moe/router"), scopes) == "moe"
    assert tracing.scope_of(path.format("latent_attention/attention/kv_up"), scopes) == "latent_attention"
    assert tracing.scope_of(path.format("latent_attention/attention/kv_up"), ("attention",)) == "attention"


@pytest.fixture(scope="module")
def followed(checkout):
    """The bf16 toy cell's first chunk through ``fit``, and the float32 reference's
    trajectory over the same batches."""
    from functools import partial

    cell = bench_helpers.toy_cell(checkout, CELLS[0], jax.devices()[:1], seed=2147483659)
    driver = bench_run.load_module(checkout, "benchmark/drivers/fit_latent.py")
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
    assert {"output_table", "layers.0.attn.wkv_a", "layers.1.shared.w2"} <= set(program["first_gradient"])


@pytest.mark.parametrize(
    "how",
    [{"precision": "fp8"}, {"fault": "half_batch"}, {"fault": "no_experts"}, {"fault": "no_shared"},
     {"fault": "no_rope_key"}, {"fault": "no_latent_norm"}, {"fault": "scale_128"},
     {"fault": "no_routed_scale"}],
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


def test_counts_against_numbers_counted_by_hand():
    """The published widths at 1 x 4,096 positions (ISSUE 33's arithmetic)."""
    assert counts_latent.band_pairs(4096, None) == 8_390_656  # counts_windowed's causal half square
    assert counts_latent.projection_weights(SIZES) == {
        "q": 2048 * 3072, "kv_a": 2048 * 576, "kv_b": 512 * 4096, "o": 2048 * 2048}
    parts = counts_latent.attention_forward_flops_by_part(SIZES, 1)
    assert sum(parts[k] for k in ("q", "kv_a", "kv_b", "o")) == 2 * 4096 * 13_762_560  # 13.76M a layer
    assert parts["pairs"] == 8_390_656 * 16 * 2 * (192 + 128)
    by_kind = counts_latent.forward_flops_by_kind(SIZES, 1)
    assert by_kind["latent_attention"] == 5 * sum(parts.values())
    assert by_kind["shared_expert"] == 4 * 3 * 2 * 4096 * 2048 * 2816
    assert by_kind["dense_ffn"] == 3 * 2 * 4096 * 2048 * 11264
    # 6 of 64 routed, 8 of 64 held: 3,072 assignments a layer at even routing, 384 a held expert
    assert by_kind["moe"] == 4 * (2 * 4096 * 2048 * 64 + 3 * 2 * 3072 * 2048 * 1408)
    assert by_kind["head"] == 2 * 4096 * 2048 * 20480
    total = counts_latent.step_train_flops(SIZES, 1)
    assert total == pytest.approx(8.06e12, rel=0.005)
    assert 3 * by_kind["latent_attention"] / total == pytest.approx(0.37, abs=0.005)  # the largest kind
    assert 3 * by_kind["latent_attention"] == pytest.approx(2.98e12, rel=0.005)
    # counted: half the positions are padding and nothing is routed here
    half = counts_latent.step_train_flops(SIZES, 1, 0.0, 2048)
    assert half == pytest.approx(total - 3 * 4 * 3 * 2 * 2048 * 2048 * (1408 * 1.5 + 2816))
    seconds, bound = counts_latent.attention_least_seconds(SIZES, 1, V5E)
    assert bound == "compute" and seconds == pytest.approx(3 * by_kind["latent_attention"] / 197e12)
    assert counts_latent.attention_train_bytes(SIZES, 1) == 5 * (2 * 4096 * 2048 * 2 + 2 * 13_762_560 * 4)
    # padding v to 192 would owe a third more in the mix: 3 * 5 * pairs * 16 * 2 * 64 = 0.26 TFLOP
    assert 3 * 5 * 8_390_656 * 16 * 2 * 64 == pytest.approx(0.258e12, rel=0.01)


def test_the_new_readers_read_a_hand_made_context(checkout, monkeypatch):
    from benchmark import stages

    counted = {"expert_load": [[[500] * 8] * 4] * 8, "shared_expert_tokens": [[4000] * 4] * 8}
    monkeypatch.setattr(stages, "records", lambda: [{"counters": counted}] * 3)
    context = {
        "device_kind": "TPU v5 lite", "batch_size": 1, "chips": 1, "steps": 232, "window_s": 40.0,
        "model_sizes": SIZES,
        "traced": {"steps": 16, "runs": 2,
                   "scope_s": {"latent_attention": 0.72, "shared_expert": 0.24, "moe": 0.96, "loss": 0.12}},
    }
    read = lambda name, ctx=context: bench_run.load_module(  # noqa: E731
        checkout, f"benchmark/metrics/{name}.py").read(ctx)
    assert read("latent_attention_ms_per_step") == pytest.approx(45.0)
    assert read("shared_expert_ms_per_step") == pytest.approx(15.0)
    flops = counts_latent.step_train_flops(SIZES, 1, 4000, 4000)
    assert read("latent_step_mfu_pct") == pytest.approx(100 * flops * 232 / 40.0 / 197e12)
    least, _ = counts_latent.attention_least_seconds(SIZES, 1, V5E)
    assert read("latent_attention_roofline_pct") == pytest.approx(100 * least / 0.045)
    assert 0 < read("latent_attention_roofline_pct") < 100
    # the accepted expert readers read this cell too: routers + held experts at the counted loads
    from benchmark import counts_hybrid

    moe_least, _ = counts_hybrid.moe_least_seconds(SIZES, 1, V5E, 4000)
    assert read("moe_roofline_pct") == pytest.approx(100 * moe_least / 0.06)
    assert read("moe_ms_per_step") == pytest.approx(60.0)
    assert read("expert_load_max_over_mean") == 1.0  # 500 on every held expert: even
    # where the program counts nothing, every position and the even-routing expectation stand in
    monkeypatch.setattr(stages, "records", lambda: [{"steps": 8}] * 20)
    assert read("latent_step_mfu_pct") == pytest.approx(
        100 * counts_latent.step_train_flops(SIZES, 1) * 232 / 40.0 / 197e12)
    # the parent's program has neither scope, and another model's cell no latent group: nothing, no error
    mellum = json.loads((bench_helpers.REPO / "benchmark/configs/mellum2_12b_a2b_ep8.json").read_text())
    bare = {**context, "traced": {"steps": 16, "scope_s": {"moe": 0.9, "loss": 0.1, "forward": 2.0}}}
    other = {**context, "model_sizes": {k: mellum[k] for k in ("embedding_dim", "layers", "experts", "attention")},
             "traced": {"steps": 16, "scope_s": {"attention": 0.1, "loss": 1.0}}}
    plain = {**context, "model_sizes": {"embedding_dim": 64}, "traced": {"steps": 16, "scope_s": {"loss": 1.0}}}
    for name in NEW_READERS:
        assert read(name, other) is None and read(name, plain) is None
        if name != "latent_step_mfu_pct":
            assert read(name, bare) is None
