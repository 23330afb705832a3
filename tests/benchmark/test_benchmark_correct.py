"""What ``correct`` has to catch, at a size a test run can hold.

Tolerances of the toy cells (``limits/`` beside this file), with their reasons:

* float32 program against the float32 reference (``tiny_bert4rec_f32.tiny_fit``,
  ``tiny_sasrec_f32.tiny_fit_dp4``): losses read 1e-7 and norm gaps 2e-7..2e-6 on
  the CPU (one float32 rounding, 6e-8, and the order of sums, which the data=4
  mesh changes too). Limits 1e-5 and 1e-4 leave 50x and are far under what any
  missing term gives (the smallest planted fault below reads 0.2).
* bfloat16 program against the float32 reference (``tiny_sasrec.tiny_fit``, the
  precision the real cells state): losses 1e-5..3e-4, norm gaps 0.02..0.04 at
  these toy widths, where one leaf holds 16 numbers. Limits 2e-3 and 0.1.

The control is the reference in float8 put in the program's place; the faults
are planted under a whole run (``run_cell``), which skips only the look for a
chip, and each has to come out as not correct.
"""

import jax
import pytest

import bench_helpers
from benchmark import run as bench_run
from replay_tpu.nn.train import Trainer


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return bench_helpers.make_checkout(tmp_path_factory.mktemp("bench_correct"))


def unchanged_state(real_step):
    """A step that returns its state unchanged (the counters still advance)."""

    def step(state, batch):
        new_state, metrics = real_step(state, batch)
        return state.replace(step=new_state.step, rng=new_state.rng), metrics

    return step


def half_batch(real_step):
    """Half of the batch left out, the mean taken over the rest."""

    def step(state, batch):
        mask = batch["target_padding_mask"]
        keep = (jax.numpy.arange(mask.shape[0]) < mask.shape[0] // 2)[:, None, None]
        return real_step(state, {**batch, "target_padding_mask": mask & keep})

    return step


@pytest.mark.parametrize("fault", [unchanged_state, half_batch], ids=lambda f: f.__name__)
def test_a_run_with_the_timed_path_broken_is_not_correct(checkout, monkeypatch, fault):
    build = Trainer._build_train_step
    monkeypatch.setattr(
        Trainer, "_build_train_step", lambda self, health=None: fault(build(self, health))
    )
    cell = bench_helpers.toy_cell(checkout, "tiny_sasrec.tiny_fit", jax.devices()[:1], seconds=0.2)
    result = bench_run.run_cell(cell)
    assert result["correct"] is False
    over = {k for k, c in result["checks"].items() if not c["value"] <= c["limit"]}
    assert over & {"grad_norm_gap", "update_norm_gap"}, result["checks"]
    if fault is unchanged_state:  # nothing moved: both norms read 1 by the measure
        assert result["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)
        assert result["checks"]["grad_norm_gap"]["value"] == pytest.approx(1.0)


def test_the_float8_control_and_the_planted_fault_fail_against_the_reference(checkout):
    """The reference in the program's place: in float8 (the precision below the
    bfloat16 the cells state) and with half the batch left out. Each must fail a
    number by the bf16 cell's limits, and read at least 3x what bf16 reads."""
    cell = bench_helpers.toy_cell(checkout, "tiny_sasrec.tiny_fit", jax.devices()[:1])
    driver = bench_run.load_module(checkout, "benchmark/drivers/fit.py")
    built = driver.build(cell, cell.seed)
    stream = driver.Stream(built["batcher"], built["transform"], False, cell.seed,
                           cell.traffic["scan_chunk"])
    kept = list(stream.first_chunk())
    reference = driver.follow_reference(cell, built, kept, cell.seed)
    for how in ({"precision": "fp8"}, {"fault": "half_batch"}):
        other = driver.follow_reference(cell, built, kept, cell.seed, **how)
        numbers = driver.numbers(other, reference)["numbers"]
        numbers["bad_steps"] = 0.0
        verdict = driver.compare.judge(numbers, cell.limits)
        assert verdict["correct"] is False, (how, verdict["checks"])
