"""Live metrics plane end-to-end: a scraped chunked fit with an SLO watchdog.

The smoke here is CI's acceptance gate for the metrics plane (see
.github/workflows/main.yml "live metrics plane"): a chunked
``fit(metrics_port=0)`` is scraped over HTTP *mid-fit* — the Prometheus text
must carry finite step-time / samples-per-sec / goodput gauges — a
fault-injected NaN step must trip the ``bad_steps`` SLO rule exactly once,
and the ``/snapshot`` JSON lands in the run directory as the artifact CI
uploads.
"""

import json
import math
import os
import urllib.request

import numpy as np
import pytest

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.obs import JsonlLogger, SLORule
from replay_tpu.obs.report import summarize_run
from replay_tpu.utils.faults import NaNInjector

pytestmark = [pytest.mark.jax, pytest.mark.smoke]

NUM_ITEMS = 12
SEQ_LEN = 8
BATCH = 8  # divisible by the 8-device data axis


def _run_dir(tmp_path, name):
    """CI exports REPLAY_TPU_RUN_DIR so the scrape + snapshot artifacts ship
    with the workflow; locally everything lands in tmp_path."""
    base = os.environ.get("REPLAY_TPU_RUN_DIR")
    return os.path.join(base, name) if base else str(tmp_path / name)


def make_schema() -> TensorSchema:
    # the float feature is the NaN-injection surface (integer ids can't
    # carry a NaN) — same recipe as tests/nn/test_fault_tolerance.py
    return TensorSchema(
        [
            TensorFeatureInfo(
                "item_id", FeatureType.CATEGORICAL, is_seq=True,
                feature_hint=FeatureHint.ITEM_ID, cardinality=NUM_ITEMS,
                embedding_dim=16,
            ),
            TensorFeatureInfo(
                "num_feature", FeatureType.NUMERICAL, is_seq=True, tensor_dim=1,
                embedding_dim=16,
            ),
        ]
    )


def make_batch(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    items = rng.integers(0, NUM_ITEMS, size=(BATCH, SEQ_LEN + 1)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), dtype=bool)
    return {
        "feature_tensors": {
            "item_id": items[:, :-1],
            "num_feature": rng.normal(size=(BATCH, SEQ_LEN)).astype(np.float32),
        },
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def make_trainer() -> Trainer:
    """The module's one configuration: its trainers share their two programs."""
    model = SasRec(
        schema=make_schema(), embedding_dim=16, num_blocks=1, num_heads=1,
        max_sequence_length=SEQ_LEN,
    )
    return PROGRAMS.adopt(Trainer(
        model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2),
        mesh=make_mesh(),
    ))


class MidFitScraper:
    """A RunLogger that scrapes the live exporter when the fit reaches
    ``at_step`` — proof the endpoint answers WHILE the loop is running."""

    def __init__(self, trainer: Trainer, at_step: int) -> None:
        self.trainer = trainer
        self.at_step = at_step
        self.metrics_text = None
        self.snapshot = None
        self._steps_seen = 0

    def log_event(self, event) -> None:
        if event.event != "on_train_step" or self.metrics_text is not None:
            return
        self._steps_seen += 1
        if self._steps_seen < self.at_step:
            return
        url = self.trainer.metrics_exporter.url
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as response:
            self.metrics_text = response.read().decode()
        with urllib.request.urlopen(f"{url}/snapshot", timeout=10) as response:
            self.snapshot = json.loads(response.read())


def _gauge_value(text: str, name: str) -> float:
    lines = [line for line in text.splitlines() if line.startswith(name + " ")]
    assert lines, f"{name} missing from the scrape"
    return float(lines[0].rsplit(" ", 1)[1])


def test_chunked_fit_scraped_mid_fit_with_nan_slo(tmp_path):
    run_dir = _run_dir(tmp_path, "metrics_smoke")
    trainer = make_trainer()
    injector = NaNInjector(at_steps=(3,))  # 0-based: step 4 of epoch 0
    scraper = MidFitScraper(trainer, at_step=12)  # inside epoch 1
    rules = [SLORule("replay_train_bad_steps", ">", 0, name="bad_steps")]

    with JsonlLogger(run_dir, mode="w") as sink:
        state = trainer.fit(
            lambda epoch: injector.wrap([make_batch(epoch * 10 + i) for i in range(8)]),
            epochs=2,
            scan_chunk=2,
            loggers=[sink, scraper],
            metrics_port=0,
            slo_rules=rules,
            tracer=True,  # goodput fractions reach the registry at epoch end
            log_every=0,
        )

    assert injector.injected_at == [3]
    assert int(state.bad_steps) == 1

    # -- the mid-fit scrape: live training gauges, present and finite ------- #
    text = scraper.metrics_text
    assert text is not None, "the exporter never answered mid-fit"
    for gauge in (
        "replay_train_loss",
        "replay_train_samples_per_sec",
        "replay_train_steps_per_sec",
    ):
        assert math.isfinite(_gauge_value(text, gauge)), gauge
    # the scraper sink precedes the metrics bridge in the fan-out, so at the
    # scrape instant the registry has bridged at_step - 1 steps
    assert _gauge_value(text, "replay_train_steps_total") >= scraper.at_step - 1
    assert _gauge_value(text, "replay_train_up") == 1.0
    assert _gauge_value(text, "replay_train_bad_steps") == 1.0
    assert "replay_train_step_seconds_bucket" in text
    # epoch 0 closed before the scrape: its goodput fractions are live gauges
    goodput_lines = [
        line for line in text.splitlines()
        if line.startswith("replay_goodput_fraction{")
    ]
    assert goodput_lines, "no goodput gauges in the mid-fit scrape"
    assert all(math.isfinite(float(l.rsplit(" ", 1)[1])) for l in goodput_lines)
    assert math.isfinite(_gauge_value(text, "replay_input_starvation"))

    # -- the NaN step tripped the bad_steps rule EXACTLY once --------------- #
    lines = [json.loads(line) for line in open(os.path.join(run_dir, "events.jsonl"))]
    violations = [line for line in lines if line["event"] == "on_slo_violation"]
    assert len(violations) == 1
    assert violations[0]["rule"] == "bad_steps"
    assert violations[0]["value"] == 1.0
    assert not [line for line in lines if line["event"] == "on_slo_recovery"]
    registry = trainer.metrics_registry
    assert registry.value(
        "replay_slo_violations_total", labels={"rule": "bad_steps"}
    ) == 1
    assert registry.value("replay_slo_breached", labels={"rule": "bad_steps"}) == 1.0

    # -- post-fit: exporter stopped, registry readable, report renders ------ #
    assert trainer.metrics_exporter is None
    assert registry.value("replay_train_steps_total") == 16
    assert registry.value("replay_train_up") == 0.0

    with open(os.path.join(run_dir, "metrics.txt"), "w") as fh:
        fh.write(text)
    with open(os.path.join(run_dir, "snapshot.json"), "w") as fh:
        json.dump(scraper.snapshot, fh, indent=2)

    summary = summarize_run(run_dir)
    assert summary["slo_violations"] == 1
    assert summary["slo_rules_fired"] == ["bad_steps"]
    assert summary["bad_steps"] == 1


def test_metrics_without_port_keeps_registry_only(tmp_path):
    """slo_rules alone (no exporter): the watchdog still runs on the bridged
    registry and violations still reach the sinks; no HTTP server appears."""
    trainer = make_trainer()
    injector = NaNInjector(at_steps=(1,))
    events = []

    class Sink:
        def log_event(self, event):
            events.append(event)

    trainer.fit(
        lambda epoch: injector.wrap([make_batch(i) for i in range(4)]),
        epochs=1,
        loggers=Sink(),
        slo_rules=[SLORule("replay_train_bad_steps", ">", 0, name="bad_steps")],
        log_every=0,
    )
    assert trainer.metrics_exporter is None
    assert [e.event for e in events if e.event == "on_slo_violation"] == [
        "on_slo_violation"
    ]
    assert trainer.metrics_registry.value("replay_train_bad_steps") == 1


def test_busy_metrics_port_never_fails_the_fit(tmp_path):
    """The graceful no-op: a port someone else owns logs a warning and the
    fit completes unobserved (registry still fills via the bridge)."""
    from replay_tpu.obs import MetricsExporter, MetricsRegistry

    squatter = MetricsExporter(MetricsRegistry(), port=0).start()
    trainer = make_trainer()
    try:
        state = trainer.fit(
            lambda epoch: [make_batch(i) for i in range(3)],
            epochs=1,
            metrics_port=squatter.port,
            log_every=0,
        )
        assert int(state.step) == 3
        assert trainer.metrics_registry.value("replay_train_steps_total") == 3
        assert trainer.metrics_exporter is None
    finally:
        squatter.close()
