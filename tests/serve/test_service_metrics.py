"""ScoringService live metrics: the scrapeable endpoint on a running service.

The serving half of the metrics-plane acceptance: ``ScoringService(
metrics_port=0)`` serves qps/fill/queue-wait/shed gauges WHILE answering
traffic, shed totals in the registry reconcile with ``stats()`` after the
throttled events flush at close, and serve-side SLO rules ride the same
watchdog as training.
"""

import json
import math
import time
import urllib.request

import numpy as np
import pytest

import jax

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.obs import SLORule
from replay_tpu.serve import RequestShed, ScoringService
from replay_tpu.utils.faults import LatencySpike, wrap_method

pytestmark = [pytest.mark.jax, pytest.mark.smoke]

NUM_ITEMS, SEQ_LEN, DIM = 20, 8, 8
HISTORY = [3, 1, 4, 1, 5]


@pytest.fixture(scope="module")
def model_and_params():
    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID, cardinality=NUM_ITEMS, embedding_dim=DIM,
        )
    )
    model = SasRec(
        schema=schema, embedding_dim=DIM, num_blocks=1, max_sequence_length=SEQ_LEN
    )
    ids = np.zeros((2, SEQ_LEN), np.int32)
    params = model.init(
        jax.random.PRNGKey(0), {"item_id": ids}, np.ones((2, SEQ_LEN), bool)
    )["params"]
    return model, params


def _service(model_and_params, **kwargs):
    model, params = model_and_params
    kwargs.setdefault("length_buckets", (SEQ_LEN,))
    kwargs.setdefault("batch_buckets", (1, 4))
    kwargs.setdefault("max_wait_ms", 5.0)
    return ScoringService(model, params, **kwargs)


def _scrape(service, path="/metrics"):
    url = service.metrics_exporter.url
    with urllib.request.urlopen(f"{url}{path}", timeout=10) as response:
        return response.read().decode()


def _gauge(text, name):
    lines = [line for line in text.splitlines() if line.startswith(name + " ")]
    assert lines, f"{name} missing from the scrape"
    return float(lines[0].rsplit(" ", 1)[1])


def test_live_scrape_carries_qps_fill_and_wait(model_and_params):
    service = _service(model_and_params, metrics_port=0)
    with service:
        assert service.metrics_exporter.port is not None
        for i in range(6):
            service.score(f"u{i}", history=HISTORY, timeout=30)
        text = _scrape(service)
        assert _gauge(text, "replay_serve_up") == 1.0
        assert _gauge(text, "replay_serve_rows_total") >= 6
        assert _gauge(text, "replay_serve_qps") > 0
        assert "replay_serve_batch_fill_bucket" in text
        assert "replay_serve_queue_wait_ms_bucket" in text
        snapshot = json.loads(_scrape(service, "/snapshot"))
        fill = snapshot["replay_serve_batch_fill"]
        assert fill["count"] >= 1 and 0.0 < fill["max"] <= 1.0
    # post-close: the endpoint is down, the registry keeps the final gauges
    assert service.metrics_exporter.port is None
    registry = service.metrics_registry
    assert registry.value("replay_serve_up") == 0.0
    assert registry.value("replay_serve_cache_hit_rate") is not None


def test_shed_totals_reconcile_with_stats(model_and_params):
    service = _service(
        model_and_params, metrics_port=0, max_queue_depth=1, max_wait_ms=1.0
    ).start()
    try:
        spike = LatencySpike(at_calls=[0], duration_s=0.5)
        wrap_method(service.engine, "encode", spike)
        blocker = service.submit("blocker", history=HISTORY)
        deadline = time.perf_counter() + 5.0
        while not spike.injected_at and time.perf_counter() < deadline:
            time.sleep(0.005)
        queued = service.submit("queued", history=HISTORY)
        sheds = [service.submit(f"over{i}", history=HISTORY) for i in range(3)]
        for shed in sheds:
            with pytest.raises(RequestShed):
                shed.result(timeout=5)
        blocker.result(timeout=30)
        queued.result(timeout=30)
        stats = service.stats()
        assert stats["shed"] == 3
    finally:
        service.close()
    # close() flushed the throttled on_shed tail, so the registry counter
    # reproduces the service total exactly
    registry = service.metrics_registry
    assert registry.value("replay_serve_shed_total") == stats["shed"]
    assert registry.value("replay_serve_shed_rate") == pytest.approx(
        stats["shed_rate"]
    )
    depth = registry.value(
        "replay_serve_lane_depth", labels={"lane": f"encode:L={SEQ_LEN}"}
    )
    assert depth is not None and depth >= 1


def test_serve_slo_rule_fires_through_the_logger(model_and_params):
    events = []

    class Sink:
        def log_event(self, event):
            events.append(event)

    service = _service(
        model_and_params,
        metrics_port=0,
        logger=Sink(),
        slo_rules=[SLORule("replay_serve_qps", ">", 0.0, name="any_traffic")],
    )
    with service:
        service.score("u", history=HISTORY, timeout=30)
    violations = [e for e in events if e.event == "on_slo_violation"]
    assert [e.payload["rule"] for e in violations] == ["any_traffic"]
    assert service.metrics_registry.value(
        "replay_slo_violations_total", labels={"rule": "any_traffic"}
    ) == 1


def test_busy_port_serves_traffic_unobserved(model_and_params):
    from replay_tpu.obs import MetricsExporter, MetricsRegistry

    squatter = MetricsExporter(MetricsRegistry(), port=0).start()
    try:
        service = _service(model_and_params, metrics_port=squatter.port)
        with service:
            response = service.score("u", history=HISTORY, timeout=30)
            assert math.isfinite(float(np.max(response.scores)))
            assert service.metrics_exporter.port is None
        # the bridge still populated the registry
        assert service.metrics_registry.value("replay_serve_rows_total") >= 1
    finally:
        squatter.close()
