"""ScoringService under fire: deadlines, shedding, breaker, degradation ladder.

The serve-side resilience contract (docs/serving.md "Overload and
degradation"):

* no orphaned waiters — ``close()`` resolves every pending future, a
  ``score(timeout=...)`` expiry cancels the request so batch build skips it,
  and an expired ``deadline_ms`` drops a request BEFORE it reaches the device;
* admission control — bounded lanes fail fast with ``RequestShed``;
* the breaker — consecutive engine failures open it, refused traffic walks
  the ladder (cache_only → fallback → ``CircuitOpen``), recovery re-closes it;
* degraded parity — a cache_only response is bitwise identical to a pure
  cache hit of the same stale state, with ``served_by`` correctly tagged.
"""

import threading
import time
from concurrent.futures import TimeoutError as FutureTimeoutError

import numpy as np
import pytest

import jax

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.obs import TrainerEvent
from replay_tpu.serve import (
    CircuitBreaker,
    CircuitOpen,
    DeadlineExceeded,
    FallbackScorer,
    RequestShed,
    ScoringService,
)
from replay_tpu.utils.faults import EngineErrorAt, InjectedFault, LatencySpike, wrap_method

pytestmark = [pytest.mark.jax, pytest.mark.smoke]

NUM_ITEMS, SEQ_LEN, DIM = 20, 8, 8


class EventLog:
    """RunLogger stand-in recording every emitted serve event."""

    def __init__(self):
        self.events = []
        self._lock = threading.Lock()

    def log_event(self, event: TrainerEvent) -> None:
        with self._lock:
            self.events.append((event.event, dict(event.payload)))

    def named(self, name):
        with self._lock:
            return [payload for event, payload in self.events if event == name]


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


HISTORY = [3, 1, 4, 1, 5]


class TestNoOrphanedWaiters:
    def test_close_resolves_every_pending_future(self, model_and_params):
        """The orphaned-waiter regression: futures pending at close() must be
        resolved — flushed through a healthy worker, or failed — never hung."""
        service = _service(model_and_params).start()
        # a permanently-failing engine: every dispatch errors, so pending
        # futures can only be resolved by failure paths
        wrap_method(service.engine, "encode", EngineErrorAt(at_calls=range(10_000)))
        futures = [
            service.submit(f"u{i}", history=HISTORY) for i in range(8)
        ]
        service.close()
        for future in futures:
            assert future.done(), "a pending future outlived close()"
            assert isinstance(future.exception(), Exception)
        # and the service refuses (fast-fails) new work rather than hanging it
        after = service.submit("late", history=HISTORY)
        assert after.done() and after.exception() is not None

    def test_score_timeout_cancels_and_batch_build_skips(self, model_and_params):
        """A client that gives up must not cost a scoring slot: the cancelled
        request is skipped at batch build (generation-counter style drop)."""
        service = _service(model_and_params, max_wait_ms=1.0).start()
        try:
            spike = LatencySpike(at_calls=[0], duration_s=0.4)
            wrap_method(service.engine, "encode", spike)
            blocker = service.submit("blocker", history=HISTORY)
            deadline = time.perf_counter() + 5.0
            while not spike.injected_at and time.perf_counter() < deadline:
                time.sleep(0.005)  # the worker is now wedged in the spike
            calls_before = service.engine.encode_calls
            with pytest.raises(FutureTimeoutError):
                service.score("impatient", history=HISTORY, timeout=0.05)
            blocker.result(timeout=30)
            time.sleep(0.1)  # let the worker drain the abandoned entry
            stats = service.stats()
            assert stats["cancelled"] >= 1
            # the abandoned request never reached the engine: only the
            # blocker's call landed after the wedge began
            assert service.engine.encode_calls == calls_before + 1
            assert stats["served_from"]["cold"] == 1  # blocker only
        finally:
            service.close()

    def test_deadline_expires_at_batch_build_before_device(self, model_and_params):
        log = EventLog()
        service = _service(model_and_params, max_wait_ms=1.0, logger=log).start()
        try:
            spike = LatencySpike(at_calls=[0], duration_s=0.4)
            wrap_method(service.engine, "encode", spike)
            blocker = service.submit("blocker", history=HISTORY)
            deadline = time.perf_counter() + 5.0
            while not spike.injected_at and time.perf_counter() < deadline:
                time.sleep(0.005)
            doomed = service.submit("doomed", history=HISTORY, deadline_ms=30.0)
            with pytest.raises(DeadlineExceeded) as info:
                doomed.result(timeout=30)
            assert info.value.waited_s >= 0.03 - 1e-3
            blocker.result(timeout=30)
            stats = service.stats()
            assert stats["deadline_misses"] == 1
            assert stats["deadline_miss_rate"] > 0.0
            assert stats["served_from"]["cold"] == 1  # the dropped one never scored
            # a fully-dropped batch still reports its drop accounting: the
            # worst storms must not go dark in the event stream
            dropped = [
                b for b in log.named("on_serve_batch")
                if b["rows"] == 0 and b["dropped_expired"] >= 1
            ]
            assert dropped, log.named("on_serve_batch")
        finally:
            service.close()

    def test_default_deadline_applies_when_request_has_none(self, model_and_params):
        service = _service(
            model_and_params, max_wait_ms=1.0, default_deadline_ms=30.0
        ).start()
        try:
            spike = LatencySpike(at_calls=[0], duration_s=0.4)
            wrap_method(service.engine, "encode", spike)
            blocker = service.submit("blocker", history=HISTORY)
            deadline = time.perf_counter() + 5.0
            while not spike.injected_at and time.perf_counter() < deadline:
                time.sleep(0.005)
            doomed = service.submit("doomed", history=HISTORY)  # no explicit deadline
            with pytest.raises(DeadlineExceeded):
                doomed.result(timeout=30)
            blocker.result(timeout=30)
        finally:
            service.close()


class TestAdmissionControl:
    def test_full_lane_sheds_with_depth_and_event(self, model_and_params):
        log = EventLog()
        service = _service(
            model_and_params, max_queue_depth=1, max_wait_ms=1.0, logger=log
        ).start()
        try:
            spike = LatencySpike(at_calls=[0], duration_s=0.5)
            wrap_method(service.engine, "encode", spike)
            blocker = service.submit("blocker", history=HISTORY)
            deadline = time.perf_counter() + 5.0
            while not spike.injected_at and time.perf_counter() < deadline:
                time.sleep(0.005)
            queued = service.submit("queued", history=HISTORY)  # fills the lane
            shed = service.submit("over", history=HISTORY)
            with pytest.raises(RequestShed) as info:
                shed.result(timeout=5)
            assert info.value.max_depth == 1
            assert info.value.retry_after_s is not None
            # a second shed inside the throttle window: its count coalesces
            # and MUST be flushed at close, not silently dropped
            shed2 = service.submit("over2", history=HISTORY)
            with pytest.raises(RequestShed):
                shed2.result(timeout=5)
            blocker.result(timeout=30)
            queued.result(timeout=30)
            stats = service.stats()
            assert stats["shed"] == 2 and stats["shed_rate"] > 0.0
            shed_events = log.named("on_shed")
            assert shed_events and shed_events[0]["lane"].startswith("encode")
        finally:
            service.close()
        # post-close: the trailing coalesced count was flushed, so summing
        # `count` over events.jsonl reproduces the shed total exactly
        assert sum(e["count"] for e in log.named("on_shed")) == 2

    def test_shed_encode_absorbed_by_cache_only_rung(self, model_and_params):
        """Overload degradation: a warm user's shed encode rides the hit lane
        on its stale cached state instead of failing."""
        log = EventLog()
        service = _service(
            model_and_params, max_queue_depth=1, max_wait_ms=1.0, logger=log
        ).start()
        try:
            service.score("warm", history=HISTORY, timeout=30)  # cache the state
            spike = LatencySpike(at_calls=[0], duration_s=0.5)
            wrap_method(service.engine, "encode", spike)
            blocker = service.submit("blocker", history=HISTORY)
            deadline = time.perf_counter() + 5.0
            while not spike.injected_at and time.perf_counter() < deadline:
                time.sleep(0.005)
            filler = service.submit("filler", history=HISTORY)  # encode lane full
            degraded = service.submit("warm", new_items=[7])
            response = degraded.result(timeout=30)
            assert response.served_by == "cache_only"
            assert any(
                payload["to"] == "cache_only" and payload["reason"] == "overload"
                for payload in log.named("on_degrade")
            )
            blocker.result(timeout=30)
            filler.result(timeout=30)
        finally:
            service.close()

    def test_open_loop_burst_above_capacity_is_bounded_and_fully_accounted(
        self, model_and_params
    ):
        """Overload end to end, no fallback floor: arrivals at several times
        what a stalled engine can serve, every request with a deadline. Every
        submission is accounted for (answered, shed at admission, or dropped
        expired at batch build), none hangs, and the latency of the ANSWERED
        ones stays near the deadline: nothing can queue past it."""
        deadline_ms, stall_s, submitted = 100.0, 0.02, 300
        service = _service(model_and_params, max_queue_depth=32, max_wait_ms=1.0).start()
        try:
            assert service.fallback is None
            # capacity: 4 rows a dispatch / 20 ms = 200 req/s; unbounded, the last
            # of 300 arrivals would wait ~1.5 s
            wrap_method(
                service.engine, "encode",
                LatencySpike(at_calls=range(100_000), duration_s=stall_s),
            )
            latencies = []  # of the answered; appended from the done-callbacks

            def on_done(submitted_at):
                def callback(future):
                    if future.exception() is None:
                        latencies.append(time.perf_counter() - submitted_at)

                return callback

            futures = []
            for i in range(submitted):  # ~1 ms apart: ~5x capacity, open loop
                future = service.submit(f"burst-{i}", history=HISTORY, deadline_ms=deadline_ms)
                future.add_done_callback(on_done(time.perf_counter()))
                futures.append(future)
                time.sleep(0.0005)
            # a hung future raises TimeoutError here
            outcomes = [type(future.exception(timeout=30)) for future in futures]
            answered = outcomes.count(type(None))
            drain = time.perf_counter() + 5.0  # done-callbacks run after the waiters wake
            while len(latencies) < answered and time.perf_counter() < drain:
                time.sleep(0.005)
            stats = service.stats()
        finally:
            service.close()
        shed, expired = outcomes.count(RequestShed), outcomes.count(DeadlineExceeded)
        assert answered + shed + expired == submitted, set(outcomes)  # no other error
        assert answered > 0 and shed + expired > 0, (answered, shed, expired)
        assert stats["shed"] == shed and stats["deadline_misses"] == expired
        assert stats["circuit_refusals"] == 0 and stats["breaker"]["state"] == "closed"
        # deadline + the dispatch in flight + scheduler slack, not the ~1.5 s of
        # an unbounded queue
        assert len(latencies) == answered
        assert np.percentile(latencies, 99) <= deadline_ms / 1000.0 + stall_s + 0.75


class TestDegradationLadder:
    def test_cache_only_is_bitwise_identical_to_the_pure_hit_path(
        self, model_and_params
    ):
        """THE degraded-parity gate: under an open breaker, a warm user's
        response is bitwise identical to a pure cache hit of the same stale
        state — it IS one — with served_by tagging the rung."""
        service = _service(model_and_params).start()
        try:
            service.score("warm", history=HISTORY, timeout=30)
            reference = service.score("warm", timeout=30)  # pure hit, primary
            assert reference.served_from == "hit"
            assert reference.served_by == "primary"
            for _ in range(service.breaker.failure_threshold):
                service.breaker.record_failure()
            assert service.breaker.state == "open"
            degraded = service.score("warm", new_items=[7], timeout=30)
            assert degraded.served_by == "cache_only"
            assert degraded.served_from == "hit"
            assert degraded.batch_bucket == reference.batch_bucket
            np.testing.assert_array_equal(degraded.scores, reference.scores)
            # the interaction still landed: the window advanced even though
            # the response scored the pre-advance state
            assert service.cache.peek("warm").window[-1] == 7
        finally:
            service.close()

    def test_pure_hits_stay_primary_while_breaker_is_open(self, model_and_params):
        service = _service(model_and_params).start()
        try:
            service.score("warm", history=HISTORY, timeout=30)
            for _ in range(service.breaker.failure_threshold):
                service.breaker.record_failure()
            response = service.score("warm", timeout=30)
            # a pure hit needs no encode — it is NOT degraded traffic
            assert response.served_by == "primary"
            assert response.served_from == "hit"
        finally:
            service.close()

    def test_fallback_floor_serves_cold_traffic_when_open(self, model_and_params):
        log = EventLog()
        fallback = FallbackScorer(np.arange(NUM_ITEMS, dtype=np.float32))
        service = _service(model_and_params, fallback=fallback, logger=log).start()
        try:
            for _ in range(service.breaker.failure_threshold):
                service.breaker.record_failure()
            response = service.score("brand-new", history=HISTORY, timeout=30)
            assert response.served_by == "fallback"
            assert response.served_from == "fallback"
            want_scores, want_ids = fallback.score()
            np.testing.assert_array_equal(response.scores, want_scores)
            assert response.item_ids is None and want_ids is None
            topk = service.score("another-new", history=HISTORY, k=3, timeout=30)
            np.testing.assert_array_equal(
                topk.item_ids, [NUM_ITEMS - 1, NUM_ITEMS - 2, NUM_ITEMS - 3]
            )
            assert fallback.served == 2
            assert service.stats()["served_by"]["fallback"] == 2
            assert any(
                payload["to"] == "fallback" for payload in log.named("on_degrade")
            )
        finally:
            service.close()

    def test_circuit_open_without_any_degraded_mode(self, model_and_params):
        service = _service(
            model_and_params,
            breaker=CircuitBreaker(failure_threshold=1, reset_timeout_s=60.0),
        ).start()
        try:
            service.breaker.record_failure()
            future = service.submit("cold-new", history=HISTORY)
            with pytest.raises(CircuitOpen) as info:
                future.result(timeout=5)
            assert info.value.retry_after_s == pytest.approx(60.0, abs=1.0)
            assert service.stats()["circuit_refusals"] == 1
        finally:
            service.close()


class TestBreakerIntegration:
    def test_consecutive_engine_failures_open_then_probe_recloses(
        self, model_and_params
    ):
        """The full round trip against a REAL engine: injected failures trip
        the breaker, the reset window passes, the half-open probe succeeds
        (injector exhausted) and traffic is primary again."""
        log = EventLog()
        service = _service(
            model_and_params,
            breaker=CircuitBreaker(failure_threshold=2, reset_timeout_s=0.15),
            logger=log,
        ).start()
        try:
            injector = EngineErrorAt(at_calls=range(2))
            wrap_method(service.engine, "encode", injector)
            for i in range(2):
                future = service.submit(f"trip{i}", history=HISTORY)
                with pytest.raises(InjectedFault):
                    future.result(timeout=30)
            assert service.breaker.state == "open"
            # the injector raises BEFORE the real encode (no device work), so
            # the failures are counted where the breaker lives: at dispatch
            assert service.breaker.stats()["failures"] == 2
            time.sleep(0.2)  # past the reset window: next encode is the probe
            response = service.score("probe", history=HISTORY, timeout=30)
            assert response.served_by == "primary"
            assert service.breaker.state == "closed"
            stats = service.breaker.stats()
            assert stats["opens"] == 1 and stats["closes"] == 1
            transitions = [(p["from"], p["to"]) for p in log.named("on_breaker")]
            assert transitions == [
                ("closed", "open"), ("open", "half_open"), ("half_open", "closed"),
            ]
        finally:
            service.close()

    def test_caller_supplied_transition_hook_is_chained_not_clobbered(
        self, model_and_params
    ):
        """A user's CircuitBreaker(on_transition=alerting_hook) keeps firing
        after the service wires its own event forwarding — and a raising hook
        never poisons the dispatch path."""
        seen = []

        def hook(old, new, info):
            seen.append((old, new))
            raise RuntimeError("pager down")  # must be contained

        log = EventLog()
        service = _service(
            model_and_params,
            breaker=CircuitBreaker(failure_threshold=1, on_transition=hook),
            logger=log,
        ).start()
        try:
            wrap_method(service.engine, "encode", EngineErrorAt(at_calls=[0]))
            future = service.submit("trip", history=HISTORY)
            with pytest.raises(InjectedFault):
                future.result(timeout=30)
            assert seen == [("closed", "open")]
            assert [(p["from"], p["to"]) for p in log.named("on_breaker")] == [
                ("closed", "open")
            ]
        finally:
            service.close()

    def test_stats_and_serve_end_carry_resilience_totals(self, model_and_params):
        log = EventLog()
        service = _service(model_and_params, logger=log).start()
        service.score("u", history=HISTORY, timeout=30)
        service.close()
        stats = service.stats()
        for key in (
            "shed", "deadline_misses", "cancelled", "circuit_refusals",
            "degraded", "shed_rate", "deadline_miss_rate", "error_rate",
            "served_by", "breaker",
        ):
            assert key in stats, key
        assert stats["served_by"]["primary"] == 1
        assert stats["degraded"] == 0
        (end,) = log.named("on_serve_end")
        assert end["shed_rate"] == 0.0 and end["breaker"]["state"] == "closed"
