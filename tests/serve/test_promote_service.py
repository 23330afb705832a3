"""End-to-end hot swaps through a live ScoringService (jax + smoke).

The acceptance contract, machine-checked:

* a same-shape candidate swaps in with ZERO recompilation and post-swap
  scores are bitwise the new generation's direct forward_inference;
* every response under concurrent score()/swap traffic carries ONE
  self-consistent generation — its scores reproduce that generation's
  program bit-for-bit (no torn encoder/scorer reads);
* a swap EMPTIES effective cache hits (generation mismatch = miss) instead
  of scoring old hidden states through new weights;
* a grown catalog publishes as a recompiled generation and serves the new
  item ids while the old generation stays pinned for rollback;
* chaos mid-swap (injected engine faults) rides the degradation ladder —
  the service keeps answering, degraded at worst;
* the SLO-guarded controller promotes a clean candidate and rolls a forced
  breach back exactly once, end to end.
"""

import contextlib
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.nn.vocabulary import resize_item_embeddings
from replay_tpu.obs.slo import SLORule
from replay_tpu.serve import FallbackScorer, PromotionController, ScoringService, make_window
from replay_tpu.utils.faults import EngineErrorAt, wrap_method

pytestmark = [pytest.mark.jax, pytest.mark.smoke]

NUM_ITEMS, SEQ_LEN, DIM = 20, 8, 8


class RecordingLogger:
    def __init__(self):
        self.events = []

    def log_event(self, event):
        self.events.append(event)

    def close(self):
        pass

    def named(self, name):
        return [e for e in self.events if e.event == name]


def make_model(num_items=NUM_ITEMS):
    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id", FeatureType.CATEGORICAL, is_seq=True,
            feature_hint=FeatureHint.ITEM_ID, cardinality=num_items,
            embedding_dim=DIM,
        )
    )
    model = SasRec(
        schema=schema, embedding_dim=DIM, num_blocks=1, max_sequence_length=SEQ_LEN
    )
    ids = np.zeros((2, SEQ_LEN), np.int32)
    params = jax.jit(model.init)(  # one program, not flax's ~80 eager ones
        jax.random.PRNGKey(0), {"item_id": ids}, np.ones((2, SEQ_LEN), bool)
    )["params"]
    return model, jax.tree.map(np.asarray, params)


def perturb(params, scale):
    """A same-shape candidate: every leaf scaled (different scores, same tree)."""
    return jax.tree.map(lambda x: (np.asarray(x) * scale).astype(x.dtype), params)


def direct_scores(model, params, items, length_bucket, batch_bucket, programs=None):
    """The generation's own program: AOT forward_inference at the routed
    (length, batch) bucket — what a response must reproduce bit-for-bit.
    ``programs`` keeps the compiled program of each bucket for a caller whose
    generations all have one shape (the parameters are an argument)."""

    def fwd(p, ids, mask):
        return model.apply(
            {"params": p}, {"item_id": ids}, mask, method=SasRec.forward_inference
        )

    program = (programs or {}).get((length_bucket, batch_bucket))
    if program is None:
        program = (
            jax.jit(fwd)
            .lower(
                params,
                jax.ShapeDtypeStruct((batch_bucket, length_bucket), jnp.int32),
                jax.ShapeDtypeStruct((batch_bucket, length_bucket), jnp.bool_),
            )
            .compile()
        )
        if programs is not None:
            programs[(length_bucket, batch_bucket)] = program
    window, mask, _ = make_window(items, length_bucket)
    ids = np.stack([window] * batch_bucket)
    masks = np.stack([mask] * batch_bucket)
    return np.asarray(program(params, ids, masks))[0]


@pytest.fixture()
def service_setup():
    model, params = make_model()
    logger = RecordingLogger()
    service = ScoringService(
        model, params,
        length_buckets=(SEQ_LEN,),
        batch_buckets=(1, 4),
        max_wait_ms=10.0,
        logger=logger,
    )
    with service:
        yield model, params, service, logger


def lane_buckets(response):
    """(length_bucket, batch_bucket) a response's scores were computed at."""
    lane = response.lane.split("#", 1)[0]
    assert lane.startswith("encode:L=")
    return int(lane.split("=", 1)[1]), response.batch_bucket


@contextlib.contextmanager
def scoring_clients(service, histories):
    """Closed-loop load for the block's duration: one thread a user scoring its
    history back to back. Yields ``(responses, failures, more_traffic)``:
    ``(user, response)`` pairs, the exceptions a request raised (a client stops
    at its first), and a wait for ``count`` more answers so that real traffic
    lands on both sides of whatever the block does next."""
    responses, failures = [], []
    stop = threading.Event()

    def client(user):
        while not stop.is_set():
            try:
                response = service.score(user, history=histories[user], timeout=30)
            except Exception as exc:  # noqa: BLE001 — the tests assert none
                failures.append(exc)
                return
            responses.append((user, response))

    def more_traffic(count=6, timeout_s=10.0):
        target, deadline = len(responses) + count, time.monotonic() + timeout_s
        while len(responses) < target and time.monotonic() < deadline:
            time.sleep(0.005)

    threads = [threading.Thread(target=client, args=(user,)) for user in histories]
    for thread in threads:
        thread.start()
    try:
        yield responses, failures, more_traffic
    finally:
        stop.set()
        for thread in threads:
            thread.join()


class TestHotSwap:
    def test_same_shape_swap_is_recompile_free_and_bitwise(self, service_setup):
        model, params, service, logger = service_setup
        history = [3, 5, 7, 2]
        before = service.score("u1", history=history, timeout=30)
        assert before.generation == 0 and before.role == "stable"
        np.testing.assert_array_equal(
            before.scores, direct_scores(model, params, history, *lane_buckets(before))
        )

        candidate = perturb(params, 1.01)
        generation = service.publish_candidate(candidate, label="v1")
        publishes = logger.named("on_publish")
        assert len(publishes) == 1
        assert publishes[0].payload["recompiled"] is False  # same shapes: zero recompile
        assert service.store.generation(generation).engine is None  # shared executables

        info = service.promote(generation)
        assert info == {"from_generation": 0, "to_generation": generation}
        swaps = logger.named("on_swap")
        assert len(swaps) == 1 and swaps[0].payload["reason"] == "promote"

        after = service.score("u1", history=history, timeout=30)
        assert after.generation == generation
        np.testing.assert_array_equal(
            after.scores, direct_scores(model, candidate, history, *lane_buckets(after))
        )
        assert not np.array_equal(before.scores, after.scores)

    def test_swap_empties_effective_hits(self, service_setup):
        """Satellite regression: cached embeddings were encoded by the OLD
        generation — after a swap the pure-hit path MISSES (re-encode) and
        never mixes an old hidden state with the new scorer."""
        model, params, service, logger = service_setup
        history = [1, 2, 3]
        service.score("u2", history=history, timeout=30)
        hit = service.score("u2", timeout=30)  # warmed: a true pure hit
        assert hit.served_from == "hit" and hit.generation == 0

        candidate = perturb(params, 0.99)
        generation = service.publish_candidate(candidate)
        service.promote(generation)

        post = service.score("u2", timeout=30)
        # the cached embedding certified generation 0: MISS, re-encode, and
        # the response is entirely the new generation's math
        assert post.served_from != "hit"
        assert post.generation == generation
        np.testing.assert_array_equal(
            post.scores, direct_scores(model, candidate, history, *lane_buckets(post))
        )
        assert service.stats()["generation_misses"] >= 1

        rewarmed = service.score("u2", timeout=30)
        assert rewarmed.served_from == "hit"  # re-encoded under the new generation
        assert rewarmed.generation == generation

    def test_concurrent_scores_always_carry_one_consistent_generation(
        self, service_setup
    ):
        """Swap atomicity under concurrent score() threads: every response's
        generation tag reproduces that generation's program bitwise — a batch
        torn across a swap could not match any single generation."""
        model, params, service, logger = service_setup
        all_params = {0: params}
        histories = {
            f"user-{i}": [int(x) for x in np.random.default_rng(i).integers(1, NUM_ITEMS, 4)]
            for i in range(6)
        }
        with scoring_clients(service, histories) as (responses, failures, more_traffic):
            for swap in range(1, 5):
                more_traffic()  # both sides of each swap are observed under load
                candidate = perturb(params, 1.0 + 0.01 * swap)
                generation = service.publish_candidate(candidate)
                all_params[generation] = candidate
                service.promote(generation)

        assert not failures  # zero request errors across every swap
        assert len(responses) > 10
        seen_generations = {r.generation for _, r in responses}
        assert len(seen_generations) >= 2  # the swaps were observed mid-load
        cache, programs = {}, {}
        for user, response in responses:
            assert response.generation in all_params
            key = (user, response.generation, lane_buckets(response))
            if key not in cache:
                cache[key] = direct_scores(
                    model,
                    all_params[response.generation],
                    histories[user],
                    *lane_buckets(response),
                    programs=programs,
                )
            np.testing.assert_array_equal(response.scores, cache[key])

    def test_two_retrieval_swaps_under_load_recompile_nothing(self):
        """Swap under load in RETRIEVAL mode, where every generation ships its
        own MIPS pipeline (the index embeds that generation's item table): two
        same-shape swaps while clients score back to back are pointer moves
        (no publish recompiled), cost zero request errors, end on generation 2,
        and every answer is ONE generation's encoder AND index — its top-k
        reproduces that generation's logits."""
        from replay_tpu.models import MIPSIndex
        from replay_tpu.serve import CandidatePipeline

        model, params = make_model()
        top_k = 3

        def pipeline_of(generation_params):
            item_weights = np.asarray(
                model.apply({"params": generation_params}, method=SasRec.get_item_weights)
            )
            return CandidatePipeline(MIPSIndex(item_weights), num_candidates=10, top_k=top_k)

        logger = RecordingLogger()
        service = ScoringService(
            model, params,
            length_buckets=(SEQ_LEN,),
            batch_buckets=(1, 4),
            max_wait_ms=10.0,
            retrieval=pipeline_of(params),
            logger=logger,
        )
        all_params = {0: params}
        histories = {
            f"user-{i}": [int(x) for x in np.random.default_rng(i).integers(1, NUM_ITEMS, 4)]
            for i in range(4)
        }
        with service:
            with pytest.raises(ValueError, match="CandidatePipeline"):
                service.publish_candidate(perturb(params, 1.01))  # no index of its own
            with scoring_clients(service, histories) as (responses, failures, more_traffic):
                for swap in (1, 2):
                    more_traffic()
                    candidate = perturb(params, 1.0 + 0.05 * swap)
                    generation = service.publish_candidate(
                        candidate, label=f"swap-{swap}", pipeline=pipeline_of(candidate)
                    )
                    assert not service.store.generation(generation).recompiled
                    all_params[generation] = candidate
                    service.promote(generation)
                more_traffic()
            assert service.store.stable_generation == 2
            assert service.score("after", history=[1, 2, 3], timeout=30).generation == 2

        assert not failures, failures[:1]
        assert [e.payload["recompiled"] for e in logger.named("on_publish")] == [False, False]
        assert len(logger.named("on_swap")) == 2
        assert len({response.generation for _, response in responses}) >= 2
        logits, programs = {}, {}
        for user, response in responses:
            key = (user, response.generation, lane_buckets(response))
            if key not in logits:
                logits[key] = direct_scores(
                    model, all_params[response.generation], histories[user],
                    *lane_buckets(response), programs=programs,
                ).astype(np.float64)
            want_ids = np.argsort(-logits[key], kind="stable")[:top_k]
            assert set(response.item_ids) == set(want_ids)
            np.testing.assert_allclose(
                np.sort(response.scores), np.sort(logits[key][want_ids]), rtol=1e-5
            )

    def test_grown_catalog_publishes_recompiled_and_serves_new_items(
        self, service_setup
    ):
        model, params, service, logger = service_setup
        grown = resize_item_embeddings(
            jax.tree.map(np.asarray, params), model.schema, NUM_ITEMS + 4
        )
        generation = service.publish_candidate(grown, label="grown")
        publish = logger.named("on_publish")[-1].payload
        assert publish["recompiled"] is True
        assert "embedding" in publish["recompile_reason"]
        assert service.store.generation(generation).engine is not None

        service.promote(generation)
        new_item = NUM_ITEMS + 2  # an id that did not exist at construction
        response = service.score("grown-user", history=[1, new_item], timeout=30)
        assert response.generation == generation
        assert response.scores.shape[-1] == NUM_ITEMS + 4  # the grown catalog
        # the old generation stays pinned: rollback restores the old catalog
        service.rollback()
        back = service.score("rollback-user", history=[1, 2], timeout=30)
        assert back.generation == 0
        assert back.scores.shape[-1] == NUM_ITEMS


class TestCanaryRouting:
    def test_slice_serves_candidate_rest_serves_stable(self, service_setup):
        from replay_tpu.serve import in_canary_slice

        model, params, service, logger = service_setup
        candidate = perturb(params, 1.02)
        generation = service.publish_candidate(candidate)
        service.begin_canary(generation, fraction=0.5)
        users = [f"canary-user-{i}" for i in range(12)]
        for user in users:
            response = service.score(user, history=[2, 4, 6], timeout=30)
            if in_canary_slice(user, 0.5):
                assert response.role == "candidate"
                assert response.generation == generation
                np.testing.assert_array_equal(
                    response.scores,
                    direct_scores(model, candidate, [2, 4, 6], *lane_buckets(response)),
                )
            else:
                assert response.role == "stable"
                assert response.generation == 0
        roles = service.canary_stats()
        assert roles["candidate"]["answered"] > 0
        assert roles["stable"]["answered"] > 0

    def test_publish_during_canary_refused_and_routing_stays_pinned(
        self, service_setup
    ):
        """A publish racing a live canary must not redirect the slice: the
        controller refuses it outright, and even a low-level
        service.publish_candidate leaves canary traffic on the PINNED
        generation (never a just-published unvetted candidate)."""
        model, params, service, logger = service_setup
        controller = PromotionController(
            service, promote_after=99, min_canary_requests=1, fraction=1.0
        )
        pinned = controller.publish(perturb(params, 1.01), label="pinned")
        controller.begin_canary()
        with pytest.raises(RuntimeError, match="active canary"):
            controller.publish(perturb(params, 1.02), label="racer")
        # low-level publish is allowed (it only registers a candidate) —
        # but the canary slice keeps serving the pinned generation
        racer = service.publish_candidate(perturb(params, 1.03), label="low-level")
        response = service.score("pin-user", history=[1, 2], timeout=30)
        assert response.role == "candidate"
        assert response.generation == pinned
        assert response.generation != racer
        # the candidate ROLE without a canary (shadow probing) still
        # addresses the store's latest candidate
        probe = service.submit(
            "probe-user", history=[3, 4], _role="candidate"
        ).result(timeout=30)
        assert probe.generation == pinned  # canary active: pin wins even here
        service.end_canary()
        probe2 = service.submit(
            "probe-user-2", history=[3, 4], _role="candidate"
        ).result(timeout=30)
        assert probe2.generation == racer  # no canary: shadow probe, latest

    def test_stale_epoch_outcomes_do_not_pollute_the_new_canary_window(
        self, service_setup
    ):
        """A previous candidate's in-flight request (older canary epoch)
        landing after begin_canary must not count in the fresh window."""
        model, params, service, logger = service_setup
        first = service.publish_candidate(perturb(params, 1.01))
        service.begin_canary(first, fraction=1.0)
        service.score("epoch-user", history=[1, 2], timeout=30)
        assert service.canary_stats()["candidate"]["answered"] == 1
        service.rollback()
        second = service.publish_candidate(perturb(params, 1.02))
        service.begin_canary(second, fraction=1.0)
        # fresh window starts clean…
        assert service.canary_stats()["candidate"]["answered"] == 0
        # …and an old-epoch pending resolving NOW is not counted against it
        from replay_tpu.serve.request import PendingRequest

        stale = PendingRequest(request=None, future=None, served_from="hit", role="candidate")
        stale.canary_epoch = service._canary_epoch - 1
        assert not service._counts_for_role("candidate", stale)
        fresh = PendingRequest(request=None, future=None, served_from="hit", role="candidate")
        fresh.canary_epoch = service._canary_epoch
        assert service._counts_for_role("candidate", fresh)

    def test_controller_promotes_clean_candidate_end_to_end(self, service_setup):
        model, params, service, logger = service_setup
        controller = PromotionController(
            service, promote_after=2, min_canary_requests=1, fraction=1.0
        )
        generation = controller.publish(perturb(params, 1.01), label="clean")
        controller.begin_canary()
        for _ in range(2):
            service.score("ct-user", history=[1, 2, 3], timeout=30)
            controller.evaluate()
        assert controller.stage == "promoted"
        assert service.store.stable_generation == generation
        assert len(logger.named("on_promotion")) == 1
        # post-promotion, EVERYONE serves the new generation
        assert service.score("other", history=[5], timeout=30).generation == generation

    def test_forced_breach_rolls_back_once_and_service_keeps_answering(
        self, service_setup
    ):
        model, params, service, logger = service_setup
        # a rule that breaches on ANY canary evaluation with data — the
        # deterministic forced-breach lever the canary_smoke CI job also uses
        controller = PromotionController(
            service,
            rules=(SLORule("replay_canary_requests", ">=", 0.0, name="forced"),),
            promote_after=99,
            min_canary_requests=1,
            fraction=1.0,
        )
        generation = controller.publish(perturb(params, 1.5), label="bad")
        controller.begin_canary()
        service.score("fb-user", history=[1, 2], timeout=30)
        record = controller.evaluate()
        assert record["action"] == "rollback"
        assert controller.stage == "rolled_back"
        assert len(logger.named("on_rollback")) == 1
        assert service.store.stable_generation == 0
        # exactly ONE rollback incident; the service answers on the restored gen
        for _ in range(3):
            controller.evaluate()
        assert len(logger.named("on_rollback")) == 1
        response = service.score("fb-user-2", history=[3, 4], timeout=30)
        assert response.generation == 0
        history_events = [e["event"] for e in service.generation_history()]
        assert history_events.count("rolled_back") == 1


class TestChaosMidSwap:
    def test_engine_fault_mid_swap_rides_the_ladder(self):
        """EngineErrorAt hits while a canary is live: the breaker opens, the
        ladder answers (cache_only / fallback), nothing hangs, and after the
        faults clear the service promotes normally."""
        from replay_tpu.serve import CircuitBreaker

        model, params = make_model()
        logger = RecordingLogger()
        fallback = FallbackScorer(np.arange(NUM_ITEMS + 1, dtype=np.float64))
        service = ScoringService(
            model, params,
            length_buckets=(SEQ_LEN,),
            batch_buckets=(1, 4),
            max_wait_ms=5.0,
            logger=logger,
            fallback=fallback,
            breaker=CircuitBreaker(failure_threshold=2, reset_timeout_s=0.05),
        )
        with service:
            # warm a user so the cache_only rung has material
            service.score("chaos-user", history=[1, 2, 3], timeout=30)
            generation = service.publish_candidate(perturb(params, 1.01))
            service.begin_canary(generation, fraction=1.0)

            injector = EngineErrorAt(at_calls=range(3))
            original = wrap_method(service.engine, "encode", injector)
            outcomes = []
            for i in range(6):
                try:
                    response = service.score("chaos-user", new_items=[4], timeout=30)
                    outcomes.append(response.served_by)
                except Exception as exc:  # noqa: BLE001 — the breaker's trip
                    outcomes.append(type(exc).__name__)
            service.engine.encode = original
            # every request RESOLVED (failed fast or answered — none hung);
            # the injected faults tripped the breaker and the ladder took over
            assert len(outcomes) == 6
            assert len(injector.injected_at) <= 3
            assert "cache_only" in outcomes or "fallback" in outcomes
            # faults cleared: the canary still promotes
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                response = service.score("chaos-user", new_items=[5], timeout=30)
                if response.served_by == "primary":
                    break
            assert response.served_by == "primary"
            service.promote(generation)
            final = service.score("chaos-user", new_items=[6], timeout=30)
            assert final.generation == generation
        stats = service.stats()
        # the only errors are the injected trips — the swap itself cost none
        assert stats["errors"] <= len(injector.injected_at)
