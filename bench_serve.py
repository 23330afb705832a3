"""Serving benchmark: the online scoring service under closed, open-loop,
OVERLOAD and chaos load.

Drives ``replay_tpu.serve.ScoringService`` (micro-batcher → compiled bucket
executables → per-user state cache → optional MIPS+rerank pipeline) with a
load generator and prints ONE JSON line in bench.py's record format::

    {"metric": "serve_qps", "value": ..., "unit": "req/s", "qps": ...,
     "p50_ms": ..., "p95_ms": ..., "p99_ms": ..., "batch_fill_ratio": ...,
     "cache_hit_rate": ..., "closed_loop_qps": ..., "serve_shed_rate": ...,
     "serve_deadline_miss_rate": ..., "serve_error_rate": ...,
     "overload": {...}, "chaos": {...}, "backend": ...}

Phases after a cold-seed warmup (every program is AOT-compiled at service
construction, so the timed phases never trace):

* **closed loop** — ``CLIENTS`` threads issue synchronous requests back to
  back (the saturation number: how fast can the service go when callers never
  let it idle);
* **open loop** — one generator submits with Poisson-exponential gaps at
  ``RATE`` req/s for ``SECONDS`` (the latency-under-load number: p50/p95/p99
  from submit to response, measured on completion callbacks, immune to
  coordinated omission);
* **overload** (``OVERLOAD_SECONDS > 0``, default on) — open loop at
  ``OVERLOAD_FACTOR x`` the measured closed-loop capacity with a per-request
  ``deadline_ms``: arrival rate ≫ service rate, so the bounded lanes MUST
  shed and the batch builder MUST drop expired waiters — the row asserts the
  resilience layer keeps p99 bounded (queues cannot grow without bound) with
  explicit shed/deadline-miss accounting. The fallback floor is disabled for
  this phase so admission control itself is what gets measured;
* **quant A/B** (retrieval mode) — the precision ladder's serving rung: the
  same catalog + encoder query states through a f32 and an int8-quantized
  ``CandidatePipeline`` (``replay_tpu.serve.quant``; exact f32 rescore of the
  retrieved candidates). The ``quant`` block records recall@C of the int8
  sweep, end-to-end top-k agreement, per-batch rank latency and the 4× table-
  bytes ratio; ``obs.report --compare`` gates recall/topk-match higher-better;
* **ann** (``--ann`` / ``REPLAY_TPU_SERVE_ANN=1``) — sub-linear retrieval
  A/B (docs/serving.md "Sub-linear retrieval"): brute f32 MIPS vs a
  clustered IVF index over a synthetic clustered catalog at ``ANN_ITEMS``
  scale. HARD-GATED, not observed: recall@100 >= 0.99 always; at >=10M
  items additionally speedup >= 10x vs brute; int8 / int8+pq rung recall
  gates on a fixed-geometry 100k rung catalog (pq through its 3x-overfetch
  + exact-rescore serving configuration); the 100M byte projection must
  show PQ fitting a 16 GiB HBM budget that the int8 brute table cannot. ``obs.report``
  renders the ``ann`` block and ``--compare`` gates recall/agreement
  higher-better plus ``ann_qps``;
* **swap under load** (``REPLAY_TPU_SERVE_SWAPS=N``) — N hot weight swaps
  (``serve.promote``: publish a perturbed same-shape candidate → promote,
  zero recompilation) while closed-loop clients keep scoring. The ``swap``
  block records p50/p99 across the phase, the zero-request-errors claim, the
  generation tags observed and the publish→promote apply time;
  ``obs.report --compare`` gates ``swap_p99_ms`` lower-better when both runs
  ran the phase;
* **chaos** (``--chaos`` / ``REPLAY_TPU_SERVE_CHAOS=1``) — deterministic
  fault injection via ``replay_tpu.utils.faults``: consecutive engine errors
  trip the circuit breaker (degraded traffic rides the cache_only/fallback
  ladder, tagged in ``served_by``), a latency spike exercises the client-
  abandon drop, a deadline storm exercises expiry-at-batch-build, and the
  breaker must re-close after recovery. The row asserts zero hung futures;
* **drift** (``REPLAY_TPU_SERVE_DRIFT_REQUESTS > 0``, default on) — the
  quality plane's injected preference shift (``obs.quality``): a
  ``QualityMonitor`` rides the whole run (every phase's served slates feed
  its windowed coverage/novelty/surprisal gauges and the online prequential
  hitrate/NDCG from ``new_items`` labels), then the phase sends
  ``DRIFT_REQUESTS`` steady advances (uniform labels — the distribution the
  PSI reference froze on) followed by ``DRIFT_REQUESTS`` advances whose
  labels all land on the popularity HEAD. PSI on the incoming-label series
  must cross ``DRIFT_THRESHOLD`` and trip the ``drift_psi`` SLO rule exactly
  once (the watchdog's transition latch). The ``drift`` block records
  psi before/after, the violation count and the online metrics;
  ``obs.report --compare`` gates ``quality_online_hitrate`` higher-better
  and ``quality_drift_psi`` lower-better (phase-matched).

Request mix per returning user: mostly pure cache hits, a slice of one-step
incremental advances, a trickle of cold full-history re-sends — the shape the
per-user state cache exists for. ``REPLAY_TPU_SERVE_*`` env vars override
every shape/load/resilience knob (CI smoke runs tiny configs, flagged
``shape_override``), mirroring the ``REPLAY_TPU_BENCH_*`` convention so CI
and chip runs share this one entrypoint. Events + trace land in
``runs/bench_serve/`` (the record itself is appended to events.jsonl, so
``python -m replay_tpu.obs.report runs/bench_serve`` renders the serving
section from one artifact, and ``--compare`` gates QPS/p99 regressions plus
the lower-better ``serve_error_rate`` / ``serve_deadline_miss_rate`` gates).

Backend policy mirrors bench.py: one process on the backend JAX gives it, the
record stamped with ``platform`` / ``device_kind`` / ``device_count``; on the
CPU the metric is renamed ``serve_qps_cpu_fallback``.
"""

import json
import os
import sys
import threading
import time

import numpy as np

from replay_tpu.utils.compile_cache import enable_compile_cache

_DEFAULTS = {
    "SEQ_LEN": 50,
    "NUM_ITEMS": 3706,
    "EMBEDDING_DIM": 64,
    "NUM_BLOCKS": 2,
    "USERS": 512,
    "CLIENTS": 8,
    "CLOSED_REQUESTS": 64,  # per client thread
    "RATE": 500,  # open-loop arrivals per second
    "SECONDS": 8,  # open-loop duration
    "CANDIDATES": 100,  # MIPS retrieval cut; 0 = full-catalog scoring mode
    "TOPK": 10,
}


def _knob(name: str) -> int:
    return int(os.environ.get(f"REPLAY_TPU_SERVE_{name}", _DEFAULTS[name]))


SEQ_LEN = _knob("SEQ_LEN")
NUM_ITEMS = _knob("NUM_ITEMS")
EMBEDDING_DIM = _knob("EMBEDDING_DIM")
NUM_BLOCKS = _knob("NUM_BLOCKS")
USERS = _knob("USERS")
CLIENTS = _knob("CLIENTS")
CLOSED_REQUESTS = _knob("CLOSED_REQUESTS")
RATE = _knob("RATE")
SECONDS = _knob("SECONDS")
CANDIDATES = _knob("CANDIDATES")
TOPK = _knob("TOPK")
MAX_WAIT_MS = float(os.environ.get("REPLAY_TPU_SERVE_MAX_WAIT_MS", "2.0"))
BATCH_BUCKETS = tuple(
    int(b) for b in os.environ.get("REPLAY_TPU_SERVE_BATCH_BUCKETS", "1,8,64").split(",")
)
LENGTH_BUCKETS = tuple(
    int(b)
    for b in os.environ.get("REPLAY_TPU_SERVE_LENGTH_BUCKETS", "").split(",")
    if b.strip()
) or None
# resilience/chaos knobs (not shape knobs: they never flag shape_override)
DEADLINE_MS = float(os.environ.get("REPLAY_TPU_SERVE_DEADLINE_MS", "250"))
MAX_DEPTH = int(os.environ.get("REPLAY_TPU_SERVE_MAX_DEPTH", "0"))  # 0 = auto
OVERLOAD_FACTOR = float(os.environ.get("REPLAY_TPU_SERVE_OVERLOAD_FACTOR", "4"))
OVERLOAD_SECONDS = float(os.environ.get("REPLAY_TPU_SERVE_OVERLOAD_SECONDS", "3"))
BREAKER_THRESHOLD = int(os.environ.get("REPLAY_TPU_SERVE_BREAKER_THRESHOLD", "5"))
BREAKER_RESET_MS = float(os.environ.get("REPLAY_TPU_SERVE_BREAKER_RESET_MS", "300"))
CHAOS = (
    bool(int(os.environ.get("REPLAY_TPU_SERVE_CHAOS", "0"))) or "--chaos" in sys.argv
)
# swap-under-load phase (serve.promote): N hot weight swaps while closed-loop
# clients keep scoring — proves p99 stays bounded and ZERO requests error
# across the swaps, every response tagged with one consistent generation.
# 0 = phase off (the default; obs.report only gates swap_p99_ms when both
# compared runs ran it, the PR-9 phase-matching rule)
SWAPS = int(os.environ.get("REPLAY_TPU_SERVE_SWAPS", "0"))
SWAP_GAP_MS = float(os.environ.get("REPLAY_TPU_SERVE_SWAP_GAP_MS", "200"))
# quality/drift phase (obs.quality): DRIFT_REQUESTS steady advances (uniform
# labels, the distribution the PSI reference froze on) then DRIFT_REQUESTS
# advances whose labels all land on the popularity head — the injected
# preference shift must push the incoming-label PSI past DRIFT_THRESHOLD and
# trip the drift_psi SLO rule exactly once. 0 / --no-drift = phase off.
# The threshold sits BETWEEN the bench's two PSI bands: small-window sampling
# noise plus the shift's second-order echoes (served-slate score/popularity
# drift) plateau near ~1.0, while the directly shifted incoming-label series
# lands well above ~4 — and that series climbs monotonically during the
# shift (the label window only gains head items), so the gauge crosses any
# threshold in the gap exactly once and the for_steps=2 rule cannot re-fire.
DRIFT_REQUESTS = int(os.environ.get("REPLAY_TPU_SERVE_DRIFT_REQUESTS", "256"))
DRIFT_THRESHOLD = float(os.environ.get("REPLAY_TPU_SERVE_DRIFT_THRESHOLD", "1.5"))
if "--no-drift" in sys.argv:
    DRIFT_REQUESTS = 0
# sub-linear retrieval phase (the IVF rung, docs/serving.md "Sub-linear
# retrieval"): opt-in — a >=10M-item build runs minutes of k-means on one
# CPU core, so the phase only rides along when asked (--ann /
# REPLAY_TPU_SERVE_ANN=1). The ANN knobs are phase-local: the phase builds
# its OWN synthetic clustered catalog (the regime IVF exists for — real
# item embeddings cluster by taxonomy/popularity) and never touches the
# service's shapes, so they do not flag shape_override.
ANN = bool(int(os.environ.get("REPLAY_TPU_SERVE_ANN", "0"))) or "--ann" in sys.argv
ANN_ITEMS = int(os.environ.get("REPLAY_TPU_SERVE_ANN_ITEMS", "10000000"))
ANN_DIM = int(os.environ.get("REPLAY_TPU_SERVE_ANN_DIM", "64"))
ANN_NLIST = int(os.environ.get("REPLAY_TPU_SERVE_ANN_NLIST", "0"))  # 0 = auto
ANN_NPROBE = int(os.environ.get("REPLAY_TPU_SERVE_ANN_NPROBE", "16"))
ANN_QUERIES = int(os.environ.get("REPLAY_TPU_SERVE_ANN_QUERIES", "64"))
ANN_BUILD_SAMPLE = int(os.environ.get("REPLAY_TPU_SERVE_ANN_BUILD_SAMPLE", "131072"))
# the live metrics plane rides every bench run: 0 = ephemeral port (the
# default — collision-proof); -1 disables the metrics plane entirely (no
# registry either, so the record omits its `metrics` reconciliation block —
# CI always runs with the default and gates on that block being present)
METRICS_PORT = int(os.environ.get("REPLAY_TPU_SERVE_METRICS_PORT", "0"))
if "--no-overload" in sys.argv:
    OVERLOAD_SECONDS = 0.0
SHAPE_OVERRIDE = any(_knob(k) != v for k, v in _DEFAULTS.items())

RUN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "runs", "bench_serve")


def _percentile(latencies, q: float) -> float:
    return float(np.percentile(np.asarray(latencies), q)) if latencies else float("nan")


def _classify(exc) -> str:
    """Bucket a failed future's exception for phase accounting."""
    from replay_tpu.serve import CircuitOpen, DeadlineExceeded, RequestShed

    if isinstance(exc, RequestShed):
        return "shed"
    if isinstance(exc, DeadlineExceeded):
        return "deadline_missed"
    if isinstance(exc, CircuitOpen):
        return "circuit_refused"
    return "error"


def _await_all(futures, timeout_s: float = 60.0) -> int:
    """Wait for every future to resolve; returns how many are STILL pending
    past the grace period — the zero-hung-requests acceptance number."""
    deadline = time.perf_counter() + timeout_s
    for future in futures:
        remaining = deadline - time.perf_counter()
        if remaining <= 0:
            break
        try:
            future.result(timeout=remaining)
        except Exception:  # noqa: BLE001 — accounting happens elsewhere
            pass
    return sum(1 for future in futures if not future.done())


def _run_overload(service, one_request, rate: float):
    """Open loop at ``rate`` ≫ capacity with per-request deadlines. The
    fallback floor is detached for the phase so the admission-control path
    (bounded lanes → RequestShed, expiry at batch build → DeadlineExceeded)
    is what gets measured, not the infinite-capacity popularity scorer."""
    fallback, service.fallback = service.fallback, None
    rng = np.random.default_rng(11)
    futures = []
    latencies = []
    lock = threading.Lock()
    counts = {"shed": 0, "deadline_missed": 0, "circuit_refused": 0, "error": 0}
    peak_depth = 0

    def on_done(submitted_at):
        def callback(future):
            latency = time.perf_counter() - submitted_at
            exc = future.exception()
            with lock:
                if exc is None:
                    latencies.append(latency)
                else:
                    counts[_classify(exc)] += 1

        return callback

    start = time.perf_counter()
    deadline = start + OVERLOAD_SECONDS
    submitted = 0
    try:
        while time.perf_counter() < deadline:
            user = int(rng.integers(0, USERS))
            submitted_at = time.perf_counter()
            future = one_request(rng, user, deadline_ms=DEADLINE_MS)
            future.add_done_callback(on_done(submitted_at))
            futures.append(future)
            submitted += 1
            if submitted % 64 == 0:
                peak_depth = max(peak_depth, service.batcher.queued_depth())
            gap = float(rng.exponential(1.0 / max(rate, 1.0)))
            if gap > 0.0005:  # sub-granularity sleeps only slow the generator
                time.sleep(min(gap, 1.0))
        hung = _await_all(futures)
        # result() waiters wake BEFORE done-callbacks run, so drain the
        # callback tail or the phase totals undercount vs submissions
        drain_deadline = time.perf_counter() + 10.0
        while time.perf_counter() < drain_deadline:
            with lock:
                accounted = len(latencies) + sum(counts.values())
            if accounted >= submitted - hung:
                break
            time.sleep(0.005)
    finally:
        service.fallback = fallback
    elapsed = time.perf_counter() - start
    with lock:
        completed = len(latencies)
        phase_counts = dict(counts)
    return {
        "rate": round(rate, 1),
        "factor": OVERLOAD_FACTOR,
        "seconds": OVERLOAD_SECONDS,
        "deadline_ms": DEADLINE_MS,
        "submitted": submitted,
        "completed": completed,
        "shed": phase_counts["shed"],
        "shed_rate": round(phase_counts["shed"] / submitted, 4) if submitted else 0.0,
        "deadline_missed": phase_counts["deadline_missed"],
        "deadline_miss_rate": (
            round(phase_counts["deadline_missed"] / submitted, 4) if submitted else 0.0
        ),
        "circuit_refused": phase_counts["circuit_refused"],
        "errors": phase_counts["error"],
        "error_rate": round(phase_counts["error"] / submitted, 4) if submitted else 0.0,
        "p50_ms": round(_percentile(latencies, 50) * 1000.0, 3),
        "p99_ms": round(_percentile(latencies, 99) * 1000.0, 3),
        "peak_queue_depth": peak_depth,
        "max_queue_depth": service.batcher.max_depth,
        "hung_requests": hung,
        "elapsed_s": round(elapsed, 2),
    }


def _run_quant_phase(model, params, item_weights, reranker_weights, rng):
    """int8-vs-f32 retrieval A/B (the serving rung of the precision ladder,
    docs/performance.md "The precision ladder"): the SAME catalog and query
    states through a f32 and an int8-quantized ``CandidatePipeline``.

    Measures (a) recall@C of the quantized candidate sweep vs the f32 sweep,
    (b) the end-to-end top-k agreement AFTER the int8 pipeline's exact f32
    rescore stage, (c) per-batch ``rank()`` latency for both, and (d) the
    table payload bytes (the 4× claim). ``obs.report`` renders the record and
    ``--compare`` gates recall/topk-match as higher-better.
    """
    from replay_tpu.models import MIPSIndex
    from replay_tpu.serve import CandidatePipeline
    from replay_tpu.nn.sequential.sasrec import SasRec

    candidates = min(CANDIDATES, NUM_ITEMS)
    top_k = min(TOPK, candidates)
    query_rows = min(64, USERS)
    ids = rng.integers(0, NUM_ITEMS, size=(query_rows, SEQ_LEN)).astype(np.int32)
    mask = np.ones((query_rows, SEQ_LEN), bool)
    queries = np.asarray(
        model.apply(
            {"params": params}, {"item_id": ids}, mask,
            method=SasRec.get_query_embeddings,
        )
    )

    f32_index = MIPSIndex(item_weights)
    int8_index = MIPSIndex(item_weights, precision="int8")
    pipelines = {
        "f32": CandidatePipeline(
            f32_index, num_candidates=candidates, top_k=top_k,
            reranker_weights=reranker_weights,
        ),
        "int8": CandidatePipeline(
            int8_index, num_candidates=candidates, top_k=top_k,
            reranker_weights=reranker_weights,
        ),
    }

    _, f32_ids = f32_index.search(queries, candidates)
    _, int8_ids = int8_index.search(queries, candidates)
    recall = float(
        np.mean(
            [
                len(set(a.tolist()) & set(b.tolist())) / candidates
                for a, b in zip(f32_ids, int8_ids)
            ]
        )
    )

    latency_ms = {}
    topk = {}
    for name, pipeline in pipelines.items():
        pipeline.rank(queries)  # compile + warm
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            scores, items = pipeline.rank(queries)  # np outputs: self-fencing
        latency_ms[name] = round((time.perf_counter() - t0) / reps * 1000.0, 3)
        topk[name] = items
    topk_match = float(
        np.mean(
            [
                len(set(a.tolist()) & set(b.tolist())) / top_k
                for a, b in zip(topk["f32"], topk["int8"])
            ]
        )
    )

    bytes_record = int8_index.table_bytes()
    return {
        "candidates": candidates,
        "top_k": top_k,
        "query_rows": query_rows,
        "recall_at_candidates": round(recall, 4),
        "topk_match_rate": round(topk_match, 4),
        "f32_rank_ms": latency_ms["f32"],
        "int8_rank_ms": latency_ms["int8"],
        "int8_table_bytes": bytes_record["payload_bytes"],
        "f32_table_bytes": bytes_record["f32_bytes"],
        "bytes_ratio": round(bytes_record["bytes_ratio"], 4),
    }


def _run_ann_phase():
    """Sub-linear retrieval A/B (the IVF rung, docs/serving.md "Sub-linear
    retrieval"): brute-force f32 MIPS vs a clustered IVF index over the SAME
    synthetic clustered catalog — HARD-GATED, not observed.

    The headline is f32-vs-f32 (identical scores, different candidate sweep):
    recall@100 of the probed sweep against the exact sweep, plus the
    retrieval throughput ratio. At >=10M items the phase ASSERTS speedup
    >= 10x at recall@100 >= 0.99; smaller (CI smoke) catalogs record the
    same fields but skip the throughput gate — brute simply is not slow
    enough there for sub-linear search to pay (docs/serving.md "When
    brute-force wins"). The quantized rungs gate recall on a fixed-geometry
    100k rung catalog (pinned rows-per-cluster, decoupled from ANN_ITEMS):
    int8 on its raw sweep, int8+pq through its serving
    configuration (3x candidate overfetch + exact f32 rescore -> top-100 —
    the honesty contract: approximation picks candidates, never ranks
    them). The 100M projection prices both layouts with the machine-derived
    byte model (``ivf_bytes``/``brute_bytes``, test-anchored against real
    device arrays) and asserts the PQ index fits a 16 GiB HBM budget where
    even the int8 brute table cannot.
    """
    from replay_tpu.models import MIPSIndex
    from replay_tpu.models.ivf import brute_bytes, default_nlist, ivf_bytes
    from replay_tpu.serve import CandidatePipeline

    items, dim = ANN_ITEMS, ANN_DIM
    gen = np.random.default_rng(7)
    # cluster count of the synthetic catalog: grows with the catalog but
    # saturates at ~1k (real catalogs cluster by taxonomy/popularity into
    # hundreds-to-thousands of groups regardless of item count)
    modes = max(8, min(items // 1400, 1024))
    # auto-nlist: default_nlist (~2 sqrt I), capped at 4096 (assignment is
    # I x nlist work and one CPU core builds this catalog) AND at
    # modes x nprobe / 2 — k-means splits each intrinsic cluster into
    # ~nlist/modes cells, ALL of which must land inside the nprobe probed
    # centroids for the cluster's neighbours to be reachable; past ~nprobe/2
    # fragments per cluster, recall@fixed-nprobe collapses (measured: at
    # 100k items / 71 modes / nprobe=16, nlist=512 sweeps recall 1.00 while
    # nlist=1024 drops to 0.988)
    frag_cap = 1 << int(np.log2(max(8, modes * ANN_NPROBE // 2)))
    nlist = ANN_NLIST or min(4096, default_nlist(items), frag_cap)
    nprobe = min(ANN_NPROBE, nlist)
    k = min(100, items)
    top_k = min(10, k)
    centers = gen.standard_normal((modes, dim), dtype=np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True) + 1e-9
    catalog = centers[gen.integers(0, modes, size=items)]
    catalog += 0.1 * gen.standard_normal((items, dim), dtype=np.float32)
    queries = centers[gen.integers(0, modes, size=ANN_QUERIES)]
    queries += 0.1 * gen.standard_normal((ANN_QUERIES, dim), dtype=np.float32)

    brute = MIPSIndex(catalog)
    t0 = time.perf_counter()
    ivf = MIPSIndex(
        catalog, index="ivf", nlist=nlist, nprobe=nprobe,
        build_sample=ANN_BUILD_SAMPLE,
    )
    build_s = time.perf_counter() - t0
    stats = ivf.index_stats()

    # warm (compile) both sweeps, then time the retrieval program alone —
    # the sweep is what sub-linear search accelerates; rescore/rerank are
    # candidate-sized and identical for both pipelines
    brute.search(queries, k)
    ivf.search(queries, k)
    timings = {}
    ids = {}
    for name, index, reps in (("brute", brute, 3), ("ivf", ivf, 10)):
        t0 = time.perf_counter()
        for _ in range(reps):
            _, ids[name] = index.search(queries, k)
        timings[name] = (time.perf_counter() - t0) / reps
    recall = float(
        np.mean(
            [
                len(set(a.tolist()) & set(b.tolist())) / k
                for a, b in zip(ids["brute"], ids["ivf"])
            ]
        )
    )
    speedup = timings["brute"] / timings["ivf"]

    # end-to-end agreement through the serving path: the IVF pipeline's
    # exact_rescore stage re-scores its candidates at f32, so the final
    # top-k may differ from brute ONLY where the probed sweep missed a
    # true-top-k candidate
    topk = {}
    for name, index in (("brute", brute), ("ivf", ivf)):
        pipeline = CandidatePipeline(index, num_candidates=k, top_k=top_k)
        _, topk[name] = pipeline.rank(queries)
    agreement = float(
        np.mean(
            [
                len(set(a.tolist()) & set(b.tolist())) / top_k
                for a, b in zip(topk["brute"], topk["ivf"])
            ]
        )
    )

    gate_speedup = items >= 10_000_000
    if recall < 0.99:
        msg = f"ann gate: IVF recall@{k} {recall:.4f} < 0.99 at nprobe={nprobe}"
        raise AssertionError(msg)
    if gate_speedup and speedup < 10.0:
        msg = (
            f"ann gate: IVF speedup x{speedup:.1f} < x10 vs brute at "
            f"{items} items (recall@{k} {recall:.4f})"
        )
        raise AssertionError(msg)

    # quantized rungs on a FIXED-geometry rung catalog (100k rows, same
    # generator family, own seed): the rung gates measure QUANTIZATION
    # quality, so the cluster geometry must be pinned — on a slice of the
    # headline catalog, rows-per-cluster shrinks with the slice and the
    # top-100 boundary slides into the densest near-tie band of each
    # cluster, where int8 reordering alone sinks recall (measured 0.94 on a
    # 200k slice of the 10M catalog vs 0.99+ at this pinned geometry).
    # Full-catalog rung builds would also re-run k-means + assignment twice
    # more for no extra information.
    rung_rows = 100_000
    rung_modes = max(8, rung_rows // 1400)
    pq_m = 16 if dim % 16 == 0 else 8
    pq_overfetch = 3
    rgen = np.random.default_rng(11)
    rcenters = rgen.standard_normal((rung_modes, dim), dtype=np.float32)
    rcenters /= np.linalg.norm(rcenters, axis=1, keepdims=True) + 1e-9
    rung_cat = rcenters[rgen.integers(0, rung_modes, size=rung_rows)]
    rung_cat += 0.1 * rgen.standard_normal((rung_rows, dim), dtype=np.float32)
    rung_queries = rcenters[rgen.integers(0, rung_modes, size=ANN_QUERIES)]
    rung_queries += 0.1 * rgen.standard_normal((ANN_QUERIES, dim), dtype=np.float32)
    rung_nlist = min(512, default_nlist(rung_rows))
    rung_nprobe = 48
    rung_k = 100
    _, gt_ids = MIPSIndex(rung_cat).search(rung_queries, rung_k)

    def _rung_recall(found_ids):
        return float(
            np.mean(
                [
                    len(set(a.tolist()) & set(b.tolist())) / rung_k
                    for a, b in zip(gt_ids, found_ids)
                ]
            )
        )

    int8_ivf = MIPSIndex(
        rung_cat, index="ivf", precision="int8",
        nlist=rung_nlist, nprobe=rung_nprobe,
    )
    _, int8_ids = int8_ivf.search(rung_queries, rung_k)
    recall_int8 = _rung_recall(int8_ids)

    pq_ivf = MIPSIndex(
        rung_cat, index="ivf", precision="int8+pq", pq_subspaces=pq_m,
        nlist=rung_nlist, nprobe=rung_nprobe,
    )
    overfetch = min(pq_overfetch * rung_k, rung_rows)
    _, cand_ids = pq_ivf.search(rung_queries, overfetch)
    rescored = np.asarray(pq_ivf.exact_rescore(rung_queries, cand_ids))
    order = np.argsort(-rescored, axis=1)[:, :rung_k]
    recall_pq = _rung_recall(np.take_along_axis(np.asarray(cand_ids), order, axis=1))
    for name, value in (("int8", recall_int8), ("int8+pq", recall_pq)):
        if value < 0.99:
            msg = f"ann gate: {name} rung recall@{rung_k} {value:.4f} < 0.99"
            raise AssertionError(msg)

    # the 100M projection: machine-derived bytes at serving scale (E=256,
    # nlist=65536, M=32) — the PQ index must fit a 16 GiB HBM budget that
    # even the int8 BRUTE table blows through
    hbm = 16 * 1024**3
    proj_pq = ivf_bytes(100_000_000, 256, 65536, "int8+pq", pq_subspaces=32)
    proj_int8_brute = brute_bytes(100_000_000, 256, "int8")
    if not proj_pq["total_bytes"] < hbm < proj_int8_brute["total_bytes"]:
        msg = (
            f"ann gate: 100M projection inverted — pq {proj_pq['total_bytes']} "
            f"vs hbm {hbm} vs int8 brute {proj_int8_brute['total_bytes']}"
        )
        raise AssertionError(msg)

    return {
        "items": items,
        "dim": dim,
        "nlist": int(stats["nlist"]),
        "nprobe": int(stats["nprobe"]),
        "cmax": int(stats["cmax"]),
        "scanned_fraction": round(float(stats["scanned_fraction"]), 6),
        "padded_fraction": round(float(stats["padded_fraction"]), 4),
        "build_s": round(build_s, 2),
        "queries": ANN_QUERIES,
        "recall_at_100": round(recall, 4),
        "topk_agreement": round(agreement, 4),
        "brute_ms": round(timings["brute"] * 1000.0, 3),
        "ivf_ms": round(timings["ivf"] * 1000.0, 3),
        "brute_qps": round(ANN_QUERIES / timings["brute"], 1),
        "ivf_qps": round(ANN_QUERIES / timings["ivf"], 1),
        "speedup": round(speedup, 2),
        "speedup_gated": gate_speedup,
        "rung_items": rung_rows,
        "rung_nlist": rung_nlist,
        "rung_nprobe": rung_nprobe,
        "recall_at_100_int8": round(recall_int8, 4),
        "recall_at_100_pq": round(recall_pq, 4),
        "pq_overfetch": pq_overfetch,
        "pq_subspaces": pq_m,
        "index_total_bytes": int(ivf.table_bytes()["total_bytes"]),
        "brute_table_bytes": int(items * dim * 4),
        "projection_100m": {
            "hbm_bytes": hbm,
            "pq_total_bytes": int(proj_pq["total_bytes"]),
            "int8_brute_bytes": int(proj_int8_brute["total_bytes"]),
            "pq_fits": bool(proj_pq["total_bytes"] < hbm),
            "int8_brute_fits": bool(proj_int8_brute["total_bytes"] < hbm),
        },
    }


def _run_swap_phase(service, one_request, model, params, users, clients):
    """N hot weight swaps under closed-loop load (serve.promote).

    Client threads score back to back while the main thread publishes and
    promotes perturbed same-shape candidates (zero recompile — the pointer-
    move swap; in retrieval mode each candidate ships its own rebuilt MIPS
    pipeline, since the index embeds the generation's item table). Measures
    request latency ACROSS the whole phase (each swap window included), and
    records the generation tags observed — the consistency/zero-error
    assertions the canary_smoke CI job gates on.
    """
    import jax

    def candidate_pipeline(candidate):
        if service.mode != "retrieval":
            return None
        from replay_tpu.models import MIPSIndex
        from replay_tpu.serve import CandidatePipeline

        item_weights = np.asarray(
            model.apply({"params": candidate}, method=type(model).get_item_weights)
        )
        template = service.retrieval
        return CandidatePipeline(
            MIPSIndex(item_weights),
            num_candidates=template.num_candidates,
            top_k=template.top_k,
            reranker_weights=template.reranker_weights,
        )

    latencies = []
    errors = []
    generations = set()
    lock = threading.Lock()
    stop = threading.Event()

    def client(idx: int) -> None:
        thread_rng = np.random.default_rng(5000 + idx)
        while not stop.is_set():
            user = int(thread_rng.integers(0, users))
            started = time.perf_counter()
            try:
                response = one_request(thread_rng, user).result(timeout=120)
            except Exception as exc:  # noqa: BLE001 — recorded, asserted zero
                errors.append(repr(exc))
                continue
            with lock:
                latencies.append(time.perf_counter() - started)
                generations.add(int(response.generation))

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True) for i in range(clients)
    ]
    phase_start = time.perf_counter()
    for thread in threads:
        thread.start()
    gap = max(SWAP_GAP_MS / 1000.0, 0.02)
    recompiled = 0
    swap_seconds = []
    for swap in range(SWAPS):
        time.sleep(gap)
        scale = 1.0 + 1e-3 * (swap + 1)
        candidate = jax.tree.map(
            lambda x, s=scale: (np.asarray(x) * s).astype(np.asarray(x).dtype), params
        )
        swap_start = time.perf_counter()
        generation = service.publish_candidate(
            candidate, label=f"swap-{swap}", pipeline=candidate_pipeline(candidate)
        )
        if service.store.generation(generation).recompiled:
            recompiled += 1
        service.promote(generation)
        swap_seconds.append(time.perf_counter() - swap_start)
    time.sleep(gap)
    stop.set()
    for thread in threads:
        thread.join(timeout=130)
    elapsed = time.perf_counter() - phase_start
    answered = len(latencies)
    return {
        "swaps": SWAPS,
        "recompiled_swaps": recompiled,
        "requests": answered + len(errors),
        "answered": answered,
        "errors": len(errors),
        "first_error": errors[0] if errors else None,
        "p50_ms": round(_percentile(latencies, 50) * 1000.0, 3),
        "p99_ms": round(_percentile(latencies, 99) * 1000.0, 3),
        "qps": round(answered / elapsed, 1) if elapsed > 0 else 0.0,
        # publish+promote wall time: the swap itself is a pointer move, so
        # this stays in the low milliseconds unless a recompile was needed
        "swap_apply_ms_max": round(max(swap_seconds) * 1000.0, 3) if swap_seconds else 0.0,
        "generations_seen": len(generations),
        "final_generation": service.store.stable_generation,
        "generation_misses": service.stats()["generation_misses"],
    }


def _run_chaos(service, histories, rng):
    """Deterministic serve-side fault injection (see utils/faults.py):
    engine errors trip the breaker open, degraded traffic rides the ladder,
    a latency spike exercises the client-abandon drop, a deadline storm
    exercises expiry-at-batch-build, and recovery re-closes the breaker."""
    from replay_tpu.utils.faults import EngineErrorAt, InjectedFault, LatencySpike, wrap_method

    futures = []
    stats_before = service.stats()
    threshold = service.breaker.failure_threshold
    reset_s = service.breaker.reset_timeout_s

    # re-anchor the warm user with an explicit history while the engine is
    # still healthy: the preceding overload phase may have shed its last
    # re-encode, leaving no cached embedding for the cache_only rung to ride
    warm_user = 0
    service.score(warm_user, history=histories[warm_user], timeout=30)

    # 1) consecutive engine failures -> breaker opens
    error_injector = EngineErrorAt(at_calls=range(threshold))
    original_encode = wrap_method(service.engine, "encode", error_injector)
    injected_errors = 0
    for i in range(threshold):
        future = service.submit(
            f"chaos-trip-{i}", history=rng.integers(0, NUM_ITEMS, 5).tolist()
        )
        futures.append(future)
        try:
            future.result(timeout=30)
        except InjectedFault:
            injected_errors += 1
        except Exception:  # noqa: BLE001 — counted via service stats
            pass
    state_after_trip = service.breaker.state
    # pin the breaker open for the ladder step: a scheduler pause longer than
    # the (CI-tiny) reset window would otherwise let the next request become
    # the half-open probe and come back "primary", flaking the assertions
    service.breaker.reset_timeout_s = 3600.0

    # 2) degraded traffic while open: the warm user's advance rides the
    # cache_only rung (stale embedding, hit lane); a brand-new user lands on
    # the fallback floor. served_by makes both visible.
    served_by_seen = {}
    response = service.score(warm_user, new_items=[1], timeout=30)
    served_by_seen["advance_while_open"] = response.served_by
    response = service.score(
        "chaos-cold-new", history=rng.integers(0, NUM_ITEMS, 4).tolist(), timeout=30
    )
    served_by_seen["cold_while_open"] = response.served_by

    # 3) recovery: restore the real reset window (already elapsed relative to
    # the trip, so the next encode-needing request is the half-open probe);
    # the injector is exhausted, so it succeeds and the breaker closes
    service.breaker.reset_timeout_s = reset_s
    recovered = False
    recovery_deadline = time.perf_counter() + max(10.0, 20 * reset_s)
    probe = 0
    while time.perf_counter() < recovery_deadline:
        if service.breaker.state == "closed":
            recovered = True
            break
        time.sleep(reset_s / 2 + 0.01)
        future = service.submit(
            f"chaos-probe-{probe}", history=rng.integers(0, NUM_ITEMS, 4).tolist()
        )
        futures.append(future)
        probe += 1
        try:
            future.result(timeout=30)
        except Exception:  # noqa: BLE001
            pass
    recovered = recovered or service.breaker.state == "closed"

    # 4) latency spike + client abandonment: the worker stalls on a blocker
    # encode; a short-timeout client gives up, and its cancelled request is
    # skipped at batch build (never burning the scoring slot)
    spike = LatencySpike(at_calls=[0], duration_s=max(0.2, 6 * MAX_WAIT_MS / 1000.0))
    wrap_method(service.engine, "encode", spike)
    blocker = service.submit(
        "chaos-blocker", history=rng.integers(0, NUM_ITEMS, 4).tolist()
    )
    futures.append(blocker)
    client_abandoned = 0
    try:
        service.score(
            "chaos-abandoned",
            history=rng.integers(0, NUM_ITEMS, 4).tolist(),
            timeout=0.03,
        )
    except Exception:  # noqa: BLE001 — the timeout IS the scenario
        client_abandoned = 1
    try:
        blocker.result(timeout=30)
    except Exception:  # noqa: BLE001
        pass

    # 5) deadline storm: a second spike stalls the worker while a burst of
    # tiny-deadline requests queues up; expiry at batch build must drop them
    # before any device work
    storm_spike = LatencySpike(at_calls=[0], duration_s=0.25)
    wrap_method(service.engine, "encode", storm_spike)
    storm_blocker = service.submit(
        "chaos-storm-blocker", history=rng.integers(0, NUM_ITEMS, 4).tolist()
    )
    futures.append(storm_blocker)
    time.sleep(0.02)  # let the blocker reach the worker
    storm = [
        service.submit(int(rng.integers(0, USERS)), deadline_ms=50.0)
        for _ in range(32)
    ]
    futures.extend(storm)
    hung = _await_all(futures)
    storm_missed = sum(
        1
        for future in storm
        if future.done()
        and future.exception() is not None
        and _classify(future.exception()) == "deadline_missed"
    )

    # restore the unwrapped engine
    service.engine.encode = original_encode
    stats_after = service.stats()
    served_by_delta = {
        key: stats_after["served_by"][key] - stats_before["served_by"][key]
        for key in stats_after["served_by"]
    }
    return {
        "injected_engine_errors": injected_errors,
        "injected_spikes": len(spike.injected_at) + len(storm_spike.injected_at),
        "breaker_opens": stats_after["breaker"]["opens"],
        "breaker_state_after_trip": state_after_trip,
        "breaker_state_final": service.breaker.state,
        "recovered": recovered,
        "served_by_delta": served_by_delta,
        "served_by_seen": served_by_seen,
        "client_abandoned": client_abandoned,
        "storm_submitted": len(storm),
        "storm_deadline_missed": storm_missed,
        "hung_requests": hung,
    }


def _run_drift_phase(service, monitor, histories, num_items, users, rng):
    """Injected preference shift (obs.quality): DRIFT_REQUESTS steady advances
    whose labels stay uniform (the distribution the PSI reference froze on),
    then DRIFT_REQUESTS advances whose labels ALL land on the popularity head
    — "everyone suddenly watches the blockbusters". The incoming-label PSI
    must cross DRIFT_THRESHOLD and the drift_psi SLO rule must fire exactly
    once (the watchdog's transition latch; the phase runs last so PSI never
    recovers and re-arms the rule)."""
    registry = service.metrics_registry

    def violations() -> float:
        if registry is None:
            return 0.0
        return (
            registry.value(
                "replay_slo_violations_total", labels={"rule": "drift_psi"}
            )
            or 0.0
        )

    def advance(user: int, item: int):
        histories[user].append(item)
        return service.submit(user, new_items=[item])

    violations_before = violations()

    # phase A: steady traffic — uniform labels, same mix the load phases drew.
    # Guarantees the drift reference is frozen before the shift starts even
    # when the load phases were tiny (CI's quality_smoke knobs).
    futures = [
        advance(int(rng.integers(0, users)), int(rng.integers(0, num_items)))
        for _ in range(DRIFT_REQUESTS)
    ]
    hung = _await_all(futures)
    series_before = dict(monitor.snapshot().get("drift") or {})
    psi_before = series_before.get("max")

    # phase B: the shift — every incoming label lands on the popularity head
    counts = np.bincount(
        np.concatenate([np.asarray(h, np.int64) for h in histories.values()]),
        minlength=num_items,
    )
    head_items = np.argsort(-counts)[: max(8, num_items // 64)]
    futures = [
        advance(
            int(rng.integers(0, users)),
            int(head_items[int(rng.integers(0, len(head_items)))]),
        )
        for _ in range(DRIFT_REQUESTS)
    ]
    hung += _await_all(futures)
    # close the tail window so the final PSI reaches the registry and the
    # watchdog evaluates it (flush emits through the service's own fan-out)
    monitor.flush()
    snap = monitor.snapshot()
    psi_after = (snap.get("drift") or {}).get("max")
    stable = (snap.get("roles") or {}).get("stable") or {}
    return {
        "requests": 2 * DRIFT_REQUESTS,
        "shift_requests": DRIFT_REQUESTS,
        "shift_fraction": 1.0,
        "head_items": int(len(head_items)),
        "threshold": DRIFT_THRESHOLD,
        "psi_before": psi_before,
        "psi_after": psi_after,
        "psi_peak": (
            psi_after
            if psi_before is None
            else (psi_before if psi_after is None else max(psi_before, psi_after))
        ),
        "series": dict(snap.get("drift") or {}),
        "series_before": series_before,
        "warnings": snap.get("drift_warnings", 0),
        "slo_violations": int(violations() - violations_before),
        "online_hitrate_cum": stable.get("online_hitrate_cum"),
        "online_ndcg_cum": stable.get("online_ndcg_cum"),
        "joins": stable.get("joins"),
        "hung_requests": hung,
    }


def main() -> None:
    enable_compile_cache()
    import jax

    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.models import MIPSIndex
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.obs import (
        JsonlLogger,
        PopularityDescriptor,
        QualityMonitor,
        SLORule,
        Tracer,
    )
    from replay_tpu.scenarios.two_stages import LogisticReranker
    from replay_tpu.serve import (
        CandidatePipeline,
        CircuitBreaker,
        FallbackScorer,
        ScoringService,
    )

    rng = np.random.default_rng(0)
    schema = TensorSchema(
        TensorFeatureInfo(
            "item_id",
            FeatureType.CATEGORICAL,
            is_seq=True,
            feature_hint=FeatureHint.ITEM_ID,
            cardinality=NUM_ITEMS,
            embedding_dim=EMBEDDING_DIM,
        )
    )
    model = SasRec(
        schema=schema,
        embedding_dim=EMBEDDING_DIM,
        num_blocks=NUM_BLOCKS,
        num_heads=1,
        max_sequence_length=SEQ_LEN,
        dropout_rate=0.0,
    )
    init_ids = np.zeros((2, SEQ_LEN), np.int32)
    params = model.init(
        jax.random.PRNGKey(0), {"item_id": init_ids}, np.ones((2, SEQ_LEN), bool)
    )["params"]

    retrieval = None
    quant = None
    mode = "full"
    if CANDIDATES > 0:
        # the fused candidate->rank path: MIPS over the tying head's item
        # table + the two-stage scenario's logistic re-rank weights (trained
        # here on synthetic score/label pairs — the integration is what the
        # bench exercises, not the weights' quality)
        item_weights = np.asarray(
            model.apply({"params": params}, method=SasRec.get_item_weights)
        )
        scores = rng.normal(size=(256, 1))
        labels = (scores[:, 0] + 0.3 * rng.normal(size=256) > 0).astype(np.float64)
        reranker = LogisticReranker(steps=50).fit(scores, labels)
        retrieval = CandidatePipeline(
            MIPSIndex(item_weights),
            num_candidates=min(CANDIDATES, NUM_ITEMS),
            top_k=min(TOPK, CANDIDATES, NUM_ITEMS),
            reranker_weights=reranker.serving_weights,
        )
        mode = "retrieval"
        # int8-vs-f32 retrieval A/B (the ladder's serving rung): same catalog,
        # same query states, recall/topk-match/latency/bytes — runs before the
        # service phases so its compile time never pollutes their latencies
        quant = _run_quant_phase(
            model, params, item_weights, reranker.serving_weights, rng
        )

    ann = None
    if ANN:
        # sub-linear retrieval A/B (opt-in): self-contained — the phase
        # builds its own clustered catalog at ANN_ITEMS scale, so it runs
        # before the service phases and frees everything on return
        ann = _run_ann_phase()

    histories = {
        u: rng.integers(0, NUM_ITEMS, size=int(rng.integers(1, 2 * SEQ_LEN))).tolist()
        for u in range(USERS)
    }

    # the quality plane rides the WHOLE run (every phase's served slates feed
    # the windowed gauges and the prequential join), not just the drift phase;
    # sizes derive from DRIFT_REQUESTS so the PSI reference freezes on the
    # steady half of the drift phase at the latest and the shifted half fills
    # the comparison window
    quality_monitor = None
    drift_rules = None
    if DRIFT_REQUESTS > 0:
        quality_monitor = QualityMonitor(
            PopularityDescriptor.from_train(histories, num_items=NUM_ITEMS),
            k=min(TOPK, NUM_ITEMS),
            window=max(64, DRIFT_REQUESTS // 2),
            emit_every=max(8, DRIFT_REQUESTS // 16),
            drift_reference=DRIFT_REQUESTS,
            drift_window=max(32, DRIFT_REQUESTS // 2),
            drift_min_window=max(8, DRIFT_REQUESTS // 16),
            drift_threshold=DRIFT_THRESHOLD,
        )
        # the SLO gates the DIRECTLY shifted series (incoming-label
        # popularity): its comparison window only gains head items during the
        # shift, so its PSI climbs monotonically and crosses the threshold
        # exactly once — second-order echoes (served-slate score/popularity)
        # can excurse transiently and would re-fire a max-based rule
        drift_rules = [
            SLORule(
                "replay_drift_psi_series",
                ">",
                DRIFT_THRESHOLD,
                for_steps=2,
                labels={"series": "interactions"},
                name="drift_psi",
            )
        ]

    tracer = Tracer()
    logger = JsonlLogger(RUN_DIR, mode="w")
    compile_start = time.perf_counter()
    service = ScoringService(
        model,
        params,
        length_buckets=LENGTH_BUCKETS,
        batch_buckets=BATCH_BUCKETS,
        max_wait_ms=MAX_WAIT_MS,
        cache_capacity=max(USERS * 2, 16),
        retrieval=retrieval,
        tracer=tracer,
        logger=logger,
        trace_path=os.path.join(RUN_DIR, "trace.json"),
        max_queue_depth=MAX_DEPTH if MAX_DEPTH else None,
        metrics_port=METRICS_PORT if METRICS_PORT >= 0 else None,
        quality=quality_monitor,
        slo_rules=drift_rules,
        breaker=CircuitBreaker(
            failure_threshold=BREAKER_THRESHOLD,
            reset_timeout_s=BREAKER_RESET_MS / 1000.0,
        ),
        # the degradation ladder's floor: popularity over the synthetic
        # training log (the reference's PopRec, doubled as the outage answer)
        fallback=FallbackScorer.from_interactions(
            [item for h in histories.values() for item in h], NUM_ITEMS
        ),
    )
    compile_seconds = time.perf_counter() - compile_start

    with service:
        # seed every user cold (also settles the executables)
        seed_futures = [
            service.submit(u, history=histories[u]) for u in range(USERS)
        ]
        for future in seed_futures:
            future.result(timeout=120)

        def one_request(thread_rng, user: int, deadline_ms=None):
            """The returning-user mix: mostly hits, some advances, rare colds."""
            draw = thread_rng.random()
            if draw < 0.7:
                return service.submit(user, deadline_ms=deadline_ms)
            if draw < 0.9:
                new_item = int(thread_rng.integers(0, NUM_ITEMS))
                histories[user].append(new_item)
                return service.submit(user, new_items=[new_item], deadline_ms=deadline_ms)
            return service.submit(user, history=histories[user], deadline_ms=deadline_ms)

        # ---- closed loop: saturation throughput --------------------------- #
        errors = []

        def client(idx: int) -> None:
            thread_rng = np.random.default_rng(1000 + idx)
            for _ in range(CLOSED_REQUESTS):
                user = int(thread_rng.integers(0, USERS))
                try:
                    one_request(thread_rng, user).result(timeout=120)
                except Exception as exc:  # noqa: BLE001 — recorded, not fatal
                    errors.append(repr(exc))

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True) for i in range(CLIENTS)
        ]
        closed_start = time.perf_counter()
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        closed_elapsed = time.perf_counter() - closed_start
        closed_qps = CLIENTS * CLOSED_REQUESTS / closed_elapsed

        # ---- open loop: Poisson arrivals, latency percentiles ------------- #
        latencies = []
        latency_lock = threading.Lock()
        done_count = [0]

        def on_done(submitted_at):
            def callback(future):
                latency = time.perf_counter() - submitted_at
                with latency_lock:
                    done_count[0] += 1
                    if future.exception() is None:
                        latencies.append(latency)
                    else:
                        errors.append(repr(future.exception()))

            return callback

        open_rng = np.random.default_rng(7)
        open_start = time.perf_counter()
        submitted = 0
        deadline = open_start + SECONDS
        while time.perf_counter() < deadline:
            user = int(open_rng.integers(0, USERS))
            submitted_at = time.perf_counter()
            future = one_request(open_rng, user)
            future.add_done_callback(on_done(submitted_at))
            submitted += 1
            gap = float(open_rng.exponential(1.0 / max(RATE, 1)))
            time.sleep(min(gap, 1.0))
        while True:
            with latency_lock:
                if done_count[0] >= submitted:
                    break
            time.sleep(0.005)
        open_elapsed = time.perf_counter() - open_start
        open_qps = submitted / open_elapsed

        # ---- swap-under-load: N hot weight swaps, zero errors ------------- #
        # before overload/chaos so their induced sheds/faults cannot pollute
        # the zero-request-errors claim the swap phase exists to prove
        swap = None
        if SWAPS > 0:
            swap = _run_swap_phase(
                service, one_request, model, params, USERS, CLIENTS
            )

        # ---- overload: arrivals ≫ capacity, bounded lanes must shed ------- #
        # capacity estimate: the better of the two measured loops (a closed
        # loop with few clients is latency-bound and undersells throughput)
        overload = None
        if OVERLOAD_SECONDS > 0:
            overload = _run_overload(
                service, one_request, rate=OVERLOAD_FACTOR * max(closed_qps, open_qps)
            )

        # ---- chaos: injected engine faults, breaker round trip ------------ #
        chaos = None
        if CHAOS:
            chaos = _run_chaos(service, histories, np.random.default_rng(23))

        # ---- drift: injected preference shift must trip the quality SLO --- #
        # runs LAST so the shifted distribution stays in the comparison
        # window through close — PSI never recovers, the rule fires once
        drift = None
        if quality_monitor is not None:
            drift = _run_drift_phase(
                service,
                quality_monitor,
                histories,
                NUM_ITEMS,
                USERS,
                np.random.default_rng(31),
            )

        stats = service.stats()

        # ---- live scrape: the endpoint must answer WHILE serving ---------- #
        metrics_scrape = None
        exporter = service.metrics_exporter
        if exporter is not None and exporter.port is not None:
            import urllib.request

            with urllib.request.urlopen(
                f"{exporter.url}/metrics", timeout=10
            ) as response:
                metrics_scrape = response.read().decode()
            with open(os.path.join(RUN_DIR, "metrics.txt"), "w") as fh:
                fh.write(metrics_scrape)

    # post-close reconciliation: close() flushed the throttled on_shed tails
    # into the bridge, so the registry counters must reproduce the service's
    # own totals exactly — the serve_chaos CI job gates on this equality
    metrics_record = None
    registry = service.metrics_registry
    if registry is not None:
        with open(os.path.join(RUN_DIR, "metrics_snapshot.json"), "w") as fh:
            json.dump(registry.snapshot(), fh, indent=2, default=str)
        metrics_record = {
            "scraped_live": metrics_scrape is not None,
            "shed_total": registry.value("replay_serve_shed_total") or 0.0,
            "expired_total": registry.value("replay_serve_expired_total") or 0.0,
            "rows_total": registry.value("replay_serve_rows_total") or 0.0,
            "qps_gauge": registry.value("replay_serve_qps"),
            "shed_rate_gauge": registry.value("replay_serve_shed_rate"),
            "service_shed": stats["shed"],
            "service_deadline_misses": stats["deadline_misses"],
        }

    metric = "serve_qps"
    if jax.default_backend() == "cpu":
        metric += "_cpu_fallback"
    record = {
        "metric": metric,
        "value": round(open_qps, 1),
        "unit": "req/s",
        "qps": round(open_qps, 1),
        "closed_loop_qps": round(closed_qps, 1),
        "p50_ms": round(_percentile(latencies, 50) * 1000.0, 3),
        "p95_ms": round(_percentile(latencies, 95) * 1000.0, 3),
        "p99_ms": round(_percentile(latencies, 99) * 1000.0, 3),
        "batch_fill_ratio": round(stats["batch_fill_ratio"], 4),
        "cache_hit_rate": round(stats["cache_hit_rate"], 4),
        "pure_hit_rate": round(stats["pure_hit_rate"], 4),
        "requests": stats["requests"],
        "request_errors": len(errors),
        # run-wide resilience rates (all phases), the --compare gate inputs
        "serve_shed_rate": round(stats["shed_rate"], 4),
        "serve_deadline_miss_rate": round(stats["deadline_miss_rate"], 4),
        "serve_error_rate": round(stats["error_rate"], 4),
        "served_by": stats["served_by"],
        "breaker": stats["breaker"],
        "hung_requests": (
            (overload["hung_requests"] if overload else 0)
            + (chaos["hung_requests"] if chaos else 0)
            + (drift["hung_requests"] if drift else 0)
        ),
        "mode": mode,
        "backend": jax.default_backend(),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "batch_buckets": list(BATCH_BUCKETS),
        "length_buckets": list(service.engine.length_buckets),
        "max_wait_ms": MAX_WAIT_MS,
        "max_queue_depth": service.batcher.max_depth,
        "open_loop_rate": RATE,
        "open_loop_seconds": SECONDS,
        "clients": CLIENTS,
        "users": USERS,
        "compile_seconds": round(compile_seconds, 2),
    }
    if metrics_record is not None:
        record["metrics"] = metrics_record
    if quant is not None:
        record["quant"] = quant
    if ann is not None:
        record["ann"] = ann
    if swap is not None:
        record["swap"] = swap
    if overload is not None:
        record["overload"] = overload
    if chaos is not None:
        record["chaos"] = chaos
    if drift is not None:
        record["drift"] = drift
    if SHAPE_OVERRIDE:
        record["shape_override"] = {
            "L": SEQ_LEN,
            "items": NUM_ITEMS,
            "d": EMBEDDING_DIM,
            "blocks": NUM_BLOCKS,
            "users": USERS,
        }
    if errors:
        record["first_error"] = errors[0]
    # the record rides the run's events.jsonl too, so the report CLI renders
    # qps/latency and the service-side totals from one artifact
    logger.log_record(record)
    logger.close()
    print(json.dumps(record))


if __name__ == "__main__":
    main()
