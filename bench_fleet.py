"""Fleet benchmark: N scoring replicas behind the consistent-hash router,
under Poisson traffic, a mid-run replica kill, and a drain-and-swap rollout.

Drives ``replay_tpu.serve.ServingFleet`` over a simulated million-user
population (Zipf-distributed arrivals — the head users return constantly,
which is exactly what the per-replica state caches exist for) and prints ONE
JSON line in bench.py's record format::

    {"metric": "fleet_qps", "value": ..., "unit": "req/s", "qps": ...,
     "p50_ms": ..., "p99_ms": ..., "replicas": N, "reroutes": ...,
     "cache_hit_locality": ..., "single_replica_qps": ...,
     "chaos": {..., "failover_gap_ms": ...}, "drain_swap": {...},
     "sharded_retrieval": {...}, "quality": {...}, "backend": ...}

Every replica also carries an ``obs.QualityMonitor`` over one shared
popularity descriptor (the same synthetic log the fallback ranks by), so
``fleet.stats()`` aggregates the fleet-wide quality plane — join-weighted
online hitrate, total prequential joins, the per-replica drift state — into
the record's ``quality`` block.

Phases (every replica's programs are AOT-compiled at construction — the
timed phases never trace):

* **single-replica baseline** — the same traffic mix against ONE service:
  the QPS and cache-hit-rate yardsticks the fleet must beat/preserve
  (acceptance: aggregate closed-loop QPS > single, locality > 0.9x);
* **steady state** — closed-loop saturation + open-loop Poisson arrivals at
  ``RATE`` req/s through the fleet router: aggregate QPS, p50/p99 on
  completion callbacks, per-replica routing spread, cache-hit locality
  (consistent hashing splits the population into disjoint per-replica
  working sets, so the combined hit rate must hold up against one replica
  serving everyone);
* **drain-and-swap** (``SWAP=1``, default on) — a fleet-wide zero-downtime
  rollout under load: each replica in turn is drained (router stops new
  traffic, lanes empty), hot-swapped to perturbed same-shape weights through
  the PR-14 promotion path (a pointer move, zero recompiles), and rejoined.
  The phase asserts zero request errors;
* **chaos** (``CHAOS_SECONDS > 0``, default on) — a replica is killed
  mid-traffic and revived later: the monitor's heartbeats declare it dead,
  its users fail over along their ring order (cold caches ride the
  ``cold_miss="fallback"`` degradation ladder instead of erroring — visible
  in ``served_by``), and the row records the failover gap (kill → first
  successful answer for a user homed on the victim), the reroute count, the
  bounded error rate and the zero-hung-requests invariant;
* **socket chaos** (``SOCKET_CHAOS=1``, default on, alongside the in-process
  phase) — the hard-kill upgrade: a fleet of replica server PROCESSES behind
  real HTTP (``serve.remote``, portfile-handshaked ephemeral ports), one
  ``SIGKILL``-ed mid-traffic — no close path, just dead sockets. Same
  invariants, proven across a process boundary: zero hung requests, bounded
  failover gap, taxonomy-only errors (``taxonomy_only``), death declared
  from failed ``/healthz`` scrapes, and the victim respawned on a FRESH
  port that the fleet picks up without a rebuild (``socket_chaos`` row);
* **sharded retrieval** — the TP-sharded ``MIPSIndex`` (the CEFusedTP
  ``[I/n, E]`` row layout, int8 variant included): per-shard local top-k +
  candidate-only merge, checked bitwise against the unsharded search and
  HARD-asserted table-gather-free via ``collective_inventory`` over the
  compiled program — the static invariant that lets a 10M-item catalog live
  partitioned across devices (``SHARD_ITEMS=10000000`` on a chip;
  the default is CI-sized, the assertion is shape-independent).

``REPLAY_TPU_FLEET_*`` env vars override every shape/load knob (CI smoke
runs tiny configs, flagged ``shape_override``), mirroring the
``REPLAY_TPU_SERVE_*`` convention. Each replica logs its serve events to a
``events.p<i>.jsonl`` shard and the fleet logs to ``events.jsonl`` in
``runs/bench_fleet/`` — ``python -m replay_tpu.obs.report runs/bench_fleet``
merges them into the "fleet" section (per-replica totals + health
transitions + hedge/retry counters), and ``--compare`` gates ``fleet_qps``
/ ``fleet_p99_ms`` / ``fleet_reroute_rate`` plus 10-point shifts in the p99
hop mix. The run is fully TRACED: the router and every replica each run a
live :class:`~replay_tpu.obs.Tracer`, merged after close into ONE
``runs/bench_fleet/trace.json`` (labeled Perfetto tracks; a hedged or
failed-over request's spans share a trace_id across tracks), from which the
report derives the "tail attribution" section; the JSON record carries the
slowest-request exemplar trace ids, and the chaos row links the failover
probe's answer to its timeline via ``failover_trace_id``.

Backend policy mirrors bench.py: one process on the backend JAX gives it, the
record stamped with ``platform`` / ``device_kind`` / ``device_count``; on the
CPU the metric is renamed ``fleet_qps_cpu_fallback``.
"""

import json
import os
import threading
import time

import numpy as np

from replay_tpu.utils.compile_cache import enable_compile_cache

_DEFAULTS = {
    "REPLICAS": 3,
    "SEQ_LEN": 50,
    "NUM_ITEMS": 3706,
    "EMBEDDING_DIM": 64,
    "NUM_BLOCKS": 2,
    "USERS": 1_000_000,  # simulated population (lazily seeded on first touch)
    "CLIENTS": 8,
    "CLOSED_REQUESTS": 48,  # per client thread, per closed-loop phase
    "RATE": 300,  # open-loop arrivals per second
    "SECONDS": 6,  # steady open-loop duration
    "CHAOS_SECONDS": 6,  # 0 = no chaos phase
    "SWAP": 1,  # 0 = no drain-and-swap phase
    "SOCKET_CHAOS": 1,  # 0 = no socket-boundary SIGKILL chaos phase
    "SOCKET_REPLICAS": 3,  # server PROCESSES in the socket-chaos fleet
    "CACHE": 4096,  # per-service UserStateCache capacity (fleet AND baseline)
    "SHARD_ITEMS": 262_144,  # sharded-retrieval catalog (10_000_000 on TPU)
    "SHARD_DIM": 64,
    "SHARD_TOPK": 100,
}


def _knob(name: str) -> int:
    return int(os.environ.get(f"REPLAY_TPU_FLEET_{name}", _DEFAULTS[name]))


REPLICAS = max(_knob("REPLICAS"), 1)
SOCKET_CHAOS = _knob("SOCKET_CHAOS")
SOCKET_REPLICAS = max(_knob("SOCKET_REPLICAS"), 2)
SEQ_LEN = _knob("SEQ_LEN")
NUM_ITEMS = _knob("NUM_ITEMS")
EMBEDDING_DIM = _knob("EMBEDDING_DIM")
NUM_BLOCKS = _knob("NUM_BLOCKS")
USERS = _knob("USERS")
CLIENTS = _knob("CLIENTS")
CLOSED_REQUESTS = _knob("CLOSED_REQUESTS")
RATE = _knob("RATE")
SECONDS = _knob("SECONDS")
CHAOS_SECONDS = _knob("CHAOS_SECONDS")
SWAP = _knob("SWAP")
CACHE = _knob("CACHE")
SHARD_ITEMS = _knob("SHARD_ITEMS")
SHARD_DIM = _knob("SHARD_DIM")
SHARD_TOPK = _knob("SHARD_TOPK")
MAX_WAIT_MS = float(os.environ.get("REPLAY_TPU_FLEET_MAX_WAIT_MS", "2.0"))
BATCH_BUCKETS = tuple(
    int(b) for b in os.environ.get("REPLAY_TPU_FLEET_BATCH_BUCKETS", "1,8,64").split(",")
)
ZIPF_A = float(os.environ.get("REPLAY_TPU_FLEET_ZIPF_A", "1.3"))
# hedge delay: "" = p99-derived (the production default), a number pins it,
# "0" disables hedging for the run
_HEDGE = os.environ.get("REPLAY_TPU_FLEET_HEDGE_MS", "")
HEDGE_MS = float(_HEDGE) if _HEDGE.strip() else None
HEARTBEAT_S = float(os.environ.get("REPLAY_TPU_FLEET_HEARTBEAT_S", "0.1"))
SHAPE_OVERRIDE = any(_knob(k) != v for k, v in _DEFAULTS.items())

RUN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "runs", "bench_fleet")


def _percentile(latencies, q: float) -> float:
    return float(np.percentile(np.asarray(latencies), q)) if latencies else float("nan")


def _await_all(futures, timeout_s: float = 60.0) -> int:
    """How many futures are STILL unresolved past the grace period — the
    zero-hung-requests acceptance number."""
    deadline = time.perf_counter() + timeout_s
    for future in futures:
        remaining = deadline - time.perf_counter()
        if remaining <= 0:
            break
        try:
            future.result(timeout=remaining)
        except Exception:  # noqa: BLE001 — accounted via callbacks
            pass
    return sum(1 for future in futures if not future.done())


class Traffic:
    """The returning-user mix over a Zipf-headed million-user population.

    First touch of a user sends their (deterministically generated) full
    history — the cold path; later touches are mostly pure hits with a slice
    of one-step advances and a trickle of history re-sends, the same mix
    ``bench_serve.py`` uses. Shared by every phase and both targets (fleet
    and the single-replica baseline), so the comparison is apples-to-apples.
    """

    def __init__(self, population: int, num_items: int, seq_len: int) -> None:
        self.population = int(population)
        self.num_items = int(num_items)
        self.seq_len = int(seq_len)
        self.histories = {}
        self._lock = threading.Lock()

    def pick_user(self, rng) -> int:
        return int(rng.zipf(ZIPF_A)) % self.population

    def history_for(self, user: int):
        with self._lock:
            history = self.histories.get(user)
            if history is None:
                user_rng = np.random.default_rng(900_000 + user)
                history = user_rng.integers(
                    0, self.num_items, size=int(user_rng.integers(1, 2 * self.seq_len))
                ).tolist()
                self.histories[user] = history
        return history

    def submit_one(self, target, rng, user=None, deadline_ms=None):
        if user is None:
            user = self.pick_user(rng)
        with self._lock:
            seeded = user in self.histories
        if not seeded:
            return target.submit(
                user, history=self.history_for(user), deadline_ms=deadline_ms
            )
        draw = rng.random()
        if draw < 0.7:
            return target.submit(user, deadline_ms=deadline_ms)
        if draw < 0.9:
            new_item = int(rng.integers(0, self.num_items))
            with self._lock:
                self.histories[user].append(new_item)
            return target.submit(user, new_items=[new_item], deadline_ms=deadline_ms)
        return target.submit(
            user, history=self.history_for(user), deadline_ms=deadline_ms
        )

    @property
    def touched(self) -> int:
        with self._lock:
            return len(self.histories)


def _classify(exc) -> str:
    from replay_tpu.serve import (
        CircuitOpen,
        DeadlineExceeded,
        NoHealthyReplica,
        RequestShed,
        ServiceClosed,
    )

    if isinstance(exc, RequestShed):
        return "shed"
    if isinstance(exc, DeadlineExceeded):
        return "deadline_missed"
    if isinstance(exc, CircuitOpen):
        return "circuit_refused"
    if isinstance(exc, NoHealthyReplica):
        return "no_healthy"
    if isinstance(exc, ServiceClosed):
        return "service_closed"
    if isinstance(exc, KeyError):
        # the documented failover contract: an interaction that cannot land
        # on a cold downstream cache refuses with "re-anchor with history="
        # rather than masking the drop — a distinct kind, not a raw error
        return "cold_reanchor_needed"
    return "error"


def _run_closed_loop(target, traffic, clients: int, requests_each: int, seed: int):
    """Closed-loop saturation: qps + per-thread error capture."""
    errors = []

    def client(idx: int) -> None:
        rng = np.random.default_rng(seed + idx)
        for _ in range(requests_each):
            try:
                traffic.submit_one(target, rng).result(timeout=120)
            except Exception as exc:  # noqa: BLE001 — recorded, not fatal
                errors.append(repr(exc))

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True) for i in range(clients)
    ]
    start = time.perf_counter()
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    elapsed = time.perf_counter() - start
    return clients * requests_each / elapsed, errors


def _run_open_loop(target, traffic, rate: float, seconds: float, seed: int):
    """Open-loop Poisson arrivals; latency on completion callbacks (immune to
    coordinated omission). Returns (record, futures)."""
    rng = np.random.default_rng(seed)
    latencies = []
    counts = {}
    lock = threading.Lock()
    futures = []

    def on_done(submitted_at):
        def callback(future):
            latency = time.perf_counter() - submitted_at
            exc = future.exception() if not future.cancelled() else None
            with lock:
                if future.cancelled():
                    counts["cancelled"] = counts.get("cancelled", 0) + 1
                elif exc is None:
                    latencies.append(latency)
                else:
                    kind = _classify(exc)
                    counts[kind] = counts.get(kind, 0) + 1

        return callback

    start = time.perf_counter()
    deadline = start + seconds
    submitted = 0
    while time.perf_counter() < deadline:
        submitted_at = time.perf_counter()
        future = traffic.submit_one(target, rng)
        future.add_done_callback(on_done(submitted_at))
        futures.append(future)
        submitted += 1
        gap = float(rng.exponential(1.0 / max(rate, 1.0)))
        if gap > 0.0005:
            time.sleep(min(gap, 1.0))
    hung = _await_all(futures)
    # drain the callback tail: result() waiters wake before callbacks run
    drain_deadline = time.perf_counter() + 10.0
    while time.perf_counter() < drain_deadline:
        with lock:
            accounted = len(latencies) + sum(counts.values())
        if accounted >= submitted - hung:
            break
        time.sleep(0.005)
    elapsed = time.perf_counter() - start
    with lock:
        record = {
            "submitted": submitted,
            "answered": len(latencies),
            "qps": round(len(latencies) / elapsed, 1),
            "p50_ms": round(_percentile(latencies, 50) * 1000.0, 3),
            "p99_ms": round(_percentile(latencies, 99) * 1000.0, 3),
            "hung_requests": hung,
            "errors_by_kind": dict(counts),
            "error_rate": (
                round(sum(counts.values()) / submitted, 4) if submitted else 0.0
            ),
            "elapsed_s": round(elapsed, 2),
        }
    return record, futures


def _fleet_hit_rate(services) -> float:
    """Combined state-reuse rate across replicas (hits + advances over
    answered) — the locality numerator."""
    reused = answered = 0
    for service in services:
        stats = service.stats()
        served = stats["served_from"]
        reused += served["hit"] + served["advance"]
        answered += stats["answered"]
    return reused / answered if answered else 0.0


def _run_chaos(fleet, traffic, victim: str, seconds: float):
    """Kill ``victim`` mid-traffic, measure the failover gap, revive it.

    Timeline: traffic runs for the whole phase on a generator thread; at
    ~1/3 the victim's service is closed (heartbeats then declare it dead and
    its users fail over along their ring order); a probe loop measures
    kill → first successful answer for a user homed on the victim; at ~2/3
    the service is started again and the monitor must mark it healthy.
    """
    stats_before = fleet.stats()
    futures_box = {}
    done = threading.Event()

    def generator():
        record, futures = _run_open_loop(fleet, traffic, RATE, seconds, seed=31)
        futures_box["record"] = record
        futures_box["futures"] = futures
        done.set()

    thread = threading.Thread(target=generator, daemon=True)
    thread.start()

    time.sleep(seconds / 3.0)
    # a user whose HOME is the victim, already seeded: the failover probe
    probe_user = next(
        (
            user
            for user in list(traffic.histories)
            if fleet.ring.route(user) == victim
        ),
        None,
    )
    if probe_user is None:
        probe_user = next(
            user for user in range(traffic.population)
            if fleet.ring.route(user) == victim
        )
        traffic.history_for(probe_user)
    handle = fleet.handles[victim]
    kill_at = time.perf_counter()
    handle.service.close()

    failover_gap_ms = None
    failover_served_by = None
    failover_replica = None
    failover_trace_id = None
    probe_deadline = time.perf_counter() + max(10.0, seconds)
    probe_rng = np.random.default_rng(47)
    while time.perf_counter() < probe_deadline:
        try:
            response = traffic.submit_one(
                fleet, probe_rng, user=probe_user
            ).result(timeout=5.0)
        except Exception:  # noqa: BLE001 — the gap IS these failures
            time.sleep(0.01)
            continue
        failover_gap_ms = (time.perf_counter() - kill_at) * 1000.0
        failover_served_by = response.served_by
        failover_replica = response.replica
        failover_trace_id = response.trace_id
        break

    time.sleep(max(seconds * 2.0 / 3.0 - (time.perf_counter() - kill_at), 0.0))
    # sampled AFTER the heartbeat window: the in-flight retry failover above
    # typically answers BEFORE the monitor declares the death — the probe
    # measures rerouting, this records detection
    dead_observed = fleet.health().get(victim)
    handle.service.start()
    revive_deadline = time.perf_counter() + max(5.0, 20 * HEARTBEAT_S)
    revived = False
    while time.perf_counter() < revive_deadline:
        if fleet.health().get(victim) == "healthy":
            revived = True
            break
        time.sleep(HEARTBEAT_S)
    done.wait(timeout=seconds + 120.0)
    record = futures_box.get("record", {})
    stats_after = fleet.stats()
    return {
        "killed": victim,
        "dead_observed": dead_observed,
        "revived": revived,
        "failover_gap_ms": (
            round(failover_gap_ms, 1) if failover_gap_ms is not None else None
        ),
        "failover_served_by": failover_served_by,
        "failover_replica": failover_replica,
        # the probe answer's trace id plus the slowest-request exemplars as
        # of the chaos phase's end: during the chaos window the exemplar
        # store is dominated by failover-gap requests, so these ids link
        # "the failover was slow" straight to timelines in trace.json
        "failover_trace_id": failover_trace_id,
        "exemplar_trace_ids": [
            e["trace_id"] for e in stats_after.get("latency_exemplars", ())
        ],
        "reroutes": stats_after["reroutes"] - stats_before["reroutes"],
        "retries": stats_after["retries"] - stats_before["retries"],
        "failovers": stats_after["failovers"] - stats_before["failovers"],
        "submitted": record.get("submitted"),
        "answered": record.get("answered"),
        "error_rate": record.get("error_rate"),
        "errors_by_kind": record.get("errors_by_kind"),
        "hung_requests": record.get("hung_requests"),
        "p99_ms": record.get("p99_ms"),
    }


def _run_socket_chaos(seconds: float):
    """The process-real chaos phase: a fleet of replica server PROCESSES
    behind real HTTP (``serve.remote``), one SIGKILLed mid-traffic.

    The in-process ``_run_chaos`` kills a replica by closing it — a polite
    death that resolves its own futures. This one sends ``SIGKILL`` to a
    server process: no handler, no close path, just connection-refused
    sockets. The claims upgrade accordingly: the router's only signals are
    transport errors (surfaced as the retryable ``ServiceClosed``) and
    failed ``/healthz`` scrapes, and STILL — zero hung requests, a bounded
    failover gap, taxonomy-only errors, and a respawned server on a fresh
    ephemeral port picked up without rebuilding the fleet.

    Servers run tiny fixed shapes on clean CPU (never the TPU grant): this
    phase measures the socket boundary, not the model.
    """
    from replay_tpu.parallel import clean_cpu_env
    from replay_tpu.serve import RemoteReplica, ReplicaServerProcess, ServingFleet
    from replay_tpu.utils import KillAtStep

    repo_root = os.path.dirname(os.path.abspath(__file__))
    env = clean_cpu_env(local_devices=1, repo_root=repo_root)
    os.makedirs(RUN_DIR, exist_ok=True)
    spawn_start = time.perf_counter()
    servers = [
        ReplicaServerProcess(
            env=env,
            args=[
                "--num-items", "64", "--seq-len", "12",
                "--embedding-dim", "8", "--num-blocks", "1",
            ],
            # each server records into its own flight ring: the SIGKILLed
            # one's last serve events are read back below (obs.blackbox)
            flight_path=os.path.join(RUN_DIR, f"flight.s{i}.ring"),
        )
        for i in range(SOCKET_REPLICAS)
    ]
    try:
        for server in servers:  # engines compile concurrently
            server.spawn(wait=False)
        for server in servers:
            server.wait_ready()
        spawn_seconds = time.perf_counter() - spawn_start

        replicas = {f"s{i}": RemoteReplica(server) for i, server in enumerate(servers)}
        fleet = ServingFleet(
            replicas,
            hedge_ms=HEDGE_MS,
            heartbeat_interval_s=HEARTBEAT_S,
            heartbeat_misses=3,
        )
        traffic = Traffic(10_000, 64, 12)
        victim = "s1"
        victim_server = servers[1]
        with fleet:
            futures_box = {}
            done = threading.Event()

            def generator():
                record, futures = _run_open_loop(
                    fleet, traffic, min(RATE, 100), seconds, seed=53
                )
                futures_box["record"] = record
                done.set()

            thread = threading.Thread(target=generator, daemon=True)
            thread.start()

            time.sleep(seconds / 3.0)
            probe_user = next(
                user for user in range(traffic.population)
                if fleet.ring.route(user) == victim
            )
            traffic.history_for(probe_user)
            try:
                fleet.score(probe_user, history=traffic.history_for(probe_user))
            except Exception:  # noqa: BLE001 — seeding is best-effort
                pass

            kill_at = time.perf_counter()
            KillAtStep(pid=victim_server.pid).fire()
            sigkill_rc = victim_server.proc.wait(timeout=10)

            # harvest the black box NOW, before respawn() reopens the same
            # ring and continues it — this read is the dead incarnation's
            # post-mortem: last recorded seqno, recovered records, torn tail
            from replay_tpu.obs.blackbox import read_flight

            try:
                flight = read_flight(victim_server.flight_path)
                flight_last_seqno = flight.last_seqno
                flight_recovered = flight.recovered
                flight_torn_tail = flight.torn_tail
            except (OSError, ValueError) as exc:
                print(f"flight ring unreadable after SIGKILL: {exc!r}")
                flight_last_seqno = None
                flight_recovered = 0
                flight_torn_tail = None

            failover_gap_ms = None
            failover_replica = None
            probe_rng = np.random.default_rng(59)
            probe_deadline = time.perf_counter() + max(10.0, seconds)
            while time.perf_counter() < probe_deadline:
                try:
                    response = traffic.submit_one(
                        fleet, probe_rng, user=probe_user
                    ).result(timeout=5.0)
                except Exception:  # noqa: BLE001 — the gap IS these failures
                    time.sleep(0.01)
                    continue
                failover_gap_ms = (time.perf_counter() - kill_at) * 1000.0
                failover_replica = response.replica
                break

            time.sleep(max(seconds * 2.0 / 3.0 - (time.perf_counter() - kill_at), 0.0))
            dead_observed = fleet.health().get(victim)
            old_address = replicas[victim].address
            victim_server.respawn()
            address_changed = replicas[victim].address != old_address
            revive_deadline = time.perf_counter() + max(5.0, 30 * HEARTBEAT_S)
            revived = False
            while time.perf_counter() < revive_deadline:
                if fleet.health().get(victim) == "healthy":
                    revived = True
                    break
                time.sleep(HEARTBEAT_S)
            done.wait(timeout=seconds + 120.0)
            record = futures_box.get("record", {})
        errors_by_kind = record.get("errors_by_kind") or {}
        return {
            "replicas": SOCKET_REPLICAS,
            "killed": victim,
            "sigkill_rc": sigkill_rc,
            "dead_observed": dead_observed,
            "revived": revived,
            "respawned_address_changed": address_changed,
            "failover_gap_ms": (
                round(failover_gap_ms, 1) if failover_gap_ms is not None else None
            ),
            "failover_replica": failover_replica,
            "submitted": record.get("submitted"),
            "answered": record.get("answered"),
            "hung_requests": record.get("hung_requests"),
            "error_rate": record.get("error_rate"),
            "errors_by_kind": errors_by_kind,
            # a SIGKILLed process produces ONLY taxonomy refusals through the
            # socket client — raw transport garbage would land under "error"
            "taxonomy_only": errors_by_kind.get("error", 0) == 0,
            "p99_ms": record.get("p99_ms"),
            "spawn_seconds": round(spawn_seconds, 2),
            # the dead server's flight ring, read back post-SIGKILL: proof
            # the black box survives a kill -9 with its records intact
            "flight_last_seqno": flight_last_seqno,
            "flight_records_recovered": flight_recovered,
            "torn_tail": flight_torn_tail,
        }
    finally:
        for server in servers:
            server.terminate()


def _run_drain_swap(fleet, traffic, params, clients: int):
    """Fleet-wide drain-and-swap rollout under closed-loop load: every
    replica drained → hot-swapped (pointer move) → rejoined while clients
    keep scoring. Zero request errors is the claim."""
    import jax

    latencies = []
    errors = []
    lock = threading.Lock()
    stop = threading.Event()

    reanchors = []

    def client(idx: int) -> None:
        rng = np.random.default_rng(7000 + idx)
        while not stop.is_set():
            user = traffic.pick_user(rng)
            started = time.perf_counter()
            try:
                traffic.submit_one(fleet, rng, user=user).result(timeout=120)
            except KeyError:
                # the documented client contract: a rerouted interaction that
                # cannot land cold re-anchors with the full history (which
                # both answers AND re-seeds the downstream cache)
                try:
                    fleet.submit(
                        user, history=traffic.history_for(user)
                    ).result(timeout=120)
                except Exception as exc:  # noqa: BLE001 — now a real error
                    errors.append(repr(exc))
                    continue
                reanchors.append(user)
            except Exception as exc:  # noqa: BLE001 — recorded, asserted zero
                errors.append(repr(exc))
                continue
            with lock:
                latencies.append(time.perf_counter() - started)

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True) for i in range(clients)
    ]
    for thread in threads:
        thread.start()
    time.sleep(0.1)
    scale = 1.001
    candidate = jax.tree.map(
        lambda x: (np.asarray(x) * scale).astype(np.asarray(x).dtype), params
    )
    swap_start = time.perf_counter()
    results = fleet.rolling_swap(candidate, label="fleet-rollout")
    swap_seconds = time.perf_counter() - swap_start
    time.sleep(0.1)
    stop.set()
    for thread in threads:
        thread.join(timeout=130)
    return {
        "replicas_swapped": sum(1 for r in results if "generation" in r),
        "skipped": sum(1 for r in results if r.get("skipped")),
        "drained": sum(1 for r in results if r.get("drained")),
        "generations": sorted({r["generation"] for r in results if "generation" in r}),
        "requests": len(latencies) + len(errors),
        "reanchors": len(reanchors),
        "errors": len(errors),
        "first_error": errors[0] if errors else None,
        "p50_ms": round(_percentile(latencies, 50) * 1000.0, 3),
        "p99_ms": round(_percentile(latencies, 99) * 1000.0, 3),
        "rollout_seconds": round(swap_seconds, 2),
    }


def _run_sharded_retrieval():
    """The TP-sharded MIPS block: [I/n, E] row shards on the mesh's model
    axis (f32 AND the PR-11 int8 variant), per-shard top-k + candidate-only
    merge — bitwise vs unsharded, table-gather hard-asserted absent from the
    compiled HLO via collective_inventory."""
    import jax

    from replay_tpu.models.ann import MIPSIndex
    from replay_tpu.nn import make_mesh
    from replay_tpu.parallel.introspect import collective_inventory

    n_devices = len(jax.devices())
    rng = np.random.default_rng(3)
    table = rng.normal(size=(SHARD_ITEMS, SHARD_DIM)).astype(np.float32)
    queries = rng.normal(size=(64, SHARD_DIM)).astype(np.float32)
    mesh = make_mesh(model_parallel=n_devices)
    out = {"items": SHARD_ITEMS, "dim": SHARD_DIM, "shards": n_devices}
    for precision in ("f32", "int8"):
        sharded = MIPSIndex(table, mesh=mesh, axis_name="model", precision=precision)
        unsharded = MIPSIndex(table, precision=precision)
        t0 = time.perf_counter()
        values_s, ids_s = sharded.search(queries, SHARD_TOPK)
        sharded_ms = (time.perf_counter() - t0) * 1000.0
        values_u, ids_u = unsharded.search(queries, SHARD_TOPK)
        bitwise = bool(
            np.array_equal(values_s, values_u) and np.array_equal(ids_s, ids_u)
        )
        inventory = collective_inventory(sharded.search_hlo(64, SHARD_TOPK))
        shard_bytes = sharded.table_shard_bytes()
        # the only legal cross-shard traffic is the per-shard CANDIDATES:
        # [Q, local_k] values + ids per shard (f32/s32, 8 B a pair), with 2x
        # slack for async-start tuple double counting. Independent of the
        # catalog size I — at 10M items the table shard is ~3000x this
        # budget, so a table gather cannot hide under it.
        shard_rows = -(-SHARD_ITEMS // n_devices)
        merge_budget = 2 * 64 * min(SHARD_TOPK, shard_rows) * n_devices * 8
        oversized = [
            c for c in inventory if (c.get("bytes") or 0) > merge_budget
        ]
        # the headline invariant, asserted here — not just recorded: a
        # sharded search that moves more than candidate-merge traffic is
        # gathering table rows, and that is a broken build
        assert not oversized, (
            f"sharded MIPS ({precision}) moved more than the candidate-merge "
            f"budget ({merge_budget} B): {oversized}"
        )
        collective_bytes = sum(int(c.get("bytes") or 0) for c in inventory)
        out[precision] = {
            "bitwise_vs_unsharded": bitwise,
            "table_shard_bytes": shard_bytes,
            "merge_budget_bytes": merge_budget,
            "collective_bytes": collective_bytes,
            "collectives": len(inventory),
            "table_gather_free": True,
            "search_ms": round(sharded_ms, 2),
        }
        del sharded, unsharded
    return out


def main() -> None:
    enable_compile_cache()
    import jax

    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.obs import (
        JsonlLogger,
        PopularityDescriptor,
        QualityMonitor,
        Tracer,
        merge_traces,
    )
    from replay_tpu.serve import FallbackScorer, ScoringService, ServingFleet

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

    # the degradation ladder's floor, shared by every replica: popularity
    # over a synthetic log (cold failover traffic rides this instead of
    # erroring — cold_miss="fallback")
    popularity = rng.integers(0, NUM_ITEMS, size=8192)
    fallback = FallbackScorer.from_interactions(popularity, NUM_ITEMS)

    # sharded retrieval first: its (one-off) compile must not pollute the
    # serving phases' latencies
    sharded_retrieval = _run_sharded_retrieval()

    # the quality plane, fleet-wide: one monitor per replica over ONE shared
    # popularity descriptor (the same synthetic log the fallback ranks by) —
    # fleet.stats() aggregates the join-weighted online hitrate and the
    # per-replica drift state into its "quality" block
    quality_descriptor = PopularityDescriptor.from_train(
        {0: popularity.tolist()}, num_items=NUM_ITEMS
    )

    def build_service(logger=None, tracer=None, quality=None):
        return ScoringService(
            model,
            params,
            batch_buckets=BATCH_BUCKETS,
            max_wait_ms=MAX_WAIT_MS,
            cache_capacity=CACHE,
            logger=logger,
            tracer=tracer,
            cold_miss="fallback",
            fallback=FallbackScorer(fallback.item_scores),
            quality=quality,
        )

    fleet_logger = JsonlLogger(RUN_DIR, mode="w")
    compile_start = time.perf_counter()
    # replica i's serve events land in events.p<i+1>.jsonl: the PR-10
    # process-shard layout, reused one level up so obs.report merges the
    # fleet's per-replica streams like a multi-host run's
    replica_loggers = [
        JsonlLogger(RUN_DIR, mode="w", process_index=i + 1) for i in range(REPLICAS)
    ]
    # the distributed-tracing plane: one tracer per replica plus the router's
    # own — merged after the run into ONE trace.json with labeled tracks, so
    # a hedged/failed-over request reads as one connected timeline
    router_tracer = Tracer(enabled=True)
    replica_tracers = {f"r{i}": Tracer(enabled=True) for i in range(REPLICAS)}
    services = {
        f"r{i}": build_service(
            logger=replica_loggers[i],
            tracer=replica_tracers[f"r{i}"],
            quality=QualityMonitor(quality_descriptor),
        )
        for i in range(REPLICAS)
    }
    baseline_service = build_service()
    compile_seconds = time.perf_counter() - compile_start

    traffic = Traffic(USERS, NUM_ITEMS, SEQ_LEN)

    # ---- single-replica baseline: the yardsticks ----------------------- #
    baseline_service.start()
    single_closed_qps, single_errors = _run_closed_loop(
        baseline_service, traffic, CLIENTS, CLOSED_REQUESTS, seed=100
    )
    single_open, _ = _run_open_loop(
        baseline_service, traffic, RATE, max(SECONDS / 2.0, 1.0), seed=11
    )
    single_hit_rate = _fleet_hit_rate([baseline_service])
    baseline_service.close()

    # fresh histories for the fleet phases: the fleet must build its own
    # cache locality from the same population, not inherit the baseline's
    traffic = Traffic(USERS, NUM_ITEMS, SEQ_LEN)

    fleet = ServingFleet(
        services,
        hedge_ms=HEDGE_MS,
        heartbeat_interval_s=HEARTBEAT_S,
        logger=fleet_logger,
        tracer=router_tracer,
    )
    with fleet:
        # ---- steady state: closed-loop saturation + open-loop latency --- #
        fleet_closed_qps, fleet_errors = _run_closed_loop(
            fleet, traffic, CLIENTS, CLOSED_REQUESTS, seed=200
        )
        steady, _ = _run_open_loop(fleet, traffic, RATE, SECONDS, seed=21)
        fleet_hit_rate = _fleet_hit_rate(services.values())
        steady_stats = fleet.stats()

        # ---- drain-and-swap rollout (before chaos: its zero-error claim
        # must not be polluted by the injected kill) ---------------------- #
        drain_swap = None
        if SWAP:
            drain_swap = _run_drain_swap(fleet, traffic, params, CLIENTS)

        # ---- chaos: kill + revive one replica mid-traffic ---------------- #
        chaos = None
        if CHAOS_SECONDS > 0 and REPLICAS > 1:
            chaos = _run_chaos(fleet, traffic, victim="r1", seconds=CHAOS_SECONDS)

        final_stats = fleet.stats()
        per_replica = {}
        for rid, service in services.items():
            stats = service.stats()
            router_view = final_stats["per_replica"][rid]
            per_replica[rid] = {
                "routed": router_view["routed"],
                "answered": stats["answered"],
                "cache_hit_rate": round(stats["cache_hit_rate"], 4),
                "errors": stats["errors"],
                "health": router_view["health"],
                "health_transitions": router_view["health_transitions"],
                # router-side resilience counters: hedges landed here as the
                # racing twin (wins/cancels), retries this replica's refusals
                # caused — the per-replica half of the fleet report section
                "hedges": router_view["hedges"],
                "hedge_wins": router_view["hedge_wins"],
                "hedge_cancelled": router_view["hedge_cancelled"],
                "retries": router_view["retries"],
            }

    # ---- socket-boundary chaos: SIGKILL a real server PROCESS ----------- #
    socket_chaos = None
    if SOCKET_CHAOS and CHAOS_SECONDS > 0:
        socket_chaos = _run_socket_chaos(float(CHAOS_SECONDS))

    # ONE merged trace for the whole run: the router's track plus every
    # replica's, epoch-aligned — a hedged-and-failed-over request's spans
    # share a trace_id across tracks and render as one connected timeline
    merge_traces(
        {"router": router_tracer, **replica_tracers},
        os.path.join(RUN_DIR, "trace.json"),
    )

    locality = (
        fleet_hit_rate / single_hit_rate if single_hit_rate else float("nan")
    )
    hung_requests = steady["hung_requests"] + (
        (chaos.get("hung_requests") or 0) if chaos else 0
    )
    metric = "fleet_qps"
    if jax.default_backend() == "cpu":
        metric += "_cpu_fallback"
    record = {
        "metric": metric,
        "value": steady["qps"],
        "unit": "req/s",
        "qps": steady["qps"],
        "closed_loop_qps": round(fleet_closed_qps, 1),
        "p50_ms": steady["p50_ms"],
        "p99_ms": steady["p99_ms"],
        "replicas": REPLICAS,
        "users_population": USERS,
        "users_touched": traffic.touched,
        "requests": final_stats["requests"],
        "request_errors": len(fleet_errors) + steady["errors_by_kind"].get("error", 0),
        "fleet_error_rate": round(final_stats["error_rate"], 4),
        "hung_requests": hung_requests,
        "reroutes": final_stats["reroutes"],
        "reroute_rate": round(final_stats["reroute_rate"], 4),
        "retries": final_stats["retries"],
        "hedges": final_stats["hedges"],
        "hedge_wins": final_stats["hedge_wins"],
        "hedge_cancelled": final_stats["hedge_cancelled"],
        "failovers": final_stats["failovers"],
        "cache_hit_rate": round(fleet_hit_rate, 4),
        "single_replica_qps": round(single_closed_qps, 1),
        "single_replica_open_qps": single_open["qps"],
        "single_replica_hit_rate": round(single_hit_rate, 4),
        "single_replica_p99_ms": single_open["p99_ms"],
        "cache_hit_locality": round(locality, 4),
        "qps_vs_single": (
            round(fleet_closed_qps / single_closed_qps, 3)
            if single_closed_qps
            else None
        ),
        "per_replica": per_replica,
        # the fleet-wide quality aggregation (fleet.stats): total prequential
        # joins, join-weighted online hitrate, max drift PSI across replicas
        "quality": final_stats.get("quality"),
        # slowest answered requests with their trace ids (the exemplar store
        # riding the fleet latency histogram): the JSON record's link into
        # the merged trace.json alongside it
        "latency_exemplars": final_stats["latency_exemplars"],
        # shard index -> replica id: replica i logs to events.p<i+1>.jsonl,
        # and obs.report uses this map to merge the shard-derived per-replica
        # totals under the replica's name instead of its shard number
        "replica_shards": {str(i + 1): f"r{i}" for i in range(REPLICAS)},
        "sharded_retrieval": sharded_retrieval,
        "backend": jax.default_backend(),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "batch_buckets": list(BATCH_BUCKETS),
        "open_loop_rate": RATE,
        "open_loop_seconds": SECONDS,
        "clients": CLIENTS,
        "compile_seconds": round(compile_seconds, 2),
    }
    if drain_swap is not None:
        record["drain_swap"] = drain_swap
    if chaos is not None:
        record["chaos"] = chaos
    if socket_chaos is not None:
        record["socket_chaos"] = socket_chaos
    if SHAPE_OVERRIDE:
        record["shape_override"] = {
            "replicas": REPLICAS,
            "L": SEQ_LEN,
            "items": NUM_ITEMS,
            "d": EMBEDDING_DIM,
            "users": USERS,
        }
    if single_errors or fleet_errors:
        record["first_error"] = (single_errors + fleet_errors)[0]
    # the record rides the fleet's events.jsonl so the report CLI renders
    # the "fleet" section (router events + per-replica shards + this row)
    # from one artifact
    fleet_logger.log_record(record)
    fleet_logger.close()
    for logger in replica_loggers:
        logger.close()
    print(json.dumps(record))


if __name__ == "__main__":
    main()
