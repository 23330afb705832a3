"""Host-side span tracing + goodput accounting (the profiler/Timer replacement).

Parity target: PyTorch Lightning meters a run with its profiler connector and
``Timer`` callback (replay's Lightning stack gets both for free); this layer
does the same job for the JAX trainer and goes further — it answers the
question Lightning never could: *where does wall-clock go between optimizer
steps?* TurboGR-style goodput accounting (PAPERS.md) splits a run into
``data_wait`` / ``h2d`` / ``compile`` / ``train_step`` / ``validation`` /
``checkpoint`` / ``recovery`` phases whose fractions sum to 1.0, so "is the
TPU idle because of the host?" is a one-line answer.

Design:

* :class:`Tracer` records nestable spans via ``with tracer.span(name):``.
  Thread-safe (per-thread nesting stacks, one lock on the event list) so the
  prefetch thread's ``batch_build`` spans coexist with the fit loop's spans.
  Disabled tracers return a shared null context — near-zero overhead, safe to
  leave the instrumentation in hot paths.
* Exports Chrome trace-event JSON (:meth:`Tracer.save` → ``trace.json``),
  loadable in Perfetto / ``chrome://tracing``. Bounded: the event store keeps
  the newest ``maxlen`` records while per-name running totals stay exact.
* :class:`stage` is the ONE call site per boundary of the fit path. It always
  enters a ``jax.profiler.TraceAnnotation`` (so any profiler session holds the
  span on the device ops' clock, plane ``/host:CPU``), always adds its seconds
  to the **chunk stage log** (:func:`chunk_stage_log`: a fixed-size ring of one
  record per scan chunk), and records into a :class:`Tracer` when one is
  attached. :class:`ChunkStages` is the fit thread's side of that log. What a
  thread's stages total outside any chunk (set-up: ``pkg_import``, ``split``,
  ``tokenize``, ``batcher_init``, ``init_state``) is the
  **start-up log** (:func:`startup_log`): one record per ``fit`` call.
* Once jax is imported the module listens to ``jax.monitoring``, once a process:
  every program that jax traces, lowers and compiles (or fetches from the
  persistent cache), on any thread, adds to the stage totals of the thread it
  happened on: ``compile_programs``, ``compile_trace_s``, ``compile_lower_s``,
  ``compile_backend_s``, ``compile_cache_load_s``, ``compile_cache_hits``,
  ``compile_cache_misses``. They ride the chunk's record or the start-up record
  like a stage's seconds.
* :meth:`Tracer.summary` aggregates per-name **inclusive** and **exclusive**
  (self) time; :func:`goodput_breakdown` turns an exclusive-time snapshot
  diff into the epoch/fit goodput record carried by ``on_epoch_end`` /
  ``on_fit_end`` events.

The module is import-light on purpose (no jax, no numpy): the report CLI and
the core-tier tests run it host-only. The annotation class and the listeners'
registry are taken from ``sys.modules["jax"]`` only once something else has
imported jax.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import sys
import threading
import time
from typing import (
    Any,
    Deque,
    Dict,
    Iterable,
    Iterator,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
)

__all__ = [
    "CHUNK_STAGES",
    "COMPILE_COUNTERS",
    "ChunkStages",
    "GOODPUT_SPANS",
    "REQUEST_HOP_SPANS",
    "SERVE_GOODPUT_SPANS",
    "TraceContext",
    "Tracer",
    "attach_tracer",
    "attached_tracer",
    "chunk_stage_log",
    "claim_chunk",
    "claimed_chunk",
    "goodput_breakdown",
    "lifecycle_span",
    "merge_traces",
    "package_imported",
    "stage",
    "startup_log",
    "tail_attribution",
    "traced_iterator",
]

# the phases of the goodput breakdown, in display order. "other" (derived) is
# everything the instrumentation did not attribute: python loop overhead,
# event emission, metric host work between steps. "batch_build" is the
# batcher's assembly work (SequenceBatcher(tracer=...)): when the batcher runs
# on the consuming thread its spans nest inside data_wait — listing it here
# keeps that time counted as input time rather than leaking into "other".
# "h2d" is the host-to-device copy and its fence; stacking a chunk's K batches
# (and the D2H wait on leaves that arrived as device arrays) is the separate
# "stack" span, folded into "h2d" here (_GOODPUT_FOLD). Under
# fit(scan_chunk=...) the device feed records both on the FEEDER thread, so
# they appear in trace.json but drop out of the fit thread's fractions — the
# drop is the overlap the feed bought (obs.report renders the across-thread
# total next to the in-loop share).
GOODPUT_SPANS = (
    "data_wait",
    "batch_build",
    "h2d",
    "compile",
    "train_step",
    "validation",
    "checkpoint",
    "recovery",
)

# the serving pipeline's phases (replay_tpu.serve): a request waits in the
# micro-batcher queue ("queue_wait", recorded cross-thread via
# :func:`lifecycle_span`), its batch is assembled ("batch_build", shared with
# the training batcher), scored on device ("score"), and — on the fused
# candidate->rank path — retrieved ("retrieve") and re-ranked ("rerank").
# ``goodput_breakdown(..., spans=SERVE_GOODPUT_SPANS)`` folds a serve worker's
# wall clock into fractions summing to 1.0, same contract as training.
SERVE_GOODPUT_SPANS = (
    "queue_wait",
    "batch_build",
    "score",
    "retrieve",
    "rerank",
)

# one fleet request's hops, in timeline order: the router's hash lookup
# ("route"), its hedge/backoff waits, then the replica-side serving phases.
# These are the rows of the report's "tail attribution" section — every span
# recorded with a ``trace_id`` (or batch-level ``trace_ids``) arg under one of
# these names is attributed to that request; the residual inside the
# root "request" span is "other" (dispatch handoffs, future resolution,
# device-queue time the host spans do not cover).
REQUEST_HOP_SPANS = (
    "route",
    "queue_wait",
    "batch_build",
    "score",
    "retrieve",
    "rerank",
    "backoff_wait",
    "hedge_wait",
)

# stage spans that split a goodput phase: their self time counts as the
# parent phase's, so the fractions read what they read before the split
# ("transform" runs inside the consumer's wait when no thread hides it)
_GOODPUT_FOLD = {
    "dispatch": "train_step",
    "device_wait": "train_step",
    "stack": "h2d",
    "transform": "data_wait",
}

# the spans that make up the stepping pipeline: the denominator of the
# input-starvation metric (time the step loop spent waiting on the batcher
# as a fraction of the loop's total productive+waiting time)
_STEP_PIPELINE = ("data_wait", "batch_build", "h2d", "compile", "train_step")

# the numerator: total input-side wait (blocking on the iterator + the batch
# assembly that happened inside that wait)
_INPUT_SPANS = ("data_wait", "batch_build")

_NULL_CONTEXT = contextlib.nullcontext()

# trace ids are minted per process: a short random-ish prefix (pid + coarse
# wall clock, fixed at import) plus a monotone sequence — unique across the
# fleet's processes without any coordination, and cheap (no uuid4 per request)
_TRACE_SEQ = itertools.count(1)
_TRACE_PREFIX = f"{os.getpid():x}{int(time.time() * 1e3) & 0xFFFFFF:06x}"


class TraceContext:
    """One request's distributed-trace identity: ``trace_id`` + parent span.

    Deliberately pure-JSON (:meth:`to_json` / :meth:`from_json` round-trip a
    plain dict of strings) so the context survives a future socket boundary
    between router and replica processes (ROADMAP item 9) unchanged — today it
    rides in-process through ``ScoringService.submit(_trace=...)``. Minted at
    fleet admission (:meth:`mint`); every hop records its span with
    ``trace_id=...`` in the span args, which is what lets
    :func:`merge_traces` + Perfetto render one hedged-and-failed-over request
    as a single connected timeline across router and replica tracks, and what
    :func:`tail_attribution` groups by.

    Tracing off = no context: the fleet mints only when its tracer is
    enabled, so the disabled hot path allocates nothing (``trace is None``
    everywhere).
    """

    __slots__ = ("trace_id", "parent_span")

    def __init__(self, trace_id: str, parent_span: Optional[str] = None) -> None:
        self.trace_id = str(trace_id)
        self.parent_span = parent_span

    @classmethod
    def mint(cls) -> "TraceContext":
        """A fresh root context (no parent span) — fleet admission."""
        return cls(f"t-{_TRACE_PREFIX}-{next(_TRACE_SEQ):06x}")

    def child(self, parent_span: str) -> "TraceContext":
        """The same trace, one hop deeper (``parent_span`` names the hop that
        forwarded it — e.g. ``"route"`` on the replica-bound context)."""
        return TraceContext(self.trace_id, parent_span=str(parent_span))

    def to_json(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"trace_id": self.trace_id}
        if self.parent_span is not None:
            out["parent_span"] = self.parent_span
        return out

    @classmethod
    def from_json(cls, payload: Optional[Mapping[str, Any]]) -> Optional["TraceContext"]:
        if not payload or "trace_id" not in payload:
            return None
        return cls(payload["trace_id"], parent_span=payload.get("parent_span"))

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"TraceContext({self.trace_id!r}, parent_span={self.parent_span!r})"


class _Span:
    """One live span: a reusable-context-manager-shaped frame.

    Returned by :meth:`Tracer.span`; keeps a reference to its recorded event
    dict after exit so :meth:`Tracer.carve` can re-attribute part of its self
    time (the compile-inside-first-step case).
    """

    __slots__ = ("_tracer", "name", "args", "start", "child_seconds", "record")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]) -> None:
        self._tracer = tracer
        self.name = name
        self.args = args
        self.start = 0.0
        self.child_seconds = 0.0
        self.record: Optional[Dict[str, Any]] = None

    def __enter__(self) -> "_Span":
        self._tracer._push(self)
        self.start = self._tracer._clock()
        return self

    def __exit__(self, *exc_info) -> None:
        end = self._tracer._clock()
        self._tracer._pop(self, end)


class Tracer:
    """Collects host-side spans; exports Chrome trace JSON and summaries.

    :param enabled: ``False`` turns every :meth:`span` into a shared null
        context manager — the instrumentation stays in place at near-zero cost.
    :param maxlen: how many span records the event store keeps (the newest;
        ``None`` = unbounded). Per-name, per-thread running totals are kept
        beside it, so :meth:`summary`, :meth:`snapshot` and the goodput
        fractions stay exact after old records have fallen off; only
        ``trace.json`` is then a tail of the run.
    """

    def __init__(self, enabled: bool = True, maxlen: Optional[int] = 65536) -> None:
        self.enabled = bool(enabled)
        self._clock = time.perf_counter
        self._t0 = self._clock()
        self._wall0 = time.time()
        self._lock = threading.Lock()
        self._events: Deque[Dict[str, Any]] = collections.deque(maxlen=maxlen)
        # (tid, name) -> [count, seconds, self_seconds] over every span ever
        # recorded, evicted ones included
        self._totals: Dict[Tuple[int, str], List[float]] = {}
        self._local = threading.local()

    # -- span recording ----------------------------------------------------- #
    def _stack(self) -> List[_Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = []
            self._local.stack = stack
        return stack

    def _push(self, span: _Span) -> None:
        self._stack().append(span)

    def _pop(self, span: _Span, end: float) -> None:
        stack = self._stack()
        # tolerate misnesting (a span closed out of order) instead of raising
        # from telemetry code: drop frames down to (and including) this span
        while stack:
            frame = stack.pop()
            if frame is span:
                break
        duration = max(end - span.start, 0.0)
        record = {
            "name": span.name,
            "tid": threading.get_ident(),
            "start": span.start - self._t0,
            "dur": duration,
            "self": max(duration - span.child_seconds, 0.0),
            "args": span.args,
        }
        span.record = record
        if stack:
            stack[-1].child_seconds += duration
        self._record(record)

    def _record(self, record: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(record)
            total = self._totals.setdefault((record["tid"], record["name"]), [0, 0.0, 0.0])
            total[0] += 1
            total[1] += record["dur"]
            total[2] += record["self"]

    def span(self, name: str, **args: Any):
        """Context manager timing the enclosed block as span ``name``.

        Nested spans subtract from the parent's exclusive ("self") time, so
        summary totals over sibling categories never double-count.
        """
        if not self.enabled:
            return _NULL_CONTEXT
        return _Span(self, name, args)

    def add_span(
        self, name: str, start_seconds: float, duration_seconds: float, **args: Any
    ) -> None:
        """Record a synthetic span measured outside ``with`` blocks (``start``
        relative to the tracer's epoch, i.e. another span's ``record['start']``)."""
        if not self.enabled:
            return
        duration = max(float(duration_seconds), 0.0)
        self._record(
            {
                "name": name,
                "tid": threading.get_ident(),
                "start": float(start_seconds),
                "dur": duration,
                "self": duration,
                "args": args,
            }
        )

    def carve(self, span: _Span, name: str, seconds: float, **args: Any) -> None:
        """Re-attribute ``seconds`` of a finished span's self time to ``name``.

        The carved span is recorded nested at the parent's start (Chrome trace
        renders it inside), and the parent's exclusive time shrinks by the
        same amount — used to split compile wall-time out of the step that
        triggered the (re)trace.
        """
        if not self.enabled or span is None or span.record is None:
            return
        seconds = max(min(float(seconds), span.record["self"]), 0.0)
        if seconds <= 0.0:
            return
        with self._lock:
            span.record["self"] -= seconds
            self._totals[(span.record["tid"], span.record["name"])][2] -= seconds
        self._record(
            {
                "name": name,
                "tid": span.record["tid"],
                "start": span.record["start"],
                "dur": seconds,
                "self": seconds,
                "args": args,
            }
        )

    # -- aggregation -------------------------------------------------------- #
    def now(self) -> float:
        """Epoch-relative timestamp (seconds since this tracer was created) —
        the time base of every recorded span's ``start``. Take one on the
        producing thread and hand it to :func:`lifecycle_span` on the consuming
        thread to time a phase whose begin/end straddle threads."""
        return self._clock() - self._t0

    def wall_seconds(self) -> float:
        """Seconds since this tracer was created."""
        return self._clock() - self._t0

    def summary(self, only_current_thread: bool = False) -> Dict[str, Dict[str, float]]:
        """``{name: {count, seconds, self_seconds}}`` over recorded spans
        (``seconds`` inclusive of children, ``self_seconds`` exclusive).

        ``only_current_thread`` restricts to spans recorded on the calling
        thread — what a wall-clock decomposition of THAT thread's time may
        count (work on other threads, e.g. a prefetch worker's
        ``batch_build``, overlaps it rather than consuming it).
        """
        tid = threading.get_ident() if only_current_thread else None
        with self._lock:
            totals = [(key, tuple(total)) for key, total in self._totals.items()]
        out: Dict[str, Dict[str, float]] = {}
        for (span_tid, name), (count, seconds, self_seconds) in totals:
            if tid is not None and span_tid != tid:
                continue
            entry = out.setdefault(name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0})
            entry["count"] += count
            entry["seconds"] += seconds
            entry["self_seconds"] += self_seconds
        return out

    def snapshot(self, only_current_thread: bool = False) -> Dict[str, float]:
        """Per-name exclusive-seconds totals — diff two snapshots to window a
        breakdown over an epoch (see :func:`goodput_breakdown`)."""
        return {
            name: entry["self_seconds"]
            for name, entry in self.summary(only_current_thread).items()
        }

    # -- export ------------------------------------------------------------- #
    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (the ``chrome://tracing`` / Perfetto format):
        complete events (``ph="X"``) with microsecond ``ts``/``dur``, of the
        records the store still holds (the newest ``maxlen``)."""
        with self._lock:
            events = list(self._events)
        pid = os.getpid()
        trace_events = []
        for event in sorted(events, key=lambda e: e["start"]):
            record = {
                "name": event["name"],
                "cat": "host",
                "ph": "X",
                "ts": round(event["start"] * 1e6, 3),
                "dur": round(event["dur"] * 1e6, 3),
                "pid": pid,
                "tid": event["tid"],
            }
            if event["args"]:
                record["args"] = {str(k): v for k, v in event["args"].items()}
            trace_events.append(record)
        return {
            "traceEvents": trace_events,
            "displayTimeUnit": "ms",
            "otherData": {"trace_epoch_unix": self._wall0},
        }

    def save(self, path: str) -> str:
        """Write ``trace.json`` (Chrome trace-event JSON) to ``path``."""
        directory = os.path.dirname(os.path.abspath(path))
        os.makedirs(directory, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(self.to_chrome_trace(), fh)
        return path


# --------------------------------------------------------------------------- #
# stage spans + the chunk stage log
# --------------------------------------------------------------------------- #
# the stages of the scan-chunked fit path, by the thread that runs them with
# the device feed on (docs/observability.md "Stage spans"). The names are a
# contract: the benchmark's per-layer readers and PERF.md go by them.
CHUNK_STAGES = {
    "fit": ("data_wait", "dispatch", "device_wait", "account"),
    "feeder": ("batch_build", "transform", "stack", "h2d", "feed_full"),
}
_FEEDER_FIELDS = CHUNK_STAGES["feeder"]
# span arguments that count: a stage's totals sum each under ``<stage>_<argument>``
_COUNTED_ARGS = ("device_programs", "python_rows")

# what jax reports of every program it builds (jax/_src/dispatch.py, pjit.py,
# interpreters/pxla.py and compiler.py of jax 0.9), by the field it adds to. A
# phase arrives as a scalar when it starts and as a duration when it ends, on
# the thread that does the work. ``backend_compile_duration`` wraps
# ``compiler.compile_or_get_cached``, the look into the persistent cache
# included: one event per executable, compiled or fetched, and the cache's
# load time (``cache_retrieval_time_sec``, fired on a hit) lies inside it.
# ``cache_hits`` fires on every executable fetched; ``cache_misses`` only when a
# compiled one is WRITTEN (never with no cache directory, nor under jax's
# default least compile time and entry size): what XLA compiled is
# ``compile_programs`` less ``compile_cache_hits``, whatever the cache's settings.
_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile_lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_backend_s",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}
COMPILE_COUNTERS = (
    "compile_programs",
    *_COMPILE_PHASES.values(),
    "compile_cache_load_s",
    *_CACHE_EVENTS.values(),
)

# one record per scan chunk, appended by the fit thread when the chunk's
# metrics are on the host: 4096 chunks is a quarter of an hour of SASRec at
# the ML-20M catalog. The lock is taken once per chunk (and by readers), never
# per step. The start-up log beside it gets one record per ``fit`` call.
_CHUNK_LOG: Deque[Dict[str, Any]] = collections.deque(maxlen=4096)
_STARTUP_LOG: Deque[Dict[str, Any]] = collections.deque(maxlen=4096)
_CHUNK_LOG_LOCK = threading.Lock()
_FIT_ORDINALS = itertools.count(1)

# the Tracer that stages with no tracer of their own record into (a traced
# fit attaches its tracer for its duration: Compose and the batcher then land
# in trace.json without a constructor argument)
_ATTACHED: Optional["Tracer"] = None
_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is imported
_LISTENING = False  # the jax.monitoring listeners below are registered
# (perf_counter at its top, own seconds) of every package that timed its import
_PACKAGE_IMPORTS: List[Tuple[float, float]] = []


class _ThreadStages(threading.local):
    """Seconds of the stages that ran on this thread: ``loose`` holds those of
    spans that named no chunk since the last :func:`claim_chunk` (the input
    pipeline does not know which chunk it fills), ``record`` is the chunk this
    thread last claimed. ``open`` is the innermost stage the thread is inside,
    ``phases`` the seconds nested in each compile phase jax has begun on it."""

    def __init__(self) -> None:
        self.loose: Dict[str, Any] = {}
        self.record: Optional[Dict[str, Any]] = None
        self.open: Optional["stage"] = None
        self.phases: List[float] = []


_THREAD = _ThreadStages()


def attach_tracer(tracer: Optional["Tracer"]) -> Optional["Tracer"]:
    """Make ``tracer`` the one that :class:`stage` records into when it is
    given none; returns the one attached before (hand it back to restore)."""
    global _ATTACHED
    previous, _ATTACHED = _ATTACHED, tracer
    return previous


def attached_tracer() -> Optional["Tracer"]:
    return _ATTACHED


def _totals_for(args: Mapping[str, Any]) -> Dict[str, Any]:
    """Where a stage with these arguments adds on this thread: the record of
    the chunk the thread claimed if ``args`` name that chunk, else ``loose``."""
    local = _THREAD
    record = local.record
    return record if record is not None and record["chunk"] == args.get("chunk") else local.loose


def _add_stage(name: str, seconds: float, args: Mapping[str, Any]) -> None:
    totals = _totals_for(args)
    totals[name] = totals.get(name, 0.0) + seconds
    key = args.get(name)
    if key is not None:
        by_name = totals.setdefault(name + "_by_name", {})
        by_name[key] = by_name.get(key, 0.0) + seconds
    for counter in _COUNTED_ARGS:
        count = args.get(counter)
        if count is not None:
            counted = f"{name}_{counter}"
            totals[counted] = totals.get(counted, 0) + count


def _count(name: str, amount: float) -> Dict[str, Any]:
    """A compile event adds where the innermost open stage of its thread will
    (a ``transform`` on the feeder: the chunk being filled; a ``dispatch``: the
    chunk being run), and outside any stage where one without a chunk would."""
    inside = _THREAD.open
    totals = _totals_for(inside.args if inside is not None else {})
    totals[name] = totals.get(name, 0) + amount
    return totals


def _on_phase_start(event: str, value: float, **_: Any) -> None:
    if event in _COMPILE_PHASES:
        _THREAD.phases.append(0.0)


def _on_phase_seconds(event: str, seconds: float, **_: Any) -> None:
    name = _COMPILE_PHASES.get(event)
    if name is None:
        if event == _CACHE_LOAD_EVENT:
            _count("compile_cache_load_s", seconds)
        return
    # a phase inside another (a jitted function traced while its caller is, an
    # eager op compiled during a trace) takes its seconds out of the outer
    # one's: trace + lower + backend is time of this thread, counted once
    local = _THREAD
    nested = local.phases.pop() if local.phases else 0.0
    if local.phases:
        local.phases[-1] += seconds
    own = max(seconds - nested, 0.0)
    totals = _count(name, own)
    if name == "compile_backend_s":
        totals["compile_programs"] = totals.get("compile_programs", 0) + 1
    if local.open is not None:
        local.open.compile_seconds += own


def _on_cache_event(event: str, **_: Any) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        _count(name, 1)


def _listen(monitoring: Any) -> None:
    """Register the three listeners with ``jax.monitoring``, once a process:
    module functions that write to the calling thread's totals and hold nothing."""
    global _LISTENING
    with _CHUNK_LOG_LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    monitoring.register_scalar_listener(_on_phase_start)
    monitoring.register_event_duration_secs_listener(_on_phase_seconds)
    monitoring.register_event_listener(_on_cache_event)


def _find_jax() -> Any:
    """``jax.profiler.TraceAnnotation`` once something has imported jax (and
    from then on the listeners are registered), else ``None``."""
    global _ANNOTATION
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    monitoring = getattr(jax, "monitoring", None)
    if profiler is None or monitoring is None:
        return None
    _listen(monitoring)
    _ANNOTATION = profiler.TraceAnnotation
    return _ANNOTATION


def package_imported(package: str, started: float) -> None:
    """The last line of a package's ``__init__`` hands its import to the
    calling thread's stage totals as ``pkg_import`` (``pkg_import_by_name``
    splits it by ``package``): the seconds since ``started``, the
    ``perf_counter`` reading of the ``__init__``'s first line, less those of
    the packages that timed themselves while it ran."""
    own = time.perf_counter() - started
    own -= sum(seconds for began, seconds in _PACKAGE_IMPORTS if began >= started)
    _PACKAGE_IMPORTS.append((started, own))
    _add_stage("pkg_import", own, {"pkg_import": package})
    if _ANNOTATION is None:
        _find_jax()  # what set-up builds before its first stage is counted too


class stage:  # noqa: N801 - used as ``with stage(name):``, like Tracer.span
    """``with stage(name, **args):`` one boundary of the fit path, three things.

    (a) always: a ``jax.profiler.TraceAnnotation(name, **args)``. With no
    profiler session running that is well under a microsecond; when one runs
    the span is in the capture's ``/host:CPU`` plane, one line per thread, on
    the same clock as the device ops, ``args`` as the event's stats (so a span
    argument cannot be called ``name``). Skipped until jax has been imported.
    (b) always: its seconds are added to the calling thread's stage totals,
    which :func:`claim_chunk` and :class:`ChunkStages` fold into the chunk
    stage log (:func:`chunk_stage_log`) and, outside a chunk, into the
    start-up log (:func:`startup_log`).
    (c) with an enabled :class:`Tracer` (``tracer=``, else the attached one):
    the span is recorded there as ``tracer.span(name, **args)`` would; the live
    span is :attr:`span` (for :meth:`Tracer.carve`).

    After exit :attr:`seconds` is the duration and :attr:`end` the
    ``perf_counter`` reading it closed at; :attr:`compile_seconds` is what jax
    spent tracing, lowering and compiling (or fetching) programs on this thread
    while the stage was the innermost open one. A span whose args hold a key of
    its own name (``stage("transform", transform="Mask")``) is also totalled by
    that value under ``<name>_by_name``, and one that counts (the compiled
    programs it dispatched, ``device_programs=n``; the rows a batch assembled in
    the per-row python loop, ``python_rows=n``) is summed under
    ``<name>_device_programs`` / ``<name>_python_rows``.
    """

    __slots__ = (
        "name", "args", "seconds", "end", "span", "compile_seconds",
        "_tracer", "_annotation", "_start", "_outer",
    )

    def __init__(self, name: str, tracer: Optional["Tracer"] = None, **args: Any) -> None:
        self.name = name
        self.args = args
        self.seconds = 0.0
        self.end = 0.0
        self.compile_seconds = 0.0
        self.span: Optional[_Span] = None
        self._tracer = tracer
        self._annotation = None

    def __enter__(self) -> "stage":
        # the clock is the outermost: what the span itself costs (and a wait
        # for the GIL that its own calls into the profiler let happen) is the
        # stage's, so consecutive stages of a thread tile its time
        self._start = time.perf_counter()
        tracer = self._tracer if self._tracer is not None else _ATTACHED
        if tracer is not None and tracer.enabled:
            self.span = tracer.span(self.name, **self.args)
            self.span.__enter__()
        annotation = _ANNOTATION or _find_jax()
        if annotation is not None:
            self._annotation = annotation(self.name, **self.args)
            self._annotation.__enter__()
        local = _THREAD
        self._outer, local.open = local.open, self
        return self

    def __exit__(self, *exc_info) -> None:
        _THREAD.open = self._outer
        if self._annotation is not None:
            self._annotation.__exit__(*exc_info)
        if self.span is not None:
            self.span.__exit__(*exc_info)
        self.end = time.perf_counter()
        self.seconds = self.end - self._start
        _add_stage(self.name, self.seconds, self.args)


def claim_chunk(chunk: int) -> Dict[str, Any]:
    """This thread now works for scan chunk ``chunk`` (the thread that stacks
    the chunk calls this: the feeder, or the fit thread with the feed off).

    Returns the chunk's record of this thread's stage seconds: everything the
    thread's stages took since its previous claim without naming a chunk
    (``batch_build``, ``transform``) is the new chunk's, and from here on
    stages on this thread that carry ``chunk=chunk`` (``stack``, ``h2d``,
    ``feed_full``) add to the same dict. It travels to the fit thread with the
    placed chunk; the fit thread copies it into the log at the chunk's sync."""
    local = _THREAD
    record = local.record = local.loose
    local.loose = {}
    record["chunk"] = chunk
    return record


def claimed_chunk() -> Dict[str, int]:
    """``{"chunk": n}`` for the chunk this thread last claimed, else ``{}``:
    the args of a span that belongs to whatever chunk its thread is on."""
    record = _THREAD.record
    return {} if record is None else {"chunk": record["chunk"]}


def chunk_stage_log() -> List[Dict[str, Any]]:
    """The chunk stage log, oldest record first (copies: read-only).

    One record per scan chunk of every ``fit(scan_chunk=...)`` of this
    process, the newest 4096. A record covers one done-to-done period of the
    fit thread: the ``account`` that followed the PREVIOUS chunk's sync, then
    this chunk's ``data_wait``, ``dispatch`` and ``device_wait``. Fields:
    ``chunk`` (ordinal within the fit), ``fit`` (ordinal of the fit call in
    this process), ``steps``, ``done`` (``perf_counter`` at the sync's end),
    ``period`` (since the previous ``done``; absent on the first chunk of an
    epoch, whose past holds the epoch's end work), ``compiled``, the seconds
    of the fit thread's ``data_wait``, ``dispatch``, ``device_wait``,
    ``account`` and of the feeder's ``stack``, ``h2d``, ``feed_full``,
    ``batch_build``, ``transform`` (total) with ``transform_by_name``,
    ``transform_device_programs`` (compiled programs ``Compose`` dispatched
    for the chunk's batches: 0 while the pipeline stays on the host),
    ``batch_build_python_rows`` (rows of the chunk's batches that the batcher
    assembled in its per-row python loop: 0 while the native gather takes
    every sequence feature),
    ``ce_fused_steps`` (steps of the chunk whose program ran the fused loss head,
    ``nn.loss.ce.full_softmax_route``: ``steps`` or 0),
    ``device_leaves`` (leaves of the chunk's batches that arrived as jax
    Arrays: each is a D2H read inside ``stack``), ``h2d_bytes`` and, for a
    model that counts (``sows_counters``), ``counters``: per name the chunk's
    ``[steps, ...]`` values as nested lists (``expert_load``:
    ``[steps, expert layers, held experts]``).
    And what jax built during the chunk, on the fit thread and on the feeder
    (:data:`COMPILE_COUNTERS`, from ``jax.monitoring``): ``compile_programs``
    (executables compiled or fetched from the persistent cache),
    ``compile_trace_s``, ``compile_lower_s`` and ``compile_backend_s`` (seconds
    tracing to a jaxpr, lowering to MLIR, and in the backend: XLA's compile or
    the cache's load; each second counted once, so the three add up to time of
    their thread), ``compile_cache_load_s`` (the part of ``compile_backend_s``
    that loaded from the cache), ``compile_cache_hits`` and
    ``compile_cache_misses`` (executables fetched; compiled and written to the
    cache: jax writes none without a cache directory or under its least compile
    time and entry size, so the executables XLA compiled are
    ``compile_programs`` less ``compile_cache_hits``, and a chunk or a start was
    warm where the two are equal). A feeder's program is in the record of the
    chunk it was filling. ``compiled`` says that one of the trainer's own
    programs was traced in the ``dispatch``; the counters say how much of that
    ``dispatch`` was which phase.
    Interleaved single steps (health cadence, an epoch's short tail) are in
    the next chunk's ``period`` and in none of its stages; what they built is in
    the next chunk's counters.
    """
    with _CHUNK_LOG_LOCK:
        return [dict(record) for record in _CHUNK_LOG]


def startup_log() -> List[Dict[str, Any]]:
    """The start-up log, oldest record first (copies: read-only).

    One record per ``fit`` call of this process (the newest 4096), written when
    the call begins and closed when its first chunk loop does (a per-step fit
    has none: what it does is in the next record): the seconds of every stage
    that ran on the fit's thread OUTSIDE any chunk since the previous ``fit``'s
    last chunk, by name (set-up: ``pkg_import`` with ``pkg_import_by_name``,
    ``split``, ``tokenize``, ``batcher_init``, ``init_state``; also the previous
    ``fit``'s tail, its last ``account`` and any per-step ``h2d``, and the first
    batch the call pulled itself), what jax built there
    (:data:`COMPILE_COUNTERS`, as in :func:`chunk_stage_log`; a field is absent
    where nothing added to it) and ``fit``, the call's ordinal.
    The last record is the calling thread's so far, with ``fit`` ``None``:
    what ran after the last ``fit``. A process's whole set-up is the sum over
    these records plus the chunks whose ``compiled`` is true."""
    with _CHUNK_LOG_LOCK:
        log = [_fold({}, record) for record in _STARTUP_LOG]
    log.append(_fold({"fit": None}, _THREAD.loose))
    return log


def _total(nested: Any) -> Any:
    return sum(map(_total, nested)) if isinstance(nested, list) else nested


def _fold(into: Dict[str, Any], totals: Mapping[str, Any]) -> Dict[str, Any]:
    """Add a thread's stage totals to ``into`` (the ``_by_name`` groups too)."""
    for name, value in totals.items():
        if isinstance(value, dict):
            _fold(into.setdefault(name, {}), value)
        elif value is not None:
            into[name] = into.get(name, 0) + value
    return into


class ChunkStages:
    """The fit thread's side of the chunk stage log, one per ``fit`` call.

    ``for item in stages.feed(source)`` times every pull as ``data_wait`` and
    closes the ``account`` span left open by the previous chunk;
    ``stages.stage(name)`` is :class:`stage` with this fit's tracer and the
    current chunk's ordinal; :meth:`synced` is called when the chunk's metrics
    are on the host: it opens ``account`` (closed by the next pull or by
    :meth:`close`), appends the chunk's record and moves to the next ordinal.
    """

    def __init__(self, tracer: Optional["Tracer"] = None) -> None:
        self.fit = next(_FIT_ORDINALS)
        self.tracer = tracer
        self.chunk = 0
        self._done: Optional[float] = None
        self._account: Optional[stage] = None
        self._account_seconds = 0.0
        self._wait_seconds = 0.0
        # what this thread's stages left over from earlier work is no chunk's:
        # it is this fit's start-up record
        local = _THREAD
        started, local.loose, local.record = local.loose, {}, None
        started["fit"] = self.fit
        with _CHUNK_LOG_LOCK:
            _STARTUP_LOG.append(started)
        self._started: Optional[Dict[str, Any]] = started

    def stage(self, name: str, **args: Any) -> stage:
        return stage(name, tracer=self.tracer, chunk=self.chunk, **args)

    def close(self) -> None:
        """Close the open ``account`` span, if any (the end of an epoch, or an
        exit from the loop); the next chunk has no ``period``."""
        if self._account is not None:
            self._account.__exit__(None, None, None)
            self._account_seconds += self._account.seconds
            self._account = None

    def new_epoch(self) -> None:
        self.close()
        self._done = None
        self._account_seconds = self._wait_seconds = 0.0
        if self._started is not None:
            # the fit's first chunk loop begins: what the call itself did until
            # here (its state's init, a restore) is start-up as well
            local = _THREAD
            begun, local.loose = local.loose, {}
            with _CHUNK_LOG_LOCK:
                _fold(self._started, begun)
            self._started = None

    def feed(self, source: Iterable[Any]) -> Iterator[Any]:
        iterator = iter(source)
        while True:
            # drop the chunk just run while `account` is still open: freeing
            # its device buffers is bookkeeping, not a hole between two stages
            item = None
            self.close()
            with self.stage("data_wait") as wait:
                try:
                    item = next(iterator)
                except StopIteration:
                    return
            self._wait_seconds += wait.seconds
            yield item

    def synced(
        self,
        steps: int,
        dispatch: stage,
        device_wait: stage,
        compiled: bool,
        feeder: Optional[Mapping[str, Any]] = None,
        counters: Optional[Mapping[str, Any]] = None,
        ce_fused_steps: int = 0,
    ) -> Dict[str, Any]:
        # what the model counted over the chunk (nested lists [K, ...] by name)
        # rides the `account` span as totals and the chunk's record in full
        counters = counters or {}
        self._account = self.stage(
            "account", **{name: _total(value) for name, value in counters.items()}
        )
        self._account.__enter__()
        done = device_wait.end
        feeder = feeder or {}
        record: Dict[str, Any] = {
            "chunk": self.chunk,
            "fit": self.fit,
            "steps": int(steps),
            "done": done,
            "compiled": bool(compiled),
            "data_wait": self._wait_seconds,
            "dispatch": dispatch.seconds,
            "device_wait": device_wait.seconds,
            "account": self._account_seconds,
        }
        if self._done is not None:
            record["period"] = done - self._done
        for name in _FEEDER_FIELDS:
            record[name] = float(feeder.get(name, 0.0))
        record["transform_by_name"] = dict(feeder.get("transform_by_name", ()))
        record["transform_device_programs"] = int(feeder.get("transform_device_programs", 0))
        record["batch_build_python_rows"] = int(feeder.get("batch_build_python_rows", 0))
        record["ce_fused_steps"] = int(ce_fused_steps)
        record["device_leaves"] = int(feeder.get("device_leaves", 0))
        record["h2d_bytes"] = int(feeder.get("h2d_bytes", 0))
        # what jax built for the chunk: on the thread that filled it, and on
        # this one since the previous sync (with the feed off `feeder` is this
        # thread's own claimed record, and `loose` what fell between two
        # stages). The rest of `loose` is the chunk's stages again: dropped
        local = _THREAD
        loose, local.loose = local.loose, {}
        for name in COMPILE_COUNTERS:
            record[name] = feeder.get(name, 0) + loose.get(name, 0)
        if counters:
            record["counters"] = dict(counters)
        with _CHUNK_LOG_LOCK:
            _CHUNK_LOG.append(record)
        self._done = done
        self._account_seconds = self._wait_seconds = 0.0
        self.chunk += 1
        return record


def traced_iterator(
    batches: Iterable[Any], tracer: Tracer, name: str = "data_wait"
) -> Iterator[Any]:
    """Yield from ``batches``, timing every ``next()`` as a ``name`` span.

    This is how the fit loop attributes host input time: the span covers
    exactly the wait for the batcher (prefetch queue pops included), not the
    consumer's work on the yielded batch.
    """
    iterator = iter(batches)
    while True:
        with tracer.span(name):
            try:
                batch = next(iterator)
            except StopIteration:
                return
        yield batch


def lifecycle_span(
    tracer: Tracer, name: str, started_at: float, **args: Any
) -> float:
    """Record a lifecycle phase that began on another thread; returns its
    duration in seconds.

    :func:`traced_iterator`'s cross-thread sibling: a request's ``queue_wait``
    starts when the client thread enqueues it (capture ``tracer.now()`` there)
    and ends when the serve worker dequeues it — no single ``with`` block can
    cover both, so the span is recorded synthetically on the consuming thread
    via :meth:`Tracer.add_span`.
    """
    duration = max(tracer.now() - float(started_at), 0.0)
    tracer.add_span(name, float(started_at), duration, **args)
    return duration


def merge_traces(
    shards: Mapping[str, Any], path: Optional[str] = None
) -> Dict[str, Any]:
    """Merge per-shard Chrome traces into ONE trace with labeled tracks.

    ``shards`` maps a track label ("router", "r0", ...) to a :class:`Tracer`
    or an already-exported Chrome trace dict. Each shard becomes its own
    process track: a distinct ``pid`` plus a ``process_name`` metadata event
    (``ph="M"``) carrying the label, which is how Perfetto titles the track.
    Shards run on independent ``perf_counter`` epochs; their timestamps are
    aligned onto the EARLIEST shard's epoch via each trace's
    ``otherData.trace_epoch_unix`` (the wall clock at tracer construction), so
    a request's router spans and its replica spans line up on one time axis.

    Returns the merged trace dict; when ``path`` is given also writes it
    there (the fleet's single ``trace.json``).
    """
    chrome: Dict[str, Dict[str, Any]] = {}
    for label, shard in shards.items():
        trace = shard.to_chrome_trace() if hasattr(shard, "to_chrome_trace") else shard
        chrome[str(label)] = trace
    epochs = {
        label: float((trace.get("otherData") or {}).get("trace_epoch_unix") or 0.0)
        for label, trace in chrome.items()
    }
    base_epoch = min(epochs.values()) if epochs else 0.0
    merged: List[Dict[str, Any]] = []
    tracks: Dict[str, int] = {}
    for index, (label, trace) in enumerate(chrome.items()):
        pid = index + 1
        tracks[label] = pid
        offset_us = (epochs[label] - base_epoch) * 1e6
        merged.append(
            {
                "name": "process_name",
                "ph": "M",
                "ts": 0,
                "pid": pid,
                "tid": 0,
                "args": {"name": label},
            }
        )
        for event in trace.get("traceEvents", ()):
            if event.get("ph") == "M":
                continue  # shard-local metadata is superseded by the track label
            record = dict(event)
            record["pid"] = pid
            record["ts"] = round(float(event.get("ts", 0.0)) + offset_us, 3)
            merged.append(record)
    merged.sort(key=lambda e: (e.get("ph") != "M", e.get("ts", 0.0)))
    out = {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "otherData": {"trace_epoch_unix": base_epoch, "tracks": tracks},
    }
    if path is not None:
        directory = os.path.dirname(os.path.abspath(path))
        os.makedirs(directory, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(out, fh)
    return out


def _event_trace_ids(event: Mapping[str, Any]) -> Tuple[str, ...]:
    """The request(s) a trace event belongs to: a scalar ``trace_id`` arg for
    per-request spans, a ``trace_ids`` list for batch-level spans shared by
    every co-riding request (each gets the full batch duration — request-
    centric attribution: "MY batch spent X ms scoring")."""
    args = event.get("args")
    if not args:
        return ()
    trace_id = args.get("trace_id")
    if trace_id:
        return (str(trace_id),)
    trace_ids = args.get("trace_ids")
    if trace_ids:
        return tuple(str(t) for t in trace_ids)
    return ()


def tail_attribution(
    trace_events: Iterable[Mapping[str, Any]],
    quantiles: Sequence[float] = (0.5, 0.99),
    root: str = "request",
    hops: Sequence[str] = REQUEST_HOP_SPANS,
) -> Optional[Dict[str, Any]]:
    """Decompose completed requests' latency into per-hop fractions.

    Groups Chrome trace events by ``trace_id``: each root span (``root``,
    recorded by the fleet router over a request's full submit→answer window)
    defines one completed request's latency; every hop span sharing its
    trace_id contributes its duration. Per request the hop fractions are
    clipped to the root window (concurrent hops — a hedge twin racing the
    primary — can overlap; renormalized like :func:`goodput_breakdown`) and
    the residual is ``other``, so each request's fractions sum to 1.0.

    For each quantile ``q`` the attribution is the MEAN hop mix over the
    slowest ``(1 - q)`` share of requests (nearest-rank tail subset): "what do
    the p99 requests spend their time on", not "what does the p99 request
    spend". Returns ``None`` when no root span carries a trace_id (tracing
    was off or nothing completed).
    """
    roots: Dict[str, float] = {}
    hop_seconds: Dict[str, Dict[str, float]] = {}
    hop_set = set(hops)
    for event in trace_events:
        if event.get("ph") == "M":
            continue
        name = event.get("name")
        ids = _event_trace_ids(event)
        if not ids:
            continue
        dur_s = max(float(event.get("dur") or 0.0), 0.0) / 1e6
        if name == root:
            roots[ids[0]] = max(roots.get(ids[0], 0.0), dur_s)
        elif name in hop_set:
            for tid in ids:
                per_hop = hop_seconds.setdefault(tid, {})
                per_hop[name] = per_hop.get(name, 0.0) + dur_s
    if not roots:
        return None
    per_request: List[Tuple[float, Dict[str, float]]] = []
    for trace_id, total in sorted(roots.items(), key=lambda kv: kv[1]):
        fractions: Dict[str, float] = {}
        tracked = 0.0
        per_hop = hop_seconds.get(trace_id, {})
        for name in hops:
            seconds = min(max(per_hop.get(name, 0.0), 0.0), total) if total > 0 else 0.0
            tracked += seconds
            fractions[name] = seconds / total if total > 0 else 0.0
        if total > 0 and tracked > total:
            for name in hops:
                fractions[name] *= total / tracked
            tracked = total
        fractions["other"] = (total - tracked) / total if total > 0 else 1.0
        per_request.append((total, fractions))
    n = len(per_request)
    out: Dict[str, Any] = {
        "requests": n,
        "hops": list(hops) + ["other"],
        "quantiles": {},
    }
    for q in quantiles:
        start = min(int(float(q) * n), n - 1)
        subset = per_request[start:]
        means: Dict[str, float] = {}
        for name in out["hops"]:
            means[name] = sum(f[name] for _, f in subset) / len(subset)
        # exact residual: the averaged mix must still sum to 1.0 bit-for-bit
        means["other"] = max(1.0 - sum(means[name] for name in hops), 0.0)
        key = f"p{int(round(float(q) * 100)):02d}"
        out["quantiles"][key] = {
            "latency_ms": subset[0][0] * 1e3,
            "n": len(subset),
            "fractions": means,
        }
    return out


def goodput_breakdown(
    span_self_seconds: Mapping[str, float],
    wall_seconds: float,
    spans: Iterable[str] = GOODPUT_SPANS,
) -> Dict[str, Any]:
    """Fold an exclusive-time snapshot (diff) into the goodput record.

    Returns ``{"wall_seconds", "fractions", "input_starvation"}`` where
    ``fractions`` maps every ``spans`` phase (default :data:`GOODPUT_SPANS`;
    pass :data:`SERVE_GOODPUT_SPANS` for a serving worker) plus the derived
    ``other`` to its share of ``wall_seconds`` — summing to 1.0 by
    construction — and ``input_starvation`` is the fraction of the stepping
    pipeline (data_wait + batch_build + h2d + compile + train_step) spent on
    the input side (waiting on the iterator + same-thread batch assembly).
    """
    spans = tuple(spans)
    wall = max(float(wall_seconds), 0.0)
    folded = [
        (child, parent)
        for child, parent in _GOODPUT_FOLD.items()
        if child in span_self_seconds and parent in spans and child not in spans
    ]
    if folded:
        span_self_seconds = dict(span_self_seconds)
        for child, parent in folded:
            span_self_seconds[parent] = span_self_seconds.get(parent, 0.0) + max(
                float(span_self_seconds[child]), 0.0
            )
    fractions: Dict[str, float] = {}
    tracked = 0.0
    for name in spans:
        seconds = max(float(span_self_seconds.get(name, 0.0)), 0.0)
        tracked += seconds
        fractions[name] = seconds / wall if wall > 0 else 0.0
    if wall > 0 and tracked > wall:
        # spans from concurrent threads can overlap the window; renormalize so
        # the contract (fractions sum to 1.0) survives
        for name in spans:
            fractions[name] *= wall / tracked
        tracked = wall
    fractions["other"] = (wall - tracked) / wall if wall > 0 else 1.0
    if "train_step" not in spans:
        # a non-training breakdown (e.g. SERVE_GOODPUT_SPANS) has no stepping
        # pipeline to starve — None keeps the metric honest and unrendered
        starvation = None
    else:
        pipeline = sum(
            max(float(span_self_seconds.get(name, 0.0)), 0.0) for name in _STEP_PIPELINE
        )
        input_side = sum(
            max(float(span_self_seconds.get(name, 0.0)), 0.0) for name in _INPUT_SPANS
        )
        starvation = input_side / pipeline if pipeline > 0 else 0.0
    return {
        "wall_seconds": wall,
        "fractions": fractions,
        "input_starvation": starvation,
    }
