"""Mesh-parallel trainer: the Lightning-module/Trainer replacement.

Capability parity with replay/nn/lightning/module.py:14-120 (universal model
wrapper: signature-filtered forward, loss with injected logits callback, optimizer/
scheduler factories from replay/nn/lightning/optimizer.py:26 and scheduler.py:24-45)
and the fit/validate/predict flow of notebook 09 (SURVEY.md §3.2-3.3).

TPU design — one SPMD program instead of DDP:

* A :class:`jax.sharding.Mesh` over all devices with axes
  ``("data", "model", "seq")``. Every placement decision — batch rows on
  ``data``, vocab tables on ``model`` (tensor parallelism for huge catalogs,
  SURVEY.md §2.9 TP row), sequence positions on ``seq`` (Ring Attention
  sequence parallelism for long contexts) — derives from ONE logical-axis rule
  table (:class:`replay_tpu.parallel.sharding.ShardingRules`); XLA inserts the
  all-reduces/permutes over ICI.
* ``train_step`` / ``eval_step`` are jitted once and reused; batches are
  ``device_put`` with a ``NamedSharding`` so computation follows data.
* Static shapes everywhere: final short batches must be padded by the loader
  (see replay_tpu.data.nn.iterator) and flagged with a ``valid`` row mask which
  flows into the loss (zero weight) and the metrics builder.

The trainer is model-agnostic: the forward kwargs are filtered from the batch by
signature introspection (the reference wrapper's trick), so SasRec (feature_tensors,
padding_mask), Bert4Rec (+ token_mask) and TwoTower share one loop.
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
import itertools
import logging
import math
import os
import signal as _signal
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from replay_tpu.metrics.builder import MetricsBuilder
from replay_tpu.nn.loss.ce import PLAIN
from replay_tpu.obs import (
    CompileTracker,
    ConsoleLogger,
    HealthConfig,
    JsonlLogger,
    MemoryMonitor,
    MultiLogger,
    RunLogger,
    StepTelemetry,
    Tracer,
    TrainerEvent,
    goodput_breakdown,
    traced_iterator,
)
from replay_tpu.obs.health import health_metrics
from replay_tpu.obs.trace import (
    ChunkStages,
    attach_tracer,
    attached_tracer,
    claim_chunk,
    stage,
)

logger = logging.getLogger("replay_tpu")

Batch = Dict[str, Any]


def _signature_names(func) -> List[str]:
    if func is None:
        return []
    return [p.name for p in inspect.signature(func).parameters.values() if p.name != "self"]


# --------------------------------------------------------------------------- #
# Optimizer / scheduler factories (replay/nn/lightning/optimizer.py:26,
# scheduler.py:24-45 — same roles, optax-native)
# --------------------------------------------------------------------------- #
@dataclass
class LRSchedulerFactory:
    """Learning-rate schedule factory.

    ``kind="constant"`` | ``"step"`` (decay by ``gamma`` every ``step_size``
    optimizer steps, the StepLR equivalent) | ``"warmup_linear"`` (linear 0→lr
    over ``warmup_steps``, the LambdaLR-warmup equivalent) |
    ``"warmup_cosine"`` (linear warmup then cosine decay to 0 over
    ``total_steps``).
    """

    kind: str = "constant"
    step_size: int = 1000
    gamma: float = 0.5
    warmup_steps: int = 100
    total_steps: int = 10_000

    def create(self, learning_rate: float) -> optax.Schedule:
        if self.kind == "constant":
            return optax.constant_schedule(learning_rate)
        if self.kind == "step":
            return optax.exponential_decay(
                learning_rate,
                transition_steps=self.step_size,
                decay_rate=self.gamma,
                staircase=True,
            )
        if self.kind == "warmup_linear":
            return optax.linear_schedule(0.0, learning_rate, transition_steps=self.warmup_steps)
        if self.kind == "warmup_cosine":
            return optax.warmup_cosine_decay_schedule(
                0.0, learning_rate, self.warmup_steps, self.total_steps
            )
        msg = f"Unknown scheduler kind: {self.kind}"
        raise ValueError(msg)


@dataclass
class OptimizerFactory:
    """Optimizer factory: ``adam`` | ``adamw`` | ``sgd`` (+ optional momentum),
    with gradient clipping and a pluggable LR schedule."""

    name: str = "adam"
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    betas: Tuple[float, float] = (0.9, 0.999)
    momentum: float = 0.0
    clip_grad_norm: Optional[float] = None
    scheduler: Optional[LRSchedulerFactory] = None

    def create(self) -> optax.GradientTransformation:
        lr = self.scheduler.create(self.learning_rate) if self.scheduler else self.learning_rate
        if self.name == "adam":
            core = optax.adam(lr, b1=self.betas[0], b2=self.betas[1])
            if self.weight_decay:
                core = optax.chain(optax.add_decayed_weights(self.weight_decay), core)
        elif self.name == "adamw":
            core = optax.adamw(
                lr, b1=self.betas[0], b2=self.betas[1], weight_decay=self.weight_decay
            )
        elif self.name == "sgd":
            core = optax.sgd(lr, momentum=self.momentum or None)
            if self.weight_decay:
                core = optax.chain(optax.add_decayed_weights(self.weight_decay), core)
        else:
            msg = f"Unknown optimizer: {self.name}"
            raise ValueError(msg)
        if self.clip_grad_norm:
            return optax.chain(optax.clip_by_global_norm(self.clip_grad_norm), core)
        return core


# --------------------------------------------------------------------------- #
# TrainState
# --------------------------------------------------------------------------- #
class TrainState(struct.PyTreeNode):
    """Pure pytree of everything a train step mutates.

    ``bad_steps`` counts optimizer updates the non-finite sentinel discarded
    (NaN/Inf loss or gradient norm): on such steps ``step`` and ``rng`` still
    advance — keeping step ids aligned with the batch stream across resumes —
    but ``params``/``opt_state`` keep their previous values.
    """

    step: jnp.ndarray
    params: Any
    opt_state: Any
    rng: jnp.ndarray
    bad_steps: jnp.ndarray


# --------------------------------------------------------------------------- #
# Resilience: recovery policy + preemption handling (docs/robustness.md)
# --------------------------------------------------------------------------- #
@dataclass
class RecoveryPolicy:
    """When and how ``Trainer.fit`` rolls back a diverging run.

    Two triggers share one response (restore the last checkpoint — which is
    always finite, because the sentinel never lets a non-finite update into the
    state — and back the learning rate off by ``lr_backoff``):

    * ``max_consecutive_bad`` sentinel-skipped steps in a row;
    * a monitored-metric blowup at epoch end: the monitored value went
      non-finite, or worsened past ``blowup_factor`` × the best seen (``mode=
      "min"``: value > best × factor; ``mode="max"``: value < best / factor).
      ``blowup_factor=None`` keeps only the non-finite check.

    ``max_restarts`` bounds the total rollbacks for the fit call; exhausting it
    raises ``RuntimeError`` instead of burning the remaining budget. Rollback
    restores weights/optimizer state only — the batch stream keeps moving
    forward, so the poisoned data window is not replayed.
    """

    max_consecutive_bad: int = 5
    max_restarts: int = 3
    lr_backoff: float = 0.5
    blowup_factor: Optional[float] = None

    def __post_init__(self) -> None:
        if self.max_consecutive_bad < 1:
            msg = "max_consecutive_bad must be >= 1"
            raise ValueError(msg)
        if self.max_restarts < 0:
            msg = "max_restarts must be >= 0"
            raise ValueError(msg)
        if not 0.0 < self.lr_backoff <= 1.0:
            msg = "lr_backoff must be in (0, 1]"
            raise ValueError(msg)
        if self.blowup_factor is not None and self.blowup_factor <= 1.0:
            msg = "blowup_factor must be > 1"
            raise ValueError(msg)


class PreemptionHandler:
    """SIGTERM/SIGINT → request a checkpoint at the next step boundary.

    ``fit`` installs one around its training loop (when a checkpoint manager is
    attached): the first signal only sets a flag, the loop saves a
    position-stamped mid-epoch checkpoint at the current step boundary and
    returns cleanly, and ``fit(resume=True)`` continues from that exact batch.
    A second signal falls through to the previously-installed handler, so a
    double Ctrl-C still force-exits. Off the main thread ``signal.signal``
    is unavailable — installation degrades to a no-op and the flag can only be
    set by test harnesses calling :meth:`request` directly.
    """

    def __init__(self, signals: Sequence[int] = (_signal.SIGTERM, _signal.SIGINT)) -> None:
        self.signals = tuple(signals)
        self.requested = False
        self.signal_name: Optional[str] = None
        self._previous: Dict[int, Any] = {}
        self._installed = False

    def request(self, signum: Optional[int] = None) -> None:
        self.requested = True
        if signum is not None:
            self.signal_name = _signal.Signals(signum).name

    def _handle(self, signum, frame) -> None:
        if self.requested:  # second signal: defer to the original behavior
            previous = self._previous.get(signum)
            if callable(previous):
                previous(signum, frame)
                return
            raise KeyboardInterrupt
        logger.warning(
            "received %s: checkpointing at the next step boundary, then exiting",
            _signal.Signals(signum).name,
        )
        self.request(signum)

    def __enter__(self) -> "PreemptionHandler":
        try:
            for sig in self.signals:
                self._previous[sig] = _signal.signal(sig, self._handle)
            self._installed = True
        except ValueError:  # not the main thread: restore what was installed
            for sig, previous in self._previous.items():
                _signal.signal(sig, previous)
            self._previous.clear()
            self._installed = False
        return self

    def __exit__(self, *exc_info) -> None:
        if self._installed:
            for sig, previous in self._previous.items():
                _signal.signal(sig, previous)
            self._previous.clear()
            self._installed = False


# --------------------------------------------------------------------------- #
# Mesh helpers
# --------------------------------------------------------------------------- #
def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    model_parallel: int = 1,
    seq_parallel: int = 1,
    data_parallel: Optional[int] = None,
) -> Mesh:
    """All (or given) devices arranged as a ``("data", "model", "seq")`` mesh.

    ``model_parallel`` chips shard the vocab/model axis (the CEFusedTP table
    layout), ``seq_parallel`` chips form the Ring Attention sequence axis, and
    the rest are data parallel (``data_parallel`` pins the DP extent
    explicitly; by default it absorbs every remaining chip). On a v5e-8 slice
    the defaults give pure DP over ICI; the trivial size-1 axes cost nothing —
    every ``PartitionSpec`` that does not name them behaves exactly as on the
    old 2-axis mesh.
    """
    devices = list(devices) if devices is not None else jax.devices()
    if model_parallel < 1 or seq_parallel < 1:
        msg = (
            f"model_parallel={model_parallel} and seq_parallel={seq_parallel} "
            "must be >= 1"
        )
        raise ValueError(msg)
    if len(devices) % (model_parallel * seq_parallel):
        msg = (
            f"{len(devices)} devices not divisible by model_parallel="
            f"{model_parallel} x seq_parallel={seq_parallel}"
        )
        raise ValueError(msg)
    inferred = len(devices) // (model_parallel * seq_parallel)
    if data_parallel is None:
        data_parallel = inferred
    elif data_parallel != inferred:
        msg = (
            f"data_parallel={data_parallel} inconsistent with {len(devices)} "
            f"devices / (model_parallel={model_parallel} x "
            f"seq_parallel={seq_parallel}) = {inferred}"
        )
        raise ValueError(msg)
    grid = np.array(devices).reshape(data_parallel, model_parallel, seq_parallel)
    return Mesh(grid, ("data", "model", "seq"))


def _batch_sharding(
    mesh: Mesh, rules: Any = None, batch_dim_field: str = "padding_mask"
) -> Callable[[Any], Any]:
    """Place a batch pytree from the rule table: rows over the ``batch`` rule's
    mesh axis, sequence positions over the ``length`` rule's.

    Which leaves are data-parallel is decided by the batch dimension itself: a
    leaf whose leading axis equals ``batch[batch_dim_field]``'s is a per-row
    tensor and shards over the batch axis; anything else (e.g. a shared ``[N]``
    negative-id pool) is replicated. A per-row leaf whose SECOND axis equals the
    reference's sequence length additionally shards it over the ``length`` axis
    (the SP input layout — ``[B, L]`` features arrive ``[B/dp, L/sp]`` per
    chip). Multi-host, sharded leaves are assembled with
    ``jax.make_array_from_process_local_data`` — each process contributes
    ITS disjoint slice (the Partitioning seam's contract) and the global batch
    is local × process_count; replicated leaves must be identical on every host.
    """
    from replay_tpu.parallel.sharding import ShardingRules

    if rules is None:
        rules = ShardingRules.default()
    multiprocess = jax.process_count() > 1
    scale = jax.process_count() if multiprocess else 1
    batch_axis = rules.mesh_axis("batch")
    length_axis = rules.mesh_axis("length")
    batch_size_div = rules.axis_size(mesh, "batch")
    length_div = rules.axis_size(mesh, "length")

    def put(batch):
        reference = batch.get(batch_dim_field)
        local_batch = np.asarray(reference).shape[0] if reference is not None else None
        seq_len = (
            np.asarray(reference).shape[1]
            if reference is not None and np.asarray(reference).ndim >= 2
            else None
        )

        def place(x):
            x = np.asarray(x)
            is_batch_leaf = (
                x.ndim >= 1
                and local_batch is not None
                and x.shape[0] == local_batch
                and (local_batch * scale) % max(batch_size_div, 1) == 0
            )
            if is_batch_leaf:
                axes = [batch_axis] + [None] * (x.ndim - 1)
                if (
                    length_axis is not None
                    and length_div > 1
                    and x.ndim >= 2
                    and seq_len is not None
                    and x.shape[1] == seq_len
                    and seq_len % length_div == 0
                ):
                    axes[1] = length_axis
                sharding = NamedSharding(mesh, P(*axes))
            else:
                sharding = NamedSharding(mesh, P())
            if multiprocess:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)

        return jax.tree.map(place, batch)

    return put


def _place_tree(tree: Any, shardings: Any) -> Any:
    """Place host arrays under their shardings — multi-host aware: with several
    processes, every leaf becomes a GLOBAL array assembled from identical
    process-local data (params/state are replicated; all hosts compute the same
    values from the same seed)."""
    if jax.process_count() > 1:

        def place(x, s):
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                # already a global array (e.g. a multi-host orbax restore that
                # targeted these same shardings) — cannot be host-fetched, and
                # needs no re-placement when the sharding already matches
                return x if x.sharding == s else jax.device_put(x, s)
            return jax.make_array_from_process_local_data(s, np.asarray(x))

        return jax.tree.map(place, tree, shardings)
    return jax.tree.map(jax.device_put, tree, shardings)


def _local_rows(array: jnp.ndarray) -> np.ndarray:
    """This process's rows of a batch-dim global array (identity in
    single-process runs, where every array is fully addressable).

    The output sharding of an eagerly-applied op (e.g. ``lax.top_k`` on the
    jitted eval logits) is XLA's choice, not ours: it may keep the row
    sharding OR replicate. Shards are therefore deduplicated by their global
    row offset (replicated layouts repeat the same rows on every device), and
    a fully-replicated result is cut back to the contiguous row range this
    process contributed (``make_array_from_process_local_data`` lays the
    global batch out in process order)."""
    if jax.process_count() == 1 or getattr(array, "is_fully_addressable", True):
        return np.asarray(array)
    by_offset: Dict[int, Any] = {}
    for shard in array.addressable_shards:
        by_offset.setdefault(shard.index[0].start or 0, shard)
    rows = np.concatenate(
        [np.asarray(by_offset[start].data) for start in sorted(by_offset)], axis=0
    )
    per_process = array.shape[0] // jax.process_count()
    if rows.shape[0] == array.shape[0]:
        # replicated output: every process sees the whole batch — keep only
        # the rows this process fed in (local x process_count == global)
        start = jax.process_index() * per_process
        rows = rows[start : start + per_process]
    if rows.shape[0] != per_process:
        # a partially-replicated layout XLA might invent would silently
        # duplicate/drop users in the metric accumulation — fail loudly
        msg = (
            f"_local_rows: addressable shards of a [{array.shape[0]}, ...] array "
            f"with sharding {array.sharding} cover {rows.shape[0]} distinct rows; "
            f"expected this process's {per_process} — unsupported output layout"
        )
        raise ValueError(msg)
    return rows


def _fold_counters(collection: Any) -> Dict[str, Any]:
    """A sown ``counters`` collection as ``{name: array}``: the leaves of every
    module that sowed ``name``, in module order, stacked on a leading axis."""
    by_name: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(collection)[0]:
        names = [key.key for key in path if isinstance(key, jax.tree_util.DictKey)]
        by_name.setdefault(names[-1], []).append(leaf)
    return {name: jnp.stack(leaves) for name, leaves in by_name.items()}


def _globalize_scalars(mesh: Mesh, tree: Any) -> Any:
    """Promote leaves created outside any mesh (e.g. adam's ``count`` scalar
    from ``tx.init``) to replicated arrays ON the mesh — global arrays when
    multi-host; leaves that already carry a mesh sharding pass through."""
    replicated = NamedSharding(mesh, P())

    def globalize(x):
        if hasattr(x, "sharding") and getattr(x.sharding, "mesh", None) is not None:
            return x
        return jax.make_array_from_process_local_data(replicated, np.asarray(x))

    return jax.tree.map(globalize, tree)


# param placement is rule-table-driven: replay_tpu.parallel.sharding owns the
# logical-axis annotations and the logical-name -> mesh-axis table (the old
# "embedding_" path heuristic lived here; params_shardings replaced it)


def _resolve_remat_policy(policy: Any):
    """``Trainer(remat_policy=...)`` spellings → a jax.checkpoint policy
    callable (or None = save nothing, i.e. full rematerialization)."""
    if policy is True or policy == "full":
        return None  # jax.checkpoint default: recompute everything
    if isinstance(policy, str):
        names = {
            "dots": "checkpoint_dots",
            "dots_no_batch": "checkpoint_dots_with_no_batch_dims",
        }
        if policy not in names:
            msg = (
                f"unknown remat_policy {policy!r}; use 'full', 'dots', "
                "'dots_no_batch', or a jax.checkpoint_policies callable"
            )
            raise ValueError(msg)
        return getattr(jax.checkpoint_policies, names[policy])
    if callable(policy):
        return policy
    msg = f"remat_policy must be a string, True, or callable; got {policy!r}"
    raise ValueError(msg)


def _chunk_schedule(
    batches: Iterable[Batch],
    chunk: int,
    health_every: Optional[int] = None,
    start: int = 0,
):
    """Group an executable batch stream into scan chunks and single steps.

    Yields ``("scan", [batch] * chunk)`` for full groups and
    ``("step", batch)`` otherwise. A step whose 1-based executed position
    (counted from ``start``, i.e. the fit's ``measured_total``) lands on a
    ``health_every`` cadence boundary is emitted singly — it must run through
    the health-instrumented per-step program, not the health-free scan — as
    are the (< chunk) leftovers before such a boundary and the epoch's short
    tail. Order is always the stream order; only the dispatch granularity
    changes. With ``health_every ≡ 1 (mod chunk)`` every inter-health gap
    packs into full chunks (docs/performance.md "Closing the dispatch gap").
    """
    buffered: List[Batch] = []
    position = start

    def flush():
        # leftovers shorter than a full chunk run per-step: ONE compiled scan
        # length + the per-step program, never a zoo of chunk-length variants
        for leftover in buffered:
            yield ("step", leftover)
        buffered.clear()

    for batch in batches:
        position += 1
        if health_every and position % health_every == 0:
            yield from flush()
            yield ("step", batch)
            continue
        buffered.append(batch)
        if len(buffered) == chunk:
            yield ("scan", list(buffered))
            buffered.clear()
    yield from flush()


# --------------------------------------------------------------------------- #
# Trainer
# --------------------------------------------------------------------------- #
@dataclass
class Trainer:
    """Fit / validate / predict driver around a flax model + loss.

    :param model: flax module with ``__call__`` (training forward → hidden
        states), ``get_logits(hidden, candidates)`` and ``forward_inference``.
    :param loss: a replay_tpu.nn.loss callable; its ``logits_callback`` is bound
        per step to the model's ``get_logits``.
    :param optimizer: optimizer factory (default Adam 1e-3).
    :param mesh: device mesh; default = all devices, pure data parallel.
    :param shard_vocab: shard embedding tables over the ``model`` mesh axis
        (shorthand for the default rule table's ``vocab -> "model"`` row).
    :param sharding_rules: a :class:`~replay_tpu.parallel.sharding.ShardingRules`
        table mapping logical axis names (``"batch"``, ``"length"``,
        ``"vocab"``, ...) to mesh axes. Defaults to
        ``ShardingRules.default(shard_vocab=...)`` — batch rows over ``data``,
        sequence positions over ``seq``, vocab tables over ``model`` when
        ``shard_vocab``. EVERY placement (params, optimizer state, batches,
        activation constraints, the CEFusedTP table layout) derives from this
        one table (docs/distributed_and_serving.md "One rule table").
    :param remat_policy: activation checkpointing for the encoder stack (one
        checkpoint a block; a looped HybridRec's a block application):
        ``None`` (off) / ``"full"`` (save nothing across blocks) / ``"dots"``
        (save MXU outputs only) / ``"dots_no_batch"`` / a
        ``jax.checkpoint_policies`` callable. The model is cloned with
        ``remat=True`` and the policy plumbed into its ``nn.remat``-wrapped
        blocks — the HBM-for-FLOPs trade
        (docs/performance.md "Remat: trading FLOPs for HBM").
    :param precision: mixed-precision rung (``"bf16"`` / ``"f32"`` /
        :class:`~replay_tpu.nn.Precision`): bf16 activations+compute with f32
        master params, optimizer state and loss accumulation — loss-scale-free
        on TPU, parity-gated against f32 (docs/performance.md "The precision
        ladder"). ``None`` (default) changes nothing.
    :param label_field / mask fields: batch keys produced by the transform
        templates (replay_tpu.nn.transform.template).
    """

    model: Any
    loss: Any
    optimizer: OptimizerFactory = field(default_factory=OptimizerFactory)
    mesh: Optional[Mesh] = None
    shard_vocab: bool = False
    # the ONE logical-axis rule table (parallel.sharding); None = the default
    # DP×TP×SP table derived from shard_vocab
    sharding_rules: Optional[Any] = None
    # activation checkpointing over the transformer blocks: None | "full" |
    # "dots" | "dots_no_batch" | a jax.checkpoint_policies callable
    remat_policy: Optional[Any] = None
    seed: int = 0
    feature_field: str = "feature_tensors"
    padding_mask_field: str = "padding_mask"
    label_field: str = "positive_labels"
    target_mask_field: str = "target_padding_mask"
    negative_field: str = "negative_labels"
    # every jitted path registers here: compile_tracker.report() shows traces
    # (== compiled programs; 1 per fn under the static-shapes invariant) and
    # compile wall-time, surfaced by fit's on_fit_end event
    compile_tracker: CompileTracker = field(default_factory=CompileTracker)
    # host-side span tracer (obs.trace): an ENABLED Tracer here (or passed to
    # fit as tracer=...) records data_wait/h2d/compile/train_step/validation/
    # checkpoint/recovery spans (and the chunked path's stage spans), a
    # trace.json Chrome trace and per-epoch goodput breakdowns; None = no
    # Tracer record: the stage spans still feed any profiler capture and the
    # chunk stage log (obs.trace.chunk_stage_log)
    tracer: Optional[Tracer] = None
    # in-graph model-health diagnostics (obs.health): a HealthConfig here
    # extends the jitted train step with per-group grad/param/update norms,
    # update ratios, activation stats, attention entropy, logits stats and
    # embedding coverage — all device-resident, fetched every `cadence` steps
    # by fit and emitted as a `health` payload (docs/performance.md "Model
    # health"). None = the step lowers exactly as before (no extra HLO).
    health: Optional[HealthConfig] = None
    # mixed-precision policy (docs/performance.md "The precision ladder"):
    # "bf16" / "f32" / a replay_tpu.nn.Precision. Applied at construction —
    # the model is cloned with its flax compute `dtype` set to the rung's
    # compute dtype (bf16 activations/compute; MASTER params and optimizer
    # state stay f32 via flax's param_dtype default) and loss-consumed logits
    # are up-cast to the rung's f32 accumulation dtype. None = untouched:
    # every program lowers byte-identical to the pre-precision trainer.
    precision: Optional[Any] = None

    def __post_init__(self) -> None:
        if isinstance(self.loss, str):
            from replay_tpu.nn import loss as loss_zoo

            # only losses constructible with no arguments qualify as shorthands;
            # parametrized ones (SCE, LogInCE, LogOutCE, sampled variants) need
            # an explicit instance
            by_name = {name.lower(): getattr(loss_zoo, name) for name in ("CE", "BCE")}
            if self.loss.lower() not in by_name:
                msg = (
                    f"Unknown loss shorthand {self.loss!r}; use one of "
                    f"{sorted(by_name)}, or pass a replay_tpu.nn.loss instance "
                    "(losses with required parameters, e.g. SCE/LogInCE/LogOutCE, "
                    "must be instantiated by the caller)"
                )
                raise ValueError(msg)
            self.loss = by_name[self.loss.lower()]()
        from replay_tpu.nn.precision import Precision

        self.precision = Precision.resolve(self.precision)
        if self.precision is not None:
            # bf16 rung: the model computes in bf16 through its flax dtype
            # field while params (and therefore optimizer state, gradients and
            # the sentinel arithmetic) stay f32 — loss-scale-free on TPU
            self.model = self.precision.apply_to_model(self.model)
        if self.remat_policy is not None:
            # activation-checkpointed blocks: clone the model with remat on
            # and the policy plumbed to its nn.remat-wrapped encoder stack
            if not hasattr(self.model, "remat"):
                msg = (
                    f"remat_policy={self.remat_policy!r} needs a model with a "
                    f"remat field (SasRec, Bert4Rec, HybridRec); "
                    f"{type(self.model).__name__} has none"
                )
                raise ValueError(msg)
            policy = _resolve_remat_policy(self.remat_policy)
            self.model = self.model.clone(remat=True, remat_policy=policy)
        if self.mesh is None:
            self.mesh = make_mesh()
        from replay_tpu.parallel.sharding import ShardingRules

        if self.sharding_rules is None:
            rules = ShardingRules.default(shard_vocab=self.shard_vocab)
            # hand-built legacy meshes may lack an axis the default table
            # names (e.g. a bare ("data", "model") mesh has no "seq"): the
            # DEFAULT table degrades those rules to replicated; an EXPLICIT
            # table still validates strictly
            mesh_axes = set(dict(self.mesh.shape))
            for logical, target in list(rules.rules.items()):
                targets = target if isinstance(target, tuple) else (target,)
                if any(axis is not None and axis not in mesh_axes for axis in targets):
                    rules = rules.with_rule(logical, None)
            self.sharding_rules = rules
        self.sharding_rules.validate(self.mesh)
        if (
            self.sharding_rules.axis_size(self.mesh, "length") > 1
            and getattr(self.model, "use_flash", None) != "ring"
        ):
            # sequence parallelism without the ring route would make XLA
            # all-gather the full sequence for every [B, 1, L, L] attention —
            # exactly the collective the SP path exists to avoid
            msg = (
                "sharding rule 'length' maps to a "
                f"{self.sharding_rules.axis_size(self.mesh, 'length')}-way mesh "
                "axis, but the model does not route attention through ring "
                "attention. Construct it with use_flash='ring' "
                "(SasRec/Bert4Rec), or drop seq_parallel from the mesh."
            )
            raise ValueError(msg)
        self._tx = self.optimizer.create()
        self._put_batch = _batch_sharding(
            self.mesh, self.sharding_rules, self.padding_mask_field
        )
        self._train_step = None
        self._train_scan = None
        # {name: (jitted_fn, abstract arg templates)} — ShapeDtypeStruct
        # snapshots (shape/dtype/sharding, no buffers) of every dispatched
        # program's arguments, recorded once at first dispatch so the static
        # analyses (obs.roofline) can re-lower the EXACT
        # programs later without holding donated state alive
        self._programs: Dict[str, Tuple[Any, Tuple[Any, ...]]] = {}
        self._eval_logits = None
        self._query_embeddings_fn = None
        self._catalog_fn = None
        self.last_step_metrics: Optional[Dict[str, Any]] = None
        # the most recent host-fetched health record (python scalars/lists),
        # refreshed by fit every health.cadence steps
        self.last_health: Optional[Dict[str, Any]] = None
        # live metrics plane (fit(metrics_port=...) / fit(slo_rules=...)):
        # the registry outlives the fit for post-run inspection; the exporter
        # handle exposes the bound port while the fit is live
        self.metrics_registry = None
        self.metrics_exporter = None
        self._lr_scale = 1.0  # RecoveryPolicy backoff multiplier (1.0 = none)
        self._forward_params = _signature_names(type(self.model).__call__)
        self._inference_params = (
            _signature_names(type(self.model).forward_inference)
            if hasattr(type(self.model), "forward_inference")
            else self._forward_params
        )
        # extra batch-supplied kwargs for get_logits (e.g. TwoTower's
        # item_feature_tensors catalog arrays)
        self._logits_extra_params = [
            name
            for name in _signature_names(getattr(type(self.model), "get_logits", None))
            if name not in ("hidden", "candidates_to_score")
        ]
        self.history: List[Dict[str, float]] = []

    # -- state ------------------------------------------------------------- #
    def init_state(self, example_batch: Batch, params: Optional[Any] = None) -> TrainState:
        """Initialize parameters (replicated / vocab-sharded over the mesh).

        ``params`` seeds the state with EXISTING weights instead of a fresh
        init — fresh optimizer moments, step 0. The post-vocabulary-surgery
        path (replay_tpu.nn.vocab): the reference rebuilds its optimizer the
        same way after ``set_item_embeddings_*``.
        """
        from replay_tpu.parallel.sharding import params_shardings

        # one stage: the start-up log (obs.trace.startup_log) says what of a
        # set-up was the state's init, and what jax built for it
        with stage("init_state"):
            state_rng = jax.random.split(jax.random.PRNGKey(self.seed))[1]
            if params is None:
                params = self._init_params(example_batch)
            shardings = params_shardings(self.mesh, params, self.sharding_rules)
            params = _place_tree(jax.tree.map(np.asarray, params), shardings)
            # every leaf is placed on the mesh, scalars included: a leaf created
            # outside it carries no mesh in its type, the step's outputs do, and the
            # second dispatch of each program would retrace and compile again
            opt_state = _globalize_scalars(self.mesh, self._tx.init(params))
            replicated = NamedSharding(self.mesh, P())
            step, rng, bad_steps = (
                jax.make_array_from_process_local_data(replicated, np.asarray(v))
                for v in (jnp.zeros((), jnp.int32), state_rng, jnp.zeros((), jnp.int32))
            )
            return TrainState(
                step=step, params=params, opt_state=opt_state, rng=rng, bad_steps=bad_steps
            )

    def _init_params(self, example_batch: Batch) -> Any:
        """A fresh flax init of the model's parameters from :attr:`seed` (pure:
        the tests run it under ``jax.jit`` as one program)."""
        init_rng = jax.random.split(jax.random.PRNGKey(self.seed))[0]
        kwargs = self._forward_kwargs(example_batch)
        logits_extra = {
            name: example_batch[name] for name in self._logits_extra_params if name in example_batch
        }

        def init_fn(module):
            # touch EVERY parameter path: the training forward plus the scoring
            # head (which owns e.g. TwoTower's item tower)
            hidden = module(**kwargs)
            if hasattr(module, "get_logits"):
                module.get_logits(hidden, None, **logits_extra)
            return hidden

        from replay_tpu.parallel.sharding import sharding_scope

        with sharding_scope(self.sharding_rules, self.mesh):
            return self.model.init(
                {"params": init_rng, "dropout": init_rng}, method=init_fn
            )["params"]

    def _forward_kwargs(self, batch: Batch, **overrides) -> Dict[str, Any]:
        """Filter the batch down to the model's forward signature (the reference
        wrapper's introspection trick, replay/nn/lightning/module.py:59)."""
        pool = {**batch, **overrides}
        return {name: pool[name] for name in self._forward_params if name in pool}

    def _scoped(self, fn):
        """``fn`` traced under the rule-table sharding scope: model bodies'
        ``shard_activation`` constraints resolve against THIS trainer's
        (rules, mesh), and the ring-attention route reads its mesh + seq axis
        from the same scope. The context is entered at trace time (inside
        jit), so the python-level scope costs nothing at run time."""
        from replay_tpu.parallel.sharding import sharding_scope

        rules, mesh = self.sharding_rules, self.mesh

        def scoped(*args, **kwargs):
            with sharding_scope(rules, mesh):
                return fn(*args, **kwargs)

        return scoped

    # -- program introspection (obs.roofline) ------------------------------ #
    def _record_template(self, name: str, jitted_fn, *args) -> None:
        """Snapshot a dispatched program's argument shapes/dtypes/shardings
        (once per name; no device buffers are retained)."""
        if name in self._programs:
            return

        def absify(x):
            # pin only MESH shardings: uncommitted single-device leaves (state
            # scalars created off-mesh) must stay free for jit to place, as
            # they are at real dispatch — pinning their SingleDeviceSharding
            # would conflict with the mesh-sharded params
            sharding = getattr(x, "sharding", None)
            if getattr(sharding, "mesh", None) is None:
                sharding = None
            return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=sharding)

        self._programs[name] = (jitted_fn, tuple(jax.tree.map(absify, a) for a in args))

    def lowered_hlo(self, name: str) -> str:
        """The optimized HLO text of a dispatched program (``"train_step"`` /
        ``"train_scan"``), re-lowered from its recorded templates — the input
        to the collective inventory and the no-table-gather guard."""
        if name not in self._programs:
            msg = f"no program {name!r} dispatched yet; known: {sorted(self._programs)}"
            raise KeyError(msg)
        jitted, templates = self._programs[name]
        return jitted.lower(*templates).compile().as_text()

    def analyze_programs(
        self, extra_flops: Optional[Mapping[str, float]] = None
    ) -> Dict[str, Any]:
        """Static roofline/memory/collective record per dispatched program
        (:func:`replay_tpu.obs.roofline.analyze_program`): memory- vs
        compute-bound with the predicted ceiling, the static HBM footprint
        and the collective byte inventory. ``extra_flops`` maps program name
        → analytic FLOPs the cost model cannot see (pallas heads)."""
        from replay_tpu.obs.roofline import analyze_program

        mesh_shape = {axis: int(n) for axis, n in self.mesh.shape.items()}
        out: Dict[str, Any] = {}
        for name, (jitted, templates) in self._programs.items():
            record = analyze_program(
                jitted,
                *templates,
                mesh_shape=mesh_shape,
                extra_flops=(extra_flops or {}).get(name, 0.0),
            )
            if record is not None:
                out[name] = record
        return out

    # -- train ------------------------------------------------------------- #
    def _build_train_step(self, health: Optional[HealthConfig] = None):
        model, loss, tx = self.model, self.loss, self._tx
        precision = self.precision
        if getattr(loss, "needs_item_embeddings", False) and not hasattr(
            type(model), "get_item_weights"
        ):
            msg = (
                f"{type(loss).__name__} needs the raw item table but "
                f"{type(model).__name__} defines no get_item_weights() method."
            )
            raise ValueError(msg)
        if getattr(loss, "requires_tying_head", False) and not getattr(
            model, "logits_via_item_weights", False
        ):
            msg = (
                f"{type(loss).__name__} reconstructs logits as "
                "hidden . get_item_weights()^T, which only matches get_logits for "
                "bias-free tying-head models (declared via "
                f"logits_via_item_weights=True); {type(model).__name__} makes no "
                "such declaration."
            )
            raise ValueError(msg)
        # a loss that CHOOSES its route (CE: nn.loss.ce.full_softmax_route) is bound
        # the table wherever the model declares the bias-free tying head, and
        # refuses nothing: without the declaration it keeps the plain route
        tying_head = getattr(model, "logits_via_item_weights", False) and hasattr(
            type(model), "get_item_weights"
        )
        binds_table = getattr(loss, "needs_item_embeddings", False) or (
            tying_head and hasattr(loss, "item_embeddings_callback")
        )
        if not binds_table and hasattr(loss, "item_embeddings_callback"):
            loss.item_embeddings_callback = None  # no table of another trainer's model
        if getattr(loss, "needs_mesh", False) or (binds_table and hasattr(loss, "mesh")):
            # losses that run the fused head under the mesh (CE where it chooses
            # to, CEFusedTP) shard_map over the trainer mesh
            # with their axes taken from the ONE rule table: the catalog over
            # the "vocab" rule, the flattened [B·L, E] rows over the batch
            # (× length, under SP) axes — the loss carries no layout of its own
            loss.mesh = self.mesh
            rules = self.sharding_rules
            if hasattr(loss, "axis_name"):
                vocab_axis = rules.mesh_axis("vocab")
                if vocab_axis is not None:
                    loss.axis_name = vocab_axis
            if hasattr(loss, "data_axis"):
                row_axes = tuple(
                    axis
                    for logical in ("batch", "length")
                    for axis in [rules.mesh_axis(logical)]
                    if axis is not None and rules.axis_size(self.mesh, logical) > 1
                )
                if row_axes:
                    loss.data_axis = row_axes if len(row_axes) > 1 else row_axes[0]
        label_f, tmask_f, neg_f = self.label_field, self.target_mask_field, self.negative_field
        pad_f = self.padding_mask_field
        # python-static, like `health`: which collections the forward hands back
        counts = bool(getattr(model, "sows_counters", False))
        # a looped model's per-step hidden states and exit-gate logits (HybridRec
        # with ``loop_steps`` > 1): sown into `exits` and bound to the loss, which
        # weights a loss at every exit (nn.loss.ExitWeightedCE)
        exits = bool(getattr(model, "sows_exits", False))
        # a loss that counts (ExitWeightedCE: per-exit mass and loss) hands its
        # counters over after its call; they join the model's in the step metrics
        loss_counts = bool(getattr(loss, "sows_counters", False))
        collected = ["counters"] * counts + ["exits"] * exits + ["intermediates"] * bool(
            health is not None and health.capture_intermediates
        )
        with_aux = bool(collected) or health is not None or loss_counts

        # `health` branches below are python-static (resolved at trace time,
        # like the models' sow guards): health=None lowers to byte-identical
        # HLO as the pre-health step — golden-tested — while a HealthConfig
        # yields the ONE sanctioned extra compiled variant with an auxiliary
        # `health` pytree of device scalars in the metrics (obs.health).
        def train_step(state: TrainState, batch: Batch):
            if "segment_ids" in batch and "segment_ids" not in self._forward_params:
                # packed batches on a model whose forward cannot take the
                # segment mask: signature filtering would silently DROP the
                # key and attention/loss would cross packed-sequence
                # boundaries — reject (trace-time python check, free at run
                # time), exactly like the flash-route refusal in nn.mask
                msg = (
                    f"batch carries 'segment_ids' (packed sequences) but "
                    f"{type(model).__name__}.__call__ accepts no segment_ids "
                    "parameter — training would silently attend and compute "
                    "loss across packed segment boundaries. Use an unpacked "
                    "batcher for this model, or plumb segment_ids through "
                    "its attention path (nn.mask.segment_attention_mask)."
                )
                raise ValueError(msg)
            rng, dropout_rng, loss_rng = jax.random.split(state.rng, 3)
            # batch-padding rows (fixed-shape final batch) get zero loss weight:
            # gate the target mask by the `valid` row flags from the batcher
            target_mask = batch[tmask_f]
            if "valid" in batch:
                target_mask = target_mask & batch["valid"][
                    (slice(None),) + (None,) * (target_mask.ndim - 1)
                ]

            def loss_fn(params):
                intermediates, counters = {}, {}
                kwargs = {
                    name: batch[name] for name in self._forward_params if name in batch
                }
                if "deterministic" in self._forward_params:
                    kwargs["deterministic"] = False
                # named scopes label the lowered HLO so a jax.profiler device
                # trace correlates with the host-side Tracer spans by name
                with jax.named_scope("forward"):
                    if collected:
                        # mutable `intermediates`: the bodies' sow sites (stage
                        # stats, attention entropy) become live; `counters`:
                        # what a model that declares `sows_counters` counts
                        # (sparse experts: per-expert load, dropped assignments)
                        hidden, variables = model.apply(
                            {"params": params},
                            rngs={"dropout": dropout_rng},
                            mutable=collected,
                            **kwargs,
                        )
                        intermediates = variables.get("intermediates", {})
                        counters = variables.get("counters", {})
                        if exits:
                            loss.exits = variables["exits"]
                    else:
                        hidden = model.apply(
                            {"params": params}, rngs={"dropout": dropout_rng}, **kwargs
                        )
                logits_extra = {
                    name: batch[name] for name in self._logits_extra_params if name in batch
                }
                logits_callback = partial(
                    model.apply, {"params": params}, method=type(model).get_logits, **logits_extra
                )
                if precision is not None and precision.casts_logits:
                    # f32 loss accumulation under a narrow compute dtype:
                    # candidate-shaped logits are a bf16×bf16 einsum and need
                    # the explicit up-cast (full-catalog logits already
                    # promote through the f32 item table)
                    logits_callback = precision.wrap_logits_callback(logits_callback)
                loss.logits_callback = logits_callback
                if binds_table:
                    # SCE-style losses mine hard negatives from the raw item table;
                    # the fused CE head forms its logits from it
                    loss.item_embeddings_callback = partial(
                        model.apply, {"params": params}, method=type(model).get_item_weights
                    )
                if getattr(loss, "needs_rng", False):
                    loss.rng = loss_rng
                with jax.named_scope("loss"):
                    loss_value = loss(
                        hidden,
                        batch.get("feature_tensors", {}),
                        batch[label_f],
                        batch.get(neg_f),
                        batch[pad_f],
                        target_mask,
                    )
                if not with_aux:
                    return loss_value
                counted = dict(loss.step_counters) if loss_counts else {}
                return loss_value, (hidden, intermediates, counters, counted)

            if not with_aux:
                loss_value, grads = jax.value_and_grad(loss_fn)(state.params)
            else:
                (loss_value, (hidden, intermediates, counters, loss_counters)), grads = (
                    jax.value_and_grad(loss_fn, has_aux=True)(state.params)
                )
            # non-finite sentinel: one fused flag decides, in-jit, whether this
            # update may touch the state. A NaN/Inf loss or gradient norm keeps
            # the previous params/opt_state (jnp.where select — no host round
            # trip, static shapes preserved); step/rng still advance so step
            # ids stay aligned with the batch stream across resumes.
            grad_norm = optax.global_norm(grads)
            good = jnp.isfinite(loss_value) & jnp.isfinite(grad_norm)
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)

            metrics = {"loss": loss_value, "good": good, "grad_norm": grad_norm}
            if counts or loss_counts:
                metrics["counters"] = {**_fold_counters(counters), **loss_counters}
            if health is not None:
                logits = None
                streamed_stats = None
                if health.logits_stats and hasattr(type(model), "get_logits"):
                    # last-position scoring-head stats (the catalog logits the
                    # inference path serves) — cheap next to the loss's scoring
                    last_hidden = hidden[:, -1, :] if hidden.ndim == 3 else hidden
                    if getattr(loss, "avoid_full_logits", False):
                        # memory-wall losses (CE on its fused route, read after
                        # the loss was traced above; CEFused/CEFusedTP/SCE/GBCE) never
                        # materialize [B, I] logits — neither may health. For
                        # bias-free tying heads the same stats stream over
                        # catalog chunks (obs.health.streamed_logits_stats);
                        # anything else is flagged skipped IN the record (a
                        # numeric sentinel: every sink stays scalar-typed) —
                        # never silently absent.
                        if tying_head:
                            from replay_tpu.obs.health import streamed_logits_stats

                            table = model.apply(
                                {"params": state.params},
                                method=type(model).get_item_weights,
                            )
                            with jax.named_scope("health_logits"):
                                streamed_stats = streamed_logits_stats(
                                    last_hidden, table
                                )
                        else:
                            streamed_stats = {"skipped": jnp.float32(1.0)}
                            logger.warning(
                                "health.logits_stats: %s avoids full logits and "
                                "%s has no bias-free tying head to stream stats "
                                "from — the health record carries "
                                "logits={'skipped': 1.0} instead",
                                type(loss).__name__,
                                type(model).__name__,
                            )
                    else:
                        logits_extra = {
                            name: batch[name]
                            for name in self._logits_extra_params
                            if name in batch
                        }
                        with jax.named_scope("health_logits"):
                            logits = model.apply(
                                {"params": state.params},
                                last_hidden,
                                None,
                                method=type(model).get_logits,
                                **logits_extra,
                            )
                with jax.named_scope("health"):
                    health_tree = health_metrics(
                        health, state.params, grads, updates, intermediates, logits
                    )
                if streamed_stats is not None:
                    health_tree["logits"] = streamed_stats
                health_tree["grad_norm_global"] = grad_norm
                metrics["health"] = health_tree

            def keep(new, old):
                return jnp.where(good, new, old)

            new_state = TrainState(
                step=state.step + 1,
                params=jax.tree.map(keep, params, state.params),
                opt_state=jax.tree.map(keep, opt_state, state.opt_state),
                rng=rng,
                bad_steps=state.bad_steps + (~good).astype(jnp.int32),
            )
            return new_state, metrics

        return self._scoped(train_step)

    def _h2d_span(self):
        """The per-step paths' ``h2d`` stage (recorded by the attached tracer
        when one is enabled)."""
        return stage("h2d", tracer=self.tracer)

    def traced_train_step(
        self, state: TrainState, batch: Batch
    ) -> Tuple[TrainState, jnp.ndarray]:
        """:meth:`train_step` under the attached tracer's ``train_step`` span.

        Blocks on the loss inside the span (dispatch is async — an unfenced
        span would time the enqueue, not the step) and carves XLA build time
        out of any step that triggered a (re)trace into a nested ``compile``
        span. Falls back to a plain :meth:`train_step` when tracing is off.
        Shared by ``fit``'s traced loop and the multi-chip dry run.
        """
        tracer = self.tracer
        if tracer is None or not tracer.enabled:
            return self.train_step(state, batch)
        compile_before = self.compile_tracker.total_compile_seconds
        with tracer.span("train_step") as step_span:
            state, loss_value = self.train_step(state, batch)
            jax.block_until_ready(loss_value)
        compile_delta = self.compile_tracker.total_compile_seconds - compile_before
        if compile_delta > 0:
            tracer.carve(step_span, "compile", compile_delta)
        return state, loss_value

    def train_step(self, state: TrainState, batch: Batch) -> Tuple[TrainState, jnp.ndarray]:
        """One jitted optimizer step on a (data-sharded) batch.

        Returns ``(state, loss)``; the full step metrics — ``loss``, the
        sentinel's ``good`` flag and ``grad_norm``, all device scalars — stay
        readable on :attr:`last_step_metrics` until the next step.
        """
        step_fn = self._ensure_train_step()
        with self._h2d_span():
            placed = self._put_batch(batch)
        self._record_template("train_step", step_fn, state, placed)
        with self.compile_tracker.observe("train_step"):
            new_state, metrics = step_fn(state, placed)
        self.last_step_metrics = metrics
        return new_state, metrics["loss"]

    def _ensure_train_step(self):
        """The jitted per-step program, built lazily (see :meth:`_ensure_train_scan`)."""
        if self._train_step is None:
            self._train_step = jax.jit(
                self.compile_tracker.wrap(self._build_train_step(self.health), "train_step"),
                donate_argnums=0,
            )
        return self._train_step

    def _ensure_train_scan(self):
        """The jitted K-step ``lax.scan`` program, built lazily (and rebuilt
        after anything that invalidates the per-step program: an LR-backoff
        rollback, a vocabulary resize).

        The scan path stays health-free: stacking K per-step health pytrees
        would multiply the metrics payload by K for a path whose whole point
        is minimal host involvement — ``fit(scan_chunk=...)`` interleaves
        health-instrumented single steps at the fetch cadence instead.

        Donation contract (the device feed leans on this): ONLY the TrainState
        argument is donated. The ``[K, ...]`` batch chunk is never donated, so
        a chunk pre-placed by :class:`~replay_tpu.data.nn.DevicePrefetcher`
        while the previous chunk executes cannot alias buffers this dispatch
        will invalidate.
        """
        if self._train_scan is None:
            step_fn = self._build_train_step(None)
            self._train_scan = jax.jit(
                self.compile_tracker.wrap(
                    lambda s, stacked: jax.lax.scan(step_fn, s, stacked), "train_scan"
                ),
                donate_argnums=0,
            )
        return self._train_scan

    @staticmethod
    def _stack_chunk(batches: Sequence[Batch]) -> Batch:
        """K same-shape host batches stacked into one ``[K, ...]`` pytree (the
        scan program's ``xs``), with a clear error for the one sanctioned
        shape relaxation that cannot feed a scan."""
        try:
            return jax.tree.map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]), *list(batches)
            )
        except ValueError as exc:
            msg = (
                "scan chunking stacks every batch of a chunk into one fixed "
                f"[K, ...] program input, but stacking failed: {exc}. All "
                "batches must share one shape and key structure — length-"
                "bucketed batchers (SequenceBatcher(bucket_boundaries=...)) "
                "emit a SET of widths and cannot drive fit(scan_chunk=...)."
            )
            raise ValueError(msg) from exc

    def train_steps(
        self, state: TrainState, batches: Sequence[Batch]
    ) -> Tuple[TrainState, np.ndarray]:
        """``len(batches)`` optimizer steps in ONE XLA dispatch (``lax.scan``).

        Amortizes host→device dispatch latency over K steps — the TPU stays busy
        while the host is out of the loop (one compiled program per chunk
        length). Returns the per-step losses as a ``[K]`` array. Identical math
        to K :meth:`train_step` calls. ``fit(scan_chunk=K)`` drives this path
        end-to-end with a device-feed stage overlapping the H2D copies
        (docs/performance.md "Closing the dispatch gap").
        """
        scan_fn = self._ensure_train_scan()
        stacked = self._stack_chunk(batches)
        with self._h2d_span():
            placed = self._put_stacked(stacked)
        self._record_template("train_scan", scan_fn, state, placed)
        with self.compile_tracker.observe("train_scan"):
            new_state, metrics = scan_fn(state, placed)
        # per-step [K] arrays (loss / sentinel good flags / grad norms)
        self.last_step_metrics = metrics
        return new_state, np.asarray(metrics["loss"])

    def _put_stacked(self, stacked: Batch) -> Batch:
        """Device placement for a [K, ...] stack of batches: the per-row leaves
        shard on their SECOND axis over the ``batch`` rule's mesh axis (axis 0
        is the scan axis) and — under SP — their THIRD (sequence) axis over the
        ``length`` rule's."""
        multiprocess = jax.process_count() > 1
        scale = jax.process_count() if multiprocess else 1
        rules = self.sharding_rules
        batch_axis = rules.mesh_axis("batch")
        length_axis = rules.mesh_axis("length")
        batch_div = max(rules.axis_size(self.mesh, "batch"), 1)
        length_div = rules.axis_size(self.mesh, "length")
        reference = stacked.get(self.padding_mask_field)
        reference = np.asarray(reference) if reference is not None else None
        local_batch = reference.shape[1] if reference is not None else None
        seq_len = reference.shape[2] if reference is not None and reference.ndim >= 3 else None

        def place(x):
            x = np.asarray(x)
            is_batch_leaf = (
                x.ndim >= 2
                and local_batch is not None
                and x.shape[1] == local_batch
                and (local_batch * scale) % batch_div == 0
            )
            if is_batch_leaf:
                axes = [None, batch_axis] + [None] * (x.ndim - 2)
                if (
                    length_axis is not None
                    and length_div > 1
                    and x.ndim >= 3
                    and seq_len is not None
                    and x.shape[2] == seq_len
                    and seq_len % length_div == 0
                ):
                    axes[2] = length_axis
                sharding = NamedSharding(self.mesh, P(*axes))
            else:
                sharding = NamedSharding(self.mesh, P())
            if multiprocess:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)

        return jax.tree.map(place, stacked)

    def _chunk_placer(self, tracer: Optional[Tracer], first_chunk: int = 0):
        """The device-feed ``place`` callable for the scan-chunked fit: stack
        + place a chunk on the FEEDER thread, so the next chunk's H2D copy
        overlaps the running chunk's compute. Single-step items pass through
        unplaced — the per-step path places its own batch (pre-placing would
        make ``_put_batch``'s ``np.asarray`` round-trip them back to host).

        Two stage spans, each with the chunk's ordinal (``first_chunk`` on):
        ``stack`` is ``_stack_chunk`` alone, whose ``np.asarray`` of a leaf
        that arrived as a device array is a D2H read that WAITS for whatever
        runs on the device (``device_leaves`` counts them); ``h2d`` is the copy
        and its fence. They land on the feeder thread's timeline, so the fit
        thread's goodput fractions count only what the feed could NOT hide.
        Returns ``(placed, record)``: the record (``obs.trace.claim_chunk``)
        holds this thread's stage seconds for the chunk and travels with it."""
        ordinals = itertools.count(first_chunk)

        def place(item):
            kind, payload = item
            if kind != "scan":
                return None
            chunk = next(ordinals)
            record = claim_chunk(chunk)
            record["device_leaves"] = device_leaves = sum(
                isinstance(leaf, jax.Array) for leaf in jax.tree.leaves(list(payload))
            )
            with stage("stack", tracer=tracer, chunk=chunk, device_leaves=device_leaves):
                stacked = self._stack_chunk(payload)
            record["h2d_bytes"] = nbytes = sum(
                leaf.nbytes for leaf in jax.tree.leaves(stacked)
            )
            with stage("h2d", tracer=tracer, chunk=chunk, steps=len(payload), bytes=nbytes):
                placed = self._put_stacked(stacked)
                # fence on the placing thread: the span times the real copy,
                # and the consumer dispatches on already-resident buffers
                jax.block_until_ready(placed)
            return placed, record

        return place

    def fit(self, *args, **kwargs) -> TrainState:
        attached = attached_tracer()  # a traced fit attaches its own
        try:
            return self._fit_impl(*args, **kwargs)
        except BaseException:
            # a raising fit must not leak the live metrics endpoint: the
            # non-raising exits (and the recovery-exhausted raise) close it
            # in finish_trace; this catches every other exit — data-pipeline
            # errors, checkpoint failures, Ctrl-C — so a scraper never reads
            # a crashed fit as live and the port is free for the next run
            if self.metrics_exporter is not None:
                self.metrics_exporter.close()
                self.metrics_exporter = None
            raise
        finally:
            attach_tracer(attached)

    def _fit_impl(
        self,
        train_batches: Iterable[Batch] | Callable[[], Iterable[Batch]],
        epochs: int = 1,
        state: Optional[TrainState] = None,
        val_batches: Optional[
            Callable[[], Iterable[Batch]] | Dict[str, Callable[[], Iterable[Batch]]]
        ] = None,
        metrics: Sequence[str] = ("ndcg", "recall", "map"),
        top_k: Sequence[int] = (1, 5, 10),
        item_count: Optional[int] = None,
        postprocessors: Sequence[Callable] = (),
        log_every: int = 100,
        checkpoint_manager=None,
        checkpoint_every: Optional[int] = None,
        resume: bool = False,
        monitor: Optional[str] = None,
        patience: Optional[int] = None,
        mode: str = "max",
        prefetch: int = 0,
        scan_chunk: Optional[int] = None,
        device_feed: bool = True,
        loggers: Optional[RunLogger | Sequence[RunLogger]] = None,
        profile_steps: Optional[Tuple[int, int]] = None,
        profile_dir: Optional[str] = None,
        recovery: Optional[RecoveryPolicy] = None,
        detect_anomalies: Optional[bool] = None,
        handle_preemption: Optional[bool] = None,
        tracer: Optional[Tracer | bool] = None,
        trace_path: Optional[str] = None,
        metrics_port: Optional[int] = None,
        slo_rules: Optional[Sequence[Any]] = None,
        flight_path: Optional[str] = None,
    ) -> TrainState:
        """Train for ``epochs`` passes; validates after each epoch when
        ``val_batches`` is given, appending to :attr:`history`. A dict of
        factories runs several validation streams sequentially (the reference's
        CombinedLoader), prefixing each stream's metric keys with its name.

        ``monitor`` (a history key, e.g. ``"ndcg@10"`` or ``"train_loss"``)
        enables best-state tracking: fit returns the BEST state seen, marks the
        winning checkpoint's metadata, and — with ``patience`` — stops early
        after that many epochs without improvement (Lightning's
        ModelCheckpoint(monitor=...) + EarlyStopping semantics).

        ``train_batches`` may be a re-iterable (e.g. a SequenceBatcher — its
        ``set_epoch`` is called so shuffling advances per epoch), a zero- or
        one-arg callable returning an iterable (the arg is the epoch), or a plain
        one-shot iterator (materialized once if several epochs are requested).

        ``loggers`` attaches run-telemetry sinks (``replay_tpu.obs``): fit then
        emits ``on_fit_start`` / ``on_train_step`` (loss, LR, samples/sec) /
        ``on_validation_end`` / ``on_epoch_end`` / ``on_checkpoint`` /
        ``on_fit_end`` (telemetry summary, compile report, peak device memory)
        events to every sink. ``log_every`` is itself a sink — a
        :class:`~replay_tpu.obs.ConsoleLogger` on the same event stream — so
        the old print path and a ``JsonlLogger`` run directory see identical
        records. With explicit ``loggers`` every step emits an event, costing
        one scalar device sync (the loss; the step counter is tracked on host
        after a one-time fetch, and LR-schedule evaluation is a tiny host-side
        dispatch only when a scheduler is configured); with only ``log_every``
        the cadence is every ``log_every``-th EXECUTED step, counted globally
        across epochs (the old path counted per-epoch stream positions, so
        the exact steps printed can differ from pre-event-layer logs).

        ``profile_steps=(start, stop)`` captures a ``jax.profiler`` trace of
        the half-open step window [start, stop) — counted over steps actually
        executed by this fit call — into ``profile_dir`` (default: the first
        JsonlLogger's ``run_dir/profile``, else ``./jax_profile``). A
        profiled fit's ``on_fit_end`` also carries a per-program ``roofline``
        record (``obs.roofline``: memory- vs compute-bound with the predicted
        ceiling, static HBM footprint, collective bytes), rendered by
        ``obs.report`` as the "roofline" section (docs/performance.md
        "Roofline").

        ``checkpoint_every`` additionally saves MID-epoch every that many steps,
        recording the data-iterator position (epoch + step within the epoch) in
        the checkpoint metadata. ``resume=True`` restores the manager's latest
        checkpoint and fast-forwards the (deterministic, epoch-seeded) batch
        stream to that exact position, so a killed run continues with the same
        loss curve as an uninterrupted one.

        Dispatch amortization (docs/performance.md "Closing the dispatch
        gap"): ``scan_chunk=K`` drives the :meth:`train_steps` ``lax.scan``
        path end-to-end — each epoch's batches are grouped into fixed-K
        chunks dispatched as ONE XLA program (bitwise-identical math to K
        per-step calls), with the short tail on the existing per-step path
        (exactly one extra compiled variant, no dynamic shapes). In front of
        it, ``device_feed=True`` (the default) runs a
        :class:`~replay_tpu.data.nn.DevicePrefetcher`: a feeder thread
        stacks the NEXT chunk and issues its ``device_put`` /
        ``make_array_from_process_local_data`` while the current chunk is
        still executing, so the host→device copy overlaps compute
        (donation-safe: the scan donates only the TrainState, never the
        chunk). Per-step accounting is preserved exactly: the chunk's ``[K]``
        loss/sentinel/grad-norm arrays come to host once per chunk and fan
        back out through the same bookkeeping as the per-step loop —
        ``on_train_step`` cadence, exact ``on_anomaly`` step indices and
        ``bad_steps`` totals, epoch-loss averaging. What moves to chunk
        granularity: ``checkpoint_every`` boundaries crossed inside a chunk
        save once at the chunk end (the state only exists at chunk
        boundaries), preemption exits at the next chunk boundary, and a
        recovery rollback triggered by a mid-chunk step discards the rest of
        that chunk's (already-executed, pre-rollback) accounting while the
        stream position still advances. With a :class:`HealthConfig`
        attached, every ``cadence``-th step is interleaved as a
        health-instrumented single step (the per-step program — no silent
        health loss; pick ``cadence ≡ 1 (mod scan_chunk)`` to keep full
        chunks between them). Requires ONE fixed batch shape:
        ``SequenceBatcher(bucket_boundaries=...)`` is rejected at fit start.

        Resilience (docs/robustness.md): the train step's non-finite sentinel
        always protects the state — a NaN/Inf loss or gradient norm discards
        that update in-jit and bumps ``state.bad_steps``. ``detect_anomalies``
        additionally checks the sentinel flag on host every step and emits an
        ``on_anomaly`` event per skipped step (default: on when ``recovery`` is
        set or explicit ``loggers`` are attached — those paths already pay the
        per-step device sync; off for log_every-only runs, which stay
        sync-free). A ``recovery`` policy counts bad steps regardless:
        ``detect_anomalies=False`` silences the events, never the rollback
        trigger. ``recovery`` attaches a :class:`RecoveryPolicy`: after
        ``max_consecutive_bad`` skipped steps or an epoch-end monitored-metric
        blowup, fit restores the manager's latest checkpoint (or, before any
        save, a snapshot of the initial state), backs the learning rate off,
        emits ``on_recovery`` and continues forward in the batch stream —
        bounded by ``max_restarts``, then ``RuntimeError``. ``handle_preemption``
        (default: on when a ``checkpoint_manager`` is attached) installs
        SIGTERM/SIGINT handlers for the duration of the loop: the first signal
        saves a position-stamped mid-epoch checkpoint at the next step boundary
        and returns the state cleanly, so ``fit(resume=True)`` reproduces the
        uninterrupted run exactly; a second signal force-exits.

        Tracing/goodput (docs/performance.md "Goodput and tracing"):
        ``tracer=True`` (or an ``obs.Tracer`` instance) records host-side
        spans — ``data_wait`` / ``h2d`` / ``compile`` / ``train_step`` /
        ``validation`` / ``checkpoint`` / ``recovery`` — and (a) writes a
        Chrome trace-event ``trace.json`` at fit end to ``trace_path``
        (default: the first JsonlLogger's run dir), (b) adds a ``goodput``
        breakdown (phase fractions summing to 1.0 + ``input_starvation``) to
        every ``on_epoch_end``/``on_fit_end`` event. A tracer passed as an
        ARGUMENT scopes to this fit call (detached at fit end); preattach one
        to :attr:`tracer` to trace every fit. Goodput fractions decompose the
        fit thread's wall clock — spans from other threads (a prefetch
        worker's ``batch_build``) appear in ``trace.json`` only. Tracing
        synchronizes on the loss every step for honest step times, so leave
        it off for maximum-throughput runs. Epoch windows tile the run: each
        closes at its ``on_epoch_end`` emission, so the end-of-epoch
        checkpoint save lands in the NEXT epoch's window.

        Model health (docs/performance.md "Model health"): a
        :class:`~replay_tpu.obs.HealthConfig` on :attr:`health` makes the
        jitted step also compute per-group gradient/parameter/update norms and
        update ratios, activation RMS/absmax per stage, per-head attention
        entropy, logits stats and embedding-row coverage — all on device. Fit
        fetches the record every ``cadence`` steps (one device_get), attaches
        it as a ``health`` payload to the next ``on_train_step`` and to every
        ``on_epoch_end``, and — when the config carries a ``HealthWatcher`` —
        emits ``on_health_warning`` on an EWMA blowup of the grad norm or max
        update ratio, *before* the non-finite sentinel trips; with
        ``trigger_recovery=True`` and a ``recovery`` policy the warning rolls
        back immediately. Enabling health is exactly one compiled train-step
        variant; the cadence is host-side, so no retraces after step 1.

        Live metrics plane (docs/observability.md): ``metrics_port`` attaches
        a :class:`~replay_tpu.obs.MetricsLogger` sink (the existing event
        stream bridged into a thread-safe counters/gauges/histograms registry
        — no new trainer hooks) and serves it for the duration of the fit via
        a stdlib HTTP exporter: ``GET /metrics`` is Prometheus text,
        ``/snapshot`` the JSON view. ``metrics_port=0`` binds an ephemeral
        port (read it from :attr:`metrics_exporter`); a busy port degrades to
        a logged no-op, never a failed fit. ``slo_rules`` (a sequence of
        :class:`~replay_tpu.obs.SLORule`) attaches an
        :class:`~replay_tpu.obs.SLOWatchdog` evaluated after every bridged
        step event: a rule breached for its ``for_steps`` consecutive
        evaluations emits ONE ``on_slo_violation`` through the same sinks
        (console render, events.jsonl, ``replay_slo_violations_total``), and
        the recovery transition emits ``on_slo_recovery`` with the breach
        duration. Either option implies per-step events (the explicit-loggers
        cadence); the registry stays readable after fit on
        :attr:`metrics_registry`. Multi-host fits stamp every event with this
        process's ``process_index`` so ``obs.report`` can merge per-process
        shards and compute cross-host skew.

        Black box (docs/observability.md "The black box and post-mortems"):
        ``flight_path`` attaches a
        :class:`~replay_tpu.obs.BlackboxLogger` — the same event stream,
        recorded into an mmap-backed flight ring whose last N records survive
        SIGKILL (``obs.report --postmortem`` reads what a dead fit was doing).
        Defaults from the ``REPLAY_TPU_FLIGHT_PATH`` env var, which
        ``launch_workers(run_dir=...)`` sets per rank — a worker script needs
        no change to be flight-recorded. Implies per-step events, like any
        explicit sink. On preemption (SIGTERM/SIGINT) the tracer is flushed
        to ``trace_path`` at the ``on_preemption`` boundary — before the
        shutdown-window checkpoint save — so the span tree survives even if
        the save itself dies.
        """
        if checkpoint_manager is not None and not self.history:
            # resume: prior epoch records survive the restart (metric-history
            # state_dict semantics of the reference validation callback)
            self.history = list(checkpoint_manager.history())
        one_shot = None
        if not callable(train_batches) and iter(train_batches) is train_batches:
            # a generator: re-iteration is impossible, materialize once
            one_shot = list(train_batches) if epochs > 1 else train_batches

        def batches_for(epoch: int):
            if one_shot is not None:
                return one_shot
            if callable(train_batches):
                takes_epoch = len(_signature_names(train_batches)) >= 1
                return train_batches(epoch) if takes_epoch else train_batches()
            if hasattr(train_batches, "set_epoch"):
                train_batches.set_epoch(epoch)
            return train_batches

        if mode not in ("max", "min"):
            msg = "mode must be 'max' or 'min'"
            raise ValueError(msg)
        if patience is not None and patience < 1:
            msg = "patience must be >= 1 (it counts consecutive non-improving epochs)"
            raise ValueError(msg)
        def reject_bucketed(source) -> None:
            """Bucketed batchers cannot feed the scan: fail up front with the
            real reason, not an opaque np.stack error mid-epoch. Checked on
            the fit argument AND on what a factory callable returns (the
            factory object itself carries no batcher attributes)."""
            if getattr(source, "bucket_boundaries", None) or (
                hasattr(source, "scan_compatible") and not source.scan_compatible
            ):
                msg = (
                    "fit(scan_chunk=...) stacks K batches into one compiled "
                    "[K, B, L] scan program, which requires ONE fixed batch "
                    f"shape; {type(source).__name__}(bucket_boundaries=...) "
                    "emits a set of widths. Drop the bucketing or the "
                    "scan_chunk (docs/performance.md 'Closing the dispatch "
                    "gap')."
                )
                raise ValueError(msg)

        if scan_chunk is not None:
            scan_chunk = int(scan_chunk)
            if scan_chunk < 1:
                msg = "scan_chunk must be >= 1 (optimizer steps per lax.scan dispatch)"
                raise ValueError(msg)
            reject_bucketed(train_batches)

        if state is not None:
            # continual-training guard (docs/robustness.md): params grown by
            # vocab surgery without their optimizer moments (or vice versa)
            # must fail HERE, naming the table path — not crash deep in
            # optax's first update or silently train on reset moments
            from replay_tpu.nn.vocabulary import validate_optimizer_state

            schema = getattr(self.model, "schema", None)
            if schema is not None:
                validate_optimizer_state(state.params, state.opt_state, schema)

        start_epoch, skip_steps, pending_restore_step = 0, 0, None
        resumed_best_step = None
        pending_stream_cursor = None  # out-of-core resume: seek, don't rescan
        if resume:
            if checkpoint_manager is None:
                msg = "resume=True needs a checkpoint_manager"
                raise ValueError(msg)
            if state is not None:
                msg = (
                    "resume=True restores the manager's latest checkpoint; "
                    "passing state= as well is ambiguous (the explicit state "
                    "would silently win). Drop one of the two."
                )
                raise ValueError(msg)
            latest = checkpoint_manager.latest_step()
            if latest is not None:
                meta = checkpoint_manager.metadata(latest)
                if meta.get("mid_epoch"):
                    start_epoch = int(meta["epoch"])
                    skip_steps = int(meta["step_in_epoch"])
                    # a streaming batcher's resumable position (the PR-2
                    # preemption contract extended to out-of-core runs):
                    # restore_cursor SEEKS to the exact mid-epoch state
                    # instead of re-reading and discarding skip_steps batches
                    # (multi-host: each rank reads ITS per-process sidecar —
                    # the shared one only carries process 0's cursor)
                    pending_stream_cursor = checkpoint_manager.process_metadata(
                        latest
                    ).get("stream_cursor") or meta.get("stream_cursor")
                elif "epoch" in meta:
                    start_epoch = int(meta["epoch"]) + 1
                else:
                    msg = (
                        f"Checkpoint step {latest} carries no data-iterator "
                        "position ('epoch' missing from its metadata — saved by "
                        "an older fit or a manual save_checkpoint); resuming "
                        "would silently retrain from epoch 0 on top of the "
                        "restored weights. Restore explicitly via "
                        "restore_checkpoint and pass state= instead."
                    )
                    raise ValueError(msg)
                if meta.get("lr_scale"):
                    # the killed run had backed its LR off (RecoveryPolicy);
                    # resuming at full rate would rerun the divergence
                    self._set_lr_scale(float(meta["lr_scale"]))
                pending_restore_step = latest
                resumed_best_step = checkpoint_manager.best_step()
                logger.info(
                    "resuming from step %d (epoch %d, fast-forward %d batches)",
                    latest, start_epoch, skip_steps,
                )

        best_value, best_state, stale_epochs = None, None, 0
        if resume and monitor is not None:
            # seed the monitored best from the restored history so a worse
            # post-resume epoch cannot repoint best.json / win the return value
            # NaN-guarded: a fully-fast-forwarded resumed epoch records
            # train_loss=NaN, which would poison max()/min() and freeze `improved`
            seen_values = [
                r[monitor] for r in self.history if monitor in r and math.isfinite(r[monitor])
            ]
            if seen_values:
                best_value = max(seen_values) if mode == "max" else min(seen_values)
            if resumed_best_step is not None:
                # the winning checkpoint's sidecar records the monitored value
                # at mark time (the same channel lr_scale resumes through):
                # it survives a lost/truncated history.json, so the seed never
                # regresses to None just because the history did
                try:
                    sidecar_value = checkpoint_manager.metadata(resumed_best_step).get(monitor)
                except (OSError, ValueError):
                    sidecar_value = None
                if (
                    isinstance(sidecar_value, (int, float))
                    and not isinstance(sidecar_value, bool)
                    and math.isfinite(sidecar_value)
                    and (
                        best_value is None
                        or (mode == "max" and sidecar_value > best_value)
                        or (mode == "min" and sidecar_value < best_value)
                    )
                ):
                    best_value = float(sidecar_value)

        # -- run-telemetry sinks (replay_tpu.obs) -------------------------- #
        explicit_loggers: List[RunLogger] = []
        if loggers is not None:
            # duck-typed: RunLogger is a protocol, a single sink is anything
            # with log_event (a structural conformer need not subclass it)
            explicit_loggers = (
                [loggers] if hasattr(loggers, "log_event") else list(loggers)
            )
        # -- live metrics plane (obs.metrics / obs.exporter / obs.slo) ------ #
        # the MetricsLogger is an explicit sink: live gauges need per-step
        # events, so requesting metrics/SLOs opts into the per-step device
        # sync exactly like attaching a JsonlLogger does
        metrics_logger = None
        if self.metrics_exporter is not None:
            # a previous fit raised before its terminal event: release the
            # port before (maybe) binding a fresh exporter
            self.metrics_exporter.close()
            self.metrics_exporter = None
        if metrics_port is not None or slo_rules:
            from replay_tpu.obs.exporter import MetricsExporter
            from replay_tpu.obs.metrics import MetricsLogger
            from replay_tpu.obs.slo import SLOWatchdog

            metrics_logger = MetricsLogger()
            self.metrics_registry = metrics_logger.registry
            if slo_rules:
                # emit is pointed at the sink fan-out once run_logger exists
                metrics_logger.watchdog = SLOWatchdog(
                    slo_rules, metrics_logger.registry
                )
            explicit_loggers.append(metrics_logger)
            if metrics_port is not None:
                self.metrics_exporter = MetricsExporter(
                    metrics_logger.registry,
                    port=metrics_port,
                    # the identity block /snapshot and /healthz carry, so a
                    # federation scrape can label this fit's series
                    identity={"process_index": jax.process_index()},
                ).start()
        # -- the black box (obs.blackbox): SIGKILL-surviving flight ring ----- #
        # attaching the sink IS the instrumentation: the same event stream
        # every other sink sees, stored as O(1) in-place mmap ring writes.
        # launch_workers(run_dir=...) hands workers their ring path via env,
        # so a fit inside a launched worker is flight-recorded with no
        # worker-script change.
        flight_path = flight_path or os.environ.get("REPLAY_TPU_FLIGHT_PATH")
        flight_logger = None
        if flight_path:
            from replay_tpu.obs.blackbox import BlackboxLogger

            try:
                flight_logger = BlackboxLogger(
                    flight_path,
                    meta={
                        "role": "fit",
                        "pid": os.getpid(),
                        "process_index": jax.process_index(),
                    },
                )
            except OSError as exc:
                # same posture as the exporter: the black box must never take
                # down the run it records
                logger.warning(
                    "flight recorder: cannot open %s (%s); fit runs unrecorded",
                    flight_path, exc,
                )
            else:
                explicit_loggers.append(flight_logger)
        sinks: List[RunLogger] = list(explicit_loggers)
        if log_every:
            # events already arrive at log_every cadence when no explicit
            # sinks ask for per-step records — the console then prints each one
            sinks.append(ConsoleLogger(every=log_every if explicit_loggers else 1))
        run_logger: Optional[RunLogger] = (
            MultiLogger(sinks) if len(sinks) > 1 else (sinks[0] if sinks else None)
        )
        if metrics_logger is not None and metrics_logger.watchdog is not None:
            # violations ride the SAME fan-out as every other event: jsonl,
            # console, tensorboard AND the registry's violation counter
            metrics_logger.watchdog.emit = run_logger.log_event
        event_every = 1 if explicit_loggers else (log_every or 0)

        # -- span tracing + goodput accounting (replay_tpu.obs.trace) ------- #
        prior_tracer = self.tracer
        tracer_from_arg = tracer is not None
        if tracer is True:
            tracer = Tracer()
        if isinstance(tracer, Tracer):
            self.tracer = tracer  # train_step's h2d spans route through it too
        trace = self.tracer if self.tracer is not None and self.tracer.enabled else None
        tracing = trace is not None
        # stages built without a tracer (Compose, the batcher, the feeder's
        # put) record into this fit's, if it has one; fit() restores what was
        # attached
        attach_tracer(trace)
        # the fit thread's side of the chunk stage log (scan-chunked epochs)
        chunk_stages = ChunkStages(trace)
        if tracing and trace_path is None:
            queue: List[RunLogger] = list(explicit_loggers)
            while queue:  # MultiLogger nests sinks: search them too
                sink = queue.pop(0)
                if isinstance(sink, JsonlLogger):
                    trace_path = os.path.join(sink.run_dir, "trace.json")
                    break
                if isinstance(sink, MultiLogger):
                    queue.extend(sink.loggers)
        # goodput windows decompose THIS thread's wall clock: other threads'
        # spans (a prefetch worker's batch_build) overlap it rather than
        # consume it, so they stay out of the fractions (trace.json keeps them)
        fit_trace_base = trace.snapshot(only_current_thread=True) if tracing else {}
        fit_summary_base = trace.summary() if tracing else {}
        fit_trace_t0 = time.perf_counter()

        def span(name: str, **args):
            """A trace span when tracing, else a no-op context."""
            return trace.span(name, **args) if tracing else contextlib.nullcontext()

        def trace_window(base: Dict[str, float], t0: float) -> Dict[str, Any]:
            """Goodput record over this thread's spans since (base, t0)."""
            current = trace.snapshot(only_current_thread=True)
            diff = {name: current.get(name, 0.0) - base.get(name, 0.0) for name in current}
            return goodput_breakdown(diff, time.perf_counter() - t0)

        def fit_spans() -> Dict[str, Dict[str, float]]:
            """Per-name span totals over THIS fit (all threads): a reused
            tracer's earlier fits are subtracted out."""
            out: Dict[str, Dict[str, float]] = {}
            for name, entry in trace.summary().items():
                prev = fit_summary_base.get(
                    name, {"count": 0, "seconds": 0.0, "self_seconds": 0.0}
                )
                count = entry["count"] - prev["count"]
                if count > 0:
                    out[name] = {
                        "count": count,
                        "seconds": entry["seconds"] - prev["seconds"],
                        "self_seconds": entry["self_seconds"] - prev["self_seconds"],
                    }
            return out

        def finish_trace() -> None:
            """Terminal tracing work: write trace.json, stop the metrics
            exporter, and detach a tracer that was passed as a fit argument
            (a preattached :attr:`tracer` stays; the argument form scopes to
            this fit)."""
            if tracing and trace_path is not None:
                try:
                    trace.save(trace_path)
                except OSError as exc:
                    logger.warning("trace.json not written to %s: %s", trace_path, exc)
            if tracer_from_arg:
                self.tracer = prior_tracer
            if self.metrics_exporter is not None:
                self.metrics_exporter.close()
                self.metrics_exporter = None
            if flight_logger is not None:
                # one msync so the ring survives machine death up to here;
                # SIGKILL-durability never depended on this close running
                flight_logger.close()

        # multi-host: stamp every event with this process's index so per-
        # process events.jsonl shards merge into ONE cross-host report
        # (obs.report computes step-time skew / the straggler index from it)
        event_process = jax.process_index() if jax.process_count() > 1 else None

        def emit(name: str, step=None, epoch=None, **payload) -> None:
            if run_logger is not None:
                if event_process is not None:
                    payload.setdefault("process_index", event_process)
                run_logger.log_event(
                    TrainerEvent(event=name, step=step, epoch=epoch, payload=payload)
                )
            if name == "on_preemption" and tracing and trace_path is not None:
                # flush the span tree NOW — the preemption paths emit this
                # BEFORE the shutdown-window checkpoint save, so even a save
                # that raises or a scheduler that stops waiting cannot lose
                # the trace of the run being evicted (on_fit_end re-saves
                # over this with the complete tree when it does run)
                try:
                    trace.save(trace_path)
                except OSError as exc:
                    logger.warning(
                        "trace.json not written to %s at preemption: %s",
                        trace_path, exc,
                    )
            if name == "on_fit_end":
                # every non-raising fit exit path ends in exactly one
                # on_fit_end; the raising paths call finish_trace themselves
                finish_trace()

        # -- resilience: anomaly detection / recovery / preemption ---------- #
        # host-side anomaly checks cost one device sync per step, so they
        # default on only where that sync already happens (explicit loggers)
        # or where they are required (a recovery policy); the in-jit sentinel
        # itself is always active and needs no host involvement
        check_anomalies = (
            detect_anomalies
            if detect_anomalies is not None
            else (recovery is not None or bool(explicit_loggers))
        )
        consecutive_bad, restarts = 0, 0
        initial_snapshot = None  # rollback target before any checkpoint exists

        # -- model-health diagnostics (replay_tpu.obs.health) --------------- #
        # the jitted step computes the health pytree every step (device-only);
        # the host fetches it every cadence steps — one small device_get —
        # attaches it to the next emitted on_train_step / the epoch-end event,
        # and feeds the early-warning watcher
        health_cfg = self.health
        health_watcher = health_cfg.watcher if health_cfg is not None else None
        # the scan program is health-free — chunking must not silently drop
        # the diagnostics, so every cadence-th step runs as an interleaved
        # health-instrumented single step (_chunk_schedule breaks chunks there)
        health_every = (
            health_cfg.cadence if (scan_chunk and health_cfg is not None) else None
        )
        if health_every:
            logger.info(
                "scan_chunk=%d with health cadence %d: every %dth step runs "
                "the health-instrumented per-step program (no silent health "
                "loss); cadence ≡ 1 (mod scan_chunk) keeps full chunks "
                "between health steps",
                scan_chunk, health_every, health_every,
            )
        pending_health: Optional[Dict[str, Any]] = None
        last_grad_norm = None  # device scalar; float()ed once per epoch
        # per-fit scope: a second fit must not attach the PREVIOUS fit's last
        # record to its epoch-end events (cadence may exceed a short epoch)
        self.last_health = None

        def do_recovery(reason: str, epoch: int) -> TrainState:
            with span("recovery", reason=reason):
                return _do_recovery(reason, epoch)

        def _do_recovery(reason: str, epoch: int) -> TrainState:
            """Roll back to the last checkpoint (else the initial snapshot),
            back the LR off, and return the state to continue from. The batch
            stream is NOT rewound — recovery moves forward through the data."""
            nonlocal restarts, consecutive_bad, step_base
            nonlocal pending_health, last_grad_norm
            restarts += 1
            consecutive_bad = 0
            step_base = None  # state.step jumps backward: refetch the base
            # the discarded trajectory's records must not be attributed to the
            # restored one: drop the un-emitted health record, the last grad
            # norm, and the watcher's EWMA baseline (pre-blowup norms resume)
            pending_health, last_grad_norm, self.last_health = None, None, None
            if health_watcher is not None:
                health_watcher.reset()
            if restarts > recovery.max_restarts:
                emit("on_recovery", epoch=epoch, reason=reason, restarts=restarts,
                     exhausted=True)
                # this raise skips on_fit_end: persist the trace NOW — the
                # rollback timeline is exactly what diagnosing this run needs
                finish_trace()
                msg = (
                    f"RecoveryPolicy budget exhausted: {restarts - 1} restarts "
                    f"(max_restarts={recovery.max_restarts}) did not stabilize "
                    f"the run (last trigger: {reason})"
                )
                raise RuntimeError(msg)
            target = (
                checkpoint_manager.latest_step() if checkpoint_manager is not None else None
            )
            if target is not None:
                restored = checkpoint_manager.restore(state, step=target)
                new_state = _place_tree(
                    restored, jax.tree.map(self._template_sharding, state)
                )
            else:
                new_state = jax.tree.map(lambda x: x.copy(), initial_snapshot)
            self._set_lr_scale(self._lr_scale * recovery.lr_backoff)
            logger.warning(
                "recovery %d/%d (%s): rolled back to %s, lr scale now %.3g",
                restarts, recovery.max_restarts, reason,
                f"checkpoint step {target}" if target is not None else "initial state",
                self._lr_scale,
            )
            emit("on_recovery", step=int(new_state.step), epoch=epoch, reason=reason,
                 restarts=restarts, restored_step=target, lr_scale=self._lr_scale)
            return new_state

        def save_mid_epoch(preempted: bool = False) -> None:
            # ONE position-stamping path for periodic and preemption saves:
            # resume reads the same metadata either way (epoch/n_steps are the
            # loop's live values at call time)
            extra: Dict[str, Any] = {"preempted": True} if preempted else {}
            if self._lr_scale != 1.0:  # recovery backoff survives the resume
                extra["lr_scale"] = self._lr_scale
            process_extra = None
            if cursor_source is not None:
                # the streaming batcher's exact position after n_steps batches
                # rides the sidecar, so resume SEEKS instead of rescanning;
                # cursors are recorded at produce time, so a prefetch/device-
                # feed stage reading ahead cannot outrun this lookup
                try:
                    cursor_meta = cursor_source.cursor_for(n_steps).to_metadata()
                except KeyError as exc:
                    logger.warning(
                        "stream cursor unavailable at step %d (%s); resume "
                        "will fall back to fast-forwarding the stream",
                        n_steps, exc,
                    )
                else:
                    extra["stream_cursor"] = cursor_meta
                    if jax.process_count() > 1:
                        # the shared sidecar has one writer (process 0), but
                        # every process streams its OWN disjoint shard: each
                        # rank's cursor rides its private per-process sidecar
                        process_extra = {"stream_cursor": cursor_meta}
            with span("checkpoint"):
                checkpoint_manager.save(
                    int(state.step),
                    state,
                    history=self.history,
                    metadata={
                        "mid_epoch": True,
                        "epoch": epoch,
                        "step_in_epoch": n_steps,
                        **extra,
                    },
                    process_metadata=process_extra,
                )
            emit("on_checkpoint", step=int(state.step), epoch=epoch,
                 mid_epoch=True, step_in_epoch=n_steps, **extra)

        install_preemption = (
            handle_preemption
            if handle_preemption is not None
            else checkpoint_manager is not None
        )
        preemption = PreemptionHandler() if install_preemption else None

        telemetry = StepTelemetry(warmup_steps=1)
        memory = MemoryMonitor()
        lr_schedule = (
            self.optimizer.scheduler.create(self.optimizer.learning_rate)
            if self.optimizer.scheduler is not None
            else None
        )

        def current_lr(step: int) -> float:
            # _lr_scale read at call time: recovery backoff shows up immediately
            # (every schedule kind is linear in its peak rate, so scaling the
            # schedule value equals rebuilding the schedule from the scaled lr)
            if lr_schedule is None:
                return float(self.optimizer.learning_rate) * self._lr_scale
            return float(lr_schedule(step)) * self._lr_scale

        def fit_end_payload() -> Dict[str, Any]:
            nonlocal profile_active
            payload = {
                "telemetry": telemetry.summary(),
                "compile": self.compile_tracker.report(),
                "peak_memory_bytes": memory.peak_bytes(),
                "history_len": len(self.history),
            }
            if memory.observed_samples:
                # the chunk-boundary sampling window (scan path): THIS fit's
                # high-water mark, vs the allocator's process-lifetime peak
                payload["peak_memory_sampled_bytes"] = memory.observed_peak_bytes
                payload["peak_memory_samples"] = memory.observed_samples
            if state is not None:  # sentinel-skipped updates over the run
                payload["bad_steps"] = int(state.bad_steps)
            input_record = input_summary()
            if input_record is not None:
                # cumulative feed efficiency: real vs grid tokens and the
                # steady effective-tokens/s (report renders, --compare gates)
                payload["input"] = input_record
            if tracing:
                # mirror the span layer into the event stream: whole-fit
                # goodput + THIS fit's per-span totals ride the terminal event
                payload["goodput"] = trace_window(fit_trace_base, fit_trace_t0)
                payload["spans"] = fit_spans()
            if profile_capture_dir is not None:
                if profile_active:
                    # a window still open (fit ended/preempted inside it):
                    # finalize the capture
                    profile_stack.close()
                    profile_active = False
                rooflines = self.analyze_programs()
                if rooflines:
                    payload["roofline"] = rooflines
            return payload

        emit(
            "on_fit_start",
            epoch=start_epoch,
            epochs=epochs,
            model=type(self.model).__name__,
            loss=type(self.loss).__name__,
            optimizer=self.optimizer.name,
            learning_rate=self.optimizer.learning_rate,
            mesh={axis: int(n) for axis, n in self.mesh.shape.items()},
            sharding_rules=self.sharding_rules.describe(),
            resumed=bool(resume and pending_restore_step is not None),
            **(self.precision.describe() if self.precision is not None else {}),
        )

        if profile_steps is not None:
            profile_start, profile_stop = int(profile_steps[0]), int(profile_steps[1])
            if profile_stop <= profile_start or profile_start < 0:
                msg = f"profile_steps must be a valid [start, stop) window, got {profile_steps}"
                raise ValueError(msg)

            def resolved_profile_dir() -> str:
                if profile_dir is not None:
                    return profile_dir
                queue = list(explicit_loggers)
                while queue:  # MultiLogger nests sinks: search them too
                    sink = queue.pop(0)
                    if isinstance(sink, JsonlLogger):
                        return os.path.join(sink.run_dir, "profile")
                    if isinstance(sink, MultiLogger):
                        queue.extend(sink.loggers)
                return "jax_profile"

        profile_stack = contextlib.ExitStack()
        profile_active = False
        profile_capture_dir: Optional[str] = None  # set when a window opens
        measured_total = 0  # steps actually executed by THIS fit call
        last_emitted_at = 0
        step_base = None  # int(state.step) fetched once; then tracked on host
        # effective-token accounting (docs/performance.md "Feeding the
        # beast"): real (non-padding, valid-row) vs grid tokens fed to the
        # device — the padding-waste number sequence packing exists to move
        tokens_real_total = 0
        tokens_grid_total = 0
        tick_tokens_real = 0
        tick_tokens_grid = 0

        def count_tokens(batch: Batch) -> None:
            nonlocal tokens_real_total, tokens_grid_total
            mask = batch.get(self.padding_mask_field)
            if mask is None or getattr(mask, "ndim", 0) != 2:
                return
            mask = np.asarray(mask)
            valid = batch.get("valid")
            if valid is not None:
                real = int(mask[np.asarray(valid)].sum())
            else:
                real = int(mask.sum())
            tokens_real_total += real
            tokens_grid_total += mask.size

        def input_summary() -> Optional[Dict[str, float]]:
            if not tokens_grid_total:
                return None
            steady = telemetry.summary()
            steps_per_sec = steady.get("steps_per_sec")
            tokens_per_step = tokens_real_total / max(measured_total, 1)
            effective = (
                tokens_per_step * steps_per_sec
                if steps_per_sec is not None and math.isfinite(steps_per_sec)
                else float("nan")
            )
            return {
                "tokens_real": tokens_real_total,
                "tokens_grid": tokens_grid_total,
                "padding_fraction": 1.0 - tokens_real_total / tokens_grid_total,
                "effective_tokens_per_sec": effective,
            }

        def telemetry_tick(batch: Batch) -> Dict[str, float]:
            """Fold the steps since the last tick into the telemetry window
            (shared by the per-step emit path and the epoch-tail flush)."""
            nonlocal last_emitted_at, tick_tokens_real, tick_tokens_grid
            delta = measured_total - last_emitted_at
            last_emitted_at = measured_total
            reference = batch.get(self.padding_mask_field)
            rows = (
                int(np.asarray(reference).shape[0]) if reference is not None else None
            )
            tick = telemetry.tick(samples=rows * delta if rows else None, steps=delta)
            window_real = tokens_real_total - tick_tokens_real
            window_grid = tokens_grid_total - tick_tokens_grid
            tick_tokens_real, tick_tokens_grid = tokens_real_total, tokens_grid_total
            nan = float("nan")
            tick["padding_fraction"] = (
                1.0 - window_real / window_grid if window_grid else nan
            )
            tick["effective_tokens_per_sec"] = (
                window_real / delta * tick["steps_per_sec"] if delta else nan
            )
            return tick

        if pending_restore_step is not None and start_epoch >= epochs:
            # run already complete: restore the checkpoint and return it instead
            # of raising "received no batches" — the monitored best when one is
            # marked (what the uninterrupted fit returned), latest otherwise
            first = next(iter(batches_for(0)), None)
            if first is None:
                msg = "fit() received no batches"
                raise ValueError(msg)
            template = self.init_state(first)
            restore_step = pending_restore_step
            if monitor is not None and resumed_best_step is not None:
                restore_step = resumed_best_step
            restored = checkpoint_manager.restore(template, step=restore_step)
            logger.info("resume: run already complete at step %d", restore_step)
            emit("on_fit_end", step=restore_step, epoch=start_epoch,
                 note="resume: run already complete", **fit_end_payload())
            return _place_tree(restored, jax.tree.map(self._template_sharding, template))

        def account_step(
            batch: Batch,
            loss_value,
            step_metrics: Mapping[str, Any],
            epoch: int,
            step_id: Optional[int] = None,
            bad_total: Optional[int] = None,
            on_host: bool = False,
        ) -> bool:
            """Post-execution bookkeeping for ONE optimizer step — epoch
            loss/sentinel accumulation, health fetch + watcher, anomaly
            events, profiler-window close, per-step event emission — shared
            verbatim by the per-step loop and the scan fan-out. The fan-out
            passes host numpy metrics (``on_host=True``; the chunk's [K]
            arrays were already fetched in one sync) plus explicit
            ``step_id``/``bad_total``, because ``state.step``/``bad_steps``
            already sit at the chunk END during fan-out. Returns True when a
            recovery rollback fired, so a chunked caller discards the rest of
            its chunk's pre-rollback steps.
            """
            nonlocal epoch_loss, epoch_good, n_steps, measured_total
            nonlocal last_grad_norm, pending_health, consecutive_bad, step_base
            nonlocal state, profile_active
            rolled_back = False
            good = step_metrics["good"]
            if on_host:
                # same IEEE f32 adds as the device accumulation below, on the
                # already-fetched values — bitwise-identical epoch averages
                safe_loss = np.float32(loss_value) if bool(good) else np.float32(0.0)
                good_flag = np.int32(bool(good))
                if epoch_loss is not None and not isinstance(epoch_loss, np.generic):
                    # an interleaved device-accumulated step (health single
                    # step) made the accumulator a device scalar: fold it back
                    # to host ONCE — its value is already fenced by that
                    # step's health fetch — so the chunk fan-out below never
                    # dispatches K tiny device adds per chunk
                    epoch_loss = np.float32(epoch_loss)
                    epoch_good = np.int32(epoch_good)
            else:
                # accumulate on device: float() here would sync every step.
                # Sentinel-skipped steps contribute 0 (their loss is
                # non-finite and would poison the epoch average).
                safe_loss = jnp.where(good, loss_value, 0.0)
                good_flag = good.astype(jnp.int32)
            epoch_loss = safe_loss if epoch_loss is None else epoch_loss + safe_loss
            epoch_good = good_flag if epoch_good is None else epoch_good + good_flag
            n_steps += 1
            measured_total += 1
            count_tokens(batch)
            last_grad_norm = step_metrics["grad_norm"]
            if (
                health_cfg is not None
                and "health" in step_metrics
                and measured_total % health_cfg.cadence == 0
            ):
                # THE health sync: one device_get of the small health
                # pytree — it blocks on the step's outputs, so the
                # record is loss-fenced like a StepTelemetry tick
                fetched = jax.device_get(step_metrics["health"])
                health_record = jax.tree.map(
                    lambda x: x.tolist() if getattr(x, "ndim", 0) else float(x),
                    fetched,
                )
                self.last_health = health_record
                pending_health = health_record
                if health_watcher is not None:
                    warning = health_watcher.observe(health_record)
                    if warning is not None:
                        if step_base is None:
                            step_base = int(state.step) - measured_total
                        emit(
                            "on_health_warning",
                            step=step_base + measured_total,
                            epoch=epoch,
                            **warning,
                        )
                        if health_watcher.trigger_recovery and recovery is not None:
                            state = do_recovery("health_warning", epoch)
                            epoch_loss, epoch_good = None, None
                            rolled_back = True
            if check_anomalies or recovery is not None:
                # a recovery policy must see every bad step even when
                # detect_anomalies=False silenced the event emission
                if not bool(step_metrics["good"]):
                    consecutive_bad += 1
                    if check_anomalies:
                        emit(
                            "on_anomaly",
                            step=int(state.step) if step_id is None else step_id,
                            epoch=epoch,
                            loss=float(loss_value),
                            grad_norm=float(step_metrics["grad_norm"]),
                            consecutive_bad=consecutive_bad,
                            bad_steps_total=(
                                int(state.bad_steps) if bad_total is None else bad_total
                            ),
                        )
                    if (
                        recovery is not None
                        and consecutive_bad >= recovery.max_consecutive_bad
                    ):
                        state = do_recovery("consecutive_bad_steps", epoch)
                        # the epoch average must describe the RESTORED
                        # trajectory, not the discarded one
                        epoch_loss, epoch_good = None, None
                        rolled_back = True
                else:
                    consecutive_bad = 0
            if profile_active and measured_total >= profile_stop:
                profile_stack.close()
                profile_active = False
            if event_every and measured_total % event_every == 0:
                if step_base is None:
                    # one-time base fetch: state.step then advances in
                    # lockstep with measured_total within this fit
                    step_base = int(state.step) - measured_total
                emit_step = step_base + measured_total
                loss_f = float(loss_value)  # THE per-event device sync
                tick = telemetry_tick(batch)
                emit(
                    "on_train_step",
                    step=emit_step,
                    epoch=epoch,
                    loss=loss_f,
                    # the rate the optimizer APPLIED: optax schedules
                    # are indexed by steps completed before the update
                    lr=current_lr(emit_step - 1),
                    samples_per_sec=tick["samples_per_sec"],
                    steps_per_sec=tick["steps_per_sec"],
                    step_seconds=tick["step_seconds"],
                    # padding-waste telemetry: the feed-efficiency numbers
                    # packing/bucketing exist to move (obs gauges + SLOs)
                    effective_tokens_per_sec=tick["effective_tokens_per_sec"],
                    padding_fraction=tick["padding_fraction"],
                    # a health record fetched since the last emission
                    # rides the next step event (cadences may differ)
                    **({"health": pending_health} if pending_health is not None else {}),
                    # what the model counted in this step (sparse experts:
                    # `expert_load` per layer and held expert, `dropped_assignments`)
                    **(
                        {
                            "counters": {
                                name: np.asarray(value).tolist()
                                for name, value in step_metrics["counters"].items()
                            }
                        }
                        if "counters" in step_metrics
                        else {}
                    ),
                )
                pending_health = None
            return rolled_back

        stopped_early = False
        cursor_source = None  # the current epoch's resumable batch source
        # the per-epoch goodput window: opens here and RE-opens right after
        # each on_epoch_end, so the inter-epoch tail (the end-of-epoch
        # checkpoint save, best tracking) lands in the NEXT epoch's window —
        # consecutive windows tile the fit wall-clock with no gaps
        epoch_trace_base = trace.snapshot(only_current_thread=True) if tracing else {}
        epoch_trace_t0 = time.perf_counter()
        # profile_stack closes a still-open profiler window on any exit; the
        # preemption handler restores the previous SIGTERM/SIGINT handlers
        with profile_stack, (preemption or contextlib.nullcontext()):
            for epoch in range(start_epoch, epochs):
                # n_steps = position in the epoch's batch stream (skipped batches
                # included, keeping checkpoint_every aligned across resumes);
                # epoch_good = device count of batches that actually trained AND
                # passed the sentinel on THIS process
                epoch_loss, epoch_good, n_steps = None, None, 0
                skipped = 0
                last_batch = None
                epoch_needs_mark = True  # re-mark per epoch: discounts the
                # inter-epoch validation/checkpoint gap from the telemetry window
                epoch_batches = batches_for(epoch)
                cursor_source = (
                    epoch_batches
                    if getattr(epoch_batches, "supports_cursor", False)
                    else None
                )
                if (
                    pending_stream_cursor is not None
                    and epoch == start_epoch
                    and cursor_source is not None
                ):
                    recorded = int(pending_stream_cursor.get("batches", -1))
                    if recorded == skip_steps:
                        # seek: the batcher resumes mid-epoch bit-for-bit
                        # without re-reading the skipped slabs
                        cursor_source.restore_cursor(pending_stream_cursor)
                        skipped = skip_steps  # nothing left to consume-and-drop
                        n_steps = skip_steps
                    else:
                        logger.warning(
                            "stream cursor records %d batches but the "
                            "checkpoint position is %d; falling back to "
                            "fast-forward", recorded, skip_steps,
                        )
                    pending_stream_cursor = None
                if scan_chunk:
                    # a factory callable hid its batcher from the fit-start
                    # check: reject what it actually returned, before any
                    # step of this epoch runs
                    reject_bucketed(epoch_batches)
                if prefetch:
                    from replay_tpu.data.nn.prefetch import prefetch as _prefetch

                    epoch_batches = _prefetch(iter(epoch_batches), depth=prefetch)
                if tracing and not scan_chunk:
                    # times every next() as data_wait — i.e. what the prefetch
                    # queue could NOT hide from the step loop. (Chunked, the
                    # stream is consumed on the FEEDER thread; the fit
                    # thread's data_wait is its wait on the feed, below.)
                    epoch_batches = traced_iterator(epoch_batches, trace)
                if scan_chunk:
                    # ---- scan-chunked epoch: K steps per XLA dispatch, fed by
                    # a device-prefetch stage (docs/performance.md "Closing
                    # the dispatch gap") -------------------------------------
                    from replay_tpu.data.nn.prefetch import DevicePrefetcher

                    batch_iter = iter(epoch_batches)
                    first_batch = None
                    for batch in batch_iter:
                        # the per-step loop's per-batch preamble (state init /
                        # restore / recovery snapshot / resume fast-forward),
                        # run on the fit thread BEFORE the feeder takes over
                        if state is None:
                            state = self.init_state(batch)
                            if pending_restore_step is not None:
                                restored = checkpoint_manager.restore(
                                    state, step=pending_restore_step
                                )
                                state = _place_tree(
                                    restored, jax.tree.map(self._template_sharding, state)
                                )
                                pending_restore_step = None
                        if recovery is not None and initial_snapshot is None:
                            # rollback target until the first checkpoint lands;
                            # .copy() detaches from the donation chain
                            initial_snapshot = jax.tree.map(lambda x: x.copy(), state)
                        if epoch == start_epoch and skipped < skip_steps:
                            skipped += 1
                            n_steps += 1
                            continue
                        first_batch = batch
                        break
                    if first_batch is not None:
                        stream = itertools.chain([first_batch], batch_iter)
                        items = _chunk_schedule(
                            stream, scan_chunk, health_every, start=measured_total
                        )
                        place = self._chunk_placer(trace, chunk_stages.chunk)
                        feed = (
                            DevicePrefetcher(items, place, depth=1)
                            if device_feed
                            # feed off: the same stack + placement runs on the
                            # FIT thread, inside its wait on the stream (stack
                            # and h2d land in the goodput fractions — the A/B
                            # shows exactly what the feed would have hidden)
                            else ((item, place(item)) for item in items)
                        )
                        chunk_stages.new_epoch()
                        try:
                            # every pull is a data_wait stage: the fit
                            # thread's wait on the feed
                            for item, fed in chunk_stages.feed(feed):
                                if epoch_needs_mark:
                                    telemetry.mark()
                                    epoch_needs_mark = False
                                kind, payload = item
                                steps_before = n_steps
                                if kind == "step":
                                    # health-cadence / short-tail single step
                                    # through the existing per-step program
                                    # (the health-instrumented variant when a
                                    # HealthConfig is attached)
                                    if (
                                        profile_steps is not None
                                        and not profile_active
                                        and measured_total == profile_start
                                    ):
                                        from replay_tpu.utils.profiling import (
                                            trace as _profiler_trace,
                                        )

                                        profile_capture_dir = resolved_profile_dir()
                                        profile_stack.enter_context(
                                            _profiler_trace(profile_capture_dir)
                                        )
                                        profile_active = True
                                    state, loss_value = self.traced_train_step(
                                        state, payload
                                    )
                                    account_step(
                                        payload, loss_value, self.last_step_metrics, epoch
                                    )
                                    last_batch = payload
                                else:  # "scan": K optimizer steps in ONE dispatch
                                    chunk = payload
                                    k = len(chunk)
                                    if (
                                        profile_steps is not None
                                        and not profile_active
                                        and measured_total <= profile_start < measured_total + k
                                    ):
                                        # the window rounds out to chunk boundaries
                                        from replay_tpu.utils.profiling import (
                                            trace as _profiler_trace,
                                        )

                                        profile_capture_dir = resolved_profile_dir()
                                        profile_stack.enter_context(
                                            _profiler_trace(profile_capture_dir)
                                        )
                                        profile_active = True
                                    scan_fn = self._ensure_train_scan()
                                    placed, feeder = fed
                                    compile_before = (
                                        self.compile_tracker.total_compile_seconds
                                    )
                                    # train_step = dispatch (the enqueue; a
                                    # compile is carved out of it) + device_wait
                                    # (the chunk's ONE host sync: the [K]
                                    # per-step metrics fence the span and feed
                                    # the fan-out accounting below)
                                    with chunk_stages.stage("train_step", steps=k):
                                        with chunk_stages.stage("dispatch") as dispatch:
                                            self._record_template(
                                                "train_scan", scan_fn, state, placed
                                            )
                                            with self.compile_tracker.observe("train_scan"):
                                                state, chunk_metrics = scan_fn(state, placed)
                                        with chunk_stages.stage("device_wait") as device_wait:
                                            losses = np.asarray(chunk_metrics["loss"])
                                            goods = np.asarray(chunk_metrics["good"])
                                            grad_norms = np.asarray(chunk_metrics["grad_norm"])
                                            counters = {
                                                name: np.asarray(value)
                                                for name, value in chunk_metrics.get(
                                                    "counters", {}
                                                ).items()
                                            }
                                    compile_delta = (
                                        self.compile_tracker.total_compile_seconds
                                        - compile_before
                                    )
                                    # the chunk's record goes to the stage log
                                    # and `account` opens: everything from here
                                    # to the next pull on the feed
                                    chunk_stages.synced(
                                        k, dispatch, device_wait, compile_delta > 0, feeder,
                                        {n: v.tolist() for n, v in counters.items()},
                                        # the route the loss head took when this
                                        # trainer's programs were traced (one set
                                        # of shapes: the step's and the scan's agree)
                                        ce_fused_steps=(
                                            k if getattr(self.loss, "route", PLAIN) != PLAIN else 0
                                        ),
                                    )
                                    # the scan has read the chunk's device
                                    # copy: let it go here, inside `account`
                                    placed = fed = None
                                    if tracing and compile_delta > 0:
                                        # the seconds jax said it spent tracing,
                                        # lowering and compiling (or fetching)
                                        # inside the dispatch (obs.trace's
                                        # listeners), not the whole call
                                        trace.carve(
                                            dispatch.span, "compile", dispatch.compile_seconds
                                        )
                                    self.last_step_metrics = chunk_metrics
                                    # chunk-boundary HBM sample: the scan path
                                    # otherwise only snapshots memory per
                                    # epoch; a CPU backend (no allocator
                                    # stats) makes this a no-op
                                    memory.observe()
                                    if step_base is None:
                                        # state.step already sits at the chunk END
                                        step_base = int(state.step) - (measured_total + k)
                                    bad_in_chunk = np.cumsum(~goods)
                                    bad_before = None
                                    if (
                                        check_anomalies or recovery is not None
                                    ) and bad_in_chunk[-1]:
                                        bad_before = int(state.bad_steps) - int(
                                            bad_in_chunk[-1]
                                        )
                                    for i in range(k):
                                        rolled_back = account_step(
                                            chunk[i],
                                            losses[i],
                                            {
                                                "loss": losses[i],
                                                "good": goods[i],
                                                "grad_norm": grad_norms[i],
                                                **(
                                                    {"counters": {n: v[i] for n, v in counters.items()}}
                                                    if counters
                                                    else {}
                                                ),
                                            },
                                            epoch,
                                            step_id=step_base + measured_total + 1,
                                            bad_total=(
                                                bad_before + int(bad_in_chunk[i])
                                                if bad_before is not None
                                                else None
                                            ),
                                            on_host=True,
                                        )
                                        if rolled_back:
                                            # the rest of the chunk belongs to
                                            # the DISCARDED trajectory: its
                                            # batches stay consumed (the stream
                                            # position advances, keeping
                                            # checkpoint/resume alignment) but
                                            # are not accounted
                                            n_steps += k - (i + 1)
                                            measured_total += k - (i + 1)
                                            break
                                    last_batch = chunk[-1]
                                boundary_saved = False
                                if (
                                    checkpoint_every
                                    and checkpoint_manager is not None
                                    and n_steps // checkpoint_every
                                    > steps_before // checkpoint_every
                                ):
                                    # a checkpoint_every boundary crossed INSIDE
                                    # the chunk saves once at the chunk end —
                                    # the only point this state exists; the
                                    # recorded position is the current n_steps
                                    save_mid_epoch()
                                    boundary_saved = True
                                if preemption is not None and preemption.requested:
                                    # chunk-boundary preemption exit (same
                                    # contract as the per-step path); the
                                    # event — and the trace flush it carries —
                                    # lands BEFORE the shutdown-window save,
                                    # so a save that dies cannot take the
                                    # span tree with it
                                    emit("on_preemption", step=int(state.step),
                                         epoch=epoch, signal=preemption.signal_name)
                                    if checkpoint_manager is not None and not boundary_saved:
                                        save_mid_epoch(preempted=True)
                                    logger.warning(
                                        "preemption: checkpoint saved at step %d; "
                                        "exiting fit",
                                        int(state.step),
                                    )
                                    emit("on_fit_end", step=int(state.step),
                                         epoch=epoch, preempted=True,
                                         **fit_end_payload())
                                    return state
                        finally:
                            chunk_stages.close()
                            if isinstance(feed, DevicePrefetcher):
                                feed.close()
                    epoch_batches = ()  # the per-step loop below is skipped
                for batch in epoch_batches:
                    if state is None:
                        state = self.init_state(batch)
                        if pending_restore_step is not None:
                            restored = checkpoint_manager.restore(
                                state, step=pending_restore_step
                            )
                            state = _place_tree(
                                restored, jax.tree.map(self._template_sharding, state)
                            )
                            pending_restore_step = None
                    if recovery is not None and initial_snapshot is None:
                        # rollback target until the first checkpoint lands;
                        # .copy() detaches from the donation chain
                        initial_snapshot = jax.tree.map(lambda x: x.copy(), state)
                    if epoch == start_epoch and skipped < skip_steps:
                        # fast-forward: the batch stream is deterministic per epoch,
                        # so consuming without stepping lands on the exact position
                        skipped += 1
                        n_steps += 1
                        continue
                    if epoch_needs_mark:
                        telemetry.mark()
                        epoch_needs_mark = False
                    if (
                        profile_steps is not None
                        and not profile_active
                        and measured_total == profile_start
                    ):
                        # aliased: `trace` is the fit-scope Tracer handle
                        from replay_tpu.utils.profiling import trace as _profiler_trace

                        profile_capture_dir = resolved_profile_dir()
                        profile_stack.enter_context(_profiler_trace(profile_capture_dir))
                        profile_active = True
                    # traced: loss-fenced span + compile carve; untraced: the
                    # plain async-dispatch step
                    state, loss_value = self.traced_train_step(state, batch)
                    account_step(batch, loss_value, self.last_step_metrics, epoch)
                    last_batch = batch
                    boundary_saved = False
                    if (
                        checkpoint_every
                        and checkpoint_manager is not None
                        and n_steps % checkpoint_every == 0
                    ):
                        save_mid_epoch()
                        boundary_saved = True
                    if preemption is not None and preemption.requested:
                        # the signal handler only set a flag; this is the step
                        # boundary it asked for — save a position-stamped
                        # checkpoint and exit cleanly (resume=True continues
                        # from this exact batch). A periodic save that just
                        # landed on this same step already recorded the
                        # position — don't serialize the state twice in the
                        # shutdown window.
                        emit("on_preemption", step=int(state.step), epoch=epoch,
                             signal=preemption.signal_name)
                        if checkpoint_manager is not None and not boundary_saved:
                            save_mid_epoch(preempted=True)
                        logger.warning(
                            "preemption: checkpoint saved at step %d; exiting fit",
                            int(state.step),
                        )
                        emit("on_fit_end", step=int(state.step), epoch=epoch,
                             preempted=True, **fit_end_payload())
                        return state
                # a resumed epoch averages only the steps THIS process ran, and
                # the average runs over sentinel-approved steps only (skipped
                # steps contributed 0 loss); NaN when nothing was measured or
                # every measured step was bad
                good_count = int(epoch_good) if epoch_good is not None else 0
                record = {
                    "epoch": epoch,
                    "train_loss": (
                        float(epoch_loss) / good_count if good_count else float("nan")
                    ),
                }
                if event_every and measured_total > last_emitted_at and last_batch is not None:
                    # flush the tail steps into the telemetry window HERE —
                    # float(epoch_loss) above already fenced them, and ticking
                    # after validation would dilute the steady-state rate;
                    # fits shorter than the event cadence get real numbers
                    telemetry_tick(last_batch)
                if val_batches is not None:
                    # several validation streams (the reference's sequential
                    # CombinedLoader): a dict of factories gets per-stream prefixes
                    streams = (
                        val_batches if isinstance(val_batches, dict) else {"": val_batches}
                    )
                    with span("validation"):
                        for stream_name, factory in streams.items():
                            stream_metrics = self.validate(
                                state,
                                factory(),
                                metrics=metrics,
                                top_k=top_k,
                                item_count=item_count,
                                postprocessors=postprocessors,
                            )
                            prefix = f"{stream_name}/" if stream_name else ""
                            record.update(
                                {f"{prefix}{k}": v for k, v in stream_metrics.items()}
                            )
                    emit("on_validation_end",
                         step=int(state.step) if state is not None else None,
                         epoch=epoch, record=record)
                self.history.append(record)
                epoch_payload: Dict[str, Any] = {"record": record}
                if state is not None:
                    # reliability rollups: obs.report --compare gates on the
                    # cumulative sentinel count, not just throughput/MFU
                    epoch_payload["bad_steps"] = int(state.bad_steps)
                if last_grad_norm is not None:
                    # the last executed step's global grad norm (one scalar
                    # sync per epoch; non-finite serializes as JSON null)
                    epoch_payload["grad_norm"] = float(last_grad_norm)
                input_record = input_summary()
                if input_record is not None:  # cumulative feed efficiency
                    epoch_payload["input"] = input_record
                if health_cfg is not None and self.last_health is not None:
                    epoch_payload["health"] = self.last_health
                if tracing:
                    # the goodput contract: phase fractions over this epoch's
                    # wall clock, summing to 1.0 (docs/performance.md)
                    epoch_payload["goodput"] = trace_window(
                        epoch_trace_base, epoch_trace_t0
                    )
                    # re-open the window HERE: what follows (this epoch's
                    # checkpoint save, best tracking) bills to the next epoch
                    epoch_trace_base = trace.snapshot(only_current_thread=True)
                    epoch_trace_t0 = time.perf_counter()
                emit("on_epoch_end",
                     step=int(state.step) if state is not None else None,
                     epoch=epoch, **epoch_payload)
                if not log_every:
                    # log_every=0 silences the per-step prints only — the
                    # per-epoch record line predates the event layer and stays
                    logger.info("epoch %d: %s", epoch, record)

                if (
                    recovery is not None
                    and monitor is not None
                    and monitor in record
                    # epoch_good is None when nothing fed the average — a
                    # fully-fast-forwarded resumed epoch, or a mid-epoch
                    # rollback that already answered this incident (the reset
                    # above) — so the NaN record must not burn a second restart
                    and epoch_good is not None
                ):
                    # epoch-level blowup guard: the monitored value went
                    # non-finite, or worsened past blowup_factor x the best —
                    # roll back BEFORE this epoch's checkpoint could become the
                    # rollback target, and skip its best-tracking entirely
                    value = float(record[monitor])
                    blown = not math.isfinite(value)
                    if (
                        not blown
                        and recovery.blowup_factor is not None
                        and best_value is not None
                        and math.isfinite(best_value)
                    ):
                        blown = (
                            value > best_value * recovery.blowup_factor
                            if mode == "min"
                            else value < best_value / recovery.blowup_factor
                        )
                    if blown:
                        state = do_recovery("metric_blowup", epoch)
                        continue

                improved = False
                if monitor is not None:
                    if monitor not in record:
                        msg = f"monitor '{monitor}' not in the epoch record {sorted(record)}"
                        raise KeyError(msg)
                    value = record[monitor]
                    improved = (
                        best_value is None
                        or (mode == "max" and value > best_value)
                        or (mode == "min" and value < best_value)
                    )
                    if improved:
                        # deep-copy: the NEXT train_step donates this state's buffers
                        # (donate_argnums=0), which would leave a dead pytree here
                        best_state = jax.tree.map(lambda x: x.copy(), state)
                        best_value, stale_epochs = value, 0
                    else:
                        stale_epochs += 1
                if checkpoint_manager is not None and state is not None:
                    metadata = {"epoch": epoch}
                    if self._lr_scale != 1.0:  # recovery backoff survives resume
                        metadata["lr_scale"] = self._lr_scale
                    if monitor:
                        metadata.update({"best": improved, monitor: value})
                    with span("checkpoint"):
                        checkpoint_manager.save(
                            int(state.step),
                            state,
                            history=self.history,
                            metadata=metadata,
                        )
                        if improved:
                            checkpoint_manager.mark_best(int(state.step))
                    emit("on_checkpoint", step=int(state.step), epoch=epoch,
                         mid_epoch=False, best=bool(improved) if monitor else None)
                if monitor is not None and patience is not None and stale_epochs >= patience:
                    logger.info(
                        "early stop: no %s improvement for %d epochs", monitor, patience
                    )
                    stopped_early = True
                    break
        if state is None:
            msg = "fit() received no batches"
            raise ValueError(msg)
        if best_state is None and resumed_best_step is not None and monitor is not None:
            # no post-resume epoch beat the pre-kill best: return THAT state,
            # exactly as the uninterrupted run would have
            restored = checkpoint_manager.restore(state, step=resumed_best_step)
            best_state = _place_tree(
                restored, jax.tree.map(self._template_sharding, state)
            )
        emit("on_fit_end", step=int(state.step), stopped_early=stopped_early,
             **fit_end_payload())
        return best_state if best_state is not None else state

    # the public entry is the thin exception-safe wrapper above; its help()
    # should read as the real thing
    fit.__doc__ = _fit_impl.__doc__

    # -- eval / predict ---------------------------------------------------- #
    def _build_eval_logits(self):
        model = self.model

        def eval_logits(params, batch: Batch, candidates: Optional[jnp.ndarray]):
            kwargs = {name: batch[name] for name in self._inference_params if name in batch}
            return model.apply(
                {"params": params},
                **kwargs,
                candidates_to_score=candidates,
                method=type(model).forward_inference,
            )

        return jax.jit(self.compile_tracker.wrap(self._scoped(eval_logits), "eval_logits"))

    def predict_logits(
        self, state: TrainState, batch: Batch, candidates: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        """Next-item logits [B, num_items] (or [B, K] for candidates)."""
        if self._eval_logits is None:
            self._eval_logits = self._build_eval_logits()
        return self._eval_logits(state.params, self._put_batch(batch), candidates)

    # -- eval-time catalog cache (TwoTower-style item towers) --------------- #
    def _precompute_catalog(self, state: TrainState, batch: Batch):
        """Encode the whole catalog ONCE per evaluation pass when the model has
        an item tower (the reference ItemTower's eval cache, invalidated by
        training simply because each validate/predict call recomputes it)."""
        model = self.model
        if not hasattr(type(model), "encode_items"):
            return None
        if self._catalog_fn is None:
            self._catalog_fn = jax.jit(
                self.compile_tracker.wrap(
                    self._scoped(
                        lambda params, features: model.apply(
                            {"params": params},
                            item_feature_tensors=features,
                            method=type(model).encode_items,
                        )
                    ),
                    "encode_items",
                )
            )
        return self._catalog_fn(state.params, batch.get("item_feature_tensors"))

    def _get_query_embeddings_fn(self):
        model = self.model
        if self._query_embeddings_fn is None:

            def embed(params, feature_tensors, padding_mask):
                return model.apply(
                    {"params": params},
                    feature_tensors,
                    padding_mask,
                    method=type(model).get_query_embeddings,
                )

            self._query_embeddings_fn = jax.jit(
                self.compile_tracker.wrap(self._scoped(embed), "query_embeddings")
            )
        return self._query_embeddings_fn

    def _catalog_logits(self, state: TrainState, batch: Batch, catalog) -> jnp.ndarray:
        """Score query embeddings against precomputed catalog embeddings."""
        batch = self._put_batch(batch)
        queries = self._get_query_embeddings_fn()(
            state.params, batch[self.feature_field], batch[self.padding_mask_field]
        )
        return queries @ catalog.T

    def validate(
        self,
        state: TrainState,
        batches: Iterable[Batch],
        metrics: Sequence[str] = ("ndcg", "recall", "map"),
        top_k: Sequence[int] = (1, 5, 10),
        item_count: Optional[int] = None,
        postprocessors: Sequence[Callable] = (),
    ) -> Mapping[str, float]:
        """Top-k metrics over validation batches (ground_truth/train padded with
        −1, per MetricsBuilder's contract)."""
        import itertools

        builder = MetricsBuilder(metrics=metrics, top_k=top_k, item_count=item_count)
        max_k = builder.max_k
        iterator = iter(batches)
        try:
            first = next(iterator)
        except StopIteration:
            return builder.get_metrics()
        catalog = self._precompute_catalog(state, first)
        for batch in itertools.chain([first], iterator):
            if catalog is not None:
                logits = self._catalog_logits(state, batch, catalog)
            else:
                logits = self.predict_logits(state, batch)
            for post in postprocessors:
                logits = post(logits, batch)
            _, top_ids = jax.lax.top_k(logits, max_k)
            builder.add_prediction(
                _local_rows(top_ids), batch["ground_truth"], batch.get("train"),
                batch.get("valid"),
            )
        if jax.process_count() > 1:
            # every host accumulated only ITS shard: sum the (psum-able) states
            # across hosts — the reference's sync_dist=True reduction
            from jax.experimental import multihost_utils

            gathered = multihost_utils.process_allgather(builder.state())
            builder.load_state(jax.tree.map(lambda x: np.asarray(x).sum(axis=0), gathered))
        return builder.get_metrics()

    def predict_top_k(
        self,
        state: TrainState,
        batches: Iterable[Batch],
        k: int,
        postprocessors: Sequence[Callable] = (),
        candidates: Optional[jnp.ndarray] = None,
        query_id_field: str = "query_id",
    ):
        """Top-k recommendations as (query_ids, item_ids, scores) numpy arrays.

        The per-batch path mirrors the reference predictions callback
        (replay/nn/lightning/callback/predictions_callback.py:81-108): score →
        postprocess → top-k → accumulate; candidate ids are mapped back to
        catalog ids when ``candidates`` is given.
        """
        import itertools

        if isinstance(batches, Mapping):  # a single batch: iterating it would
            batches = [batches]  # silently yield its string keys
        all_queries, all_items, all_scores = [], [], []
        iterator = iter(batches)
        try:
            first = next(iterator)
        except StopIteration:
            iterator, first = iter(()), None
        catalog = (
            self._precompute_catalog(state, first)
            if candidates is None and first is not None
            else None
        )
        batches = itertools.chain([first], iterator) if first is not None else iterator
        for batch in batches:
            if catalog is not None:
                logits = self._catalog_logits(state, batch, catalog)
            else:
                logits = self.predict_logits(state, batch, candidates)
            if candidates is not None:
                # visible to postprocessors (SeenItemsFilter's candidate matching)
                batch = {**batch, "candidates_to_score": jnp.asarray(candidates)}
            for post in postprocessors:
                logits = post(logits, batch)
            scores, top_idx = jax.lax.top_k(logits, k)
            if candidates is not None:
                top_ids = jnp.asarray(candidates)[top_idx]
            else:
                top_ids = top_idx
            valid = np.asarray(batch.get("valid", np.ones(top_ids.shape[0], bool)))
            all_items.append(np.asarray(top_ids)[valid])
            all_scores.append(np.asarray(scores)[valid])
            if query_id_field in batch:
                all_queries.append(np.asarray(batch[query_id_field])[valid])
        items = np.concatenate(all_items) if all_items else np.zeros((0, k), np.int32)
        scores = np.concatenate(all_scores) if all_scores else np.zeros((0, k), np.float32)
        queries = np.concatenate(all_queries) if all_queries else np.arange(items.shape[0])
        return queries, items, scores

    def predict_query_embeddings(self, state: TrainState, batches: Iterable[Batch]):
        """Last-position query embeddings [N, E] (the reference
        QueryEmbeddingsPredictionCallback), e.g. for two-stage features."""
        fn = self._get_query_embeddings_fn()
        chunks, queries = [], []
        for batch in batches:
            batch = self._put_batch(batch)
            embeddings = fn(state.params, batch[self.feature_field], batch[self.padding_mask_field])
            valid = np.asarray(batch.get("valid", np.ones(embeddings.shape[0], bool)))
            chunks.append(np.asarray(embeddings)[valid])
            if "query_id" in batch:
                queries.append(np.asarray(batch["query_id"])[valid])
        embeddings = np.concatenate(chunks) if chunks else np.zeros((0, 0))
        query_ids = np.concatenate(queries) if queries else np.arange(len(embeddings))
        return query_ids, embeddings

    def resize_vocabulary(
        self,
        state: TrainState,
        new_cardinality: int,
        init_tensor=None,
        carry_opt_state: bool = True,
        init: str = "mean",
        rng: Optional[jax.Array] = None,
    ) -> TrainState:
        """Catalog growth between — or DURING — retrains: item-table surgery
        with the optimizer moments resized in lockstep.

        ``carry_opt_state=True`` (default, the continual-training path) keeps
        every trained row's Adam moments and zero-initializes the cold rows'
        (``vocabulary.resize_optimizer_state``) so a mid-run grow neither
        crashes deep in optax nor silently resets the optimizer; ``False``
        restores the old between-retrains behavior (fresh ``tx.init`` state).
        ``init`` picks the cold-row warm start when no ``init_tensor`` is
        given: ``"mean"`` (the reference default) or ``"xavier"`` (the
        reference's expansion recipe, ``set_item_embeddings_by_size``).
        Step/rng carry over either way."""
        from replay_tpu.nn.vocabulary import (
            resize_item_embeddings,
            set_item_embeddings_by_size,
        )
        from replay_tpu.parallel.sharding import params_shardings

        host_params = jax.tree.map(np.asarray, state.params)
        host_opt = (
            jax.tree.map(np.asarray, state.opt_state) if carry_opt_state else None
        )
        if init == "xavier" and init_tensor is None:
            result = set_item_embeddings_by_size(
                host_params, self.model.schema, new_cardinality, rng=rng,
                opt_state=host_opt,
            )
        elif init == "mean" or init_tensor is not None:
            result = resize_item_embeddings(
                host_params, self.model.schema, new_cardinality, init_tensor,
                opt_state=host_opt,
            )
        else:
            msg = f"unknown init {init!r}: use 'mean' or 'xavier'"
            raise ValueError(msg)
        params, resized_opt = result if carry_opt_state else (result, None)
        shardings = params_shardings(self.mesh, params, self.sharding_rules)
        params = _place_tree(params, shardings)
        self._train_step = None  # shapes changed: retrace
        self._train_scan = None
        self._eval_logits = None
        self._query_embeddings_fn = None
        self._catalog_fn = None
        opt_state = self._tx.init(params)
        if carry_opt_state:
            # the fresh init is the SHAPE/placement template only: carried
            # host moments land leaf-by-leaf on its shardings (moments keep
            # their vocab sharding like a checkpoint restore would). Only
            # MESH shardings pin — uncommitted state scalars (Adam's count)
            # must stay free or the jitted step hits a device conflict
            def place(template, value):
                value = np.asarray(value)
                sharding = getattr(template, "sharding", None)
                if isinstance(sharding, NamedSharding):
                    return jax.device_put(value, sharding)
                return jnp.asarray(value)

            opt_state = jax.tree.map(place, opt_state, resized_opt)
        if jax.process_count() > 1:
            opt_state = _globalize_scalars(self.mesh, opt_state)
        return TrainState(
            step=state.step,
            params=params,
            opt_state=opt_state,
            rng=state.rng,
            bad_steps=state.bad_steps,
        )

    def finetune(
        self,
        state: TrainState,
        train_batches,
        new_cardinality: Optional[int] = None,
        init: str = "xavier",
        epochs: int = 1,
        **fit_kwargs,
    ) -> TrainState:
        """The continual-training entry (docs/robustness.md "Zero-downtime
        swaps and canary promotion"): optionally grow the catalog —
        optimizer-state-safe, xavier warm start for the cold rows — then fit
        from the given trained state on the fresh interaction tail. A thin,
        named seam so the promotion driver and the replay harness share one
        code path with plain ``fit``."""
        schema = self.model.schema
        feature_name = schema.item_id_feature_name
        if new_cardinality is not None and feature_name is not None:
            if new_cardinality < schema[feature_name].cardinality:
                msg = (
                    f"finetune cannot shrink the catalog "
                    f"({schema[feature_name].cardinality} -> {new_cardinality})"
                )
                raise ValueError(msg)
            if new_cardinality > schema[feature_name].cardinality:
                state = self.resize_vocabulary(
                    state, new_cardinality, carry_opt_state=True, init=init
                )
        return self.fit(train_batches, epochs=epochs, state=state, **fit_kwargs)

    def _set_lr_scale(self, scale: float) -> None:
        """Rebuild the optimizer with the base learning rate scaled by
        ``scale`` (RecoveryPolicy backoff). The optax state layout is identical
        for any LR, so a restored ``opt_state`` keeps working; the jitted step
        functions are invalidated (one retrace per rollback — rare by design)."""
        self._lr_scale = float(scale)
        factory = dataclasses.replace(
            self.optimizer, learning_rate=self.optimizer.learning_rate * self._lr_scale
        )
        self._tx = factory.create()
        self._train_step = None
        self._train_scan = None

    # -- checkpointing ------------------------------------------------------ #
    def save_checkpoint(
        self, path: str, state: TrainState, backend: Optional[str] = None
    ) -> None:
        """Write the full TrainState (params + optimizer + PRNG) to ``path``.

        ``backend=None`` defers to save_pytree's default: npz on one process,
        orbax under multi-host (npz would host-gather non-addressable leaves).
        """
        from replay_tpu.utils.checkpoint import save_pytree

        save_pytree(path, state, {"step": int(state.step)}, backend=backend)

    def restore_checkpoint(self, path: str, example_batch: Batch) -> TrainState:
        """Rebuild a TrainState from disk; the example batch supplies the template
        structure and the mesh shardings are re-applied on load."""
        from replay_tpu.utils.checkpoint import restore_pytree

        template = self.init_state(example_batch)
        restored = restore_pytree(path, template)
        shardings = jax.tree.map(self._template_sharding, template)
        return _place_tree(restored, shardings)

    def _template_sharding(self, target_leaf):
        # inherit the template's MESH sharding (params AND optimizer moments
        # keep their vocab sharding); other leaves replicate over the mesh
        sharding = getattr(target_leaf, "sharding", None)
        if not isinstance(sharding, NamedSharding):
            sharding = NamedSharding(self.mesh, P())
        return sharding

    def predict_dataframe(self, state, batches, k, **kwargs):
        """predict_top_k as a tidy (query_id, item_id, rating) pandas frame —
        the PandasTopItemsCallback equivalent."""
        import pandas as pd

        queries, items, scores = self.predict_top_k(state, batches, k, **kwargs)
        return pd.DataFrame(
            {
                "query_id": np.repeat(queries, k),
                "item_id": items.reshape(-1),
                "rating": scores.reshape(-1),
            }
        )
