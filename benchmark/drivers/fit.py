"""Driver of the traffic kind ``fit``: one ``Trainer.fit`` with the real input
pipeline running, timed over a window of ``--seconds``.

The path that is timed is the README quick-start path: seeded synthetic log ->
``LastNSplitter`` -> ``Dataset`` -> ``SequenceTokenizer`` ->
``SequenceBatcher(windows, shuffle)`` -> the model's default train transforms ->
``Trainer.fit(stream, scan_chunk=K, device_feed=True, loggers=<sink>)``.

ONE trainer, with its compiled ``train_scan`` and its state, is built in set-up
from weights this file makes from the seed (``reference.init_params``). It is
driven through its first chunk of K steps (whose losses, Adam moments and
parameters are copied out for the comparison that decides ``correct``), and the
same trainer and state go on through the warm-up chunks into the window. The
window opens at the host-observed completion of the last warm-up chunk and
closes at the completion of the last chunk whose batches were handed over before
the deadline; the stream ends on a chunk boundary, so the per-step tail program
never exists. Rates are everything that completed inside the window over the
window's whole length.

``fit`` is called twice on that one trainer (first chunk; then warm-up + window),
because the program keeps its state to itself while a ``fit`` runs: the state
after the first chunk can only be read between two calls. Both calls drive the
same compiled program; ``compiles_in_window`` checks that.

With ``--trace 1`` the profiler is switched on when the window has closed and
records ``trace_chunks`` further chunks of the same stream: the host-clock and
counter metrics are of the undisturbed window, the device metrics of that slice.

A traffic file's keys: ``scan_chunk``, ``device_feed``, ``chips`` and ``mesh``
(axes ``data``/``model``/``seq``), ``batch_scale`` (global batch = the
configuration's batch times this), ``warmup_chunks``, ``settle_seconds`` (one pause
of the fit thread after the first warm-up chunk, so that the feed is full when the
window opens), ``trace_chunks``,
``windows``, ``shuffle`` and the ``history`` group of ``datagen``.
"""

from __future__ import annotations

import gc
import importlib
import shutil
import time
from functools import partial
from pathlib import Path
from typing import Any, Dict, Iterator, List, Mapping, Optional

import numpy as np

from benchmark import compare, datagen

SEED_MODULUS = 2**31 - 1  # --seed may exceed 32 signed bits; JAX keys and numpy take this


def resolve(dotted: str):
    module, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(module), name)


def reference_model(config: Mapping[str, Any]) -> Dict[str, Any]:
    """The sizes the plain reference and the shape counts need."""
    keys = (
        "embedding_dim", "num_blocks", "num_heads", "max_sequence_length", "ffn_dim",
        "activation", "dropout", "causal", "num_items",
    )
    return {key: config[key] for key in keys}


def expand(template: Mapping[str, str], num_blocks: int) -> Dict[str, str]:
    """``{i}`` in a name stands for every block."""
    out = {}
    for name, path in template.items():
        if "{i}" in name:
            for i in range(num_blocks):
                out[name.format(i=i)] = path.format(i=i)
        else:
            out[name] = path
    return out


def to_program_tree(flat: Mapping[str, Any], paths: Mapping[str, str]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for name, path in paths.items():
        node = tree
        *parents, leaf = path.split("/")
        for key in parents:
            node = node.setdefault(key, {})
        node[leaf] = flat[name]
    return tree


def from_program_tree(tree: Mapping[str, Any], paths: Mapping[str, str]) -> Dict[str, Any]:
    out = {}
    for name, path in paths.items():
        node = tree
        for key in path.split("/"):
            node = node[key]
        out[name] = node
    return out


def prepare_sequences(config, traffic, seed: int):
    """pandas log -> LastNSplitter -> Dataset -> SequenceTokenizer (README)."""
    from replay_tpu.data import Dataset, FeatureHint, FeatureInfo, FeatureSchema, FeatureType
    from replay_tpu.data.nn import (
        SequenceTokenizer, TensorFeatureInfo, TensorFeatureSource, TensorSchema,
    )
    from replay_tpu.data.schema import FeatureSource
    from replay_tpu.splitters import LastNSplitter

    item = config["program"]["item_feature"]
    log = datagen.synthetic_log(config["num_items"], config["users"], traffic["history"], seed)
    log = log.rename(columns={"item_id": item})
    train_log, _ = LastNSplitter(N=1, divide_column="user_id", query_column="user_id").split(log)
    schema = FeatureSchema(
        [
            FeatureInfo("user_id", FeatureType.CATEGORICAL, FeatureHint.QUERY_ID),
            FeatureInfo(item, FeatureType.CATEGORICAL, FeatureHint.ITEM_ID),
            FeatureInfo("timestamp", FeatureType.NUMERICAL, FeatureHint.TIMESTAMP),
        ]
    )
    tensor_schema = TensorSchema(
        TensorFeatureInfo(
            item, FeatureType.CATEGORICAL, is_seq=True, feature_hint=FeatureHint.ITEM_ID,
            feature_sources=[TensorFeatureSource(FeatureSource.INTERACTIONS, item)],
            embedding_dim=config["embedding_dim"],
        )
    )
    tokenizer = SequenceTokenizer(tensor_schema, handle_unknown_rule="drop")
    train_seq = tokenizer.fit_transform(Dataset(feature_schema=schema, interactions=train_log))
    if tensor_schema[item].cardinality != config["num_items"]:
        raise RuntimeError(
            f"catalog is {tensor_schema[item].cardinality} items, the configuration "
            f"states {config['num_items']}"
        )
    return len(log), tensor_schema, train_seq


class Stream:
    """The batches handed to ``fit``: epochs of the batcher, cycled, each batch
    through the model's train transforms, with what the metrics need noted for
    every batch (seconds to produce it, valid rows, non-padding positions)."""

    def __init__(self, batcher, transform, takes_key: bool, seed: int, scan_chunk: int,
                 warmup_chunks: int = 0, seconds: float = 0.0, extra_chunks: int = 0,
                 clock: Optional["ChunkClock"] = None):
        self.batcher, self.transform, self.takes_key = batcher, transform, takes_key
        self.scan_chunk, self.warmup_chunks = scan_chunk, warmup_chunks
        self.seconds, self.extra_chunks, self.clock = seconds, extra_chunks, clock
        self.produce_seconds: List[float] = []
        self.rows: List[int] = []
        self.tokens: List[int] = []
        self.kept: List[Dict[str, Any]] = []  # the first chunk's batches, for the reference
        self.window_chunks: Optional[int] = None
        self._raw = self._epochs()
        if takes_key:
            import jax

            self._key = jax.random.PRNGKey(seed)

    def _epochs(self) -> Iterator[Dict[str, Any]]:
        epoch = 0
        while True:
            self.batcher.set_epoch(epoch)
            yield from self.batcher
            epoch += 1

    def _next(self) -> Dict[str, Any]:
        started = time.perf_counter()
        raw = next(self._raw)
        if self.takes_key:
            import jax

            self._key, sub = jax.random.split(self._key)
            batch = self.transform(raw, sub)
        else:
            batch = self.transform(raw)
        self.produce_seconds.append(time.perf_counter() - started)
        padding = np.asarray(batch["padding_mask"])
        valid = np.asarray(batch["valid"]) if "valid" in batch else np.ones(len(padding), bool)
        self.rows.append(int(valid.sum()))
        self.tokens.append(int((padding & valid[:, None]).sum()))
        return batch

    def first_chunk(self) -> Iterator[Dict[str, Any]]:
        for _ in range(self.scan_chunk):
            batch = self._next()
            self.kept.append(batch)
            yield batch

    def timed(self) -> Iterator[Dict[str, Any]]:
        """Warm-up chunks, then chunks until the deadline has passed at a chunk
        boundary, then ``extra_chunks`` more (the traced slice)."""
        produced, extra = 0, self.extra_chunks
        while True:
            opened = self.clock.window_open
            if (
                self.window_chunks is None
                and opened is not None
                and produced > self.warmup_chunks  # the window holds a chunk at least
                and time.perf_counter() >= opened + self.seconds
            ):
                self.window_chunks = produced - self.warmup_chunks
            if self.window_chunks is not None:
                if extra == 0:
                    return
                extra -= 1
            for _ in range(self.scan_chunk):
                yield self._next()
            produced += 1


class ChunkClock:
    """The benchmark's ``RunLogger`` sink. ``on_train_step`` events arrive in
    bursts of ``scan_chunk`` once a chunk's metrics are on the host, so the first
    event of a burst is the host-observed completion of that chunk."""

    def __init__(self, scan_chunk: int, warmup_chunks: int, trainer, settle_seconds=0.0):
        self.scan_chunk, self.warmup_chunks, self.trainer = scan_chunk, warmup_chunks, trainer
        self.settle_seconds = settle_seconds
        self.events = 0
        self.losses: List[float] = []
        self.chunk_done: List[float] = []
        self.window_open: Optional[float] = None
        self.traces_at_open: Optional[Dict[str, int]] = None
        self.stream: Optional[Stream] = None
        self.on_window_close = None  # called once, on the fit thread
        self.closed = False

    def log_event(self, event) -> None:
        if event.event != "on_train_step":
            return
        if self.events % self.scan_chunk == 0:
            self.chunk_done.append(time.perf_counter())
            done = len(self.chunk_done)
            if done == 1 and self.warmup_chunks > 1 and self.settle_seconds:
                # let the feed fill during warm-up: the fit thread pauses once, the
                # feeder gets its two chunks ahead, and the window always sees the
                # filled pipeline (PERF.md, Findings PR 24: the two feed states)
                time.sleep(self.settle_seconds)
            if done == self.warmup_chunks:
                self.window_open = self.chunk_done[-1]
                self.traces_at_open = dict(self.trainer.compile_tracker.traces)
            stream = self.stream
            if (
                not self.closed
                and stream is not None
                and stream.window_chunks is not None
                and done >= self.warmup_chunks + stream.window_chunks
            ):
                self.closed = True
                if self.on_window_close is not None:
                    self.on_window_close()
        self.events += 1
        self.losses.append(float(event.payload["loss"]))


def dropout_keys(trainer_seed: int, steps: int, site_paths: Mapping[str, str]):
    """The key of every dropout site at each of the first ``steps`` steps, as
    flax derives it from the trainer's seed: the state's key is the second half
    of ``split(PRNGKey(seed))``; each step splits it in three (carry, dropout,
    loss); a site folds its module path and the call count 1 into the dropout
    key. Made by the benchmark from the seed; the reference draws the masks."""
    import jax
    from flax.core.scope import LazyRng

    rng = jax.random.split(jax.random.PRNGKey(trainer_seed))[1]
    out = []
    for _ in range(steps):
        rng, dropout_rng, _ = jax.random.split(rng, 3)
        out.append(
            {
                site: LazyRng.create(dropout_rng, *path.split("/"), 1).as_jax_rng()
                for site, path in site_paths.items()
            }
        )
    return out


def reference_batch(batch: Mapping[str, Any], item_feature: str) -> Dict[str, Any]:
    """A batch as the program was handed it, under the reference's names."""
    ids = np.asarray(batch["feature_tensors"][item_feature])
    out = {
        "item_id": ids.astype(np.int32),
        "padding_mask": np.asarray(batch["padding_mask"]),
        "labels": np.asarray(batch["positive_labels"])[..., 0].astype(np.int32),
        "target_mask": np.asarray(batch["target_padding_mask"])[..., 0],
        "valid": np.asarray(batch["valid"]) if "valid" in batch else np.ones(len(ids), bool),
    }
    if "token_mask" in batch:
        out["token_mask"] = np.asarray(batch["token_mask"]).reshape(ids.shape)
    return out


def device_peak_bytes(device) -> int:
    """Peak bytes held on a device. The v5e's runtime keeps a program's
    temporaries in a reservation of their own, apart from the allocator's buffers
    (``peak_bytes_in_use`` read 75 MB beside a 5.7 GB ``peak_bytes_reserved`` for
    ``train_scan``: PERF.md, Findings PR 24): the peak is the two together."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def first_moment(opt_state):
    import jax

    found = jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
    moments = [x.mu for x in found if hasattr(x, "mu")]
    if len(moments) != 1:
        raise RuntimeError(f"expected one Adam state in the optimizer state, found {len(moments)}")
    return moments[0]


def build(cell, seed: int):
    """Set-up up to the trainer and its first state. Returns what ``run`` and the
    scripts that read limits on the chip both need."""
    import jax

    from replay_tpu.data.nn import SequenceBatcher
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.transform import Compose

    config, traffic = cell.config, cell.traffic
    program = config["program"]
    model_sizes = reference_model(config)
    reference = importlib.import_module(f"benchmark.reference.{config['reference']['module']}")

    rows, tensor_schema, train_seq = prepare_sequences(config, traffic, seed)
    pipes = resolve(program["transforms"])(tensor_schema, **program.get("transform_kwargs", {}))
    batch_size = config["batch_size"] * int(traffic.get("batch_scale", 1))
    batcher = SequenceBatcher(
        train_seq, batch_size=batch_size, max_sequence_length=program["batcher_length"],
        windows=traffic["windows"], shuffle=traffic["shuffle"], seed=seed,
    )
    if len(batcher) < traffic["scan_chunk"]:
        raise RuntimeError(f"an epoch holds {len(batcher)} batches, under one chunk")

    kwargs = {name: config[key] for name, key in program["model_kwargs"].items()}
    model = resolve(program["model"])(schema=tensor_schema, **kwargs)
    optimizer = config["optimizer"]
    mesh_axes = traffic.get("mesh", {})
    trainer = Trainer(
        model=model, loss=resolve(program["loss"])(),
        optimizer=OptimizerFactory(
            name=optimizer["name"], learning_rate=optimizer["learning_rate"],
            betas=(optimizer["b1"], optimizer["b2"]),
        ),
        mesh=make_mesh(
            cell.devices[: cell.chips], model_parallel=int(mesh_axes.get("model", 1)),
            seq_parallel=int(mesh_axes.get("seq", 1)),
        ),
        precision=config["precision"], seed=seed,
    )
    param_paths = expand(program["param_paths"], config["num_blocks"])
    make_weights = jax.jit(partial(reference.init_params, model_sizes))
    weights_key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return {
        "rows": rows, "batcher": batcher, "transform": Compose(pipes["train"]),
        "trainer": trainer, "reference": reference, "model_sizes": model_sizes,
        "param_paths": param_paths, "make_weights": make_weights, "weights_key": weights_key,
        "batch_size": batch_size,
        "site_paths": expand(program["dropout_paths"], config["num_blocks"]),
    }


def follow_reference(cell, built, kept, seed: int, precision="f32", fault=None):
    """The plain reference through the first chunk, as a trajectory: per-step
    losses, Adam's first moment after the chunk, the parameters' change over it.
    ``precision`` and ``fault`` make it the control or a planted fault."""
    import jax

    config = cell.config
    weights = built["make_weights"](built["weights_key"])
    batches = [reference_batch(b, config["program"]["item_feature"]) for b in kept]
    keys = dropout_keys(seed, len(batches), built["site_paths"])
    losses, moment, after = built["reference"].train_steps(
        weights, batches, keys, built["model_sizes"], config["optimizer"],
        config["reference"]["head_row_blocks"], precision=precision, fault=fault,
    )
    change = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b), after, weights)
    return {"losses": losses, "moment": jax.device_get(moment), "change": change}


def drive_first_chunk(cell, built, stream: Stream, fit):
    """Set-up's part of the timed path: the trainer's state from the seed's
    weights, then the first chunk through ``fit``. Returns the state to go on
    with and the program's trajectory over the chunk."""
    import jax

    trainer, param_paths = built["trainer"], built["param_paths"]
    first = iter(stream.first_chunk())
    example = next(first)
    weights = built["make_weights"](built["weights_key"])
    initial = jax.device_get(weights)
    state = trainer.init_state(example, params=to_program_tree(weights, param_paths))
    del weights
    sink = ChunkClock(stream.scan_chunk, 0, trainer)
    state = fit(_chain(example, first), state=state, loggers=sink)
    after = jax.device_get(from_program_tree(state.params, param_paths))
    moment = jax.device_get(from_program_tree(first_moment(state.opt_state), param_paths))
    change = {k: np.asarray(after[k]) - initial[k] for k in initial}
    return state, {"losses": list(sink.losses), "moment": moment, "change": change}


def numbers(program: Mapping[str, Any], reference: Mapping[str, Any]) -> Dict[str, Any]:
    return compare.training_numbers(
        program["losses"], reference["losses"], program["moment"], reference["moment"],
        program["change"], reference["change"],
    )


def run(cell) -> Dict[str, Any]:
    import jax

    config, traffic = cell.config, cell.traffic
    seed = cell.seed % SEED_MODULUS
    scan_chunk, warmup = int(traffic["scan_chunk"]), int(traffic["warmup_chunks"])
    stages = {"imports_and_devices": time.perf_counter()}  # marks; differences below
    built = build(cell, seed)
    stages["data_and_trainer"] = time.perf_counter()
    trainer = built["trainer"]

    clock = ChunkClock(scan_chunk, warmup, trainer, float(traffic.get("settle_seconds", 0.0)))
    stream = Stream(
        built["batcher"], built["transform"], bool(config["program"]["transform_takes_key"]),
        seed, scan_chunk, warmup, cell.seconds,
        int(traffic["trace_chunks"]) if cell.trace else 0, clock,
    )
    clock.stream = stream
    fit = partial(
        trainer.fit, epochs=1, scan_chunk=scan_chunk,
        device_feed=bool(traffic["device_feed"]), log_every=0,
    )

    # -- set-up: weights from the seed, the first chunk, what the comparison needs
    state, program = drive_first_chunk(cell, built, stream, fit)
    stages["first_chunk"] = time.perf_counter()

    # -- warm-up chunks, then the window, through the same trainer and state
    capture_dir = Path(cell.root) / ".bench_trace" / cell.name
    if cell.trace:
        shutil.rmtree(capture_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the host is the input pipeline: trace it lightly
        options.host_tracer_level = 1
        clock.on_window_close = lambda: jax.profiler.start_trace(
            str(capture_dir), profiler_options=options
        )
    try:
        state = fit(stream.timed(), state=state, loggers=clock)
        jax.block_until_ready(state)
    finally:
        if cell.trace and clock.closed:
            jax.profiler.stop_trace()
    traces_at_close = dict(trainer.compile_tracker.traces)
    # the captures carry no scope path per op: it comes from the program's HLO text
    hlo_text = trainer.lowered_hlo(config["program"]["scan_program"]) if cell.trace else ""
    window_chunks = stream.window_chunks
    window_open = stages["warmup"] = clock.window_open
    window_close = clock.chunk_done[warmup + window_chunks - 1]
    window_s = window_close - window_open
    bad_steps = int(state.bad_steps)
    memory_peak = max(device_peak_bytes(d) for d in cell.devices[: cell.chips])

    # batches: [first chunk][warm-up chunks][window chunks][traced slice]
    lo = scan_chunk * (1 + warmup)
    hi = lo + scan_chunk * window_chunks
    steps = hi - lo
    samples, tokens = sum(stream.rows[lo:hi]), sum(stream.tokens[lo:hi])
    context = {
        "window_s": window_s, "steps": steps, "chunks": window_chunks,
        "chunk_gaps_s": list(np.diff(clock.chunk_done[warmup - 1 : warmup + window_chunks])),
        "produce_seconds": stream.produce_seconds[lo:hi],
        "compiles_in_window": sum(traces_at_close.values())
        - sum((clock.traces_at_open or {}).values()),
        "memory_peak_bytes": memory_peak,
        "model_sizes": built["model_sizes"], "batch_size": built["batch_size"],
        "chips": cell.chips, "scan_chunk": scan_chunk,
        "scan_program": config["program"]["scan_program"],
        "setup_stages_s": dict(
            zip(stages, np.diff([cell.started, *stages.values()]).round(3).tolist())
        ),
        "capture_dir": str(capture_dir) if cell.trace else None,
        "hlo_text": hlo_text,
        "memory_stats": {
            k: int(v) for k, v in (cell.devices[0].memory_stats() or {}).items()
            if isinstance(v, (int, float))
        },
    }
    end_to_end = {
        "setup_s": window_open - cell.started,
        "fit_samples_per_s": samples / window_s,
        "fit_tokens_per_s": tokens / window_s,
    }

    # -- the comparison, once the window has closed, the peak has been read and
    #    the program's state is freed
    kept = stream.kept
    del state, trainer, fit, clock, stream
    built["trainer"] = built["batcher"] = None
    gc.collect()
    reference_started = time.perf_counter()
    compared = numbers(program, follow_reference(cell, built, kept, seed))
    compared["numbers"]["bad_steps"] = float(bad_steps)  # non-finite steps the sentinel skipped
    verdict = compare.judge(compared["numbers"], cell.limits)
    context["reference_s"] = time.perf_counter() - reference_started
    context["comparison"] = compared["detail"]
    return {
        "correct": verdict["correct"], "attempted": steps, "failed": bad_steps,
        "end_to_end": end_to_end, "context": context, "checks": verdict["checks"],
    }


def _chain(first, rest):
    yield first
    yield from rest


def read_capture(cell, context) -> Dict[str, Any]:
    """The traced slice reduced to device numbers; the capture is then removed."""
    from benchmark import tracing

    events = tracing.load_events(tracing.find_xplane(context["capture_dir"]))
    traced = tracing.reduce_capture(
        events, context["scan_program"], ("loss", "forward"), cell.chips,
        tracing.op_paths_from_hlo(context["hlo_text"]),
    )
    traced["steps"] = traced["runs"] * context["scan_chunk"]
    if not cell.keep_capture:
        shutil.rmtree(context["capture_dir"], ignore_errors=True)
    return traced
