"""Span tracing + goodput accounting (obs.trace).

Core tier: the Tracer is pure host code — nesting/exclusive-time math, Chrome
trace-event export, thread safety, and the input-starvation accounting against
a deliberately slow (and a fast) fake batcher. The jax smoke test drives a
traced ``Trainer.fit`` end-to-end: valid ``trace.json``, goodput fractions
summing to 1.0 on every epoch-end/fit-end event — the PR's acceptance gate.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from replay_tpu.obs import GOODPUT_SPANS, Tracer, goodput_breakdown, traced_iterator


# --------------------------------------------------------------------------- #
# tracer core (host-only)
# --------------------------------------------------------------------------- #
def test_nested_spans_split_inclusive_and_exclusive_time():
    tracer = Tracer()
    with tracer.span("outer"):
        time.sleep(0.02)
        with tracer.span("inner"):
            time.sleep(0.02)
    summary = tracer.summary()
    assert summary["outer"]["count"] == 1 and summary["inner"]["count"] == 1
    # inclusive outer covers the inner; exclusive outer does not
    assert summary["outer"]["seconds"] >= summary["inner"]["seconds"]
    assert summary["outer"]["self_seconds"] == pytest.approx(
        summary["outer"]["seconds"] - summary["inner"]["seconds"], abs=1e-6
    )
    assert summary["inner"]["self_seconds"] == pytest.approx(
        summary["inner"]["seconds"], abs=1e-9
    )


def test_disabled_tracer_records_nothing_and_reuses_null_context():
    tracer = Tracer(enabled=False)
    ctx_a = tracer.span("x")
    ctx_b = tracer.span("y", attr=1)
    assert ctx_a is ctx_b  # one shared null context: near-zero overhead
    with ctx_a:
        pass
    tracer.add_span("z", 0.0, 1.0)
    assert tracer.summary() == {}
    assert tracer.to_chrome_trace()["traceEvents"] == []


def test_span_args_reach_chrome_trace(tmp_path):
    tracer = Tracer()
    with tracer.span("step", index=3, phase="train"):
        pass
    path = tracer.save(str(tmp_path / "trace.json"))
    payload = json.load(open(path))
    (event,) = payload["traceEvents"]
    assert event["name"] == "step" and event["ph"] == "X"
    assert event["args"] == {"index": 3, "phase": "train"}


def test_chrome_trace_is_valid(tmp_path):
    tracer = Tracer()
    for i in range(3):
        with tracer.span("step"):
            with tracer.span("inner"):
                pass
    tracer.add_span("synthetic", 0.0, 0.001)
    path = tracer.save(str(tmp_path / "trace.json"))
    payload = json.load(open(path))
    events = payload["traceEvents"]
    assert len(events) == 7
    for event in events:
        # the acceptance contract: name/ph/ts present, durations non-negative
        assert "name" in event and "ph" in event and "ts" in event
        assert event["ph"] == "X"
        assert event["dur"] >= 0 and event["ts"] >= 0
    # events are time-sorted for chrome/perfetto friendliness
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    assert payload["displayTimeUnit"] == "ms"


def test_threaded_spans_all_recorded():
    tracer = Tracer()

    def work(i):
        for _ in range(25):
            with tracer.span(f"thread_{i}"):
                with tracer.span("inner"):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    summary = tracer.summary()
    assert summary["inner"]["count"] == 100
    for i in range(4):
        assert summary[f"thread_{i}"]["count"] == 25
        # nesting stacks are per-thread: each thread's inner nested under ITS span
        assert summary[f"thread_{i}"]["self_seconds"] <= summary[f"thread_{i}"]["seconds"]


def test_carve_reattributes_self_time():
    tracer = Tracer()
    with tracer.span("train_step") as span:
        time.sleep(0.03)
    before = tracer.summary()["train_step"]
    tracer.carve(span, "compile", 0.02)
    summary = tracer.summary()
    assert summary["compile"]["self_seconds"] == pytest.approx(0.02, abs=1e-9)
    assert summary["train_step"]["self_seconds"] == pytest.approx(
        before["self_seconds"] - 0.02, abs=1e-9
    )
    # inclusive step duration unchanged: the carved span nests inside it
    assert summary["train_step"]["seconds"] == pytest.approx(before["seconds"], abs=1e-9)
    # carving more than the span's remaining self time clamps, never negative
    tracer.carve(span, "compile", 99.0)
    assert tracer.summary()["train_step"]["self_seconds"] >= 0.0


# --------------------------------------------------------------------------- #
# goodput math (host-only)
# --------------------------------------------------------------------------- #
def test_goodput_fractions_sum_to_one():
    spans = {"data_wait": 0.2, "train_step": 0.5, "compile": 0.1, "unrelated": 9.0}
    record = goodput_breakdown(spans, wall_seconds=1.0)
    fractions = record["fractions"]
    assert set(fractions) == {*GOODPUT_SPANS, "other"}
    assert sum(fractions.values()) == pytest.approx(1.0, abs=1e-9)
    assert fractions["other"] == pytest.approx(0.2, abs=1e-9)  # unrelated excluded
    assert record["input_starvation"] == pytest.approx(0.2 / 0.8, abs=1e-9)


def test_goodput_overlapping_spans_renormalize():
    # concurrent-thread spans can exceed the wall window; the sum-to-1.0
    # contract must survive
    record = goodput_breakdown({"data_wait": 2.0, "train_step": 2.0}, wall_seconds=1.0)
    assert sum(record["fractions"].values()) == pytest.approx(1.0, abs=1e-9)
    assert record["fractions"]["other"] == pytest.approx(0.0, abs=1e-9)


def test_goodput_zero_wall_degrades():
    record = goodput_breakdown({}, wall_seconds=0.0)
    assert record["fractions"]["other"] == 1.0
    assert record["input_starvation"] == 0.0


def _goodput_of_loop(batch_delay: float, step_delay: float, n: int = 8):
    """The fit loop's accounting shape, minus jax: a traced iterator feeding a
    fake train step, folded through the same helpers Trainer.fit uses."""
    tracer = Tracer()

    def batcher():
        for _ in range(n):
            if batch_delay:
                time.sleep(batch_delay)
            yield {}

    start = time.perf_counter()
    for _ in traced_iterator(batcher(), tracer):
        with tracer.span("train_step"):
            time.sleep(step_delay)
    return goodput_breakdown(tracer.snapshot(), time.perf_counter() - start)


def test_slow_batcher_shows_input_starvation():
    """A batcher injecting 20ms/batch against a 2ms step must attribute the
    bulk of the pipeline to data_wait — the 'is the TPU idle because of the
    host?' one-liner."""
    record = _goodput_of_loop(batch_delay=0.02, step_delay=0.002)
    expected = 0.02 / (0.02 + 0.002)  # ≈ 0.91 of the stepping pipeline
    assert record["input_starvation"] > 0.7
    assert record["input_starvation"] == pytest.approx(expected, abs=0.15)
    assert record["fractions"]["data_wait"] > 0.6
    assert sum(record["fractions"].values()) == pytest.approx(1.0, abs=1e-9)


def test_fast_batcher_shows_no_starvation():
    record = _goodput_of_loop(batch_delay=0.0, step_delay=0.01)
    assert record["input_starvation"] < 0.1
    assert record["fractions"]["train_step"] > 0.6


def test_same_thread_batch_build_counts_as_input_time():
    """A batcher sharing the consumer's tracer nests batch_build inside
    data_wait; that assembly time must count toward starvation (input side),
    not leak into 'other'."""
    tracer = Tracer()

    def batcher():
        for _ in range(6):
            with tracer.span("batch_build"):
                time.sleep(0.01)
            yield {}

    start = time.perf_counter()
    for _ in traced_iterator(batcher(), tracer):
        with tracer.span("train_step"):
            time.sleep(0.002)
    record = goodput_breakdown(tracer.snapshot(), time.perf_counter() - start)
    assert record["fractions"]["other"] < 0.2
    assert record["input_starvation"] > 0.6  # ≈ 10/12 of the pipeline
    assert sum(record["fractions"].values()) == pytest.approx(1.0, abs=1e-9)


def test_snapshot_only_current_thread_excludes_worker_spans():
    tracer = Tracer()
    with tracer.span("train_step"):
        pass
    def record_span():
        with tracer.span("batch_build"):
            pass

    worker = threading.Thread(target=record_span)
    worker.start()
    worker.join()
    assert "batch_build" in tracer.snapshot()
    assert "batch_build" not in tracer.snapshot(only_current_thread=True)
    assert "train_step" in tracer.snapshot(only_current_thread=True)


def test_sequence_batcher_records_batch_build_spans():
    """SequenceBatcher(tracer=...) times every batch assembly."""
    import pandas as pd

    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import (
        SequenceBatcher,
        SequentialDataset,
        TensorFeatureInfo,
        TensorSchema,
    )

    schema = TensorSchema(
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=100)
    )
    frame = pd.DataFrame(
        {"query_id": np.arange(7), "item_id": [np.arange(1 + i) for i in range(7)]}
    )
    dataset = SequentialDataset(schema, "query_id", "item_id", frame)
    tracer = Tracer()
    batcher = SequenceBatcher(dataset, batch_size=2, max_sequence_length=4, tracer=tracer)
    batches = list(batcher)
    summary = tracer.summary()
    assert summary["batch_build"]["count"] == len(batches) == 4
    # tracing must not perturb the batches themselves
    plain = list(SequenceBatcher(dataset, batch_size=2, max_sequence_length=4))
    for traced, untraced in zip(batches, plain):
        np.testing.assert_array_equal(traced["item_id"], untraced["item_id"])


# --------------------------------------------------------------------------- #
# traced fit end-to-end (jax smoke) — the CI trace.json artifact producer
# --------------------------------------------------------------------------- #
def _run_dir(tmp_path, name):
    base = os.environ.get("REPLAY_TPU_RUN_DIR")
    return os.path.join(base, name) if base else str(tmp_path / name)


@pytest.mark.jax
@pytest.mark.smoke
def test_traced_fit_writes_valid_trace_and_goodput(tmp_path):
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.obs import JsonlLogger

    num_items, seq_len, batch_size = 12, 8, 8
    schema = TensorSchema(
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=num_items,
                          embedding_dim=16)
    )
    model = SasRec(schema=schema, embedding_dim=16, num_blocks=1, num_heads=1,
                   max_sequence_length=seq_len)
    trainer = Trainer(model=model, loss=CE(),
                      optimizer=OptimizerFactory(learning_rate=1e-2), mesh=make_mesh())
    rng = np.random.default_rng(0)

    def make_batch():
        items = rng.integers(0, num_items, size=(batch_size, seq_len + 1)).astype(np.int32)
        mask = np.ones((batch_size, seq_len), dtype=bool)
        return {
            "feature_tensors": {"item_id": items[:, :-1]},
            "padding_mask": mask,
            "positive_labels": items[:, 1:, None],
            "target_padding_mask": mask[:, :, None],
        }

    batches = [make_batch() for _ in range(3)]

    def val_batches():
        batch = dict(batches[0])
        batch["ground_truth"] = batches[0]["positive_labels"][:, -1, :].astype(np.int32)
        return [batch]

    run_dir = _run_dir(tmp_path, "trace_smoke")
    # mode="w": REPLAY_TPU_RUN_DIR is a fixed path in CI — re-runs must not append
    with JsonlLogger(run_dir, mode="w") as sink:
        trainer.fit(lambda: iter(batches), epochs=2, loggers=sink, tracer=True,
                    val_batches=val_batches, metrics=("ndcg",), top_k=(5,))

    # trace.json: valid Chrome trace-event JSON next to events.jsonl
    trace_path = os.path.join(run_dir, "trace.json")
    payload = json.load(open(trace_path))
    events = payload["traceEvents"]
    assert events, "traced fit recorded no spans"
    for event in events:
        assert "name" in event and "ph" in event and "ts" in event
        assert event["dur"] >= 0
    names = {event["name"] for event in events}
    assert {"data_wait", "h2d", "train_step", "compile", "validation"} <= names

    # goodput: every epoch-end and the fit-end carry fractions summing to 1.0
    lines = [json.loads(line) for line in open(os.path.join(run_dir, "events.jsonl"))]
    epoch_ends = [line for line in lines if line["event"] == "on_epoch_end"]
    fit_end = lines[-1]
    assert fit_end["event"] == "on_fit_end"
    assert len(epoch_ends) == 2
    for record in (*epoch_ends, fit_end):
        goodput = record["goodput"]
        fractions = goodput["fractions"]
        assert sum(fractions.values()) == pytest.approx(1.0, abs=0.05)
        assert all(value >= 0 for value in fractions.values())
        assert 0.0 <= goodput["input_starvation"] <= 1.0
    # the first epoch pays the train-step compile; the second must not
    assert epoch_ends[0]["goodput"]["fractions"]["compile"] > 0
    assert epoch_ends[1]["goodput"]["fractions"]["compile"] == pytest.approx(0.0, abs=1e-9)
    # span summaries mirrored into the event stream
    assert fit_end["spans"]["train_step"]["count"] == 6
    # tracing leaves the static-shapes invariant intact
    assert trainer.compile_tracker.traces["train_step"] == 1


PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py


def _tiny_trainer(embedding_dim=8, own_programs=False):
    """A trainer of the module's tiny model and a batch maker. The trainers run
    the same programs and share them (traced and lowered once a module), but for
    one whose compile counts a test asserts (``own_programs``)."""
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.sasrec import SasRec

    num_items, seq_len = 12, 8
    schema = TensorSchema(
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=num_items,
                          embedding_dim=embedding_dim)
    )
    model = SasRec(schema=schema, embedding_dim=embedding_dim, num_blocks=1,
                   num_heads=1, max_sequence_length=seq_len)
    trainer = Trainer(model=model, loss=CE(),
                      optimizer=OptimizerFactory(learning_rate=1e-2), mesh=make_mesh())
    if own_programs:
        PROGRAMS.share_init(trainer)  # the same model: the same fresh parameters
    else:
        PROGRAMS.adopt(trainer)

    def make_batch(seed):
        rng = np.random.default_rng(seed)
        items = rng.integers(0, num_items, size=(8, seq_len + 1)).astype(np.int32)
        mask = np.ones((8, seq_len), dtype=bool)
        return {
            "feature_tensors": {"item_id": items[:, :-1]},
            "padding_mask": mask,
            "positive_labels": items[:, 1:, None],
            "target_padding_mask": mask[:, :, None],
        }

    return trainer, make_batch


class _Recorder:
    def __init__(self):
        self.events = []

    def log_event(self, event):
        self.events.append(event)


@pytest.mark.jax
def test_fit_argument_tracer_scopes_to_that_fit():
    """fit(tracer=True) must not leave the trainer permanently tracing: the
    next fit runs untraced (no per-step loss fence, no goodput payloads)."""
    trainer, make_batch = _tiny_trainer()
    trainer.fit(lambda: iter([make_batch(0), make_batch(1)]), epochs=1, tracer=True)
    assert trainer.tracer is None  # detached at fit end
    recorder = _Recorder()
    trainer.fit(lambda: iter([make_batch(2), make_batch(3)]), epochs=1, loggers=recorder)
    for event in recorder.events:
        assert "goodput" not in event.payload and "spans" not in event.payload


@pytest.mark.jax
def test_preattached_tracer_reports_per_fit_spans():
    """A Trainer-attached tracer accumulates across fits (one timeline), but
    each fit-end `spans` payload covers only THAT fit's spans."""
    trainer, make_batch = _tiny_trainer()
    trainer.tracer = Tracer()
    first, second = _Recorder(), _Recorder()
    trainer.fit(lambda: iter([make_batch(0), make_batch(1)]), epochs=1, loggers=first)
    trainer.fit(lambda: iter([make_batch(2), make_batch(3)]), epochs=1, loggers=second)
    assert trainer.tracer is not None  # preattached: stays for every fit
    spans_a = first.events[-1].payload["spans"]
    spans_b = second.events[-1].payload["spans"]
    assert spans_a["train_step"]["count"] == 2
    assert spans_b["train_step"]["count"] == 2  # not 4: earlier fits subtracted
    # the shared timeline still holds everything
    assert trainer.tracer.summary()["train_step"]["count"] == 4


@pytest.mark.jax
def test_epoch_end_checkpoint_bills_to_next_epoch_window(tmp_path):
    """Goodput windows tile the fit: epoch N's end-of-epoch checkpoint save
    must show up in epoch N+1's `checkpoint` fraction, not vanish between
    windows."""
    from replay_tpu.utils.checkpoint import CheckpointManager

    trainer, make_batch = _tiny_trainer()
    manager = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=10)
    recorder = _Recorder()
    trainer.fit(lambda epoch: [make_batch(10 * epoch + i) for i in range(2)],
                epochs=2, loggers=recorder, tracer=True, checkpoint_manager=manager)
    epoch_ends = [e for e in recorder.events if e.event == "on_epoch_end"]
    assert len(epoch_ends) == 2
    # epoch 0's save happened after epoch 0's window closed -> epoch 1 sees it
    assert epoch_ends[1].payload["goodput"]["fractions"]["checkpoint"] > 0
    # fit-end window covers the final save
    fit_end = recorder.events[-1]
    assert fit_end.payload["goodput"]["fractions"]["checkpoint"] > 0
    assert fit_end.payload["spans"]["checkpoint"]["count"] == 2


@pytest.mark.jax
def test_untraced_fit_emits_no_goodput():
    """tracer=None keeps the event schema exactly as before (additive change)."""
    from replay_tpu.data import FeatureHint, FeatureType
    from replay_tpu.data.nn import TensorFeatureInfo, TensorSchema
    from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
    from replay_tpu.nn.loss import CE
    from replay_tpu.nn.sequential.sasrec import SasRec
    from replay_tpu.obs import RunLogger

    class Recorder(RunLogger):
        def __init__(self):
            self.events = []

        def log_event(self, event):
            self.events.append(event)

    num_items, seq_len = 12, 8
    schema = TensorSchema(
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=num_items,
                          embedding_dim=8)
    )
    model = SasRec(schema=schema, embedding_dim=8, num_blocks=1, num_heads=1,
                   max_sequence_length=seq_len)
    trainer = PROGRAMS.adopt(  # the tiny model's programs, as _tiny_trainer's
        Trainer(model=model, loss=CE(),
                optimizer=OptimizerFactory(learning_rate=1e-2), mesh=make_mesh())
    )
    rng = np.random.default_rng(1)
    items = rng.integers(0, num_items, size=(8, seq_len + 1)).astype(np.int32)
    mask = np.ones((8, seq_len), dtype=bool)
    batch = {
        "feature_tensors": {"item_id": items[:, :-1]},
        "padding_mask": mask,
        "positive_labels": items[:, 1:, None],
        "target_padding_mask": mask[:, :, None],
    }
    recorder = Recorder()
    trainer.fit(lambda: iter([batch, batch]), epochs=1, loggers=recorder)
    for event in recorder.events:
        assert "goodput" not in event.payload and "spans" not in event.payload


# --------------------------------------------------------------------------- #
# traced scan-chunked fit (jax smoke) — the CI chunked_smoke artifact producer
# --------------------------------------------------------------------------- #
@pytest.mark.jax
@pytest.mark.smoke
def test_traced_chunked_fit_goodput_sums_and_h2d_overlaps(tmp_path):
    """A traced fit(scan_chunk=K) with the device feed: goodput fractions
    still sum to 1.0, the chunk h2d spans land on the FEEDER thread (the
    overlap trace.json shows next to the fit thread's train_step spans), and
    chunked train_step spans carry their per-step attribution (steps=K)."""
    from replay_tpu.obs import JsonlLogger

    from replay_tpu.obs.trace import COMPILE_COUNTERS, chunk_stage_log, stage, startup_log

    trainer, make_batch = _tiny_trainer(own_programs=True)  # its compile counts are asserted
    batches = [make_batch(i) for i in range(7)]  # two K=3 chunks + one tail step
    with stage("split"):  # set-up: outside any chunk, so the fit's start-up record's
        time.sleep(0.002)
    fits_before = len(startup_log())

    run_dir = _run_dir(tmp_path, "chunked_smoke")
    # mode="w": REPLAY_TPU_RUN_DIR is a fixed path in CI — re-runs must not append
    with JsonlLogger(run_dir, mode="w") as sink:
        trainer.fit(lambda: iter(batches), epochs=2, loggers=sink, tracer=True,
                    scan_chunk=3)

    payload = json.load(open(os.path.join(run_dir, "trace.json")))
    events = payload["traceEvents"]
    for event in events:
        assert "name" in event and "ph" in event and "ts" in event
        assert event["dur"] >= 0
    by_name = {}
    for event in events:
        by_name.setdefault(event["name"], []).append(event)
    # chunk dispatches carry per-step attribution; the tail step has none
    chunk_spans = [e for e in by_name["train_step"] if e.get("args", {}).get("steps")]
    assert [e["args"]["steps"] for e in chunk_spans] == [3, 3, 3, 3]
    # h2d overlaps: the device feed places chunks on the feeder thread, a
    # DIFFERENT tid than the fit thread's train_step spans
    step_tids = {e["tid"] for e in by_name["train_step"]}
    h2d_tids = {e["tid"] for e in by_name["h2d"]}
    assert h2d_tids - step_tids, "no h2d span on the feeder thread"
    # the fit thread still times its wait on the feed as data_wait
    assert "data_wait" in by_name

    lines = [json.loads(line) for line in open(os.path.join(run_dir, "events.jsonl"))]
    epoch_ends = [line for line in lines if line["event"] == "on_epoch_end"]
    fit_end = lines[-1]
    assert fit_end["event"] == "on_fit_end"
    assert len(epoch_ends) == 2
    for record in (*epoch_ends, fit_end):
        goodput = record["goodput"]
        fractions = goodput["fractions"]
        assert sum(fractions.values()) == pytest.approx(1.0, abs=0.05)
        assert all(value >= 0 for value in fractions.values())
        assert 0.0 <= goodput["input_starvation"] <= 1.0
    # per-step events fan out of the chunk: 7 steps per epoch, losses intact
    steps = [line for line in lines if line["event"] == "on_train_step"]
    assert len(steps) == 14
    assert all(np.isfinite(s["loss"]) for s in steps)
    # one compiled scan + one compiled per-step program (the tail)
    assert trainer.compile_tracker.traces["train_scan"] == 1
    assert trainer.compile_tracker.traces["train_step"] == 1

    # the start-up log: one record for this fit, holding the stage that ran
    # before it and the state's init (inside the fit, before its first chunk)
    started = startup_log()[fits_before - 1]
    assert startup_log()[-1]["fit"] is None and len(startup_log()) == fits_before + 1
    assert started["split"] >= 0.002
    records = [r for r in chunk_stage_log() if r["fit"] == started["fit"]]
    assert [r["chunk"] for r in records] == [0, 1, 2, 3]
    assert all("split" not in r and "init_state" not in r for r in records)
    # what jax said it built: the scan in the first chunk's dispatch, split
    # into its phases; the per-step program of the first epoch's tail in the
    # chunk after it; nothing in the steady ones
    first = records[0]
    assert first["compiled"] and first["compile_programs"] >= 1
    assert first["compile_trace_s"] > 0 and first["compile_lower_s"] > 0
    assert first["compile_backend_s"] > 0
    phases = sum(first[k] for k in ("compile_trace_s", "compile_lower_s", "compile_backend_s"))
    assert phases <= first["dispatch"]
    assert first["compile_cache_hits"] + first["compile_cache_misses"] >= 1
    assert records[2]["compile_programs"] >= 1 and not records[2]["compiled"]
    for steady in (records[1], records[3]):
        # (a call whose arguments are committed anew looks its jaxpr up again:
        # jax reports that as a trace of some tens of microseconds)
        assert steady["compile_trace_s"] < 0.01
        assert [steady[k] for k in COMPILE_COUNTERS if k != "compile_trace_s"] == [0] * 6
    # the carved `compile` span is those phases, not the whole dispatch
    dispatches = sorted(by_name["dispatch"], key=lambda e: e["ts"])
    assert by_name["compile"][0]["dur"] <= dispatches[0]["dur"]
    assert by_name["compile"][0]["dur"] == pytest.approx(1e6 * phases, rel=0.01)


# --------------------------------------------------------------------------- #
# serve spans: cross-thread lifecycle timing + the serve goodput breakdown
# --------------------------------------------------------------------------- #
def test_lifecycle_span_records_across_threads():
    from replay_tpu.obs import lifecycle_span

    tracer = Tracer()
    started = {}

    def producer():
        started["at"] = tracer.now()

    producer_thread = threading.Thread(target=producer)
    producer_thread.start()
    producer_thread.join()
    time.sleep(0.02)
    duration = lifecycle_span(tracer, "queue_wait", started["at"], lane="hit")
    assert duration >= 0.015
    (event,) = tracer.to_chrome_trace()["traceEvents"]
    assert event["name"] == "queue_wait"
    assert event["args"] == {"lane": "hit"}
    assert event["dur"] == pytest.approx(duration * 1e6, rel=1e-3)
    summary = tracer.summary()
    assert summary["queue_wait"]["count"] == 1


def test_lifecycle_span_on_disabled_tracer_is_a_noop():
    from replay_tpu.obs import lifecycle_span

    tracer = Tracer(enabled=False)
    duration = lifecycle_span(tracer, "queue_wait", 0.0)
    assert duration >= 0.0
    assert tracer.to_chrome_trace()["traceEvents"] == []


def test_serve_goodput_fractions_sum_to_one():
    from replay_tpu.obs import SERVE_GOODPUT_SPANS

    spans = {"queue_wait": 0.6, "batch_build": 0.05, "score": 0.2,
             "retrieve": 0.04, "rerank": 0.03}
    breakdown = goodput_breakdown(spans, 1.0, spans=SERVE_GOODPUT_SPANS)
    fractions = breakdown["fractions"]
    assert set(fractions) == set(SERVE_GOODPUT_SPANS) | {"other"}
    assert sum(fractions.values()) == pytest.approx(1.0)
    assert fractions["queue_wait"] == pytest.approx(0.6)
    # no stepping pipeline in a serve breakdown -> starvation is None
    assert breakdown["input_starvation"] is None


def test_serve_goodput_renormalizes_overlapping_queue_waits():
    """queue_wait is inherently concurrent (many requests wait at once): when
    tracked span time exceeds the wall window the fractions renormalize so
    the sum-to-1.0 contract survives."""
    from replay_tpu.obs import SERVE_GOODPUT_SPANS

    spans = {"queue_wait": 5.0, "score": 1.0}  # 6s of spans in a 2s window
    breakdown = goodput_breakdown(spans, 2.0, spans=SERVE_GOODPUT_SPANS)
    fractions = breakdown["fractions"]
    assert sum(fractions.values()) == pytest.approx(1.0)
    assert fractions["queue_wait"] == pytest.approx(5.0 / 6.0)
    assert fractions["other"] == pytest.approx(0.0)


def test_training_goodput_still_reports_starvation():
    spans = {"data_wait": 0.2, "train_step": 0.6}
    breakdown = goodput_breakdown(spans, 1.0)
    assert breakdown["input_starvation"] == pytest.approx(0.25)


# --------------------------------------------------------------------------- #
# the bound, the stage helper and the chunk stage log (host-only but for the
# profiler capture)
# --------------------------------------------------------------------------- #
def _fake_clock(tracer):
    """A clock that advances 1 ms a reading: two tracers read the same times."""
    ticks = iter(range(10**9))
    tracer._clock = lambda: next(ticks) * 1e-3
    tracer._t0 = 0.0


def test_bounded_tracer_evicts_records_and_keeps_totals_exact():
    bounded, unbounded = Tracer(maxlen=8), Tracer(maxlen=None)
    for tracer in (bounded, unbounded):
        _fake_clock(tracer)
        for i in range(50):
            with tracer.span("train_step", i=i) as step:
                with tracer.span("data_wait"):
                    pass
            if i % 10 == 0:  # carve after the parent's record may have been evicted
                tracer.carve(step, "compile", 0.001)
    assert len(bounded.to_chrome_trace()["traceEvents"]) == 8
    assert len(unbounded.to_chrome_trace()["traceEvents"]) == 105
    # the newest records are the ones kept
    kept = [e["args"]["i"] for e in bounded.to_chrome_trace()["traceEvents"] if "args" in e]
    assert kept == [46, 47, 48, 49]
    assert bounded.summary() == unbounded.summary()
    assert bounded.summary()["train_step"]["count"] == 50
    assert bounded.summary(only_current_thread=True) == unbounded.summary(only_current_thread=True)
    assert bounded.snapshot() == unbounded.snapshot()
    wall = 1.0
    assert goodput_breakdown(bounded.snapshot(), wall) == goodput_breakdown(unbounded.snapshot(), wall)
    assert goodput_breakdown(bounded.snapshot(), wall)["fractions"]["compile"] == pytest.approx(0.005)
    assert Tracer()._events.maxlen == 65536  # bounded unless asked otherwise


def test_stage_with_no_tracer_allocates_no_event():
    from replay_tpu.obs.trace import attached_tracer, claim_chunk, stage

    assert attached_tracer() is None
    bystander, disabled = Tracer(), Tracer(enabled=False)
    claim_chunk(-1)  # what this thread's stages held before is not this test's
    with stage("dispatch") as plain:
        pass
    with stage("dispatch", tracer=disabled) as off:
        pass
    assert plain.span is None and off.span is None
    assert bystander.summary() == {} and disabled.summary() == {}
    assert len(bystander._events) == len(disabled._events) == 0
    # the seconds are kept all the same: the stage log's side
    assert plain.seconds >= 0 and plain.end >= plain.seconds
    assert claim_chunk(-2)["dispatch"] == pytest.approx(plain.seconds + off.seconds)


def test_stage_records_into_its_tracer_else_the_attached_one():
    from replay_tpu.obs.trace import attach_tracer, stage

    own, attached = Tracer(), Tracer()
    previous = attach_tracer(attached)
    try:
        with stage("batch_build"):
            pass
        with stage("batch_build", tracer=own, rows=4) as explicit:
            pass
    finally:
        assert attach_tracer(previous) is attached
    assert attached.summary()["batch_build"]["count"] == 1
    assert own.summary()["batch_build"]["count"] == 1  # an explicit tracer wins
    assert explicit.span.record["args"] == {"rows": 4}
    with stage("batch_build"):
        pass
    assert attached.summary()["batch_build"]["count"] == 1  # detached: no more


def test_stage_annotation_is_in_the_profiler_capture(tmp_path):
    """Under a profiler session a stage is in the capture's host plane under its
    name, its args as the event's stats, one line per thread."""
    import jax
    from jax.profiler import ProfileData

    from replay_tpu.obs.trace import stage

    def feeder():
        with stage("transform", transform="TokenMaskTransform"):
            time.sleep(0.001)

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level, options.host_tracer_level = 0, 1  # the benchmark's options
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with stage("data_wait", chunk=3):
            time.sleep(0.001)
        thread = threading.Thread(target=feeder)
        thread.start()
        thread.join()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    host = [p for p in ProfileData.from_file(str(xplane)).planes if p.name == "/host:CPU"]
    assert len(host) == 1
    found = {}
    for index, line in enumerate(host[0].lines):
        for event in line.events:
            if event.name in ("data_wait", "transform"):
                found[event.name] = (index, {k: v for k, v in event.stats}, event.duration_ns)
    assert found["data_wait"][1] == {"chunk": 3}
    assert found["transform"][1] == {"transform": "TokenMaskTransform"}
    assert found["data_wait"][0] != found["transform"][0]  # two threads, two lines
    assert all(duration >= 1e6 for _, _, duration in found.values())


def test_goodput_folds_the_stage_children_into_their_phase():
    """dispatch + device_wait read as train_step, stack as h2d, transform as
    data_wait: the fractions are what one span a phase gave."""
    split = {"train_step": 0.01, "dispatch": 0.2, "device_wait": 0.3, "h2d": 0.05,
             "stack": 0.1, "data_wait": 0.05, "transform": 0.1, "account": 0.1}
    whole = {"train_step": 0.51, "h2d": 0.15, "data_wait": 0.15}
    folded = goodput_breakdown(split, 1.0)
    assert folded["fractions"] == pytest.approx(goodput_breakdown(whole, 1.0)["fractions"])
    assert folded["input_starvation"] == pytest.approx(goodput_breakdown(whole, 1.0)["input_starvation"])
    assert set(folded["fractions"]) == set(GOODPUT_SPANS) | {"other"}
    assert folded["fractions"]["other"] == pytest.approx(0.19)  # account is the loop's own


def test_chunk_stages_tile_the_fit_threads_time_and_carry_the_feeders():
    """The fit thread's side of the stage log against a fake feed: one record a
    chunk, the four stages sum to the done-to-done period, the feeder's record
    travels with the chunk, a new epoch starts a new period."""
    import queue

    from replay_tpu.obs.trace import ChunkStages, chunk_stage_log, claim_chunk, claimed_chunk, stage

    buffer = queue.Queue(maxsize=1)

    def feeder():
        for chunk in range(4):
            for _ in range(2):
                with stage("batch_build", python_rows=3):
                    time.sleep(0.002)
                with stage("transform", transform="A", device_programs=1):
                    time.sleep(0.001)
                with stage("transform", transform="B", device_programs=0):
                    pass
            record = claim_chunk(chunk)
            record["device_leaves"] = 2
            with stage("stack", chunk=chunk):
                time.sleep(0.001)
            with stage("feed_full", **claimed_chunk()):
                buffer.put(record)
        buffer.put(None)

    thread = threading.Thread(target=feeder)
    thread.start()
    stages = ChunkStages()
    for record in stages.feed(iter(buffer.get, None)):
        with stages.stage("dispatch") as dispatch:
            time.sleep(0.002)
        with stages.stage("device_wait") as device_wait:
            time.sleep(0.05)
        stages.synced(2, dispatch, device_wait, compiled=stages.chunk == 0, feeder=record,
                      ce_fused_steps=2 * (stages.chunk % 2))
        time.sleep(0.005)  # bookkeeping: `account` is open
        if stages.chunk == 2:
            stages.new_epoch()
    stages.close()
    thread.join()
    # by the fit's ordinal, not by position: the log is a ring
    records = [r for r in chunk_stage_log() if r["fit"] == stages.fit]
    assert [r["chunk"] for r in records] == [0, 1, 2, 3]
    assert [r["compiled"] for r in records] == [True, False, False, False]
    assert [r["ce_fused_steps"] for r in records] == [0, 2, 0, 2]  # as the fit thread said
    assert ["period" in r for r in records] == [False, True, False, True]
    for record in records:
        assert record["steps"] == 2 and record["device_leaves"] == 2
        assert record["batch_build"] >= 0.004 and record["stack"] >= 0.001
        assert set(record["transform_by_name"]) == {"A", "B"}
        assert sum(record["transform_by_name"].values()) == pytest.approx(record["transform"])
        assert record["transform_by_name"]["A"] >= 0.002
        # two batches a chunk, one program each from A: summed per chunk
        assert record["transform_device_programs"] == 2
        # and three rows a batch from the batcher's python loop
        assert record["batch_build_python_rows"] == 6
        assert record["device_wait"] >= 0.05 and record["dispatch"] >= 0.002
    for record in (records[1], records[3]):
        assert record["account"] >= 0.005
        tiled = sum(record[k] for k in ("data_wait", "dispatch", "device_wait", "account"))
        assert tiled == pytest.approx(record["period"], rel=0.03)
    # the feeder waited on the full queue while the fit thread ran chunk 1
    assert records[2]["feed_full"] >= 0.01
    assert ChunkStages().fit == stages.fit + 1  # the next fit call's ordinal


TRACE, LOWER, BACKEND = (
    f"/jax/core/compile/{phase}_duration"
    for phase in ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile")
)
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT, CACHE_MISS = "/jax/compilation_cache/cache_hits", "/jax/compilation_cache/cache_misses"


def test_compile_events_land_in_the_record_of_the_chunk_they_happened_in():
    """``jax.monitoring`` events fired by hand: on a thread that claimed a chunk
    they are in that chunk's record, on the fit thread in the record ``synced``
    writes, and one chunk's record holds what BOTH threads built."""
    from jax import monitoring

    from replay_tpu.obs.trace import COMPILE_COUNTERS, ChunkStages, chunk_stage_log, claim_chunk, stage

    fed = {}

    def feeder():
        with stage("transform", transform="Mask"):  # names no chunk: the one being filled
            monitoring.record_event_duration_secs(TRACE, 0.25)
        fed[0] = claim_chunk(0)
        with stage("h2d", chunk=0):
            monitoring.record_event_duration_secs(BACKEND, 0.5)
            monitoring.record_event_duration_secs(CACHE_LOAD, 0.125)
            monitoring.record_event(CACHE_HIT)
        with stage("batch_build"):  # the next chunk's batches: not chunk 0's
            monitoring.record_event_duration_secs(BACKEND, 4.0)
        fed[1] = claim_chunk(1)

    thread = threading.Thread(target=feeder)
    thread.start()
    thread.join()
    assert fed[0]["compile_trace_s"] == 0.25 and fed[0]["compile_backend_s"] == 0.5
    assert fed[0]["compile_cache_load_s"] == 0.125
    assert (fed[0]["compile_programs"], fed[0]["compile_cache_hits"]) == (1, 1)
    assert (fed[1]["compile_programs"], fed[1]["compile_backend_s"]) == (1, 4.0)

    stages = ChunkStages()
    monitoring.record_event_duration_secs(LOWER, 0.0625)  # between two stages: the next sync's
    with stages.stage("dispatch") as dispatch:
        monitoring.record_event_duration_secs(TRACE, 1.0)
        monitoring.record_event_duration_secs(LOWER, 2.0)
        monitoring.record_event_duration_secs(BACKEND, 3.0)
        monitoring.record_event(CACHE_MISS)
        monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")  # not counted
        monitoring.record_event_duration_secs("/jax/compilation_cache/compile_time_saved_sec", 9.0)
    with stages.stage("device_wait") as device_wait:
        pass
    assert dispatch.compile_seconds == 6.0 and device_wait.compile_seconds == 0.0
    record = stages.synced(2, dispatch, device_wait, compiled=True, feeder=fed[0])
    assert [record[k] for k in COMPILE_COUNTERS] == [2, 1.25, 2.0625, 3.5, 0.125, 1, 1]
    # the fit thread's totals were taken: the next chunk starts from nought
    with stages.stage("dispatch") as dispatch:
        pass
    with stages.stage("device_wait") as device_wait:
        pass
    steady = stages.synced(2, dispatch, device_wait, compiled=False)
    stages.close()
    assert [steady[k] for k in COMPILE_COUNTERS] == [0] * 7
    assert chunk_stage_log()[-2:] == [record, steady]


def test_compile_phases_count_each_second_once():
    """jax opens a phase with a scalar and closes it with a duration: one inside
    another (a jitted function traced inside a trace, an eager op compiled
    during it) takes its seconds out of the outer one's."""
    from jax import monitoring

    from replay_tpu.obs.trace import claim_chunk, stage

    record = claim_chunk(-1)
    with stage("dispatch", chunk=-1) as dispatch:
        monitoring.record_scalar(TRACE, 100.0)
        monitoring.record_scalar(TRACE, 100.1)
        monitoring.record_event_duration_secs(TRACE, 0.25)  # the inner jit's trace
        monitoring.record_scalar(BACKEND, 100.5)
        monitoring.record_event_duration_secs(BACKEND, 0.5)  # an eager op's program
        monitoring.record_event_duration_secs(TRACE, 2.0)
    assert (record["compile_trace_s"], record["compile_backend_s"]) == (1.5, 0.5)
    assert record["compile_programs"] == 1 and dispatch.compile_seconds == 2.0


def test_the_listeners_are_registered_once_a_process():
    import jax
    from jax._src import monitoring as registry

    from replay_tpu.obs import trace

    with trace.stage("dispatch"):  # a stage that finds jax imported registers them
        pass
    trace._listen(jax.monitoring)
    trace._listen(jax.monitoring)
    assert registry._event_listeners.count(trace._on_cache_event) == 1
    assert registry._event_duration_secs_listeners.count(trace._on_phase_seconds) == 1
    assert registry._scalar_listeners.count(trace._on_phase_start) == 1
    trace.claim_chunk(-1)
    jax.monitoring.record_event(CACHE_MISS)
    assert trace.claim_chunk(-2)["compile_cache_misses"] == 1


def test_what_runs_outside_a_chunk_is_the_next_fits_start_up_record(monkeypatch):
    """Stages and compile events outside any chunk go to the record of the next
    ``fit`` call, a chunk's stages do not; a reader also gets what ran since."""
    from jax import monitoring

    from replay_tpu.obs import trace
    from replay_tpu.obs.trace import ChunkStages, chunk_stage_log, stage, startup_log

    ChunkStages()  # what this thread did before is an earlier fit's
    monkeypatch.setattr(trace, "_PACKAGE_IMPORTS", [])
    now = time.perf_counter()
    trace.package_imported("inner.package", now - 1.0)  # imported while the outer was
    trace.package_imported("outer.package", now - 3.0)
    with stage("init_state"):
        with stage("inner"):  # a stage inside a stage: both by name
            monitoring.record_event_duration_secs(BACKEND, 0.5)
        monitoring.record_event(CACHE_MISS)
    assert startup_log()[-1]["init_state"] > 0 and startup_log()[-1]["fit"] is None

    stages = ChunkStages()
    for _ in range(2):
        with stages.stage("dispatch") as dispatch:
            pass
        with stages.stage("device_wait") as device_wait:
            pass
        stages.synced(1, dispatch, device_wait, compiled=False)
    stages.close()
    with stage("tokenize"):
        pass

    *_, started, since = startup_log()
    assert started["fit"] == stages.fit and since["fit"] is None
    assert started["pkg_import"] == pytest.approx(3.0, abs=0.01)
    assert started["pkg_import_by_name"]["inner.package"] == pytest.approx(1.0, abs=0.01)
    assert started["pkg_import_by_name"]["outer.package"] == pytest.approx(2.0, abs=0.01)
    assert started["init_state"] >= started["inner"] > 0
    assert (started["compile_programs"], started["compile_backend_s"]) == (1, 0.5)
    assert started["compile_cache_misses"] == 1
    assert not {"dispatch", "device_wait", "tokenize"} & set(started)
    # after the fit: its last `account`, and the stage that followed
    assert "tokenize" in since and "init_state" not in since and "dispatch" not in since
    for record in chunk_stage_log()[-2:]:
        assert record["fit"] == stages.fit
        assert not {"init_state", "pkg_import", "tokenize"} & set(record)
        assert record["compile_programs"] == 0
