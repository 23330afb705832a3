"""Stage spans of the scan-chunked fit path (obs.trace.stage / chunk_stage_log).

ONE toy chunked fit with the device feed on, through the real input pipeline
(``SequenceBatcher`` -> the default SASRec transforms in a ``Compose``), traced
explicitly; every test reads what that one fit left behind: the chunk stage log,
the tracer's spans on both threads, the goodput record.
"""

import threading
import time

import jax
import numpy as np
import pandas as pd
import pytest

from replay_tpu.data import FeatureHint, FeatureType
from replay_tpu.data.nn import SequenceBatcher, SequentialDataset, TensorFeatureInfo, TensorSchema
from replay_tpu.nn import OptimizerFactory, Trainer, make_mesh
from replay_tpu.nn.loss import CE
from replay_tpu.nn.sequential.sasrec import SasRec
from replay_tpu.nn.transform import Compose
from replay_tpu.nn.transform.template import make_default_sasrec_transforms
from replay_tpu.obs import GOODPUT_SPANS, Tracer, chunk_stage_log
from replay_tpu.obs.trace import CHUNK_STAGES, claim_chunk

pytestmark = pytest.mark.jax

NUM_ITEMS, SEQ_LEN, BATCH, SCAN_CHUNK = 40, 8, 8, 3
PROGRAMS = None  # this module's SharedPrograms, set by tests/conftest.py
FIT_STAGES = CHUNK_STAGES["fit"]


def last_fit_records():
    """The records of the process's newest ``fit`` (by its ordinal: the log is a ring)."""
    log = chunk_stage_log()
    return [r for r in log if r["fit"] == log[-1]["fit"]]


class PausingSink:
    """A logger that holds the fit thread for a moment once per chunk, so a
    chunk's period is long beside the microseconds between two stages."""

    def __init__(self):
        self.events = []

    def log_event(self, event):
        self.events.append(event)
        if event.event == "on_train_step" and event.step % SCAN_CHUNK == 0:
            time.sleep(0.03)


@pytest.fixture(scope="module")
def traced_fit():
    schema = TensorSchema(
        TensorFeatureInfo("item_id", FeatureType.CATEGORICAL, is_seq=True,
                          feature_hint=FeatureHint.ITEM_ID, cardinality=NUM_ITEMS,
                          embedding_dim=16)
    )
    rng = np.random.default_rng(0)
    frame = pd.DataFrame({
        "query_id": np.arange(64),
        "item_id": [rng.integers(0, NUM_ITEMS, size=rng.integers(5, 30)) for _ in range(64)],
    })
    batcher = SequenceBatcher(
        SequentialDataset(schema, "query_id", "item_id", frame), batch_size=BATCH,
        max_sequence_length=SEQ_LEN + 1, windows=True, shuffle=True,
    )
    compose = Compose(make_default_sasrec_transforms(schema)["train"])
    chunks_an_epoch = len(batcher) // SCAN_CHUNK
    assert chunks_an_epoch >= 5

    def stream():
        for index, batch in enumerate(batcher):
            if index < chunks_an_epoch * SCAN_CHUNK:  # whole chunks: no tail step
                yield compose(batch)

    model = SasRec(schema=schema, embedding_dim=16, num_blocks=1, num_heads=1,
                   max_sequence_length=SEQ_LEN)
    # one device: the stages are the host's, and a one-chip program compiles fastest
    trainer = PROGRAMS.share_init(  # flax's init as one jitted program
        Trainer(model=model, loss=CE(), optimizer=OptimizerFactory(learning_rate=1e-2),
                mesh=make_mesh(jax.devices()[:1]))
    )
    tracer, sink = Tracer(), PausingSink()
    trainer.fit(stream, epochs=2, scan_chunk=SCAN_CHUNK, tracer=tracer, loggers=sink,
                log_every=0)
    records = last_fit_records()
    return {
        "stream": stream, "records": records, "tracer": tracer, "sink": sink, "trainer": trainer,
        "chunks_an_epoch": chunks_an_epoch, "fit_tid": threading.get_ident(),
        "transforms": [type(t).__name__ for t in compose.transforms],
    }


def test_one_record_per_chunk(traced_fit):
    records, per_epoch = traced_fit["records"], traced_fit["chunks_an_epoch"]
    assert [r["chunk"] for r in records] == list(range(2 * per_epoch))
    assert len({r["fit"] for r in records}) == 1
    assert all(r["steps"] == SCAN_CHUNK for r in records)
    assert [r["compiled"] for r in records] == [True] + [False] * (2 * per_epoch - 1)
    # done-to-done: no period on an epoch's first chunk, one on every other
    assert ["period" in r for r in records] == ([False] + [True] * (per_epoch - 1)) * 2
    assert all(b["done"] > a["done"] for a, b in zip(records, records[1:]))
    for record in records:
        assert all(record[name] >= 0 for name in FIT_STAGES + CHUNK_STAGES["feeder"])
        assert record["h2d_bytes"] > 0


def test_fit_thread_stages_sum_to_the_period(traced_fit):
    timed = [r for r in traced_fit["records"] if "period" in r]
    closing = [sum(r[name] for name in FIT_STAGES) / r["period"] for r in timed]
    # each record within 2% on a quiet machine; a fit thread descheduled between
    # two stages (the suite shares its cores) opens one record wider, so the
    # test holds the median to 2%, the whole to 5% and no record over its period
    assert np.median(closing) == pytest.approx(1.0, abs=0.02)
    assert sum(r[name] for r in timed for name in FIT_STAGES) >= 0.95 * sum(
        r["period"] for r in timed
    )
    assert all(share <= 1.0 + 1e-9 for share in closing)
    # the pause is a logger's: it is the fit thread's `account`
    assert np.median([r["account"] for r in traced_fit["records"] if "period" in r]) >= 0.03


def test_feeder_spans_are_on_another_thread_with_the_same_chunk(traced_fit):
    events = traced_fit["tracer"].to_chrome_trace()["traceEvents"]
    by_name = {}
    for event in events:
        by_name.setdefault(event["name"], []).append(event)
    fit_tid = traced_fit["fit_tid"]
    chunks = set(range(2 * traced_fit["chunks_an_epoch"]))
    for name in FIT_STAGES:
        assert {e["tid"] for e in by_name[name]} == {fit_tid}, name
    assert {e["args"]["chunk"] for e in by_name["dispatch"]} == chunks
    assert {e["args"]["chunk"] for e in by_name["device_wait"]} == chunks
    for name in ("stack", "h2d", "feed_full"):
        tids = {e["tid"] for e in by_name[name]}
        assert fit_tid not in tids, name
        assert {e["args"]["chunk"] for e in by_name[name]} == chunks, name
    # the input pipeline runs on the feeder too, but for an epoch's first batch,
    # which the fit thread pulls itself before the feeder starts
    for name, per_batch in (("batch_build", 1), ("transform", len(traced_fit["transforms"]))):
        on_fit_thread = [e for e in by_name[name] if e["tid"] == fit_tid]
        assert len(on_fit_thread) == 2 * per_batch, name
        assert len(by_name[name]) == len(chunks) * SCAN_CHUNK * per_batch, name
    # a chunk's `train_step` stays the parent of its dispatch and device_wait
    assert [e["args"]["steps"] for e in by_name["train_step"]] == [SCAN_CHUNK] * len(chunks)


def test_stack_counts_the_device_leaves_of_the_default_transforms(traced_fit):
    # the default SASRec transforms keep numpy in numpy: no leaf arrives as a
    # jax Array, no transform dispatched a device program
    assert {r["device_leaves"] for r in traced_fit["records"]} == {0}
    assert {r["transform_device_programs"] for r in traced_fit["records"]} == {0}
    # and on the CPU the CE head writes its logits: no step ran the fused head
    assert {r["ce_fused_steps"] for r in traced_fit["records"]} == {0}
    events = traced_fit["tracer"].to_chrome_trace()["traceEvents"]
    assert {e["args"]["device_leaves"] for e in events if e["name"] == "stack"} == {0}
    assert {e["args"]["device_programs"] for e in events if e["name"] == "transform"} == {0}
    # one jax Array planted per batch is one device-to-host read inside `stack`
    trainer, stream = traced_fit["trainer"], traced_fit["stream"]

    def planted():
        for batch in stream():
            yield {**batch, "padding_mask": jax.numpy.asarray(batch["padding_mask"])}

    trainer.fit(planted, epochs=1, scan_chunk=SCAN_CHUNK, log_every=0)
    records = last_fit_records()
    assert len(records) == traced_fit["chunks_an_epoch"]
    assert {r["device_leaves"] for r in records} == {SCAN_CHUNK}


def test_bert4rec_transforms_dispatch_one_program_a_batch(traced_fit):
    """The MLM pipeline through the same trainer's chunked fit: ``Compose`` runs
    ONE compiled program a batch (the stochastic run), and only the two masks
    it makes arrive as jax Arrays."""
    from replay_tpu.nn.transform.template import make_default_bert4rec_transforms

    trainer, schema = traced_fit["trainer"], traced_fit["trainer"].model.schema
    compose = Compose(make_default_bert4rec_transforms(schema, mask_prob=0.3)["train"])
    rng = np.random.default_rng(3)
    raws = [
        {"item_id": rng.integers(0, NUM_ITEMS, (BATCH, SEQ_LEN)).astype(np.int32),
         "item_id_mask": np.ones((BATCH, SEQ_LEN), bool), "valid": np.ones(BATCH, bool)}
        for _ in range(3 * SCAN_CHUNK)
    ]

    def stream():
        key = jax.random.PRNGKey(0)
        for raw in raws:
            key, sub = jax.random.split(key)
            batch = compose(raw, sub)
            batch.pop("token_mask")  # SASRec does not take it; its stack would count it
            yield batch

    tracer = Tracer()
    trainer.fit(stream, epochs=1, scan_chunk=SCAN_CHUNK, tracer=tracer, log_every=0)
    records = last_fit_records()
    assert len(records) == 3
    # an epoch's first batch is pulled by the fit thread before the feeder starts
    assert [r["transform_device_programs"] for r in records] == [SCAN_CHUNK - 1] + [SCAN_CHUNK] * 2
    assert {r["device_leaves"] for r in records} == {SCAN_CHUNK}  # target_padding_mask
    runs = [e for e in tracer.to_chrome_trace()["traceEvents"]
            if e["name"] == "transform" and e["args"]["device_programs"]]
    assert len(runs) == len(raws) and {e["args"]["device_programs"] for e in runs} == {1}
    assert {e["args"]["transform"] for e in runs} == {
        "TokenMaskTransform+CopyTransform+EqualityMaskTransform+UnsqueezeTransform+GroupTransform"
    }


def test_records_hold_the_input_pipeline_by_transform(traced_fit):
    for record in traced_fit["records"][1:]:
        assert set(record["transform_by_name"]) == set(traced_fit["transforms"])
        assert sum(record["transform_by_name"].values()) == pytest.approx(record["transform"])
        assert record["batch_build"] > 0


def test_compose_of_two_transforms_yields_two_transform_names():
    from replay_tpu.nn.transform import RenameTransform, UnsqueezeTransform

    compose = Compose([RenameTransform({"a": "b"}), UnsqueezeTransform("b", -1)])
    claim_chunk(-1)  # what this thread's stages held before is not this test's
    out = compose({"a": np.zeros((2, 3), np.int32)})
    assert out["b"].shape == (2, 3, 1)
    totals = claim_chunk(-2)
    assert type(out["b"]) is np.ndarray and totals["transform_device_programs"] == 0
    assert set(totals["transform_by_name"]) == {"RenameTransform", "UnsqueezeTransform"}
    assert totals["transform"] == pytest.approx(sum(totals["transform_by_name"].values()))


def test_goodput_of_the_traced_chunked_fit_reads_the_same_phases(traced_fit):
    fit_end = traced_fit["sink"].events[-1]
    assert fit_end.event == "on_fit_end"
    fractions = fit_end.payload["goodput"]["fractions"]
    # the stage spans split phases, they add none
    assert set(fractions) == set(GOODPUT_SPANS) | {"other"}
    assert sum(fractions.values()) == pytest.approx(1.0, abs=1e-6)
    spans = fit_end.payload["spans"]
    wall = fit_end.payload["goodput"]["wall_seconds"]
    # train_step is what it was: the enqueue and the sync, the compile carved out
    step_self = sum(spans[name]["self_seconds"] for name in ("train_step", "dispatch", "device_wait"))
    assert fractions["train_step"] == pytest.approx(step_self / wall, rel=1e-6)
    assert fractions["compile"] == pytest.approx(spans["compile"]["self_seconds"] / wall, rel=1e-6)
    assert spans["compile"]["count"] == 1 and fractions["compile"] > 0
    # the feed hid stack and copy from the fit thread
    assert fractions["h2d"] == 0.0 and spans["h2d"]["count"] == len(traced_fit["records"])


def test_with_the_feed_off_stack_and_copy_are_the_fit_threads(traced_fit):
    trainer = traced_fit["trainer"]  # compiled: this fit only re-runs the scan
    sink, tracer = PausingSink(), Tracer()
    trainer.fit(traced_fit["stream"], epochs=1, scan_chunk=SCAN_CHUNK, device_feed=False,
                tracer=tracer, loggers=sink, log_every=0)
    records = last_fit_records()
    assert [r["chunk"] for r in records] == list(range(traced_fit["chunks_an_epoch"]))
    assert records[0]["fit"] > traced_fit["records"][0]["fit"]
    assert all(r["stack"] > 0 and r["h2d"] > 0 for r in records)
    assert all(r["device_leaves"] == 0 and not r["compiled"] for r in records)
    threads = {e["tid"] for e in tracer.to_chrome_trace()["traceEvents"]}
    assert threads == {threading.get_ident()}
    fractions = sink.events[-1].payload["goodput"]["fractions"]
    spans = sink.events[-1].payload["spans"]
    wall = sink.events[-1].payload["goodput"]["wall_seconds"]
    # h2d reads stack + copy, as it did when one span wrapped both
    assert fractions["h2d"] == pytest.approx(
        (spans["stack"]["self_seconds"] + spans["h2d"]["self_seconds"]) / wall, rel=1e-6
    )
    assert fractions["h2d"] > 0
