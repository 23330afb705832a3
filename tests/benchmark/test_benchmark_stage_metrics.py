"""The per-layer readers of the program's chunk stage log
(``benchmark/metrics/<name>.py`` over ``benchmark/stages.py``), against a
handmade log: the median over chunks, nothing under 10 records, records in which
a program compiled left out, only the process's last ``fit``."""

import collections
import json

import pytest

import bench_helpers
from benchmark import run as bench_run

SPEC = json.loads((bench_helpers.REPO / "BENCHMARK.json").read_text())
STAGE_METRICS = [m for m in SPEC["per_layer"] if m["source"] == "program_span"]
# what each reader gives for the handmade log below
EXPECTED = {
    "feed_wait_ms_per_chunk": 2.0,
    "host_gap_pct": 100.0 * (0.002 + 0.004 + 0.006) / 0.25,
    "bookkeeping_ms_per_chunk": 6.0,
    "batch_build_ms_per_step": 1e3 * 0.016 / 8,
    "transform_ms_per_step": 1e3 * 0.08 / 8,
    "stack_sync_ms_per_chunk": 12.0,
    "h2d_ms_per_chunk": 3.0,
    "feed_slack_ms_per_chunk": 90.0,
}


def record(fit, chunk, compiled=False, scale=1.0, period=0.25):
    out = {
        "fit": fit, "chunk": chunk, "steps": 8, "done": 100.0 + chunk, "compiled": compiled,
        "data_wait": 0.002 * scale, "dispatch": 0.004 * scale, "device_wait": 0.238,
        "account": 0.006 * scale, "stack": 0.012 * scale, "h2d": 0.003 * scale,
        "feed_full": 0.09 * scale, "batch_build": 0.016 * scale, "transform": 0.08 * scale,
        "transform_by_name": {"NextTokenTransform": 0.08 * scale}, "device_leaves": 16,
        "h2d_bytes": 1 << 20,
    }
    if period is not None:
        out["period"] = period
    return out


def handmade_log(steady=11):
    """An earlier fit whose numbers are ten times off, then the last fit: a chunk
    that compiled (far off too), its first steady chunk with no period, ``steady``
    more, of which two read far from the rest (the median leaves them where they
    are)."""
    log = [record(1, chunk, scale=10.0) for chunk in range(12)]
    log.append(record(2, 0, compiled=True, scale=50.0, period=None))
    log.append(record(2, 1, period=None))
    log += [record(2, 2 + chunk) for chunk in range(steady - 2)]
    log += [record(2, 2 + steady, scale=40.0), record(2, 3 + steady, scale=0.1)]
    return log


@pytest.fixture
def stage_log(monkeypatch):
    from replay_tpu.obs import trace

    def install(records):
        monkeypatch.setattr(trace, "_CHUNK_LOG", collections.deque(records, maxlen=4096))

    return install


def reader(name):
    return bench_run.load_module(bench_helpers.REPO, f"benchmark/metrics/{name}.py").read


def test_the_stage_metrics_are_the_eight_of_the_two_cells():
    assert sorted(m["name"] for m in STAGE_METRICS) == sorted(EXPECTED)
    for metric in STAGE_METRICS:
        assert metric["moves"] == "fit_samples_per_s"
        assert metric["workloads"] == ["sasrec_ml20m.fit", "bert4rec_ml20m.fit"]
        assert metric["layer"] in ("trainer loop", "input pipeline")
        assert (metric["unit"], metric["better"]) == (
            ("%", "lower") if metric["name"] == "host_gap_pct"
            else ("ms", "higher" if metric["name"] == "feed_slack_ms_per_chunk" else "lower")
        )


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_median_of_the_last_fits_steady_chunks(name, stage_log):
    stage_log(handmade_log())
    assert reader(name)({}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_nothing_under_ten_records_or_with_no_log(name, stage_log, monkeypatch):
    # 9 steady records of the last fit (8 with a period): the earlier fit's 12 do not count
    stage_log(handmade_log(steady=8))
    assert reader(name)({}) is None
    stage_log([])
    assert reader(name)({}) is None
    # a program without the log (the parent of the PR that brought it): nothing, no error
    from replay_tpu.obs import trace

    monkeypatch.delattr(trace, "chunk_stage_log")
    assert reader(name)({}) is None
