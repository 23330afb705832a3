"""The per-layer readers of the program's start-up log
(``benchmark/metrics/<name>.py`` over ``benchmark/startup.py``), against a
handmade start-up log and chunk log: a span summed over the whole log, a compile
counter over the ``fit`` calls' start-up records and the chunks that compiled,
the steady count over the last ``fit``'s other chunks, nothing from a program
without the log."""

import collections
import json

import pytest

import bench_helpers
from benchmark import run as bench_run

SPEC = json.loads((bench_helpers.REPO / "BENCHMARK.json").read_text())
SETUP_METRICS = [m for m in SPEC["per_layer"] if m["layer"] == "set-up"]
CELLS = [w["name"] for w in SPEC["workloads"]]
# what each reader gives for the handmade logs below
EXPECTED = {
    "setup_import_s": 1.5 + 0.25,
    "setup_data_s": (2.0 + 3.0 + 0.5) + 0.125,
    "setup_init_state_s": 4.0 + 1.0,
    "setup_trace_lower_s": (0.5 + 0.25) + (0.125 + 0.0625) + (2.0 + 1.0),
    "setup_backend_compile_s": 6.0 + 0.5 + 8.0,
    "setup_programs_built": 30 + 3 + 2,
    "setup_cache_misses": (30 - 26) + (3 - 3) + (2 - 1),  # built less fetched
    "steady_programs_built": 1 + 2,
}


def chunk(fit, ordinal, compiled=False, **counters):
    return {"fit": fit, "chunk": ordinal, "steps": 8, "compiled": compiled, **counters}


STARTUP_LOG = [
    # the first fit call: the whole of set-up before it
    {
        "fit": 1, "pkg_import": 1.5, "pkg_import_by_name": {"replay_tpu.nn": 1.0},
        "split": 2.0, "tokenize": 3.0, "batcher_init": 0.5, "init_state": 4.0,
        "compile_programs": 30, "compile_trace_s": 0.5, "compile_lower_s": 0.25,
        "compile_backend_s": 6.0, "compile_cache_load_s": 1.0, "compile_cache_hits": 26,
        # jax wrote one of the four it compiled: the others lay under its thresholds
        "compile_cache_misses": 1,
    },
    # the second: what ran between the two (a field is absent where nothing added to it)
    {
        "fit": 2, "init_state": 1.0, "account": 0.001, "compile_programs": 3,
        "compile_trace_s": 0.125, "compile_lower_s": 0.0625, "compile_backend_s": 0.5,
        "compile_cache_hits": 3,
    },
]
# after the last fit: the benchmark's own reference compiles; its spans count, its programs do not
SINCE = {
    "pkg_import": 0.25, "tokenize": 0.125, "compile_programs": 50, "compile_trace_s": 9.0,
    "compile_lower_s": 9.0, "compile_backend_s": 90.0, "compile_cache_hits": 10,
}
CHUNK_LOG = [
    chunk(1, 0, compiled=True, compile_programs=2, compile_trace_s=2.0, compile_lower_s=1.0,
          compile_backend_s=8.0, compile_cache_hits=1),
    chunk(1, 1, compile_programs=7),  # an earlier fit's steady chunk: in neither sum
    chunk(2, 0, compile_programs=1),
    chunk(2, 1, compile_programs=0),
    chunk(2, 2, compile_programs=2),
]


@pytest.fixture
def logs(monkeypatch):
    from replay_tpu.obs import trace

    monkeypatch.setattr(trace, "_STARTUP_LOG", collections.deque(STARTUP_LOG, maxlen=4096))
    monkeypatch.setattr(trace, "_CHUNK_LOG", collections.deque(CHUNK_LOG, maxlen=4096))
    monkeypatch.setattr(trace._THREAD, "loose", dict(SINCE))
    return trace


def reader(name):
    return bench_run.load_module(bench_helpers.REPO, f"benchmark/metrics/{name}.py").read


def test_the_set_up_metrics_are_the_eight_in_every_cell():
    assert [m["name"] for m in SPEC["per_layer"][-8:]] == list(EXPECTED)
    assert sorted(m["name"] for m in SETUP_METRICS) == sorted(EXPECTED)
    for metric in SETUP_METRICS:
        steady = metric["name"] == "steady_programs_built"
        assert metric["moves"] == ("fit_samples_per_s" if steady else "setup_s")
        assert metric["workloads"] == CELLS
        assert (metric["source"], metric["better"]) == ("program_counter", "lower")
        assert metric["unit"] == ("s" if metric["name"].endswith("_s") else "count")


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_sums_its_fields_over_its_records(name, logs):
    assert reader(name)({}) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_gives_nothing_for_a_program_without_the_start_up_log(name, logs, monkeypatch):
    # the parent of the PR that brought the log: nothing, no error
    monkeypatch.delattr(logs, "startup_log")
    assert reader(name)({}) is None


def test_steady_count_needs_a_chunk_to_read(logs, monkeypatch):
    monkeypatch.setattr(logs, "_CHUNK_LOG", collections.deque(maxlen=4096))
    assert reader("steady_programs_built")({}) is None
    assert reader("setup_programs_built")({}) == 30 + 3  # the start-up records alone
