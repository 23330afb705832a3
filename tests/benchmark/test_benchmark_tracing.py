"""The trace reduction on events with known answers: a hand-made nest, and a
slice of the first chip trace of ``sasrec_ml20m.fit`` recorded beside this file."""

import json
from pathlib import Path

import pytest

from benchmark import tracing

PLANE = "/device:TPU:0"


PATHS = {
    "while.1": "jit(train_scan)/while",
    "fusion.1": "jit(train_scan)/while/body/jvp(forward)/dot_general",
    "fusion.2": "jit(train_scan)/while/body/transpose(jvp(loss))/mul",
    "fusion.3": "jit(train_scan)/while/body/adam/add",
    "copy.9": "jit(other)/copy",
}


def event(line, name, start, dur):
    return {"plane": PLANE, "line": line, "name": name, "start_ns": start, "dur_ns": dur}


def handmade():
    ops, mods = tracing.OPS_LINE, tracing.MODULES_LINE
    return [
        event(mods, "jit_train_scan(1)", 0, 1000),
        event(mods, "jit_train_scan(1)", 1500, 1000),
        event(mods, "jit_other(2)", 1100, 100),
        # run 1: a while spanning two leaves with a 100 ns hole between them
        event(ops, "while.1", 0, 1000),
        event(ops, "fusion.1", 0, 400),
        event(ops, "fusion.2", 500, 500),
        event(ops, "copy.9", 1100, 100),  # another program, between the runs
        # run 2: no parent; 300 ns idle at the end
        event(ops, "fusion.1", 1500, 300),
        event(ops, "fusion.3", 1800, 400),
    ]


def test_handmade_busy_idle_scopes_and_top_ops():
    got = tracing.reduce_capture(handmade(), "train_scan", ("loss", "forward"), 1, PATHS)
    assert got["runs"] == 2
    assert got["window_s"] == pytest.approx(2500e-9)
    # 400 + 500 + 100 + 300 + 400: the while does no work, the hole inside it is idle
    assert got["busy_s"] == pytest.approx(1700e-9)
    assert got["scope_s"]["forward"] == pytest.approx(700e-9)
    assert got["scope_s"]["loss"] == pytest.approx(500e-9)
    # the other program's copy 100 + adam 400; the while's own 100 is under no scope
    assert got["scope_s"]["other"] == pytest.approx(500e-9)
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(700e-9)]
    assert ["after copy.9", pytest.approx(300e-9)] in got["idle_gaps"]


@pytest.mark.parametrize(
    "path, scope",
    [
        ("jit(train_scan)/while/body/jvp(forward)/body/encoder/dot_general", "forward"),
        ("jit(train_scan)/while/body/transpose(jvp(forward))/body/embed/mul", "forward"),
        ("jit(train_scan)/while/body/transpose(jvp(loss))/dot_general", "loss"),
        ("jit(train_scan)/while/body/jvp(loss)/reduce_max", "loss"),
        ("jit(train_scan)/while/body/add", None),
        ("jit(train_scan)/while/body/lossy/add", None),
    ],
)
def test_scope_of_sees_through_transform_wrappers(path, scope):
    assert tracing.scope_of(path, ("loss", "forward")) == scope


def test_more_chips_than_planes_is_an_error():
    with pytest.raises(RuntimeError, match="device plane"):
        tracing.reduce_capture(handmade(), "train_scan", ("loss", "forward"), chips=4)


def recorded_events(recorded):
    plane, names = recorded["plane"], recorded["names"]
    module = recorded["module"]
    events = [{"plane": plane, "line": tracing.MODULES_LINE, "name": module[0],
               "start_ns": module[1], "dur_ns": module[2]}]
    events += [{"plane": plane, "line": tracing.OPS_LINE, "name": names[i], "start_ns": start,
                "dur_ns": dur} for i, start, dur in recorded["ops"]]
    return events


def test_recorded_chip_trace_gives_the_numbers_read_by_hand():
    recorded = json.loads((Path(__file__).parent / "trace" / "recorded_trace.json").read_text())
    got = tracing.reduce_capture(
        recorded_events(recorded), "train_scan", ("loss", "forward"), chips=1,
        op_paths=recorded["op_paths"],
    )
    want = recorded["by_hand"]
    assert got["runs"] == want["runs"]
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for scope, seconds in want["scope_s"].items():
        assert got["scope_s"][scope] == pytest.approx(seconds, rel=1e-9)
    assert got["device_ops"][0] == [want["top_op"], pytest.approx(want["top_op_s"], rel=1e-9)]
    # the head is nearly the whole step: 25.4 of 28.5 ms, the blocks 2.9 ms
    assert got["scope_s"]["loss"] / got["window_s"] == pytest.approx(0.8916, abs=1e-3)
