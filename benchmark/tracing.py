"""From a ``jax.profiler`` capture to device numbers: the benchmark's reduction.

A capture directory holds ``plugins/profile/<time>/<host>.xplane.pb``, which
``jax.profiler.ProfileData`` reads with nothing but JAX. Device planes are named
``/device:TPU:<n>``; on each, the line ``XLA Ops`` carries one event per executed
HLO instruction and ``XLA Modules`` one per executed program. (What was found in
the first chip trace read by hand is in PERF.md, Findings.)

:func:`load_events` flattens the device planes into plain dicts (what the test
trace beside the tests holds as JSON); every other function works on that list,
so the same reduction runs on the chip and in the tests.

Nesting: a ``while`` (the scan over steps) is itself an event that spans its
body's events on the same line, and a few fusions span a small event of their
own. Time is therefore attributed by SELF time (duration minus what the event
holds), and control-flow containers (``while``, ``conditional``, ``call``) do no
work themselves: they count neither as busy nor under any scope, so the holes
between a loop body's ops stay idle.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

Event = Dict[str, Any]
CONTAINERS = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# An op event's stats carry no scope path on the v5e (PERF.md, Findings PR 24): the
# path comes from the compiled program's HLO text, by instruction name.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SHORT = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")


def find_xplane(capture_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(capture_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {capture_dir}")
    return found[-1]


def load_events(xplane_path: str, plane_prefix: str = "/device:") -> List[Event]:
    """Events of the device planes' op and module lines, as plain dicts."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    events: List[Event] = []
    for plane in data.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for event in line.events:
                events.append(
                    {
                        "plane": plane.name,
                        "line": line.name,
                        "name": event.name,
                        "start_ns": float(event.start_ns),
                        "dur_ns": float(event.duration_ns),
                    }
                )
    return events


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e["plane"] for e in events})


def op_paths_from_hlo(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name metadata} of a compiled program's HLO text. The
    op_name holds the ``jax.named_scope`` path (``jit(f)/while/body/jvp(loss)/..``);
    a fusion carries that of the instruction it was built around."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if found:
            path = _OP_NAME.search(line)
            if path:
                out[found.group(1)] = path.group(1)
    return out


def instruction_name(event_name: str) -> str:
    """``%fusion.9 = f32[8]{0} fusion(...)`` (a v5e op event's name) -> ``fusion.9``."""
    return event_name.split(" ", 1)[0].lstrip("%")


def is_container(event_name: str) -> bool:
    return instruction_name(event_name).split(".")[0] in CONTAINERS


def short_name(event_name: str) -> str:
    """Instruction name and result shape: what a breakdown line can hold."""
    found = _SHORT.match(event_name)
    if not found:
        return event_name[:80]
    return f"{found.group(1)} {found.group(2)}" if found.group(2) else found.group(1)


_SEGMENT = r"(?:^|[/(\s]){}(?:$|[/)\s])"


def scope_of(path: str, scopes: Sequence[str]) -> Optional[str]:
    """The first of ``scopes`` that is a segment of ``path``; transform wrappers
    such as ``jvp(loss)`` and ``transpose(jvp(loss))`` are seen through. Give
    inner scopes first (``loss`` before ``forward`` would be wrong only if one
    nested in the other; here they are siblings)."""
    for scope in scopes:
        if re.search(_SEGMENT.format(re.escape(scope)), path):
            return scope
    return None


def self_times(line_events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """(event, self nanoseconds) for the events of ONE line."""
    ordered = sorted(line_events, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    out: List[List[Any]] = []
    stack: List[int] = []
    for event in ordered:
        end = event["start_ns"] + event["dur_ns"]
        while stack:
            parent = out[stack[-1]][0]
            if event["start_ns"] >= parent["start_ns"] + parent["dur_ns"] - 1e-6:
                stack.pop()
            else:
                break
        if stack and end <= out[stack[-1]][0]["start_ns"] + out[stack[-1]][0]["dur_ns"] + 1e-6:
            out[stack[-1]][1] -= event["dur_ns"]
        out.append([event, event["dur_ns"]])
        stack.append(len(out) - 1)
    return [(e, max(s, 0.0)) for e, s in out]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for start, stop in sorted(intervals):
        if end is None or start > end:
            total += stop - start
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def program_runs(events: Sequence[Event], plane: str, hint: str) -> List[Event]:
    """Executions of the timed program on ``plane``: the module events whose name
    holds ``hint``, or, where none does, those of the module with most time."""
    modules = [e for e in events if e["plane"] == plane and e["line"] == MODULES_LINE]
    named = [e for e in modules if hint and hint in e["name"]]
    if named:
        return sorted(named, key=lambda e: e["start_ns"])
    by_name: Dict[str, float] = {}
    for e in modules:
        by_name[e["name"]] = by_name.get(e["name"], 0.0) + e["dur_ns"]
    if not by_name:
        return []
    top = max(by_name, key=by_name.get)
    return sorted((e for e in modules if e["name"] == top), key=lambda e: e["start_ns"])


def reduce_plane(
    events: Sequence[Event], plane: str, hint: str, scopes: Sequence[str],
    op_paths: Optional[Mapping[str, str]] = None,
) -> Optional[Dict[str, Any]]:
    """Busy and idle time, per-scope self time and the top ops of ONE device over
    the steady slice: from the start of the first whole run of the program in the
    capture to the end of the last."""
    runs = program_runs(events, plane, hint)
    if not runs:
        return None
    start = runs[0]["start_ns"]
    stop = runs[-1]["start_ns"] + runs[-1]["dur_ns"]
    ops = [
        e
        for e in events
        if e["plane"] == plane
        and e["line"] == OPS_LINE
        and e["start_ns"] >= start - 1e-6
        and e["start_ns"] + e["dur_ns"] <= stop + 1e-6
    ]
    paths = op_paths or {}
    timed = [(e, ns) for e, ns in self_times(ops) if not is_container(e["name"])]
    working = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e, _ in timed]
    by_scope: Dict[str, float] = {scope: 0.0 for scope in scopes}
    by_scope["other"] = 0.0
    by_op: Dict[str, float] = {}
    for event, self_ns in timed:
        scope = scope_of(paths.get(instruction_name(event["name"]), ""), scopes) or "other"
        by_scope[scope] += self_ns
        name = short_name(event["name"])
        by_op[name] = by_op.get(name, 0.0) + self_ns
    gaps: Dict[str, float] = {}
    ordered = sorted((e for e, _ in timed), key=lambda e: e["start_ns"])
    end = None
    for event in ordered:
        if end is not None and event["start_ns"] > end[0]:
            key = f"after {short_name(end[1])}"
            gaps[key] = gaps.get(key, 0.0) + event["start_ns"] - end[0]
        stop_here = event["start_ns"] + event["dur_ns"]
        if end is None or stop_here > end[0]:
            end = (stop_here, event["name"])
    top = lambda table: [  # noqa: E731
        [name, ns / 1e9] for name, ns in sorted(table.items(), key=lambda kv: -kv[1])[:10]
    ]
    return {
        "runs": len(runs),
        "window_s": (stop - start) / 1e9,
        "busy_s": union_ns(working) / 1e9,
        "scope_s": {scope: ns / 1e9 for scope, ns in by_scope.items()},
        "device_ops": top(by_op),
        "idle_gaps": top(gaps),
    }


def reduce_capture(
    events: Sequence[Event], hint: str, scopes: Sequence[str], chips: int,
    op_paths: Optional[Mapping[str, str]] = None,
) -> Dict[str, Any]:
    """The per-device reductions averaged over the chips used. Raises where the
    capture holds no whole run of the program on some device."""
    planes = device_planes(events)
    if len(planes) < chips:
        raise RuntimeError(f"the capture holds {len(planes)} device plane(s), the cell uses {chips}")
    per_plane = [
        reduce_plane(events, plane, hint, scopes, op_paths) for plane in planes[:chips]
    ]
    if any(r is None or r["busy_s"] <= 0 for r in per_plane):
        raise RuntimeError("the capture holds no whole run of the timed program on a device")
    mean = lambda key: sum(r[key] for r in per_plane) / len(per_plane)  # noqa: E731
    return {
        "planes": planes[:chips],
        "runs": min(r["runs"] for r in per_plane),
        "window_s": mean("window_s"),
        "busy_s": mean("busy_s"),
        "scope_s": {
            scope: sum(r["scope_s"][scope] for r in per_plane) / len(per_plane)
            for scope in per_plane[0]["scope_s"]
        },
        "device_ops": per_plane[0]["device_ops"],
        "idle_gaps": per_plane[0]["idle_gaps"],
    }
