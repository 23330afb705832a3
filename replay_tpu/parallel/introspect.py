"""Collective + sharding introspection over lowered/compiled HLO.

Beyond-parity (the reference's DDP story has no cross-device observability at
all — SURVEY.md §2.9): the DP×TP(×SP) programs this stack compiles move bytes
through XLA-inserted collectives that no host-side tracer can see. This module
makes them inspectable *statically*, from the compiled program's HLO text —
no device execution, no profiler session:

* :func:`collective_inventory` walks an ``as_text()`` dump and returns every
  collective op (all-gather / all-reduce / reduce-scatter / collective-permute
  / all-to-all, ``-start`` async variants included) with its result shape,
  dtype, byte size and replica groups, plus a best-effort mesh-axis guess.
* :func:`summarize_collectives` folds an inventory into the
  ``{count, bytes, by_op}`` record carried by roofline records and dry runs.
* :func:`sharding_report` renders every param leaf's ``PartitionSpec`` and
  flags *accidental full replication* — a table that was supposed to shard
  over the mesh (``expect_sharded``) but lowered replicated, the silent way a
  vocab-TP run degenerates into n_tp copies of the catalog.

The HLO-text parsing half is import-light (pure ``re``); only
:func:`sharding_report` touches jax (lazily) to read leaf shardings. The
CEFusedTP no-table-gather regression guard (tests/parallel/test_collectives.py)
is built on :func:`collective_inventory`: PR 7's core invariant — the
``[I/n_tp, E]`` item table is never all-gathered, only the ``[rows]``-sized
lse/max combine moves over the TP axis — is now a static assertion, not a
memory graph someone eyeballs.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Mapping, Optional, Sequence

__all__ = [
    "collective_bytes",
    "collective_inventory",
    "sharding_report",
    "summarize_collectives",
]

# HLO element sizes in bytes (shape strings like f32[8,16]{1,0})
_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
)

# `%name = f32[8,16]{1,0} all-gather(...)` — the shape part is matched
# lazily up to the first op token because optimized-HLO layouts carry
# tiling/memory-space annotations (`{1,0:T(8,128)}`, `{1,0:S(1)}`) and async
# starts have tuple shapes; the op token itself is always the first thing
# after the result shape, so the lazy match cannot overshoot into operands
_COLLECTIVE_RE = re.compile(
    r"%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>.+?)\s"
    r"(?P<op>" + "|".join(_COLLECTIVE_OPS) + r")(?:-start)?\("
)

_SHAPE_RE = re.compile(r"(?P<dtype>[a-z]\w*?)\[(?P<dims>[\d,\s]*)\]")

_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{(?P<groups>[^}]*(?:\},\{[^}]*)*)\}\}")
# iota-form groups: replica_groups=[2,4]<=[4,2]T(1,0)
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(?P<shape>[\d,]+)\]<=")


def _shape_bytes(shape_text: str) -> Optional[int]:
    """Total byte size of an HLO shape string (sum over tuple elements);
    None when no parseable array shape is present (token/opaque shapes)."""
    total = 0
    seen = False
    for match in _SHAPE_RE.finditer(shape_text):
        dtype = match.group("dtype")
        if dtype not in _DTYPE_BYTES:
            continue
        seen = True
        dims = [int(d) for d in match.group("dims").replace(" ", "").split(",") if d]
        count = 1
        for dim in dims:
            count *= dim
        total += count * _DTYPE_BYTES[dtype]
    return total if seen else None


def _parse_groups(line: str) -> Optional[List[List[int]]]:
    match = _REPLICA_GROUPS_RE.search(line)
    if match:
        groups = []
        for part in match.group("groups").split("},{"):
            ids = [int(x) for x in part.strip("{}").split(",") if x.strip()]
            if ids:
                groups.append(ids)
        return groups or None
    match = _IOTA_GROUPS_RE.search(line)
    if match:
        # iota form [G, S]<=[...]: G groups of S devices; synthesize the ids
        # only as sizes (the permutation is not worth re-deriving here)
        dims = [int(d) for d in match.group("shape").split(",")]
        if len(dims) == 2:
            return [list(range(start * dims[1], (start + 1) * dims[1])) for start in range(dims[0])]
    return None


def _axis_guess(groups: Optional[List[List[int]]], mesh_shape: Optional[Mapping[str, int]]) -> Optional[str]:
    """Best-effort mesh-axis attribution from replica-group stride.

    A ``("data", "model")`` mesh lays devices out row-major: groups of
    consecutive ids (stride 1) move over the LAST axis, groups with stride ==
    last-axis size move over the first. Returns None when the pattern matches
    neither (multi-axis collectives, permutes with custom pairs).
    """
    if not groups or not mesh_shape or len(mesh_shape) < 1:
        return None
    axes = list(mesh_shape.items())
    group = groups[0]
    if len(group) < 2:
        return None
    stride = group[1] - group[0]
    if any(b - a != stride for a, b in zip(group, group[1:])):
        return None
    # row-major layout: the last axis has stride 1; an axis earlier in the
    # tuple has stride == product of the later axes' sizes
    running = 1
    for name, size in reversed(axes):
        if stride == running and len(group) == size:
            return name
        running *= size
    return None


def collective_inventory(
    hlo_text: str, mesh_shape: Optional[Mapping[str, int]] = None
) -> List[Dict[str, Any]]:
    """Every collective op in an HLO ``as_text()`` dump.

    Returns one record per op: ``{"op", "name", "shape", "bytes",
    "replica_groups", "group_size", "mesh_axis"}``. ``bytes`` is the RESULT
    shape's size — the resident footprint the collective materializes (for an
    all-gather this is the gathered tensor, i.e. what the no-table-gather
    guard bounds); per-shard shapes in an SPMD module are per-device.
    ``mesh_axis`` is a best-effort stride guess against ``mesh_shape`` (e.g.
    ``{"data": 4, "model": 2}``), None when ambiguous. ``-done`` halves of
    async pairs are skipped — the ``-start`` op carries the shape.
    """
    inventory: List[Dict[str, Any]] = []
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        match = _COLLECTIVE_RE.search(line)
        if not match:
            continue
        groups = _parse_groups(line)
        record = {
            "op": match.group("op"),
            "name": match.group("name"),
            "shape": " ".join(match.group("shape").split()),
            "bytes": _shape_bytes(match.group("shape")),
            "replica_groups": groups,
            "group_size": len(groups[0]) if groups else None,
            "mesh_axis": _axis_guess(groups, mesh_shape),
        }
        inventory.append(record)
    return inventory


def collective_bytes(inventory: Sequence[Mapping[str, Any]]) -> int:
    """Total result bytes over an inventory (unparseable shapes count 0)."""
    return int(sum(entry.get("bytes") or 0 for entry in inventory))


def summarize_collectives(inventory: Sequence[Mapping[str, Any]]) -> Dict[str, Any]:
    """Fold an inventory into the record roofline records / dry runs carry:
    ``{"count", "bytes", "by_op": {op: {"count", "bytes"}}}``."""
    by_op: Dict[str, Dict[str, int]] = {}
    for entry in inventory:
        bucket = by_op.setdefault(str(entry.get("op")), {"count": 0, "bytes": 0})
        bucket["count"] += 1
        bucket["bytes"] += int(entry.get("bytes") or 0)
    return {
        "count": len(inventory),
        "bytes": collective_bytes(inventory),
        "by_op": by_op,
    }


def sharding_report(
    params: Any,
    mesh: Any = None,
    expect_sharded: Sequence[str] = ("embedding_",),
    rules: Any = None,
) -> Dict[str, Any]:
    """Render every param leaf's PartitionSpec; flag accidental replication.

    Returns ``{"params": [{"path", "shape", "spec", "bytes", "replicated"}],
    "replicated_bytes", "sharded_bytes", "flags": [...]}``. A leaf is
    *replicated* when its spec names no mesh axis. ``flags`` lists the
    failure modes a DP×TP(×SP) run must not ship silently:

    * with a :class:`~replay_tpu.parallel.sharding.ShardingRules` table in
      ``rules`` (the preferred mode): any leaf whose logical-axis annotation
      maps to a multi-device mesh axis under the table but lowered fully
      replicated — the rule said shard, the program did not. This is the
      "zero accidental full replication under the rules" check the dryrun and
      CI hard-assert. Leaves the rule table legitimately replicates (rule →
      None, or a non-divisible dim the placement already warned about) are
      never flagged.
    * without ``rules`` (legacy mode): a ≥2-D leaf whose path matches
      ``expect_sharded`` but lowered fully replicated on a multi-device
      ``model`` axis (the vocab-TP table degenerating into n_tp full copies);
    * any leaf with no readable sharding at all (host arrays that never got
      placed).
    """
    import jax

    model_axis_size = None
    if mesh is not None:
        try:
            model_axis_size = int(dict(mesh.shape).get("model", 1))
        except (TypeError, ValueError):
            model_axis_size = None

    expected_axes = None
    if rules is not None:
        if mesh is None:
            msg = "sharding_report(rules=...) needs the mesh to size the rules"
            raise ValueError(msg)
        from replay_tpu.parallel.sharding import logical_axes

        def rule_expectation(path, leaf):
            """Mesh axes the table wants for this leaf (divisible dims only —
            the same resolved_axis decision param placement made)."""
            names = logical_axes(path, leaf)
            shape = tuple(getattr(leaf, "shape", ()) or ())
            return tuple(
                rules.resolved_axis(mesh, name, dim)
                for name, dim in zip(names, shape)
            )

        expected_axes = rule_expectation

    table: List[Dict[str, Any]] = []
    flags: List[str] = []
    replicated_bytes = 0
    sharded_bytes = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        path_str = jax.tree_util.keystr(path)
        shape = tuple(getattr(leaf, "shape", ()) or ())
        nbytes = int(getattr(leaf, "nbytes", 0) or 0)
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        spec_str = str(spec) if spec is not None else None
        replicated = spec is None or not any(axis is not None for axis in tuple(spec))
        table.append(
            {
                "path": path_str,
                "shape": list(shape),
                "spec": spec_str,
                "bytes": nbytes,
                "replicated": bool(replicated),
            }
        )
        if replicated:
            replicated_bytes += nbytes
        else:
            sharded_bytes += nbytes
        if sharding is None:
            flags.append(f"{path_str}: no sharding readable (never placed?)")
        elif expected_axes is not None:
            wanted = expected_axes(path, leaf)
            if replicated and any(axis is not None for axis in wanted):
                flags.append(
                    f"{path_str}: fully replicated {list(shape)} but the rule "
                    f"table wants {wanted} (accidental replication)"
                )
        elif (
            replicated
            and len(shape) >= 2
            and model_axis_size
            and model_axis_size > 1
            and any(marker in path_str for marker in expect_sharded)
        ):
            flags.append(
                f"{path_str}: fully replicated {list(shape)} on an "
                f"n_tp={model_axis_size} mesh — expected a 'model'-sharded "
                "table (accidental replication)"
            )
    return {
        "params": table,
        "replicated_bytes": replicated_bytes,
        "sharded_bytes": sharded_bytes,
        "flags": flags,
    }
