"""One sharding-rule table: T5X-style logical-axis partitioning.

Beyond-parity (SURVEY.md §2.9; ROADMAP item 2): the reference has no sharding
story at all, and until this module the repo's own sharding was per-case
plumbing — ``make_mesh`` hardcoded a 2-axis grid, ``Trainer`` decided param
placement by string-matching ``"embedding_"`` in tree paths, and ``CEFusedTP``
carried its own ``shard_vocab`` layout. Following the T5X partitioning design
(SNIPPETS [3]), every array dimension now carries a *logical axis name* and ONE
:class:`ShardingRules` table maps logical names → mesh axes of the 3-axis
``("data", "model", "seq")`` mesh built by ``replay_tpu.nn.make_mesh``:

========  ====================================================================
logical   meaning
========  ====================================================================
batch     per-example rows of a batch (data parallelism)
length    sequence positions of an activation (sequence parallelism — the
          Ring Attention axis, arXiv 2310.01889)
vocab     item-catalog rows of an embedding table (vocab tensor parallelism —
          the CEFusedTP ``[I/n_tp, E]`` layout)
embed     the model width (residual stream)
heads     the fused attention head·head_dim projection width
mlp       the FFN hidden width
kv        per-head key/value width (reserved; fused into ``heads`` today)
position  rows of a positional table (NEVER sequence-sharded: positional rows
          are indexed by a python slice, not by activation position)
layers    the stacked-blocks axis of a ``scan_blocks`` encoder
expert    the experts a sparse layer holds (``replay_tpu.nn.moe``): mapped to no
          mesh axis by default; expert parallelism maps it once the exchange
          of tokens between chips exists
========  ====================================================================

The default table maps ``batch → "data"``, ``length → "seq"``, ``vocab →
"model"`` (when vocab TP is on) and replicates everything else — exactly the
DP×TP×SP layout the dryrun and the ``sasrec_l1024`` bench family validate.
Params are annotated by :func:`logical_axes` — a declarative path→logical-name
table for the existing flax modules (the module-annotation equivalent T5X gets
from ``param_with_axes``) — so the trainer derives EVERY NamedSharding (params,
optimizer state, batches, activation constraints) from the one table, and
``parallel.introspect.sharding_report(rules=...)`` flags any leaf whose rule
wanted a mesh axis but lowered replicated.

A table row that cannot shard (row count not divisible by the mesh axis) warns
ONCE with the offending shape/axis and replicates that dimension — the silent
fallback the old ``_params_shardings`` shipped is now loud.
"""

from __future__ import annotations

import threading
import warnings
from contextlib import contextmanager
from dataclasses import dataclass, field, replace
from typing import Any, Dict, Mapping, Optional, Tuple, Union

__all__ = [
    "LOGICAL_AXES",
    "ShardingRuleWarning",
    "ShardingRules",
    "active_scope",
    "logical_axes",
    "logical_axes_tree",
    "params_shardings",
    "shard_activation",
    "sharding_scope",
]

MeshAxis = Union[None, str, Tuple[str, ...]]

LOGICAL_AXES = (
    "batch",
    "length",
    "vocab",
    "embed",
    "heads",
    "kv",
    "mlp",
    "position",
    "layers",
    "expert",
)


class ShardingRuleWarning(UserWarning):
    """A rule wanted to shard a dimension that cannot shard (falls back to
    replication for that dimension — loudly, once per offending leaf)."""


# ---------------------------------------------------------------------------
# the rule table
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShardingRules:
    """ONE logical-name → mesh-axis table driving every placement decision.

    ``rules`` maps each logical axis name to a mesh axis name, a tuple of mesh
    axis names (a dimension sharded over several axes, e.g. flattened
    ``[B·L, E]`` rows over ``("data", "seq")``), or ``None`` (replicated).
    Unknown logical names are an error at :meth:`spec` time — a typo must not
    silently replicate.
    """

    rules: Mapping[str, MeshAxis] = field(default_factory=dict)

    @classmethod
    def default(cls, shard_vocab: bool = False) -> "ShardingRules":
        """The DP×TP×SP production table: batch rows over ``data``, sequence
        positions over ``seq``, and (with ``shard_vocab``) catalog rows over
        ``model``. Everything else replicates — the vocab table is the only
        param big enough to earn TP today (docs/distributed_and_serving.md)."""
        return cls(
            rules={
                "batch": "data",
                "length": "seq",
                "vocab": "model" if shard_vocab else None,
                "embed": None,
                "heads": None,
                "kv": None,
                "mlp": None,
                "position": None,
                "layers": None,
                "expert": None,
            }
        )

    def with_rule(self, logical: str, mesh_axis: MeshAxis) -> "ShardingRules":
        """A copy with one rule overridden (rule tables are immutable)."""
        if logical not in LOGICAL_AXES:
            msg = f"unknown logical axis {logical!r}; known: {LOGICAL_AXES}"
            raise KeyError(msg)
        merged = dict(self.rules)
        merged[logical] = mesh_axis
        return replace(self, rules=merged)

    def mesh_axis(self, logical: str) -> MeshAxis:
        """The mesh axis (or tuple / None) a logical name maps to."""
        if logical is None:
            return None
        if logical not in LOGICAL_AXES:
            msg = f"unknown logical axis {logical!r}; known: {LOGICAL_AXES}"
            raise KeyError(msg)
        return self.rules.get(logical)

    def spec(self, *logical_names: Optional[str]):
        """A ``PartitionSpec`` for an array whose dims carry these names."""
        from jax.sharding import PartitionSpec as P

        return P(*(self.mesh_axis(name) for name in logical_names))

    def validate(self, mesh) -> None:
        """Every mapped mesh axis must exist on the mesh (typos fail loudly
        at trainer construction, not as a cryptic XLA error mid-fit)."""
        mesh_axes = set(dict(mesh.shape))
        for logical, target in self.rules.items():
            if logical not in LOGICAL_AXES:
                msg = f"unknown logical axis {logical!r}; known: {LOGICAL_AXES}"
                raise KeyError(msg)
            targets = target if isinstance(target, tuple) else (target,)
            for axis in targets:
                if axis is not None and axis not in mesh_axes:
                    msg = (
                        f"rule {logical!r} -> {target!r} names mesh axis "
                        f"{axis!r}, but the mesh has axes {sorted(mesh_axes)} "
                        "(build it with replay_tpu.nn.make_mesh)"
                    )
                    raise ValueError(msg)

    def axis_size(self, mesh, logical: str) -> int:
        """Product of the mesh-axis sizes a logical name shards over (1 when
        replicated)."""
        target = self.mesh_axis(logical)
        if target is None:
            return 1
        targets = target if isinstance(target, tuple) else (target,)
        size = 1
        for axis in targets:
            size *= int(mesh.shape[axis])
        return size

    def resolved_axis(self, mesh, logical: Optional[str], dim: int) -> MeshAxis:
        """The mesh axis (or tuple) a dimension of extent ``dim`` actually
        shards over under this table: the rule's target when it spans more
        than one device AND ``dim`` divides its total size, else ``None``
        (replicate). The ONE divisibility/triviality decision shared by param
        placement, activation constraints and the accidental-replication
        report."""
        target = self.mesh_axis(logical)
        if target is None:
            return None
        size = self.axis_size(mesh, logical)
        if size <= 1 or dim % size:
            return None
        return target

    def describe(self) -> Dict[str, Any]:
        """JSON-friendly view for run records / reports."""
        return {
            name: (list(axis) if isinstance(axis, tuple) else axis)
            for name, axis in self.rules.items()
        }


# ---------------------------------------------------------------------------
# the path → logical-name annotator (the module-annotation equivalent for the
# existing flax modules: one declarative table instead of per-module metadata)
# ---------------------------------------------------------------------------
# matched against the '/'-joined param path, FIRST match wins; each entry maps
# a (component-substring, leaf-name) pattern to the logical names of the
# TRAILING dims (a scan_blocks 'layers' dim is detected by ndim and prepended)
_PARAM_RULES: Tuple[Tuple[Tuple[str, ...], str, Tuple[str, ...]], ...] = (
    # per-feature vocab tables (SequenceEmbedding's embedding_<feature> scope,
    # CategoricalEmbedding/CategoricalListEmbedding nn.Embed) — the TP tables
    (("embedding_", "table"), "embedding", ("vocab", "embed")),
    # an output head that is not the item table (HybridRec, tie_embeddings=False)
    ((), "output_table", ("vocab", "embed")),
    # positional tables: indexed by a python slice over max_sequence_length,
    # so their row axis is 'position', never the sequence-sharded 'length'
    ((), "positional_embedding", ("position", "embed")),
    # Bert4Rec's learned <MASK> vector
    ((), "mask_embedding", ("embed",)),
    # attention projections: qkv kernels [embed, heads·head_dim], out kernel
    # [heads·head_dim, embed]
    (("attention", "out"), "kernel", ("heads", "embed")),
    (("attention", "out"), "bias", ("embed",)),
    (("attention",), "kernel", ("embed", "heads")),
    (("attention",), "bias", ("heads",)),
    # differential-attention lambda vectors live in per-head space
    (("attention",), "lambda_q1", ("heads",)),
    (("attention",), "lambda_k1", ("heads",)),
    (("attention",), "lambda_q2", ("heads",)),
    (("attention",), "lambda_k2", ("heads",)),
    # sparse experts (nn.moe): stacked SwiGLU kernels [expert, embed, mlp] /
    # [expert, mlp, embed]; the router [embed, all experts] and the selection
    # bias [all experts] span EVERY expert, held here or not: no "expert" name
    (("moe",), "gate", ("expert", "embed", "mlp")),
    (("moe",), "value", ("expert", "embed", "mlp")),
    (("moe",), "out", ("expert", "mlp", "embed")),
    (("moe", "router"), "kernel", ("embed", None)),
    (("moe",), "expert_bias", (None,)),
    # gated short convolution (nn.conv): in_proj [embed, 3·embed] holds three
    # gates side by side ("mlp": the widened axis), out_proj [embed, embed]
    (("conv", "in_proj"), "kernel", ("embed", "mlp")),
    (("conv", "out_proj"), "kernel", ("mlp", "embed")),
    (("conv",), "kernel", (None, "embed")),
    # FFN: inner/gate/value kernels [embed, mlp], outer/out [mlp, embed]
    (("ffn", "outer"), "kernel", ("mlp", "embed")),
    (("ffn", "outer"), "bias", ("embed",)),
    (("ffn", "out"), "kernel", ("mlp", "embed")),
    (("ffn",), "kernel", ("embed", "mlp")),
    (("ffn",), "bias", ("mlp",)),
    # per-head norms on q and k (GroupedQueryAttention): one head's width
    (("q_norm",), "scale", ("kv",)),
    (("k_norm",), "scale", ("kv",)),
    # norms and generic projections live in the residual stream. A proj
    # kernel's INPUT dim gets no logical name: it is a stacked-feature /
    # tensor_dim axis (NumericalEmbedding, ConcatAggregator) — and naming it
    # "embed" too would build a duplicate-axis PartitionSpec the moment an
    # "embed" rule maps to a mesh axis
    ((), "scale", ("embed",)),
    (("norm",), "bias", ("embed",)),
    (("proj",), "kernel", (None, "embed")),
    (("proj",), "bias", ("embed",)),
)


def _path_components(path: Any) -> Tuple[str, ...]:
    """Normalize a jax key path (or a pre-joined string) to components."""
    if isinstance(path, str):
        return tuple(part for part in path.replace("'", "").replace("[", "/").replace("]", "").split("/") if part)
    import jax

    return tuple(
        part
        for part in jax.tree_util.keystr(path).replace("'", "").replace("[", "/").replace("]", "").split("/")
        if part
    )


def logical_axes(path: Any, leaf: Any) -> Tuple[Optional[str], ...]:
    """Logical axis names for one param leaf, from the declarative table.

    Unmatched leaves get all-``None`` names (replicated under any rules) —
    annotation coverage is reported, never guessed from shapes. A leaf whose
    ndim exceeds its pattern by one (a ``scan_blocks`` stacked encoder) gets
    ``"layers"`` prepended.
    """
    ndim = len(getattr(leaf, "shape", ()) or ())
    components = _path_components(path)
    leaf_name = components[-1] if components else ""
    scope = components[:-1]
    for markers, name, axes in _PARAM_RULES:
        if name != leaf_name:
            continue
        if not all(any(marker in part for part in scope) for marker in markers):
            continue
        if ndim == len(axes):
            return axes
        if ndim == len(axes) + 1:  # nn.scan-stacked blocks: [layers, ...]
            return ("layers",) + axes
        continue  # shape disagrees with the pattern: keep looking
    return (None,) * ndim


def logical_axes_tree(params: Any) -> Any:
    """The logical-axis annotation for every leaf of a param pytree."""
    import jax

    return jax.tree_util.tree_map_with_path(logical_axes, params)


# one warning per offending (path, axis) per process: the non-divisible
# fallback must be loud, not spammy — tests reset via _reset_rule_warnings()
_WARNED: set = set()
_WARNED_LOCK = threading.Lock()


def _reset_rule_warnings() -> None:
    with _WARNED_LOCK:
        _WARNED.clear()


def _resolved_dim_axis(
    mesh, rules: ShardingRules, logical: Optional[str], dim: int, path_str: str
) -> MeshAxis:
    """:meth:`ShardingRules.resolved_axis`, plus the one-time
    ShardingRuleWarning when the fallback was a DIVISIBILITY failure (a rule
    that wanted to shard but could not) rather than a trivial axis."""
    resolved = rules.resolved_axis(mesh, logical, dim)
    if resolved is not None:
        return resolved
    target = rules.mesh_axis(logical)
    if target is None:
        return None
    size = rules.axis_size(mesh, logical)
    if size > 1 and dim % size:
        key = (path_str, logical, target, dim)
        with _WARNED_LOCK:
            seen = key in _WARNED
            _WARNED.add(key)
        if not seen:
            targets = target if isinstance(target, tuple) else (target,)
            warnings.warn(
                f"sharding rule {logical!r} -> {target!r}: {path_str} has "
                f"{dim} rows, not divisible by the {size}-way "
                f"{'×'.join(targets)} mesh axis — REPLICATING this dimension "
                "instead (pad the table or change the rule)",
                ShardingRuleWarning,
                stacklevel=3,
            )
    return None


def params_shardings(mesh, params: Any, rules: ShardingRules) -> Any:
    """NamedShardings for a param pytree, derived from the rule table.

    Replaces the old path-string heuristic: every leaf is annotated by
    :func:`logical_axes` and placed by the ONE table. Non-divisible dims warn
    once (:class:`ShardingRuleWarning`) and replicate.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(path, leaf) -> NamedSharding:
        names = logical_axes(path, leaf)
        path_str = jax.tree_util.keystr(path)
        shape = tuple(getattr(leaf, "shape", ()) or ())
        resolved = tuple(
            _resolved_dim_axis(mesh, rules, name, dim, path_str)
            for name, dim in zip(names, shape)
        )
        return NamedSharding(mesh, P(*resolved))

    return jax.tree_util.tree_map_with_path(place, params)


# ---------------------------------------------------------------------------
# activation scope: the trainer installs (rules, mesh) while tracing its
# programs; model bodies call shard_activation(...) and the ring-attention
# route reads the mesh + seq axis from here (flax modules stay mesh-free)
# ---------------------------------------------------------------------------
_SCOPE = threading.local()


@contextmanager
def sharding_scope(rules: ShardingRules, mesh):
    """Install the (rules, mesh) pair for the duration of a program trace."""
    previous = getattr(_SCOPE, "value", None)
    _SCOPE.value = (rules, mesh)
    try:
        yield
    finally:
        _SCOPE.value = previous


def active_scope() -> Optional[Tuple[ShardingRules, Any]]:
    """The installed (rules, mesh), or None outside any trainer program."""
    return getattr(_SCOPE, "value", None)


def shard_activation(x, *logical_names: Optional[str]):
    """``with_sharding_constraint`` from the rule table; identity when no
    scope is installed (direct ``model.apply`` outside a trainer) or when
    every resolved axis is trivial. Non-divisible dims silently relax to
    replicated — activations are shaped by the batcher, and a short final
    batch must not warn per step.
    """
    scope = active_scope()
    if scope is None:
        return x
    rules, mesh = scope
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if len(logical_names) != x.ndim:
        msg = (
            f"shard_activation: {len(logical_names)} logical names for a "
            f"{x.ndim}-d activation {tuple(x.shape)}"
        )
        raise ValueError(msg)
    resolved = [
        rules.resolved_axis(mesh, name, dim)
        for name, dim in zip(logical_names, x.shape)
    ]
    if not any(axis is not None for axis in resolved):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved))
    )
