"""Vocab-sharded (tensor-parallel) fused catalog logsumexp.

Beyond-parity (SURVEY.md §2.9 TP row): the reference's CE head materializes
``[B, L, num_items]`` logits on ONE device (replay/nn/loss/ce.py:10) and has
no exact full-softmax story past that device's memory. The single-device
kernel (``replay_tpu.ops.fused_ce``) removes the ``[N, I]`` logits tensor
from HBM; this wrapper removes the ``[I, E]`` ITEM TABLE from the single
device. The table lives ``[I/n_tp, E]`` per chip over the mesh's
tensor-parallel axis (the same ``("model", None)`` layout
``Trainer(shard_vocab=True)`` places the embedding params in), each shard runs
the tile-wise online max/sum locally, and the shards combine with the two-pass
reduction

    m_g = pmax(lse_local)            s_g = psum(exp(lse_local − m_g))
    lse_g = m_g + log(s_g)

expressed as ``logsumexp(all_gather(lse_local))`` inside ``shard_map`` — the
all_gather moves ``n_tp`` scalars per row (nothing next to the table), and
unlike a raw ``pmax`` it is differentiable, so autodiff produces exactly the
backward the math wants: the cotangent reaching each shard is its softmax
share ``exp(lse_local − lse_g)``, ``dh`` is psummed across shards (the
transpose of the replicated-in ``hidden``), and ``dW`` stays shard-local (the
transpose of the sharded-in table).

Catalogs not divisible by ``n_tp`` are zero-padded to the shard grid and the
padding is masked INSIDE the kernel via its traced ``num_valid`` scalar
(each shard computes its own valid count from ``lax.axis_index``); a shard
that is entirely padding yields a finite ≈−1e30 lse whose contribution
underflows to exactly 0 in the combine (see ``ops/fused_ce._MASK``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from replay_tpu.ops.fused_ce import fused_lse


def sharded_fused_lse(
    hidden: jnp.ndarray,
    table: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "model",
    data_axis: Optional[str] = "data",
    tile: int = 256,
    item_tile: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``logsumexp(hidden @ table.T, axis=-1)`` with the catalog sharded over
    ``mesh``'s ``axis_name`` axis.

    :param hidden: ``[N, E]`` row vectors — sharded over ``data_axis`` when
        given (``N`` must divide by that axis size), replicated over
        ``axis_name``.
    :param table: ``[num_items, E]`` item embeddings (logically global; under
        ``shard_vocab`` the rows are already placed ``P(axis_name, None)`` and
        shard_map keeps them in place).
    :param data_axis: mesh axis the rows are data-parallel over — a single
        name, or a TUPLE of names for rows flattened from several sharded
        dims (the DP×SP fit's ``[B·L, E]`` hidden states stay sharded over
        ``("data", "seq")``; the trainer's rule table picks this). ``None``
        replicates the rows on every shard group (single-axis TP meshes).
    :return: ``[N]`` float32 log-sum-exp values, numerically equal to the
        replicated :func:`~replay_tpu.ops.fused_ce.fused_lse` up to the
        shard-combine's f32 reassociation.
    """
    if axis_name not in mesh.shape:
        msg = f"mesh {dict(mesh.shape)} has no {axis_name!r} axis to shard the catalog over"
        raise ValueError(msg)
    n_tp = mesh.shape[axis_name]
    num_items, _ = table.shape
    if data_axis is not None:
        row_axes = data_axis if isinstance(data_axis, tuple) else (data_axis,)
        n_data = 1
        for axis in row_axes:
            size = mesh.shape.get(axis)
            if size is None:
                msg = f"mesh {dict(mesh.shape)} has no {axis!r} axis for the rows"
                raise ValueError(msg)
            n_data *= size
        if hidden.shape[0] % n_data:
            msg = (
                f"sharded_fused_lse: {hidden.shape[0]} rows do not divide over "
                f"the {n_data}-way {data_axis!r} axes"
            )
            raise ValueError(msg)
    pad = -num_items % n_tp
    if pad:
        table = jnp.pad(table, ((0, pad), (0, 0)))
    shard_rows = (num_items + pad) // n_tp

    def body(h_block, w_shard):
        start = jax.lax.axis_index(axis_name) * shard_rows
        num_valid = jnp.clip(num_items - start, 0, shard_rows)
        lse_local = fused_lse(
            h_block, w_shard, tile, item_tile, interpret, num_valid=num_valid
        )
        # two-pass psum-style combine over the catalog shards: n_tp scalars
        # per row; differentiable (its VJP is each shard's softmax share)
        return jax.nn.logsumexp(jax.lax.all_gather(lse_local, axis_name), axis=0)

    row_spec = P(data_axis, None) if data_axis is not None else P(None, None)
    out_spec = P(data_axis) if data_axis is not None else P()
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(row_spec, P(axis_name, None)),
        out_specs=out_spec,
        # pallas_call has no replication rule; correctness is covered by the
        # parity tests on the virtual 8-device mesh (tests/ops)
        check_vma=False,
    )(hidden, table)
