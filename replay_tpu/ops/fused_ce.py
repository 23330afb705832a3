"""Fused full-softmax log-sum-exp over the item catalog (pallas, TPU).

Beyond-parity: the reference computes full-catalog CE by materializing
``[B, L, num_items]`` logits (replay/nn/loss/ce.py:10 via a torch linear head).
At recsys scales that tensor dominates the train step's HBM traffic — for the
notebook-09 config it is ~190 MB per step against a 474 KB item table; at
ML-20M scale it is gigabytes. This kernel computes
``lse_n = logsumexp_i(h_n · w_i)`` tile-by-tile in VMEM with a flash-style
online max/sum over catalog tiles, so neither axis is ever resident in full:
HBM sees only the hidden states, the table, and one scalar per row.

Training works through ``jax.custom_vjp`` with two sweeps over the catalog:
the gradients need ``softmax @ W`` for ``dh`` and ``softmaxᵀ @ (g · h)`` for
``dW``, and each sweep forms every ``[row_tile, item_tile]`` logits block once —

- the differentiated forward, gridded (rows, items), keeps beside the running
  max/sum a ``[row_tile, E]`` accumulator of ``exp(logits − max) @ W_j`` (as
  flash attention's forward keeps ``P @ V``), rescaled by the same
  ``exp(m_old − m_new)`` as the sum, and writes
  ``PW = acc / sum`` beside ``lse``; the backward's ``dh`` is then ``g · PW``,
  one elementwise op on ``[N, E]``;
- ``dW = softmaxᵀ @ (g · h)`` gridded (items, rows) so the dW block accumulates
  over the consecutive inner row axis; the row weight ``g`` scales the
  ``[row_tile, E]`` rows, not the logits block.

An undifferentiated call runs the same forward and drops ``PW``. (TPU pallas
grids execute sequentially, which is what makes same-block accumulation across
the inner axis well-defined.)

Two provisions for callers beyond the single-device case:

- ``num_valid`` may be a TRACED int32 scalar smaller than ``table.shape[0]``:
  the vocab-sharded wrapper (replay_tpu.parallel.sharded_ce) gives each shard
  a fixed-shape ``[I/n_tp, E]`` slice but a per-shard valid count derived from
  ``lax.axis_index`` at run time. Padding columns are masked with a large
  FINITE negative (``_MASK``) rather than −inf, so a shard whose slice is
  entirely padding still produces a well-defined (≈ −1e30) lse instead of
  NaN-ing the online max/sum; ``exp(_MASK − lse)`` underflows to exactly 0.0
  for any realistic lse, so results are bit-identical to the −inf mask.
- a VMEM-budget guard: the ``[row_tile, item_tile]`` working set is estimated
  up front and ``item_tile`` auto-shrinks (lane-aligned halving) instead of
  failing at Mosaic compile time (the round-3 16 MB bwd-kernel incident); one
  warning is logged per shrunk configuration.

``interpret`` is the caller's decision and defaults to False (compile). The
loss heads resolve it with ``ops.flash_attention.pallas_interpret``: compiled
on ``"tpu"``, interpreted (and logged at WARNING) on ``"cpu"``, an error on any
other backend.
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("replay_tpu")

_LANE = 128  # TPU lane width: catalog axis is padded to a multiple of this
_DEFAULT_ROWS = 512  # rows per program where the caller names none (row_tile)
_DEFAULT_ITEM_TILE = 4096  # catalog tiles: [row_tile, item_tile] logits blocks
# finite catalog-padding mask: exp(_MASK - lse) == 0.0 exactly for any
# realistic lse (f32 exp underflows below ~-104), so real rows are
# bit-identical to a -inf mask, while a FULLY-masked shard (the TP wrapper's
# empty tail shard) still yields a finite ~-1e30 lse instead of NaN
_MASK = -1e30
# per-core VMEM budget for one kernel invocation: 16 MiB of VMEM minus
# headroom for Mosaic's own buffers — exceeding it fails at compile time.
# Calibrated against the round-3 evidence: [256, 4096] at E=64 compiled and
# ran (≈8 MB by the model below), the E=300 bwd kernel at the same tile
# (≈24 MB) died at the 16 MB limit.
_VMEM_BUDGET_BYTES = 14 * 1024 * 1024
_shrink_warned: Set[Tuple[int, int, int, int]] = set()


def _pad_to(value: int, multiple: int) -> int:
    return ((value + multiple - 1) // multiple) * multiple


def _working_set_bytes(tile: int, item_tile: int, embed: int) -> int:
    """Estimated peak VMEM of one grid step of the larger of the two kernels a
    gradient runs, all f32: the pipeline's blocks, double-buffered by Mosaic
    (forward: h, W in, lse and PW out; dW: h, W, g, lse in, dW out), what the
    step keeps beside them (the forward's [tile, E] accumulator and max/sum
    scratch, dW's g-weighted rows) and the [tile, item_tile] logits block (its
    exp reuses the buffer)."""
    forward = 2 * (2 * tile * embed + item_tile * embed + tile) + tile * embed + 2 * tile
    dw = 2 * (tile * embed + 2 * item_tile * embed + 2 * tile) + tile * embed
    return 4 * (max(forward, dw) + tile * item_tile)


def _resolve_item_tile(num_items: int, item_tile, tile: int, embed: int) -> int:
    """Lane-align the catalog tile and shrink it to the VMEM budget.

    The guard runs BEFORE the kernel is built: the round-3 incident was a
    [256, 4096] bwd block at d=300 blowing the 16 MB Mosaic limit at compile
    time — opaque to the caller. Halving keeps lane alignment; one warning per
    shrunk configuration records the decision in the run log.
    """
    requested = _DEFAULT_ITEM_TILE if item_tile is None else item_tile
    resolved = min(_pad_to(requested, _LANE), _pad_to(max(num_items, 1), _LANE))
    shrunk = resolved
    while shrunk > _LANE and _working_set_bytes(tile, shrunk, embed) > _VMEM_BUDGET_BYTES:
        shrunk = _pad_to(shrunk // 2, _LANE)
    if shrunk != resolved:
        key = (tile, resolved, shrunk, embed)
        if key not in _shrink_warned:
            _shrink_warned.add(key)
            logger.warning(
                "fused_ce: item_tile %d would need ~%.1f MB of VMEM at "
                "row_tile=%d, embed=%d (budget %.0f MB): shrunk to %d. Pass "
                "item_tile= explicitly to silence.",
                resolved,
                _working_set_bytes(tile, resolved, embed) / 2**20,
                tile,
                embed,
                _VMEM_BUDGET_BYTES / 2**20,
                shrunk,
            )
    return shrunk


def row_tile(rows: int, tile: Optional[int]) -> int:
    """Rows a program for a caller that names none: 512 (no more than the rows there
    are), which read 2.5-5% under 256 at every width tried (TPU v5e, 25,600 x 27,278,
    the head alone, forward + backward, my chip run, PR 34: d 64 6.63 against 6.98
    ms, d 128 6.58 against 6.75, d 192 11.93 against 12.49; 128 rows read 7.76, 7.49,
    13.98). The catalog's tile is :func:`_resolve_item_tile`'s."""
    return min(_DEFAULT_ROWS, _pad_to(max(rows, 1), 8)) if tile is None else tile


def _masked_logits(num_valid_ref, h, w, item_block):
    """One [T, item_tile] logits block of f32 rows ``h`` and catalog tile ``w``
    (the ``item_block``-th), with catalog padding masked to _MASK.

    The mask is a [1, item_tile] row vector (a few KB) rather than a full-size
    iota compare, which would cost as much VMEM as the logits block itself.
    """
    item_tile = w.shape[0]
    logits = jnp.dot(h, w.T, preferred_element_type=jnp.float32)
    col = item_block * item_tile + jax.lax.broadcasted_iota(jnp.int32, (1, item_tile), 1)
    return logits + jnp.where(col < num_valid_ref[0], 0.0, _MASK).astype(jnp.float32)


def _lse_kernel(num_valid_ref, h_ref, w_ref, lse_ref, pw_ref, m_ref, s_ref, acc_ref):
    """Online logsumexp: running max/sum scratch across the inner item grid.

    The exp block that feeds the sum also feeds ``acc += exp(logits − max) @ W_j``,
    rescaled with the sum when the max moves; ``pw = acc / s`` is written beside
    ``lse``.
    """
    from jax.experimental import pallas as pl

    j, num_j = pl.program_id(1), pl.num_programs(1)

    @pl.when(j == 0)
    def _reset():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)
    logits = _masked_logits(num_valid_ref, h_ref[...].astype(jnp.float32), w, j)
    tile_max = jnp.max(logits, axis=-1, keepdims=True)  # finite even for a
    new_max = jnp.maximum(m_ref[...], tile_max)  # fully-masked tile (_MASK)
    rescale = jnp.exp(m_ref[...] - new_max)
    probs = jnp.exp(logits - new_max)
    s_ref[...] = s_ref[...] * rescale + jnp.sum(probs, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * rescale + jnp.dot(probs, w, preferred_element_type=jnp.float32)
    m_ref[...] = new_max

    @pl.when(j == num_j - 1)
    def _finalize():
        lse_ref[...] = m_ref[...] + jnp.log(s_ref[...])
        pw_ref[...] = acc_ref[...] / s_ref[...]


def _dw_kernel(num_valid_ref, h_ref, w_ref, g_ref, lse_ref, dw_ref):
    """dW[j] = sum_i softmax_blockᵀ @ (g_i · h_i) — inner row axis accumulates.

    Grid is (items, rows): program_id(0) is the item tile, program_id(1) the
    row tile. The row weight scales the [T, E] rows, one pass fewer over the
    [T, item_tile] block.
    """
    from jax.experimental import pallas as pl

    h = h_ref[...].astype(jnp.float32)
    logits = _masked_logits(num_valid_ref, h, w_ref[...].astype(jnp.float32), pl.program_id(0))
    softmax = jnp.exp(logits - lse_ref[...])
    contrib = jnp.dot(softmax.T, h * g_ref[...], preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dw_ref[...] = contrib

    @pl.when(pl.program_id(1) != 0)
    def _accumulate():
        dw_ref[...] += contrib


def _prepare(hidden: jnp.ndarray, table: jnp.ndarray, tile: int, item_tile: int):
    n, embed = hidden.shape
    num_rows = table.shape[0]
    n_pad = _pad_to(max(n, 1), tile)
    items_pad = _pad_to(max(num_rows, 1), item_tile)
    hidden = jnp.pad(hidden, ((0, n_pad - n), (0, 0)))
    table = jnp.pad(table, ((0, items_pad - num_rows), (0, 0)))
    return hidden, table, n, n_pad, items_pad, embed, num_rows


def fused_lse(
    hidden: jnp.ndarray,
    table: jnp.ndarray,
    tile: int = 256,
    item_tile: Optional[int] = None,
    interpret: bool = False,
    num_valid=None,
):
    """``logsumexp(hidden @ table.T, axis=-1)`` without materializing the logits.

    :param hidden: ``[N, E]`` row vectors (any float dtype; f32 accumulation).
    :param table: ``[num_items, E]`` item embeddings.
    :param tile: rows per program.
    :param item_tile: catalog columns per program (defaults to 4096, shrunk
        lane-aligned to the VMEM budget; the catalog is swept with an online
        max/sum so any size compiles).
    :param num_valid: valid leading rows of ``table`` — everything past it is
        masked out of the softmax. May be a TRACED int32 scalar (the
        vocab-sharded wrapper's per-shard count); default: all rows.
    :return: ``[N]`` float32 log-sum-exp values.
    """
    if num_valid is None:
        num_valid = table.shape[0]
    return _fused_lse(
        hidden, table, jnp.asarray(num_valid, jnp.int32), tile, item_tile, interpret
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_lse(hidden, table, num_valid, tile, item_tile, interpret):
    return _run_forward(hidden, table, num_valid, tile, item_tile, interpret)[0]


def _run_forward(hidden, table, num_valid, tile, item_tile, interpret):
    """``lse`` ``[N]`` and ``PW = softmax @ W`` ``[N, E]`` float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    item_tile = _resolve_item_tile(table.shape[0], item_tile, tile, hidden.shape[1])
    hidden_p, table_p, n, n_pad, items_pad, embed, _ = _prepare(
        hidden, table, tile, item_tile
    )
    rows = lambda i, j, *_: (i, 0)  # noqa: E731
    lse, pw = pl.pallas_call(
        _lse_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_pad // tile, items_pad // item_tile),
            in_specs=[
                pl.BlockSpec((tile, embed), rows),
                pl.BlockSpec((item_tile, embed), lambda i, j, *_: (j, 0)),
            ],
            out_specs=[pl.BlockSpec((tile, 1), rows), pl.BlockSpec((tile, embed), rows)],
            scratch_shapes=[
                pltpu.VMEM((tile, 1), jnp.float32),
                pltpu.VMEM((tile, 1), jnp.float32),
                pltpu.VMEM((tile, embed), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, embed), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.reshape(num_valid, (1,)), hidden_p, table_p)
    return lse[:n, 0], pw[:n]


def _fused_lse_fwd(hidden, table, num_valid, tile, item_tile, interpret):
    lse, pw = _run_forward(hidden, table, num_valid, tile, item_tile, interpret)
    return lse, (hidden, table, num_valid, lse, pw)


def _fused_lse_bwd(tile, item_tile, interpret, residuals, grad_lse):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hidden, table, num_valid, lse, pw = residuals
    g = grad_lse.astype(jnp.float32)
    item_tile = _resolve_item_tile(table.shape[0], item_tile, tile, hidden.shape[1])
    hidden_p, table_p, n, n_pad, items_pad, embed, num_rows = _prepare(
        hidden, table, tile, item_tile
    )
    dw = pl.pallas_call(
        _dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(items_pad // item_tile, n_pad // tile),
            in_specs=[
                pl.BlockSpec((tile, embed), lambda j, i, *_: (i, 0)),
                pl.BlockSpec((item_tile, embed), lambda j, i, *_: (j, 0)),
                pl.BlockSpec((tile, 1), lambda j, i, *_: (i, 0)),
                pl.BlockSpec((tile, 1), lambda j, i, *_: (i, 0)),
            ],
            out_specs=pl.BlockSpec((item_tile, embed), lambda j, i, *_: (j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((items_pad, embed), jnp.float32),
        interpret=interpret,
    )(
        jnp.reshape(num_valid, (1,)),
        hidden_p,
        table_p,
        jnp.pad(g, (0, n_pad - n)).reshape(n_pad, 1),
        jnp.pad(lse, (0, n_pad - n)).reshape(n_pad, 1),
    )

    return (
        (g[:, None] * pw).astype(hidden.dtype),
        dw[:num_rows].astype(table.dtype),
        # num_valid is an int scalar: its cotangent is the symbolic float0 zero
        np.zeros(np.shape(num_valid), jax.dtypes.float0),
    )


_fused_lse.defvjp(_fused_lse_fwd, _fused_lse_bwd)
