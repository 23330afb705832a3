"""Device-resident IVF: sub-linear MIPS retrieval for catalogs the sweep can't.

Capability parity with replay/models/extensions/ann/ (SURVEY §2.8: hnswlib /
nmslib C++ approximate indexes behind ANNMixin, ref ann_mixin.py:26,
README.md:199-202): the reference goes sub-linear with graph indexes because a
CPU exact sweep is too slow; here the exact sweep (``models/ann.py``) IS fast —
until the catalog grows past ~10M items and the O(I) sweep, not the table
bytes, becomes the serving wall (ROADMAP item 6). This module is the TPU-shaped
answer: a clustered inverted-file (IVF) index whose every stage is a fixed-shape
compiled program.

Build (deterministic, seeded):
  * k-means over a host-sampled subset of the item table — ``jax.lax.scan``
    chunks, a FIXED iteration count, L2 assignment via ``argmax(x·c − |c|²/2)``,
    empty cells keep their previous centroid. Same seed → bitwise-same index.
  * full-table assignment (top-2 cells per row, chunked) + one host spill pass
    that moves rows beyond ``cell_cap_factor × mean`` to their runner-up cell,
    bounding the widest cell so the fixed-width gather wastes less.
  * cells padded to a static BUCKET LADDER of widths (multiples of 8, ~1.25×
    steps — the same discipline as ``SequenceBatcher`` bucketing) and laid out
    in one flat ``[S, E]`` cell-major storage with per-cell ``starts``/
    ``lengths`` and ``storage_ids`` (−1 on padding) plus a CMAX tail guard, so
    every cell gather is a ``dynamic_slice`` of the SAME static shape.

Search (one executable per (Q, k), zero retraces):
  centroid scan ``q @ centroidsᵀ`` → top-``nprobe`` cells → ``lax.scan`` over
  the probes gathering each padded cell (CMAX rows) and scoring it → collected
  ``[Q, nprobe·CMAX]`` scores → ONE final ``lax.top_k``. Probing ranks cells by
  inner product (MIPS-consistent); padded rows are masked to −inf by the true
  cell length before the cut. Scores are the approximate SELECTION signal only:
  the serving pipeline feeds every candidate through ``MIPSIndex.exact_rescore``
  so approximation picks candidates but never ranks them.

Precision rungs (the ladder's serving rungs, docs/performance.md):
  * ``f32``   — cells store raw rows; per-candidate scores are exact dots.
  * ``int8``  — cells store per-row symmetrically quantized rows + f32 scales
    (``replay_tpu.serve.quant``); the probe gather reads ¼ the bytes.
  * ``int8+pq`` — stacks product-quantized residuals on the int8 rung: cells
    store ``pq_subspaces`` uint8 codes per row (8× below int8 at E=64) against
    per-subspace 256-entry f32 codebooks trained on residuals ``x − c(x)``;
    scoring is ``q·c(x) + Σ_m LUT_m[code_m]`` with the LUT built once per query
    batch. The f32 master stays host-side for ``exact_rescore`` — the rung's
    honesty contract is unchanged.

Sharded (the PR-15 ``[I/n, E]`` model-axis layout): centroids replicate, CELLS
partition — ``nlist % n_shards == 0`` contiguous cells per shard, per-shard
storage padded to the widest shard, ``starts`` local to the shard's flat
storage. Each shard probes the top-``nprobe/n`` of its OWN cells (the probed
set can differ from the unsharded index — documented in docs/serving.md) and
contributes ``local_k`` candidates; only candidates cross the mesh, never cell
rows, and ``collective_inventory`` hard-asserts it on the compiled HLO.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import numpy as np

_ASSIGN_CHUNK = 8192


def default_nlist(num_items: int, n_shards: int = 1) -> int:
    """Power-of-two cell count ≈ 2·√I, clamped to [n_shards·8, I // 4] —
    keeps mean cell width ≈ √I/2 so ``nprobe`` cells stay a vanishing
    fraction of the catalog, and stays divisible by any power-of-two mesh."""
    target = max(8 * n_shards, int(2 * np.sqrt(max(num_items, 1))))
    nlist = 1 << int(np.ceil(np.log2(target)))
    upper = max(8 * n_shards, num_items // 4)
    while nlist > upper and nlist > 8 * n_shards:
        nlist //= 2
    return int(nlist)


def ladder_width(n: int) -> int:
    """Smallest bucket-ladder width ≥ n: multiples of 8 growing ~1.25× —
    the static set of cell widths (same discipline as sequence bucketing)."""
    if n <= 0:
        return 0
    w = 8
    while w < n:
        w = max(w + 8, int(w * 1.25) // 8 * 8)
    return w


@dataclasses.dataclass(frozen=True)
class IVFConfig:
    nlist: int
    nprobe: int = 32
    build_iters: int = 10
    build_sample: int = 131072
    pq_subspaces: int = 8
    cell_cap_factor: float = 1.6
    seed: int = 0


@dataclasses.dataclass
class IVFState:
    """Device-resident index state + the build stats the report renders."""

    config: IVFConfig
    precision: str
    num_items: int
    dim: int
    centroids: object  # [nlist, E] f32, replicated
    storage: Optional[object]  # [S, E] f32|int8 cell-major rows (None for pq)
    row_scales: Optional[object]  # [S] f32 (int8 rung only)
    codes: Optional[object]  # [S, M] uint8 (pq rung only)
    codebooks: Optional[object]  # [M, 256, E/M] f32 (pq rung only)
    storage_ids: object  # [S] int32 global item ids, -1 on padding
    starts: object  # [nlist] int32 (shard-local offsets when sharded)
    lengths: object  # [nlist] int32 true cell sizes
    cmax: int  # widest ladder width = the static gather shape
    storage_rows: int  # S (per shard when sharded)
    padded_fraction: float
    mesh: object = None
    axis_name: str = "model"
    n_shards: int = 1


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------


def _kmeans_centroids(sample: np.ndarray, nlist: int, iters: int, seed: int):
    """Fixed-iteration chunked k-means on device; returns [nlist, E] f32."""
    import jax
    import jax.numpy as jnp

    rows = sample.shape[0]
    chunk = min(_ASSIGN_CHUNK, rows)
    rows_eff = (rows // chunk) * chunk
    rng = np.random.default_rng(seed)
    init = sample[rng.choice(rows, nlist, replace=False)]
    xs = jnp.asarray(sample[:rows_eff])

    @partial(jax.jit, static_argnums=(2,))
    def kmeans_iter(x, cent, nchunks):
        halfsq = 0.5 * jnp.sum(cent * cent, axis=1)

        def step(carry, block):
            sums, counts = carry
            a = jnp.argmax(block @ cent.T - halfsq[None, :], axis=1)
            return (sums.at[a].add(block), counts.at[a].add(1.0)), None

        blocks = x.reshape(nchunks, -1, x.shape[1])
        (sums, counts), _ = jax.lax.scan(
            step, (jnp.zeros_like(cent), jnp.zeros(cent.shape[0])), blocks
        )
        # empty cells keep their previous centroid (deterministic, no resample)
        return jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], cent)

    cent = jnp.asarray(init)
    for _ in range(iters):
        cent = kmeans_iter(xs, cent, rows_eff // chunk)
    return cent


def _assign_top2(table: np.ndarray, centroids) -> np.ndarray:
    """[I, 2] best + runner-up cell per row (L2), chunked on device."""
    import jax
    import jax.numpy as jnp

    rows, dim = table.shape
    chunk = min(_ASSIGN_CHUNK, rows)
    pad = (-rows) % chunk
    if pad:
        table = np.concatenate([table, np.zeros((pad, dim), table.dtype)])

    @partial(jax.jit, static_argnums=(2,))
    def assign(x, cent, nchunks):
        halfsq = 0.5 * jnp.sum(cent * cent, axis=1)

        def one(block):
            _, top2 = jax.lax.top_k(block @ cent.T - halfsq[None, :], 2)
            return top2

        return jax.lax.map(one, x.reshape(nchunks, -1, x.shape[1])).reshape(-1, 2)

    out = assign(jnp.asarray(table), centroids, table.shape[0] // chunk)
    return np.asarray(out)[:rows]


def _spill_overflow(top2: np.ndarray, nlist: int, cap: int) -> np.ndarray:
    """Deterministic spill passes: rows beyond ``cap`` in their best cell
    (original row order) move to their runner-up, bounding the widest cell.
    Later passes re-trim cells the first pass overflowed — only rows still
    sitting in their top-1 cell can move (a spilled row has no third choice),
    so the loop provably terminates."""
    cells = top2[:, 0].copy()
    for _ in range(4):
        counts = np.bincount(cells, minlength=nlist)
        over = np.where(counts > cap)[0]
        if not len(over):
            break
        moved = 0
        for c in over:
            rows = np.where(cells == c)[0]
            movable = rows[cells[rows] == top2[rows, 0]]
            excess = counts[c] - cap
            spill = movable[len(movable) - min(excess, len(movable)):]
            cells[spill] = top2[spill, 1]
            moved += len(spill)
        if moved == 0:
            break
    return cells


def _train_pq(residuals: np.ndarray, subspaces: int, iters: int, seed: int):
    """Per-subspace 256-entry codebooks over residual rows → [M, 256, E/M]."""
    import jax
    import jax.numpy as jnp

    rows, dim = residuals.shape
    if rows < 256:
        msg = f"int8+pq needs >= 256 training rows, got {rows}"
        raise ValueError(msg)
    sub = dim // subspaces
    parts = residuals.reshape(rows, subspaces, sub).transpose(1, 0, 2)  # [M, T, sub]
    rng = np.random.default_rng(seed + 1)
    init = parts[:, rng.choice(rows, 256, replace=False), :]  # [M, 256, sub]

    @jax.jit
    def kmeans_iter(x, cent):
        def one(xs, cs):
            halfsq = 0.5 * jnp.sum(cs * cs, axis=1)
            a = jnp.argmax(xs @ cs.T - halfsq[None, :], axis=1)
            sums = jnp.zeros_like(cs).at[a].add(xs)
            counts = jnp.zeros(cs.shape[0]).at[a].add(1.0)
            return jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], cs)

        return jax.vmap(one)(x, cent)

    xs = jnp.asarray(parts)
    cent = jnp.asarray(init)
    for _ in range(iters):
        cent = kmeans_iter(xs, cent)
    return cent  # [M, 256, sub]


def _encode_pq(residuals: np.ndarray, codebooks) -> np.ndarray:
    """uint8 codes [I, M]: nearest codebook entry per subspace, chunked."""
    import jax
    import jax.numpy as jnp

    rows, dim = residuals.shape
    subspaces = int(codebooks.shape[0])
    sub = dim // subspaces
    chunk = min(_ASSIGN_CHUNK, rows)
    pad = (-rows) % chunk
    if pad:
        residuals = np.concatenate([residuals, np.zeros((pad, dim), residuals.dtype)])

    @partial(jax.jit, static_argnums=(2,))
    def encode(x, cent, nchunks):
        halfsq = 0.5 * jnp.sum(cent * cent, axis=2)  # [M, 256]

        def one(block):
            parts = block.reshape(block.shape[0], subspaces, sub)
            scores = jnp.einsum("cms,mks->cmk", parts, cent) - halfsq[None, :, :]
            return jnp.argmax(scores, axis=2).astype(jnp.uint8)

        return jax.lax.map(one, x.reshape(nchunks, -1, x.shape[1])).reshape(-1, subspaces)

    out = encode(jnp.asarray(residuals), codebooks, residuals.shape[0] // chunk)
    return np.asarray(out)[:rows]


def build_ivf(
    host_vectors: np.ndarray,
    precision: str,
    config: IVFConfig,
    mesh=None,
    axis_name: str = "model",
) -> IVFState:
    """Train + lay out the index. Deterministic: same inputs, same seed →
    bitwise-identical centroids, layout, and codes (tests pin it)."""
    import jax
    import jax.numpy as jnp

    num_items, dim = host_vectors.shape
    nlist, nprobe = config.nlist, config.nprobe
    n_shards = 1
    if mesh is not None:
        n_shards = int(mesh.shape[axis_name])
        if nlist % n_shards != 0:
            msg = f"ivf nlist={nlist} must divide over {n_shards} '{axis_name}' shards"
            raise ValueError(msg)
        if nprobe % n_shards != 0:
            msg = f"ivf nprobe={nprobe} must divide over {n_shards} '{axis_name}' shards"
            raise ValueError(msg)
    if not 0 < nlist <= num_items:
        msg = f"ivf nlist={nlist} must be in [1, num_items={num_items}]"
        raise ValueError(msg)
    if not 0 < nprobe <= nlist:
        msg = f"ivf nprobe={nprobe} must be in [1, nlist={nlist}]"
        raise ValueError(msg)
    if precision == "int8+pq" and dim % config.pq_subspaces != 0:
        msg = f"pq_subspaces={config.pq_subspaces} must divide dim={dim}"
        raise ValueError(msg)

    table = np.asarray(host_vectors, np.float32)
    rng = np.random.default_rng(config.seed)
    sample_rows = min(config.build_sample, num_items)
    sample = table[rng.choice(num_items, sample_rows, replace=False)]

    centroids = _kmeans_centroids(sample, nlist, config.build_iters, config.seed)
    top2 = _assign_top2(table, centroids)
    cap = max(1, int(np.ceil(config.cell_cap_factor * num_items / nlist)))
    cells = _spill_overflow(top2, nlist, cap)
    counts = np.bincount(cells, minlength=nlist)

    # pq codebooks train on residuals of the SAME sampled rows
    codebooks = None
    cent_np = np.asarray(centroids)
    if precision == "int8+pq":
        # residuals of a fresh sample against their assigned centroid
        sample_idx = rng.choice(num_items, sample_rows, replace=False)
        residual_sample = table[sample_idx] - cent_np[cells[sample_idx]]
        codebooks = _train_pq(residual_sample, config.pq_subspaces, config.build_iters, config.seed)

    # ---- cell-major flat layout on the bucket ladder, per shard ----
    order = np.argsort(cells, kind="stable")
    widths = np.array([ladder_width(int(c)) for c in counts], np.int64)
    cmax = int(widths.max())
    nlist_loc = nlist // n_shards
    shard_widths = widths.reshape(n_shards, nlist_loc)
    shard_payload = shard_widths.sum(axis=1)
    storage_rows = int(shard_payload.max()) + cmax  # CMAX tail guard per shard
    total_rows = storage_rows * n_shards

    rows_np = np.zeros((total_rows, dim), np.float32)
    sids_np = np.full(total_rows, -1, np.int32)
    starts_np = np.zeros(nlist, np.int32)  # shard-LOCAL offsets
    cell_rows = np.split(order, np.cumsum(counts)[:-1])
    for shard in range(n_shards):
        offset = 0
        for local_c in range(nlist_loc):
            c = shard * nlist_loc + local_c
            starts_np[c] = offset
            rows = cell_rows[c]
            base = shard * storage_rows + offset
            rows_np[base:base + len(rows)] = table[rows]
            sids_np[base:base + len(rows)] = rows
            offset += int(widths[c])

    padded_fraction = float(1.0 - num_items / max(total_rows, 1))

    # ---- precision rungs of the flat storage ----
    storage = row_scales = codes = None
    if precision == "int8+pq":
        # per-row cell ids over the flat layout (tail-guard rows stay cell 0
        # of their shard; their sids are -1 so the length mask excludes them)
        cell_ids = np.zeros(total_rows, np.int64)
        for shard in range(n_shards):
            base = shard * storage_rows
            local_cells = np.repeat(
                np.arange(shard * nlist_loc, (shard + 1) * nlist_loc),
                shard_widths[shard],
            )
            cell_ids[base:base + len(local_cells)] = local_cells
        residual_rows = rows_np - cent_np[cell_ids]
        residual_rows[sids_np < 0] = 0.0
        codes = _encode_pq(residual_rows, codebooks)
    elif precision == "int8":
        from replay_tpu.serve.quant import quantize_embeddings

        quantized = quantize_embeddings(rows_np)
        storage = quantized.values
        row_scales = quantized.scales
    else:
        storage = rows_np

    # ---- device placement ----
    def place(arr, spec):
        if mesh is None:
            return jnp.asarray(arr)
        from jax.sharding import NamedSharding

        return jax.device_put(jnp.asarray(arr), NamedSharding(mesh, spec))

    if mesh is not None:
        from jax.sharding import PartitionSpec as P

        row_spec, vec_spec, rep_spec = P(axis_name), P(axis_name, None), P()
    else:
        row_spec = vec_spec = rep_spec = None

    state = IVFState(
        config=config,
        precision=precision,
        num_items=num_items,
        dim=dim,
        centroids=place(cent_np, rep_spec) if mesh is not None else centroids,
        storage=place(storage, vec_spec) if storage is not None else None,
        row_scales=place(row_scales, row_spec) if row_scales is not None else None,
        codes=place(codes, vec_spec) if codes is not None else None,
        codebooks=place(np.asarray(codebooks), rep_spec) if codebooks is not None else None,
        storage_ids=place(sids_np, row_spec),
        starts=place(starts_np, row_spec),
        lengths=place(counts.astype(np.int32), row_spec),
        cmax=cmax,
        storage_rows=storage_rows,
        padded_fraction=padded_fraction,
        mesh=mesh,
        axis_name=axis_name,
        n_shards=n_shards,
    )
    return state


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def _probe_scores(state: IVFState, queries, cscores, probes, starts, lengths,
                  storage, row_scales, codes, lut):
    """[Q, nprobe_eff·CMAX] scores + per-probe start rows, via a lax.scan over
    the probed cells — each step ONE fixed-shape dynamic_slice gather."""
    import jax
    import jax.numpy as jnp

    cmax, dim = state.cmax, state.dim
    nprobe_eff = probes.shape[1]

    def step(_, p):
        cell = probes[:, p]  # [Q]
        st = starts[cell]
        if codes is not None:
            block = jax.vmap(
                lambda s: jax.lax.dynamic_slice(codes, (s, 0), (cmax, codes.shape[1]))
            )(st)  # [Q, CMAX, M] uint8
            base = jnp.take_along_axis(cscores, cell[:, None], axis=1)  # [Q, 1] = q·c
            q_idx = jnp.arange(block.shape[0])[:, None, None]
            m_idx = jnp.arange(block.shape[2])[None, None, :]
            scores = base + jnp.sum(lut[q_idx, m_idx, block.astype(jnp.int32)], axis=-1)
        else:
            rows = jax.vmap(
                lambda s: jax.lax.dynamic_slice(storage, (s, 0), (cmax, dim))
            )(st)
            if row_scales is not None:
                sc = jax.vmap(lambda s: jax.lax.dynamic_slice(row_scales, (s,), (cmax,)))(st)
                scores = jnp.einsum("qe,qce->qc", queries, rows.astype(queries.dtype)) * sc
            else:
                scores = jnp.einsum("qe,qce->qc", queries, rows)
        valid = jnp.arange(cmax)[None, :] < lengths[cell][:, None]
        return None, (jnp.where(valid, scores, -jnp.inf), st)

    _, (scores, sts) = jax.lax.scan(step, None, jnp.arange(nprobe_eff))
    scores = jnp.moveaxis(scores, 0, 1).reshape(queries.shape[0], -1)
    sts = jnp.moveaxis(sts, 0, 1)  # [Q, nprobe_eff]
    return scores, sts


def _resolve_ids(storage_ids, sts, positions, cmax):
    """Map flat top-k positions back to global item ids: position → (probe,
    offset) → storage row → id, without materializing [Q, nprobe·CMAX] ids."""
    import jax.numpy as jnp

    probe_idx = positions // cmax
    offset = positions % cmax
    start = jnp.take_along_axis(sts, probe_idx, axis=1)
    return storage_ids[start + offset]


def _query_lut(state: IVFState, queries):
    """[Q, M, 256] additive LUT: q_m · codebook_m entries, once per batch."""
    import jax.numpy as jnp

    subspaces = int(state.codebooks.shape[0])
    sub = state.dim // subspaces
    parts = queries.reshape(queries.shape[0], subspaces, sub)
    return jnp.einsum("qms,mks->qmk", parts, state.codebooks)


def make_search_fn(state: IVFState, k: int):
    """One jitted fixed-`nprobe` search program for ``[Q, E]`` query batches."""
    import jax
    import jax.numpy as jnp

    nprobe = state.config.nprobe
    if k > nprobe * state.cmax:
        msg = (
            f"k={k} exceeds the probed candidate pool "
            f"(nprobe={nprobe} x cmax={state.cmax}); raise nprobe"
        )
        raise ValueError(msg)

    if state.mesh is None:

        @jax.jit
        def search(queries):
            cscores = queries @ state.centroids.T  # [Q, nlist]
            _, probes = jax.lax.top_k(cscores, nprobe)
            lut = _query_lut(state, queries) if state.codes is not None else None
            scores, sts = _probe_scores(
                state, queries, cscores, probes, state.starts, state.lengths,
                state.storage, state.row_scales, state.codes, lut,
            )
            values, positions = jax.lax.top_k(scores, k)
            return values, _resolve_ids(state.storage_ids, sts, positions, state.cmax)

        return search

    from jax.sharding import PartitionSpec as P

    n = state.n_shards
    axis = state.axis_name
    nlist_loc = state.config.nlist // n
    nprobe_loc = nprobe // n
    local_k = min(k, nprobe_loc * state.cmax)
    dim = state.dim
    quantized = state.row_scales is not None
    pq = state.codes is not None

    def local_search(queries, centroids, sids, starts, lengths, *payload):
        # each shard probes the top-nprobe/n of its OWN contiguous cell block
        shard = jax.lax.axis_index(axis)
        block = jax.lax.dynamic_slice(centroids, (shard * nlist_loc, 0), (nlist_loc, dim))
        cscores = queries @ block.T  # [Q, nlist/n]
        _, probes = jax.lax.top_k(cscores, nprobe_loc)
        if pq:
            storage, row_scales, codes = None, None, payload[0]
            codebooks = payload[1]
            subspaces = int(codebooks.shape[0])
            parts = queries.reshape(queries.shape[0], subspaces, dim // subspaces)
            lut = jnp.einsum("qms,mks->qmk", parts, codebooks)
        elif quantized:
            storage, row_scales, codes, lut = payload[0], payload[1], None, None
        else:
            storage, row_scales, codes, lut = payload[0], None, None, None
        scores, sts = _probe_scores(
            state, queries, cscores, probes, starts, lengths, storage, row_scales, codes, lut
        )
        values, positions = jax.lax.top_k(scores, local_k)
        return values, _resolve_ids(sids, sts, positions, state.cmax)

    if pq:
        payload_arrays = (state.codes, state.codebooks)
        payload_specs = (P(axis, None), P())
    elif quantized:
        payload_arrays = (state.storage, state.row_scales)
        payload_specs = (P(axis, None), P(axis))
    else:
        payload_arrays = (state.storage,)
        payload_specs = (P(axis, None),)

    sharded = jax.shard_map(
        local_search,
        mesh=state.mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis)) + payload_specs,
        out_specs=(P(None, axis), P(None, axis)),
        check_vma=False,
    )

    @jax.jit
    def search(queries):
        # [Q, local_k·n] candidates -> global merge; only candidates cross
        # the mesh (collective_inventory asserts this on the HLO)
        values, ids = sharded(
            queries, state.centroids, state.storage_ids, state.starts,
            state.lengths, *payload_arrays,
        )
        merged, pos = jax.lax.top_k(values, k)
        return merged, jnp.take_along_axis(ids, pos, axis=1)

    return search


# ---------------------------------------------------------------------------
# machine-derived byte accounting (actual AND projected share one formula)
# ---------------------------------------------------------------------------


def ivf_bytes(
    num_items: int,
    dim: int,
    nlist: int,
    precision: str,
    pq_subspaces: int = 8,
    padded_fraction: float = 0.10,
) -> dict:
    """Byte breakdown of an IVF index — the SAME formula prices the built
    index (tests anchor it against real array nbytes) and the 100M-item
    projection the bench reports, so memory claims stay machine-derived."""
    rows = int(round(num_items / max(1.0 - padded_fraction, 1e-6)))
    if precision == "int8+pq":
        cell_bytes = rows * pq_subspaces
        codebook_bytes = pq_subspaces * 256 * (dim // pq_subspaces) * 4
        scale_bytes = 0
    elif precision == "int8":
        cell_bytes = rows * dim
        codebook_bytes = 0
        scale_bytes = rows * 4
    else:
        cell_bytes = rows * dim * 4
        codebook_bytes = 0
        scale_bytes = 0
    centroid_bytes = nlist * dim * 4
    id_bytes = rows * 4
    total = cell_bytes + centroid_bytes + codebook_bytes + scale_bytes + id_bytes
    return {
        "precision": precision,
        "cell_bytes": int(cell_bytes),
        "centroid_bytes": int(centroid_bytes),
        "codebook_bytes": int(codebook_bytes),
        "scale_bytes": int(scale_bytes),
        "id_bytes": int(id_bytes),
        "total_bytes": int(total),
    }


def brute_bytes(num_items: int, dim: int, precision: str) -> dict:
    """Byte cost of the exact sweep's device table at the same rung."""
    itemsize = 1 if precision.startswith("int8") else 4
    payload = num_items * dim * itemsize
    scale_bytes = num_items * 4 if precision.startswith("int8") else 0
    return {
        "precision": precision,
        "table_bytes": int(payload),
        "scale_bytes": int(scale_bytes),
        "total_bytes": int(payload + scale_bytes),
    }
