"""Exact MIPS retrieval — the HNSW/ANN extension, TPU-style.

Capability parity with replay/models/extensions/ann/ (ANNMixin over hnswlib/
nmslib C++ indexes, ref ann_mixin.py:26): the reference approximates maximum-
inner-product search because CPU exact search is too slow; on TPU the exact
[Q, E] × [E, I] scores ARE the fast path (one MXU matmul), optionally sharded
over a mesh axis so each chip scores its slice of the catalog and only per-shard
top-k candidates (k × n_shards rows, not the full score matrix) are merged.

``ANNMixin`` plugs the index into any item-vector model (ALS, Word2Vec): fitted
factors build the index once, ``predict``/``get_nearest_items`` query it.
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import pandas as pd


class MIPSIndex:
    """Exact maximum-inner-product top-k over (optionally mesh-sharded) items.

    ``precision="int8"`` stores the catalog per-row symmetrically quantized
    (``replay_tpu.serve.quant``): the device sweep reads ¼ the bytes — the
    traffic that dominates retrieval latency for memory-bound catalogs — and
    scores dequantize in registers (``(q @ w_int8ᵀ) * scale``). The f32
    master copy stays HOST-side (``host_vectors``) and feeds
    :meth:`exact_rescore`, the full-precision candidate rescoring the
    serving pipeline applies before its top-k cut; device HBM holds only the
    int8 rows + f32 scales. Mesh-sharded, the int8 values keep the CEFusedTP
    ``[I/n, E]`` row-shard layout (scales shard ``[I/n]`` alongside) — the
    layout that lets 10M-item tables fit where f32 cannot.
    """

    def __init__(
        self,
        item_vectors: np.ndarray,
        mesh=None,
        axis_name: str = "data",
        precision: str = "f32",
        index: str = "brute",
        nlist: Optional[int] = None,
        nprobe: int = 32,
        build_iters: int = 10,
        build_sample: int = 131072,
        pq_subspaces: int = 8,
        seed: int = 0,
    ) -> None:
        import jax
        import jax.numpy as jnp

        if index not in ("brute", "ivf"):
            msg = f"MIPSIndex index must be 'brute' or 'ivf', got {index!r}"
            raise ValueError(msg)
        if index == "ivf":
            if precision not in ("f32", "int8", "int8+pq"):
                msg = (
                    "MIPSIndex(index='ivf') precision must be 'f32', 'int8' or "
                    f"'int8+pq', got {precision!r}"
                )
                raise ValueError(msg)
        elif precision not in ("f32", "int8"):
            msg = f"MIPSIndex precision must be 'f32' or 'int8', got {precision!r}"
            raise ValueError(msg)
        self.num_items, self.dim = item_vectors.shape
        self.host_vectors = np.asarray(item_vectors)  # unpadded f32 master copy
        self.mesh = mesh
        self.axis_name = axis_name
        self.precision = precision
        self.index_mode = index
        self._ivf = None
        self._search_cache = {}
        self._rescore_fn = None

        if index == "ivf":
            from replay_tpu.models.ivf import IVFConfig, build_ivf, default_nlist

            n_shards = 1 if mesh is None else int(mesh.shape[axis_name])
            if nlist is None:
                nlist = default_nlist(self.num_items, n_shards)
            config = IVFConfig(
                nlist=int(nlist),
                nprobe=int(nprobe),
                build_iters=int(build_iters),
                build_sample=int(build_sample),
                pq_subspaces=int(pq_subspaces),
                seed=int(seed),
            )
            self._ivf = build_ivf(
                self.host_vectors.astype(np.float32), precision, config,
                mesh=mesh, axis_name=axis_name,
            )
            self.item_vectors = None  # cell-major storage lives in self._ivf
            self.item_scales = None
            self._payload_nbytes = self._ivf_bytes()["cell_bytes"]
            return

        scales = None
        if precision == "int8":
            from replay_tpu.serve.quant import quantize_embeddings

            quantized = quantize_embeddings(self.host_vectors)
            item_vectors = quantized.values  # int8 [I, E]
            scales = quantized.scales  # f32 [I]
            self._payload_nbytes = quantized.nbytes
        else:
            item_vectors = np.asarray(item_vectors)
            self._payload_nbytes = int(
                self.num_items * self.dim * item_vectors.dtype.itemsize
            )

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # pad the catalog to a shard multiple with zero rows; the search
            # masks padded positions to -inf before the per-shard top-k
            n_shards = mesh.shape[axis_name]
            padded_rows = -(-self.num_items // n_shards) * n_shards
            if padded_rows != self.num_items:
                pad = padded_rows - self.num_items
                item_vectors = np.concatenate(
                    [item_vectors, np.zeros((pad, self.dim), item_vectors.dtype)]
                )
                if scales is not None:
                    scales = np.concatenate([scales, np.zeros(pad, scales.dtype)])
            self.item_vectors = jax.device_put(
                jnp.asarray(item_vectors), NamedSharding(mesh, P(axis_name, None))
            )
            if scales is not None:
                self.item_scales = jax.device_put(
                    jnp.asarray(scales), NamedSharding(mesh, P(axis_name))
                )
        else:
            self.item_vectors = jnp.asarray(item_vectors)
            if scales is not None:
                self.item_scales = jnp.asarray(scales)
        if scales is None:
            self.item_scales = None

        self._search_cache = {}
        self._rescore_fn = None

    @property
    def is_approximate(self) -> bool:
        """True when the sweep only SELECTS candidates (IVF probing and/or a
        quantized table) — the pipeline's cue to insert ``exact_rescore``
        before ranking. Only the brute f32 sweep scores exactly."""
        return self.index_mode == "ivf" or self.precision != "f32"

    def _ivf_bytes(self) -> dict:
        from replay_tpu.models.ivf import ivf_bytes

        state = self._ivf
        return ivf_bytes(
            self.num_items,
            self.dim,
            state.config.nlist,
            self.precision,
            pq_subspaces=state.config.pq_subspaces,
            padded_fraction=state.padded_fraction,
        )

    def index_stats(self) -> dict:
        """Build/search geometry the bench records and the report renders."""
        if self.index_mode != "ivf":
            return {"index": "brute", "num_items": self.num_items, "dim": self.dim}
        state = self._ivf
        return {
            "index": "ivf",
            "num_items": self.num_items,
            "dim": self.dim,
            "nlist": state.config.nlist,
            "nprobe": state.config.nprobe,
            "cmax": state.cmax,
            "padded_fraction": round(state.padded_fraction, 4),
            "scanned_fraction": round(
                state.config.nprobe * state.cmax / max(self.num_items, 1), 4
            ),
            "n_shards": state.n_shards,
        }

    def table_bytes(self) -> dict:
        """Logical payload bytes of the device catalog (unpadded rows): the
        honesty number to report next to the f32 baseline.
        IVF adds the machine-derived breakdown (centroid/cell/codebook/id
        bytes) priced by the same formula as the 100M projection."""
        f32_bytes = int(self.num_items * self.dim * 4)
        out = {
            "precision": self.precision,
            "payload_bytes": int(self._payload_nbytes),
            "f32_bytes": f32_bytes,
            "bytes_ratio": self._payload_nbytes / max(f32_bytes, 1),
        }
        if self.index_mode == "ivf":
            out.update(self._ivf_bytes())
            out["payload_bytes"] = out["total_bytes"]
            out["bytes_ratio"] = out["total_bytes"] / max(f32_bytes, 1)
        return out

    def _compiled_search(self, k: int):
        import jax
        import jax.numpy as jnp

        if k in self._search_cache:
            return self._search_cache[k]
        if self.index_mode == "ivf":
            from replay_tpu.models.ivf import make_search_fn

            search = make_search_fn(self._ivf, k)
            self._search_cache[k] = search
            return search
        quantized = self.precision == "int8"

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            n_shards = self.mesh.shape[self.axis_name]
            shard_size = self.item_vectors.shape[0] // n_shards
            num_items = self.num_items
            # a shard can contribute at most its own rows; the global merge still
            # sees >= k candidates because n_shards * shard_size >= num_items >= k
            local_k = min(k, shard_size)

            def local_topk(queries, items, *scales):
                if quantized:
                    # weight-only dequantization: the HBM read is int8 (¼ the
                    # bytes); the up-cast + per-row scale fuse into the matmul
                    scores = (queries @ items.T.astype(queries.dtype)) * scales[0][None, :]
                else:
                    scores = queries @ items.T  # [Q, I/shards]
                offset = jax.lax.axis_index(self.axis_name) * shard_size
                positions = offset + jnp.arange(shard_size)
                # catalog-padding rows can never win
                scores = jnp.where(positions[None, :] < num_items, scores, -jnp.inf)
                values, idx = jax.lax.top_k(scores, local_k)
                return values, idx + offset

            # the int8 variant rides ONE extra [I/n] scales operand sharded
            # alongside the rows; the f32 program is untouched
            scale_specs = (P(self.axis_name),) if quantized else ()
            scale_args = (self.item_scales,) if quantized else ()
            sharded = jax.shard_map(
                local_topk,
                mesh=self.mesh,
                in_specs=(P(), P(self.axis_name, None)) + scale_specs,
                out_specs=(P(None, self.axis_name), P(None, self.axis_name)),
                check_vma=False,
            )

            @jax.jit
            def search(queries):
                # [Q, k*shards] candidates -> global top-k merge
                values, idx = sharded(queries, self.item_vectors, *scale_args)
                merged_values, merged_pos = jax.lax.top_k(values, k)
                return merged_values, jnp.take_along_axis(idx, merged_pos, axis=1)

        elif quantized:

            @jax.jit
            def search(queries):
                scores = (
                    queries @ self.item_vectors.T.astype(queries.dtype)
                ) * self.item_scales[None, :]
                return jax.lax.top_k(scores, k)

        else:

            @jax.jit
            def search(queries):
                scores = queries @ self.item_vectors.T
                return jax.lax.top_k(scores, k)

        self._search_cache[k] = search
        return search

    def search_hlo(self, rows: int, k: int) -> str:
        """Compiled HLO text of the ``[rows, dim]`` search program — the
        input :func:`~replay_tpu.parallel.introspect.collective_inventory`
        hard-asserts over: a mesh-sharded index must move per-shard top-k
        CANDIDATES (``k x n_shards`` rows) across the mesh, never the
        ``[I/n, E]`` table rows themselves. Uses the same cached jitted
        search the serving path runs, so the assertion inspects the real
        program, not a re-derivation."""
        import jax
        import jax.numpy as jnp

        spec = jax.ShapeDtypeStruct((int(rows), self.dim), jnp.float32)
        return self._compiled_search(k).lower(spec).compile().as_text()

    def table_shard_bytes(self) -> int:
        """Per-shard payload bytes of the device table (padded rows included)
        — the collective-size threshold the no-gather assertion compares
        against. For IVF this is the per-shard CELL payload (rows for
        f32/int8, uint8 codes for int8+pq): the bytes a table-sized gather
        would have to move."""
        if self.index_mode == "ivf":
            state = self._ivf
            if self.precision == "int8+pq":
                return state.storage_rows * state.config.pq_subspaces
            itemsize = 1 if self.precision == "int8" else 4
            return state.storage_rows * self.dim * itemsize
        rows = int(self.item_vectors.shape[0])
        if self.mesh is not None:
            rows = rows // int(self.mesh.shape[self.axis_name])
        itemsize = 1 if self.precision == "int8" else 4
        return rows * self.dim * itemsize

    def exact_rescore(self, query_vectors, candidate_ids):
        """Full-precision scores of already-retrieved candidates.

        ``[Q, E]`` queries × ``[Q, C]`` candidate ids → ``[Q, C]`` exact f32
        inner products against the MASTER (unquantized) rows — the serving
        pipeline's re-rank input, so the quantized sweep only decides WHICH C
        items are scored, never their final ranking scores. The f32 rows are
        gathered from the host-side master copy (C×E×4 bytes per query — tiny
        next to the table sweep the int8 path just avoided); for an f32 index
        this reproduces ``search_jax``'s scores exactly (tests pin it).
        """
        import jax
        import jax.numpy as jnp

        if self._rescore_fn is None:

            @jax.jit
            def rescore(queries, rows):
                return jnp.einsum(
                    "qe,qce->qc",
                    queries.astype(jnp.float32),
                    rows.astype(jnp.float32),
                )

            self._rescore_fn = rescore
        rows = self.host_vectors[np.asarray(candidate_ids)]  # [Q, C, E] f32
        return self._rescore_fn(jnp.asarray(query_vectors, jnp.float32), jnp.asarray(rows))

    def search_jax(self, query_vectors, k: int):
        """(scores [Q, k], item ids [Q, k]) as DEVICE arrays — the fused
        serving path (``replay_tpu.serve``) hands the encoder's last-hidden
        state straight in and the candidate ids straight to the re-rank
        program, no host round-trip between retrieval stages."""
        import jax.numpy as jnp

        if k > self.num_items:
            msg = f"k={k} exceeds the catalog size {self.num_items}"
            raise ValueError(msg)
        return self._compiled_search(k)(jnp.asarray(query_vectors, jnp.float32))

    def search(self, query_vectors: np.ndarray, k: int):
        """(scores [Q, k], item ids [Q, k]) of the highest inner products."""
        values, indices = self.search_jax(query_vectors, k)
        return np.asarray(values), np.asarray(indices)


class ANNMixin:
    """Adds exact-MIPS retrieval to models exposing user/item factor matrices.

    Models whose native ranking is cosine (Word2Vec) set ``_ann_metric =
    "cosine"`` and the index stores/queries L2-normalized vectors, keeping
    ``predict_ann``'s top-k faithful to ``predict``'s.
    """

    _mips_index: Optional[MIPSIndex] = None
    _ann_metric: str = "dot"

    def fit(self, dataset):
        self._mips_index = None  # refit invalidates the index
        return super().fit(dataset)

    def build_ann_index(self, mesh=None, axis_name: str = "data") -> "ANNMixin":
        self._check_fitted()
        self._mips_index = MIPSIndex(self._ann_item_vectors(), mesh=mesh, axis_name=axis_name)
        return self

    def _maybe_normalize(self, vectors: np.ndarray) -> np.ndarray:
        if self._ann_metric == "cosine":
            return vectors / (np.linalg.norm(vectors, axis=-1, keepdims=True) + 1e-9)
        return vectors

    def _ann_item_vectors(self) -> np.ndarray:
        if getattr(self, "item_factors", None) is not None:
            return self._maybe_normalize(np.asarray(self.item_factors, np.float32))
        if getattr(self, "item_vectors", None) is not None:
            return self._maybe_normalize(np.asarray(self.item_vectors, np.float32))
        msg = f"{type(self).__name__} exposes no item vectors for ANN."
        raise ValueError(msg)

    def _ann_query_vectors(self, dataset, queries: np.ndarray) -> np.ndarray:
        if getattr(self, "user_factors", None) is not None:
            q_index = pd.Index(self.fit_queries)
            positions = q_index.get_indexer(queries)
            if (positions < 0).any():
                cold = np.asarray(queries)[positions < 0]
                msg = f"Queries not seen at fit time have no factors: {cold[:5].tolist()}"
                raise ValueError(msg)
            return self._maybe_normalize(np.asarray(self.user_factors[positions], np.float32))
        return self._maybe_normalize(
            np.asarray(self._query_vectors(dataset, queries), np.float32)
        )

    def predict_ann(self, dataset, k: int, queries=None) -> pd.DataFrame:
        """Top-k via the index (no seen-filtering: serving-style retrieval)."""
        if self._mips_index is None:
            self.build_ann_index()
        if queries is None:
            queries = self.fit_queries
        queries = np.asarray(queries)
        q_vec = self._ann_query_vectors(dataset, queries)
        scores, indices = self._mips_index.search(q_vec, k)
        items = np.asarray(self.fit_items)[indices]
        return pd.DataFrame(
            {
                self.query_column: np.repeat(queries, k),
                self.item_column: items.reshape(-1),
                "rating": scores.reshape(-1),
            }
        )

    def get_nearest_items_ann(self, items, k: int) -> pd.DataFrame:
        """Top-k most similar catalog items per given item id."""
        if self._mips_index is None:
            self.build_ann_index()
        i_index = pd.Index(self.fit_items)
        positions = i_index.get_indexer(np.asarray(items))
        if (positions < 0).any():
            unknown = np.asarray(items)[positions < 0]
            msg = f"Items not seen at fit time: {unknown[:5].tolist()}"
            raise ValueError(msg)
        # the index already holds the (normalized) catalog — just slice it
        vectors = self._mips_index.host_vectors[positions]
        scores, indices = self._mips_index.search(vectors, k + 1)
        out = []
        for row, item in enumerate(np.asarray(items)):
            neighbours = [
                (self.fit_items[j], s)
                for j, s in zip(indices[row], scores[row])
                if self.fit_items[j] != item
            ][:k]
            out.append(
                pd.DataFrame(
                    {
                        "item_idx": item,
                        "neighbour_item_idx": [n for n, _ in neighbours],
                        "similarity": [s for _, s in neighbours],
                    }
                )
            )
        return pd.concat(out, ignore_index=True)
