"""Semantic similarity search: sharded TPU index + Retriever.

TPU-native replacement for the reference's FAISS stack
(``distllm/rag/search.py``; SURVEY.md section 2.4 N2):

- :class:`TpuIndexV2` mirrors ``FaissIndexV2``'s surface — build-if-missing
  from an embeddings dataset, persist to disk, precision ``float32`` (exact
  inner product, MXU matmul + ``lax.top_k``, multi-chip via shard_map) or
  ``ubinary`` (sign-bit packed, Hamming search + fp32 **rescore** with
  ``rescore_multiplier`` oversampling, same semantics as
  sentence-transformers' ``semantic_search_faiss`` path, ``search.py:314-322``),
  score-threshold filtering, and row access ``get(indices, key)``.
  ``index_type`` accepts the reference's HNSW names but serves them with the
  exact search (on TPU the brute-force matmul IS the fast path; approximate
  graphs are a CPU workaround).
- :class:`TpuIndexV1` — deprecated V1 surface kept for config compatibility
  (``search.py:402-666``), same engine underneath.
- :class:`Retriever` — query path with sort-by-length batching, encoder +
  pooler, L2 normalization, order restoration (``search.py:743-928``).
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Literal

import jax.numpy as jnp
import numpy as np
from pydantic import Field

from distllm_tpu.embed.encoders.base import Encoder
from distllm_tpu.embed.poolers.base import Pooler
from distllm_tpu.ops.topk import (
    SCAN_CHUNK_BITS,
    SCAN_CHUNK_INT8,
    group_rows,
    hamming_topk,
    int8_topk,
    pack_sign_bits,
    quantize_int8_rows,
    topk_inner_product,
)
from distllm_tpu.utils import BaseConfig


@dataclass
class BatchedSearchResults:
    """Parity with the reference's result container (``search.py:26-31``)."""

    total_indices: list[list[int]]
    total_scores: list[list[float]]


def _load_embeddings_dataset(dataset_dir: str | Path):
    """Load an embeddings dataset; a directory of UUID shard subdirs (the
    distributed-embedding output layout) is concatenated automatically, so
    indexes build straight from unmerged multi-shard runs."""
    from datasets import concatenate_datasets, load_from_disk

    dataset_dir = Path(dataset_dir)
    if not (dataset_dir / 'dataset_info.json').exists():
        shards = sorted(
            p
            for p in dataset_dir.iterdir()
            if p.is_dir() and (p / 'dataset_info.json').exists()
        )
        if shards:
            return concatenate_datasets(
                [load_from_disk(str(p)) for p in shards]
            )
    return load_from_disk(str(dataset_dir))


class TpuIndexV2Config(BaseConfig):
    name: Literal['tpu_index_v2', 'faiss_index_v2'] = 'tpu_index_v2'
    dataset_dir: Path
    index_dir: Path | None = Field(
        default=None,
        description='Where the packed index file lives; defaults to '
        'dataset_dir/tpu_index.',
    )
    index_type: str = Field(
        default='flat',
        description="'flat' (exact) — 'hnsw*' names accepted and served "
        'exactly (TPU brute force beats CPU graphs).',
    )
    precision: Literal['float32', 'int8', 'ubinary'] = 'float32'
    rescore_multiplier: int = Field(
        default=4,
        description='int8/ubinary: oversample factor before fp32 rescoring.',
    )
    metric: Literal['inner_product'] = 'inner_product'
    normalize: bool = Field(
        default=True, description='L2-normalize embeddings (cosine/IP).'
    )
    mesh: dict | None = Field(
        default=None,
        description='MeshSpec kwargs (e.g. {"data": -1}) to shard the corpus '
        'over chips; None = single device.',
    )

    def get_index(self) -> 'TpuIndexV2':
        mesh = None
        if self.mesh is not None:
            from distllm_tpu.parallel.mesh import MeshSpec, make_mesh

            mesh = make_mesh(MeshSpec(**self.mesh))
        return TpuIndexV2(self, mesh=mesh)


class TpuIndexV2:
    def __init__(self, config: TpuIndexV2Config, mesh=None) -> None:
        self.config = config
        self.mesh = mesh
        self.dataset = _load_embeddings_dataset(config.dataset_dir)
        index_dir = config.index_dir or (Path(config.dataset_dir) / 'tpu_index')
        self._index_file = Path(index_dir) / f'index_{config.precision}.npz'
        self._build_or_load()

    # ------------------------------------------------------------ building
    # Rows per build/load chunk: bounds peak host RSS at O(chunk), not
    # O(corpus) (the reference streams its quantization through a
    # ProcessPoolExecutor for the same reason, search.py:210-221).
    _CHUNK_ROWS = 65536

    def _chunk(self, lo: int) -> np.ndarray:
        hi = min(lo + self._CHUNK_ROWS, len(self.dataset))
        # Arrow → numpy directly: the default format hands back Python
        # lists of floats (50M objects per chunk at 768 dims), which made
        # a 1M-row build take tens of minutes.
        rows = np.asarray(
            self.dataset.with_format('numpy', columns=['embeddings'])[lo:hi][
                'embeddings'
            ],
            dtype=np.float32,
        )
        if self.config.normalize:
            norms = np.linalg.norm(rows, axis=1, keepdims=True)
            rows = rows / np.clip(norms, 1e-12, None)
        return rows

    def _build_shards(self) -> None:
        """Stream the corpus into per-chunk index shard files.

        Chunks are read, normalized, and (for ubinary) sign-bit packed in a
        thread pool — numpy releases the GIL, giving the reference's
        parallel-quantization behavior without pickling the corpus.
        """
        import json
        from concurrent.futures import ThreadPoolExecutor

        shard_dir = self._index_file.parent
        shard_dir.mkdir(parents=True, exist_ok=True)
        offsets = list(range(0, len(self.dataset), self._CHUNK_ROWS))

        def build_one(part: int) -> str:
            rows = self._chunk(offsets[part])
            if self.config.precision == 'ubinary':
                rows = pack_sign_bits(rows)
            elif self.config.precision == 'int8':
                codes, scales = quantize_int8_rows(rows)
                name = f'{self._index_file.stem}.part{part:05d}.npz'
                np.savez(shard_dir / name, codes=codes, scales=scales)
                return name
            name = f'{self._index_file.stem}.part{part:05d}.npy'
            np.save(shard_dir / name, rows)
            return name

        with ThreadPoolExecutor(max_workers=8) as pool:
            parts = list(pool.map(build_one, range(len(offsets))))
        meta = {'num_rows': len(self.dataset), 'parts': parts}
        self._meta_file.write_text(json.dumps(meta))

    def _iter_stored_chunks(self):
        """Yield index chunks (mmap'd shard parts, or the legacy npz)."""
        import json

        if self._meta_file.exists():
            meta = json.loads(self._meta_file.read_text())
            for name in meta['parts']:
                yield np.load(self._index_file.parent / name, mmap_mode='r')
        else:  # legacy single-file layout
            yield np.load(self._index_file)['embeddings']

    def _build_or_load(self) -> None:
        import json

        self._meta_file = self._index_file.with_suffix('.meta.json')
        if self._meta_file.exists():
            # A stale index (dataset re-embedded since the build) would
            # silently mis-align rows; rebuild when the row count moved.
            meta = json.loads(self._meta_file.read_text())
            if meta.get('num_rows') != len(self.dataset):
                self._build_shards()
        elif not self._index_file.exists():
            self._build_shards()
        self._num_real = len(self.dataset)

        if self.config.precision == 'ubinary':
            # Packed bits are corpus/32 bytes — assemble on host, GROUP
            # into [G, chunk, H/8] (ops/topk.group_rows), then one
            # device_put: the grouped layout rides hamming_topk's single-
            # dispatch lax.scan (ops/topk.group_rows has the old record's
            # numbers; not re-measured). NO second fp32 host
            # copy: rescore candidates are gathered per query batch from
            # the arrow-mmap'd dataset.
            self._packed = jnp.asarray(group_rows(
                np.concatenate(
                    [np.asarray(c) for c in self._iter_stored_chunks()]
                ),
                SCAN_CHUNK_BITS,
            ))
            self._corpus = None
            self._int8 = None
            return

        if self.config.precision == 'int8':
            # corpus/4 bytes on device (codes) + tiny scales: the middle
            # tier — MXU int8 scoring with fp32 rescore (same rescore path
            # as ubinary). Beyond-reference extension: the reference
            # validates only float32/ubinary (search.py:172-176). Single-
            # device codes are grouped for the scan path like ubinary.
            parts = list(self._iter_stored_chunks())
            codes = np.concatenate([np.asarray(p['codes']) for p in parts])
            scales = np.concatenate([np.asarray(p['scales']) for p in parts])
            if self.mesh is not None and self.mesh.shape.get('data', 1) > 1:
                self._int8 = self._put_row_sharded((codes, 0), (scales, 1))
            else:
                self._int8 = (
                    jnp.asarray(group_rows(codes, SCAN_CHUNK_INT8)),
                    jnp.asarray(group_rows(scales, SCAN_CHUNK_INT8)),
                )
            self._packed = None
            self._corpus = None
            return

        self._packed = None
        self._int8 = None
        if self.mesh is not None and self.mesh.shape.get('data', 1) > 1:
            # Multi-chip: assemble on host (pod hosts have the RAM), pad to
            # a shardable row count — padded indices (>= _num_real) are
            # dropped in the search filter.
            embeddings = np.concatenate(
                [np.asarray(c) for c in self._iter_stored_chunks()]
            )
            (self._corpus,) = self._put_row_sharded((embeddings, 0))
            return

        # Single device: assemble directly in HBM chunk by chunk via a
        # donated dynamic-update-slice, so host RSS stays O(chunk).
        import jax

        update = jax.jit(
            lambda buf, part, lo: jax.lax.dynamic_update_slice(
                buf, part, (lo, 0)
            ),
            donate_argnums=0,
        )
        buf = None
        lo = 0
        for chunk in self._iter_stored_chunks():
            part = np.asarray(chunk, dtype=np.float32)
            if buf is None:
                dim = part.shape[1]
                buf = jnp.zeros((self._num_real, dim), jnp.float32)
            buf = update(buf, part, lo)
            lo += part.shape[0]
        self._corpus = buf

    def _put_row_sharded(self, *arrays_with_fill) -> tuple:
        """Pad each host array to a row count divisible by the mesh's
        ``data`` axis (with the given fill value) and device_put it
        row-sharded. One home for the pad+shard math of every precision
        tier; padded indices (>= ``_num_real``) are dropped downstream."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        shards = self.mesh.shape['data']
        out = []
        for arr, fill in arrays_with_fill:
            pad = (-arr.shape[0]) % shards
            if pad:
                block = np.full((pad, *arr.shape[1:]), fill, arr.dtype)
                arr = np.concatenate([arr, block])
            spec = P('data', *([None] * (arr.ndim - 1)))
            out.append(jax.device_put(arr, NamedSharding(self.mesh, spec)))
        return tuple(out)

    def __len__(self) -> int:
        return len(self.dataset)

    # ------------------------------------------------------------- search
    def search(
        self,
        query_embeddings: np.ndarray,  # [B, H] fp32 (normalized by Retriever)
        top_k: int = 1,
        score_threshold: float = 0.0,
    ) -> BatchedSearchResults:
        if self.config.precision == 'ubinary':
            scores, indices = self._search_ubinary(query_embeddings, top_k)
        elif self.config.precision == 'int8':
            scores, indices = self._search_int8(query_embeddings, top_k)
        else:
            scores, indices = topk_inner_product(
                jnp.asarray(query_embeddings), self._corpus, top_k, self.mesh
            )
            scores, indices = np.asarray(scores), np.asarray(indices)
        # Score-threshold filter (reference ``search.py:338-382``); padding
        # rows from the sharded layout (index >= corpus size) are dropped.
        total_indices, total_scores = [], []
        for row_scores, row_idx in zip(scores, indices):
            keep = (row_scores >= score_threshold) & (row_idx < self._num_real)
            total_indices.append([int(i) for i in row_idx[keep]])
            total_scores.append([float(s) for s in row_scores[keep]])
        return BatchedSearchResults(total_indices, total_scores)

    def _search_ubinary(self, queries: np.ndarray, top_k: int):
        query_bits = jnp.asarray(pack_sign_bits(queries))
        oversample = min(
            top_k * self.config.rescore_multiplier, len(self.dataset)
        )
        _, cand = hamming_topk(
            query_bits, self._packed, oversample, n_valid=self._num_real
        )
        return self._rescore(queries, np.asarray(cand), top_k)

    def _search_int8(self, queries: np.ndarray, top_k: int):
        oversample = min(
            top_k * self.config.rescore_multiplier, len(self.dataset)
        )
        codes, scales = self._int8
        _, cand = int8_topk(
            jnp.asarray(queries.astype(np.float32)), codes, scales,
            oversample, self.mesh, n_valid=self._num_real,
        )
        return self._rescore(queries, np.asarray(cand), top_k)

    def _rescore(self, queries: np.ndarray, cand: np.ndarray, top_k: int):
        """fp32 rescore of quantized-tier candidates against the
        full-precision query (sentence-transformers rescore semantics).
        Candidate vectors come from the arrow-mmap'd dataset per batch —
        the index keeps NO fp32 corpus copy (that second copy doubled host
        RSS in earlier revisions).

        ``cand`` may contain padded-row indices (>= ``_num_real``) from a
        sharded layout; their ORIGINAL indices are preserved (so the
        ``search()`` filter drops them) while the dataset gather uses a
        clamped copy and their rescores are pinned to -inf so they can
        never displace a real neighbor in the top-k.
        """
        valid = cand < self._num_real
        flat = np.minimum(cand, self._num_real - 1).reshape(-1)
        order_back = np.argsort(np.argsort(flat))
        gathered = np.asarray(
            self.dataset[np.sort(flat).tolist()]['embeddings'],
            dtype=np.float32,
        )[order_back]
        cand_vectors = gathered.reshape(*cand.shape, -1)
        if self.config.normalize:
            norms = np.linalg.norm(cand_vectors, axis=-1, keepdims=True)
            cand_vectors = cand_vectors / np.clip(norms, 1e-12, None)
        rescored = np.einsum('bh,boh->bo', queries.astype(np.float32), cand_vectors)
        rescored = np.where(valid, rescored, -np.inf)
        order = np.argsort(-rescored, axis=1)[:, :top_k]
        indices = np.take_along_axis(cand, order, axis=1)
        scores = np.take_along_axis(rescored, order, axis=1)
        return scores, indices

    # ------------------------------------------------------------ row access
    def get(self, indices: list[int], key: str) -> list[Any]:
        """Row field access (reference ``search.py:384-399``)."""
        rows = self.dataset[indices]
        return list(rows[key])


class TpuIndexV1Config(BaseConfig):
    """Deprecated V1 surface (reference ``search.py:402-666``)."""

    name: Literal['tpu_index_v1', 'faiss_index_v1'] = 'tpu_index_v1'
    dataset_dir: Path
    metric: Literal['inner_product', 'l2'] = 'inner_product'

    def get_index(self) -> 'TpuIndexV2':
        warnings.warn(
            'TpuIndexV1 is deprecated; use TpuIndexV2.',
            DeprecationWarning,
            stacklevel=2,
        )
        v2 = TpuIndexV2Config(dataset_dir=self.dataset_dir)
        return TpuIndexV2(v2)


class RetrieverConfig(BaseConfig):
    """Parity with ``RetrieverConfig.get_retriever`` (``search.py:669-712``)."""

    faiss_config: dict[str, Any]
    encoder_config: dict[str, Any]
    pooler_config: dict[str, Any]
    batch_size: int = 8

    def get_retriever(self, register: bool = False) -> 'Retriever':
        from distllm_tpu.embed import get_encoder, get_pooler

        index_config = dict(self.faiss_config)
        index_config.pop('name', None)
        index = TpuIndexV2Config(**index_config).get_index()
        encoder = get_encoder(self.encoder_config, register=register)
        pooler = get_pooler(self.pooler_config)
        return Retriever(index, encoder, pooler, self.batch_size)


class Retriever:
    """Query encoding + index search (reference ``search.py:715-928``)."""

    def __init__(
        self,
        index: TpuIndexV2,
        encoder: Encoder,
        pooler: Pooler,
        batch_size: int = 8,
    ) -> None:
        self.index = index
        self.encoder = encoder
        self.pooler = pooler
        self.batch_size = batch_size

    def get_pooled_embeddings(self, queries: list[str]) -> np.ndarray:
        """Sort-by-length → batch → encode → pool → normalize → restore order."""
        from distllm_tpu.embed.embedders.full_sequence import compute_embeddings

        embeddings = compute_embeddings(
            queries, self.encoder, self.pooler, self.batch_size, normalize=False
        )
        norms = np.linalg.norm(embeddings, axis=1, keepdims=True)
        return embeddings / np.clip(norms, 1e-12, None)

    def search(
        self,
        query: str | list[str],
        top_k: int = 1,
        score_threshold: float = 0.0,
    ) -> tuple[BatchedSearchResults, np.ndarray]:
        """Returns (results, query_embeddings) — reference ``search.py:743-798``."""
        queries = [query] if isinstance(query, str) else list(query)
        embeddings = self.get_pooled_embeddings(queries)
        return self.index.search(embeddings, top_k, score_threshold), embeddings

    def get(self, indices: list[int], key: str) -> list[Any]:
        return self.index.get(indices, key)

    def get_texts(self, indices: list[int]) -> list[str]:
        """Parity with ``Retriever.get_texts`` (``search.py:915-928``)."""
        return self.index.get(indices, 'text')
