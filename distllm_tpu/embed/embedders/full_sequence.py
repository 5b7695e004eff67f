"""Full-sequence embedder — the hot loop of the embed pipeline.

Reference parity: ``distllm/embed/embedders/full_sequence.py:20-80`` — a
preallocated host ``[N, H]`` buffer filled batch by batch. TPU adaptations:

- texts are sorted by whitespace length and restored afterwards, so each
  bucketed batch wastes minimal padding (the reference's Retriever does this
  for queries, ``rag/search.py:800-836``; we apply it to the hot loop too);
- partial final batches are padded to the fixed batch size with fully-masked
  rows (jit re-specializes on batch shape otherwise);
- encode+pool+normalize stay on device; only pooled ``[B, H]`` rows transfer
  to host per batch (vs per-batch ``[B, S, H]`` ``.cpu()`` in torch).
"""

from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import Field

from distllm_tpu.embed.datasets.base import TextCorpus
from distllm_tpu.embed.embedders.base import EmbedderResult
from distllm_tpu.embed.encoders.base import Encoder
from distllm_tpu.embed.poolers.base import Pooler
from distllm_tpu.utils import BaseConfig


def compute_embeddings(
    texts: list[str],
    encoder: Encoder,
    pooler: Pooler,
    batch_size: int,
    normalize: bool = False,
    flush_every: int = 64,
    max_resident_groups: int = 8,
    tokenize_ahead: int = 2,
    stats: dict | None = None,
) -> np.ndarray:
    """Embed ``texts`` → host ``[N, H]`` float32 array in original order.

    Dispatch is asynchronous: each batch's forward+pool is enqueued and the
    pooled ``[B, H]`` device arrays are collected without blocking, so host
    tokenization of batch *i+1* overlaps device compute of batch *i*. Every
    ``flush_every`` batches the pooled rows are concatenated ON DEVICE into
    one array whose host copy starts asynchronously (one device→host round
    trip per group rather than per batch). At most ``max_resident_groups``
    sealed groups stay on device: past that the oldest (whose async copy has
    had the longest to land) is drained into the host buffer, so device
    residency stays O(flush_every · batch · H) rather than O(corpus).

    ``tokenize_ahead`` batches are tokenized on a background thread while
    the main thread dispatches: dispatch itself is ~free (async), so the
    device only starves when HOST tokenization of the next batch outlasts
    device compute of the current one — true for heavy HF tokenizers on
    long chunks (fast tokenizers release the GIL, so the overlap is real).
    ``0`` restores inline tokenization.

    ``stats``, when given, is filled with bucket-occupancy telemetry:
    ``tokens_real`` / ``tokens_padded`` (device token slots incl. padding)
    and ``bucket_batches`` (batches dispatched per bucket length) — the
    numbers that say whether the bucket ladder is wasting MXU cycles.
    """
    n = len(texts)
    out = np.empty((n, encoder.embedding_size), dtype=np.float32)
    if n == 0:
        return out
    order = sorted(range(n), key=lambda i: len(texts[i].split()))
    pending: list[tuple[list[int], jnp.ndarray]] = []
    # (indices, concatenated device array) per flush group, fetched at the
    # end. Pooled rows are tiny ([N, H] fp32), so whole-corpus residency on
    # device is trivial next to the model — what matters is the number of
    # device→host fetches: a fetch per batch puts a blocking sync into
    # every step of the loop, while one device-side concat per flush
    # group + one async copy keeps the dispatch queue full.
    groups: list[tuple[list[int], jnp.ndarray]] = []
    # Fused encode+pool (one dispatch/batch) when the encoder supports it;
    # composed per-stage dispatches otherwise (e.g. FakeEncoder).
    fused = (
        encoder.pooled_forward(pooler, normalize)
        if hasattr(encoder, 'pooled_forward')
        else None
    )

    def drain_group() -> None:
        idx_all, group = groups.pop(0)
        out[idx_all] = np.asarray(group, dtype=np.float32)

    def seal_group() -> None:
        if not pending:
            return
        idx_all = [i for idx, _ in pending for i in idx]
        rows = [dev[: len(idx)] for idx, dev in pending]
        group = jnp.concatenate(rows, axis=0) if len(rows) > 1 else rows[0]
        if isinstance(group, jax.Array):  # a fake encoder hands back numpy
            group.copy_to_host_async()  # overlaps later groups' compute
        groups.append((idx_all, group))
        pending.clear()
        # Bound device residency: drain the OLDEST group (its async copy has
        # had the longest to complete, so this rarely blocks) once more than
        # max_resident_groups are outstanding.
        while len(groups) > max_resident_groups:
            drain_group()

    def tokenize(lo: int):
        idx = order[lo : lo + batch_size]
        batch = encoder.tokenizer([texts[i] for i in idx])
        return idx, batch.pad_batch_to(
            batch_size, pad_id=encoder.tokenizer.pad_id
        )

    starts = list(range(0, n, batch_size))
    if tokenize_ahead > 0 and len(starts) > 1:
        batches = _prefetched(tokenize, starts, tokenize_ahead)
    else:
        batches = (tokenize(s) for s in starts)

    # try/finally around the consumer loop: deterministically finalize the
    # prefetch generator (its own finally stops the tokenizer thread) even
    # when the loop raises, e.g. an encoder OOM — GC finalization can be
    # arbitrarily deferred while the exception's traceback pins this frame.
    try:
        for idx, batch in batches:
            if stats is not None:
                stats['tokens_real'] = stats.get('tokens_real', 0) + int(
                    batch.attention_mask.sum()
                )
                stats['tokens_padded'] = (
                    stats.get('tokens_padded', 0) + batch.input_ids.size
                )
                hist = stats.setdefault('bucket_batches', {})
                bucket = int(batch.input_ids.shape[1])
                hist[bucket] = hist.get(bucket, 0) + 1
            if fused is not None:
                pooled = fused(batch)
            else:
                pooled = pooler.pool(
                    encoder.forward(batch), batch.attention_mask
                )
                if normalize:
                    # Same guarded normalize as the fused path (zero vectors
                    # from fully-masked pad rows must not produce NaN).
                    pooled = pooled / jnp.clip(
                        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12
                    )
                pooled = pooled.astype(jnp.float32)
            pending.append((idx, pooled))
            if len(pending) >= flush_every:
                seal_group()
    finally:
        batches.close()
    seal_group()
    while groups:
        drain_group()
    return out


def _prefetched(tokenize, starts, depth):
    """Yield tokenized batches in order, keeping ``depth`` submissions in
    flight on one background thread. Owns the pool: created on first
    iteration, shut down in the generator's ``finally`` — which the caller
    triggers deterministically via ``close()`` on error."""
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=1)
    try:
        # Bounded lookahead: at most `depth` tokenized batches wait in
        # flight, keeping host memory O(depth · batch · seq).
        window = [pool.submit(tokenize, s) for s in starts[:depth]]
        for i, _ in enumerate(starts):
            if i + depth < len(starts):
                window.append(pool.submit(tokenize, starts[i + depth]))
            yield window.pop(0).result()
    finally:
        pool.shutdown(wait=False, cancel_futures=True)


class FullSequenceEmbedderConfig(BaseConfig):
    name: Literal['full_sequence'] = 'full_sequence'
    normalize_embeddings: bool = Field(
        default=False, description='L2-normalize pooled embeddings.'
    )


class FullSequenceEmbedder:
    def __init__(self, config: FullSequenceEmbedderConfig) -> None:
        self.config = config

    def embed(
        self,
        corpus: TextCorpus,
        encoder: Encoder,
        pooler: Pooler,
        batch_size: int,
    ) -> EmbedderResult:
        embeddings = compute_embeddings(
            corpus.texts,
            encoder,
            pooler,
            batch_size,
            normalize=self.config.normalize_embeddings,
        )
        return EmbedderResult(
            embeddings=embeddings, text=corpus.texts, metadata=corpus.metadata
        )
