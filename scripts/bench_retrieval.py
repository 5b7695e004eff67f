"""Retrieval at production scale: exact vs ubinary tiers, with recall.

Justifies (or refutes) TpuIndexV2's exact-only design at the reference's
production sizes (tens of millions of chunk embeddings — ref
``examples/scaling/polaris/.../nodes256.yaml`` embeds lit-scale corpora;
``FaissIndexV2`` offers HNSW for that regime, ``distllm/rag/search.py:229-250``).

Measures, on whatever backend JAX resolves (CPU host or the TPU chip):

1. **Exact fp32 tier** (``ops/topk.topk_inner_product``): query latency at
   1M/2M/4M x 768. A 16 GiB v5e holds ~4-5M x 768 fp32 rows on-chip; past
   that the corpus must shard over a mesh (``data`` axis) or drop to the
   binary tier — this prints the HBM budget alongside the latency.
2. **ubinary tier** (``ops/topk.hamming_topk`` + fp32 rescore): packed
   sign-bits are corpus/32 bytes (10M x 768 = 960 MB — fits ONE chip to
   ~100M rows), with sentence-transformers-style oversampled rescore.
3. **Recall@k of the ubinary tier vs exact ground truth** on the same 10M
   corpus — hardware-independent quality evidence (ground truth via
   chunked host matmul).

Prints one JSON line per measurement. No faiss/hnswlib exists in this
environment for a CPU-graph comparison; the exact numbers and the recall
table are the decision evidence (docs/retrieval_at_scale.md).
"""

from __future__ import annotations

import argparse
import json
import time


import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distllm_tpu.ops.topk import (  # noqa: E402
    SCAN_CHUNK_BITS,
    SCAN_CHUNK_INT8,
    group_rows,
    hamming_topk,
    int8_topk,
    pack_sign_bits,
    quantize_int8_rows,
    topk_inner_product,
)

CHUNK = 1 << 18  # corpus generation/ground-truth chunk (256k rows)


def _emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _gen_chunk(rng: np.random.Generator, rows: int, dim: int) -> np.ndarray:
    x = rng.standard_normal((rows, dim), dtype=np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x


def _planted_queries(corpus_rows: np.ndarray, n: int, dim: int,
                     noise_norm: float) -> np.ndarray:
    """Queries = noisy copies of corpus rows: gives the corpus real
    nearest-neighbor structure (pure-random vectors have none, which makes
    any recall number a meaningless floor). ``noise_norm`` is the expected
    L2 norm of the added noise relative to the unit source vector:
    0.5 puts the true neighbor's IP around 1/sqrt(1.25) ~ 0.89 — a
    realistic hard retrieval regime."""
    rng = np.random.default_rng(3)
    src = corpus_rows[rng.integers(0, len(corpus_rows), size=n)]
    sigma = noise_norm / np.sqrt(dim)
    q = src + sigma * rng.standard_normal((n, dim), dtype=np.float32)
    return q / np.linalg.norm(q, axis=1, keepdims=True)


def _sync(x) -> None:
    jax.block_until_ready(x)


def _device_corpus(n: int, dim: int, seed: int) -> tuple:
    """Stream a [n, dim] corpus straight into a device buffer chunk-wise
    (donated dynamic-update-slice, same pattern as TpuIndexV2's single-
    device load): host RSS stays O(CHUNK), device peak O(n) — the array
    being measured. Returns (corpus, first_rows) with the first rows kept
    on host for query planting."""
    update = jax.jit(
        lambda buf, part, lo: jax.lax.dynamic_update_slice(buf, part, (lo, 0)),
        donate_argnums=0,
    )
    rng = np.random.default_rng(seed)
    buf = jnp.zeros((n, dim), jnp.float32)
    first_rows = None
    for lo in range(0, n, CHUNK):
        chunk = _gen_chunk(rng, min(CHUNK, n - lo), dim)
        if first_rows is None:
            first_rows = chunk[:4096].copy()
        buf = update(buf, chunk, lo)
    return buf, first_rows


def bench_exact(n_queries: int, sizes: list[int], dim: int, top_k: int,
                trials: int) -> None:
    for n in sizes:
        corpus_bytes = n * dim * 4
        # Per-size rebuild keeps device peak at O(n), not O(max + n).
        corpus, first_rows = _device_corpus(n, dim, seed=1)
        q = jnp.asarray(
            _planted_queries(first_rows, n_queries, dim, noise_norm=0.5)
        )
        _sync(corpus)
        # warmup compile
        s, i = topk_inner_product(q, corpus, top_k)
        _sync((s, i))
        times = []
        for _ in range(trials):
            t0 = time.perf_counter()
            s, i = topk_inner_product(q, corpus, top_k)
            _sync((s, i))
            times.append(time.perf_counter() - t0)
        best = min(times)
        # Chain 8 async dispatches with ONE final sync so the host's
        # dispatch-and-sync time amortizes and the per-call number
        # approaches the device time (same method as probe_decode).
        reps = 8
        t0 = time.perf_counter()
        outs = [topk_inner_product(q, corpus, top_k) for _ in range(reps)]
        _sync(outs[-1])
        chained = (time.perf_counter() - t0) / reps
        _emit(
            tier='exact_fp32', rows=n, dim=dim, batch=n_queries,
            top_k=top_k, latency_ms=round(best * 1e3, 1),
            latency_chained_ms=round(chained * 1e3, 1),
            queries_per_s=round(n_queries / chained, 1),
            corpus_gib=round(corpus_bytes / 2**30, 2),
            platform=jax.default_backend(),
        )
        del corpus


def bench_ubinary(rows: int, dim: int, n_queries: int, top_k: int,
                  rescore_multiplier: int, trials: int,
                  scratch: str) -> None:
    """Build packed bits + exact ground truth chunk-wise (host RSS stays
    O(chunk) + O(packed)); the fp32 corpus goes to a disk memmap — the
    faithful stand-in for the production index's arrow-mmap'd dataset,
    which is where rescore candidates are gathered from. Then time
    hamming + gather + rescore, and score recall vs the ground truth."""
    import os

    rng = np.random.default_rng(2)
    # Queries planted from the first chunk's rows (the chunk loop below
    # re-generates the same stream from the same seed).
    first = _gen_chunk(np.random.default_rng(2), min(CHUNK, rows), dim)
    queries = _planted_queries(first, n_queries, dim, noise_norm=0.5)
    del first

    mmap_path = os.path.join(scratch, f'bench_retrieval_{rows}x{dim}.f32')
    corpus_mm = np.lib.format.open_memmap(
        mmap_path, mode='w+', dtype=np.float32, shape=(rows, dim)
    )
    packed_parts = []
    gt_scores = None  # running exact top-k for ground truth
    gt_idx = None
    t_build = time.perf_counter()
    for lo in range(0, rows, CHUNK):
        n = min(CHUNK, rows - lo)
        chunk = _gen_chunk(rng, n, dim)
        corpus_mm[lo:lo + n] = chunk
        packed_parts.append(pack_sign_bits(chunk))
        scores = queries @ chunk.T  # [B, n] exact ground truth
        k = min(top_k, n)
        part = np.argpartition(-scores, k - 1, axis=1)[:, :k]
        part_scores = np.take_along_axis(scores, part, axis=1)
        part_idx = part + lo
        if gt_scores is None:
            gt_scores, gt_idx = part_scores, part_idx
        else:
            cat_s = np.concatenate([gt_scores, part_scores], axis=1)
            cat_i = np.concatenate([gt_idx, part_idx], axis=1)
            keep = np.argpartition(-cat_s, top_k - 1, axis=1)[:, :top_k]
            gt_scores = np.take_along_axis(cat_s, keep, axis=1)
            gt_idx = np.take_along_axis(cat_i, keep, axis=1)
        del chunk
    corpus_mm.flush()
    packed = np.concatenate(packed_parts)
    packed_parts.clear()
    build_secs = time.perf_counter() - t_build
    _emit(tier='ubinary_build', rows=rows, dim=dim,
          packed_gib=round(packed.nbytes / 2**30, 3),
          build_secs=round(build_secs, 1))

    oversample = top_k * rescore_multiplier
    # The exact nearest neighbor per query (= the planted source): the
    # meaningful quality target. The other top-k ground-truth rows of a
    # synthetic corpus are random near-ties no quantizer can rank, so the
    # overlap recall@k is reported but top1_hit is the headline.
    gt_top1 = np.take_along_axis(
        gt_idx, np.argmax(gt_scores, axis=1, keepdims=True), axis=1
    )[:, 0]

    def measure(tier: str, cand_fn, extra: dict) -> None:
        cand = cand_fn()  # warmup compile
        _sync(cand)
        times, scan_times = [], []
        recall = top1_hit = None
        for _ in range(trials):
            t0 = time.perf_counter()
            cand = np.asarray(cand_fn())
            t1 = time.perf_counter()
            # Gather candidates from the disk memmap exactly the way the
            # production path gathers from the arrow mmap (sorted access).
            flat = cand.reshape(-1)
            order_back = np.argsort(np.argsort(flat))
            vectors = corpus_mm[np.sort(flat)][order_back]
            vectors = vectors.reshape(*cand.shape, dim)
            rescored = np.einsum('bh,boh->bo', queries, vectors)
            order = np.argsort(-rescored, axis=1)[:, :top_k]
            got_idx = np.take_along_axis(cand, order, axis=1)
            times.append(time.perf_counter() - t0)
            scan_times.append(t1 - t0)
            hits = sum(
                len(set(map(int, got_idx[b])) & set(map(int, gt_idx[b])))
                for b in range(len(queries))
            )
            recall = hits / (len(queries) * top_k)
            top1_hit = float(
                np.mean(
                    [gt_top1[b] in got_idx[b] for b in range(len(queries))]
                )
            )
        best = min(times)
        _emit(
            tier=tier, rows=rows, dim=dim, batch=n_queries,
            top_k=top_k, oversample=oversample,
            latency_ms=round(best * 1e3, 1),
            scan_ms=round(min(scan_times) * 1e3, 1),
            queries_per_s=round(n_queries / best, 1),
            recall_at_k=round(recall, 4),
            top1_hit=round(top1_hit, 4),
            platform=jax.default_backend(),
            **extra,
        )

    try:
        # Grouped [G, C, ...] layout (ops/topk.group_rows): the serving
        # layout — hamming/int8 scans run as ONE lax.scan dispatch.
        corpus_bits = jax.device_put(group_rows(packed, SCAN_CHUNK_BITS))
        query_bits = jnp.asarray(pack_sign_bits(queries))
        measure(
            'ubinary_rescore',
            lambda: hamming_topk(
                query_bits, corpus_bits, oversample, n_valid=rows
            )[1],
            {'packed_gib': round(packed.nbytes / 2**30, 3)},
        )
        del corpus_bits

        # int8 tier: quantize from the memmap AFTER the ubinary phase so
        # codes (~corpus/4 bytes) never coexist with it in host RAM, and
        # its build cost is timed on its own, not inside 'ubinary_build'.
        t_q = time.perf_counter()
        code_host = np.empty((rows, dim), np.int8)
        scale_host = np.empty((rows,), np.float32)
        for lo in range(0, rows, CHUNK):
            hi = min(lo + CHUNK, rows)
            code_host[lo:hi], scale_host[lo:hi] = quantize_int8_rows(
                np.asarray(corpus_mm[lo:hi])
            )
        int8_build_secs = time.perf_counter() - t_q
        codes = jax.device_put(group_rows(code_host, SCAN_CHUNK_INT8))
        scales = jax.device_put(group_rows(scale_host, SCAN_CHUNK_INT8))
        codes_gib = round(code_host.nbytes / 2**30, 3)
        del code_host, scale_host
        queries_dev = jnp.asarray(queries)
        measure(
            'int8_rescore',
            lambda: int8_topk(
                queries_dev, codes, scales, oversample, n_valid=rows
            )[1],
            {'codes_gib': codes_gib,
             'build_secs': round(int8_build_secs, 1)},
        )
    finally:
        del corpus_mm
        os.unlink(mmap_path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--dim', type=int, default=768)
    ap.add_argument('--queries', type=int, default=32)
    ap.add_argument('--topk', type=int, default=10)
    ap.add_argument('--trials', type=int, default=3)
    ap.add_argument('--exact-sizes', type=str, default='1000000,2000000,4000000')
    ap.add_argument('--ubinary-rows', type=int, default=10_000_000)
    ap.add_argument('--rescore-multiplier', type=int, default=4)
    ap.add_argument('--skip-exact', action='store_true')
    ap.add_argument('--skip-ubinary', action='store_true')
    ap.add_argument('--scratch', type=str, default='/tmp')
    args = ap.parse_args()

    if not args.skip_exact:
        sizes = [int(s) for s in args.exact_sizes.split(',') if s]
        bench_exact(args.queries, sizes, args.dim, args.topk, args.trials)
    if not args.skip_ubinary:
        bench_ubinary(args.ubinary_rows, args.dim, args.queries, args.topk,
                      args.rescore_multiplier, args.trials, args.scratch)


if __name__ == '__main__':
    main()
