"""Quantized-scan formulation A/B at production scale, clean-room timing.

Times the PUBLIC topk entry points (outputs fetched, so nothing dead-code
eliminates) with chained async calls and one final sync, one process on
the chip. Variants:

- hamming int32: unpack + int8xint8->int32 dot (current)
- hamming bf16:  unpack to bf16, bf16xbf16->f32 dot (exact for 0/1 bits)
- int8 int32:    int8xint8->int32 chunked scan (current)
- int8 bf16:     codes converted to bf16 in-graph, f32 accumulate
                 (|err| <= ~0.5% relative; the fp32 rescore absorbs it)

Hypothesis under test: XLA TPU emulates integer dots (the 10M ubinary
scan took seconds in a 2026-07-31 record on older code, not the ~50 ms
its byte traffic predicts; not re-measured); bf16
keeps the scan on the native MXU path.
"""

from __future__ import annotations

import os
import pathlib as _pl
import sys as _sys
import time

_sys.path.insert(0, str(_pl.Path(__file__).resolve().parent.parent))


import functools

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.ops import topk as topk_mod
from distllm_tpu.ops.topk import (
    _chunk_candidates,
    _unpack_bits,
    hamming_topk,
    int8_topk,
    pack_sign_bits,
    quantize_int8_rows,
)

CHUNK_GEN = 1 << 18


def hamming_topk_bf16(query_bits, corpus_bits, k, chunk_size=1 << 18):
    """bf16-dot formulation of the Hamming scan (candidate A/B twin)."""
    n = corpus_bits.shape[0]
    k = min(k, n)
    approx = n >= topk_mod.APPROX_TOPK_MIN_ROWS
    qu = _unpack_bits(query_bits).astype(jnp.bfloat16)
    q_pop = jnp.sum(qu.astype(jnp.float32), axis=1)

    @functools.partial(jax.jit, static_argnums=(3,))
    def chunk_distances(q_unpacked, q_popcount, corpus_chunk, chunk_k):
        cu = _unpack_bits(corpus_chunk).astype(jnp.bfloat16)
        dots = jax.lax.dot_general(
            q_unpacked, cu, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        c_pop = jnp.sum(cu.astype(jnp.float32), axis=1)
        distances = q_popcount[:, None] + c_pop[None, :] - 2.0 * dots
        return _chunk_candidates(-distances, chunk_k, approx)

    best_neg = best_idx = None
    for start in range(0, n, chunk_size):
        chunk = corpus_bits[start : start + chunk_size]
        neg, idx = chunk_distances(qu, q_pop, chunk, min(k, chunk.shape[0]))
        idx = idx + start
        if best_neg is None:
            best_neg, best_idx = neg, idx
        else:
            cat_n = jnp.concatenate([best_neg, neg], axis=1)
            cat_i = jnp.concatenate([best_idx, idx], axis=1)
            best_neg, pos = jax.lax.top_k(cat_n, k)
            best_idx = jnp.take_along_axis(cat_i, pos, axis=1)
    return (-best_neg).astype(jnp.int32), best_idx


def int8_topk_bf16(queries, codes, scales, k, chunk_size=1 << 19):
    """bf16-scored int8 scan (codes convert to bf16 in-graph)."""
    n = codes.shape[0]
    k = min(k, n)
    approx = n >= topk_mod.APPROX_TOPK_MIN_ROWS
    qf = queries.astype(jnp.bfloat16)

    @functools.partial(jax.jit, static_argnums=(3,))
    def chunk_topk(q, codes_part, scales_part, chunk_k):
        raw = jax.lax.dot_general(
            q, codes_part.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return _chunk_candidates(raw * scales_part[None, :], chunk_k, approx)

    best_s = best_i = None
    for start in range(0, n, chunk_size):
        cp = codes[start : start + chunk_size]
        sp = scales[start : start + chunk_size]
        s, i = chunk_topk(qf, cp, sp, min(k, cp.shape[0]))
        i = i + start
        if best_s is None:
            best_s, best_i = s, i
        else:
            cat_s = jnp.concatenate([best_s, s], axis=1)
            cat_i = jnp.concatenate([best_i, i], axis=1)
            best_s, pos = jax.lax.top_k(cat_s, k)
            best_i = jnp.take_along_axis(cat_i, pos, axis=1)
    return best_s, best_i


def hamming_topk_tall(query_bits, corpus_bits, k, chunk_size=1 << 18):
    """Swapped-orientation bf16 scan: corpus is the tall LHS (M=C rows,
    N=32 queries), so each chunk streams through the MXU in its natural
    row-major layout instead of being transposed as an [N, K] RHS."""
    n = corpus_bits.shape[0]
    k = min(k, n)
    approx = n >= topk_mod.APPROX_TOPK_MIN_ROWS
    qu = _unpack_bits(query_bits).astype(jnp.bfloat16)
    q_pop = jnp.sum(qu.astype(jnp.float32), axis=1)

    @functools.partial(jax.jit, static_argnums=(3,))
    def chunk_distances(q_unpacked, q_popcount, corpus_chunk, chunk_k):
        cu = _unpack_bits(corpus_chunk).astype(jnp.bfloat16)
        dots = jax.lax.dot_general(
            cu, q_unpacked, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [C, B]
        c_pop = jnp.sum(cu.astype(jnp.float32), axis=1)
        distances = (
            q_popcount[None, :] + c_pop[:, None] - 2.0 * dots
        ).T  # [B, C]
        return _chunk_candidates(-distances, chunk_k, approx)

    best_neg = best_idx = None
    for start in range(0, n, chunk_size):
        chunk = corpus_bits[start : start + chunk_size]
        neg, idx = chunk_distances(qu, q_pop, chunk, min(k, chunk.shape[0]))
        idx = idx + start
        if best_neg is None:
            best_neg, best_idx = neg, idx
        else:
            cat_n = jnp.concatenate([best_neg, neg], axis=1)
            cat_i = jnp.concatenate([best_idx, idx], axis=1)
            best_neg, pos = jax.lax.top_k(cat_n, k)
            best_idx = jnp.take_along_axis(cat_i, pos, axis=1)
    return (-best_neg).astype(jnp.int32), best_idx


def int8_topk_tall(queries, codes, scales, k, chunk_size=1 << 19):
    """Swapped-orientation bf16-scored int8 scan (codes as tall LHS)."""
    n = codes.shape[0]
    k = min(k, n)
    approx = n >= topk_mod.APPROX_TOPK_MIN_ROWS
    qf = queries.astype(jnp.bfloat16)

    @functools.partial(jax.jit, static_argnums=(3,))
    def chunk_topk(q, codes_part, scales_part, chunk_k):
        raw = jax.lax.dot_general(
            codes_part.astype(jnp.bfloat16), q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [C, B]
        scores = (raw * scales_part[:, None]).T  # [B, C]
        return _chunk_candidates(scores, chunk_k, approx)

    best_s = best_i = None
    for start in range(0, n, chunk_size):
        cp = codes[start : start + chunk_size]
        sp = scales[start : start + chunk_size]
        s, i = chunk_topk(qf, cp, sp, min(k, cp.shape[0]))
        i = i + start
        if best_s is None:
            best_s, best_i = s, i
        else:
            cat_s = jnp.concatenate([best_s, s], axis=1)
            cat_i = jnp.concatenate([best_i, i], axis=1)
            best_s, pos = jax.lax.top_k(cat_s, k)
            best_i = jnp.take_along_axis(cat_i, pos, axis=1)
    return best_s, best_i


def timed_chain(fn, reps=4):
    outs = fn()  # compile + settle
    np.asarray(outs[1]).ravel()[:1]
    t0 = time.perf_counter()
    all_outs = [fn() for _ in range(reps)]
    for o in all_outs:
        np.asarray(o[1]).ravel()[:1]
    return (time.perf_counter() - t0) / reps


def main() -> None:
    small = bool(os.environ.get('DISTLLM_BENCH_SMALL'))
    rows = (1 << 20) if small else 10_000_000
    dim = 768
    k = 40
    rng = np.random.default_rng(0)
    print(f'rows={rows} dim={dim} k={k}', flush=True)

    packed_parts, code_parts, scale_parts = [], [], []
    queries = None
    for lo in range(0, rows, CHUNK_GEN):
        n = min(CHUNK_GEN, rows - lo)
        chunk = rng.standard_normal((n, dim)).astype(np.float32)
        chunk /= np.linalg.norm(chunk, axis=1, keepdims=True)
        if queries is None:
            queries = chunk[:32] + 0.5 * rng.standard_normal(
                (32, dim)
            ).astype(np.float32) / np.sqrt(dim)
        packed_parts.append(pack_sign_bits(chunk))
        c, s = quantize_int8_rows(chunk)
        code_parts.append(c)
        scale_parts.append(s)
    packed = jax.device_put(np.concatenate(packed_parts))
    packed_parts.clear()
    q_bits = jnp.asarray(pack_sign_bits(queries))
    q_dev = jnp.asarray(queries)

    t = timed_chain(lambda: hamming_topk(q_bits, packed, k))
    print(f'hamming int32-dot: {t * 1e3:8.1f} ms/scan', flush=True)
    t = timed_chain(lambda: hamming_topk_bf16(q_bits, packed, k))
    print(f'hamming bf16-dot : {t * 1e3:8.1f} ms/scan', flush=True)
    t = timed_chain(lambda: hamming_topk_tall(q_bits, packed, k))
    print(f'hamming bf16-tall: {t * 1e3:8.1f} ms/scan', flush=True)
    del packed

    codes = jax.device_put(np.concatenate(code_parts))
    scales = jax.device_put(np.concatenate(scale_parts))
    code_parts.clear()
    scale_parts.clear()
    t = timed_chain(lambda: int8_topk(q_dev, codes, scales, k))
    print(f'int8 int32-dot   : {t * 1e3:8.1f} ms/scan', flush=True)
    sa, ia = int8_topk(q_dev, codes, scales, k)
    t = timed_chain(lambda: int8_topk_bf16(q_dev, codes, scales, k))
    print(f'int8 bf16-dot    : {t * 1e3:8.1f} ms/scan', flush=True)
    t = timed_chain(lambda: int8_topk_tall(q_dev, codes, scales, k))
    print(f'int8 bf16-tall   : {t * 1e3:8.1f} ms/scan', flush=True)
    sb, ib = int8_topk_bf16(q_dev, codes, scales, k)
    overlap = np.mean([
        len(set(map(int, np.asarray(ia)[b])) &
            set(map(int, np.asarray(ib)[b]))) / k
        for b in range(32)
    ])
    print(f'int8 bf16 vs int32 candidate overlap@{k}: {overlap:.3f}',
          flush=True)


if __name__ == '__main__':
    main()
