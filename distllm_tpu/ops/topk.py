"""Exact inner-product top-k over device-sharded corpora.

The FAISS replacement's compute core (SURVEY.md section 2.4 N2): embeddings
live row-sharded across chips (mesh ``data`` axis); each chip computes its
shard's ``Q @ E_shard^T`` on the MXU and a local ``lax.top_k``; the per-shard
candidates (k per chip) are concatenated — a tiny ICI all-gather instead of
gathering the full ``[B, N]`` score matrix — and reduced with one final
``top_k``. Also hosts the binary (Hamming) scoring path used by ubinary
quantized indexes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def topk_inner_product(
    queries: jnp.ndarray,  # [B, H] fp32
    corpus: jnp.ndarray,  # [N, H] (possibly sharded over mesh 'data')
    k: int,
    mesh: Mesh | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k by inner product. Returns (scores [B, k], indices [B, k])."""
    k = min(k, corpus.shape[0])
    if mesh is None or mesh.shape.get('data', 1) == 1:
        scores = queries @ corpus.T
        return jax.lax.top_k(scores, k)
    return _topk_sharded(queries, corpus, k, mesh)


def _sharded_topk(score_fn, row_count, operands, in_specs, k, mesh):
    """Shared multi-chip top-k scaffold: each chip scores its row shard
    (``score_fn(replicated..., sharded...) -> [B, rows/shard]``), takes a
    local top-k, offsets indices by its shard start, and the k-per-chip
    candidates are concatenated (tiny ICI all-gather vs the full [B, N]
    score matrix) and reduced with one final ``top_k``. Both the exact
    fp32 and the int8 tiers route here so the offset/merge math has one
    home."""
    n_shards = mesh.shape['data']
    shard_rows = row_count // n_shards

    def per_shard(*args):
        scores = score_fn(*args)
        local_k = min(k, scores.shape[1])
        s, i = jax.lax.top_k(scores, local_k)
        offset = jax.lax.axis_index('data') * shard_rows
        return s, i + offset

    sharded = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(None, 'data'), P(None, 'data')),
    )
    cand_scores, cand_idx = sharded(*operands)  # [B, k*shards]
    merged_scores, merged_pos = jax.lax.top_k(cand_scores, k)
    merged_idx = jnp.take_along_axis(cand_idx, merged_pos, axis=1)
    return merged_scores, merged_idx


def _topk_sharded(queries, corpus, k, mesh):
    def score(q, e_shard):
        return q @ e_shard.T  # [B, n/shards] on-chip MXU matmul

    return _sharded_topk(
        score, corpus.shape[0], (queries, corpus),
        (P(), P('data', None)), k, mesh,
    )


def quantize_int8_rows(
    embeddings: np.ndarray,
) -> tuple[np.ndarray, np.ndarray]:
    """fp32 ``[N, H]`` → (``int8`` codes ``[N, H]``, fp32 scales ``[N]``).

    Symmetric per-row absmax quantization (sentence-transformers' int8
    precision semantics). 4x smaller than fp32 — the single-chip middle
    tier between exact fp32 (~4M x 768 rows in 16 GiB HBM) and ubinary
    (32x smaller, Hamming-approximate): scores stay MXU matmuls (int8
    inputs, int32 accumulate) and ranking error is ~1e-2 relative, which
    the oversampled fp32 rescore absorbs.
    """
    absmax = np.abs(embeddings).max(axis=1)
    scales = np.where(absmax == 0, 1.0, absmax / 127.0).astype(np.float32)
    codes = np.clip(
        np.round(embeddings / scales[:, None]), -127, 127
    ).astype(np.int8)
    return codes, scales


def int8_topk(
    queries: jnp.ndarray,  # [B, H] fp32
    codes: jnp.ndarray,  # [N, H] int8, or grouped [G, C, H] (group_rows)
    scales: jnp.ndarray,  # [N] fp32 ([G, C] when grouped)
    k: int,
    mesh: Mesh | None = None,
    chunk_size: int = 1 << 19,
    n_valid: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k inner product against an int8-quantized corpus.

    Queries are quantized per-row on the fly so the score matmul runs
    int8 x int8 → int32 on the MXU; the true scale is reapplied before
    ``top_k``. The single-device path processes the corpus axis in
    ``chunk_size`` slabs with a running top-k, so peak memory is
    ``O(B * chunk_size)`` rather than ``[B, N]`` — this tier exists for
    corpora past the fp32 HBM limit, where a full score matrix at batch
    128 would itself OOM. Returns (approx scores [B, k], indices [B, k]).

    Pass ``codes`` pre-grouped as ``[G, C, H]`` (:func:`group_rows`, with
    ``scales [G, C]`` and ``n_valid`` = real row count) for the fast
    single-dispatch ``lax.scan`` path — what ``TpuIndexV2`` serves with.
    """
    if codes.ndim == 3:
        if n_valid is None:
            # group_rows zero-pads the last slab; without the real row
            # count those all-zero rows would rank as valid neighbors and
            # leak out-of-range indices to the caller.
            raise ValueError('grouped codes [G, C, H] require n_valid')
        if mesh is not None and mesh.shape.get('data', 1) > 1:
            # The grouped scan is a single-device serving layout; silently
            # ignoring the mesh would score the FULL corpus on every chip
            # and return duplicate candidates. Mirror the n_valid guard.
            raise ValueError(
                'grouped codes [G, C, H] cannot combine with a data-sharded '
                'mesh; pass flat [N, H] codes for the sharded path'
            )
        n = n_valid
        k = min(k, n)
        qmax = jnp.abs(queries).max(axis=1)
        qscale = jnp.where(qmax == 0, 1.0, qmax / 127.0)
        qi = jnp.clip(
            jnp.round(queries / qscale[:, None]), -127, 127
        ).astype(jnp.int8)
        return _grouped_scan_topk(
            (qi, qscale), codes, (scales,),
            scorer='int8', k=k,
            n_valid=n, approx=n >= APPROX_TOPK_MIN_ROWS,
        )
    n = codes.shape[0]
    k = min(k, n)
    qmax = jnp.abs(queries).max(axis=1)
    qscale = jnp.where(qmax == 0, 1.0, qmax / 127.0)
    qi = jnp.clip(
        jnp.round(queries / qscale[:, None]), -127, 127
    ).astype(jnp.int8)

    if mesh is not None and mesh.shape.get('data', 1) > 1:
        # Per-shard rows are already N/shards; each chip scores its slab
        # in one matmul (shard the corpus further if [B, N/shards] scores
        # ever dominate a chip's HBM).
        return _sharded_topk(
            _score_int8, n, (qi, qscale, codes, scales),
            (P(), P(), P('data', None), P('data')), k, mesh,
        )

    # Chunk-local candidate selection: exact below APPROX_TOPK_MIN_ROWS
    # total rows, TPU approx_max_k above (this tier rescored in fp32
    # anyway; exact sort over large chunks dominated the 10M scan).
    approx = n >= APPROX_TOPK_MIN_ROWS

    @functools.partial(jax.jit, static_argnums=(4,))
    def chunk_topk(q_codes, q_scale, codes_part, scales_part, chunk_k):
        return _chunk_candidates(
            _score_int8(q_codes, q_scale, codes_part, scales_part),
            chunk_k,
            approx,
        )

    best_scores = None
    best_idx = None
    for start in range(0, n, chunk_size):
        codes_part = codes[start : start + chunk_size]
        scales_part = scales[start : start + chunk_size]
        chunk_k = min(k, codes_part.shape[0])
        s, i = chunk_topk(qi, qscale, codes_part, scales_part, chunk_k)
        i = i + start
        if best_scores is None:
            best_scores, best_idx = s, i
        else:
            cat_s = jnp.concatenate([best_scores, s], axis=1)
            cat_i = jnp.concatenate([best_idx, i], axis=1)
            best_scores, pos = jax.lax.top_k(cat_s, k)
            best_idx = jnp.take_along_axis(cat_i, pos, axis=1)
    return best_scores, best_idx


def pack_sign_bits(embeddings: np.ndarray) -> np.ndarray:
    """fp32 ``[N, H]`` → uint8 ``[N, H/8]`` sign-bit packing (ubinary).

    Matches sentence-transformers' ``quantize_embeddings(..., 'ubinary')``:
    bit = 1 where value > 0, packed big-endian within each byte.
    """
    if embeddings.shape[1] % 8 != 0:
        raise ValueError(f'embedding dim {embeddings.shape[1]} not divisible by 8')
    bits = (embeddings > 0).astype(np.uint8)
    return np.packbits(bits, axis=1)


# Corpora past this row count switch the per-chunk candidate selection
# from exact lax.top_k (a full bitonic sort over the chunk — 12.5 s for
# one 10M-row ubinary scan in a 2026-07-31 record on older code, in git
# history; not re-measured) to the TPU-native jax.lax.approx_max_k (~0.95 per-element recall). Quantized-tier
# candidates feed an oversampled fp32 rescore, so serving quality is set
# by top1/rescore behavior, not the last near-tie in the candidate set.
APPROX_TOPK_MIN_ROWS = 1 << 20

# Grouped-scan slab sizes (rows per lax.scan step) for the quantized
# tiers — ONE home so the index (rag/search.py) and the retrieval bench
# measure the same serving layout.
SCAN_CHUNK_BITS = 1 << 18
SCAN_CHUNK_INT8 = 1 << 19


def group_rows(arr: np.ndarray, chunk: int) -> np.ndarray:
    """Host-side: pad ``[N, ...]`` to a chunk multiple and reshape to
    ``[G, chunk, ...]`` — the layout the grouped-scan tops consume.

    Do this ONCE at index build: the grouped tensors ride a single-
    dispatch ``lax.scan`` whose chunk slabs are contiguous scan slices.
    A 2026-07-31 record on older code (in git history; not re-measured)
    has 32 ms/scan grouped at 10M x 768 int8 against seconds for the
    python slice-per-chunk loop over a monolithic device array.
    """
    n = arr.shape[0]
    pad = (-n) % chunk
    if pad:
        arr = np.concatenate(
            [arr, np.zeros((pad, *arr.shape[1:]), arr.dtype)]
        )
    return arr.reshape(arr.shape[0] // chunk, chunk, *arr.shape[1:])


def _score_int8(qi, qscale, codes_part, scales_part):
    """int8 x int8 → int32 MXU scores with the true scales reapplied —
    the ONE home for the int8 scoring formula (flat loop, grouped scan,
    and the sharded path all call this)."""
    raw = jax.lax.dot_general(
        qi, codes_part, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return raw.astype(jnp.float32) * qscale[:, None] * scales_part[None, :]


def _score_hamming(qu, q_pop, chunk_bits):
    """Negated Hamming distances via the MXU identity
    ``hamming(a,b) = |a| + |b| - 2 a·b`` over unpacked 0/1 int8 vectors
    (higher = closer, so top-k machinery applies unchanged)."""
    cu = _unpack_bits(chunk_bits)
    dots = jax.lax.dot_general(
        qu, cu, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    c_pop = jnp.sum(cu.astype(jnp.int32), axis=1)
    distances = q_pop[:, None] + c_pop[None, :] - 2 * dots
    return -distances.astype(jnp.float32)


def _score_grouped_chunk(scorer: str, queries, chunk, extras):
    """Per-chunk fp32 scores [B, C] for the grouped-scan tops."""
    if scorer == 'int8':
        qi, qscale = queries
        (scales_c,) = extras
        return _score_int8(qi, qscale, chunk, scales_c)
    if scorer == 'hamming':
        qu, q_pop = queries
        return _score_hamming(qu, q_pop, chunk)
    raise ValueError(scorer)


@functools.partial(
    jax.jit, static_argnames=('scorer', 'k', 'n_valid', 'approx')
)
def _grouped_scan_topk(
    queries, corpus3, extras, *, scorer, k, n_valid, approx
):
    """Single-dispatch top-k over a grouped corpus ``[G, C, ...]``.

    Padded rows (global index >= n_valid) mask to -inf before candidate
    selection; per-chunk candidates merge once at the end (G*chunk_k is
    tiny). One executable per (scorer, shapes) — the scan runs all G
    chunks inside a single dispatch, which is what makes the 10M scan
    ~32 ms instead of seconds of per-chunk dispatch/slice overhead.
    """
    c = corpus3.shape[1]
    chunk_k = min(k, c)

    def body(g, xs):
        scores = _score_grouped_chunk(scorer, queries, xs[0], xs[1:])
        base = g * c
        col = base + jnp.arange(c)[None, :]
        scores = jnp.where(col < n_valid, scores, -jnp.inf)
        s, i = _chunk_candidates(scores, chunk_k, approx)
        return g + 1, (s, i + base)

    _, (ss, ii) = jax.lax.scan(body, 0, (corpus3, *extras))
    b = ss.shape[1]
    flat_s = jnp.transpose(ss, (1, 0, 2)).reshape(b, -1)
    flat_i = jnp.transpose(ii, (1, 0, 2)).reshape(b, -1)
    # Final exact merge returns the CALLER'S k (bounded by what exists),
    # not the per-chunk k — k > chunk size must not truncate silently.
    top_s, pos = jax.lax.top_k(flat_s, min(k, flat_s.shape[1]))
    return top_s, jnp.take_along_axis(flat_i, pos, axis=1)


def _chunk_candidates(scores_f32: jnp.ndarray, k: int, approx: bool):
    if approx:
        return jax.lax.approx_max_k(scores_f32, k)
    return jax.lax.top_k(scores_f32, k)


def _unpack_bits(packed: jnp.ndarray) -> jnp.ndarray:
    """uint8 ``[..., H/8]`` → 0/1 int8 ``[..., H]`` (big-endian, matching
    :func:`pack_sign_bits` / np.packbits)."""
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = (packed[..., :, None] >> shifts) & jnp.uint8(1)
    return bits.astype(jnp.int8).reshape(*packed.shape[:-1], -1)


def hamming_topk(
    query_bits: jnp.ndarray,  # [B, H/8] uint8
    corpus_bits: jnp.ndarray,  # [N, H/8] uint8, or grouped [G, C, H/8]
    k: int,
    chunk_size: int = 1 << 18,
    n_valid: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k by smallest Hamming distance. Returns (distances, indices).

    Scoring is an MXU matmul, not a VPU popcount sweep:
    ``hamming(a, b) = |a| + |b| - 2 a·b`` over the unpacked 0/1 vectors,
    so each chunk unpacks to int8 in VMEM-sized slabs and scores as an
    int8 x int8 → int32 dot. (The first implementation XOR+popcounted a
    materialized [B, chunk, H/8] tensor and exact-sorted every chunk:
    12.5 s per 10M-row scan on the chip.) Distances are exact ints;
    candidate selection per chunk is exact below ``APPROX_TOPK_MIN_ROWS``
    rows and TPU ``approx_max_k`` above. The corpus axis is processed in
    chunks with a running top-k so peak memory is ``O(B * chunk_size)``.

    Pass ``corpus_bits`` pre-grouped as ``[G, C, H/8]``
    (:func:`group_rows`, with ``n_valid`` = real row count) for the
    single-dispatch ``lax.scan`` path serving uses.
    """
    if corpus_bits.ndim == 3:
        if n_valid is None:
            raise ValueError('grouped corpus [G, C, H/8] requires n_valid')
        n = n_valid
        k = min(k, n)
        qu3 = _unpack_bits(query_bits)
        q_pop3 = jnp.sum(qu3.astype(jnp.int32), axis=1)
        neg, idx = _grouped_scan_topk(
            (qu3, q_pop3), corpus_bits, (),
            scorer='hamming', k=k,
            n_valid=n, approx=n >= APPROX_TOPK_MIN_ROWS,
        )
        # approx_max_k's bin maxima can surface -inf-masked padded rows as
        # candidates when a chunk has fewer valid rows than bins; casting
        # -(-inf) to int32 is UB in XLA. Clamp those candidates to a finite
        # max-distance sentinel so callers see an unambiguous "no neighbor"
        # distance (true distances are <= H) instead of garbage. The
        # sentinel must be fp32-REPRESENTABLE below 2**31: -(2**31 - 1)
        # rounds to -2**31 in fp32 and its negation overflows the very
        # int32 cast this guards; 2**31 - 128 is the largest fp32 value
        # strictly under INT32_MAX.
        neg = jnp.maximum(neg, jnp.float32(-2147483520.0))
        return (-neg).astype(jnp.int32), idx
    n = corpus_bits.shape[0]
    k = min(k, n)
    approx = n >= APPROX_TOPK_MIN_ROWS
    qu = _unpack_bits(query_bits)  # [B, H] int8
    q_pop = jnp.sum(qu.astype(jnp.int32), axis=1)  # [B]

    @functools.partial(jax.jit, static_argnums=(3,))
    def chunk_distances(q_unpacked, q_popcount, corpus_chunk, chunk_k):
        return _chunk_candidates(
            _score_hamming(q_unpacked, q_popcount, corpus_chunk),
            chunk_k,
            approx,
        )

    best_neg = None
    best_idx = None
    for start in range(0, n, chunk_size):
        chunk = corpus_bits[start : start + chunk_size]
        chunk_k = min(k, chunk.shape[0])
        neg, idx = chunk_distances(qu, q_pop, chunk, chunk_k)
        idx = idx + start
        if best_neg is None:
            best_neg, best_idx = neg, idx
        else:
            cat_neg = jnp.concatenate([best_neg, neg], axis=1)
            cat_idx = jnp.concatenate([best_idx, idx], axis=1)
            best_neg, pos = jax.lax.top_k(cat_neg, k)
            best_idx = jnp.take_along_axis(cat_idx, pos, axis=1)
    return (-best_neg).astype(jnp.int32), best_idx
