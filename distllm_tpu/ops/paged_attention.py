"""Paged-KV attention — the core kernel of the generation engine.

The reference delegates this to vLLM's CUDA paged-attention
(``generate/generators/vllm_backend.py``; SURVEY.md section 2.4 N1). Here the
KV cache lives in HBM as fixed-size blocks, stored HEAD-FOLDED, the layout
the Pallas kernel reads (each KV head a 128-aligned lane band of a token's
row, or two 64-wide heads a band: :func:`_half_tile_heads`)::

    k_cache, v_cache : [num_blocks, block_size, num_kv_heads * head_dim]

or every layer's at once, a STACKED pool ``[L, num_blocks, ...]`` that
goes to the writers, the XLA twins and the kernel whole with ``layer=``:
a stacked pool is addressed, never sliced (:func:`_layer_pages`; the
operand's rank decides).

No function here reshapes a cache's rows: under the TPU's tiled layout a
reshape of the two minor dims is a copy of the whole buffer, not a bitcast
(a merge of the two LEADING dims, the stacked pool's flat view, is one).
The writers fold the NEW rows (``[.., num_kv_heads, head_dim]`` activations), the XLA
twins unfold the pages they GATHERED, and ``num_kv_heads`` is the folded
width over the queries' ``head_dim``.

Each sequence owns a row of ``block_tables`` (block ids, padded) plus a
``context_lens`` entry (valid tokens). Every serving dispatch — decode
windows, mixed prefill+decode, chunked/prefix-cache tail prefill, and
speculative verification — funnels through the RAGGED per-row-query-span
formulation, which has two implementations behind one backend selector
(:func:`ragged_paged_attention`):

- :func:`ragged_paged_attention_xla` — gather + masked softmax; XLA fuses
  this well and it is the portable, always-available baseline (also runs on
  CPU for tests) and the bit-exactness reference.
- :func:`ragged_paged_attention_pallas` — fused Pallas TPU kernel: block
  tables are scalar-prefetched and only a row's live KV pages are DMA'd
  HBM→VMEM, double-buffered (the next chunk in flight while one is
  computed), online-softmax accumulation in fp32 scratch — no ``[.., S,
  T]`` score tensor is ever materialized. A span over one runs a grid over
  (row, query tile, KV chunk) that skips the chunks a tile cannot see; a
  span of one (decode rows) WALKS the chunks each row holds under a grid
  over rows (:func:`_walk_row`): no step exists that fetches nothing.

A LATENT pool (``models.common.PagedGroup.row``) is one plane: the
callers pass ``v_cache=None`` and ``value_lanes``, keys are the whole rows
and a token's value is the first ``value_lanes`` lanes of its row, so the
kernel copies a page across HBM once and the writers write one plane.

Both handle GQA (query heads grouped natively over KV heads), per-row query
spans with ``q_lens`` padding masks, static or TRACED sliding windows
(gemma2 alternating layers), ``logit_softcap``, custom score scales, and
fp32 softmax/accumulation. A decode row is just the span-1 degenerate case:
:func:`decode_attention` is the one way in for it, the ragged kernel at a
span of one, while :func:`paged_attention_xla` keeps its own dense decode-shaped
formulation (same math, separately maintained — fixes to the ragged XLA op
do NOT automatically reach it).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from distllm_tpu.observability.instruments import ATTN_BACKEND_LABELS

# Head dims the Pallas kernel is exercised at in CI (tests/test_aot_tpu.py
# compiles these for a v5e topology). The kernel needs a head that is whole
# 128-lane tiles, or 64 wide in a pool row of whole tiles (two heads a tile:
# _half_tile_heads); 'auto' backend selection routes through
# supported_head_dim so untested shapes never auto-enable the kernel:
# widen this tuple when a new shape gains AOT coverage. 640 is a LATENT
# row (``models/deepseek_v3.py``: 512 latent + 64 rotary values in five
# whole lane tiles, one KV head, values its first 512 lanes).
TESTED_HEAD_DIMS = (64, 128, 640)


def supported_head_dim(head_dim: int) -> bool:
    """True when `attn_backend='auto'` may select the Pallas kernel."""
    return head_dim in TESTED_HEAD_DIMS


# Legal values for the engine/generator `attn_backend` selector. 'auto'
# resolves at engine construction (pinned like qmm_backend, never re-read
# mid-serve): 'pallas' on TPU when supports_model passes, else 'xla'.
# 'interpret' runs the SAME ragged Pallas kernel through the Pallas
# interpreter — CPU-runnable, the parity/identity test tier. The non-'auto'
# labels are owned by the metrics catalog (one source for the selector set
# and the distllm_engine_attn_backend_info scrape schema).
ATTN_BACKENDS = ('auto', *ATTN_BACKEND_LABELS)


def supports_model(model_cfg) -> bool:
    """May `attn_backend='auto'` select the Pallas kernel for this model?
    Softcapping, traced sliding windows and custom scales are the kernel's
    own, so eligibility is the head width's DMA/CI contract: a tested width
    that is whole 128-lane tiles (a pool row, ``num_kv_heads`` of them, then is)
    or is 64 wide with an even count (it shares its tile with a neighbour)."""
    d = model_cfg.head_size
    return supported_head_dim(d) and not (d % 128 and model_cfg.num_kv_heads % 2)


def kv_sublane_tile(kv_dtype) -> int:
    """Sublane-tile rows for a KV-cache dtype (Mosaic: 8 for 4-byte,
    16 for 2-byte, 32 for 1-byte). The ragged kernel DMAs each page into
    a ``block_size``-row band of its folded VMEM buffer, so ``block_size``
    must be a multiple of this."""
    return max(1, 32 // jnp.dtype(kv_dtype).itemsize)


class QuantizedKV(NamedTuple):
    """Int8 paged-KV container: block data plus per-block-per-KV-head scales.

    ``data`` keeps the paged layout (``[..., num_blocks, block_size,
    num_kv_heads * head_dim]`` int8) and ``scale`` a parallel fp32 array
    with the block-size axis dropped and one entry a KV head (``[...,
    num_blocks, num_kv_heads]``) — symmetric quantization, ``x ≈ data *
    scale`` with ``scale = absmax / 127`` over the block's live rows per
    KV head. The
    engine's pool carries a leading layer axis on both members; per-layer
    slices inside the model scans drop it.

    A NamedTuple is an automatic pytree, so a ``QuantizedKV`` rides the
    existing k/v argument slots through ``jax.jit`` (donation applies to
    both leaves), ``lax.scan`` carries, and ``jax.tree.map``-written
    block ops (gather/scatter/copy treat data and scale uniformly
    because the block axis is axis -3 of ``data`` and axis -2 of
    ``scale`` — axis 1 of each for the engine's pool). Full-precision
    caches stay bare arrays: every op in this module dispatches on
    ``isinstance(cache, QuantizedKV)`` so the unquantized paths emit
    bit-identical HLO to the pre-int8 code.
    """

    data: jnp.ndarray
    scale: jnp.ndarray


# Symmetric int8 range: scale = absmax / KV_QUANT_MAX maps the block's
# largest magnitude to +/-127 (-128 unused, keeping the code symmetric).
KV_QUANT_MAX = 127.0


def kv_storage_dtype(cache):
    """The dtype KV blocks are stored as (int8 for :class:`QuantizedKV`)."""
    if isinstance(cache, QuantizedKV):
        return jnp.dtype(cache.data.dtype)
    return jnp.dtype(cache.dtype)


def _kv_data(cache):
    return cache.data if isinstance(cache, QuantizedKV) else cache


def _layer_pages(k_cache, v_cache, layer):  # distlint: traced
    """``(k_pages, v_pages, base)``: a K and a V pool (V None for a latent
    pool) as the runs of pages the ops below index, and the id of
    ``layer``'s block 0 in such a run.

    One layer's run of pages (rank 3, the ops' own unit) is that
    run already: it comes back as it is with ``base`` None, and nothing
    is added to a block id. A STACKED pool ``[L, blocks, block_size,
    folded]`` is addressed, never sliced: a slice handed to the kernel (a
    custom call wants its operand materialised) or to a scatter is a
    copy of the layer's whole plane out of the pool and back, every
    layer of every step. It is viewed as ``[L * blocks, block_size,
    folded]``, a merge of two untiled leading dims and so a bitcast, and
    ``base = layer * blocks`` (``layer`` a Python int, or traced under a
    rolled layer scan) is added to the block ids AFTER dead rows and pad
    positions were sent to block 0, so a layer's trash block is its own
    block 0. An int8 pool's scales ``[L, blocks, N_kv]`` are viewed the
    same way. :func:`_like` gives written pages their shape back.
    """
    data = _kv_data(k_cache)
    if data.ndim == 3:
        return k_cache, v_cache, None
    if layer is None:
        raise ValueError(
            'a stacked pool [L, blocks, block_size, folded] needs the '
            'layer whose pages are meant'
        )
    k_pages, v_pages = jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), (k_cache, v_cache)
    )
    return k_pages, v_pages, layer * data.shape[1]


def _in_layer(block_ids, base):  # distlint: traced
    """Block ids of one layer as ids into :func:`_layer_pages`'s run."""
    return block_ids if base is None else block_ids + base


def _like(pages, cache):  # distlint: traced
    """Written pages in the shape of the pool they were taken from
    (their own, for a rank-3 buffer; None for a latent pool's V)."""
    return jax.tree.map(lambda a, c: a.reshape(c.shape), pages, cache)


def fold_heads(rows):  # distlint: traced
    """``[..., num_kv_heads, head_dim]`` -> ``[..., num_kv_heads *
    head_dim]``: a token's row as the pool stores it. For rows and blocks
    (new K/V, gathered pages, a tier's payload), never for a pool."""
    return rows.reshape(*rows.shape[:-2], rows.shape[-2] * rows.shape[-1])


def unfold_heads(rows, num_kv_heads: int):  # distlint: traced
    """:func:`fold_heads`'s inverse, for rows and blocks taken OUT of a
    pool."""
    return rows.reshape(
        *rows.shape[:-1], num_kv_heads, rows.shape[-1] // num_kv_heads
    )


def quantize_kv_rows(rows, scale):  # distlint: traced
    """Quantize ``rows`` (``[..., num_kv_heads, head_dim]``) against a
    per-KV-head ``scale`` (``[..., num_kv_heads]``). Zero scales (fresh
    all-zero blocks, trash-block garbage) emit exact zeros — the guarded
    denominator keeps the traced division finite so no NaN can reach the
    scatter even on the dead branch of the ``where``."""
    denom = jnp.where(scale > 0, scale, 1.0)[..., None]
    q = jnp.round(rows.astype(jnp.float32) / denom)
    q = jnp.clip(q, -KV_QUANT_MAX, KV_QUANT_MAX)
    return jnp.where(scale[..., None] > 0, q, 0.0).astype(jnp.int8)


def _rescale_int8_blocks(data, old_scale, new_scale):  # distlint: traced
    """Re-express int8 block rows quantized at ``old_scale`` in units of
    ``new_scale`` (``data [..., block_size, num_kv_heads * head_dim]``
    gathered blocks, scales ``[..., num_kv_heads]``). Appends only ever
    GROW a block's running absmax (``new_scale >= old_scale``), so the ratio is <= 1 and
    the rounded product stays in range; zero ``new_scale`` (fresh or
    trash blocks) zeroes the stale rows."""
    denom = jnp.where(new_scale > 0, new_scale, 1.0)
    ratio = jnp.where(new_scale > 0, old_scale / denom, 0.0)
    heads = unfold_heads(data, new_scale.shape[-1]).astype(jnp.float32)
    out = jnp.round(heads * ratio[..., None, :, None])
    out = jnp.clip(out, -KV_QUANT_MAX, KV_QUANT_MAX).astype(jnp.int8)
    return fold_heads(out)


def _gather_kv_blocks(cache, block_tables, head_dim):  # distlint: traced
    """Gather ``[B, max_blocks, block_size, num_kv_heads, head_dim]``
    blocks for attention (the gathered pages unfolded, never the cache),
    dequantizing int8 caches in the same fused expression (XLA folds the
    scale multiply into the gather consumers — no separate dequant pass
    or fp32 cache copy is ever materialized)."""
    data = _kv_data(cache)
    pages = unfold_heads(data[block_tables], data.shape[-1] // head_dim)
    if isinstance(cache, QuantizedKV):
        scales = cache.scale[block_tables]  # [B, max_blocks, num_kv_heads]
        return pages.astype(jnp.float32) * scales[:, :, None, :, None]
    return pages


def resolve_attn_backend(
    attn_backend: str,
    model_cfg,
    *,
    block_size: 'int | None' = None,
    kv_dtype=None,
) -> str:
    """Resolve the ``attn_backend`` selector to a concrete kernel, once.

    Mirrors the ``qmm_backend`` pinning pattern: the engine calls this at
    construction and closes its jitted serving functions over the result,
    so a config change after init can never re-route live dispatches.
    'auto' picks the Pallas kernel on TPU for CI-covered head dims —
    AND, when the caller provides the KV block geometry, only when
    ``block_size`` meets the kernel's sublane-tile DMA contract and the
    pool is not int8 under heads that share a lane tile (64 wide: the
    kernel refuses that pool by name) — and
    falls back to the always-available XLA path everywhere else (an
    'auto' config must never trace into the kernel's ValueErrors).
    """
    if attn_backend not in ATTN_BACKENDS:
        raise ValueError(
            f'attn_backend must be one of {ATTN_BACKENDS}, '
            f'got {attn_backend!r}'
        )
    if attn_backend != 'auto':
        return attn_backend
    eligible = jax.default_backend() == 'tpu' and supports_model(model_cfg)
    if eligible and block_size is not None and kv_dtype is not None:
        eligible = block_size % kv_sublane_tile(kv_dtype) == 0 and not (
            model_cfg.head_size % 128
            and jnp.dtype(kv_dtype) == jnp.dtype(jnp.int8)
        )
    return 'pallas' if eligible else 'xla'


def paged_attention_xla(  # distlint: traced
    q: jnp.ndarray,  # [B, num_heads, head_dim]
    k_cache: jnp.ndarray,  # [num_blocks, block_size, num_kv_heads * head_dim]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    context_lens: jnp.ndarray,  # [B] int32 (valid tokens incl. current)
    sliding_window: 'int | jnp.ndarray | None' = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    value_lanes: int | None = None,
    layer=None,
) -> jnp.ndarray:
    """Reference implementation: gather blocks then masked attention.

    ``sliding_window`` may be a static int, None, or a TRACED int32 scalar
    (per-layer windows riding a layer scan — gemma2's alternating
    local/global pattern; 0/negative means no window on that layer).
    ``scale`` overrides the 1/sqrt(head_dim) score scale
    (query_pre_attn_scalar); ``logit_softcap`` applies tanh(s/cap)*cap to
    the scaled scores before masking (both gemma2). A stacked pool
    ``[L, ...]`` goes in whole with its ``layer`` (:func:`_layer_pages`).
    """
    k_cache, v_cache, base = _layer_pages(k_cache, v_cache, layer)
    block_tables = _in_layer(block_tables, base)
    b, num_heads, head_dim = q.shape
    _, block_size, folded = _kv_data(k_cache).shape
    num_kv_heads = folded // head_dim
    max_blocks = block_tables.shape[1]
    group = num_heads // num_kv_heads

    # [B, max_blocks, block_size, Nkv, Hd] -> [B, T, Nkv, Hd]
    # (int8 caches dequantize inside the gather expression)
    k = _gather_kv_blocks(k_cache, block_tables, head_dim).reshape(
        b, max_blocks * block_size, num_kv_heads, head_dim
    )
    if v_cache is None:  # latent rows: values are lanes of the keys
        v = k[..., :value_lanes]
    else:
        v = _gather_kv_blocks(v_cache, block_tables, head_dim).reshape(
            b, max_blocks * block_size, num_kv_heads, head_dim
        )

    qg = q.reshape(b, num_kv_heads, group, head_dim).astype(jnp.float32)
    scores = jnp.einsum('bkgd,btkd->bkgt', qg, k.astype(jnp.float32))
    scores = scores * jnp.float32(
        scale if scale is not None else head_dim ** -0.5
    )
    if logit_softcap is not None:
        from distllm_tpu.models.common import softcap

        scores = softcap(scores, logit_softcap)
    positions = jnp.arange(max_blocks * block_size)[None, :]
    valid = positions < context_lens[:, None]
    if sliding_window is not None:
        # Match prefill's window mask: only the last `sliding_window` keys.
        # For a traced window, <= 0 disables the clamp on that layer.
        windowed = positions > context_lens[:, None] - 1 - sliding_window
        if isinstance(sliding_window, int):
            valid = valid & windowed
        else:
            valid = valid & (windowed | (sliding_window <= 0))
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum('bkgt,btkd->bkgd', probs, v.astype(jnp.float32))
    return out.reshape(b, num_heads, v.shape[-1]).astype(q.dtype)


def ragged_paged_attention_xla(  # distlint: traced
    q: jnp.ndarray,  # [B, S, num_heads, head_dim] per-row query spans
    k_cache: jnp.ndarray,  # [num_blocks, block_size, num_kv_heads * head_dim]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    context_lens: jnp.ndarray,  # [B] total valid tokens incl. the span
    q_positions: jnp.ndarray,  # [B, S] absolute position of each query
    q_lens: 'jnp.ndarray | None' = None,  # [B] valid queries per row
    sliding_window: 'int | jnp.ndarray | None' = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    value_lanes: int | None = None,
    layer=None, block_length: int = 1,
) -> jnp.ndarray:
    """Ragged per-row-query-length attention over paged KV — the shared
    op of prefix-cache tail prefill, chunked prefill, mixed
    prefill+decode serving windows, and speculative verification
    (docs/serving.md).

    Each row carries a SPAN of queries at absolute ``q_positions``; every
    query attends to all cached positions ``<=`` its own (the span's K/V
    must already be written into the paged blocks — write-then-attend,
    exactly like the decode path). Rows are ragged: a decode row is the
    span-1 DEGENERATE CASE (its single query at position
    ``context_lens - 1`` sees the whole context — numerically the
    :func:`paged_attention_xla` result, though that op keeps its own
    standalone dense formulation: a masking or numeric fix here must be
    mirrored there), while a prefill-chunk row's queries attend
    causally over chunk + paged prefix. ``q_lens`` (optional) masks each
    row's padding queries so their softmax rows stay finite; with
    ``q_lens=None`` padding queries compute garbage the caller discards
    (masking only touches pad rows — valid rows are bit-identical either
    way). Gather + masked fp32 softmax; XLA fuses this well and it runs
    on CPU for tests.

    This is the portable baseline and bit-exactness reference of the
    backend pair: :func:`ragged_paged_attention_pallas` is the fused TPU
    fast path (grid over row × query tile × KV chunk, online softmax, no
    dense score tensor), selected per engine via
    :func:`ragged_paged_attention`'s ``backend`` argument. This XLA path
    stays the always-available fallback and the identity baseline the
    parity matrix (``tests/test_ragged_attention.py``) pins the kernel
    against. A stacked pool ``[L, ...]`` goes in whole with its ``layer``
    (:func:`_layer_pages`).
    """
    k_cache, v_cache, base = _layer_pages(k_cache, v_cache, layer)
    block_tables = _in_layer(block_tables, base)
    b, s, num_heads, head_dim = q.shape
    _, block_size, folded = _kv_data(k_cache).shape
    num_kv_heads = folded // head_dim
    max_blocks = block_tables.shape[1]
    group = num_heads // num_kv_heads

    k = _gather_kv_blocks(k_cache, block_tables, head_dim).reshape(
        b, max_blocks * block_size, num_kv_heads, head_dim
    )
    if v_cache is None:  # latent rows: values are lanes of the keys
        v = k[..., :value_lanes]
    else:
        v = _gather_kv_blocks(v_cache, block_tables, head_dim).reshape(
            b, max_blocks * block_size, num_kv_heads, head_dim
        )
    qg = q.reshape(b, s, num_kv_heads, group, head_dim).astype(jnp.float32)
    scores = jnp.einsum('bskgd,btkd->bkgst', qg, k.astype(jnp.float32))
    scores = scores * jnp.float32(
        scale if scale is not None else head_dim ** -0.5
    )
    if logit_softcap is not None:
        from distllm_tpu.models.common import softcap

        scores = softcap(scores, logit_softcap)
    kv_pos = jnp.arange(max_blocks * block_size)[None, None, :]  # [1, 1, T]
    qp = _block_ceiling(q_positions, block_length)[:, :, None]  # [B, S, 1]
    valid = (kv_pos < context_lens[:, None, None]) & (kv_pos <= qp)
    if sliding_window is not None:
        # Same window semantics as the dense prefill mask: query at
        # position p sees keys in (p - window, p]. Traced windows <= 0
        # disable the clamp (gemma2 alternating layers).
        windowed = kv_pos > qp - sliding_window
        if isinstance(sliding_window, int):
            valid = valid & windowed
        else:
            valid = valid & (windowed | (sliding_window <= 0))
    if q_lens is not None:
        # Padding queries keep key 0 visible: an all-masked softmax row is
        # NaN, and a NaN in a pad row can poison reductions downstream.
        q_valid = jnp.arange(s)[None, :, None] < q_lens[:, None, None]
        valid = valid | (~q_valid & (kv_pos == 0))
    scores = jnp.where(valid[:, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum('bkgst,btkd->bskgd', probs, v.astype(jnp.float32))
    return out.reshape(b, s, num_heads, v.shape[-1]).astype(q.dtype)


def _ragged_paged_attn_kernel(
    # Operand order (positional, by grid-spec contract):
    #
    # scalar-prefetch (SMEM):
    #   block_tables_ref,  # [B, max_blocks] int32
    #   context_lens_ref,  # [B] int32
    #   q_start_ref,  # [B] int32 — absolute position of row's first query
    #   q_lens_ref,  # [B] int32 — valid queries per row (0 = fully padded)
    #   window_ref,  # [1] int32 — sliding window; <= 0 disables
    # array operands. The KV caches arrive HEAD-FOLDED, as the pool stores
    # them: [num_blocks, block_size, num_kv_heads * head_dim], so each KV
    # head occupies a 128-aligned LANE band. (Folding a four-dim cache at
    # the call was no bitcast under the TPU's tiled layout but a copy of
    # the whole buffer, a call: why the pool is stored so.) This is
    # the layout trick that retires the Mosaic rejections the decode-only
    # kernel died on (both reproduced + pinpointed on this container's
    # toolchain, 2026-08-04): slicing the kv-head dim out of the MIDDLE
    # of a page buffer (kb[:, h, :]) is an "implicit dim change", and
    # per-head HBM DMA slices (cache[page, :, h]) break sublane tile
    # alignment whenever num_kv_heads < the tile — while a static lane
    # slice at a 128 multiple is always tile-aligned.
    #   q_ref,  # [num_kv_heads, span_tile * group, head_dim] (VMEM)
    #   k_cache_ref,  # [num_blocks, block_size, num_kv_heads*head_dim] (HBM)
    #   v_cache_ref,  #   a layer's buffer, or a stacked pool's L * blocks
    #       pages with block_tables_ref counting from the layer's block 0
    #   [k_scale_ref, v_scale_ref]  # quantized only: [num_blocks, 128]
    #       fp32 (HBM) — per-block per-KV-head scales, lane-padded to 128
    #       so each page's scale row DMAs with an aligned minor dim
    #   out_ref,  # [num_kv_heads, span_tile * group, head_dim] (VMEM)
    # scratch — KV buffers are pre-flattened [slot, chunk_tokens, folded]:
    # each page DMAs into a statically-offset row band, so the compute
    # side never reshapes at all (a traced-slot reshape was the third
    # Mosaic lowering rejection this layout designs out).
    #   k_buf,  # [2, chunk_tokens, num_kv_heads * head_dim] VMEM
    #   v_buf,
    #   [ks_buf, vs_buf]  # quantized only: [2, pages_per_chunk, 128] fp32
    #   sems,  # DMA semaphores [2, pages_per_chunk, 2 (4 when quantized)]
    #   acc_ref,  # [num_kv_heads, span_tile * group, head_dim] fp32
    #   m_ref,  # [num_kv_heads, span_tile*group, 128] fp32, lane-replicated
    #   l_ref,  # [num_kv_heads, span_tile*group, 128] fp32, lane-replicated
    *refs,
    block_size: int,
    pages_per_chunk: int,
    num_kv_heads: int,
    group: int,
    span_tile: int,
    scale: float,
    logit_softcap: float | None,
    quantized: bool = False,
    value_lanes: int | None = None, walk: bool = False, block_length: int = 1,
):
    """The SPAN schedule, grid (B, q_tiles, kv_chunks): one row × one query
    tile × one chunk of KV pages per step (a span of one: ``_walk_row``).

    Pages of a chunk are DMA'd HBM→VMEM individually (they are scattered
    by the paged allocator), double-buffered across grid steps: while
    chunk c computes, chunk c+1's copies are in flight. Chunks a tile
    cannot see — beyond ``context_lens``, past the tile's last query
    (causality), or entirely before the sliding-window start of its first
    query — issue no DMAs and no compute, so a decode row (span 1) pays
    exactly the old decode-only kernel's traffic and a chunk row streams
    only its causal prefix per tile.

    Online softmax is the flash-attention recurrence per (query, head)
    lane: running max ``m`` and denominator ``l`` live lane-replicated in
    fp32 scratch (minor dim 128 — never a 1-wide minor dim, which is what
    tripped Mosaic's "implicit dim change" lowering on the retired
    decode-only kernel), the chunk's probabilities are folded into the
    fp32 accumulator with the usual ``exp(m_prev - m_new)`` correction,
    and no ``[.., S, T]`` score tensor ever exists.

    With ``value_lanes`` (a latent pool) there is no ``v_cache_ref`` and no
    ``v_buf`` among the operands: a head's values are the first
    ``value_lanes`` lanes of its key band, read from the page copy the
    scores were made from, and ``out_ref``/``acc_ref`` are ``value_lanes``
    wide.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    latent = value_lanes is not None
    # One unpacking for both schedules (:func:`_kernel_refs`: absent
    # operands are None). ``walk`` (a span of one: decode rows, grid
    # (rows, 1, 1)) shares this function's scratch reset and ``compute``
    # and takes its own schedule at :func:`_walk_row`, which copies the
    # pages a row sees itself: the span schedule's ``issue`` and
    # ``wait`` then copy nothing. Its statements stay on the lines and
    # columns they had: the serialized kernel carries both, so a prefill
    # program lowers to the text it had before the walk and finds its
    # entry in the compile cache (PERF.md section 6, PR 38).
    kernel_refs = _kernel_refs(refs, latent, quantized, walk)
    (
        block_tables_ref, context_lens_ref, q_start_ref, q_lens_ref,
        window_ref, q_ref, k_cache_ref, v_cache_ref, k_scale_ref,
        v_scale_ref, out_ref, k_buf, v_buf, ks_buf, vs_buf, sems,
        acc_ref, m_ref, l_ref, _slot_ref,
    ) = kernel_refs
    span_pages = range(0 if walk else pages_per_chunk)

    # Grid (rows, query tiles, chunks); under the walk (rows, 1, 1).

    seq = pl.program_id(0)
    qt = pl.program_id(1)
    c = pl.program_id(2)
    num_chunks = pl.num_programs(2)
    ctx = context_lens_ref[seq]
    q0 = q_start_ref[seq]
    q_len = q_lens_ref[seq]
    win = window_ref[0]
    chunk_tokens = pages_per_chunk * block_size
    head_dim = q_ref.shape[-1]
    rows = span_tile * group  # query-tile rows per KV head

    # Pages this row actually owns (valid block-table prefix).
    n_pages = (ctx + block_size - 1) // block_size
    span_off = qt * span_tile  # first span index of this query tile
    # Keys this tile can ever see: [lo, hi). The tile's FIRST query has
    # the lowest sliding-window floor; its LAST valid query bounds the
    # causal ceiling. Fully padded tiles (span_off >= q_len) skip
    # everything and emit zeros.
    lo = jnp.where(win > 0, jnp.maximum(q0 + span_off - win + 1, 0), 0)
    hi = jnp.minimum(ctx, q0 + jnp.minimum(q_len, span_off + span_tile))
    tile_active = q_len > span_off
    if block_length > 1: hi = jnp.minimum(ctx, _block_ceiling(q0 + jnp.minimum(q_len, span_off + span_tile) - 1, block_length) + 1)  # noqa: E701
    def chunk_needed(ci):
        start = ci * chunk_tokens
        return tile_active & (start < hi) & ((ci + 1) * chunk_tokens > lo)

    def issue(ci, slot):
        # Clamp logical page ids into the row's valid range: the DMA
        # engine must copy *something* per issued descriptor, and the
        # compute mask discards anything outside [lo, hi). One contiguous
        # whole-page descriptor per page (the head fold keeps pages
        # contiguous, so the descriptor count stays 2 per page).
        for p in span_pages:
            logical = ci * pages_per_chunk + p
            page = jnp.clip(logical, 0, jnp.maximum(n_pages - 1, 0))
            page_id = block_tables_ref[seq, page]
            rows_at = slice(p * block_size, (p + 1) * block_size)
            pltpu.make_async_copy(
                k_cache_ref.at[page_id],
                k_buf.at[slot, rows_at],
                sems.at[slot, p, 0],
            ).start()
            if not latent:
                pltpu.make_async_copy(
                    v_cache_ref.at[page_id],
                    v_buf.at[slot, rows_at],
                    sems.at[slot, p, 1],
                ).start()
            if quantized:
                # The page's scale row rides the same double-buffered
                # prefetch: a 128-lane fp32 row per page (512 B) next to
                # the page's int8 payload — dequant needs no extra pass.
                pltpu.make_async_copy(
                    k_scale_ref.at[page_id],
                    ks_buf.at[slot, p],
                    sems.at[slot, p, 2],
                ).start()
                pltpu.make_async_copy(
                    v_scale_ref.at[page_id],
                    vs_buf.at[slot, p],
                    sems.at[slot, p, 3],
                ).start()

    def wait(slot):
        for p in span_pages:
            rows_at = slice(p * block_size, (p + 1) * block_size)
            pltpu.make_async_copy(
                k_cache_ref.at[0],
                k_buf.at[slot, rows_at],
                sems.at[slot, p, 0],
            ).wait()
            if not latent:
                pltpu.make_async_copy(
                    v_cache_ref.at[0],
                    v_buf.at[slot, rows_at],
                    sems.at[slot, p, 1],
                ).wait()
            if quantized:
                pltpu.make_async_copy(
                    k_scale_ref.at[0],
                    ks_buf.at[slot, p],
                    sems.at[slot, p, 2],
                ).wait()
                pltpu.make_async_copy(
                    v_scale_ref.at[0],
                    vs_buf.at[slot, p],
                    sems.at[slot, p, 3],
                ).wait()

    @pl.when(c == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(chunk_needed(0))
        def _():
            issue(0, 0)

    # Double buffering: start chunk c+1's copies before computing chunk c.
    @pl.when((c + 1 < num_chunks) & chunk_needed(c + 1))
    def _():
        issue(c + 1, (c + 1) % 2)

    def compute(slot, c=c):
        # ``slot`` is a PYTHON int here (the caller branches on chunk parity):
        # every KV access below is a static-slot, static-lane-band load
        # straight from the ref. This toolchain's Mosaic rejects a
        # full-plane bf16 load of the folded buffer ("invalid offsets in
        # tiling target" — construct-probed 2026-08-04: full-plane f32
        # loads and per-band bf16 loads both compile; only the
        # full-plane bf16 load fails), so the head band IS the load.
        # Per-score-row span index / absolute query position. Query-tile
        # rows interleave (span, group): row r serves span span_off +
        # r // group, so GQA head grouping is native — one [rows, C] dot
        # per KV head scores every query x grouped-head pair at once.
        span_idx = span_off + (
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
        )  # [rows, 1]
        qp = q0 + span_idx  # [rows, 1] absolute query positions
        kvp = c * chunk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk_tokens), 1
        )  # [1, C] absolute key positions
        valid = (kvp < ctx) & (kvp <= qp) & (span_idx < q_len)
        if block_length > 1: valid = (kvp < ctx) & (kvp <= _block_ceiling(qp, block_length)) & (span_idx < q_len)  # noqa: E701
        # Sliding window: a query at p sees keys in (p - win, p]; win <= 0
        # disables (a traced per-layer window where 0 means global).
        valid = valid & ((kvp > qp - win) | (win <= 0))

        if quantized:
            # Per-key page index [1, C]: dequant applies each page's
            # per-head scale to its block_size-column band of the scores
            # (q · (k_int8 · s) == (q · k_int8) · s per key column), so
            # the int8 band feeds the MXU untouched and the scale is one
            # VPU multiply on the [rows, C] scores — the fused-dequant
            # shape, never an fp32 KV copy in VMEM.
            col_page = jax.lax.broadcasted_iota(
                jnp.int32, (1, chunk_tokens), 1
            ) // block_size

            def page_scale_vec(scale_buf, slot, h):
                vec = jnp.zeros((1, chunk_tokens), jnp.float32)
                for p in range(pages_per_chunk):  # static unroll
                    vec = jnp.where(
                        col_page == p, scale_buf[slot, p, h], vec
                    )
                return vec

        for h in range(num_kv_heads):  # static unroll over KV heads
            qh = q_ref[h]  # [rows, Hd]
            # Head h is a static LANE band of the folded buffer — a
            # 128-aligned slice, always tile-aligned.
            kh = k_buf[slot, :, h * head_dim:(h + 1) * head_dim]  # [C, Hd]
            if kh.dtype != qh.dtype:
                # int8 bands (and bf16 pools under fp32 models) promote
                # to the query dtype for the MXU dot; int8 magnitudes
                # (<= 127) are exact in bf16's 8-bit significand.
                kh = kh.astype(qh.dtype)
            scores = (
                jax.lax.dot_general(
                    qh, kh,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [rows, C]
            if quantized:
                scores = scores * page_scale_vec(ks_buf, slot, h)
            if logit_softcap is not None:
                cap = jnp.float32(logit_softcap)
                scores = jnp.tanh(scores / cap) * cap
            scores = jnp.where(valid, scores, -jnp.inf)
            m_prev = m_ref[h]  # [rows, 128] lane-replicated
            blk_max = jnp.max(scores, axis=-1, keepdims=True)  # [rows, 1]
            new_m = jnp.maximum(m_prev, blk_max)
            # A query row can be fully masked in an in-range chunk (the
            # chunk serves a LATER query of the same tile): keep the
            # recurrence NaN-free by rebasing on 0 until the row sees its
            # first live key — exp(-inf - 0) = 0, so l/acc stay 0.
            safe_m = jnp.where(new_m == -jnp.inf, 0.0, new_m)
            correction = jnp.exp(m_prev - safe_m)  # m_prev=-inf -> 0
            probs = jnp.exp(scores - safe_m[:, :1])  # masked lanes -> 0
            l_ref[h] = l_ref[h] * correction + jnp.sum(
                probs, axis=-1, keepdims=True
            )
            if latent:  # the values: leading lanes of the key band
                vh = k_buf[slot, :, h * head_dim:h * head_dim + value_lanes]
            else:
                vh = v_buf[slot, :, h * head_dim:(h + 1) * head_dim]  # [C, Hd]
            if quantized:
                # probs · (v_int8 · s) == (probs · s_per_key) · v_int8:
                # fold V's per-page scale into the probabilities (one
                # [rows, C] VPU multiply) and promote the int8 band to
                # the query dtype for the MXU — same fusion as K.
                probs = probs * page_scale_vec(vs_buf, slot, h)
                vh = vh.astype(q_ref.dtype)
            pv = jax.lax.dot_general(
                probs.astype(vh.dtype), vh,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, Hd]
            acc_ref[h] = acc_ref[h] * correction[:, :1] + pv
            m_ref[h] = new_m
    if walk: return _walk_row(kernel_refs, compute, block_size, pages_per_chunk, group, scale, logit_softcap, value_lanes, quantized)  # noqa: E701
    @pl.when(chunk_needed(c))
    def _():
        wait(c % 2)
        # Compute is branched on chunk parity so every KV-buffer access
        # uses a STATIC slot index (DMA descriptors take traced indices
        # fine — construct-probed). The duplicated trace is two copies
        # of the same straight-line block — free at runtime, one branch
        # executes.
        @pl.when(c % 2 == 0)
        def _():
            compute(0)

        @pl.when(c % 2 == 1)
        def _():
            compute(1)

    @pl.when(c == num_chunks - 1)
    def _():
        # Rows that never saw a live key (q_lens padding, padded tile
        # tail) have l = 0 and emit exact zeros — finite, so a pad row
        # can never poison downstream reductions.
        for h in range(num_kv_heads):
            out = acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-9)
            out_ref[h] = out.astype(out_ref.dtype)


def ragged_paged_attention_pallas(
    q: jnp.ndarray,  # [B, S, num_heads, head_dim] per-row query spans
    k_cache: jnp.ndarray,  # [num_blocks, block_size, num_kv_heads * head_dim]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    context_lens: jnp.ndarray,  # [B] total valid tokens incl. the span
    q_positions: jnp.ndarray,  # [B, S] absolute position of each query
    q_lens: 'jnp.ndarray | None' = None,  # [B] valid queries per row
    sliding_window: 'int | jnp.ndarray | None' = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    *,
    pages_per_chunk: int | None = None,
    span_tile: int | None = None,
    interpret: bool = False,
    value_lanes: int | None = None,
    layer=None, block_length: int = 1,
) -> jnp.ndarray:
    """Fused Pallas TPU kernel twin of :func:`ragged_paged_attention_xla`.

    One kernel serves the whole serving surface: decode rows (span 1),
    prefill-chunk / cache-hit tail rows (causal over chunk + paged
    prefix), and speculative verify spans, with GQA grouping, ``q_lens``
    pad-query masking, static or TRACED ``sliding_window`` (gemma2
    alternating layers; ``<= 0`` disables), ``logit_softcap``, custom
    ``scale``, and fp32 online-softmax accumulation — never a dense
    ``[.., S, T]`` score tensor.

    CONTRACT beyond the XLA twin: each row's ``q_positions`` must be
    CONSECUTIVE (``q_positions[b, i] == q_positions[b, 0] + i``) — true
    for every serving span (decode rows, chunk tails, verify spans), and
    what lets the kernel scalar-prefetch one start position per row
    instead of streaming a position tensor. Pad-query rows (``>=
    q_lens``) emit exact zeros where the XLA twin emits key-0 garbage;
    both are finite and both are discarded by every caller, so valid
    rows are the parity surface (pinned by the interpret-mode matrix in
    ``tests/test_ragged_attention.py``).

    ``pages_per_chunk`` controls how many KV pages one step fetches and
    computes (default: 128 keys for a span over one, 512 on a latent
    plane; :func:`walk_keys_a_step` for a span of one); ``span_tile`` caps
    the query-span positions per grid tile (default: up to 512 query rows
    after GQA flattening). ``interpret=True`` runs the same kernel on the
    Pallas interpreter (the ``attn_backend='interpret'`` engine tier).

    A STACKED pool ``[L, blocks, block_size, folded]`` goes in whole with
    its ``layer``: the kernel fetches pages from HBM by id, so it is
    handed every layer's pages as one run (a bitcast) and ids that start
    at the layer's block 0 (:func:`_layer_pages`); the kernel itself is
    the one a rank-3 buffer gets, and a rank-3 buffer lowers as before.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k_cache, v_cache, base = _layer_pages(k_cache, v_cache, layer)
    block_tables = _in_layer(block_tables, base)
    quantized = isinstance(k_cache, QuantizedKV)
    latent = v_cache is None
    if latent and (quantized or value_lanes is None or value_lanes % 128):
        raise ValueError(
            'a latent pool (v_cache None) is read as bare rows whose first '
            f'value_lanes lanes (whole 128-lane tiles, got {value_lanes}) '
            'are the values; it has no int8 form'
        )
    k_data = _kv_data(k_cache)
    v_data = None if latent else _kv_data(v_cache)
    value_dim = value_lanes if latent else q.shape[-1]
    b, s, num_heads, head_dim = q.shape
    _, block_size, folded = k_data.shape
    num_kv_heads = folded // head_dim
    max_blocks = block_tables.shape[1]
    group = num_heads // num_kv_heads
    if head_dim % 128 and (_pairs_heads(head_dim, folded) or not interpret):
        # two 64-wide heads share a lane tile; any other width raises there
        return _half_tile_heads(
            q, k_cache, v_cache, block_tables, context_lens, q_positions,
            q_lens, sliding_window, scale, logit_softcap, pages_per_chunk,
            span_tile, interpret,
        )
    # Each page DMAs into a [block_size]-row band of the folded KV buffer,
    # so the band offsets must land on sublane-tile boundaries (16 rows
    # for 2-byte dtypes, 8 for fp32, 32 for int8). EngineConfig's default
    # block_size of 16 satisfies every full-precision serving dtype but
    # NOT int8 KV, and 'auto' resolution (resolve_attn_backend with the
    # block geometry) routes misaligned configs to XLA before ever
    # tracing here — reaching this raise means an explicit 'pallas' pin.
    sublane = kv_sublane_tile(k_data.dtype)
    if block_size % sublane and not interpret:
        raise ValueError(
            f'pallas paged attention needs block_size % {sublane} == 0 '
            f'for {jnp.dtype(k_data.dtype).name} KV caches, '
            f'got {block_size}; use block_size={sublane} '
            "(EngineConfig.block_size) or attn_backend='xla'"
        )
    # A span of one (decode rows) WALKS the chunks each row holds
    # (:func:`_walk_row`, keys a step by :func:`walk_keys_a_step`); a
    # longer span keeps the grid over the widest table's chunks.
    walk = s == 1
    if pages_per_chunk is None:
        # Spans: 128 keys a step over K/V pools, 512 over a latent plane
        # (ONE head, rows 5 lane tiles wide, every query head on it: a
        # 512-query span at context 8448 ran 62 TFLOP/s at 128 keys and
        # 132 at 512 with 1024-row tiles, PR 32, PERF.md section 6).
        chunk_tokens = _default_keys_a_step(k_data, latent, walk)
        pages_per_chunk = max(1, chunk_tokens // block_size)
    pages_per_chunk = min(pages_per_chunk, max_blocks)
    num_chunks = 1 if walk else -(-max_blocks // pages_per_chunk)
    if span_tile is None:
        # ~512 post-GQA query rows per tile keeps q/out/acc + the m/l
        # scratch + double-buffered KV pages comfortably inside VMEM at
        # 7B dims while still feeding the MXU full tiles (1024 rows on a
        # latent plane's one head, above).
        span_tile = max(1, (1024 if latent else 512) // group)
        # A power of two: with 6 queries a KV head 85 positions (with 5,
        # 102) would be 510 rows, which Mosaic refuses (a block's rows: a
        # multiple of 8), and the spans' pow2 buckets must divide evenly.
        span_tile = 1 << (span_tile.bit_length() - 1)
    span_tile = min(span_tile, s)
    num_q_tiles = -(-s // span_tile)

    if scale is None:
        scale = head_dim ** -0.5
    # One compiled signature for every window variant: the sliding window
    # rides a scalar-prefetch operand whether static, absent (0 = off),
    # or a traced per-layer value (gemma2 alternating layers).
    if sliding_window is None:
        window_arr = jnp.zeros((1,), jnp.int32)
    else:
        window_arr = jnp.asarray(sliding_window, jnp.int32).reshape((1,))
    if q_lens is None:
        # No pad masking requested: every span position is a live query
        # (the XLA twin's q_lens=None semantics for valid rows).
        q_lens = jnp.full((b,), s, jnp.int32)

    # Group-major query layout: [B, S, Nh, Hd] -> [B, Nkv, S*G, Hd] so the
    # kernel reads one contiguous [rows, Hd] plane per KV head with no
    # in-kernel reshapes across the head dim (row r = span r//G, group
    # member r%G). The transpose touches only the tiny activation tensor.
    qg = q.reshape(b, s, num_kv_heads, group, head_dim)
    qg = qg.transpose(0, 2, 1, 3, 4).reshape(
        b, num_kv_heads, s * group, head_dim
    )
    # The caches go to the kernel as they lie, head-folded [nb, bs, Nkv*Hd]
    # (a stacked pool as one run of pages, never a slice): a head is a
    # 128-aligned lane band. Where walk_block says so the walk takes its
    # softmax state STACKED, and the queries too unless a head's are tiles.
    state = (num_kv_heads, span_tile * group)  # [.., rows] of q, acc, m, l
    if walk and walk_block(num_kv_heads, group)[0] == 'stacked':  # ONE block of Nkv * group rows
        state, qg = (1, num_kv_heads * group), qg.reshape(b, 1, -1, head_dim) if group < 8 else qg
    extra_operands = []
    if quantized:
        if num_kv_heads > 128:
            raise ValueError(
                'pallas int8 paged attention supports at most 128 KV '
                f'heads (one scale lane row per page), got {num_kv_heads}'
            )
        # Scale rows pad to a full 128-lane minor dim so each page's
        # per-head scales DMA as one aligned [128] fp32 row (512 B)
        # beside the page's int8 payload. The pad is a tiny HLO pad of
        # the [nb, nkv] scale array per dispatch, not a cache copy.
        extra_operands = [
            jnp.pad(
                c.scale.astype(jnp.float32),
                ((0, 0), (0, 128 - num_kv_heads)),
            )
            for c in (k_cache, v_cache)
        ]

    rows = span_tile * group
    kernel = functools.partial(
        _ragged_paged_attn_kernel,
        block_size=block_size,
        pages_per_chunk=pages_per_chunk,
        num_kv_heads=num_kv_heads,
        group=group,
        span_tile=span_tile,
        scale=float(scale),
        logit_softcap=(
            None if logit_softcap is None else float(logit_softcap)
        ),
        quantized=quantized,
        value_lanes=value_lanes if latent else None, walk=walk, block_length=block_length,
    )
    kv_scratch = [
        pltpu.VMEM(
            (2, pages_per_chunk * block_size, folded),
            data.dtype,
        )
        for data in ((k_data,) if latent else (k_data, v_data))
    ]
    if quantized:
        kv_scratch += [
            pltpu.VMEM((2, pages_per_chunk, 128), jnp.float32),
            pltpu.VMEM((2, pages_per_chunk, 128), jnp.float32),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, num_q_tiles, num_chunks),
        in_specs=[
            pl.BlockSpec(
                (None, *qg.shape[1:3], head_dim) if walk else (None, *state, head_dim),
                lambda i, qi, j, *_: (i, 0, qi, 0),
            ),
        ] + [pl.BlockSpec(memory_space=pl.ANY)] * (
            (1 if latent else 2) + len(extra_operands)
        ),
        out_specs=pl.BlockSpec(
            (None, num_kv_heads, rows, value_dim),
            lambda i, qi, j, *_: (i, 0, qi, 0),
        ),
        scratch_shapes=kv_scratch + [
            pltpu.SemaphoreType.DMA(
                (2, pages_per_chunk, 4 if quantized else 1 if latent else 2)
            ),
            pltpu.VMEM((*state, value_dim), jnp.float32),
            pltpu.VMEM((*state, 128), jnp.float32),
            pltpu.VMEM((*state, 128), jnp.float32),
        ] + [pltpu.SMEM((2,), jnp.int32)] * walk,  # the walk's slot
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, num_kv_heads, s * group, value_dim), q.dtype
        ),
        interpret=interpret,
    )(
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        q_positions[:, 0].astype(jnp.int32),
        q_lens.astype(jnp.int32),
        window_arr,
        qg,
        *((k_data,) if latent else (k_data, v_data)),
        *extra_operands,
    )
    return (
        out.reshape(b, num_kv_heads, s, group, value_dim)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, s, num_heads, value_dim)
    )


def ragged_paged_attention(
    q: jnp.ndarray,  # [B, S, num_heads, head_dim] per-row query spans
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,
    context_lens: jnp.ndarray,
    q_positions: jnp.ndarray,
    q_lens: 'jnp.ndarray | None' = None,
    sliding_window: 'int | jnp.ndarray | None' = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    *,
    backend: str = 'xla',
    value_lanes: int | None = None,
    layer=None, block_length: int = 1,
) -> jnp.ndarray:
    """THE serving attention callsite: dispatch one ragged paged span
    batch through the selected backend.

    ``backend`` is a RESOLVED selector value ('xla' | 'pallas' |
    'interpret' — see :data:`ATTN_BACKENDS`; the engine resolves 'auto'
    once at construction via :func:`resolve_attn_backend` and closes its
    jitted serving functions over the result, mirroring ``qmm_backend``).
    'xla' is the always-available bit-exact baseline; 'pallas' is the
    fused TPU kernel; 'interpret' runs the same kernel on the Pallas
    interpreter (CPU parity/identity tests). Every serving dispatch —
    ``decode_loop``/``decode_step`` span-1 rows, ``prefill_paged`` tails,
    ``mixed_window`` chunk rows, ``spec_window`` verify spans — routes
    through here, so one kernel accelerates the whole serving surface.
    """
    if backend in ('pallas', 'interpret'):
        return ragged_paged_attention_pallas(
            q, k_cache, v_cache, block_tables, context_lens, q_positions,
            q_lens=q_lens, sliding_window=sliding_window, scale=scale,
            logit_softcap=logit_softcap, interpret=backend == 'interpret',
            value_lanes=value_lanes, layer=layer, block_length=block_length,
        )
    if backend != 'xla':
        raise ValueError(
            f'unresolved or unknown attn backend {backend!r}; expected '
            "'xla', 'pallas', or 'interpret' (resolve 'auto' via "
            'resolve_attn_backend before dispatch)'
        )
    return ragged_paged_attention_xla(
        q, k_cache, v_cache, block_tables, context_lens, q_positions,
        q_lens=q_lens, sliding_window=sliding_window, scale=scale,
        logit_softcap=logit_softcap, value_lanes=value_lanes, layer=layer, block_length=block_length,
    )


class _KernelRefs(NamedTuple):
    """The paged kernel's operands by name, None where a call has none (a
    latent pool has no V, a full-precision pool no scales, the span
    schedule no ``slot``). The order is the grid spec's: scalar prefetch,
    arrays, output, scratch (``_ragged_paged_attn_kernel`` says what each
    holds)."""

    block_tables: object
    context_lens: object
    q_start: object
    q_lens: object
    window: object
    q: object
    k_cache: object
    v_cache: object
    k_scale: object
    v_scale: object
    out: object
    k_buf: object
    v_buf: object
    ks_buf: object
    vs_buf: object
    sems: object
    acc: object
    m: object
    l: object  # noqa: E741 -- the online softmax's denominator
    slot: object  # [2] int32 SMEM: the walk's next slot, and whether it has begun


def _kernel_refs(refs, latent, quantized, walk) -> _KernelRefs:
    refs = list(refs)

    def take(n, present=True):
        return [refs.pop(0) if present else None for _ in range(n)]

    return _KernelRefs(
        *take(7), *take(1, not latent), *take(2, quantized), *take(2),
        *take(1, not latent), *take(2, quantized), *take(4), *take(1, walk),
    )


# The walk's chunk: the most keys a step, a power of two of at most
# WALK_MAX_KEYS, whose two slots of K (and V) pages stay within
# WALK_BUFFER_BYTES of VMEM and whose copies within WALK_SEMAPHORES (every
# copy in flight has a DMA semaphore of its own; 512 of them ran out of
# the chip's 2 KB for them). Within a chunk the walk's unit is a TURN of
# WALK_PAGES_A_TURN pages: its copies go a turn at a time, and the stacked
# form of its softmax block a FOLD of WALK_TURNS_A_FOLD turns (512 keys at
# blocks of 16), or the whole chunk where the heads' stacked rows are at
# most WALK_WHOLE_CHUNK_ROWS (:func:`walk_block`). PERF.md section 6 has
# the sweeps on the chip behind the four (PR 38), behind the turn and the
# fold (PR 49) and behind the fold from 8 queries a head up (PR 55).
WALK_MAX_KEYS = 1024
WALK_BUFFER_BYTES = 8 << 20
WALK_SEMAPHORES = 256
WALK_PAGES_A_TURN = 8
WALK_TURNS_A_FOLD = 4
WALK_WHOLE_CHUNK_ROWS = 32


def walk_keys_a_step(
    row_lanes: int, dtype, *, planes: int, block_size: int
) -> int:
    """Keys a step of the row walk (:func:`_walk_row`) fetches and folds
    into the softmax, chosen from what a call's operands show: the folded
    row width, the stored dtype (an int8 pool's page brings two scale
    rows), the planes a key's page is copied from (2 for a K and a V
    pool, 1 for a latent plane) and the block size. Not a setting and not
    a family's name; ``pages_per_chunk=`` overrides it in tests.
    """
    itemsize = jnp.dtype(dtype).itemsize
    copies = planes + (2 if itemsize == 1 else 0)  # a page's, in one slot
    keys = min(
        WALK_MAX_KEYS,
        WALK_BUFFER_BYTES // (2 * planes * row_lanes * itemsize),
        WALK_SEMAPHORES // (2 * copies) * block_size,
    )
    keys = max(keys, block_size)
    return 1 << (keys.bit_length() - 1)


def walk_pages_a_turn(pages_per_chunk: int) -> int:
    """Pages a turn of the row walk: the largest power of two that divides
    a chunk's pages, at most WALK_PAGES_A_TURN (128 keys at blocks of 16,
    256 at an int8 pool's 32: whole sublane tiles of every stored dtype)."""
    return min(pages_per_chunk & -pages_per_chunk, WALK_PAGES_A_TURN)


def walk_block(heads: int, group: int) -> 'tuple[str, int | None]':
    """The form of the row walk's softmax block and the turns it folds at a
    time, from a call's static shapes alone: its KV heads (128-lane bands of
    a pool row: two 64-wide heads are one) and the queries a KV head (a
    block of positions folded in, as ``models/sdar.py`` does, counts). Not
    a setting and not a family's name. ``('per_head', None)``: the span
    schedule's ``compute``, a KV head at a time over the whole chunk.
    ``('stacked', turns)``: ONE softmax for all heads' rows
    (:func:`_stacked_block`), ``turns`` turns of 8 pages a fold (None: the
    whole chunk), over the folds that hold a key the row sees.

    The cost model. A softmax block pays two lane reductions, a rescale of
    the accumulator and the latency of two dependent matmuls whatever its
    width, so what counts is how often a row pays them: per head it is
    ``heads`` times a chunk, stacked once a fold. The vector unit's work on
    the scores is the same either way (``heads x group x keys`` elements),
    and so is the matrix unit's, provided a head's products take its rows
    alone:

    * under 8 queries a head (a head's rows are no whole sublane tile) a
      head's scores come from ALL stacked rows, the others zeroed, summed
      over the heads: ``heads`` times the useful products, on a matrix unit
      that the few rows leave idle anyway. 4 turns a fold (PR 49's sweep:
      ``mistral7b`` 0.104 / 0.095 / 0.093 / 0.104 ms at 1 / 2 / 4 / 8).
    * from 8 up, where the queries a head are whole sublane tiles of 8 and
      there is more than one head, a head's scores are ITS rows against its
      band and the heads' arrays are laid one under the other (whole tiles:
      no data moves). Summed over zeroed rows instead the stacked form LOST
      there (``sdar`` 0.168 ms for the per-head block's 0.166).
    * one head (a latent plane) has nothing to stack, and narrower folds
      only multiply the fixed cost (``kanana`` 0.372 per head, 0.377-0.711
      by folds); queries a head that are no whole tiles (none in a cell)
      keep the per-head block unmeasured.

    The fold: 4 turns (512 keys) let a row skip the half of a chunk below
    its window's floor or past its context's end; the whole chunk pays the
    fixed costs once. Up to ``WALK_WHOLE_CHUNK_ROWS`` stacked rows (a ``[32,
    1024]`` float32 score array is half the vector registers) the whole
    chunk is faster; above, the two are level where rows see whole chunks
    and 4 turns win under a window. Kernel alone on one v5e chip, ms a call,
    per head / stacked at 1 / 2 / 4 / 8 turns (PERF.md section 6, PR 55):

    ==============================  ========  =====  =====  =========  =========
    shape (rows, heads x queries)   per head  1      2      4          8
    ==============================  ========  =====  =====  =========  =========
    ``lfm2`` (96, 4 x 8)            1.283     1.700  1.310  1.117      **1.034**
    ``laguna`` window (48, 8 x 8)   0.300     0.212  0.182  **0.175**  0.197
    ``solar`` (128, 8 x 8)          4.017     4.438  3.770  **3.719**  3.711
    ``sdar`` (48, 4 x 32)           0.166     0.180  0.150  **0.134**  0.132
    ==============================  ========  =====  =====  =========  =========

    Some of a block's heads stacked (2 or 4 of 8) lost to all of them at
    every shape."""
    if group < 8:
        return 'stacked', WALK_TURNS_A_FOLD
    if heads == 1 or group % 8:
        return 'per_head', None
    if heads * group <= WALK_WHOLE_CHUNK_ROWS:
        return 'stacked', None
    return 'stacked', WALK_TURNS_A_FOLD


def walk_block_form(query_heads: int, head_dim: int, row_lanes: int) -> str:
    """:func:`walk_block`'s form (``'stacked'`` or ``'per_head'``) for a
    decode call of ``query_heads`` queries a row (a block of positions
    folded in counts) over a pool whose rows are ``row_lanes`` wide, as the
    kernel's wrapper reckons its heads: a latent plane's row is ONE head
    (``head_dim`` is the row), two 64-wide heads share a 128-lane band. For
    the engine's telemetry."""
    band = 128 if _pairs_heads(head_dim, row_lanes) else head_dim
    heads = row_lanes // band
    return walk_block(heads, query_heads // heads)[0]


def _default_keys_a_step(k_data, latent, walk) -> int:
    if walk:
        return walk_keys_a_step(
            k_data.shape[-1], k_data.dtype, planes=1 if latent else 2,
            block_size=k_data.shape[1],
        )
    return 512 if latent else 128


def _div(x, by):
    """``x // by`` of two non-negative ints as ONE equation (``//`` traces
    ten for the signs)."""
    return jax.lax.div(x, jnp.int32(by))


def _stacked_block(r: _KernelRefs, p_lo, p_hi, block_size, pages_per_chunk,
                   group, turns, scale, logit_softcap, value_lanes, quantized):
    """The row walk's online-softmax block over the KV heads' query rows
    STACKED (:func:`walk_block`): ``block(slot, chunk)`` folds into
    ``r.acc[0]``, ``r.m[0]``, ``r.l[0]`` (``[heads x group, ..]``) the
    FOLDS of ``chunk`` (``turns`` turns each, or the most that divides a
    chunk's turns; None: the whole chunk) that hold a page of ``[p_lo,
    p_hi)``, what the grid step's row sees.

    The heads' scores make one ``[R, fold_keys]`` array: one mask, one pair
    of lane reductions and one rescale a fold for all heads. Where the
    queries arrive a head (``r.q`` is ``[heads, group, Hd]``: a head's rows
    are whole sublane tiles) a head's scores are its rows against its lane
    band of the keys, the heads' arrays laid one under the other, and its
    output its rows of the probabilities against its band of the values.
    Where they arrive stacked (``[1, R, Hd]``: under 8 queries a head) a
    head's scores come from ITS rows of the stacked queries (the others
    zeroed), summed over the heads, and its output rows are kept of ALL
    probabilities against its band. The mathematics is the span schedule's
    ``compute``: float32 scores, maxima, sums and accumulator, probabilities
    cast to the pages' dtype for the second product, an int8 page's scale
    on its columns.
    """
    import jax.experimental.pallas as pl

    seq, win = pl.program_id(0), r.window[0]
    head_dim = r.q.shape[-1]
    heads = r.k_buf.shape[-1] // head_dim
    a_head = r.q.shape[0] > 1  # the queries arrive a head, not stacked
    turn_pages = walk_pages_a_turn(pages_per_chunk)
    fold_pages = pages_per_chunk if turns is None else turn_pages * max(
        n for n in range(1, turns + 1)  # whole folds a chunk
        if pages_per_chunk // turn_pages % n == 0
    )
    fold_keys = fold_pages * block_size
    row_head = _div(
        jax.lax.broadcasted_iota(jnp.int32, (r.acc.shape[1], 1), 0), group
    )  # [R, 1]: the KV head a stacked row belongs to
    column = jax.lax.broadcasted_iota(jnp.int32, (1, fold_keys), 1)

    def page_scales(scale_buf, slot, page0, h):
        """[1, fold_keys]: head ``h``'s scale of each key's page."""
        col_page = _div(column, block_size)
        vec = jnp.zeros((1, fold_keys), jnp.float32)
        for p in range(fold_pages):  # static unroll
            vec = jnp.where(col_page == p, scale_buf[slot, page0 + p, h], vec)
        return vec

    def fold(slot, chunk, t):
        keys = pl.ds(pl.multiple_of(t * fold_keys, fold_keys), fold_keys)
        q0 = r.q_start[seq]
        # absolute key positions; every stacked row is the row's ONE query
        kvp = chunk * (pages_per_chunk * block_size) + t * fold_keys + column
        valid = (kvp < r.context_lens[seq]) & (kvp <= q0)
        valid = valid & ((kvp > q0 - win) | (win <= 0))
        q = None if a_head else r.q[0]  # [R, Hd]
        scores, parts = None, []
        for h in range(heads):  # static unroll over KV heads
            kh = r.k_buf[slot, keys, h * head_dim:(h + 1) * head_dim]
            if a_head:
                own = r.q[h]  # [group, Hd]
            else:
                own = q if heads == 1 else jnp.where(
                    row_head == h, q, jnp.zeros_like(q)
                )
            part = jax.lax.dot_general(
                own, kh.astype(own.dtype),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [group, C], or [R, C] that is zero outside head h's rows
            if quantized:
                part = part * page_scales(r.ks_buf, slot, t * fold_pages, h)
            if a_head:
                parts.append(part)
            else:
                scores = part if scores is None else scores + part
        if a_head:
            scores = jnp.concatenate(parts, axis=0)
        scores = scores * scale
        if logit_softcap is not None:
            cap = jnp.float32(logit_softcap)
            scores = jnp.tanh(scores / cap) * cap
        scores = jnp.where(valid, scores, -jnp.inf)
        m_prev = r.m[0]  # [R, 128] lane-replicated
        new_m = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        # a fold holds a key the row sees, so ``new_m`` is finite
        correction = jnp.exp(m_prev - new_m)  # m_prev=-inf -> 0
        probs = jnp.exp(scores - new_m[:, :1])  # masked lanes -> 0
        r.l[0] = r.l[0] * correction + jnp.sum(probs, axis=-1, keepdims=True)
        out, parts = None, []
        for h in range(heads):
            if value_lanes is not None:  # the values: the band's first lanes
                vh = r.k_buf[slot, keys, h * head_dim:h * head_dim + value_lanes]
            else:
                vh = r.v_buf[slot, keys, h * head_dim:(h + 1) * head_dim]
            weights = probs[h * group:(h + 1) * group] if a_head else probs
            if quantized:
                weights = weights * page_scales(r.vs_buf, slot, t * fold_pages, h)
                vh = vh.astype(r.q.dtype)
            part = jax.lax.dot_general(
                weights.astype(vh.dtype), vh,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [group, Hd], or [R, Hd] of which head h's rows are its output
            if a_head:
                parts.append(part)
            else:
                if heads > 1:
                    part = jnp.where(row_head == h, part, 0.0)
                out = part if out is None else out + part
        if a_head:
            out = jnp.concatenate(parts, axis=0)
        r.acc[0] = r.acc[0] * correction[:, :1] + out
        r.m[0] = new_m

    def block(slot, chunk):
        first = chunk * pages_per_chunk

        def one(t, carry):
            fold(slot, chunk, t)
            return carry

        jax.lax.fori_loop(
            _div(jnp.maximum(p_lo - first, 0), fold_pages),
            _div(
                jnp.minimum(p_hi - first, pages_per_chunk) + fold_pages - 1,
                fold_pages,
            ),
            one, 0,
        )

    return block


def _walk_row(r: _KernelRefs, compute, block_size, pages_per_chunk, group,
              scale, logit_softcap, value_lanes, quantized):
    """The paged kernel's schedule for a span of one (decode rows): grid
    over ROWS, and inside a grid step a loop over the chunks that row
    holds and can see, from the one with its sliding-window floor to the
    one with its context's end. The trip count is read from the
    scalar-prefetched lengths, so no step exists that fetches nothing
    (the grid over the widest table's chunks ran 1,024 steps a
    ``mistral7b`` decode call of which about 85 fetched); a row with no
    sequence iterates nothing and emits exact zeros.

    Within a chunk the unit is a TURN of :func:`walk_pages_a_turn` pages.
    Only the pages the row sees are copied, the whole turns among them as
    straight-line code a turn and the ragged run at either end by halves
    (4, 2, 1 pages): a chunk wider than what the row has left moves no
    byte for the rest. Two buffer slots alternate along the whole CALL's
    walk (``r.slot`` carries the next one across grid steps, as the
    buffers and their semaphores are carried): while a chunk is computed
    the next one's copies are in flight, the row's next chunk or, on its
    last, the first chunk of the next row that has one. Only the call's
    first chunk is waited for with nothing behind it.

    The online-softmax block has two forms, chosen by :func:`walk_block`
    from the KV heads and the queries a KV head. Stacked (``r.acc``,
    ``r.m``, ``r.l`` hold ONE block of ``heads x group`` rows, and ``r.q``
    too unless a head's rows are whole sublane tiles):
    :func:`_stacked_block`, over the folds of a chunk that hold a key the
    row sees, so that the masked rest of a part-filled chunk costs no
    compute either. Otherwise (one head, or queries a head from 8 up that
    are no whole tiles) ``compute(slot, chunk)``, the span schedule's own
    block (``_ragged_paged_attn_kernel``) over the whole chunk, traced once
    with a traced slot: the mask discards what the buffer still holds past
    the row's end.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    seq, rows = pl.program_id(0), pl.num_programs(0)
    win = r.window[0]
    turn_pages = walk_pages_a_turn(pages_per_chunk)
    # (pool in HBM, its two-slot buffer) of what a call has: K and V or a
    # latent plane, and an int8 pool's scale rows.
    planes, scales = (
        [pair for pair in pairs if pair[0] is not None]
        for pairs in (
            ((r.k_cache, r.k_buf), (r.v_cache, r.v_buf)),
            ((r.k_scale, r.ks_buf), (r.v_scale, r.vs_buf)),
        )
    )

    def visible(row):
        """Pages ``[p_lo, p_hi)`` of ``row``'s table that its one query
        sees (``(position - window, position]`` below the context's end):
        ``p_hi == p_lo`` for a row with no valid query."""
        q0 = r.q_start[row]
        lo = jnp.where(win > 0, jnp.maximum(q0 - win + 1, 0), 0)
        hi = jnp.minimum(r.context_lens[row], q0 + 1)
        p_lo = _div(lo, block_size)
        live = (r.q_lens[row] > 0) & (hi > lo)
        return p_lo, jnp.where(live, _div(hi + block_size - 1, block_size), p_lo)

    def next_live(row):
        """The first row at or after ``row`` that sees a page (``rows``
        when none does): scalar reads only."""
        def dead(t):
            p_lo, p_hi = visible(jnp.minimum(t, rows - 1))
            return (t < rows) & (p_hi <= p_lo)

        return jax.lax.while_loop(dead, lambda t: t + 1, row)

    def copies(row, chunk, p_lo, p_hi, slot, start):
        """Start, or wait for, the copy of every page of ``chunk`` within
        ``[p_lo, p_hi)`` into ``slot``: one contiguous whole-page
        descriptor a page and plane, and an int8 pool's scale rows beside
        them. No descriptor names a block outside that range (a windowed
        group has given back what lies below it). The whole turns go as
        straight-line code a turn, which the compiler can schedule; the
        pages short of a whole turn, below the first (a window's floor
        inside a turn) and above the last (the context's end), by halves
        of a turn: three branches at most, each straight-line, where a
        loop turn a page stood (PERF.md section 6, PRs 38 and 49)."""
        first = chunk * pages_per_chunk
        # the row's places in the chunk, [lo, hi), and its whole turns
        lo = jnp.maximum(p_lo - first, 0)
        hi = jnp.minimum(p_hi - first, pages_per_chunk)
        turn_lo = _div(lo + turn_pages - 1, turn_pages)
        turn_hi = _div(hi, turn_pages)
        head_end = jnp.minimum(hi, turn_lo * turn_pages)
        tail = jnp.maximum(turn_hi * turn_pages, head_end)

        def one(p):  # ``p``: the page's place in the chunk
            # a wait reads the semaphore and the buffer's size alone
            page_id = r.block_tables[row, first + p] if start else 0
            at = pl.ds(pl.multiple_of(p * block_size, block_size), block_size)
            for i, (pool, buf) in enumerate((*planes, *scales)):
                copy = pltpu.make_async_copy(
                    pool.at[page_id], buf.at[slot, at if i < len(planes) else p],
                    r.sems.at[slot, p, i],
                )
                copy.start() if start else copy.wait()

        def ragged(end, carry):
            p = jnp.where(end == 0, lo, tail)
            n = jnp.where(end == 0, head_end - lo, hi - tail)
            for bit in range(1, turn_pages.bit_length()):
                half = turn_pages >> bit  # 4, 2, 1 pages of a turn of 8

                @pl.when(n & half != 0)
                def _(p=p, half=half):
                    for j in range(half):
                        one(p + j)

                p = p + (n & half)
            return carry

        def turn(t, carry):
            for j in range(turn_pages):
                one(t * turn_pages + j)
            return carry

        # the head exists only under a window, the tail where the row ends
        # inside a turn: a chunk seen whole takes neither
        jax.lax.fori_loop(
            jnp.where(head_end > lo, 0, 1), jnp.where(hi > tail, 2, 1),
            ragged, 0,
        )
        jax.lax.fori_loop(turn_lo, turn_hi, turn, 0)

    @pl.when(seq == 0)
    def _():
        # What meets a zero probability unmasked must be finite: a page
        # that is not fetched leaves its band of the value buffer as it
        # was, and fresh VMEM holds anything. After this only pool bytes
        # land there.
        for buf in (planes[-1][1], *(buf for _, buf in scales[1:])):
            buf[...] = jnp.zeros_like(buf)
        r.slot[0] = 0  # the slot the walk waits in next
        r.slot[1] = 0  # whether the call's first chunk has been started

    p_lo, p_hi = visible(seq)
    c_lo = _div(p_lo, pages_per_chunk)
    c_hi = _div(p_hi + pages_per_chunk - 1, pages_per_chunk)

    block = compute  # (slot, chunk): the span schedule's, a head at a time
    form, turns = walk_block(r.k_buf.shape[-1] // r.q.shape[-1], group)
    if form == 'stacked':
        block = _stacked_block(
            r, p_lo, p_hi, block_size, pages_per_chunk, group, turns, scale,
            logit_softcap, value_lanes, quantized,
        )

    @pl.when(p_hi <= p_lo)
    def _():
        # No valid query: no chunk, no wait, exact zeros (finite, so a pad
        # row can never poison a reduction downstream).
        r.out[...] = jnp.zeros_like(r.out)

    @pl.when(p_hi > p_lo)
    def _():
        def chunk(ci, slot):
            # Before chunk ci is waited for, start what is computed next
            # into the other slot: this row's chunk ci + 1 or, on its
            # last chunk, the first chunk of the next row that has one.
            nxt = jax.lax.cond(
                ci + 1 < c_hi, lambda: seq, lambda: next_live(seq + 1)
            )

            @pl.when(nxt < rows)
            def _():
                n_lo, n_hi = visible(nxt)
                n_chunk = jnp.where(
                    nxt == seq, ci + 1, _div(n_lo, pages_per_chunk)
                )
                copies(nxt, n_chunk, n_lo, n_hi, 1 - slot, True)

            @pl.when(ci >= c_lo)
            def _():
                copies(seq, ci, p_lo, p_hi, slot, False)
                block(slot, ci)

            return 1 - slot

        # The call's first live row has nothing in flight: it takes one
        # turn more, in front, that only starts its own first chunk.
        r.slot[0] = jax.lax.fori_loop(
            c_lo - 1 + r.slot[1], c_hi, chunk, r.slot[0]
        )
        r.slot[1] = 1
        if form == 'stacked':  # a KV head's rows of the stacked block
            r.acc[0] = r.acc[0] / jnp.maximum(r.l[0][:, :1], 1e-9)
            for h in range(r.out.shape[0]):
                r.out[h] = r.acc[0, h * group:(h + 1) * group].astype(
                    r.out.dtype
                )
        else:
            for h in range(r.acc.shape[0]):
                out = r.acc[h] / jnp.maximum(r.l[h][:, :1], 1e-9)
                r.out[h] = out.astype(r.out.dtype)


def decode_attention(  # distlint: traced
    q: jnp.ndarray,  # [B, num_heads, head_dim]: one query a row
    k_cache: jnp.ndarray,
    v_cache: 'jnp.ndarray | None',
    block_tables: jnp.ndarray,  # [B, max_blocks] int32
    context_lens: jnp.ndarray,  # [B] valid tokens incl. the query's
    positions: jnp.ndarray,  # [B] the query's position, context_lens - 1
    *,
    backend: str,
    layer=None,
    sliding_window: 'int | jnp.ndarray | None' = None,
    scale: float | None = None,
    logit_softcap: float | None = None,
    value_lanes: int | None = None,
) -> jnp.ndarray:
    """THE way into the kernel for a decode row (every family's
    ``_decode_core``): :func:`paged_attention_xla` under ``'xla'``, and
    otherwise :func:`ragged_paged_attention` at a span of one, the kernel's
    row walk (``backend`` is a RESOLVED selector value, as there). A plain
    function: no jit and no scope of its own, so the kernel's call keeps the
    name and the scope its caller gives it. Returns ``[B, num_heads,
    head_dim or value_lanes]``."""
    if backend == 'xla':
        return paged_attention_xla(
            q, k_cache, v_cache, block_tables, context_lens,
            sliding_window=sliding_window, scale=scale,
            logit_softcap=logit_softcap, value_lanes=value_lanes, layer=layer,
        )
    return ragged_paged_attention(
        q[:, None], k_cache, v_cache, block_tables, context_lens,
        positions[:, None], sliding_window=sliding_window, scale=scale,
        logit_softcap=logit_softcap, backend=backend, value_lanes=value_lanes,
        layer=layer,
    )[:, 0]


def _write_token_kv_quantized(k_cache, v_cache, new_k, new_v, block_ids,
                              offsets):  # distlint: traced
    """Quantize-at-write for the decode path: rescale-on-append.

    Each touched block keeps a RUNNING absmax (its scale only grows):
    the appended row's per-head absmax joins the block's current scale,
    the block's existing int8 rows are ratio-multiplied into the new
    units (one gathered [B, bs, nkv, hd] rescale — never a re-walk of
    the original activations), and the fresh row is quantized once at
    the final scale. A row landing at block offset 0 starts a fresh
    block, so its inherited scale resets to 0. Frozen/dead rows arrive
    routed to the trash block 0 (duplicate scatter indices land there
    nondeterministically — garbage, but finite: scales are amax/127 and
    the guarded quant/rescale divisions can never mint a NaN for the
    masked softmax to multiply).
    """

    def write_one(cache, new):
        amax = jnp.max(
            jnp.abs(new.astype(jnp.float32)), axis=-1
        )  # [B, nkv]
        scale_before = jnp.where(
            (offsets == 0)[:, None], 0.0, cache.scale[block_ids]
        )
        new_scale = jnp.maximum(scale_before, amax / KV_QUANT_MAX)
        blocks = _rescale_int8_blocks(
            cache.data[block_ids], scale_before, new_scale
        )
        data = cache.data.at[block_ids].set(blocks)
        data = data.at[block_ids, offsets].set(
            fold_heads(quantize_kv_rows(new, new_scale))
        )
        return QuantizedKV(data, cache.scale.at[block_ids].set(new_scale))

    return write_one(k_cache, new_k), write_one(v_cache, new_v)


def write_token_kv(  # distlint: traced
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    new_k: jnp.ndarray,  # [B, num_kv_heads, head_dim]
    new_v: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    positions: jnp.ndarray,  # [B] token index being written
    layer=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter one new token's K/V per sequence into its paged block
    (quantizing at write time for int8 :class:`QuantizedKV` pools). The
    new rows are folded to the pool's ``num_kv_heads * head_dim`` rows;
    the pool is only scattered into. A latent pool is one plane:
    ``v_cache`` and ``new_v`` None, ``new_k [B, 1, row]`` the tokens' rows,
    and None comes back in V's place. A stacked pool ``[L, ...]`` goes in
    and comes back whole: the rows are written into ``layer``'s pages of
    it, in place (:func:`_layer_pages`)."""
    k_pages, v_pages, base = _layer_pages(k_cache, v_cache, layer)
    block_size = _kv_data(k_pages).shape[1]
    batch = positions.shape[0]
    block_ids = _in_layer(
        block_tables[jnp.arange(batch), positions // block_size], base
    )
    offsets = positions % block_size
    if isinstance(k_pages, QuantizedKV):
        k_pages, v_pages = _write_token_kv_quantized(
            k_pages, v_pages, new_k, new_v, block_ids, offsets
        )
        return _like(k_pages, k_cache), _like(v_pages, v_cache)
    k_pages = k_pages.at[block_ids, offsets].set(
        fold_heads(new_k).astype(k_pages.dtype)
    )
    if v_pages is not None:  # a latent pool has no V plane
        v_pages = v_pages.at[block_ids, offsets].set(
            fold_heads(new_v).astype(v_pages.dtype)
        )
    return _like(k_pages, k_cache), _like(v_pages, v_cache)


def write_chunk_kv(  # distlint: traced
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    new_k: jnp.ndarray,  # [B, S, num_kv_heads, head_dim] tail K
    new_v: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, max_blocks]
    positions: jnp.ndarray,  # [B, S] absolute position per tail token
    valid: jnp.ndarray,  # [B, S] bool — padding rows/tokens route to trash
    layer=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a batch of ragged spans' K/V into their paged blocks.

    The multi-token sibling of :func:`write_token_kv` and the write half
    of the ragged path (prefix-cache tail prefill, chunked prefill, and
    chunk rows riding mixed serving windows): ``valid`` carries the
    per-row raggedness — invalid positions write to the reserved trash
    block 0, the same pad-safety contract as :func:`write_prefill_kv`.
    A latent pool is one plane (``v_cache`` and ``new_v`` None). A stacked
    pool ``[L, ...]`` goes in and comes back whole, written in ``layer``'s
    pages (:func:`_layer_pages`): that layer's block 0 is the trash.
    """
    k_pages, v_pages, base = _layer_pages(k_cache, v_cache, layer)
    block_size = _kv_data(k_pages).shape[1]
    b, s = positions.shape
    block_ids = _in_layer(
        jnp.where(
            valid,
            jnp.take_along_axis(block_tables, positions // block_size, axis=1),
            0,
        ),
        base,
    )
    offsets = jnp.where(valid, positions % block_size, 0)
    if isinstance(k_pages, QuantizedKV):
        k_pages, v_pages = _write_chunk_kv_quantized(
            k_pages, v_pages, new_k, new_v, block_tables, positions,
            valid, block_ids, offsets, base,
        )
        return _like(k_pages, k_cache), _like(v_pages, v_cache)
    flat_blocks = block_ids.reshape(-1)
    flat_offsets = offsets.reshape(-1)
    k_flat = fold_heads(new_k).reshape(b * s, -1)
    k_pages = k_pages.at[flat_blocks, flat_offsets].set(
        k_flat.astype(k_pages.dtype)
    )
    if v_pages is not None:  # a latent pool has no V plane
        v_flat = fold_heads(new_v).reshape(b * s, -1)
        v_pages = v_pages.at[flat_blocks, flat_offsets].set(
            v_flat.astype(v_pages.dtype)
        )
    return _like(k_pages, k_cache), _like(v_pages, v_cache)


def _write_chunk_kv_quantized(k_cache, v_cache, new_k, new_v, block_tables,
                              positions, valid, block_ids, offsets,
                              base=None):  # distlint: traced
    """Ragged-span quantize-at-write (the :func:`write_chunk_kv` int8
    path). A row's span covers a CONTIGUOUS run of at most
    ``S // block_size + 1`` blocks (spans are position-consecutive with
    trailing-pad ``valid`` masks — the same contract the Pallas kernel
    scalar-prefetches one start position per row on), so the touched set
    is a static-width gather: per touched block take the running-absmax
    max of the block's prior scale (0 when the span covers the block's
    offset 0 — a fresh block) and the span tokens landing in it, rescale
    the gathered int8 rows once, scatter them back, then scatter the new
    tokens quantized at the final per-block scales. Dead rows / dead
    touched slots route to the trash block 0 exactly like the
    full-precision path (finite garbage, see
    :func:`_write_token_kv_quantized`). ``block_ids`` and the caches are
    :func:`_layer_pages`'s; ``base`` places the touched blocks likewise."""
    block_size = k_cache.data.shape[1]
    b, s = positions.shape
    max_blocks = block_tables.shape[1]
    nt = s // block_size + 1  # static max blocks a span can touch
    start_blk = positions[:, 0] // block_size  # [B] first logical block
    touched = start_blk[:, None] + jnp.arange(nt)[None, :]  # [B, nt]
    touched_cl = jnp.clip(touched, 0, max_blocks - 1)
    last_pos = jnp.max(jnp.where(valid, positions, -1), axis=1)  # [B]
    live = (touched <= last_pos[:, None] // block_size) & (
        last_pos[:, None] >= 0
    )
    phys = _in_layer(
        jnp.where(
            live, jnp.take_along_axis(block_tables, touched_cl, axis=1), 0
        ),
        base,
    )  # [B, nt] physical touched blocks (dead -> trash)
    fresh = touched * block_size >= positions[:, :1]  # span covers row 0
    tb = jnp.clip(
        positions // block_size - start_blk[:, None], 0, nt - 1
    )  # [B, S] touched-slot index per token
    onehot = (
        tb[:, :, None] == jnp.arange(nt)[None, None, :]
    ) & valid[:, :, None]  # [B, S, nt]

    def write_one(cache, new):
        amax_tok = jnp.max(
            jnp.abs(new.astype(jnp.float32)), axis=-1
        )  # [B, S, nkv]
        contrib = jnp.max(
            jnp.where(onehot[..., None], amax_tok[:, :, None, :], 0.0),
            axis=1,
        )  # [B, nt, nkv] span absmax per touched block
        scale_before = jnp.where(fresh[..., None], 0.0, cache.scale[phys])
        new_scale = jnp.maximum(scale_before, contrib / KV_QUANT_MAX)
        blocks = _rescale_int8_blocks(
            cache.data[phys], scale_before, new_scale
        )
        flat_phys = phys.reshape(-1)
        data = cache.data.at[flat_phys].set(
            blocks.reshape(-1, *blocks.shape[2:])
        )
        scale = cache.scale.at[flat_phys].set(
            new_scale.reshape(-1, new_scale.shape[-1])
        )
        scale_tok = jnp.take_along_axis(
            new_scale, tb[:, :, None], axis=1
        )  # [B, S, nkv]
        q = fold_heads(quantize_kv_rows(new, scale_tok))
        data = data.at[block_ids.reshape(-1), offsets.reshape(-1)].set(
            q.reshape(b * s, -1)
        )
        return QuantizedKV(data, scale)

    return write_one(k_cache, new_k), write_one(v_cache, new_v)


def write_prefill_kv(  # distlint: traced
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    k_seq: jnp.ndarray,  # [S, num_kv_heads, head_dim] one sequence's K
    v_seq: jnp.ndarray,
    block_table_row: jnp.ndarray,  # [max_blocks]
    length: jnp.ndarray,  # scalar — valid tokens in k_seq
    layer=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a prefilled sequence's K/V into its blocks (pad-safe).

    Padded positions (``>= length``) are routed to the TRASH BLOCK: block 0
    is reserved by the allocator (never handed to a sequence), so garbage
    writes land there harmlessly. Clamping to a valid slot instead would race
    real data through XLA's nondeterministic duplicate-index scatter.
    A latent pool is one plane (``v_cache`` and ``v_seq`` None). A stacked
    pool ``[L, ...]`` goes in and comes back whole, written in ``layer``'s
    pages (:func:`_layer_pages`).
    """
    k_pages, v_pages, base = _layer_pages(k_cache, v_cache, layer)
    seq_len = k_seq.shape[0]
    block_size = _kv_data(k_pages).shape[1]
    positions = jnp.arange(seq_len)
    valid = positions < length
    block_ids = _in_layer(
        jnp.where(valid, block_table_row[positions // block_size], 0), base
    )
    offsets = jnp.where(valid, positions % block_size, 0)
    if isinstance(k_pages, QuantizedKV):
        k_pages, v_pages = _write_prefill_kv_quantized(
            k_pages, v_pages, k_seq, v_seq, block_table_row, length,
            block_ids, offsets, valid, base,
        )
        return _like(k_pages, k_cache), _like(v_pages, v_cache)
    k_pages = k_pages.at[block_ids, offsets].set(
        fold_heads(k_seq).astype(k_pages.dtype)
    )
    if v_pages is not None:  # a latent pool has no V plane
        v_pages = v_pages.at[block_ids, offsets].set(
            fold_heads(v_seq).astype(v_pages.dtype)
        )
    return _like(k_pages, k_cache), _like(v_pages, v_cache)


def _write_prefill_kv_quantized(k_cache, v_cache, k_seq, v_seq,
                                block_table_row, length, block_ids,
                                offsets, valid, base=None):  # distlint: traced
    """Whole-sequence quantize-at-write (the :func:`write_prefill_kv`
    int8 path). A full prefill writes every block from its offset 0, so
    every touched block is FRESH: each block's scale is simply the
    absmax of its live rows (token → block is the static ``s //
    block_size`` map — no running-absmax bookkeeping needed), and each
    row quantizes once at its block's final scale. Pad rows and dead
    blocks route to the trash block 0 (finite garbage, same contract as
    the full-precision path)."""
    seq_len = k_seq.shape[0]
    block_size = k_cache.data.shape[1]
    nt = -(-seq_len // block_size)
    pad = nt * block_size - seq_len
    live_blk = jnp.arange(nt) * block_size < length
    phys = _in_layer(
        jnp.where(live_blk, block_table_row[jnp.arange(nt)], 0), base
    )

    def write_one(cache, seq):
        amax = jnp.max(jnp.abs(seq.astype(jnp.float32)), axis=-1)
        amax = jnp.where(valid[:, None], amax, 0.0)  # [S, nkv]
        contrib = jnp.pad(amax, ((0, pad), (0, 0))).reshape(
            nt, block_size, -1
        ).max(axis=1)  # [nt, nkv]
        new_scale = contrib / KV_QUANT_MAX
        scale = cache.scale.at[phys].set(new_scale)
        scale_tok = jnp.repeat(new_scale, block_size, axis=0)[:seq_len]
        data = cache.data.at[block_ids, offsets].set(
            fold_heads(quantize_kv_rows(seq, scale_tok))
        )
        return QuantizedKV(data, scale)

    return write_one(k_cache, k_seq), write_one(v_cache, v_seq)


def _pairs_heads(head_dim: int, folded: int) -> bool:
    """Whether the Pallas kernel reads this pool's heads two to a lane tile:
    64-wide heads in a row of whole 128-lane tiles."""
    return head_dim == 64 and folded % 128 == 0


def _half_tile_heads(
    q, k_pages, v_pages, block_tables, context_lens, q_positions, q_lens,
    sliding_window, scale, logit_softcap, pages_per_chunk, span_tile,
    interpret,
):
    """64-wide heads through :func:`ragged_paged_attention_pallas`, from
    the pool as it is stored: rows of ``num_kv_heads * 64`` lanes, no head
    padded (``k_pages``/``v_pages`` and ``block_tables`` are
    :func:`_layer_pages`'s, as the kernel's wrapper has them).

    The kernel's unit is a 128-lane band of a page: a load or a DMA of half
    a tile is what Mosaic refuses or relayouts. So a band is handed to it
    as ONE key head of 128 dims that ``2 * group`` queries share: the
    queries of the band's first head widened with zeros behind, those of
    its second with zeros in front. A widened query's score against the
    band is its own head's score (the zeros meet the neighbour's lanes);
    its weighted sum over the band's values holds its head's output in its
    head's half, and the other half (the neighbour's values under this
    head's weights) is dropped here (the second head's half rolled down,
    then the lower half of every row). The MXU contracts and emits 128 lanes
    a pass either way, the softmax rows are the ``num_heads`` rows a
    kernel of 64-wide bands would have, and a page crosses HBM once. Both
    schedules (the grid over spans and the row walk) and
    :func:`walk_keys_a_step` see a pool of ``folded // 128`` heads of 128.
    An int8 pool keeps one scale a head, which a band of two heads cannot
    apply to its scores: refused by name.
    """
    b, s, num_heads, head_dim = q.shape
    folded = _kv_data(k_pages).shape[-1]
    if not _pairs_heads(head_dim, folded):
        raise ValueError(
            'pallas paged attention needs heads of whole 128-lane tiles, or '
            '64-wide heads in a pool row of whole tiles (two heads a tile); '
            f'got head_dim {head_dim} in rows of {folded} lanes'
        )
    if isinstance(k_pages, QuantizedKV):
        raise ValueError(
            'kv_cache_dtype=int8 at 64-wide heads is not implemented in the '
            'pallas kernel (two heads share a lane tile and each has a '
            "scale of its own); use attn_backend='xla'"
        )
    tiles = folded // 128
    group = num_heads // (2 * tiles)  # queries a 64-wide KV head
    halves = q.reshape(b, s, tiles, 2, group, head_dim)
    zeros = jnp.zeros_like(halves[:, :, :, 0])
    wide = jnp.stack(
        [
            jnp.concatenate([halves[:, :, :, 0], zeros], axis=-1),
            jnp.concatenate([zeros, halves[:, :, :, 1]], axis=-1),
        ],
        axis=3,
    )  # [B, S, tiles, 2, group, 128]: a band's 2 * group queries
    out = ragged_paged_attention_pallas(
        wide.reshape(b, s, num_heads, 2 * head_dim), k_pages, v_pages,
        block_tables, context_lens, q_positions, q_lens=q_lens,
        sliding_window=sliding_window,
        scale=head_dim ** -0.5 if scale is None else scale,
        logit_softcap=logit_softcap, pages_per_chunk=pages_per_chunk,
        span_tile=span_tile, interpret=interpret,
    ).reshape(b, s, tiles, 2, group, 2 * head_dim)
    # A band's second head has its output in the band's upper half: rolled
    # down by a head, every head's is the lower half of its rows. (Taken as
    # two half-tile slices stacked, ``[..., 0, :, :64]`` and ``[..., 1, :,
    # 64:]``, XLA's TPU compiler made ONE bitcast of the kernel's result,
    # which reads the lower half of EVERY row: PERF.md section 7, PR 55.)
    second = jnp.arange(2).reshape(1, 1, 1, 2, 1, 1) == 1
    out = jnp.where(second, jnp.roll(out, -head_dim, axis=-1), out)
    return out[..., :head_dim].reshape(b, s, num_heads, head_dim)


def _block_ceiling(positions, block_length: int):
    """The last key a query at ``positions`` sees: itself under the causal
    ceiling (``block_length`` 1, the positions as they came: nothing is
    traced), else the last position of its block of ``block_length``, ``(p
    // block_length + 1) * block_length - 1``: the BLOCK-CAUSAL mask of a
    model that decides a block's positions together (``models/sdar.py``).
    ``ragged_paged_attention`` and both its twins take ``block_length`` for
    prefill spans (a static; the kernel's tile ceiling and its mask read it
    in the lines they had, so a causal program lowers to the text it had); a
    span of one and 64-wide heads keep the causal ceiling. Defined at the
    file's end: no line above moves."""
    if block_length == 1:
        return positions
    return (positions // block_length + 1) * block_length - 1
