"""The fused ragged Pallas paged-attention kernel (interpret mode) against its
XLA twin over sliding windows: GQA grouping x static and traced windows on
ragged rows, and 7 queries a head around a window of 4096 at spans of 1 and
512. The rest of the parity matrix: ``test_ragged_attention.py``."""

import numpy as np
import pytest

import jax.numpy as jnp

from distllm_tpu.ops.paged_attention import (
    ragged_paged_attention_pallas,
    ragged_paged_attention_xla,
)
from test_ragged_attention import _assert_parity, _setup


# 1, 2, and the serving groups: 4 (mistral7b, granite), 6 and 8 (laguna's
# full and window layers) queries a KV head, over head-folded pools.
@pytest.mark.parametrize(
    'nh,nkv', [(4, 4), (4, 2), (8, 2), (12, 2), (16, 2)]
)
@pytest.mark.parametrize(
    'window',
    [None, 3, 'traced', 'traced_zero'],
    ids=['nowin', 'win3', 'traced', 'traced0'],
)
def test_ragged_parity_gqa_by_window(rng, nh, nkv, window):
    """GQA grouping × sliding-window variants, ragged q_lens rows."""
    q, k, v, bt, ctx, pos, q_lens = _setup(rng, nkv=nkv, nh=nh)
    if window == 'traced':
        window = jnp.int32(4)  # traced per-layer window (gemma2 shape)
    elif window == 'traced_zero':
        window = jnp.int32(0)  # traced disable: <= 0 means global
    ref = ragged_paged_attention_xla(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window
    )
    out = ragged_paged_attention_pallas(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window,
        interpret=True,
    )
    _assert_parity(out, ref, q_lens, q.shape[1])


@pytest.mark.parametrize('span', [1, 512], ids=['span1', 'span512'])
@pytest.mark.parametrize('window', [4096, None], ids=['win4096', 'nowin'])
def test_parity_at_7_queries_a_head_around_a_window_of_4096(rng, span, window):
    """SmallThinker's attention: 28 query heads on 4 KV heads of 128, blocks
    of 16, a window of 4096 (and none: the full
    layers). Rows that stay under the window, that cross it (a decode row
    at its very edge; a 512-token span that starts under it and ends past
    it) and that lie well past it, a row's 7 query rows a head never a
    whole sublane tile: the walk's stacked block over 28 rows at span 1, the
    span schedule's tile of 64 positions x 7 at 512."""
    nh, nkv, hd, block = 28, 4, 128, 16
    contexts = (1000, 4096, 4097, 4300, 9000) if span == 1 else (600, 4300, 5100)
    tables = [-(-c // block) for c in contexts]
    num_blocks = 1 + sum(tables)
    k, v = (
        jnp.asarray(rng.normal(size=(num_blocks, block, nkv * hd)), jnp.float32)
        for _ in range(2)
    )
    bt = np.zeros((len(contexts), max(tables)), np.int32)
    ids = rng.permutation(num_blocks - 1) + 1
    for row, n in enumerate(tables):
        bt[row, :n], ids = ids[:n], ids[n:]
    ctx = jnp.asarray(contexts, jnp.int32)
    pos = ctx[:, None] - span + jnp.arange(span)[None]
    q = jnp.asarray(rng.normal(size=(len(contexts), span, nh, hd)), jnp.float32)
    q_lens = jnp.full((len(contexts),), span, jnp.int32)
    args = (q, k, v, jnp.asarray(bt), ctx, pos)
    ref = ragged_paged_attention_xla(
        *args, q_lens=q_lens, sliding_window=window
    )
    out = ragged_paged_attention_pallas(
        *args, q_lens=q_lens, sliding_window=window, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4
    )
    if window:  # and the window is read: without it the past rows differ
        full = ragged_paged_attention_xla(*args, q_lens=q_lens)
        assert np.abs(np.asarray(full) - np.asarray(ref))[-1].max() > 1e-3
