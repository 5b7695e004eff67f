"""The row walk (``test_ragged_row_walk.py``) against the XLA twin by pool
and knob, and by queries a head."""

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.ops.paged_attention import (
    ragged_paged_attention_pallas,
    ragged_paged_attention_xla,
)
from test_ragged_row_walk import _EDGE_PAGES, _WALK_BS, _assert_walk_parity, _edge_setup, _walk_setup


@pytest.mark.parametrize('window', [None, 6], ids=['nowin', 'win6'])
@pytest.mark.parametrize(
    'variant',
    ['stacked', 'stacked_traced', 'latent', 'int8', 'softcap', 'scale',
     'latent_turns', 'int8_turns', 'heads64_turns'],
)
def test_row_walk_parity_by_pool_and_knob(rng, variant, window):
    """One walk for every span-1 caller: a stacked pool with its layer
    (a Python int, and traced under a rolled scan), a latent plane with
    ``value_lanes``, an int8 pool with its scale rows, softcap, a
    caller's scale. ``*_turns``: the pool at the chip's block and turn
    (``_EDGE_CTX``: two turns a chunk, the contexts on their edges), and
    64-wide heads, two to a lane tile."""
    from distllm_tpu.ops.paged_attention import QuantizedKV

    kwargs, jit_layer = {}, None
    setup, pages = _walk_setup, 2
    if variant.endswith('_turns'):
        setup, pages = _edge_setup, _EDGE_PAGES
        variant = variant[:-len('_turns')]
    if variant == 'latent':  # one head of 256 lanes, values its first 128
        q, k, _, bt, ctx, pos, q_lens = setup(rng, nh=4, nkv=1, hd=256)
        v, kwargs = None, {'value_lanes': 128}
    elif variant == 'heads64':
        q, k, v, bt, ctx, pos, q_lens = setup(rng, nh=8, nkv=4, hd=64)
    else:
        q, k, v, bt, ctx, pos, q_lens = setup(rng)
    if variant.startswith('stacked'):
        other_k, other_v = k[::-1], v[::-1]
        k, v = jnp.stack([other_k, k, other_v]), jnp.stack([other_v, v, k])
        jit_layer = jnp.int32(1) if variant == 'stacked_traced' else 1
    elif variant == 'int8':
        k, v = (
            QuantizedKV(
                jnp.asarray(
                    rng.integers(-127, 128, size=pool.shape), jnp.int8
                ),
                jnp.asarray(
                    rng.uniform(0.01, 0.03, size=(pool.shape[0], 2)),
                    jnp.float32,
                ),
            )
            for pool in (k, v)
        )
    elif variant == 'softcap':
        kwargs = {'logit_softcap': 30.0}
    elif variant == 'scale':
        kwargs = {'scale': 0.25}

    def run(fn, **more):
        call = lambda layer: fn(  # noqa: E731
            q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window,
            layer=layer, **kwargs, **more,
        )
        if variant == 'stacked_traced':
            return jax.jit(call)(jit_layer)
        return call(jit_layer)

    out = run(
        ragged_paged_attention_pallas, pages_per_chunk=pages, interpret=True
    )
    _assert_walk_parity(out, run(ragged_paged_attention_xla), q_lens)


@pytest.mark.parametrize('setup', ['short', 'turn_edges'])
@pytest.mark.parametrize(
    'nh,nkv,hd',
    [(8, 2, 8), (10, 2, 8), (12, 2, 8), (16, 2, 8), (32, 1, 256),
     (16, 16, 8)],
    ids=['group4', 'group5', 'group6', 'group8', 'group32_latent',
         'group1_16heads'],
)
def test_row_walk_parity_by_queries_a_head(rng, nh, nkv, hd, setup):
    """The head shapes that take the walk in the cells: 4 (mistral7b,
    granite), 5 (falcon-h1), 6 and 8 (laguna's full and window layers)
    queries a KV head, 32 queries on one latent head (kanana), and ONE
    query a KV head at 16 heads (ouro); each over short rows in chunks
    of 16 keys and over the turn's and the chunk's edges
    (``_EDGE_CTX``)."""
    setup = _walk_setup if setup == 'short' else _edge_setup
    q, k, v, bt, ctx, pos, q_lens = setup(rng, nh=nh, nkv=nkv, hd=hd)
    kwargs = {}
    if nkv == 1:
        v, kwargs = None, {'value_lanes': 128}
    ref = ragged_paged_attention_xla(
        q, k, v, bt, ctx, pos, q_lens=q_lens, **kwargs
    )
    out = ragged_paged_attention_pallas(
        q, k, v, bt, ctx, pos, q_lens=q_lens,
        pages_per_chunk=4 if k.shape[1] == _WALK_BS else _EDGE_PAGES,
        interpret=True, **kwargs,
    )
    _assert_walk_parity(out, ref, q_lens)


# ---- sdar (PR 54): a block-causal ceiling for prefill spans, and a block of
# 4 positions folded into a group of 8 through the row walk ----

@pytest.mark.parametrize('block_length', [1, 4])
@pytest.mark.parametrize('span, start', [(16, 0), (64, 32), (24, 8)])
def test_span_parity_under_a_block_causal_ceiling(rng, block_length, span, start):
    """A prefill span under ``block_length``: a query at ``p`` sees the keys
    before the end of its block, ``(p // B + 1) * B``, in the kernel's tile
    ceiling and its mask as in the XLA twin; 1 is the causal ceiling. Spans
    of whole blocks behind cached whole blocks (what ``models/sdar.py``
    prefills), several query tiles, and rows padded past their length."""
    nh, nkv, hd, block = 8, 2, 128, 16
    lens = (span, span - 8, 8)
    tables = [-(-(start + span) // block)] * len(lens)
    num_blocks = 1 + sum(tables)
    k, v = (
        jnp.asarray(rng.normal(size=(num_blocks, block, nkv * hd)), jnp.float32)
        for _ in range(2)
    )
    bt = (1 + np.arange(num_blocks - 1, dtype=np.int32)).reshape(len(lens), -1)
    ctx = jnp.asarray([start + n for n in lens], jnp.int32)
    pos = jnp.broadcast_to(start + jnp.arange(span)[None], (len(lens), span))
    q = jnp.asarray(rng.normal(size=(len(lens), span, nh, hd)), jnp.float32)
    q_lens = jnp.asarray(lens, jnp.int32)
    args = (q, k, v, jnp.asarray(bt), ctx, pos)
    ref = ragged_paged_attention_xla(*args, q_lens=q_lens, block_length=block_length)
    out = ragged_paged_attention_pallas(
        *args, q_lens=q_lens, block_length=block_length, interpret=True,
        span_tile=16,
    )
    for row, n in enumerate(lens):  # pad queries: zeros here, key 0 there
        np.testing.assert_allclose(
            np.asarray(out)[row, :n], np.asarray(ref)[row, :n], atol=2e-5, rtol=1e-4
        )
    causal = ragged_paged_attention_xla(*args, q_lens=q_lens)
    differs = np.abs(np.asarray(causal) - np.asarray(ref))[0].max(axis=(1, 2))
    if block_length == 1:
        assert differs.max() == 0.0
    else:  # a block's last query sees what the causal one does; its first more
        assert differs[block_length - 1::block_length].max() < 1e-6
        assert differs[0::block_length].min() > 1e-4


def test_row_walk_parity_at_32_queries_a_head(rng):
    """A block of 4 positions folded into a group of 8 (``models/sdar.py``'s
    denoise forward): 128 query rows on 4 KV heads of 128, every row seeing
    its whole context: the walk's per-head block over 32 rows."""
    nh, nkv, hd, block = 128, 4, 128, 16
    contexts = (20, 515, 1030, 64)
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
    q = jnp.asarray(rng.normal(size=(len(contexts), 1, nh, hd)), jnp.float32)
    args = (q, k, v, jnp.asarray(bt), ctx, ctx[:, None] - 1)
    ref = ragged_paged_attention_xla(*args)
    out = ragged_paged_attention_pallas(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)
