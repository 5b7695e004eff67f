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
     'latent_turns', 'int8_turns', 'heads64_turns',
     'group8', 'softcap_group8', 'int8_group8', 'int8_group8_turns',
     'int8_group32_turns', 'lanes_group8', 'lanes_group8_turns',
     'lanes_group32_turns', 'heads64_group8_turns'],
)
def test_row_walk_parity_by_pool_and_knob(rng, variant, window):
    """One walk for every span-1 caller: a stacked pool with its layer
    (a Python int, and traced under a rolled scan), a latent plane with
    ``value_lanes``, an int8 pool with its scale rows, softcap, a
    caller's scale. ``*_turns``: the pool at the chip's block and turn
    (``_EDGE_CTX``: two turns a chunk, the contexts on their edges), and
    64-wide heads, two to a lane tile. ``*_group8``, ``*_group32``: the
    same pools and knobs at 8 and 32 queries a KV head on two heads, where
    the stacked block takes the queries a head and lays the heads' scores
    one under the other (``lanes``: K/V bands of 256 lanes whose values are
    the first 128, ``value_lanes`` on more than one head; ``heads64``: four
    64-wide heads of 4 queries, two bands of 8)."""
    from distllm_tpu.ops.paged_attention import QuantizedKV

    kwargs, jit_layer = {}, None
    setup, pages = _walk_setup, 2
    if variant.endswith('_turns'):
        setup, pages = _edge_setup, _EDGE_PAGES
        variant = variant[:-len('_turns')]
    heads = {}
    for group in (8, 32):
        if variant.endswith(f'_group{group}') or variant == f'group{group}':
            heads = {'nh': 2 * group, 'nkv': 2}
            variant = variant[:-len(f'_group{group}')] or 'plain'
    if variant == 'latent':  # one head of 256 lanes, values its first 128
        q, k, _, bt, ctx, pos, q_lens = setup(rng, nh=4, nkv=1, hd=256)
        v, kwargs = None, {'value_lanes': 128}
    elif variant == 'lanes':  # two heads' bands, the values their first lanes
        q, k, _, bt, ctx, pos, q_lens = setup(rng, hd=256, **heads)
        v, kwargs = None, {'value_lanes': 128}
    elif variant == 'heads64':
        q, k, v, bt, ctx, pos, q_lens = setup(
            rng, nh=16 if heads else 8, nkv=4, hd=64
        )
    else:
        q, k, v, bt, ctx, pos, q_lens = setup(rng, **heads)
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
     (16, 16, 8), (32, 4, 8), (64, 8, 8), (128, 4, 8), (24, 2, 8),
     (28, 4, 8)],
    ids=['group4', 'group5', 'group6', 'group8', 'group32_latent',
         'group1_16heads', 'group8_4heads', 'group8_8heads',
         'group32_4heads', 'group12', 'group7_4heads'],
)
def test_row_walk_parity_by_queries_a_head(rng, nh, nkv, hd, setup):
    """The head shapes that take the walk in the cells: 4 (mistral7b,
    granite), 5 (falcon-h1), 6 and 8 (laguna's full and window layers)
    queries a KV head, 32 queries on one latent head (kanana), ONE
    query a KV head at 16 heads (ouro), 8 on 4 heads (lfm2's bands: 32
    stacked rows, the whole chunk a fold), 8 on 8 (solar, laguna's window
    layers), 32 on 4 (sdar), 7 on 4 (smallthinker), and 12 on 2 (from 8 up
    and no whole tiles: the per-head block); each over short rows in
    chunks of 16 keys and over the turn's and the chunk's edges
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


# Blocks of 16 in ONE chunk of 64 pages, as on the chip: 8 turns of 128
# keys, two folds of 512. Contexts that end inside the first fold, on its
# last key, one key past it, in the chunk's last page, on the chunk's last
# key, and past it in a second chunk; a dead row between them.
_FOLD_CTX = (100, 511, 512, 513, 0, 1010, 1024, 1025, 1100)


@pytest.mark.parametrize('window', [None, 300, 700], ids=['nowin', 'win300', 'win700'])
@pytest.mark.parametrize(
    'nh,nkv,pool',
    [(8, 2, 'bf'), (32, 4, 'bf'), (64, 8, 'bf'), (64, 2, 'bf'),
     (32, 4, 'int8'), (64, 2, 'int8'), (64, 2, 'lanes')],
    ids=['group4', 'group8_whole_chunk', 'group8_8heads', 'group32',
         'group8_whole_chunk_int8', 'group32_int8', 'group32_lanes'],
)
def test_row_walk_parity_by_fold(rng, nh, nkv, pool, window):
    """The stacked block folds 4 turns (512 keys) of a chunk of 1,024 at a
    time and only the folds that hold a key the row sees, or the whole
    chunk up to 32 stacked rows of whole tiles: rows whose context ends
    inside the first fold, on a fold's edge, in the chunk's last page and
    past the chunk, with a window whose floor lies in the first fold or in
    the second, under 8 queries a head (the zeroed rows summed) and at 8
    and 32 (a head's rows, the heads' scores one under the other), over an
    int8 pool and with ``value_lanes``."""
    from distllm_tpu.ops.paged_attention import QuantizedKV, walk_block

    hd = 256 if pool == 'lanes' else 8
    q, k, v, bt, ctx, pos, q_lens = _walk_setup(
        rng, nh=nh, nkv=nkv, hd=hd, ctx=_FOLD_CTX, block=16, table=70,
        num_blocks=700,
    )
    kwargs = {}
    if pool == 'lanes':
        v, kwargs = None, {'value_lanes': 128}
    elif pool == 'int8':
        k, v = (
            QuantizedKV(
                jnp.asarray(rng.integers(-127, 128, size=p.shape), jnp.int8),
                jnp.asarray(
                    rng.uniform(0.01, 0.03, size=(p.shape[0], nkv)), jnp.float32
                ),
            )
            for p in (k, v)
        )
    whole = nkv * (nh // nkv) <= 32 and nh // nkv >= 8
    assert walk_block(nkv, nh // nkv) == ('stacked', None if whole else 4)
    ref = ragged_paged_attention_xla(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window, **kwargs
    )
    out = ragged_paged_attention_pallas(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window,
        pages_per_chunk=64, interpret=True, **kwargs,
    )
    _assert_walk_parity(out, ref, q_lens)


def test_row_walk_parity_at_32_queries_a_head(rng):
    """A block of 4 positions folded into a group of 8 (``models/sdar.py``'s
    denoise forward): 128 query rows on 4 KV heads of 128, every row seeing
    its whole context: the walk's stacked block over 4 x 32 rows, a head's
    scores from its 32 rows, 512 keys a fold (contexts in the first fold,
    in the second and in a second chunk)."""
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
