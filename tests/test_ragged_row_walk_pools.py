"""The row walk (``test_ragged_row_walk.py``) against the XLA twin by pool
and knob, and by queries a head."""

import pytest

import jax
import jax.numpy as jnp

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
