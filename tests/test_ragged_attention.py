"""Parity matrix for the fused ragged Pallas paged-attention kernel.

``ragged_paged_attention_pallas`` (interpret mode — the same kernel code
path Mosaic compiles on TPU, executed on CPU) is pinned against
``ragged_paged_attention_xla``, the always-available bit-exactness
baseline, across the full serving feature surface: decode rows × chunk
rows × GQA grouping × static/traced sliding windows × logit softcap ×
custom scale × ``q_lens`` padding × query tiling. The engine-level
greedy fp32 token-identity test at the bottom flips the backend under a
real serving loop (prefix cache + chunked prefill, so ragged spans and
decode spans both dispatch through the kernel).

Boundary being tested: VALID rows/queries must match the XLA path to
fp32 tolerance; PAD queries are exact zeros from the kernel (the XLA
twin emits finite key-0 garbage there) — both finite, both discarded by
every caller (docs/serving.md "Attention kernel backends").
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distllm_tpu.ops.paged_attention import (
    ragged_paged_attention_pallas,
    ragged_paged_attention_xla,
)


def _setup(rng, *, num_blocks=12, block_size=4, nkv=2, nh=4, hd=8, b=3,
           s=5):
    # Head-folded, as the pool stores a layer: [blocks, block_size, nkv*hd].
    k = jnp.asarray(
        rng.normal(size=(num_blocks, block_size, nkv * hd)).astype(np.float32)
    )
    v = jnp.asarray(
        rng.normal(size=(num_blocks, block_size, nkv * hd)).astype(np.float32)
    )
    max_blocks = 8
    # Block 0 is the trash block by engine convention; tables point at
    # arbitrary scattered real blocks like the paged allocator produces.
    bt = jnp.asarray(
        rng.integers(1, num_blocks, size=(b, max_blocks)), jnp.int32
    )
    # Row 0: mid-stream chunk; row 1: span == context (fresh prefill);
    # row 2: decode-like single live query (rest is q_lens padding).
    ctx = jnp.asarray([17, s, 9][:b], jnp.int32)
    q_lens = jnp.asarray([s, s, 1][:b], jnp.int32)
    q0 = ctx - q_lens
    pos = q0[:, None] + jnp.arange(s)[None, :]
    q = jnp.asarray(rng.normal(size=(b, s, nh, hd)).astype(np.float32))
    return q, k, v, bt, ctx, pos, q_lens


def _assert_parity(out, ref, q_lens, s):
    out, ref = np.asarray(out), np.asarray(ref)
    assert np.isfinite(out).all(), 'pallas emitted non-finite values'
    valid = np.arange(s)[None, :] < np.asarray(q_lens)[:, None]
    np.testing.assert_allclose(
        out[valid], ref[valid], atol=1e-5, rtol=1e-4
    )


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


@pytest.mark.parametrize('softcap', [None, 30.0], ids=['nocap', 'cap30'])
@pytest.mark.parametrize('scale', [None, 0.25], ids=['defscale', 'scale'])
def test_ragged_parity_softcap_and_scale(rng, softcap, scale):
    """gemma2 knobs: tanh logit softcap and query_pre_attn_scalar scale,
    with a sliding window riding along."""
    q, k, v, bt, ctx, pos, q_lens = _setup(rng)
    ref = ragged_paged_attention_xla(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=5,
        scale=scale, logit_softcap=softcap,
    )
    out = ragged_paged_attention_pallas(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=5,
        scale=scale, logit_softcap=softcap, interpret=True,
    )
    _assert_parity(out, ref, q_lens, q.shape[1])


def test_ragged_parity_decode_rows(rng):
    """Span-1 rows (the decode degenerate case) match the decode op."""
    from distllm_tpu.ops.paged_attention import (
        decode_attention,
        paged_attention_xla,
    )

    q, k, v, bt, ctx, pos, _ = _setup(rng, s=1)
    qd = q[:, 0]
    for window in (None, 6):
        ref = paged_attention_xla(
            qd, k, v, bt, ctx, sliding_window=window
        )
        out = decode_attention(
            qd, k, v, bt, ctx, ctx - 1, sliding_window=window,
            backend='interpret',
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-4
        )


# ---- the row walk: the kernel's schedule for a span of one (decode rows)

# Blocks of 4 tokens, a table of 16: a chunk of 2 pages is 8 keys. One
# batch holds a row with no sequence, one token, exactly one such chunk,
# one key past it, a row inside its third chunk, and the widest table.
_WALK_BS, _WALK_TABLE = 4, 16
_WALK_CTX = (0, 1, 8, 9, 23, 64)


# Blocks of 16 tokens, a chunk of 16 pages: a turn of 8 pages is 128 keys
# and a chunk 256, as on the chip. Contexts that end on every edge of a
# page, a turn and a chunk, and a row with no sequence between live rows.
_EDGE_BS, _EDGE_TABLE, _EDGE_PAGES = 16, 34, 16
_EDGE_CTX = (1, 15, 16, 17, 127, 0, 128, 129, 255, 256, 257, 513)


def _walk_setup(rng, *, nh=8, nkv=2, hd=8, ctx=_WALK_CTX, num_blocks=72,
                block=_WALK_BS, table=_WALK_TABLE):
    b = len(ctx)
    k, v = (
        jnp.asarray(
            rng.normal(size=(num_blocks, block, nkv * hd)), jnp.float32
        )
        for _ in range(2)
    )
    # every row its own scattered blocks, as the paged allocator hands out
    bt = jnp.asarray(
        rng.permutation(num_blocks - 1)[:b * table].reshape(b, table) + 1
        if b * table < num_blocks
        else rng.integers(1, num_blocks, size=(b, table)), jnp.int32,
    )
    ctx = jnp.asarray(ctx, jnp.int32)
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    q = jnp.asarray(rng.normal(size=(b, 1, nh, hd)), jnp.float32)
    return q, k, v, bt, ctx, pos, (ctx > 0).astype(jnp.int32)


def _window_arg(window):
    if window == 'traced':
        return jnp.int32(6)  # starts inside a chunk of 8 keys
    if window == 'traced_zero':
        return jnp.int32(0)
    return window


def _edge_setup(rng, **kwargs):
    """``_walk_setup`` at the chip's block and turn (``_EDGE_CTX``)."""
    return _walk_setup(
        rng, ctx=_EDGE_CTX, block=_EDGE_BS, table=_EDGE_TABLE, num_blocks=96,
        **kwargs,
    )


def _assert_walk_parity(out, ref, q_lens):
    out, ref = np.asarray(out), np.asarray(ref)
    live = np.asarray(q_lens) > 0
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[live], ref[live], atol=1e-5, rtol=1e-4)
    assert np.abs(out[~live]).max(initial=0.0) == 0.0  # a pad row: zeros


@pytest.mark.parametrize(
    'window', [None, 6, 'traced', 'traced_zero', 300],
    ids=['nowin', 'win6', 'traced', 'traced0', 'win300'],
)
@pytest.mark.parametrize(
    'pages', [1, 2, 4, 8, 16, None, 'edges', 'edges_one_chunk'],
    ids=['keys4', 'keys8', 'keys16', 'keys32', 'keys64', 'rule',
         'turn_edges', 'turn_edges_one_chunk'],
)
def test_row_walk_parity_by_keys_a_step_and_window(rng, pages, window):
    """Ragged contexts in one batch, every chunk width from one page to
    the whole table (and the rule's own, capped by the table), static and
    traced windows that start inside a chunk. ``turn_edges``: blocks of 16
    and turns of 128 keys in chunks of 256 (and in one chunk as wide as
    the table, 34 pages: turns of 2), contexts that end on every edge of a
    page, a turn and a chunk, a dead row between live ones, and windows
    whose floor lies inside a turn (6: in the turn's last page; 300: with
    whole turns and a chunk's edge above it)."""
    if str(pages).startswith('edges'):
        q, k, v, bt, ctx, pos, q_lens = _edge_setup(rng)
        pages = _EDGE_PAGES if pages == 'edges' else _EDGE_TABLE
    else:
        q, k, v, bt, ctx, pos, q_lens = _walk_setup(rng)
    window = _window_arg(window)
    ref = ragged_paged_attention_xla(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window
    )
    out = ragged_paged_attention_pallas(
        q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window,
        pages_per_chunk=pages, interpret=True,
    )
    _assert_walk_parity(out, ref, q_lens)


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


def _kernel_call(span):
    """The ``pallas_call`` equation of a traced call at ``span``."""
    q, k, v, bt, ctx, _, _ = _walk_setup(np.random.default_rng(0))
    b = q.shape[0]
    q = jnp.zeros((b, span, *q.shape[2:]), q.dtype)
    pos = jnp.maximum(ctx - span, 0)[:, None] + jnp.arange(span)[None, :]
    jaxpr = jax.make_jaxpr(
        lambda *a: ragged_paged_attention_pallas(
            *a, pages_per_chunk=2, interpret=True
        )
    )(q, k, v, bt, ctx, pos)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == 'pallas_call']
    return call


@pytest.mark.parametrize(
    'span,grid', [(1, (6, 1, 1)), (16, (6, 1, 8)), (2, (6, 1, 8))],
    ids=['span1_walks', 'span16_grid_over_chunks', 'span2_grid_over_chunks'],
)
def test_only_a_span_of_one_walks(span, grid):
    """The schedule is chosen by what the call shows, its span: one query
    a row walks under a grid over rows; any longer span keeps the grid
    (rows, query tiles, chunks of the widest table) and the parent's
    kernel (its jaxpr's size at the cells' widths is pinned in
    ``tests/test_aot_tpu.py``)."""
    call = _kernel_call(span)
    assert tuple(call.params['grid_mapping'].grid) == grid
    has_loop = 'while' in str(call.params['jaxpr'])
    assert has_loop == (span == 1)


@pytest.mark.parametrize(
    'lanes,dtype,planes,block,keys',
    [
        (1024, 'bfloat16', 2, 16, 1024),  # mistral7b, granite, laguna
        (640, 'bfloat16', 1, 16, 1024),  # kanana's latent plane
        (1024, 'int8', 2, 32, 1024),
        (4096, 'bfloat16', 2, 16, 256),  # 32 KV heads of 128: VMEM bounds
        (4096, 'float32', 2, 16, 128),
        (128, 'float32', 2, 8, 512),  # small blocks: the semaphores do
        (128, 'float32', 2, 4, 256),
        (256, 'float32', 1, 4, 512),
    ],
)
def test_walk_keys_a_step_rule(lanes, dtype, planes, block, keys):
    """Keys a step follow the row's width, the dtype, the planes and the
    block size: the most that keep two slots of pages in the walk's VMEM
    allowance and the copies in flight within their semaphores."""
    from distllm_tpu.ops.paged_attention import (
        WALK_BUFFER_BYTES,
        WALK_SEMAPHORES,
        walk_keys_a_step,
    )

    got = walk_keys_a_step(lanes, dtype, planes=planes, block_size=block)
    assert got == keys
    held = 2 * planes * got * lanes * jnp.dtype(dtype).itemsize
    copies = planes + 2 * (dtype == 'int8')
    assert held <= WALK_BUFFER_BYTES
    assert 2 * copies * (got // block) <= WALK_SEMAPHORES


@pytest.mark.parametrize('layer', [0, 1, 2], ids=['first', 'middle', 'last'])
@pytest.mark.parametrize('traced', [False, True], ids=['int', 'traced'])
def test_stacked_pool_is_addressed_by_layer(rng, layer, traced):
    """A stacked pool ``[L, blocks, block_size, folded]`` goes to the
    writers and the readers WHOLE, with the layer whose pages are meant
    (a Python int when the layers are unrolled, traced under a rolled
    scan). A chunk span, a decode row and a dead row: what is written and
    read is what the layer's own plane gives, the dead row's write lands
    in THAT layer's block 0, and no other layer's bytes move."""
    from distllm_tpu.ops.paged_attention import (
        decode_attention,
        paged_attention_xla,
        write_chunk_kv,
        write_token_kv,
    )

    layers, s = 3, 5
    q, _, _, bt, ctx, pos, q_lens = _setup(rng, s=s)
    # row 0 a mid-stream chunk, row 1 one live query, row 2 DEAD: no
    # queries, and a table the caller has sent to the trash block
    q_lens = jnp.asarray([s, 1, 0], jnp.int32)
    ctx = jnp.asarray([17, 9, 0], jnp.int32)
    pos = jnp.maximum(ctx - q_lens, 0)[:, None] + jnp.arange(s)[None, :]
    bt = bt.at[2].set(0)
    stack_k, stack_v = (
        jnp.asarray(rng.normal(size=(layers, 12, 4, 16)).astype(np.float32))
        for _ in range(2)
    )
    new_k, new_v = (
        jnp.asarray(rng.normal(size=(3, s, 2, 8)).astype(np.float32))
        for _ in range(2)
    )
    valid = jnp.arange(s)[None, :] < q_lens[:, None]
    li = jnp.int32(layer) if traced else layer

    def run(fn, *args):
        """``fn(*args, layer)``, the layer a tracer when asked for."""
        if traced:
            return jax.jit(fn)(*args, li)
        return fn(*args, li)

    def untouched_but(after, before, what):
        for other in range(layers):
            if other != layer:
                np.testing.assert_array_equal(
                    np.asarray(after[other]), np.asarray(before[other]),
                    err_msg=f'{what} moved bytes of layer {other}',
                )

    # --- the chunk writer, against the layer's own plane
    got_k, got_v = run(
        lambda k, v, layer: write_chunk_kv(
            k, v, new_k, new_v, bt, pos, valid, layer=layer
        ), stack_k, stack_v,
    )
    want_k, want_v = write_chunk_kv(
        stack_k[layer], stack_v[layer], new_k, new_v, bt, pos, valid
    )
    assert got_k.shape == stack_k.shape
    for got, want, before in (
        (got_k, want_k, stack_k), (got_v, want_v, stack_v)
    ):
        # block 0 holds whichever dead position landed last: compare past it
        np.testing.assert_array_equal(
            np.asarray(got[layer, 1:]), np.asarray(want[1:])
        )
        untouched_but(got, before, 'write_chunk_kv')
        # the dead positions' rows went to this layer's block 0, offset 0
        assert not np.array_equal(
            np.asarray(got[layer, 0, 0]), np.asarray(before[layer, 0, 0])
        )
        np.testing.assert_array_equal(
            np.asarray(got[layer, 0, 1:]), np.asarray(before[layer, 0, 1:])
        )

    # --- the readers over the written pool: a span, then decode rows
    ref = ragged_paged_attention_xla(
        q, want_k, want_v, bt, ctx, pos, q_lens=q_lens
    )
    for reader, kwargs in (
        (ragged_paged_attention_xla, {}),
        (ragged_paged_attention_pallas, {'interpret': True}),
    ):
        out = run(
            lambda k, v, layer, reader=reader, kwargs=kwargs: reader(
                q, k, v, bt, ctx, pos, q_lens=q_lens, layer=layer, **kwargs
            ), got_k, got_v,
        )
        _assert_parity(out, ref, q_lens, s)

    # --- the token writer and the decode readers; row 2 out of budget
    tok_pos = jnp.asarray([16, 8, 3], jnp.int32)
    tok_ctx = tok_pos + 1
    tok_k, tok_v = new_k[:, 0], new_v[:, 0]
    dec_k, dec_v = run(
        lambda k, v, layer: write_token_kv(
            k, v, tok_k, tok_v, bt, tok_pos, layer=layer
        ), got_k, got_v,
    )
    one_k, one_v = write_token_kv(
        got_k[layer], got_v[layer], tok_k, tok_v, bt, tok_pos
    )
    np.testing.assert_array_equal(np.asarray(dec_k[layer]), np.asarray(one_k))
    np.testing.assert_array_equal(np.asarray(dec_v[layer]), np.asarray(one_v))
    untouched_but(dec_k, got_k, 'write_token_kv')
    untouched_but(dec_v, got_v, 'write_token_kv')
    # the dead row's token: block 0 of this layer, at its offset
    np.testing.assert_array_equal(
        np.asarray(dec_k[layer, 0, 3]), np.asarray(tok_k[2]).reshape(-1)
    )
    ref = paged_attention_xla(q[:2, 0], one_k, one_v, bt[:2], tok_ctx[:2])
    for backend in ('xla', 'interpret'):
        out = run(
            lambda k, v, layer, backend=backend: decode_attention(
                q[:2, 0], k, v, bt[:2], tok_ctx[:2], tok_ctx[:2] - 1,
                layer=layer, backend=backend,
            ), dec_k, dec_v,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-4
        )


def test_stacked_pool_needs_its_layer(rng):
    """The pool's rank decides: a stacked pool with no layer named is
    refused, by the readers and by the writers alike."""
    from distllm_tpu.ops.paged_attention import write_token_kv

    q, k, v, bt, ctx, pos, q_lens = _setup(rng)
    with pytest.raises(ValueError, match='stacked pool'):
        ragged_paged_attention_xla(q, k[None], v[None], bt, ctx, pos)
    with pytest.raises(ValueError, match='stacked pool'):
        write_token_kv(k[None], v[None], q[:, 0, :2], q[:, 0, :2], bt, ctx - 1)


def test_ragged_parity_query_tiling_and_chunking(rng):
    """Long spans across multiple query tiles and multi-page KV chunks:
    tiling must be invisible (same values as the untiled XLA gather)."""
    q, k, v, bt, ctx, pos, q_lens = _setup(
        rng, s=13, nh=8, nkv=2, num_blocks=16
    )
    ctx = jnp.asarray([30, 13, 22], jnp.int32)
    q_lens = jnp.asarray([13, 13, 7], jnp.int32)
    pos = (ctx - q_lens)[:, None] + jnp.arange(13)[None, :]
    for window in (None, 5):
        ref = ragged_paged_attention_xla(
            q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window
        )
        out = ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=window,
            span_tile=4, pages_per_chunk=2, interpret=True,
        )
        _assert_parity(out, ref, q_lens, 13)


def test_ragged_pad_rows_are_exact_zeros(rng):
    """q_lens=0 rows and pad queries emit exact finite zeros — stricter
    than the XLA twin's key-0 garbage, and the property that keeps a NaN
    out of the trash block under sliding windows."""
    q, k, v, bt, ctx, pos, _ = _setup(rng, s=6)
    q_lens = jnp.asarray([6, 0, 2], jnp.int32)
    out = np.asarray(
        ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=q_lens, sliding_window=2,
            interpret=True,
        )
    )
    assert np.isfinite(out).all()
    assert np.abs(out[1]).max() == 0.0  # fully padded row
    assert np.abs(out[2, 2:]).max() == 0.0  # pad tail of a ragged row


def test_ragged_q_lens_none_matches_xla(rng):
    """q_lens=None: every span position is computed as a live query (the
    prefill alias contract) — full-tensor parity, not just valid rows."""
    q, k, v, bt, ctx, pos, _ = _setup(rng)
    ctx = jnp.asarray([17, 9, 12], jnp.int32)
    pos = (ctx - q.shape[1])[:, None] + jnp.arange(q.shape[1])[None, :]
    ref = ragged_paged_attention_xla(q, k, v, bt, ctx, pos, q_lens=None)
    out = ragged_paged_attention_pallas(
        q, k, v, bt, ctx, pos, q_lens=None, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-4
    )


def test_dispatcher_backend_routing(rng):
    """The one serving callsite: 'xla' and 'interpret' agree on valid
    rows; an unresolved selector fails loudly."""
    from distllm_tpu.ops.paged_attention import ragged_paged_attention

    q, k, v, bt, ctx, pos, q_lens = _setup(rng)
    ref = ragged_paged_attention(
        q, k, v, bt, ctx, pos, q_lens=q_lens, backend='xla'
    )
    out = ragged_paged_attention(
        q, k, v, bt, ctx, pos, q_lens=q_lens, backend='interpret'
    )
    _assert_parity(out, ref, q_lens, q.shape[1])
    with pytest.raises(ValueError, match='attn backend'):
        ragged_paged_attention(
            q, k, v, bt, ctx, pos, q_lens=q_lens, backend='auto'
        )


def test_resolve_attn_backend_contract(monkeypatch):
    from types import SimpleNamespace

    from distllm_tpu.ops.paged_attention import resolve_attn_backend

    mc = SimpleNamespace(head_size=128)
    # CPU: 'auto' must land on the always-available XLA fallback.
    assert resolve_attn_backend('auto', mc) == 'xla'
    # Explicit pins pass through untouched.
    assert resolve_attn_backend('pallas', mc) == 'pallas'
    assert resolve_attn_backend('interpret', mc) == 'interpret'
    with pytest.raises(ValueError, match='attn_backend'):
        resolve_attn_backend('cuda', mc)
    # On TPU, 'auto' eligibility includes the kernel's DMA contract on
    # the KV block geometry: a block_size the kernel would reject must
    # resolve to XLA (never trace into the kernel's ValueError), while
    # the default geometry selects the kernel.
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    # Head-dim CI contract: 128 is tested, 256 is a multiple of 128 but
    # outside TESTED_HEAD_DIMS so 'auto' must keep XLA.
    assert resolve_attn_backend('auto', mc) == 'pallas'
    assert (
        resolve_attn_backend('auto', SimpleNamespace(head_size=256)) == 'xla'
    )
    assert resolve_attn_backend(
        'auto', mc, block_size=16, kv_dtype='bfloat16'
    ) == 'pallas'
    assert resolve_attn_backend(
        'auto', mc, block_size=8, kv_dtype='bfloat16'
    ) == 'xla'
    assert resolve_attn_backend(
        'auto', mc, block_size=8, kv_dtype='float32'
    ) == 'pallas'  # fp32 sublane tile is 8


def _tiny_engine(attn_backend):
    from distllm_tpu.generate.engine import EngineConfig, LLMEngine
    from distllm_tpu.models import mistral

    cfg = mistral.MistralConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)

    class _Tok:
        eos_id = None

    engine_cfg = EngineConfig(
        block_size=4, num_blocks=48, max_num_seqs=3, max_model_len=64,
        decode_steps=4, pipeline_depth=1, attn_backend=attn_backend,
        enable_prefix_cache=True, prefill_chunk_tokens=8,
    )
    return LLMEngine(cfg, params, _Tok(), engine_cfg)


@pytest.mark.parametrize('flipped', ['interpret'])
def test_engine_token_identity_backend_flipped(flipped):
    """Greedy fp32 serving produces IDENTICAL tokens with the backend
    flipped from 'xla' to the ragged Pallas kernel (interpret mode — the
    same kernel the TPU compiles). Prefix cache + chunked prefill are on,
    so both ragged chunk spans and span-1 decode rows dispatch through
    the flipped kernel. This is the engine-level identity boundary from
    docs/serving.md: cross-kernel identity is pinned in fp32 (bf16 may
    round a near-tied logit differently across compiled programs)."""
    from distllm_tpu.generate.engine import SamplingParams

    rng = np.random.default_rng(7)
    shared = list(rng.integers(1, 128, size=10))
    prompts = [
        shared + list(rng.integers(1, 128, size=int(n)))
        for n in (3, 11, 6)
    ]
    sampling = SamplingParams(temperature=0.0, max_tokens=6)
    outs = {}
    for backend in ('xla', flipped):
        engine = _tiny_engine(backend)
        assert engine.telemetry['attn_backend'] == backend
        outs[backend] = engine.generate_ids(prompts, sampling)
        engine.shutdown()
    assert outs['xla'] == outs[flipped], (
        'greedy fp32 token stream diverged when the attention backend '
        'flipped — the kernel identity contract is broken'
    )
