"""The row walk, the ragged paged-attention kernel's schedule for a span of
one (decode rows), against the XLA twin: by keys a step and window, which
spans walk, the keys-a-step rule, the rule of the softmax block's form, and
the stacked pool addressed by layer.
By pool, knob and queries a head: ``test_ragged_row_walk_pools.py``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distllm_tpu.ops.paged_attention import (
    ragged_paged_attention_pallas,
    ragged_paged_attention_xla,
)
from test_ragged_attention import _assert_parity, _setup


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


@pytest.mark.parametrize(
    'heads,group,form,turns',
    [
        (8, 4, 'stacked', 4),  # mistral7b (both cells), granite
        (16, 1, 'stacked', 4),  # ouro
        (4, 5, 'stacked', 4),  # falcon-h1
        (8, 6, 'stacked', 4),  # laguna's full layers
        (4, 7, 'stacked', 4),  # smallthinker, both groups
        (8, 8, 'stacked', 4),  # laguna's window layers, solar: 64 rows
        (4, 8, 'stacked', None),  # lfm2's four bands of paired heads: 32 rows
        (4, 32, 'stacked', 4),  # sdar: a block of 4 positions x 8
        (1, 32, 'per_head', None),  # kanana's one latent head
        (2, 12, 'per_head', None),  # from 8 up and no whole sublane tiles
        (2, 16, 'stacked', None),
        (2, 24, 'stacked', 4),
    ],
)
def test_walk_block_rule(heads, group, form, turns):
    """The form of the walk's softmax block and the turns a fold, from the
    KV heads and the queries a KV head of every cell's decode calls:
    stacked wherever a head's softmax by itself would pay the block's fixed
    cost once a head, the whole chunk a fold up to 32 stacked rows of whole
    sublane tiles."""
    from distllm_tpu.ops.paged_attention import (
        WALK_WHOLE_CHUNK_ROWS,
        walk_block,
    )

    assert walk_block(heads, group) == (form, turns)
    assert (form == 'stacked' and turns is None) == (
        heads > 1 and group % 8 == 0
        and heads * group <= WALK_WHOLE_CHUNK_ROWS
    )


@pytest.mark.parametrize(
    'query_heads,head_dim,row_lanes,form',
    [
        (32, 128, 1024, 'stacked'),  # mistral7b
        (32, 64, 512, 'stacked'),  # lfm2: 8 KV heads of 64 are 4 bands x 8
        (128, 128, 512, 'stacked'),  # sdar: 32 heads x a block of 4
        (32, 640, 640, 'per_head'),  # kanana: the row is one head
        (64, 128, 1024, 'stacked'),  # solar, laguna's window layers
        (24, 128, 256, 'per_head'),  # 12 queries a head
    ],
)
def test_walk_block_form_reckons_heads_as_the_kernel_does(
    query_heads, head_dim, row_lanes, form
):
    """What the engine's telemetry names (``walk_block``) is the rule's
    answer for the heads the kernel's wrapper makes of a pool row."""
    from distllm_tpu.ops.paged_attention import walk_block_form

    assert walk_block_form(query_heads, head_dim, row_lanes) == form


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
