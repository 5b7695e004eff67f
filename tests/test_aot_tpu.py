"""Compile-only TPU (Mosaic) lowering tests — no hardware needed.

The locally installed libtpu can build a compile-only PJRT topology
(``jax.experimental.topologies``), which catches the class of failures CPU
interpret mode cannot: Mosaic lowering rejections (block-shape rules, DMA
patterns) and HBM budgeting.

Two tiers. Unmarked: each Pallas kernel of the main path alone, at the
real widths (Mistral-7B serving, PubMedBERT embedding), asserting the
kernel is in the compiled program (``tpu_custom_call``) — 0.1-2 s each,
so every tier-1 run compiles for the chip. ``slow``: whole windows and
forwards around those kernels; ``scripts/aot_preflight.py`` runs the full
7B serving matrix.

The families' decode windows: ``test_aot_windows.py``; their chunk prefills:
``test_aot_prefill.py`` and ``test_aot_moe_prefill.py``; what all of them
share (the described chip, the readers of a compiled text, the cells):
``aot_tpu.py``. A new family's compiles are a file of their own beside them.
"""

import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402
from aot_tpu import (  # noqa: F401 -- fixtures, asked for by name
    v5e,
)
from aot_tpu import (
    _7B,
    _HD,
    _assert_decode_calls_walk,
    _assert_kernel_compiled,
    _assert_pools_go_to_the_kernel_as_they_lie,
    _assert_span_calls_keep_the_grid,
    _compile,
    _kernel_equations,
    _kernel_programs,
    _kernel_schedules,
)


@pytest.mark.parametrize('kv,block_size,span,widths', [
    ('bf16', 16, 1, _7B), ('bf16', 16, 16, _7B),
    ('int8', 32, 1, _7B), ('int8', 32, 16, _7B),
    ('bf16', 16, 1, (16, 16, 16, 1024)),
], ids=['bf16-span1', 'bf16-span16', 'int8-span1', 'int8-span16',
        'ouro-span1'])
def test_ragged_kernel_compiles_at_7b_widths(
    v5e, kv, block_size, span, widths
):
    """Decode (span 1) and chunk/verify (span 16) rows over a bf16 pool
    at block 16 and the int8 ``QuantizedKV`` pool at block 32 (int8 at
    block 16 is refused by the kernel's own sublane contract), and decode
    rows at Ouro-2.6B's widths: 16 rows of ONE query a KV head at 16 heads,
    2048-lane rows, 512 keys a step in four turns."""
    from distllm_tpu.ops.paged_attention import (
        QuantizedKV,
        ragged_paged_attention_pallas,
    )

    rows, nh, nkv, table_tokens = widths
    num_blocks, max_blocks = 712, table_tokens // block_size
    shape = (num_blocks, block_size, nkv * _HD)  # head-folded, as stored
    if kv == 'int8':
        pool = QuantizedKV(
            v5e(shape, jnp.int8), v5e((num_blocks, nkv), jnp.float32)
        )
    else:
        pool = v5e(shape, jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, bt, ctx, pos, ql: ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=ql
        )
    ).lower(
        v5e((rows, span, nh, _HD), jnp.bfloat16), pool, pool,
        v5e((rows, max_blocks), jnp.int32), v5e((rows,), jnp.int32),
        v5e((rows, span), jnp.int32), v5e((rows,), jnp.int32),
    ).compile()
    _assert_kernel_compiled(compiled)
    assert _kernel_schedules(compiled) == ['walk' if span == 1 else 'grid']


# A paged prefill bucket's trace and lowering are mostly the kernel's
# unrolled body, paid once a bucket (28 of them in ``mistral7b.chat_steady``:
# PERF.md section 5), so the SPAN kernel's size is a set-up cost: these are
# the parent's numbers (PR 36's tree, counted before the row walk went in).
@pytest.mark.parametrize('widths,span,parent', [
    (dict(rows=32, nh=32, nkv=8, hd=128), 16, 1015),
    (dict(rows=4, nh=32, nkv=8, hd=128), 512, 1015),
    (dict(rows=4, nh=48, nkv=8, hd=128), 512, 1015),
    (dict(rows=4, nh=32, nkv=1, hd=640, value_lanes=512), 512, 880),
    (dict(rows=4, nh=16, nkv=16, hd=128), 512, 1663),  # PR 48's tree
], ids=['mistral16', 'mistral512', 'laguna512', 'kanana512', 'ouro16'])
def test_span_kernel_traces_no_more_than_the_parent(widths, span, parent):
    """A span over one traces the parent's kernel, equation for
    equation; the span-1 kernel (the row walk: ``compute`` traced once
    over a turn's band, the page copies straight-line a turn) is smaller
    than it, printed beside it."""
    spans = _kernel_equations(span, **widths)
    walks = _kernel_equations(1, **widths)
    print(f'kernel jaxpr equations: span {span}: {spans}, span 1: {walks}')
    assert spans == parent
    assert walks < spans


@pytest.mark.parametrize(
    'm,k,n', [(32, 4096, 14336), (128, 4096, 32000)],
    ids=['mlp_up_b32', 'lm_head_b128'],
)
def test_int8_matmul_kernel_compiles_at_7b_widths(v5e, m, k, n):
    """Off the main path ('auto' means XLA there), so this compile is the
    kernel's only chip-facing check."""
    from distllm_tpu.ops.quantized_matmul import int8_matmul_pallas

    compiled = int8_matmul_pallas.lower(
        v5e((m, k), jnp.bfloat16), v5e((k, n), jnp.int8),
        v5e((1, n), jnp.float32),
    ).compile()
    _assert_kernel_compiled(compiled)


@pytest.mark.parametrize(
    'b,s,d', [(64, 256, 768), (64, 160, 768)], ids=['s256', 's160']
)
def test_encoder_kernel_compiles_at_pubmedbert_widths(v5e, b, s, d):
    """160 is a fine-ladder rung that is NOT a multiple of 128."""
    from distllm_tpu.ops.encoder_attention import encoder_attention

    compiled = jax.jit(
        lambda q, k, v, m: encoder_attention(q, k, v, m, num_heads=12)
    ).lower(
        v5e((b, s, d), jnp.bfloat16), v5e((b, s, d), jnp.bfloat16),
        v5e((b, s, d), jnp.bfloat16), v5e((b, s), jnp.int32),
    ).compile()
    _assert_kernel_compiled(compiled)


# ---- whole windows and forwards (slow tier) ----

@pytest.mark.slow
def test_encoder_attention_compiles_for_tpu(v5e):
    from distllm_tpu.ops.encoder_attention import encoder_attention

    # 160 is a fine-ladder rung that is NOT a multiple of 128 — the case
    # the library flash kernel rejects and Mosaic block rules can trip on.
    b, s, d = 8, 160, 256
    jax.jit(
        lambda q, k, v, m: encoder_attention(q, k, v, m, num_heads=4)
    ).lower(
        v5e((b, s, d), jnp.bfloat16),
        v5e((b, s, d), jnp.bfloat16),
        v5e((b, s, d), jnp.bfloat16),
        v5e((b, s), jnp.int32),
    ).compile()


@pytest.mark.slow
@pytest.mark.parametrize('backend', ['pallas', 'xla'])
def test_decode_window_compiles_for_tpu(v5e, backend):
    """The window (its layers unrolled: straight-line cache updates that
    depend on XLA's buffer reuse rather than on while-carry aliasing) must
    lower, and a missed reuse in it would add full-cache-sized temps:
    asserted against below."""
    from distllm_tpu.models import mistral

    # head_dim must be 128 (the Pallas kernel's DMA alignment contract).
    cfg = mistral.MistralConfig(
        vocab_size=2048, hidden_size=1024, num_layers=2, num_heads=8,
        num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
    )
    shapes = jax.eval_shape(
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda x: v5e(x.shape, x.dtype), shapes)
    b, nb, bs, rows = 8, 64, 16, 16
    kshape = (cfg.num_layers, nb, bs, cfg.num_kv_heads * cfg.head_size)
    cache_bytes = 2 * int(np.prod(kshape)) * 2  # k + v, bf16
    compiled = _compile(
        mosaic_kernel=(backend == 'pallas'),
        build=lambda: jax.jit(
            lambda p, i, po, c, k, v, bt, sl, t, tp, mp, tk, sd:
                mistral.decode_loop(
                    p, cfg, i, po, k, v, bt, c, sl, t, tp, mp, tk, sd,
                    num_steps=4, attn_backend=backend,
                    max_table_positions=256, sampling_top_window=16,
                ),
            donate_argnums=(4, 5),
        ).lower(
            params, v5e((b,), jnp.int32), v5e((b,), jnp.int32),
            v5e((b,), jnp.int32), v5e(kshape, jnp.bfloat16),
            v5e(kshape, jnp.bfloat16), v5e((b, rows), jnp.int32),
            v5e((b,), jnp.int32), v5e((b,), jnp.float32),
            v5e((b,), jnp.float32), v5e((b,), jnp.float32),
            v5e((b,), jnp.int32), v5e((b,), jnp.uint32),
        ).compile()
    )
    temps = getattr(compiled.memory_analysis(), 'temp_size_in_bytes', None)
    if temps is not None:
        # Unrolling must not degrade in-place cache updates to copies:
        # each missed reuse adds a full-cache-sized temp. The bound is
        # absolute: activation temps at these dims are ~2.5 MB, well under
        # one 4 MB cache copy.
        assert temps < cache_bytes, (
            f'window temps {temps} vs one cache copy {cache_bytes}'
        )


@pytest.mark.slow
def test_ragged_paged_attention_compiles_for_tpu(v5e):
    """The fused ragged kernel must lower clean under Mosaic at every
    serving span shape — the hard version of what nine PRs of 'implicit
    dim change' xfails could not assert for the retired decode-only
    kernel. Covers the standalone op at chunk-span, decode-span, and
    gemma2-knob (traced window + softcap + scale) signatures, plus the
    full prefill_paged forward with the backend pinned 'pallas' (the
    mixed/spec windows' ragged half compiles the same graph)."""
    from distllm_tpu.models import mistral
    from distllm_tpu.ops.paged_attention import ragged_paged_attention_pallas

    b, nb, bs, rows = 8, 64, 16, 16
    nh, nkv, hd = 8, 4, 128

    def op(q, k, v, bt, ctx, pos, ql, w=None, **kw):
        return ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=ql, sliding_window=w, **kw
        )

    for s in (16, 1):  # chunk span and the decode degenerate span
        _compile(
            lambda s=s: jax.jit(op).lower(
                v5e((b, s, nh, hd), jnp.bfloat16),
                v5e((nb, bs, nkv * hd), jnp.bfloat16),
                v5e((nb, bs, nkv * hd), jnp.bfloat16),
                v5e((b, rows), jnp.int32), v5e((b,), jnp.int32),
                v5e((b, s), jnp.int32), v5e((b,), jnp.int32),
            ).compile()
        )
    # gemma2 knobs through ONE compiled signature: traced per-layer
    # window scalar, logit softcap, custom scale.
    _compile(
        lambda: jax.jit(
            lambda q, k, v, bt, ctx, pos, ql, w: op(
                q, k, v, bt, ctx, pos, ql, w,
                logit_softcap=30.0, scale=0.0884,
            )
        ).lower(
            v5e((b, 16, nh, hd), jnp.bfloat16),
            v5e((nb, bs, nkv * hd), jnp.bfloat16),
            v5e((nb, bs, nkv * hd), jnp.bfloat16),
            v5e((b, rows), jnp.int32), v5e((b,), jnp.int32),
            v5e((b, 16), jnp.int32), v5e((b,), jnp.int32),
            v5e((), jnp.int32),
        ).compile()
    )
    # The serving forward that carries the ragged spans (prefix-cache
    # tails, chunked prefill, and the mixed/spec windows' chunk half).
    cfg = mistral.MistralConfig(
        vocab_size=2048, hidden_size=1024, num_layers=2, num_heads=8,
        num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
    )
    shapes = jax.eval_shape(
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda x: v5e(x.shape, x.dtype), shapes)
    kshape = (cfg.num_layers, nb, bs, cfg.num_kv_heads * cfg.head_size)
    _compile(
        lambda: jax.jit(
            lambda p, i, po, k, v, bt, c, t: mistral.prefill_paged(
                p, cfg, i, po, k, v, bt, c, t,
                max_table_positions=256, attn_backend='pallas',
            ),
            donate_argnums=(3, 4),
        ).lower(
            params, v5e((4, 16), jnp.int32), v5e((4, 16), jnp.int32),
            v5e(kshape, jnp.bfloat16), v5e(kshape, jnp.bfloat16),
            v5e((4, rows), jnp.int32), v5e((4,), jnp.int32),
            v5e((4,), jnp.int32),
        ).compile()
    )


@pytest.mark.slow  # Mosaic window compile — see the tier note above.
def test_int8_decode_window_compiles_for_tpu(v5e):
    """Per-layer dequant inside the scan must not materialize the float
    stack as HLO temps (the whole-tree dequant OOMed 7B on 16 GiB)."""
    from distllm_tpu.models import mistral
    from distllm_tpu.ops.quantization import quantize_pytree_abstract

    # head_dim must be 128 (the Pallas kernel's DMA alignment contract).
    cfg = mistral.MistralConfig(
        vocab_size=2048, hidden_size=1024, num_layers=2, num_heads=8,
        num_kv_heads=4, intermediate_size=512, dtype='bfloat16',
    )
    shapes = jax.eval_shape(
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), cfg)
    )

    from distllm_tpu.ops.quantization import QTensor

    params = quantize_pytree_abstract(shapes, make_leaf=v5e)
    # Bytes a whole-tree dequant would materialize as bf16 HLO temps:
    # only the leaves that actually became QTensor.
    float_stack_bytes = sum(
        int(np.prod(leaf.shape)) * 2
        for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor)
        )
        if isinstance(leaf, QTensor)
    )
    b, nb, bs, rows = 8, 64, 16, 16
    kshape = (cfg.num_layers, nb, bs, cfg.num_kv_heads * cfg.head_size)
    compiled = _compile(
        lambda: jax.jit(
            lambda p, i, po, c, k, v, bt, sl, t, tp, mp, tk, sd:
                mistral.decode_loop(
                    p, cfg, i, po, k, v, bt, c, sl, t, tp, mp, tk, sd,
                    num_steps=4, attn_backend='pallas',
                    max_table_positions=256,
                    sampling_top_window=16,
                ),
            donate_argnums=(4, 5),
        ).lower(
            params, v5e((b,), jnp.int32), v5e((b,), jnp.int32),
            v5e((b,), jnp.int32), v5e(kshape, jnp.bfloat16),
            v5e(kshape, jnp.bfloat16), v5e((b, rows), jnp.int32),
            v5e((b,), jnp.int32), v5e((b,), jnp.float32),
            v5e((b,), jnp.float32), v5e((b,), jnp.float32),
            v5e((b,), jnp.int32), v5e((b,), jnp.uint32),
        ).compile()
    )
    mem = compiled.memory_analysis()
    temp = getattr(mem, 'temp_size_in_bytes', None)
    if temp is not None:
        # A whole-tree dequant would materialize the full bf16 stack
        # (float_stack_bytes) as temps; per-layer dequant stays well under.
        assert temp < float_stack_bytes // 2


def test_window_and_prefill_agree_on_a_narrow_gate_layout(v5e):
    """An engine that owns its weights moves them into the layouts the
    decode window's AOT compile chose, and every other program then takes
    that tree. For a leaf whose minor dimension does not fill a lane tile
    the window's own choice is one the prefill program could not run (on
    the chip, PR 30: a 12-wide gate kernel, ``expected parameter 6 of size
    12288 ... got 16384``), so ``auto_layout_formats`` keeps such a leaf
    in the device's default layout: window and prefill then ask for the
    same buffer. Toy ``laguna`` widths with 12 and 16 query heads."""
    from jax.experimental.layout import Format, Layout

    from distllm_tpu.generate.engine.engine import auto_layout_formats
    from distllm_tpu.models import laguna
    from chip_smoke import WINDOWED_MODEL  # the smoke's toy: 12 and 16 heads

    cfg = laguna.LagunaConfig.from_hf_config(WINDOWED_MODEL)
    shapes = jax.eval_shape(
        lambda: laguna.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    bare = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), shapes)
    b, table, block = 2, 32, 16
    i32, f32 = jnp.int32, jnp.float32

    def pools(*layers_blocks):
        return tuple(
            v5e((layers, blocks, block, cfg.num_kv_heads * cfg.head_dim),
                jnp.bfloat16)
            for layers, blocks in layers_blocks
        )

    k = pools((cfg.count('full'), 43), (cfg.count('window'), 30))

    def window_fn(params, ids, pos, ctx, k, v, bt, steps_left, *sampling):
        return laguna.decode_loop(
            params, cfg, ids, pos, k, v, bt, ctx, steps_left, *sampling,
            num_steps=2, attn_backend='pallas', max_table_positions=512,
        )

    def window_formats(param_formats):
        rows = (v5e((b,), i32),) * 3
        return jax.jit(
            window_fn, donate_argnums=(4, 5),
            in_shardings=(param_formats,) + (Format(),) * 12,
        ).lower(
            bare, *rows, k, k, (v5e((b, table), i32),) * 2, v5e((b,), i32),
            v5e((b,), f32), v5e((b,), f32), v5e((b,), f32), v5e((b,), i32),
            v5e((b,), jnp.uint32),
        ).compile().input_formats[0][0]

    prefill = jax.jit(
        lambda params, ids, pos, k, v, bt, ctx, tails: laguna.prefill_paged(
            params, cfg, ids, pos, k, v, bt, ctx, tails,
            max_table_positions=512, attn_backend='pallas',
        ), donate_argnums=(3, 4),
    ).lower(
        params, v5e((1, 128), i32), v5e((1, 128), i32), k, k,
        (v5e((1, table), i32),) * 2, v5e((1,), i32), v5e((1,), i32),
    ).compile().input_formats[0][0]

    def narrow(tree):
        return {
            jax.tree_util.keystr(path): str(fmt.layout)
            for (path, fmt), shape in zip(
                jax.tree_util.tree_flatten_with_path(tree)[0],
                jax.tree.leaves(shapes),
            ) if shape.shape[-1] < 128
        }

    kept = narrow(window_formats(auto_layout_formats(bare)))
    assert "['full']['attn_gate']['kernel']" in kept
    assert kept == narrow(prefill)
    # What the rule is there for: left to itself the window takes the
    # 12-wide gate in a layout that is not the device's default.
    auto = narrow(window_formats(Format(Layout.AUTO)))
    gate = "['full']['attn_gate']['kernel']"
    assert auto[gate] != kept[gate]


# ---- 64-wide heads: two to a lane tile of the pool's row (PR 39) ----

@pytest.mark.parametrize('rows, span', [(96, 1), (128, 1), (4, 512)],
                         ids=['decode_96_rows', 'decode_128_rows',
                              'prefill_4x512'])
def test_ragged_kernel_compiles_at_64_wide_heads(v5e, rows, span):
    """The kernel alone at ``lfm2-8b-a1b``'s attention widths (32 query
    heads on 8 KV heads of 64 dims, a pool row of 512 lanes, contexts to
    8,448; the cell's 96 decode rows and the 128 rows and 25,600 blocks a
    layer of the issue's first size): Mosaic takes the row walk and the
    grid over spans from the pool as it is stored, no head padded."""
    from distllm_tpu.ops.paged_attention import ragged_paged_attention_pallas

    pool = v5e((6, 25600, 16, 512), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, bt, ctx, pos, lens: ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=lens, layer=3
        )
    ).lower(
        v5e((rows, span, 32, 64), jnp.bfloat16), pool, pool,
        v5e((rows, 528), jnp.int32), v5e((rows,), jnp.int32),
        v5e((rows, span), jnp.int32), v5e((rows,), jnp.int32),
    ).compile()
    _assert_pools_go_to_the_kernel_as_they_lie(compiled, [(6 * 25600, 16, 512)])
    if span == 1:
        _assert_decode_calls_walk(compiled)
    else:
        _assert_span_calls_keep_the_grid(compiled)


def test_span_kernel_lowers_to_the_same_text_whoever_traces_it_first(v5e):
    """A Mosaic body carries its operations' debug locations, and jax
    caches a traced function with its first caller's stack: traced first
    from another stack, on another thread, at these shapes and others (the
    cell's check does so beside the engine's warm-up), the kernel lowers to
    the bytes it lowers to alone. Otherwise the persistent compile cache
    misses every program that holds it whenever the order flips."""
    import threading

    from distllm_tpu.ops import kda

    def lowered(rows):
        wide = v5e((rows, 512, 64, 128), jnp.float32)
        return jax.jit(
            lambda *a: kda.span_kernel(*a, form=(64, 32, 4))
        ).lower(
            wide, wide, wide, wide, v5e((rows, 512, 64), jnp.float32),
            v5e((rows, 64, 128, 128), jnp.float32),
        ).as_text()

    alone = lowered(4)
    jax.clear_caches()
    other = threading.Thread(
        target=lambda: [(lambda rows: lowered(rows))(rows) for rows in (1, 4)]
    )
    other.start()
    other.join()
    _ = jnp.where(jnp.ones((32, 1), bool), jnp.ones((32, 128)), 0.0) * 2.0
    assert 'tpu_custom_call' in alone
    assert lowered(4) == alone


def test_inputs_kernel_lowers_to_the_same_text_whoever_traces_it_first(v5e):
    """The way-in kernel's body is part of the compile cache's key as the
    span kernel's is, and the cell's check traces ``_kda_inputs`` on a thread
    beside the engine's warm-up: traced first from another stack, on another
    thread, at these shapes and others, it lowers to the bytes it lowers to
    alone (``kda._one_source``)."""
    import threading

    from distllm_tpu.ops import kda

    def lowered(rows):
        third = v5e((rows, 512, 8192), jnp.bfloat16)
        return jax.jit(
            lambda *a: kda.inputs_kernel(
                a[:3], *a[3:], form=(512, 128, 256), head=128,
                q_scale=128 ** -0.5, eps=1e-6,
            )
        ).lower(
            third, third, third, v5e((rows, 3, 24576), jnp.bfloat16),
            v5e((4, 24576), jnp.bfloat16),
        ).as_text()

    alone = lowered(4)
    jax.clear_caches()
    other = threading.Thread(
        target=lambda: [(lambda rows: lowered(rows))(rows) for rows in (1, 4)]
    )
    other.start()
    other.join()
    _ = jax.nn.sigmoid(jnp.ones((128, 256))) * jnp.ones((1, 256))
    assert 'tpu_custom_call' in alone
    assert lowered(4) == alone


# ---- smallthinker (PR 52): two cache groups at a window of 4096, 7 queries
# a KV head, the ranking ahead of attention ----

@pytest.mark.parametrize('rows, span', [(48, 1), (4, 512)], ids=['walk', 'span512'])
@pytest.mark.parametrize('window', [4096, None], ids=['win4096', 'nowin'])
def test_ragged_kernel_compiles_at_7_queries_a_head(v5e, rows, span, window):
    """28 query heads on 4 KV heads of 128 over the cell's window pool and
    a table to 16k tokens: the decode walk takes the stacked block over 28
    query rows (not a whole number of 8-row sublane tiles), the span
    schedule a tile of 64 positions x 7."""
    from distllm_tpu.ops.paged_attention import ragged_paged_attention_pallas

    pool = v5e((12509, 16, 4 * 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, bt, ctx, pos, ql: ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=ql, sliding_window=window
        )
    ).lower(
        v5e((rows, span, 28, 128), jnp.bfloat16), pool, pool,
        v5e((rows, 1024), jnp.int32), v5e((rows,), jnp.int32),
        v5e((rows, span), jnp.int32), v5e((rows,), jnp.int32),
    ).compile()
    _assert_kernel_compiled(compiled)
    assert _kernel_schedules(compiled) == ['walk' if span == 1 else 'grid']


# What PR 55 must not move: a walk under 8 queries a KV head and a span
# over one. sha256 of the kernel's program as PR 54's tree lowers it
# (``_kernel_programs``: locations dropped, so this file's and the kernel
# file's lines may move and a changed operation may not).
@pytest.mark.parametrize('rows, span, nh, nkv, table, program', [
    (32, 1, 32, 8, 256,
     '0be73ec3ca3822ee5e69ac3bb86ea4709f945b9afed51086761367ea275bcd19'),
    (48, 1, 28, 4, 1024,
     '048a28d6a6228629663a2dc3010761055a622fd50ba549274f4595bfc034be66'),
    (4, 512, 32, 8, 256,
     'e5b747121b8a059e6e51bac038c920983d3b60f28e761f84c0d1b9fcd92d0743'),
], ids=['mistral7b_walk', 'smallthinker_walk', 'span512'])
def test_walks_under_8_and_spans_lower_to_the_parents_program(
    v5e, rows, span, nh, nkv, table, program
):
    """The rule that gives 8 queries a head and more the stacked block
    changes nothing below them (``mistral7b``'s 8 x 4 and ``smallthinker``'s
    4 x 7 stacked rows: same fold, same constants, operation for operation)
    and nothing in the span schedule."""
    import hashlib

    from distllm_tpu.ops.paged_attention import ragged_paged_attention_pallas

    pool = v5e((4096, 16, nkv * _HD), jnp.bfloat16)
    text = jax.jit(
        lambda q, k, v, bt, ctx, pos, ql: ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=ql
        )
    ).lower(
        v5e((rows, span, nh, _HD), jnp.bfloat16), pool, pool,
        v5e((rows, table), jnp.int32), v5e((rows,), jnp.int32),
        v5e((rows, span), jnp.int32), v5e((rows,), jnp.int32),
    ).as_text()
    (kernel,) = _kernel_programs(text)
    assert hashlib.sha256(kernel.encode()).hexdigest() == program


# ---- sdar (PR 54): a block of 4 positions folded into a group of 8 through
# the row walk, and prefill spans under a block-causal ceiling ----

@pytest.mark.parametrize('rows, span, heads, block_length', [
    (48, 1, 128, 1), (4, 512, 32, 4), (4, 512, 32, 1),
], ids=['walk_32_a_head', 'span512_block4', 'span512_causal'])
def test_ragged_kernel_compiles_for_blocks_of_positions(
    v5e, rows, span, heads, block_length
):
    """Over the sdar cell's stacked pool of 48 layers: the decode walk at 32
    queries a KV head (128 query rows on 4 KV heads: the stacked block, the
    queries a head, since PR 55), the
    span schedule's tile of 64 positions x 8 under ``block_length`` 4; and
    ``block_length`` 1 lowers to the text a call that never names it does."""
    from distllm_tpu.ops.paged_attention import ragged_paged_attention_pallas

    pool = v5e((48, 2560, 16, 4 * 128), jnp.bfloat16)
    shapes = (
        v5e((rows, span, heads, 128), jnp.bfloat16), pool, pool,
        v5e((rows, 128), jnp.int32), v5e((rows,), jnp.int32),
        v5e((rows, span), jnp.int32), v5e((rows,), jnp.int32),
    )

    def call(**kw):
        return jax.jit(
            lambda q, k, v, bt, ctx, pos, ql: ragged_paged_attention_pallas(
                q, k, v, bt, ctx, pos, q_lens=ql, layer=jnp.int32(7), **kw
            )
        ).lower(*shapes)

    # Both from ONE line: a Mosaic body carries its callers' locations.
    lowered, plain = [call(**kw) for kw in ({'block_length': block_length}, {})]
    compiled = lowered.compile()
    _assert_kernel_compiled(compiled)
    assert _kernel_schedules(compiled) == ['walk' if span == 1 else 'grid']
    assert (lowered.as_text() == plain.as_text()) == (block_length == 1)
