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

The topology is described ONLY inside the module-scoped fixture: only one
process may hold libtpu, every xdist worker imports this file, and a
module that touches the topology at import gives the workers different
tests to collect.
"""

import functools

import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402


def _compile(build, mosaic_kernel: bool = True):
    """Run a compile — HARD, no Mosaic-artifact tolerance.

    History (ISSUE 3 → ISSUE 12): the retired decode-only Pallas kernel's
    block layout tripped some Mosaic toolchains with an ``implicit dim
    change`` lowering rejection (message mutated across containers:
    ``Overriding implicit dim change`` → ``Unsupported implicit dim
    change: from "16,{0,0},(16,128),-2" to none``), and these tests
    xfail-gated on that message family for nine PRs. The ragged kernel
    that replaced it (``ragged_paged_attention_pallas``) was designed
    around the artifact — lane-replicated 128-wide softmax state instead
    of 1-wide minor dims, no in-kernel reshapes across the head dim — and
    compiles clean on this container's toolchain, so the gate is retired:
    ANY compile failure, Mosaic or otherwise, is a hard test failure
    again. ``mosaic_kernel`` is kept for call-site documentation of which
    builds lower a Pallas kernel at all.
    """
    del mosaic_kernel
    return build()


@pytest.fixture(scope='module')
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform='tpu', topology_name='v5e:2x2'
        )
    except Exception as exc:  # no libtpu / unsupported platform
        pytest.skip(f'no compile-only TPU topology available: {exc!r}')
    sharding = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip (the next run warns and
    # compiles again): keep the cache off around this module.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield sds
    jax.config.update('jax_enable_compilation_cache', cache_was_on)
    compilation_cache.reset_cache()


def _assert_kernel_compiled(compiled) -> None:
    assert 'tpu_custom_call' in compiled.as_text(), (
        'no Pallas kernel in the compiled program'
    )


def _kernel_schedules(compiled) -> list:
    """``'walk'`` or ``'grid'`` for each paged-attention call of a
    compiled program: a serialized Mosaic body names the functions its
    source lines are in, and only the row walk's names ``_walk_row``."""
    import base64
    import re

    bodies = re.findall(
        r'custom_call_config[^A-Za-z0-9]+body[^A-Za-z0-9]+'
        r'([A-Za-z0-9+/=]{100,})',
        compiled.as_text(),
    )
    bodies = [base64.b64decode(body) for body in bodies]
    return [
        'walk' if b'_walk_row' in body else 'grid'
        for body in bodies if b'_ragged_paged_attn_kernel' in body
    ]


def _assert_decode_calls_walk(compiled) -> None:
    """Every paged-attention call of a decode window is a span of one
    and takes the row walk."""
    schedules = _kernel_schedules(compiled)
    assert schedules and set(schedules) == {'walk'}, schedules


def _assert_span_calls_keep_the_grid(compiled) -> None:
    schedules = _kernel_schedules(compiled)
    assert schedules and set(schedules) == {'grid'}, schedules


# ---- kernel-only compiles at the real widths (tier-1, seconds each) ----

# Mistral-7B-Instruct-v0.3 attention widths at the serving batch.
_B, _NH, _NKV, _HD = 32, 32, 8, 128
_7B = (_B, _NH, _NKV, 512)  # rows, heads, KV heads, the table's tokens


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


def _count_equations(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    total += _count_equations(sub)
    return total


def _kernel_equations(span, *, rows, nh, nkv, hd, value_lanes=None):
    """Equations of the kernel's jaxpr, nested ones counted, as a call
    at these widths traces it (no topology needed: tracing only)."""
    from distllm_tpu.ops.paged_attention import ragged_paged_attention_pallas

    sds = jax.ShapeDtypeStruct
    pool = sds((712, 16, nkv * hd), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, bt, ctx, pos, ql: ragged_paged_attention_pallas(
            q, k, v, bt, ctx, pos, q_lens=ql, value_lanes=value_lanes
        )
    )(
        sds((rows, span, nh, hd), jnp.bfloat16), pool,
        None if value_lanes else pool, sds((rows, 256), jnp.int32),
        sds((rows,), jnp.int32), sds((rows, span), jnp.int32),
        sds((rows,), jnp.int32),
    )
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == 'pallas_call']
    return _count_equations(call.params['jaxpr'])


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
            (v5e((blocks, block, cfg.num_kv_heads * cfg.head_dim),
                 jnp.bfloat16),) * layers
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


# ---- the pools go to the kernel as they lie (tier-1, ~10 s a program) ----

def _hlo_defs(text: str) -> dict:
    """``name -> (result type, opcode, the rest of the line)`` of every
    instruction of a compiled program's text."""
    import re

    defs = {}
    for line in text.splitlines():
        m = re.match(r'^\s*(?:ROOT )?%(\S+) = (.*)$', line)
        if not m:
            continue
        rest, depth = m.group(2), 0
        for i, ch in enumerate(rest):
            depth += (ch == '(') - (ch == ')')
            if ch == ' ' and depth == 0:
                break
        call = rest[i + 1:]
        defs[m.group(1)] = (rest[:i], call.partition('(')[0], call)
    return defs


def _holds(result_type: str, shape: tuple) -> bool:
    """Does an instruction's result (a tuple's members too) hold an array
    of ``shape``'s size?"""
    import re

    size = int(np.prod(shape))
    return any(
        int(np.prod([int(d) for d in dims.split(',')])) == size
        for dims in re.findall(r'bf16\[([0-9,]+)\]', result_type)
    )


def _holds_a_scatter(text: str, call: str) -> bool:
    """Is the computation a ``fusion`` calls one that scatters (the
    in-place write)?"""
    import re

    callee = re.search(r'calls=%(\S+?)[,\s]', call + ' ')
    body = text.partition(f'\n%{callee.group(1)} (')[2].partition('\n}')[0]
    return ' scatter(' in body


def _assert_pools_go_to_the_kernel_as_they_lie(compiled, buffers) -> None:
    """No relayout of a pool-sized array, and the paged kernel reads the
    pools themselves: (1) no ``reshape``, ``copy`` or ``transpose`` whose
    result is the size of one of ``buffers``; (2) each K and V operand of
    each paged kernel call is, behind bitcasts and the compiler's own
    staging of a buffer through its fast memory (``copy-start`` /
    ``copy-done``), a parameter, a loop's carry, or the in-place write (a
    ``scatter``, alone or fused)."""
    import re

    text = compiled.as_text()
    defs = _hlo_defs(text)
    relayouts = [
        f'%{name} = {result[:40]} {opcode}'
        for name, (result, opcode, _) in defs.items()
        if opcode in ('reshape', 'copy', 'transpose')
        and any(_holds(result, shape) for shape in buffers)
    ]
    assert not relayouts, relayouts

    kernels = [
        call for _, opcode, call in defs.values()
        if opcode == 'custom-call' and 'tpu_custom_call' in call
    ]
    assert kernels, 'no Pallas kernel in the compiled program'
    pools_read = 0
    for call in kernels:
        operands = re.match(r'custom-call\(([^)]*)\)', call).group(1)
        for operand in operands.split(', '):
            name = operand.rpartition('%')[2]  # past an /*index=n*/ note
            if not any(_holds(defs[name][0], shape) for shape in buffers):
                continue
            while defs[name][1] in ('bitcast', 'copy-done', 'copy-start'):
                name = re.match(
                    r'[a-z\-]+\(%([^,)\s]+)', defs[name][2]
                ).group(1)
            result, opcode, producer = defs[name]
            assert opcode in ('parameter', 'get-tuple-element', 'scatter') or (
                opcode == 'fusion' and _holds_a_scatter(text, producer)
            ), f'%{name} = {result[:40]} {producer[:80]}'
            pools_read += 1
    assert pools_read >= 2  # a K and a V at the least


def _hlo_computations(text: str) -> tuple[dict, str]:
    """``(name -> the lines of its body, the entry's name)`` of a compiled
    program's text."""
    import re

    bodies, entry, into = {}, None, None
    for line in text.splitlines():
        m = re.match(r'^(ENTRY )?%(\S+) \(.*\{\s*$', line)
        if m:
            into = bodies.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif line.startswith('}'):
            into = None
        elif into is not None:
            into.append(line)
    return bodies, entry


_CALLED = r'(?:body|condition|to_apply|calls|\w+_computations?)=\{?((?:%[^\s,)}]+(?:, )?)+)'


def _weight_slices_in_the_step_scan(text: str, params) -> list:
    """Every op in a loop's body (the computations reached from a ``while``
    of the entry: the step scan, what it calls and the loops inside it, and
    no fused computation) that MAKES an array of a weight's shape: a
    ``fusion``, ``copy``, ``slice``, ``dynamic-slice`` or ``transpose``
    with a result, or a tuple's member, that has the dimensions of a leaf of
    ``params`` or of one layer of a stacked leaf (a matrix of a MiB or more;
    axes of 1 left aside). A fusion whose root is a ``bitcast`` makes
    nothing, and one that holds a matmul makes its product (``solar``'s 128
    rows by 8192 are also a low-rank kernel's shape). ``[(op, results of
    that shape)]``.

    A weight is read by the dot that multiplies by it, where it lies. An op
    of this list reads a layer's kernel out of its stack and writes it down
    again every step: the compiler merges the static slices that an unrolled
    walk takes of one stacked leaf into one multi-output fusion, and a slice
    inside such a fusion can no longer be an operand of its dot (``PERF.md``
    section 6, PR 51). The cure is ``models.common.unstack``."""
    import re

    def dims(shape):
        return tuple(int(d) for d in shape if int(d) != 1)

    weights = set()
    for leaf in jax.tree.leaves(params):
        for shape in (leaf.shape, leaf.shape[1:]):
            size = int(np.prod(shape)) * jnp.dtype(leaf.dtype).itemsize
            if len(dims(shape)) >= 2 and size >= 1 << 20:
                weights.add(dims(shape))
    bodies, entry = _hlo_computations(text)
    defs = {name: _hlo_defs('\n'.join(lines)) for name, lines in bodies.items()}

    def called(instructions, opcodes=None):
        return [
            name for _, opcode, call in instructions.values()
            if (opcode != 'fusion' if opcodes is None else opcode in opcodes)
            for group in re.findall(_CALLED, call)
            for name in re.findall(r'%([^\s,)}]+)', group)
        ]

    loops = called(defs[entry], ('while',))
    reached = set()
    while loops:
        name = loops.pop()
        if name not in reached:
            reached.add(name)
            loops += called(defs[name])
    found = []
    for comp in sorted(reached):
        for name, (result, opcode, call) in defs[comp].items():
            if opcode not in ('fusion', 'copy', 'slice', 'dynamic-slice', 'transpose'):
                continue
            held = [
                f'{dtype}[{shape}]'
                for dtype, shape in re.findall(r'(\w+)\[([0-9,]+)\]', result)
                if dims(shape.split(',')) in weights
            ]
            if held and opcode == 'fusion':
                (callee,) = called({name: (result, opcode, call)}, ('fusion',))
                fused = _hlo_defs('\n'.join(bodies[callee])).values()
                root = [op for _, op, _ in fused][-1]
                if root == 'bitcast' or any(
                    op in ('convolution', 'dot') for _, op, _ in fused
                ):
                    continue
            if held:
                found.append((f'%{name} = {opcode}', held))
    return found


def _assert_no_weight_is_sliced_in_the_step_scan(compiled, params) -> None:
    found = _weight_slices_in_the_step_scan(compiled.as_text(), params)
    assert not found, [
        f'{op}: {len(held)} x {held[0]}' for op, held in found
    ]


@pytest.fixture(scope='module')
def laguna_cell(v5e):
    """The laguna cell's configuration cut to one period of layers (one
    full layer, three window layers; the dense MLP and three sparse), the
    parameters and the pools at the cell's sizes: 9600 and 1757 blocks."""
    import json
    from pathlib import Path

    from distllm_tpu.models import laguna

    root = Path(__file__).resolve().parents[1]
    hf = json.loads((root / 'benchmarks/configs/laguna-xs.2.json').read_text())
    hf['num_hidden_layers'] = 4
    for key in ('layer_types', 'mlp_layer_types', 'num_attention_heads_per_layer'):
        hf[key] = hf[key][:4]
    cfg = laguna.LagunaConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: laguna.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    buffers = [
        (blocks, 16, cfg.num_kv_heads * cfg.head_dim)
        for blocks in (9600, 1757)
    ]
    pools = tuple(
        (v5e(shape, jnp.bfloat16),) * cfg.count(kind)
        for kind, shape in zip(('full', 'window'), buffers)
    )
    return laguna, cfg, params, pools, buffers


@pytest.fixture(scope='module')
def laguna_window(v5e, laguna_cell):
    """The decode window at the cell's 48 rows, compiled once."""
    laguna, cfg, params, pools, buffers = laguna_cell
    b, i32, f32 = 48, jnp.int32, jnp.float32

    def window_fn(params, ids, pos, ctx, k, v, bt, steps_left, *sampling):
        return laguna.decode_loop(
            params, cfg, ids, pos, k, v, bt, ctx, steps_left, *sampling,
            num_steps=8, attn_backend='pallas', max_table_positions=8448,
        )

    return jax.jit(window_fn, donate_argnums=(4, 5)).lower(
        params, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), pools, pools,
        (v5e((b, 528), i32),) * 2, v5e((b,), i32), v5e((b,), f32),
        v5e((b,), f32), v5e((b,), f32), v5e((b,), i32), v5e((b,), jnp.uint32),
    ).compile()


def test_laguna_decode_window_reads_the_pools_as_they_lie(laguna_cell, laguna_window):
    _assert_pools_go_to_the_kernel_as_they_lie(laguna_window, laguna_cell[4])
    _assert_decode_calls_walk(laguna_window)


def test_laguna_chunk_prefill_reads_the_pools_as_they_lie(v5e, laguna_cell):
    """The ``(512, 4)`` program: four rows of a 512-token span."""
    laguna, cfg, params, pools, buffers = laguna_cell
    i32 = jnp.int32
    compiled = jax.jit(
        lambda params, ids, pos, k, v, bt, ctx, tails: laguna.prefill_paged(
            params, cfg, ids, pos, k, v, bt, ctx, tails,
            max_table_positions=8448, attn_backend='pallas',
        ), donate_argnums=(3, 4),
    ).lower(
        params, v5e((4, 512), i32), v5e((4, 512), i32), pools, pools,
        (v5e((4, 528), i32),) * 2, v5e((4,), i32), v5e((4,), i32),
    ).compile()
    _assert_pools_go_to_the_kernel_as_they_lie(compiled, buffers)
    _assert_span_calls_keep_the_grid(compiled)


# ---- a latent pool's planes go to the kernel as they lie (PR 32) ----

@pytest.fixture(scope='module')
def kanana_cell(v5e):
    """The cell's configuration cut to three layers (the dense one and two
    sparse), the parameters in the form the engine serves from
    (``deepseek_v3.serving_params``) and the planes at the cell's sizes."""
    import json
    from pathlib import Path

    from distllm_tpu.models import deepseek_v3

    root = Path(__file__).resolve().parents[1]
    hf = json.loads((root / 'benchmarks/configs/kanana-2-30b-a3b.json').read_text())
    hf['num_hidden_layers'] = 3
    cfg = deepseek_v3.DeepseekV3Config.from_hf_config(hf)
    shapes = jax.eval_shape(lambda: deepseek_v3.serving_params(
        deepseek_v3.init_on_device(jax.random.PRNGKey(0), cfg)
    ))
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    plane = (hf['engine']['num_blocks'], 16, cfg.stored_row)
    return deepseek_v3, cfg, params, (v5e(plane, jnp.bfloat16),) * 3, plane, hf['engine']


def test_decode_window_reads_the_planes_as_they_lie(v5e, kanana_cell):
    """No op of the decode window has a whole plane as its result but the
    in-place write, and the kernel reads the planes themselves."""
    deepseek_v3, cfg, params, planes, plane, engine = kanana_cell
    b, i32, f32 = engine['max_num_seqs'], jnp.int32, jnp.float32

    def window_fn(params, ids, pos, ctx, k, v, bt, steps_left, *sampling):
        return deepseek_v3.decode_loop(
            params, cfg, ids, pos, k, v, bt, ctx, steps_left, *sampling,
            num_steps=8, attn_backend='pallas', max_table_positions=8448,
        )

    compiled = jax.jit(window_fn, donate_argnums=(4, 5)).lower(
        params, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), planes, (),
        v5e((b, 528), i32), v5e((b,), i32), v5e((b,), f32), v5e((b,), f32),
        v5e((b,), f32), v5e((b,), i32), v5e((b,), jnp.uint32),
    ).compile()
    _assert_pools_go_to_the_kernel_as_they_lie(compiled, [plane])
    _assert_decode_calls_walk(compiled)
    # the decode calls of the kernel, as the roofline metric's pattern
    # names them: [rows, 1 KV head, 32 queries, 512 value lanes]
    assert f'bf16[{b},1,32,512]' in compiled.as_text()


def test_chunk_prefill_reads_the_planes_as_they_lie(v5e, kanana_cell):
    """The ``(512, 4)`` program: four rows of a 512-token span, 16384
    queries on the one KV head a row."""
    deepseek_v3, cfg, params, planes, plane, _ = kanana_cell
    i32 = jnp.int32
    compiled = jax.jit(
        lambda params, ids, pos, k, v, bt, ctx, tails: deepseek_v3.prefill_paged(
            params, cfg, ids, pos, k, v, bt, ctx, tails,
            max_table_positions=8448, attn_backend='pallas',
        ), donate_argnums=(3, 4),
    ).lower(
        params, v5e((4, 512), i32), v5e((4, 512), i32), planes, (),
        v5e((4, 528), i32), v5e((4,), i32), v5e((4,), i32),
    ).compile()
    _assert_pools_go_to_the_kernel_as_they_lie(compiled, [plane])
    _assert_span_calls_keep_the_grid(compiled)


def _assert_stacked_pool_is_addressed(compiled, pool) -> None:
    """A stacked pool ``[L, blocks, block_size, folded]`` is addressed,
    never sliced: (1) no instruction's result is the size of a layer's
    plane; (2) every instruction whose result is the size of the pool is
    the pool handed on (a parameter, the loop and its tuples, a bitcast,
    the compiler's own staging of a small pool through its fast memory) or
    the in-place write (a ``scatter``, alone or in a fusion); (3) each
    kernel's K and V operand is the pool itself behind bitcasts."""
    text = compiled.as_text()
    defs = _hlo_defs(text)
    planes = [
        f'%{name} = {result[:40]} {opcode}'
        for name, (result, opcode, _) in defs.items()
        if _holds(result, pool[1:])
    ]
    assert not planes, planes

    handed_on = (
        'parameter', 'get-tuple-element', 'tuple', 'while', 'bitcast',
        'copy-start', 'copy-done', 'scatter',
    )
    others = [
        f'%{name} = {result[:40]} {call[:60]}'
        for name, (result, opcode, call) in defs.items()
        if _holds(result, pool) and opcode not in handed_on
        and not (opcode == 'fusion' and _holds_a_scatter(text, call))
    ]
    assert not others, others
    _assert_pools_go_to_the_kernel_as_they_lie(compiled, [pool])


def _mistral_7b(v5e, num_layers):
    """Mistral-7B's widths cut to ``num_layers``: module, config, and the
    parameters as shapes."""
    from distllm_tpu.models import mistral

    cfg = mistral.MistralConfig(dtype='bfloat16', num_layers=num_layers)
    shapes = jax.eval_shape(
        lambda: mistral.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    return mistral, cfg, jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)


@functools.lru_cache(maxsize=None)
def _mistral_window(v5e, pool):
    """The 7B decode window (8 steps, 32 rows, the layers unrolled) over
    ``mistral7b.batch_generate``'s 640 blocks a layer."""
    mistral, cfg, params = _mistral_7b(v5e, pool[0])
    b, i32, f32 = 32, jnp.int32, jnp.float32
    pools = v5e(pool, jnp.bfloat16)
    return jax.jit(
        lambda p, i, po, c, k, v, bt, sl, tmp, tp_, mp, tk, sd:
            mistral.decode_loop(
                p, cfg, i, po, k, v, bt, c, sl, tmp, tp_, mp, tk, sd,
                num_steps=8, attn_backend='pallas', max_table_positions=4096,
            ),
        donate_argnums=(4, 5),
    ).lower(
        params, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), pools, pools,
        v5e((b, 256), i32), v5e((b,), i32), v5e((b,), f32), v5e((b,), f32),
        v5e((b,), f32), v5e((b,), i32), v5e((b,), jnp.uint32),
    ).compile()


def _mistral_chunk_prefill(v5e, pool):
    """The ``(4, 512)`` span program: the layers under the ROLLED scan, so
    the layer whose pages are meant is a traced value."""
    mistral, cfg, params = _mistral_7b(v5e, pool[0])
    i32 = jnp.int32
    pools = v5e(pool, jnp.bfloat16)
    return jax.jit(
        lambda p, ids, pos, k, v, bt, ctx, tails: mistral.prefill_paged(
            p, cfg, ids, pos, k, v, bt, ctx, tails,
            max_table_positions=4096, attn_backend='pallas',
        ), donate_argnums=(3, 4),
    ).lower(
        params, v5e((4, 512), i32), v5e((4, 512), i32), pools, pools,
        v5e((4, 256), i32), v5e((4,), i32), v5e((4,), i32),
    ).compile()


@functools.lru_cache(maxsize=None)
def _granite_window(v5e, pool):
    """``granite-4.0-h-small``'s decode window (8 steps, 96 rows, 8192
    blocks a layer) with TWO attention layers among two Mamba ones: the
    cell's one-layer stack has nothing to slice."""
    import json
    from pathlib import Path

    from distllm_tpu.models import granite_hybrid

    root = Path(__file__).resolve().parents[1]
    hf = json.loads(
        (root / 'benchmarks/configs/granite-4.0-h-small.json').read_text()
    )
    hf['layer_types'] = ['mamba', 'attention'] * pool[0]
    hf['num_hidden_layers'] = len(hf['layer_types'])
    cfg = granite_hybrid.GraniteHybridConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: granite_hybrid.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    b, i32, f32 = hf['engine']['max_num_seqs'], jnp.int32, jnp.float32
    state = jax.tree.map(
        lambda a: v5e((b, *a.shape), a.dtype), cfg.state_spec()
    )
    pools = v5e(pool, jnp.bfloat16)
    return jax.jit(
        lambda p, i, po, c, k, v, bt, sl, tmp, tp_, mp, tk, sd, st:
            granite_hybrid.decode_loop(
                p, cfg, i, po, k, v, bt, c, sl, tmp, tp_, mp, tk, sd,
                num_steps=8, attn_backend='pallas', state=st,
            ),
        donate_argnums=(4, 5, 13),
    ).lower(
        params, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), pools, pools,
        v5e((b, 256), i32), v5e((b,), i32), v5e((b,), f32), v5e((b,), f32),
        v5e((b,), f32), v5e((b,), i32), v5e((b,), jnp.uint32), state,
    ).compile()


# The chunk prefill is compiled over 8 layers, not 2: a 42 MB pool the
# compiler stages through the chip's fast memory for the length of the
# rolled loop and copies between the memory spaces inside it, which a
# cell's 671 MB pool is too large for; at 168 MB the text is the cell's.
@pytest.mark.parametrize('program,pool', [
    (_mistral_window, (2, 640, 16, _NKV * _HD)),
    (_mistral_chunk_prefill, (8, 640, 16, _NKV * _HD)),
    (_granite_window, (2, 8192, 16, _NKV * _HD)),
], ids=['mistral_decode_window', 'mistral_chunk_prefill', 'granite_decode_window'])
def test_stacked_pool_is_addressed_not_sliced(v5e, program, pool):
    """A family whose pool stays stacked hands it to the writers and to the
    paged kernel WHOLE, with the layer whose pages are meant. Sliced out
    for the kernel call (a custom call wants its operand materialised), a
    layer's plane was copied out of the pool and written back: 128 plane
    fusions and 66 pool-sized ones a step of ``mistral7b``'s window, 4.27
    ms of a 29.61 ms step on the chip (PR 31). The decode windows' calls
    take the row walk, the span program's keep the grid over chunks."""
    compiled = program(v5e, pool)
    _assert_stacked_pool_is_addressed(compiled, pool)
    if program is _mistral_chunk_prefill:
        _assert_span_calls_keep_the_grid(compiled)
    else:
        _assert_decode_calls_walk(compiled)


@pytest.mark.parametrize('program', ['write_prefill', 'gather_blocks'])
def test_stacked_pool_programs_copy_no_pool(v5e, program):
    """The two programs that touch every layer of a stacked pool at once,
    at ``mistral7b``'s sizes (a 0.67 GB pool beside 14.5 GB of weights: a
    copy of it does not fit). Written with a window over the layer axis
    (``.at[:, blocks, offsets]``, ``c[:, ids]``) the TPU compiler moves
    that axis of the whole head-folded pool inward and back: 671 MB of
    temporaries, and ``RESOURCE_EXHAUSTED`` at the cell's first dense
    prefill (on the chip, PR 31). As (layer, block, offset) rows: none."""
    from distllm_tpu.generate.engine.engine import (
        _gather_blocks_all_layers,
        _write_prefill_all_layers,
    )

    pool = v5e((32, 640, 16, _NKV * _HD), jnp.bfloat16)
    if program == 'write_prefill':
        # K and V as the engine's dense prefill program hands them over:
        # rows already folded, so this program (lowered again inside a
        # served window for each commitment of the pools) relayouts nothing
        seq = v5e((32, 1, 512, _NKV * _HD), jnp.bfloat16)
        compiled = jax.jit(_write_prefill_all_layers, donate_argnums=(0, 1)).lower(
            pool, pool, seq, seq, v5e((1, 256), jnp.int32), v5e((1,), jnp.int32)
        ).compile()
    else:
        compiled = jax.jit(_gather_blocks_all_layers).lower(
            pool, pool, v5e((8,), jnp.int32)
        ).compile()
    plane = 640 * 16 * _NKV * _HD * 2
    assert compiled.memory_analysis().temp_size_in_bytes < plane


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


def _lfm2(v5e, layers=None):
    """The ``lfm2`` cell's configuration (cut to its first ``layers`` if
    given): module, config, parameters, the pools' shape, the state and the
    engine's settings at the cell's sizes, 19,200 blocks and 96 slots."""
    import json
    from pathlib import Path

    from distllm_tpu.models import lfm2

    root = Path(__file__).resolve().parents[1]
    hf = json.loads((root / 'benchmarks/configs/lfm2-8b-a1b.json').read_text())
    if layers is not None:
        hf['layer_types'] = hf['layer_types'][:layers]
        hf['num_hidden_layers'] = layers
    cfg = lfm2.Lfm2MoeConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: lfm2.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    engine = hf['engine']
    pool = (cfg.num_paged_layers, engine['num_blocks'], 16, 512)
    state = jax.tree.map(
        lambda a: v5e((engine['max_num_seqs'], *a.shape), a.dtype),
        cfg.state_spec(),
    )
    return lfm2, cfg, params, pool, state, engine


@pytest.fixture(scope='module')
def lfm2_cell(v5e):
    """The cell's configuration cut to its first 7 layers (two attention
    layers, every kind of layer: conv under the dense MLP, conv and
    attention under the experts)."""
    return _lfm2(v5e, 7)


@pytest.fixture(scope='module')
def lfm2_window(v5e, lfm2_cell):
    """The decode window at the cell's 96 rows, compiled once."""
    lfm2, cfg, params, pool, state, engine = lfm2_cell
    b, i32, f32 = engine['max_num_seqs'], jnp.int32, jnp.float32
    assert b == 96
    pools = v5e(pool, jnp.bfloat16)
    return jax.jit(
        lambda p, i, po, c, k, v, bt, sl, tmp, tp_, mp, tk, sd, st:
            lfm2.decode_loop(
                p, cfg, i, po, k, v, bt, c, sl, tmp, tp_, mp, tk, sd,
                num_steps=8, attn_backend='pallas', max_table_positions=8448,
                state=st,
            ),
        donate_argnums=(4, 5, 13),
    ).lower(
        params, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), pools, pools,
        v5e((b, 528), i32), v5e((b,), i32), v5e((b,), f32), v5e((b,), f32),
        v5e((b,), f32), v5e((b,), i32), v5e((b,), jnp.uint32), state,
    ).compile()


def test_lfm2_decode_window_addresses_the_pool(lfm2_cell, lfm2_window):
    """The decode window at the cell's 96 rows: the stacked pool of 512-
    lane rows goes to the writers and to the kernel whole (no plane and no
    pool copied, no head padded to a tile), every call takes the row walk,
    and the state's buffers are rewritten in place."""
    pool = lfm2_cell[3]
    _assert_stacked_pool_is_addressed(lfm2_window, pool)
    _assert_decode_calls_walk(lfm2_window)
    # nothing as large as the weights' smallest bank is left over as a
    # temporary: the pools and the state are updated where they lie
    assert lfm2_window.memory_analysis().temp_size_in_bytes < 256 << 20


def test_lfm2_chunk_prefill_addresses_the_pool(v5e, lfm2_cell):
    """The ``(512, 4)`` program: four rows of a 512-token span through the
    conv spans (state gathered and scattered by slot) and the grid over
    spans at 64-wide heads."""
    lfm2, cfg, params, pool, state, _ = lfm2_cell
    i32 = jnp.int32
    pools = v5e(pool, jnp.bfloat16)
    compiled = jax.jit(
        lambda p, ids, pos, k, v, bt, ctx, tails, st, slots: lfm2.prefill_paged(
            p, cfg, ids, pos, k, v, bt, ctx, tails, st, slots,
            max_table_positions=8448, attn_backend='pallas',
        ), donate_argnums=(3, 4, 8),
    ).lower(
        params, v5e((4, 512), i32), v5e((4, 512), i32), pools, pools,
        v5e((4, 528), i32), v5e((4,), i32), v5e((4,), i32), state,
        v5e((4,), i32),
    ).compile()
    _assert_stacked_pool_is_addressed(compiled, pool)
    _assert_span_calls_keep_the_grid(compiled)


# ---- the routed experts' two forms (PR 40; models/moe.py) ----

def _assert_banks_are_streamed_by_a_dot(compiled, banks) -> None:
    """A decode window's routed experts run the dense form: (1) no grouped
    matmul (``ragged-dot``) is left in the program; (2) the layer's bank is
    ADDRESSED inside its stack, never copied: outside the fused
    computations (whose instructions are not materialised) nothing but the
    stack handed on (a parameter, the loop and its tuples, a bitcast) has a
    result the size of a bank or of the stack. Sliced out for a kernel call
    a bank was 100-226 MB copied a call (PR 26)."""
    import re

    text = compiled.as_text()
    assert 'ragged-dot' not in text
    assert ' convolution(' in text  # what a batched dot is on the TPU
    fused = set(re.findall(r'calls=%([^,\s)]+)', text))
    copies, computation = [], None
    for line in text.splitlines():
        head = re.match(r'^(?:ENTRY )?%(\S+) \(', line)
        if head:
            computation = head.group(1)
            continue
        m = re.match(r'^\s*(?:ROOT )?%(\S+) = (\S+) ([a-z\-]+)\(', line)
        if not m or computation in fused:
            continue
        name, result, opcode = m.groups()
        if opcode in ('parameter', 'get-tuple-element', 'tuple', 'while',
                      'bitcast'):
            continue
        if any(_holds(result, shape) for shape in banks):
            copies.append(f'%{name} = {result[:50]} {opcode}')
    assert not copies, copies


def test_granite_decode_window_streams_its_banks_densely(v5e):
    """96 rows over 36 held experts of ``[4096, 768]``, the layer a traced
    index of the scan over a kind's layers."""
    compiled = _granite_window(v5e, (2, 8192, 16, _NKV * _HD))
    _assert_banks_are_streamed_by_a_dot(
        compiled, [(36, 4096, 768), (2, 36, 4096, 768)]
    )


def test_lfm2_decode_window_streams_its_banks_densely(lfm2_cell, lfm2_window):
    """96 rows over 16 held experts of ``[2048, 1792]``, the layer a static
    index (the layers unrolled); the 7-layer cut stacks 5 sparse layers."""
    bank = jax.tree.leaves(lfm2_cell[2]['sparse']['gate'])[0].shape
    assert bank == (5, 16, 2048, 1792)
    _assert_banks_are_streamed_by_a_dot(lfm2_window, [bank[1:], bank])


def _granite(v5e, layer_types=None):
    """The granite cell's configuration (cut to ``layer_types`` if given):
    module, config, parameters and state as shapes at the cell's sizes."""
    import json
    from pathlib import Path

    from distllm_tpu.models import granite_hybrid

    root = Path(__file__).resolve().parents[1]
    hf = json.loads(
        (root / 'benchmarks/configs/granite-4.0-h-small.json').read_text()
    )
    if layer_types is not None:
        hf['layer_types'] = list(layer_types)
        hf['num_hidden_layers'] = len(layer_types)
    cfg = granite_hybrid.GraniteHybridConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: granite_hybrid.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    state = jax.tree.map(
        lambda a: v5e((hf['engine']['max_num_seqs'], *a.shape), a.dtype),
        cfg.state_spec(),
    )
    return granite_hybrid, cfg, params, state


@pytest.fixture(scope='module')
def granite_cell(v5e):
    """Cut to four layers: an attention layer among three Mamba ones."""
    return _granite(v5e, ('mamba', 'mamba', 'attention', 'mamba'))


def _granite_full_prefill(v5e, bucket, rows):
    """The granite cell's ``(bucket, rows)`` prefill program at FULL depth
    (the nine Mamba layers under one scan), compiled; and the stack of
    banks' shape."""
    granite_hybrid, cfg, params, state = _granite(v5e)
    bank = jax.tree.leaves(params['mamba']['gate'])[0].shape
    assert bank == (9, 36, 4096, 768)
    i32 = jnp.int32
    pools = v5e((1, 8192, 16, _NKV * _HD), jnp.bfloat16)
    compiled = jax.jit(
        lambda p, ids, pos, k, v, bt, ctx, tails, st, slots:
            granite_hybrid.prefill_paged(
                p, cfg, ids, pos, k, v, bt, ctx, tails, st, slots,
                attn_backend='pallas',
            ),
        donate_argnums=(3, 4, 8),
    ).lower(
        params, v5e((rows, bucket), i32), v5e((rows, bucket), i32), pools,
        pools, v5e((rows, 256), i32), v5e((rows,), i32), v5e((rows,), i32),
        state, v5e((rows,), i32),
    ).compile()
    return compiled, bank


@pytest.mark.parametrize('bucket, rows', [(64, 1), (16, 4)])
def test_granite_tail_prefill_reads_its_banks_as_they_lie(v5e, bucket, rows):
    """A chunk tail of the granite cell at FULL depth (the nine Mamba
    layers under one scan): its 64 rows take the dense form, and the stack
    of banks stays where it lies. At 121-128 rows the compiler turned the
    whole ``bf16[9, 36, 4096, 768]`` stacks over outside that scan (three
    1.9 GB copies: the program did not fit the chip, PR 40), which a
    three-layer cut does not show; the rule stops at 120 rows for it."""
    from distllm_tpu.models import moe

    assert moe.expert_form(bucket * rows, 10, 36, 72, 4096, 768) == 'dense'
    assert moe.expert_form(128, 10, 36, 72, 4096, 768) == 'grouped'
    compiled, bank = _granite_full_prefill(v5e, bucket, rows)
    _assert_banks_are_streamed_by_a_dot(compiled, [bank[1:], bank])
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


# The temporaries of this tree's programs, compiled here for the described
# v5e at full depth. PR 43 re-pinned them, on purpose: with no float32
# ``[pairs, hidden]`` array behind the kernel they fell from PR 41's
# 1,337,857,536 and 111,249,408 bytes (``ragged_dot`` three times a layer;
# PR 42 stayed within 64 MB of those) by 168 MB and 45 MB.
_GRANITE_PREFILL_TEMP_AT_PR43 = {(512, 4): 1169762816, (128, 1): 66647552}


@pytest.mark.parametrize('bucket, rows', sorted(_GRANITE_PREFILL_TEMP_AT_PR43))
def test_granite_grouped_prefill_reads_its_banks_as_they_lie(
    v5e, bucket, rows, monkeypatch
):
    """The grouped form over the repo's kernel (PR 42) at FULL depth, the
    cell's largest prefill program and the ``(128, 1)`` tail behind the
    dense form's fence: the kernel takes the stack of banks whole and adds
    the layer in its index map, so no ``copy`` in the program has a result
    the size of a bank or of the stack (the lesson of PR 40's fence: a cut
    to a few layers does not show what XLA does to a stack under the full
    scan), and the temporaries stay within 64 MB of the pinned ones, either
    way: growth is what took this cell out of the chip's memory at PR 40."""
    import re

    from distllm_tpu.models import moe

    monkeypatch.setattr(moe, 'grouped_backend', lambda: 'pallas')
    assert moe.expert_form(bucket * rows, 10, 36, 72, 4096, 768) == 'grouped'
    compiled, bank = _granite_full_prefill(v5e, bucket, rows)
    text = compiled.as_text()
    assert 'ragged-dot' not in text
    copies = [
        line.strip()[:120] for line in text.splitlines()
        if (m := re.match(r'^\s*(?:ROOT )?%\S+ = (\S+) copy\(', line))
        and any(_holds(m.group(1), shape) for shape in (bank[1:], bank))
    ]
    assert not copies, copies
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert abs(temp - _GRANITE_PREFILL_TEMP_AT_PR43[bucket, rows]) < 64 << 20


def test_lfm2_prefill_traces_the_kernel_once(v5e, monkeypatch):
    """``lfm2`` unrolls its 22 expert layers: the ``(512, 4)`` program's
    text holds ONE ``expert_matmuls`` function (the jitted op, its tiles
    static and the layer an operand) under the 22 calls of its two kinds
    of sparse layer, and so one body of each of its two kernel calls, not
    44: what the program pays in set-up is a shape's, not a layer's (PR
    42; PR 37 was refused for 13.7 s of ``setup_s``)."""
    import re

    from distllm_tpu.models import moe

    lfm2, cfg, params, pool, state, _ = _lfm2(v5e)  # all 24 layers
    assert jax.tree.leaves(params['sparse']['gate'])[0].shape == (
        22, 16, 2048, 1792
    )
    monkeypatch.setattr(moe, 'grouped_backend', lambda: 'pallas')
    i32 = jnp.int32
    pools = v5e(pool, jnp.bfloat16)
    text = jax.jit(
        lambda p, ids, pos, k, v, bt, ctx, tails, st, slots: lfm2.prefill_paged(
            p, cfg, ids, pos, k, v, bt, ctx, tails, st, slots,
            max_table_positions=8448, attn_backend='pallas',
        ),
    ).lower(
        params, v5e((4, 512), i32), v5e((4, 512), i32), pools, pools,
        v5e((4, 528), i32), v5e((4,), i32), v5e((4,), i32), state,
        v5e((4,), i32),
    ).as_text()
    assert len(re.findall(r'call @lfm2_\w+_sparse_layer\(', text)) == 22
    assert text.count('func.func private @expert_matmuls(') == 1
    assert text.count('kernel_name = "grouped_matmul"') == 2
    assert 'ragged_dot' not in text


def _chunk_prefill_text(v5e, family, request) -> str:
    """The lowered text of a family's ``(512, 4)`` prefill program at its
    cell's widths."""
    i32 = jnp.int32
    spans = (v5e((4, 512), i32), v5e((4, 512), i32))
    rows = (v5e((4,), i32), v5e((4,), i32))
    kw = dict(attn_backend='pallas')
    if family == 'granite':
        module, cfg, params, state = request.getfixturevalue('granite_cell')
        pools = v5e((1, 8192, 16, _NKV * _HD), jnp.bfloat16)
        operands = (pools, pools, v5e((4, 256), i32), *rows, state,
                    v5e((4,), i32))
    elif family == 'lfm2':
        module, cfg, params, pool, state, _ = request.getfixturevalue(
            'lfm2_cell'
        )
        pools = v5e(pool, jnp.bfloat16)
        operands = (pools, pools, v5e((4, 528), i32), *rows, state,
                    v5e((4,), i32))
    elif family in ('laguna', 'smallthinker'):
        module, cfg, params, pools, _ = request.getfixturevalue(f'{family}_cell')
        width = 528 if family == 'laguna' else 1024
        operands = (pools, pools, (v5e((4, width), i32),) * 2, *rows)
    else:
        module, cfg, params, planes, _, _ = request.getfixturevalue(
            'kanana_cell'
        )
        operands = (planes, (), v5e((4, 528), i32), *rows)
    if family != 'granite':
        kw['max_table_positions'] = 16384 if family == 'smallthinker' else 8448
    return jax.jit(
        lambda p, ids, pos, k, v, bt, ctx, tails, *state:
            module.prefill_paged(
                p, cfg, ids, pos, k, v, bt, ctx, tails, *state, **kw
            ),
    ).lower(params, *spans, *operands).as_text()


@pytest.mark.parametrize(
    'family', ['granite', 'laguna', 'kanana', 'lfm2', 'smallthinker']
)
def test_chunk_prefill_keeps_the_grouped_matmul(
    v5e, family, request, monkeypatch
):
    """Every ``(512, 4)`` prefill program stays on the grouped form: the
    text is the one the program lowers to with the rule taken out and
    every call sent to the grouped form. On the chip (``grouped_backend``
    says so there; the test says it here) the grouped matmul is the repo's
    kernel (PR 42): its call is in the text and no ``ragged_dot``."""
    from distllm_tpu.models import moe

    monkeypatch.setattr(moe, 'grouped_backend', lambda: 'pallas')
    texts = []
    # One call site: a Mosaic kernel's serialized body carries the lines
    # of the frames it was traced under.
    for rule in (moe.expert_form, lambda *shape: 'grouped'):
        monkeypatch.setattr(moe, 'expert_form', rule)
        texts.append(_chunk_prefill_text(v5e, family, request))
    assert 'kernel_name = "grouped_matmul"' in texts[0]
    assert 'ragged_dot' not in texts[0]
    assert texts[0] == texts[1]


# The sha256 (first 16 digits) of what ``routed_experts`` lowers to since PR
# 43 (the way back is one pass: a token's k rows gathered in bfloat16, the
# gate, the ``where`` and the sum over k behind the gather) for a
# 2,048-token call at each family's widths and arguments, the layer a
# traced index into the stack. PR 42's values (the kernel's two calls in
# front of a float32 product in sorted order, its gather back and the sum)
# stood here until PR 43 moved them, as PR 39's (``ragged_dot`` three
# times) had until PR 42.
_GROUPED_AT_PR43 = {
    'granite': ((10, 36, 72, 4096, 768, 9), {}, '472be48ae7bb2c0d'),
    'laguna': ((8, 64, 256, 2048, 512, 19), {'routed_scale': 2.5},
               '0c6026f94373d7c2'),
    'kanana': ((6, 32, 128, 2048, 768, 23),
               {'scoring': 'sigmoid', 'routed_scale': 2.448, 'bias': True},
               'a06b6ba9d9445b61'),
    'lfm2': ((4, 16, 32, 2048, 1792, 22),
             {'scoring': 'sigmoid', 'norm_eps': 1e-6, 'bias': True},
             '165852da581852f1'),
}


@pytest.mark.parametrize('family', sorted(_GROUPED_AT_PR43))
def test_grouped_form_lowers_to_the_parents_text(v5e, family, monkeypatch):
    """The grouped form is pinned to the byte: a prefill program's expert
    layer lowers to the text it had at PR 43 (the compile cache's key, and
    what XLA compiles, follow from it). PR 43 moved all four on purpose:
    the float32 product in sorted order, its gather back to token order and
    the sum over k behind the kernel's two calls became one gather of a
    token's k rows in the rows' dtype with the gate and the sum behind it;
    no float32 tensor of ``[pairs, hidden]`` is left (the row gather in
    front of the kernel, bfloat16, is PR 42's still). A Mosaic kernel's
    serialized body carries the checkout's path and the lines of the
    frames it was traced under, so the two bodies are left out of the
    hash (``tests/test_grouped_matmul.py`` holds what
    they compute): their operands, shapes and the call's other fields are
    in it. A change of jax may move all four at once; a change of one is a
    change to the grouped path."""
    import re

    import hashlib

    from distllm_tpu.models import moe

    (k, held, routed, hidden, width, layers), kw, want = (
        _GROUPED_AT_PR43[family]
    )
    monkeypatch.setattr(moe, 'grouped_backend', lambda: 'pallas')
    kw = dict(kw)
    biased = kw.pop('bias', False)
    bf, f32 = jnp.bfloat16, jnp.float32
    assert moe.expert_form(2048, k, held, routed, hidden, width) == 'grouped'

    def fn(x, router, gate, up, down, bias, counted, layer):
        return moe.routed_experts(
            x, router, gate, up, down, k, first_expert=0, counted=counted,
            layer=layer, select_bias=bias if biased else None, **kw,
        )

    text = jax.jit(fn).lower(
        v5e((2048, hidden), bf), v5e((hidden, routed), bf),
        v5e((layers, held, hidden, width), bf),
        v5e((layers, held, hidden, width), bf),
        v5e((layers, held, width, hidden), bf), v5e((routed,), f32),
        v5e((2048,), jnp.bool_), v5e((), jnp.int32),
    ).as_text()
    text, bodies = re.subn(r'\\22body\\22: \\22[^\\]*\\22', 'body', text)
    assert bodies == 2
    assert f'tensor<{2048 * k}x{hidden}xf32>' not in text
    assert f'tensor<2048x{k}x{hidden}xbf16>' in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


# ---- falcon_h1 (PR 41): pages AND state in every layer, 5 queries a KV head ----

@pytest.fixture(scope='module')
def falcon_h1_cell(v5e):
    """The cell's configuration at the cut's FULL depth (6 layers, one
    stacked tree): the parameters, the pool of every layer and the state of
    every layer at the cell's sizes, 8192 blocks and 96 slots."""
    import json
    from pathlib import Path

    from distllm_tpu.models import falcon_h1

    root = Path(__file__).resolve().parents[1]
    hf = json.loads(
        (root / 'benchmarks/configs/falcon-h1-34b.json').read_text()
    )
    cfg = falcon_h1.FalconH1Config.from_hf_config(hf)
    assert cfg.num_layers == 6 and cfg.num_heads // cfg.num_kv_heads == 5
    shapes = jax.eval_shape(
        lambda: falcon_h1.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    engine = hf['engine']
    pool = (cfg.num_layers, engine['num_blocks'], 16, 512)
    state = jax.tree.map(
        lambda a: v5e((engine['max_num_seqs'], *a.shape), a.dtype),
        cfg.state_spec(),
    )
    return falcon_h1, cfg, params, pool, state, engine


@pytest.fixture(scope='module')
def falcon_h1_window(v5e, falcon_h1_cell):
    """The decode window at the cell's 96 rows and full depth, compiled
    once."""
    falcon_h1, cfg, params, pool, state, engine = falcon_h1_cell
    b, i32, f32 = engine['max_num_seqs'], jnp.int32, jnp.float32
    assert b == 96
    pools = v5e(pool, jnp.bfloat16)
    return jax.jit(
        lambda p, i, po, c, k, v, bt, sl, tmp, tp_, mp, tk, sd, st:
            falcon_h1.decode_loop(
                p, cfg, i, po, k, v, bt, c, sl, tmp, tp_, mp, tk, sd,
                num_steps=8, attn_backend='pallas', max_table_positions=4096,
                state=st,
            ),
        donate_argnums=(4, 5, 13),
    ).lower(
        params, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), pools, pools,
        v5e((b, 256), i32), v5e((b,), i32), v5e((b,), f32), v5e((b,), f32),
        v5e((b,), f32), v5e((b,), i32), v5e((b,), jnp.uint32), state,
    ).compile()


def test_falcon_h1_decode_window_updates_pages_and_state_in_place(
    falcon_h1_cell, falcon_h1_window
):
    """The decode window at the cell's 96 rows and full depth: every layer
    writes a page and a state slot in the same step. The stacked pool goes
    to the writers and to the kernel whole, every kernel call (5 queries a
    KV head) takes the row walk, and nothing as large as a layer's states
    (96 x 4 MB) is left over as a temporary beside the sampler's rows."""
    compiled = falcon_h1_window
    _assert_stacked_pool_is_addressed(compiled, falcon_h1_cell[3])
    _assert_decode_calls_walk(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 1536 << 20


def test_falcon_h1_chunk_prefill_addresses_the_pool(v5e, falcon_h1_cell):
    """The ``(512, 4)`` program at full depth: four rows of a 512-token
    span through ONE scan over the six layers, the SSD spans of state 256
    in two groups (state gathered and scattered by slot) and the grid over
    spans at 5 queries a KV head."""
    falcon_h1, cfg, params, pool, state, _ = falcon_h1_cell
    i32 = jnp.int32
    pools = v5e(pool, jnp.bfloat16)
    compiled = jax.jit(
        lambda p, ids, pos, k, v, bt, ctx, tails, st, slots:
            falcon_h1.prefill_paged(
                p, cfg, ids, pos, k, v, bt, ctx, tails, st, slots,
                max_table_positions=4096, attn_backend='pallas',
            ),
        donate_argnums=(3, 4, 8),
    ).lower(
        params, v5e((4, 512), i32), v5e((4, 512), i32), pools, pools,
        v5e((4, 256), i32), v5e((4,), i32), v5e((4,), i32), state,
        v5e((4,), i32),
    ).compile()
    _assert_stacked_pool_is_addressed(compiled, pool)
    _assert_span_calls_keep_the_grid(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2048 << 20


# ---- solar_open2 (PR 45): a float32 matrix state a KDA layer, 8 queries a KV head ----

@pytest.fixture(scope='module')
def solar_open2_window(v5e):
    """``(compiled, parameters, pool, rows)``: the decode window at the
    cell's slots and full depth (one period: G K K K, 40 held experts a
    layer), compiled once with the family's kernels on."""
    import json
    from pathlib import Path

    from distllm_tpu.models import moe, solar_open2

    root = Path(__file__).resolve().parents[1]
    hf = json.loads(
        (root / 'benchmarks/configs/solar-open2-250b.json').read_text()
    )
    cfg = solar_open2.SolarOpen2Config.from_hf_config(hf)
    assert cfg.layer_indices() == [('gqa', 0), ('kda', 0), ('kda', 1), ('kda', 2)]
    assert cfg.num_heads // cfg.num_kv_heads == 8
    shapes = jax.eval_shape(
        lambda: solar_open2.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    engine = hf['engine']
    b, i32, f32 = engine['max_num_seqs'], jnp.int32, jnp.float32
    pool = (1, engine['num_blocks'], 16, 1024)
    state = jax.tree.map(
        lambda a: v5e((b, *a.shape), a.dtype), cfg.state_spec()
    )
    pools = v5e(pool, jnp.bfloat16)
    table = engine['max_model_len'] // engine['block_size']
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, 'grouped_backend', lambda: 'pallas')
        compiled = jax.jit(
            lambda p, i, po, c, k, v, bt, sl, tmp, tp_, mp, tk, sd, st:
                solar_open2.decode_loop(
                    p, cfg, i, po, k, v, bt, c, sl, tmp, tp_, mp, tk, sd,
                    num_steps=8, attn_backend='pallas', state=st,
                ),
            donate_argnums=(4, 5, 13),
        ).lower(
            params, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), pools,
            pools, v5e((b, table), i32), v5e((b,), i32), v5e((b,), f32),
            v5e((b,), f32), v5e((b,), f32), v5e((b,), i32),
            v5e((b,), jnp.uint32), state,
        ).compile()
    return compiled, params, pool, b


def test_solar_open2_decode_window_updates_its_matrix_states_in_place(solar_open2_window):
    """The decode window at the cell's slots and full depth for a described
    v5e: the one attention layer's pool goes to the kernel as it lies and
    its calls (8 queries a KV head) take the row walk; the three
    matrix-state pools (slots x 4 MB each) are donated and rewritten in
    place, so nothing as large as ONE of them is left over as a temporary."""
    from distllm_tpu.models import moe

    compiled, _, pool, b = solar_open2_window
    # a stack of one layer has no plane to slice: no relayout of the pool,
    # and the kernel reads the pool itself
    _assert_pools_go_to_the_kernel_as_they_lie(compiled, [pool, pool[1:]])
    # At 121 rows and over the routed experts take the grouped kernel, whose
    # serialized bodies name what the process traced before them, the paged
    # kernel among it: the walk is counted among the other bodies.
    import base64
    import re

    bodies = [
        base64.b64decode(body) for body in re.findall(
            r'custom_call_config[^A-Za-z0-9]+body[^A-Za-z0-9]+'
            r'([A-Za-z0-9+/=]{100,})', compiled.as_text(),
        )
    ]
    paged = [body for body in bodies if b'_grouped_matmul_kernel' not in body]
    assert paged and all(b'_walk_row' in body for body in paged)
    grouped = moe.expert_form(b, 8, 40, 320, 4096, 1280) == 'grouped'
    assert (len(paged) < len(bodies)) == grouped
    one_matrix_pool = b * 64 * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_matrix_pool


# ---- the span form of the Kimi-delta rule as a kernel (PR 46) ----

def _solar_open2_prefill(v5e, rows, span=512):
    """``solar_open2.prefill_paged`` at the cell's widths (one period, 40
    held experts) lowered for ``rows`` spans of ``span`` tokens."""
    import json
    from pathlib import Path

    from distllm_tpu.models import solar_open2

    root = Path(__file__).resolve().parents[1]
    hf = json.loads(
        (root / 'benchmarks/configs/solar-open2-250b.json').read_text()
    )
    cfg = solar_open2.SolarOpen2Config.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: solar_open2.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    engine, i32 = hf['engine'], jnp.int32
    pools = v5e((1, engine['num_blocks'], 16, 1024), jnp.bfloat16)
    state = jax.tree.map(
        lambda a: v5e((engine['max_num_seqs'], *a.shape), a.dtype),
        cfg.state_spec(),
    )
    table = engine['max_model_len'] // engine['block_size']
    return jax.jit(
        lambda p, ids, pos, k, v, bt, cl, tl, st, sl:
            solar_open2.prefill_paged(
                p, cfg, ids, pos, k, v, bt, cl, tl, st, sl,
                attn_backend='pallas',
            ),
        donate_argnums=(3, 4, 8),
    ).lower(
        params, v5e((rows, span), i32), v5e((rows, span), i32), pools, pools,
        v5e((rows, table), i32), v5e((rows,), i32), v5e((rows,), i32), state,
        v5e((rows,), i32),
    )


@pytest.fixture(scope='module')
def solar_open2_prefill_defs(v5e):
    """The cell's ``(512, 4)`` prefill program compiled for a described v5e
    with the family's kernels on, as ``_hlo_defs`` of its text."""
    from distllm_tpu.models import moe
    from distllm_tpu.ops import kda

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, 'grouped_backend', lambda: 'pallas')
        patch.setattr(kda, 'span_backend', lambda: 'pallas')
        text = _solar_open2_prefill(v5e, rows=4).compile().as_text()
    return _hlo_defs(text)


def _kernel_calls(defs: dict, name: str) -> dict:
    """``call's name -> its operands' names`` of a kernel's custom calls."""
    import re

    return {
        call_name: re.findall(r'%([\w.\-]+)', call.partition(')')[0])
        for call_name, (_, opcode, call) in defs.items()
        if opcode == 'custom-call' and call_name.startswith(name)
    }


def _behind_the_moves(defs: dict, name: str) -> str:
    """The instruction that made ``name``'s array, behind XLA's moves of it
    between memories (an asynchronous copy, whole or in slices that a
    ``ConcatBitcast`` joins): they change where it lies, not how."""
    import re

    moves = ('copy-done', 'copy-start', 'slice-done', 'slice-start', 'bitcast')
    while True:
        _, opcode, call = defs[name]
        if opcode not in moves and 'ConcatBitcast' not in call:
            return name
        name = re.findall(r'%([\w.\-]+)', call.partition(')')[0])[0]


def test_solar_open2_prefill_hands_the_span_kernel_its_operands_as_they_lie(
    solar_open2_prefill_defs,
):
    """The cell's ``(512, 4)`` prefill program for a described v5e with the
    span form as the kernel: Mosaic takes the kernel at the published head
    sizes, each KDA layer calls it once, and ``q, k, v, g`` reach it as
    ``[B, S, H d]`` straight from the fusions that make them and ``o`` leaves
    it so: no copy or transpose of an operand stands between (the scan read
    ``[N, B, H, C, d]`` float32 copies of all five)."""
    defs = solar_open2_prefill_defs
    calls = _kernel_calls(defs, 'kda_span')
    assert len(calls) == 3
    for operands in calls.values():
        assert len(operands) == 6
        for operand in operands:
            opcode = defs[operand][1]
            assert opcode not in ('copy', 'transpose'), (operand, opcode)
    moved = [
        name for name, (result, opcode, _) in defs.items()
        if opcode in ('copy', 'transpose') and 'f32[4,512,8192]' in result
    ]
    assert not moved


def test_solar_open2_prefill_makes_q_k_v_in_one_kernel_a_layer(
    solar_open2_prefill_defs,
):
    """The same program's way into the rule (PR 47): each KDA layer calls
    ``kda_inputs`` once; the three projections reach it straight from their
    matmuls' fusions, in bfloat16 and with no concatenation, copy or
    transpose between; its three results are the span kernel's first three
    operands as they leave it; and neither the float32 passes of the XLA
    form (``f32[4,515,24576]``, ``f32[4,512,24576]``) nor the convolutions'
    whole input in any dtype (only the next span's rows read it) is left
    anywhere in the program."""
    import re

    defs = solar_open2_prefill_defs
    ways_in = _kernel_calls(defs, 'kda_inputs')
    assert len(ways_in) == 3
    for operands in ways_in.values():
        assert len(operands) == 9  # q~, k~, v~; the carried rows and taps x 3
        made_by = [_behind_the_moves(defs, name) for name in operands[:3]]
        assert len(set(made_by)) == 3
        for operand, maker in zip(operands[:3], made_by):
            assert defs[operand][0].startswith('bf16[4,512,8192]'), operand
            result, opcode, call = defs[maker]
            # a projection's matmul, in the layout the kernel reads
            assert opcode == 'fusion' and 'dot_general' in call, (maker, call)
            assert result.startswith('bf16[4,512,8192]{2,1,0'), (maker, result)
    spans = _kernel_calls(defs, 'kda_span')
    fed = set()
    for operands in spans.values():
        for i, operand in enumerate(operands[:3]):
            _, opcode, call = defs[operand]
            assert opcode == 'get-tuple-element', (operand, opcode)
            source = re.findall(r'%([\w.\-]+)', call)[0]
            assert source in ways_in and f'index={i}' in call, call
            fed.add(source)
    assert fed == set(ways_in)
    whole = re.compile(r'\[4,51[25],24576\]')
    left = [
        (name, result[:40]) for name, (result, opcode, _) in defs.items()
        if whole.search(result) and opcode != 'parameter'
    ]
    assert not left


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


# ---- a looped model's planes: 192 of them under one table (PR 48) ----

@pytest.fixture(scope='module')
def ouro_cell(v5e):
    """The ``ouro-2.6b`` configuration at FULL depth (48 layers, 4 passes:
    PR 40's lesson, a cut in depth does not show what XLA does to a stacked
    tree under the whole walk), the parameters as shapes, and the cell's
    pool: 192 planes of the configuration's blocks."""
    import json
    from pathlib import Path

    from distllm_tpu.models import ouro

    root = Path(__file__).resolve().parents[1]
    hf = json.loads((root / 'benchmarks/configs/ouro-2.6b.json').read_text())
    cfg = ouro.OuroConfig.from_hf_config(hf).model_copy(update={'dtype': hf['dtype']})
    assert (cfg.num_layers, cfg.total_ut_steps) == (48, 4)
    shapes = jax.eval_shape(lambda: ouro.init_on_device(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    engine = hf['engine']
    blocks, row = engine['num_blocks'], cfg.num_kv_heads * cfg.head_size
    if blocks * engine['block_size'] * row == cfg.hidden_size * cfg.intermediate_size:
        # 352 blocks make a plane the size of an MLP kernel (2048 x 5632),
        # and the checks below tell arrays apart by their size
        blocks -= 1
    pool = (cfg.num_planes, blocks, engine['block_size'], row)
    return ouro, cfg, params, pool, engine


@pytest.fixture(scope='module')
def ouro_window(v5e, ouro_cell):
    """The decode window at the cell's rows and full depth, compiled once."""
    ouro, cfg, params, pool, engine = ouro_cell
    b, i32, f32 = engine['max_num_seqs'], jnp.int32, jnp.float32
    tables = -(-engine['max_model_len'] // engine['block_size'])
    pools = v5e(pool, jnp.bfloat16)
    return jax.jit(
        lambda p, i, po, c, k, v, bt, sl, tmp, tp_, mp, tk, sd: ouro.decode_loop(
            p, cfg, i, po, k, v, bt, c, sl, tmp, tp_, mp, tk, sd,
            num_steps=engine['decode_steps'], attn_backend='pallas',
            max_table_positions=engine['max_model_len'],
        ),
        donate_argnums=(4, 5),
    ).lower(
        params, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), pools, pools,
        v5e((b, tables), i32), v5e((b,), i32), v5e((b,), f32), v5e((b,), f32),
        v5e((b,), f32), v5e((b,), i32), v5e((b,), jnp.uint32),
    ).compile()


def test_ouro_decode_window_addresses_192_planes(ouro_cell, ouro_window):
    """The decode window at the cell's rows: the passes a rolled loop around
    the 48 unrolled layers, both pools in its carry, the plane a traced ``t
    * L + l``. No op has a pool-sized result but the in-place write, none a
    plane-sized one, and the kernel's decode calls (one query a KV head, a
    folded row of 2048 lanes) take the row walk over the pool as it lies."""
    _assert_stacked_pool_is_addressed(ouro_window, ouro_cell[3])
    _assert_decode_calls_walk(ouro_window)
    # 48 bodies and not 192: the kernel's calls of one pass
    assert len(_kernel_schedules(ouro_window)) == ouro_cell[1].num_layers


def test_ouro_chunk_prefill_addresses_192_planes(v5e, ouro_cell):
    """The ``(512, 1)`` program: a rolled layer scan inside the rolled loop
    over the passes, the plane traced in both."""
    ouro, cfg, params, pool, engine = ouro_cell
    i32 = jnp.int32
    tables = -(-engine['max_model_len'] // engine['block_size'])
    pools = v5e(pool, jnp.bfloat16)
    compiled = jax.jit(
        lambda p, ids, pos, k, v, bt, ctx, tails: ouro.prefill_paged(
            p, cfg, ids, pos, k, v, bt, ctx, tails,
            max_table_positions=engine['max_model_len'], attn_backend='pallas',
        ), donate_argnums=(3, 4),
    ).lower(
        params, v5e((1, 512), i32), v5e((1, 512), i32), pools, pools,
        v5e((1, tables), i32), v5e((1,), i32), v5e((1,), i32),
    ).compile()
    _assert_stacked_pool_is_addressed(compiled, pool)
    _assert_span_calls_keep_the_grid(compiled)


# ---- no weight is sliced inside the step scan (PR 51) ----

def _kanana_window(v5e, cell, params, layers=None):
    """The ``kanana`` decode window over ``params`` as the engine compiles
    it (``_compile_auto_layout``): ``auto_layout_formats`` for the weights."""
    from jax.experimental.layout import Format

    from distllm_tpu.generate.engine.engine import auto_layout_formats

    deepseek_v3, cfg, _, planes, _, engine = cell
    if layers is not None:
        cfg = cfg.model_copy(update={'num_layers': layers})
        planes = planes[:1] * layers
    b, i32, f32 = engine['max_num_seqs'], jnp.int32, jnp.float32
    bare = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)

    def window_fn(params, ids, pos, ctx, k, v, bt, steps_left, *sampling):
        return deepseek_v3.decode_loop(
            params, cfg, ids, pos, k, v, bt, ctx, steps_left, *sampling,
            num_steps=8, attn_backend='pallas', max_table_positions=8448,
        )

    return jax.jit(
        window_fn, donate_argnums=(4, 5),
        in_shardings=(auto_layout_formats(bare),) + (Format(),) * 12,
    ).lower(
        bare, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), planes, (),
        v5e((b, 528), i32), v5e((b,), i32), v5e((b,), f32), v5e((b,), f32),
        v5e((b,), f32), v5e((b,), i32), v5e((b,), jnp.uint32),
    ).compile()


def _kanana_stacks(cell, layers=None):
    """The family's public tree (stacks) at the cell's widths, as shapes."""
    deepseek_v3, cfg = cell[:2]
    if layers is not None:
        cfg = cfg.model_copy(update={'num_layers': layers})
    return jax.eval_shape(
        lambda: deepseek_v3.init_on_device(jax.random.PRNGKey(0), cfg)
    )


def _window_and_params(family, v5e, request):
    """``(compiled decode window, its parameter tree)`` of a family, the
    windows this file builds at their cut depths (default layouts but
    ``kanana``'s, which is compiled as the engine compiles it)."""
    if family == 'kanana':
        cell = request.getfixturevalue('kanana_cell')
        return _kanana_window(v5e, cell, cell[2]), cell[2]
    if family == 'solar_open2':
        return request.getfixturevalue('solar_open2_window')[:2]
    if family in ('mistral', 'granite'):
        build = {'mistral': _mistral_window, 'granite': _granite_window}[family]
        compiled = build(v5e, (2, {'mistral': 640, 'granite': 8192}[family], 16, _NKV * _HD))
        return compiled, compiled.args_info[0][0]
    cell = request.getfixturevalue(f'{family}_cell')
    return request.getfixturevalue(f'{family}_window'), cell[2]


def _sliced(what: str):
    return pytest.mark.xfail(strict=True, reason=(
        f'{what}: written down for the next writer, each a claim in its own '
        "cell with its own traced pair (PERF.md section 7); a cure turns the "
        'case red until this mark goes'
    ))


@pytest.mark.parametrize('family', [
    'kanana', 'laguna', 'ouro', 'solar_open2', 'mistral', 'smallthinker',
    pytest.param('lfm2', marks=_sliced(
        'two multi-output fusions at the cut\'s two attention layers, 2 x '
        'bf16[1,2048,2048] (8 MB each) and 2 x bf16[1,2048,512] (2 MB each), '
        'one result of each in VMEM: 10 MB a layer a step, 60 MB at the '
        'cell\'s six attention layers if none stays in VMEM'
    )),
    pytest.param('falcon_h1', marks=_sliced(
        'six single-result fusions bf16[1,5120,2560] (the attention q '
        'kernels, 26 MB each) and six bf16[1,5120,512] (5 MB each) at the '
        'cut\'s 6 layers, every result in VMEM here (a read the dot no '
        'longer makes itself): 189 MB a step written back only if one leaves VMEM'
    )),
    pytest.param('granite', marks=_sliced(
        'one fusion of 2 x bf16[1,4096,4096] (32 MB each, both in HBM) with '
        'two attention layers in the stack: 64 MB read and written a step; '
        'the cell\'s cut has one attention layer and nothing to slice'
    )),
])
def test_decode_window_slices_no_weight(v5e, request, family):
    """An unrolled window takes each layer's kernels out of their stacks by
    static slices, and a static slice folds into its dot only until the
    compiler merges the layers' slices of one leaf into one fusion: then
    every layer's kernel is read and written down again each step (the
    ``kanana`` window's q, k-up and v-up kernels, 805 MB and 2.2 ms of a 24
    ms step at 24 layers; PR 51). No op in any window's step scan makes an
    array of a weight's shape; a family that shows one holds that leaf a
    layer an array (``common.unstack``)."""
    _assert_no_weight_is_sliced_in_the_step_scan(
        *_window_and_params(family, v5e, request)
    )


def test_the_stacked_kanana_window_is_what_the_check_is_for(v5e, kanana_cell):
    """The same window over the family's PUBLIC tree, the stacks the parent
    served from: one fusion a leaf of q, k-up and v-up, each with all three
    layers' kernels as its results, at the 3-layer cut."""
    stacks = _kanana_stacks(kanana_cell)
    found = _weight_slices_in_the_step_scan(
        _kanana_window(v5e, kanana_cell, stacks).as_text(), stacks
    )
    assert sorted((len(held), held[0]) for _, held in found) == [
        (3, 'bf16[1,2048,6144]'), (3, 'bf16[1,512,4096]'), (3, 'bf16[1,512,4096]'),
    ]
    assert all(op.endswith('= fusion') for op, _ in found)


@pytest.mark.slow
@pytest.mark.parametrize('form', ['serving', 'stacks'])
def test_full_depth_kanana_window_slices_no_weight(v5e, kanana_cell, form):
    """The cell's 24 layers (17-19 s a compile): a cut shows the pattern,
    the full depth its cost. Over the stacks six fusions, 19 + 5 results a
    leaf, most of them written to HBM (``S(1)`` marks the few in VMEM); over
    the serving form none, and no multi-output fusion of a weight's slices."""
    deepseek_v3 = kanana_cell[0]
    params = _kanana_stacks(kanana_cell, 24)
    if form == 'serving':
        params = jax.eval_shape(deepseek_v3.serving_params, params)
    text = _kanana_window(v5e, kanana_cell, params, 24).as_text()
    found = _weight_slices_in_the_step_scan(text, params)
    if form == 'serving':
        assert not found, found
        return
    assert sorted(len(held) for _, held in found) == [5, 5, 5, 19, 19, 19]
    assert sum(len(held) for _, held in found) == 3 * 24


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


@pytest.fixture(scope='module')
def smallthinker_cell(v5e):
    """The smallthinker cell's configuration at its own depth (16 layers:
    4 full, 12 window), the parameters and the pools at the cell's sizes:
    22000 blocks and the engine's own 12509."""
    import json
    from pathlib import Path

    from distllm_tpu.models import smallthinker

    root = Path(__file__).resolve().parents[1]
    hf = json.loads(
        (root / 'benchmarks/configs/smallthinker-21b-a3b.json').read_text()
    )
    cfg = smallthinker.SmallThinkerConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: smallthinker.serving_params(
            smallthinker.init_on_device(jax.random.PRNGKey(0), cfg)
        )
    )  # the tree the engine serves from: q, k and v a layer an array
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    rows = hf['engine']['max_num_seqs']
    buffers = [
        (blocks, 16, cfg.num_kv_heads * cfg.head_dim)
        for blocks in (hf['engine']['num_blocks'], 1 + rows * 258 + 4 * 31)
    ]
    pools = tuple(
        (v5e(shape, jnp.bfloat16),) * cfg.count(kind)
        for kind, shape in zip(('full', 'window'), buffers)
    )
    return smallthinker, cfg, params, pools, buffers


@pytest.fixture(scope='module')
def smallthinker_window(v5e, smallthinker_cell):
    """The decode window at the cell's 48 rows and depth, compiled once."""
    smallthinker, cfg, params, pools, buffers = smallthinker_cell
    b, i32, f32 = 48, jnp.int32, jnp.float32

    def window_fn(params, ids, pos, ctx, k, v, bt, steps_left, *sampling):
        return smallthinker.decode_loop(
            params, cfg, ids, pos, k, v, bt, ctx, steps_left, *sampling,
            num_steps=8, attn_backend='pallas', max_table_positions=16384,
        )

    return jax.jit(window_fn, donate_argnums=(4, 5)).lower(
        params, v5e((b,), i32), v5e((b,), i32), v5e((b,), i32), pools, pools,
        (v5e((b, 1024), i32),) * 2, v5e((b,), i32), v5e((b,), f32),
        v5e((b,), f32), v5e((b,), f32), v5e((b,), i32), v5e((b,), jnp.uint32),
    ).compile()


def test_smallthinker_decode_window_reads_the_pools_as_they_lie(
    smallthinker_cell, smallthinker_window
):
    """No pool-sized result but the scatters, every kernel call the row
    walk, and the programs fit the chip beside weights and pools."""
    _assert_pools_go_to_the_kernel_as_they_lie(
        smallthinker_window, smallthinker_cell[4]
    )
    _assert_decode_calls_walk(smallthinker_window)
    memory = smallthinker_window.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.5 * 2**30


def test_smallthinker_chunk_prefill_reads_the_pools_as_they_lie(v5e, smallthinker_cell):
    """The ``(512, 4)`` program at the cell's depth: four rows of a
    512-token span (``test_chunk_prefill_keeps_the_grouped_matmul`` holds
    its experts to the grouped kernel)."""
    smallthinker, cfg, params, pools, buffers = smallthinker_cell
    i32 = jnp.int32
    compiled = jax.jit(
        lambda params, ids, pos, k, v, bt, ctx, tails: smallthinker.prefill_paged(
            params, cfg, ids, pos, k, v, bt, ctx, tails,
            max_table_positions=16384, attn_backend='pallas',
        ), donate_argnums=(3, 4),
    ).lower(
        params, v5e((4, 512), i32), v5e((4, 512), i32), pools, pools,
        (v5e((4, 1024), i32),) * 2, v5e((4,), i32), v5e((4,), i32),
    ).compile()
    _assert_pools_go_to_the_kernel_as_they_lie(compiled, buffers)
    _assert_span_calls_keep_the_grid(compiled)
