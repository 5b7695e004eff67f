"""Compile-only TPU lowering tests (``test_aot_tpu.py``) of the routed
experts' families in prefill: the two forms of the experts (PR 40;
models/moe.py; banks read as they lie, the grouped matmul kept, the kernel
traced once) and ``lfm2``'s chunk prefill over its pool."""

import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402
from aot_tpu import (  # noqa: F401 -- fixtures, asked for by name
    granite_cell,
    kanana_cell,
    laguna_cell,
    lfm2_cell,
    smallthinker_cell,
    v5e,
)
from aot_tpu import (
    _GRANITE_PREFILL_TEMP_AT_PR43,
    _GROUPED_AT_PR43,
    _HD,
    _NKV,
    _assert_banks_are_streamed_by_a_dot,
    _assert_span_calls_keep_the_grid,
    _assert_stacked_pool_is_addressed,
    _granite,
    _holds,
    _lfm2,
)


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
