"""Compile-only TPU lowering tests (``test_aot_tpu.py``) of the families'
chunk prefills: the pools and planes go to the kernel as they lie, and the
span form of the Kimi-delta rule is handed its operands as they lie."""

import numpy as np
import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402
from aot_tpu import (  # noqa: F401 -- fixtures, asked for by name
    falcon_h1_cell,
    kanana_cell,
    laguna_cell,
    ouro_cell,
    sdar_cell,
    smallthinker_cell,
    v5e,
)
from aot_tpu import (
    _assert_no_matmul_is_recomputed,
    _assert_pools_go_to_the_kernel_as_they_lie,
    _assert_span_calls_keep_the_grid,
    _assert_stacked_pool_is_addressed,
    _chunk_prefill,
    _laguna,
    _smallthinker,
    _behind_the_moves,
    _hlo_defs,
    _kernel_calls,
)


def test_laguna_chunk_prefill_reads_the_pools_as_they_lie(v5e, laguna_cell):
    """The ``(512, 4)`` program: four rows of a 512-token span."""
    compiled = _chunk_prefill(v5e, laguna_cell, 4, 528, 8448)
    for pool in laguna_cell[4]:  # each group's stacked pool, by layer
        _assert_stacked_pool_is_addressed(compiled, pool)
    _assert_span_calls_keep_the_grid(compiled)


def test_chunk_prefill_reads_the_planes_as_they_lie(v5e, kanana_cell):
    """The ``(512, 4)`` program: four rows of a 512-token span, 16384
    queries on the one KV head a row."""
    compiled = _chunk_prefill(v5e, kanana_cell, 4, 528, 8448)
    _assert_pools_go_to_the_kernel_as_they_lie(compiled, [kanana_cell[4]])
    _assert_span_calls_keep_the_grid(compiled)


# The programs of the two families that walk two cache groups unrolled, the
# layer a traced scalar of a kind's jitted function (``once_a_kind``), over
# the cells' own pools (168 MB of pool or more: a smaller one the compiler
# stages whole through the chip's fast memory, ``test_aot_windows.py``):
# ``laguna`` at two periods, so that the full group too has a plane to slice
# (2 x 315 MB and 6 x 58 MB), ``smallthinker`` at its depth (4 x 360 MB,
# 12 x 205 MB).
@pytest.mark.parametrize('cell,tables,max_table_positions', [
    (lambda v5e: _laguna(v5e, 8), 528, 8448),
    (_smallthinker, 1024, 16384),
], ids=['laguna', 'smallthinker'])
def test_one_row_chunk_prefill_addresses_the_stacked_pools(
    v5e, cell, tables, max_table_positions
):
    """The ``(512, 1)`` span program, a one-row tail as the cells dispatch
    it: every group's stacked pool goes to the writers and to the kernel
    whole, no plane and no pool copied (``test_aot_windows.py::
    test_stacked_pool_is_addressed_not_sliced`` for the families that scan
    their layers), and the kernel's calls keep the grid over chunks."""
    of = cell(v5e)
    compiled = _chunk_prefill(v5e, of, 1, tables, max_table_positions)
    for pool in of[4]:  # the two groups' shapes
        assert pool[0] > 1 and np.prod(pool) * 2 >= 168e6
        _assert_stacked_pool_is_addressed(compiled, pool)
    _assert_span_calls_keep_the_grid(compiled)


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


def test_smallthinker_chunk_prefill_reads_the_pools_as_they_lie(v5e, smallthinker_cell):
    """The ``(512, 4)`` program at the cell's depth: four rows of a
    512-token span (``test_chunk_prefill_keeps_the_grouped_matmul`` holds
    its experts to the grouped kernel)."""
    compiled = _chunk_prefill(v5e, smallthinker_cell, 4, 1024, 16384)
    for pool in smallthinker_cell[4]:  # each group's stacked pool
        _assert_stacked_pool_is_addressed(compiled, pool)
    _assert_span_calls_keep_the_grid(compiled)
    _assert_no_matmul_is_recomputed(compiled)


def test_sdar_prefill_span_is_block_causal_on_the_grid(v5e, sdar_cell):
    """The ``(512, 4)`` program at the cell's depth under the span
    schedule's ``block_length`` 4: the stacked pool addressed, never sliced,
    and every kernel call the grid."""
    sdar, cfg, params, pool, shape, engine = sdar_cell
    i32 = jnp.int32
    tables = engine['max_model_len'] // engine['block_size']
    compiled = jax.jit(
        lambda params, ids, pos, k, v, bt, ctx, tails: sdar.prefill_paged(
            params, cfg, ids, pos, k, v, bt, ctx, tails,
            max_table_positions=engine['max_model_len'], attn_backend='pallas',
        ), donate_argnums=(3, 4),
    ).lower(
        params, v5e((4, 512), i32), v5e((4, 512), i32), pool, pool,
        v5e((4, tables), i32), v5e((4,), i32), v5e((4,), i32),
    ).compile()
    _assert_stacked_pool_is_addressed(compiled, shape)
    _assert_span_calls_keep_the_grid(compiled)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.3 * 2**30
