"""Compile-only TPU lowering tests (``test_aot_tpu.py``) of the families'
decode windows at their cut depths: the pools, planes and states go to the
kernel as they lie and are updated in place, a stacked pool is addressed and
not sliced, the routed banks are streamed by a dot, and no weight is sliced
inside the step scan."""

import pytest

jax = pytest.importorskip('jax')
import jax.numpy as jnp  # noqa: E402
from aot_tpu import (  # noqa: F401 -- fixtures, asked for by name
    falcon_h1_cell,
    falcon_h1_window,
    kanana_cell,
    laguna_cell,
    laguna_window,
    lfm2_cell,
    lfm2_window,
    ouro_cell,
    ouro_window,
    sdar_cell,
    sdar_window,
    smallthinker_cell,
    smallthinker_window,
    solar_open2_window,
    v5e,
)
from aot_tpu import (
    _HD,
    _NKV,
    _assert_banks_are_streamed_by_a_dot,
    _assert_decode_calls_walk,
    _assert_no_weight_is_sliced_in_the_step_scan,
    _assert_pools_go_to_the_kernel_as_they_lie,
    _assert_span_calls_keep_the_grid,
    _assert_stacked_pool_is_addressed,
    _granite_window,
    _kanana_stacks,
    _kanana_window,
    _kernel_modules,
    _kernel_schedules,
    _mistral_chunk_prefill,
    _mistral_window,
    _weight_slices_in_the_step_scan,
)


def test_laguna_decode_window_reads_the_pools_as_they_lie(laguna_cell, laguna_window):
    for pool in laguna_cell[4]:  # each group's stacked pool, addressed by layer
        _assert_stacked_pool_is_addressed(laguna_window, pool)
    # 6 queries a KV head in the full layers and 8 in the window layers:
    # both stacked since PR 55
    _assert_decode_calls_walk(laguna_window, blocks={'stacked'})


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
    _assert_decode_calls_walk(compiled, blocks={'per_head'})  # ONE head
    # the decode calls of the kernel, as the roofline metric's pattern
    # names them: [rows, 1 KV head, 32 queries, 512 value lanes]
    assert f'bf16[{b},1,32,512]' in compiled.as_text()


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
    """A family hands its stacked pool to the writers and to the paged
    kernel WHOLE, with the layer whose pages are meant (the two families
    that walk two groups unrolled: ``test_aot_prefill.py::
    test_one_row_chunk_prefill_addresses_the_stacked_pools``). Sliced out
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


def test_lfm2_decode_window_addresses_the_pool(lfm2_cell, lfm2_window):
    """The decode window at the cell's 96 rows: the stacked pool of 512-
    lane rows goes to the writers and to the kernel whole (no plane and no
    pool copied, no head padded to a tile), every call takes the row walk
    with the stacked block (4 bands x 8 queries), and the state's buffers
    are rewritten in place."""
    pool = lfm2_cell[3]
    _assert_stacked_pool_is_addressed(lfm2_window, pool)
    _assert_decode_calls_walk(lfm2_window, blocks={'stacked'})
    # nothing as large as the weights' smallest bank is left over as a
    # temporary: the pools and the state are updated where they lie
    assert lfm2_window.memory_analysis().temp_size_in_bytes < 256 << 20


def test_lfm2_way_back_from_the_kernel_keeps_both_halves(lfm2_window):
    """Two 64-wide heads share a band, and the second one's output is the
    band's UPPER 64 lanes. Taken as two half-tile slices stacked, XLA's TPU
    compiler made one ``bitcast`` of the kernel's ``[96, 4, 8, 128]`` result
    into 64-lane rows, the lower half of every row (wrong for half the
    heads, on the chip alone: PERF.md section 7, PR 55). No call's result
    goes into a 64-lane shape by a bare bitcast."""
    import re

    text = lfm2_window.as_text()
    calls = re.findall(
        r'(%distllm\.attn_full[\w.]*) = bf16\[96,4,8,128\]\S* custom-call', text
    )
    assert calls
    for call in calls:
        assert not re.search(
            r'bf16\[[0-9,]*,64\]\S* bitcast\(' + re.escape(call) + r'\)', text
        ), call


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
    # At 121 rows and over the routed experts take the grouped kernel: the
    # paged kernel's calls are told from its by their modules' names.
    modules = _kernel_modules(compiled.as_text())
    paged = [
        module for name, module in modules
        if name == '_ragged_paged_attn_kernel'
    ]
    assert paged and all('_walk_row' in module for module in paged)
    grouped = moe.expert_form(b, 8, 40, 320, 4096, 1280) == 'grouped'
    assert (len(paged) < len(modules)) == grouped
    one_matrix_pool = b * 64 * 128 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < one_matrix_pool


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


def test_smallthinker_decode_window_reads_the_pools_as_they_lie(
    smallthinker_cell, smallthinker_window
):
    """No pool-sized result but the scatters, every kernel call the row
    walk, and the programs fit the chip beside weights and pools."""
    for pool in smallthinker_cell[4]:  # each group's stacked pool
        _assert_stacked_pool_is_addressed(smallthinker_window, pool)
    _assert_decode_calls_walk(smallthinker_window)
    memory = smallthinker_window.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.5 * 2**30


def test_sdar_block_window_reads_the_pool_as_it_lies(sdar_cell, sdar_window):
    """The block window at all 48 layers: no pool-sized result but the
    scatters, every attention call the row walk (a block folded into the
    group: 32 queries a KV head, the stacked block), no layer's bank copied
    out of its stack, and the program fits the chip beside weights and
    pool."""
    _, _, params, _, pool, _ = sdar_cell
    _assert_stacked_pool_is_addressed(sdar_window, pool)
    _assert_decode_calls_walk(sdar_window, blocks={'stacked'})
    memory = sdar_window.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.3 * 2**30
