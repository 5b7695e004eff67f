"""What the compile-only TPU (Mosaic) lowering tests share (``test_aot_*.py``;
not a test file itself): the described chip, what is read from a compiled
program's text, and the families' cells and decode windows at their cut
depths, each a module-scoped fixture of the file that asks for it.

The topology is described ONLY inside the module-scoped ``v5e`` fixture: only
one process may hold libtpu, every xdist worker imports these files, and a
module that touches the topology at import gives the workers different tests
to collect.
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


def _kernel_modules(text: str, debug_info: bool = True) -> list:
    """``(name, text)`` of the Mosaic module of every Pallas call in a
    lowered or compiled program's text: the serialized body parsed and
    printed, with the locations its operations carry or without. (The raw
    bytes also name what the process traced BEFORE the kernel, so a search
    of them tells kernels apart only while no other kernel was traced
    first: read the module.)"""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    modules = []
    for body in re.findall(
        r'body\\*(?:22|")\s*:\s*\\*(?:22|")([A-Za-z0-9+/=]{100,})', text
    ):
        with mlir.make_ir_context() as context:  # jax's dialects and Mosaic's
            tpu.register_dialect(context)
            context.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(body))
            attributes = module.operation.attributes
            name = ''  # a module may have none
            if 'sym_name' in attributes:
                name = ir.StringAttr(attributes['sym_name']).value
            modules.append(
                (name, module.operation.get_asm(enable_debug_info=debug_info))
            )
    return modules


def _kernel_kinds(compiled) -> list:
    """``'grid'``, ``'walk:stacked'`` or ``'walk:per_head'`` for each
    paged-attention call of a compiled program: the locations of a Mosaic
    module name the functions its source lines are in, only the row walk's
    names ``_walk_row``, and only a walk with the stacked softmax block
    ``_stacked_block``."""
    return [
        'grid' if '_walk_row' not in module
        else 'walk:stacked' if '_stacked_block' in module else 'walk:per_head'
        for name, module in _kernel_modules(compiled.as_text())
        if name == '_ragged_paged_attn_kernel'
    ]


def _kernel_programs(lowered_text: str) -> list:
    """The Mosaic module of every Pallas call of a LOWERED text, printed
    with its debug locations dropped. The raw text carries file, line and
    function of each operation's ten innermost frames, its callers' among
    them, so it moves with any line above a kernel; this is the program
    alone."""
    return [module for _, module in _kernel_modules(lowered_text, False)]


def _kernel_schedules(compiled) -> list:
    """``'walk'`` or ``'grid'`` for each paged-attention call of a
    compiled program (``_kernel_kinds`` without the walk's block form)."""
    return [kind.split(':')[0] for kind in _kernel_kinds(compiled)]


def _assert_decode_calls_walk(compiled, blocks=None) -> None:
    """Every paged-attention call of a decode window is a span of one
    and takes the row walk; with ``blocks``, the set of forms its softmax
    blocks take (``'stacked'``, ``'per_head'``)."""
    kinds = _kernel_kinds(compiled)
    assert kinds and {k.split(':')[0] for k in kinds} == {'walk'}, kinds
    if blocks is not None:
        assert {k.split(':')[1] for k in kinds} == set(blocks), kinds


def _assert_span_calls_keep_the_grid(compiled) -> None:
    schedules = _kernel_schedules(compiled)
    assert schedules and set(schedules) == {'grid'}, schedules


# ---- kernel-only compiles at the real widths (tier-1, seconds each) ----

# Mistral-7B-Instruct-v0.3 attention widths at the serving batch.
_B, _NH, _NKV, _HD = 32, 32, 8, 128
_7B = (_B, _NH, _NKV, 512)  # rows, heads, KV heads, the table's tokens


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


def _assert_no_matmul_is_recomputed(compiled) -> None:
    """XLA's rematerialization runs when ITS count of a program's memory
    passes the chip's, and an unrolled chain of writes into one multi-GB
    pool reads to it as two such pools: what it then moves behind a write
    is free, a matmul it duplicates is not (``PERF.md`` section 6, PR 56).
    No instruction it made (``%name.remat``, ``.remat2``) is a matmul or a
    fusion that holds one."""
    import re

    text = compiled.as_text()
    recomputed = [
        f'%{name} = {result[:40]} {opcode}'
        for name, (result, opcode, call) in _hlo_defs(text).items()
        if re.search(r'\.remat\d*$', name) and (
            opcode == 'convolution' or opcode == 'fusion' and ' convolution(' in
            text.partition(
                '\n%' + re.search(r'calls=%(\S+?)[,\s]', call + ' ').group(1) + ' ('
            )[2].partition('\n}')[0]
        )
    ]
    assert not recomputed, recomputed


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


def _laguna(v5e, layers):
    """The laguna cell's configuration cut to its first ``layers``: module,
    config, parameters, and each group's stacked pool and its shape at the
    cell's sizes, 9600 and 1757 blocks a layer."""
    import json
    from pathlib import Path

    from distllm_tpu.models import laguna

    root = Path(__file__).resolve().parents[1]
    hf = json.loads((root / 'benchmarks/configs/laguna-xs.2.json').read_text())
    hf['num_hidden_layers'] = layers
    for key in ('layer_types', 'mlp_layer_types', 'num_attention_heads_per_layer'):
        hf[key] = hf[key][:layers]
    cfg = laguna.LagunaConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: laguna.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    shapes = [
        (cfg.count(kind), blocks, 16, cfg.num_kv_heads * cfg.head_dim)
        for kind, blocks in (('full', 9600), ('window', 1757))
    ]
    pools = tuple(v5e(shape, jnp.bfloat16) for shape in shapes)
    return laguna, cfg, params, pools, shapes


@pytest.fixture(scope='module')
def laguna_cell(v5e):
    """One period of layers (one full layer, three window layers; the dense
    MLP and three sparse): the full group's stack of one has no plane to
    slice, ``_laguna(v5e, 8)`` has two."""
    return _laguna(v5e, 4)


@pytest.fixture(scope='module')
def laguna_window(v5e, laguna_cell):
    """The decode window at the cell's 48 rows, compiled once."""
    laguna, cfg, params, pools, _ = laguna_cell
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


def _assert_stacked_pool_is_addressed(compiled, pool) -> None:
    """A stacked pool ``[L, blocks, block_size, folded]`` is addressed,
    never sliced: (1) no instruction's result is the size of a layer's
    plane (a stack of one layer IS its plane: nothing to slice); (2) every
    instruction whose result is the size of the pool is the pool handed
    on (a parameter, the loop and its tuples, a bitcast,
    the compiler's own staging of a small pool through its fast memory) or
    the in-place write (a ``scatter``, alone or in a fusion); (3) each
    kernel's K and V operand is the pool itself behind bitcasts."""
    text = compiled.as_text()
    defs = _hlo_defs(text)
    planes = [
        f'%{name} = {result[:40]} {opcode}'
        for name, (result, opcode, _) in defs.items()
        if pool[0] > 1 and _holds(result, pool[1:])
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


def _chunk_prefill(v5e, cell, rows, tables, max_table_positions):
    """The ``(512, rows)`` span program of a family whose cell is
    ``(module, config, parameters, pools, ...)``: ``rows`` rows of a
    512-token span over ``tables`` (one table's width, or a pair's)."""
    module, cfg, params, pools = cell[:4]
    i32 = jnp.int32
    if cfg.cache_spec().latent:  # one group of planes, no V plane
        k, v, tables = pools, (), v5e((rows, tables), i32)
    else:  # two cache groups: a pair of each operand
        k, v, tables = pools, pools, (v5e((rows, tables), i32),) * 2
    return jax.jit(
        lambda params, ids, pos, k, v, bt, ctx, tails: module.prefill_paged(
            params, cfg, ids, pos, k, v, bt, ctx, tails,
            max_table_positions=max_table_positions, attn_backend='pallas',
        ), donate_argnums=(3, 4),
    ).lower(
        params, v5e((rows, 512), i32), v5e((rows, 512), i32), k, v, tables,
        v5e((rows,), i32), v5e((rows,), i32),
    ).compile()


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


# The temporaries of this tree's programs, compiled here for the described
# v5e at full depth. PR 43 re-pinned them, on purpose: with no float32
# ``[pairs, hidden]`` array behind the kernel they fell from PR 41's
# 1,337,857,536 and 111,249,408 bytes (``ragged_dot`` three times a layer;
# PR 42 stayed within 64 MB of those) by 168 MB and 45 MB.
_GRANITE_PREFILL_TEMP_AT_PR43 = {(512, 4): 1169762816, (128, 1): 66647552}


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


def _smallthinker(v5e):
    """The smallthinker cell's configuration at its own depth (16 layers:
    4 full, 12 window), the parameters and each group's stacked pool at the
    cell's sizes: 22000 blocks a layer and the engine's own 12509."""
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
    shapes = [
        (cfg.count(kind), blocks, 16, cfg.num_kv_heads * cfg.head_dim)
        for kind, blocks in (
            ('full', hf['engine']['num_blocks']),
            ('window', 1 + rows * 258 + 4 * 31),
        )
    ]
    pools = tuple(v5e(shape, jnp.bfloat16) for shape in shapes)
    return smallthinker, cfg, params, pools, shapes


@pytest.fixture(scope='module')
def smallthinker_cell(v5e):
    return _smallthinker(v5e)


@pytest.fixture(scope='module')
def smallthinker_window(v5e, smallthinker_cell):
    """The decode window at the cell's 48 rows and depth, compiled once."""
    smallthinker, cfg, params, pools, _ = smallthinker_cell
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


@pytest.fixture(scope='module')
def sdar_cell(v5e):
    """The sdar cell's configuration at its own depth (all 48 layers, 16 of
    128 experts a layer), the parameters and the stacked pool at the cell's
    2560 blocks."""
    import json
    from pathlib import Path

    from distllm_tpu.models import sdar

    root = Path(__file__).resolve().parents[1]
    hf = json.loads(
        (root / 'benchmarks/configs/sdar-30b-a3b-chat.json').read_text()
    )
    cfg = sdar.SdarConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: sdar.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    params = jax.tree.map(lambda a: v5e(a.shape, a.dtype), shapes)
    pool = (
        cfg.num_layers, hf['engine']['num_blocks'], 16,
        cfg.num_kv_heads * cfg.head_dim,
    )
    return sdar, cfg, params, v5e(pool, jnp.bfloat16), pool, hf['engine']


@pytest.fixture(scope='module')
def sdar_window(v5e, sdar_cell):
    """The block window at the cell's 48 rows and depth (two blocks of 4
    positions, 4 denoise forwards and a commit each), compiled once."""
    sdar, cfg, params, pool, _, engine = sdar_cell
    b, i32, f32 = engine['max_num_seqs'], jnp.int32, jnp.float32

    def window_fn(params, ids, pos, ctx, k, v, bt, steps_left, *rest):
        return sdar.decode_loop(
            params, cfg, ids, pos, k, v, bt, ctx, steps_left, *rest[:5],
            num_steps=engine['decode_steps'], attn_backend='pallas',
            max_table_positions=engine['max_model_len'],
            denoise_steps=engine['denoise_steps'], unmask_threshold=rest[5],
        )

    tables = engine['max_model_len'] // engine['block_size']
    return jax.jit(window_fn, donate_argnums=(4, 5)).lower(
        params, v5e((b, cfg.block_length), i32), v5e((b,), i32),
        v5e((b,), i32), pool, pool, v5e((b, tables), i32), v5e((b,), i32),
        v5e((b,), f32), v5e((b,), f32), v5e((b,), f32), v5e((b,), i32),
        v5e((b,), jnp.uint32), v5e((b,), f32),
    ).compile()
