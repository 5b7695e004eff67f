"""A latent cache group (``common.PagedGroup.row``) under the serving
engine, at toy widths on the CPU: the pool the engine builds from the
declaration (one plane a layer, no V plane), the writers for one plane, the
engine's tokens against the reference, preemption by recompute, every
refusal by name, and the records and telemetry the benchmark's readers use.
(The programs compiled for a described v5e at the cell's widths are in
``test_aot_tpu.py``: one process may hold libtpu.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_deepseek_v3 as ref
from deepseek_toy import BLOCK, NoTokenizer, make_engine, prompt, tiny
from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.generate.engine.kv_cache import PagedKVCache
from distllm_tpu.models import common, deepseek_v3

GREEDY = dict(temperature=0.0)


def _teacher_forced_gaps(hf, params, prompts, outputs):
    """Largest token gap (``ref.token_gaps``) of each row's greedy tokens
    against the reference's logits."""
    gaps = []
    for p, out in zip(prompts, outputs):
        tokens = list(p) + list(out)[:-1]
        at = len(p) - 1 + np.arange(len(out))[None]
        logits = ref.deepseek_logits(params, hf, np.asarray(tokens)[None], at)
        gaps.append(float(ref.token_gaps(logits, [out]).max()))
    return gaps


def test_the_pool_is_one_plane_a_layer_and_has_no_v_plane():
    hf, params, engine = make_engine()
    kv, cfg = engine.kv, engine.model_cfg
    assert kv.latent and kv.v_pool == () and engine._pools() == (kv.k_pool, ())
    assert len(kv.k_pool) == 3 and {p.shape for p in kv.k_pool} == {(64, BLOCK, 256)}
    assert kv.shape == (3, 64, BLOCK, 1, 256) and kv.spec('v') == ()
    assert engine.window_kv is None and engine.state_pool is None
    # blocks x block size x the stored row x layers, and nothing for values
    assert kv.hbm_bytes == 64 * BLOCK * cfg.stored_row * 3 * 4
    assert engine.telemetry['kv_pools'] == {'latent': {
        'layers': 3, 'window': None, 'blocks': 64, 'bytes': kv.hbm_bytes,
        'block_shape': [BLOCK, 256],
    }}
    assert engine._prefill is None  # every prefill takes the paged route
    # The admission arithmetic is in blocks, whatever a block holds.
    assert engine.max_blocks_per_seq == 96 // BLOCK


def test_host_view_reads_rows_and_their_value_lanes():
    hf, params, engine = make_engine()
    p = prompt(np.random.default_rng(0), 9)
    before = engine.flight.total_recorded
    engine.generate_ids([p], SamplingParams(max_tokens=3, **GREEDY))
    record = next(
        r for r in engine.flight.snapshot()[before - engine.flight.total_recorded:]
        if r['kind'] == 'request'
    )
    ends = np.asarray([record['kv_first_block'], record['kv_tail_block']])
    rows = np.asarray(engine.kv.k[0][ends])
    values = np.asarray(engine.kv.v[0][ends])
    assert rows.shape == (2, BLOCK, 1, 256) and values.shape == (2, BLOCK, 1, 128)
    np.testing.assert_array_equal(values, rows[..., :128])
    want = ref.first_layer_rows(params, hf, p[:BLOCK], np.arange(BLOCK))
    assert ref.row_content_error(rows[0, :, 0, :136], want) < 1e-5


def test_writers_write_one_plane():
    from distllm_tpu.ops.paged_attention import (
        write_chunk_kv,
        write_prefill_kv,
        write_token_kv,
    )

    rng = np.random.default_rng(1)
    plane = jnp.zeros((6, 4, 256))
    rows = jnp.asarray(rng.standard_normal((2, 5, 1, 256)), jnp.float32)
    tables = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
    positions = jnp.asarray([[0, 1, 2, 3, 4], [3, 4, 5, 6, 7]], jnp.int32)
    valid = jnp.asarray([[1, 1, 1, 1, 1], [1, 1, 1, 0, 0]], bool)
    got, none = write_chunk_kv(plane, None, rows, None, tables, positions, valid)
    assert none is None
    np.testing.assert_array_equal(got[2, 0], rows[0, 4, 0])  # position 4
    np.testing.assert_array_equal(got[3, 3], rows[1, 0, 0])  # position 3
    np.testing.assert_array_equal(got[4, 1], rows[1, 2, 0])  # position 5
    assert not np.asarray(got[4, 2:]).any() and not np.asarray(got[5]).any()
    got, none = write_token_kv(
        plane, None, rows[:, 0], None, tables, jnp.asarray([5, 9])
    )
    assert none is None
    np.testing.assert_array_equal(got[2, 1], rows[0, 0, 0])
    np.testing.assert_array_equal(got[5, 1], rows[1, 0, 0])
    got, none = write_prefill_kv(
        plane, None, rows[0], None, tables[1], jnp.asarray(3)
    )
    assert none is None
    np.testing.assert_array_equal(got[3, :3], rows[0, :3, 0])
    assert not np.asarray(got[3, 3:]).any() and not np.asarray(got[4]).any()


def test_a_pool_container_of_latent_rows():
    kv = PagedKVCache(2, 8, 4, 99, 99, dtype='float32', row=256,
                      value_lanes=128)
    assert kv.shape == (2, 8, 4, 1, 256) and kv.pool_shape == (2, 8, 4, 256)
    assert kv.v_pool == () and kv.hbm_bytes == 2 * 8 * 4 * 256 * 4
    assert kv.spec() == (jax.ShapeDtypeStruct((8, 4, 256), jnp.float32),) * 2
    with pytest.raises(ValueError, match='no int8'):
        PagedKVCache(2, 8, 4, 1, 1, dtype='int8', row=256, value_lanes=128)
    # A K/V pool is what it was.
    kv = PagedKVCache(2, 8, 4, 2, 16, dtype='float32')
    assert not kv.latent and kv.v_pool.shape == kv.k_pool.shape == (2, 8, 4, 32)
    assert kv.spec('v') == kv.spec() == jax.ShapeDtypeStruct((2, 8, 4, 32), jnp.float32)


@pytest.mark.parametrize('backend', ['xla', 'interpret'])
def test_engine_tokens_are_the_references(backend):
    """Prompts past two chunks, a short one beside them, decode windows of
    4 steps: every greedy token is the reference's largest logit."""
    over = dict(attn_backend=backend)
    if backend == 'interpret':  # the kernel, interpreted: fewer layers and steps
        over.update(hf_over={'num_hidden_layers': 2}, decode_steps=2)
    hf, params, engine = make_engine(**over)
    assert engine.telemetry['attn_backend'] == backend
    rng = np.random.default_rng(2)
    prompts = [prompt(rng, n) for n in ((21, 5, 30) if backend == 'xla' else (19, 5))]
    outputs = engine.generate_ids(
        prompts, SamplingParams(max_tokens=7 if backend == 'xla' else 3, **GREEDY)
    )
    assert max(_teacher_forced_gaps(hf, params, prompts, outputs)) < 1e-3


def test_a_preempted_request_is_admitted_again_and_gives_the_same_tokens():
    from distllm_tpu.observability import instruments

    rng = np.random.default_rng(4)
    prompts = [prompt(rng, 30), prompt(rng, 30)]
    params_ = SamplingParams(max_tokens=20, **GREEDY)
    _, _, roomy = make_engine(max_num_seqs=2)
    want = roomy.generate_ids(prompts, params_)
    # 18 usable blocks of 4 tokens; two rows of 30 + 20 tokens need 26.
    hf, params, tight = make_engine(num_blocks=19, max_num_seqs=2)
    # As if finished requests had used none of their budgets: the
    # look-ahead then admits both rows, and the pool runs short under them.
    tight._ewma['budget_use'] = 0.0
    before = instruments.SCHED_PREEMPTIONS.value
    got = tight.generate_ids(prompts, params_)
    assert instruments.SCHED_PREEMPTIONS.value > before
    assert got == want
    assert max(_teacher_forced_gaps(hf, params, prompts, got)) < 1e-3


def test_records_and_programs_carry_what_the_readers_use():
    hf, params, engine = make_engine()
    compiled = []

    def on_duration(event, seconds, **kw):
        if event == '/jax/core/compile/backend_compile_duration':
            compiled.append(str(kw.get('fun_name')))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    rng = np.random.default_rng(5)
    prompts = [prompt(rng, n) for n in (40, 13, 25)]
    before = engine.flight.total_recorded
    engine.generate_ids(prompts, SamplingParams(max_tokens=12, **GREEDY))
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    decodes = [r for r in records if r['kind'] == 'decode']
    prefills = [r for r in records if r['kind'] == 'prefill']
    requests = [r for r in records if r['kind'] == 'request']
    assert decodes and prefills and len(requests) == 3
    for r in decodes:
        # kv_blocks with its present meaning: the rows' contexts in blocks
        assert r['kv_blocks'] >= r['batch'] and 'kv_blocks_window' not in r
        # 2 sparse layers x 3 experts a token, every expert held here
        assert r['moe_pairs'] == r['moe_pairs_held'] > 0
    assert all('kv_blocks' in r for r in prefills)
    for r in requests:
        assert 1 <= r['kv_first_block'] and 1 <= r['kv_tail_block']
    names = {n.removeprefix('jit(').removesuffix(')') for n in compiled}
    assert {'deepseek_window_fn', 'deepseek_prefill_fn'} <= names
    assert not {'window_fn', 'prefill_fn', 'laguna_window_fn'} & names


# each refusal raises, naming the setting and its reason.
@pytest.mark.parametrize('setting, over', [
    ('enable_prefix_cache', dict(enable_prefix_cache=True)),
    ('host_kv_tier_bytes', dict(enable_prefix_cache=True, host_kv_tier_bytes=1 << 20)),
    ('enable_mixed_batching', dict(enable_mixed_batching=True)),
    ('draft_k', dict(draft_k=2)),
    ('kv_cache_dtype=int8', dict(kv_cache_dtype='int8')),
    ('quantization', dict(quantization='int8')),
])
def test_a_latent_group_refuses_what_cannot_be_right_yet(setting, over):
    with pytest.raises(
        ValueError, match=f'{setting} cannot serve a model with a latent'
    ):
        make_engine(**over)


def test_a_latent_group_refuses_a_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ('expert', 'model'))
    hf, cfg, params = tiny(0)
    with pytest.raises(ValueError, match='mesh cannot serve a model with a latent'):
        LLMEngine(
            cfg, params, NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2), mesh=mesh,
        )


@pytest.mark.parametrize('groups', [
    (('latent', None, 136), ('near', 8, None)),  # beside a windowed group
    (('latent', 8, 136),),  # behind a window
])
def test_latent_groups_the_engine_has_no_allocator_for_are_refused(groups):
    hf, cfg, params = tiny(0)

    class Mixed(type(cfg)):
        def cache_spec(self):
            return common.CacheSpec(
                paged=tuple(
                    common.PagedGroup(
                        name, 3, window, row=row,
                        value_lanes=128 if row else None,
                    )
                    for name, window, row in groups
                ),
                programs='distllm_tpu.models.deepseek_v3',
            )

    with pytest.raises(ValueError, match='a latent group holds whole contexts'):
        LLMEngine(
            Mixed(**cfg.model_dump()), params, NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2),
        )


def test_auto_resolves_to_the_kernel_for_a_latent_row(monkeypatch):
    from distllm_tpu.ops import paged_attention

    cell = deepseek_v3.DeepseekV3Config()  # the published widths
    assert cell.head_size == 640 and paged_attention.supports_model(cell)
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert paged_attention.resolve_attn_backend(
        'auto', cell, block_size=16, kv_dtype='bfloat16'
    ) == 'pallas'
    hf, cfg, params = tiny(0)  # a 256-lane row has no AOT coverage
    assert paged_attention.resolve_attn_backend(
        'auto', cfg, block_size=16, kv_dtype='bfloat16'
    ) == 'xla'


def test_a_kv_group_is_the_default_declaration():
    group = common.PagedGroup('kv', 3)
    assert group.row is None and group.value_lanes is None and group.stored_row is None
    spec = common.CacheSpec(paged=(group,), programs='x')
    assert not spec.latent and not spec.windowed
