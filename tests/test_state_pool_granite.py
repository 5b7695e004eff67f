"""granitemoehybrid on the CPU at tiny widths: the program (models/
granite_hybrid.py, models/moe.py) against the plain reference
(benchmarks/reference_granite.py), seeded weights.

Tolerances. The program and the reference are both float32 here, so they
differ only by the order of sums (chunked SSD against step-by-step
recurrence, grouped against masked experts): measured differences are a few
1e-6 of the logits' spread, the limits 2e-4. A bfloat16 SSM state (2^-9 a
step, compounding) or a score scale of 1/sqrt(head) instead of
``attention_multiplier`` moves logits by more than 1e-2 of their spread at
these sizes, which the tests of the perturbed program below show.

The file's name sorts it after ``test_startup_attribution.py`` on purpose:
it is the longest file of the suite, and started beside ``test_history.py``
(as ``test_granite_hybrid.py`` would be under ``--dist loadfile``) its
compiles begin between the two arms of that file's timing sentinel, which
then failed in two whole runs of three.
"""

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_granite as ref
from distllm_tpu.models import granite_hybrid as gh
from distllm_tpu.models.moe import routed_experts

LAYERS = ('mamba', 'mamba', 'attention', 'mamba')


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'granitemoehybrid', 'vocab_size': 64, 'hidden_size': 32,
        'layer_types': list(LAYERS), 'num_hidden_layers': len(LAYERS),
        'num_attention_heads': 4, 'num_key_value_heads': 2,
        'mamba_n_heads': 8, 'mamba_d_head': 8, 'mamba_d_state': 16,
        'mamba_d_conv': 4, 'mamba_chunk_size': 8, 'mamba_expand': 2,
        'mamba_n_groups': 1, 'intermediate_size': 16,
        'shared_intermediate_size': 24, 'num_local_experts': 8,
        'num_experts_per_tok': 3, 'embedding_multiplier': 12.0,
        'attention_multiplier': 0.25, 'residual_multiplier': 0.22,
        'logits_scaling': 4.0, 'rms_norm_eps': 1e-5,
        'position_embedding_type': 'nope', 'tie_word_embeddings': True,
    }
    hf.update(over)
    return hf


def tiny(seed=0, **over):
    hf = tiny_hf(**over)
    cfg = gh.GraniteHybridConfig.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = gh.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits.
    params = jax.tree.map(
        lambda a: a * 4.0 if a.ndim >= 3 and a.shape[-1] > 1 else a, params
    )
    return hf, cfg, params


def spread(a, b):
    """Largest difference as a share of the reference's spread."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / b.std())


def test_config_reads_published_keys_and_the_share():
    hf = tiny_hf(num_local_experts=4, num_routed_experts=8, first_local_expert=4)
    cfg = gh.GraniteHybridConfig.from_hf_config(hf)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert) == (8, 4, 4)
    assert cfg.num_layers == 4 and cfg.num_paged_layers == 1
    assert cfg.layer_runs() == [('mamba', 0, 2), ('attention', 0, 1), ('mamba', 2, 1)]
    spec = cfg.state_spec()
    assert spec['ssm'][0].shape == (8, 8, 16) and spec['ssm'][0].dtype == jnp.float32
    assert spec['conv'][0].shape == (3, 64 + 32) and len(spec['conv']) == 3


@pytest.mark.parametrize('key, value', [
    ('position_embedding_type', 'rope'), ('mamba_n_groups', 2),
    ('mamba_proj_bias', True), ('mamba_d_head', 4),
])
def test_config_refuses_what_is_not_implemented(key, value):
    with pytest.raises(ValueError, match='granitemoehybrid'):
        gh.GraniteHybridConfig.from_hf_config(tiny_hf(**{key: value}))


def test_decoder_families_has_the_row():
    from distllm_tpu.models import decoder_family

    cls, module = decoder_family('granitemoehybrid')
    assert cls is gh.GraniteHybridConfig and module is gh


# (a) full forward logits, program against reference.
@pytest.mark.parametrize('seed', [0, 1])
def test_forward_logits_match_reference(seed):
    hf, cfg, params = tiny(seed)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 64, (2, 21)).astype(np.int32)
    mask = np.ones_like(ids)
    mask[1, 13:] = 0  # right padding must not reach earlier positions
    hidden = gh.apply(params, cfg, jnp.asarray(ids), jnp.asarray(mask))
    got = gh.logits(params, cfg, hidden)
    want = ref.granite_logits(params, hf, ids)
    assert spread(got[0], want[0]) < 2e-4
    assert spread(got[1, :13], want[1, :13]) < 2e-4


@pytest.mark.parametrize('what', ['bf16_ssm_state', 'sqrt_score_scale'])
def test_tolerance_breaks_on_a_wrong_program(what, monkeypatch):
    """The two faults the limits are set against move the logits by far
    more than the limit of the tests above."""
    hf, cfg, params = tiny(0)
    ids = np.random.default_rng(0).integers(0, 64, (1, 40)).astype(np.int32)
    if what == 'sqrt_score_scale':
        cfg = cfg.model_copy(update={'attention_multiplier': 8 ** -0.5})
    else:
        real = gh.ssd_chunked

        def rounded(x, dt, a, b_in, c_in, ssm0, chunk):
            # The state rounded to bfloat16 at every position: chunk 1.
            def step(ssm, xs):
                y, ssm = real(*(t[:, None] for t in xs), a=a, ssm0=ssm, chunk=1)
                return ssm.astype(jnp.bfloat16).astype(jnp.float32), y[:, 0]

            def real_step(x_t, dt_t, b_t, c_t, a, ssm0, chunk):
                return real(x_t, dt_t, a, b_t, c_t, ssm0, chunk)

            ssm = ssm0
            ys = []
            for t in range(x.shape[1]):
                y, ssm = real_step(
                    x[:, t:t + 1], dt[:, t:t + 1], b_in[:, t:t + 1],
                    c_in[:, t:t + 1], a, ssm, 1,
                )
                ssm = ssm.astype(jnp.bfloat16).astype(jnp.float32)
                ys.append(y)
            return jnp.concatenate(ys, axis=1), ssm

        monkeypatch.setattr(gh, 'ssd_chunked', rounded)
    mask = jnp.ones_like(jnp.asarray(ids))
    got = gh.logits(params, cfg, gh.apply(params, cfg, jnp.asarray(ids), mask))
    want = ref.granite_logits(params, hf, ids)
    assert spread(got, want) > 2e-3


# (c) chunked SSD against the step-by-step recurrence.
@pytest.mark.parametrize('length, chunk', [(5, 8), (8, 8), (13, 8), (37, 16), (300, 256)])
def test_ssd_chunks_match_the_recurrence(length, chunk):
    rng = np.random.default_rng(length)
    b, h, p, n = 2, 3, 4, 5
    x = jnp.asarray(rng.normal(size=(b, length, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.3, (b, length, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    b_in = jnp.asarray(rng.normal(size=(b, length, n)), jnp.float32)
    c_in = jnp.asarray(rng.normal(size=(b, length, n)), jnp.float32)
    zero = jnp.zeros((b, h, p, n), jnp.float32)
    want, _ = ref.ssm_steps(x, dt, a, b_in, c_in, jnp.zeros((h,)))
    got, state = gh.ssd_chunked(x, dt, a, b_in, c_in, zero, chunk)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # Carried across a cut at any position, chunk boundary or not.
    cut = length // 3 + 1
    y1, s1 = gh.ssd_chunked(
        x[:, :cut], dt[:, :cut], a, b_in[:, :cut], c_in[:, :cut], zero, chunk
    )
    y2, s2 = gh.ssd_chunked(
        x[:, cut:], dt[:, cut:], a, b_in[:, cut:], c_in[:, cut:], s1, chunk
    )
    np.testing.assert_allclose(
        jnp.concatenate([y1, y2], 1), want, rtol=2e-4, atol=2e-5
    )
    np.testing.assert_allclose(s2, state, rtol=2e-4, atol=2e-5)


def test_ssd_positions_with_dt_zero_pass_the_state_through():
    rng = np.random.default_rng(3)
    b, s, h, p, n = 1, 11, 2, 4, 3
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (b, s, h)), jnp.float32)
    dt = dt.at[:, 7:].set(0.0)
    a = -jnp.ones((h,))
    bc = jnp.asarray(rng.normal(size=(2, b, s, n)), jnp.float32)
    zero = jnp.zeros((b, h, p, n))
    _, full = gh.ssd_chunked(x, dt, a, bc[0], bc[1], zero, 4)
    _, short = gh.ssd_chunked(x[:, :7], dt[:, :7], a, bc[0][:, :7], bc[1][:, :7], zero, 4)
    np.testing.assert_allclose(full, short, rtol=1e-6)


# (d) the share.
def _one_layer(hf, params, kind='mamba', i=0):
    return jax.tree.map(lambda a: a[i], params[kind])


def test_expert_shares_add_up_to_the_uncut_layer():
    hf, cfg, params = tiny(2)
    lp = _one_layer(hf, params)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(19, 32)), jnp.float32)
    with jax.default_matmul_precision('highest'):
        whole = ref.routed_experts(h, lp, hf, 0) + ref.shared_mlp(h, lp)
        parts = ref.shared_mlp(h, lp)  # the shared MLP counted once
        for first in (0, 4):
            share = {
                **lp, **{n: {'kernel': lp[n]['kernel'][first:first + 4]}
                         for n in ('gate', 'up', 'down')},
            }
            want = ref.routed_experts(h, share, hf, first)
            got, pairs = routed_experts(
                h, share['router']['kernel'], share['gate']['kernel'],
                share['up']['kernel'], share['down']['kernel'], 3,
                first_expert=first,
            )
            # The held share equals the reference given the same share.
            assert spread(got, want) < 2e-5
            assert int(pairs[0]) == 19 * 3 and 0 < int(pairs[1]) < 19 * 3
            parts = parts + got
    assert spread(parts, whole) < 2e-5


def test_pairs_count_only_the_rows_that_count():
    hf, cfg, params = tiny(2)
    lp = _one_layer(hf, params)
    h = jnp.asarray(np.random.default_rng(4).normal(size=(6, 32)), jnp.float32)
    counted = jnp.asarray([True, True, False, True, False, False])
    _, pairs = routed_experts(
        h, lp['router']['kernel'], lp['gate']['kernel'], lp['up']['kernel'],
        lp['down']['kernel'], 3, counted=counted,
    )
    assert pairs.tolist() == [9, 9]  # every expert held: all 9 pairs


def test_program_with_a_share_matches_the_reference_with_that_share():
    hf, cfg, params = tiny(
        5, num_local_experts=4, num_routed_experts=8, first_local_expert=4
    )
    assert params['mamba']['gate']['kernel'].shape == (3, 4, 32, 16)
    assert params['mamba']['router']['kernel'].shape == (3, 32, 8)
    ids = np.random.default_rng(5).integers(0, 64, (1, 17)).astype(np.int32)
    mask = jnp.ones_like(jnp.asarray(ids))
    got = gh.logits(params, cfg, gh.apply(params, cfg, jnp.asarray(ids), mask))
    assert spread(got, ref.granite_logits(params, hf, ids)) < 2e-4


def test_params_from_hf_layout():
    """HF stacks ``input_linear`` as [E, 2 * I, H], gate rows first."""
    hf = tiny_hf()
    cfg = gh.GraniteHybridConfig.from_hf_config(hf)
    rng = np.random.default_rng(0)
    sd = {'model.embed_tokens.weight': rng.normal(size=(64, 32)),
          'model.norm.weight': np.ones(32)}
    for li, kind in enumerate(LAYERS):
        p = f'model.layers.{li}'
        sd.update({
            f'{p}.input_layernorm.weight': np.ones(32),
            f'{p}.post_attention_layernorm.weight': np.ones(32),
            f'{p}.block_sparse_moe.router.layer.weight': rng.normal(size=(8, 32)),
            f'{p}.block_sparse_moe.input_linear.weight': rng.normal(size=(8, 32, 32)),
            f'{p}.block_sparse_moe.output_linear.weight': rng.normal(size=(8, 32, 16)),
            f'{p}.shared_mlp.input_linear.weight': rng.normal(size=(48, 32)),
            f'{p}.shared_mlp.output_linear.weight': rng.normal(size=(32, 24)),
        })
        if kind == 'attention':
            for n, out in (('q', 32), ('k', 16), ('v', 16), ('o', 32)):
                shape = (32, 32) if n == 'o' else (out, 32)
                sd[f'{p}.self_attn.{n}_proj.weight'] = rng.normal(size=shape)
        else:
            sd.update({
                f'{p}.mamba.in_proj.weight': rng.normal(size=(64 + 96 + 8, 32)),
                f'{p}.mamba.conv1d.weight': rng.normal(size=(96, 1, 4)),
                f'{p}.mamba.conv1d.bias': rng.normal(size=(96,)),
                f'{p}.mamba.dt_bias': rng.normal(size=(8,)),
                f'{p}.mamba.A_log': rng.normal(size=(8,)),
                f'{p}.mamba.D': np.ones(8),
                f'{p}.mamba.norm.weight': np.ones(64),
                f'{p}.mamba.out_proj.weight': rng.normal(size=(32, 64)),
            })
    params = gh.params_from_hf(sd, cfg)
    shapes = jax.eval_shape(lambda: gh.init_on_device(jax.random.PRNGKey(0), cfg))
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    assert jax.tree.map(np.shape, params) == jax.tree.map(lambda s: s.shape, shapes)
    moe_in = sd['model.layers.0.block_sparse_moe.input_linear.weight']
    np.testing.assert_array_equal(params['mamba']['gate']['kernel'][0, 3], moe_in[3, :16].T)
    np.testing.assert_array_equal(params['mamba']['up']['kernel'][0, 3], moe_in[3, 16:].T)
    np.testing.assert_array_equal(
        params['mamba']['conv'][0][:, 5], sd['model.layers.0.mamba.conv1d.weight'][5, 0]
    )
    specs = gh.param_specs(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda s: 0, specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    ) == jax.tree.structure(jax.tree.map(lambda s: 0, shapes))
    assert specs['mamba']['gate']['kernel'] == jax.sharding.PartitionSpec(None, 'expert', None, None)


# ------------------------------------------------------------ the engine
from benchmarks.reference import token_gaps  # noqa: E402
from distllm_tpu.generate.engine.engine import (  # noqa: E402
    EngineConfig,
    LLMEngine,
    SamplingParams,
)


class _NoTokenizer:
    eos_id = None


def make_engine(seed=0, hf_over=None, **over):
    hf, cfg, params = tiny(seed, **(hf_over or {}))
    settings = dict(
        block_size=4, num_blocks=64, max_num_seqs=4, max_model_len=96,
        prefill_chunk_tokens=8, decode_steps=4, attn_backend='xla',
        enable_prefix_cache=False,
    )
    settings.update(over)
    engine = LLMEngine(cfg, params, _NoTokenizer(), EngineConfig(**settings))
    return hf, params, engine


def assert_teacher_forced(hf, params, prompts, outputs, limit=1e-3):
    """Every generated token is the reference's greedy token given the same
    history, or within ``limit`` standard deviations of it (float32 on both
    sides: a tie is the one way to differ)."""
    width = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    ids = np.zeros((len(prompts), width), np.int32)
    for row, (p, o) in enumerate(zip(prompts, outputs)):
        ids[row, :len(p) + len(o)] = list(p) + list(o)
    logits = ref.granite_logits(params, hf, ids)
    gaps = token_gaps(logits, [len(p) for p in prompts], outputs)
    assert max(gaps) < limit, gaps


def _prompt(rng, n):
    return [int(t) for t in rng.integers(0, 64, n)]


# (b) prefill of n tokens then m decode steps, through the state pool and
# the paged KV, against the reference's full forward at n + m: n shorter
# than, equal to and 2.5 times the prefill chunk (8).
@pytest.mark.parametrize('n', [5, 8, 20])
@pytest.mark.parametrize('backend', ['xla', 'interpret'])
def test_prefill_then_decode_carries_state(n, backend):
    if backend == 'interpret' and n != 20:
        pytest.skip('one length through the Pallas interpreter is enough')
    hf, params, engine = make_engine(attn_backend=backend)
    prompt = _prompt(np.random.default_rng(n), n)
    out = engine.generate_ids(
        [prompt], SamplingParams(temperature=0.0, max_tokens=7)
    )
    assert len(out[0]) == 7
    assert_teacher_forced(hf, params, [prompt], out)
    assert engine.telemetry['state_pool_slots'] == 4
    # 3 Mamba layers x (8 x 8 x 16 float32 + 3 x 96 float32) a slot.
    assert engine.telemetry['state_pool_bytes'] == 4 * 3 * (1024 + 288) * 4


# (e) through LLMEngine.generate_ids.
def test_rows_of_different_lengths_finish_at_different_windows():
    hf, params, engine = make_engine()
    rng = np.random.default_rng(1)
    prompts = [_prompt(rng, 6), _prompt(rng, 19)]
    rids = [
        engine.add_request(prompts[0], SamplingParams(temperature=0.0, max_tokens=3)),
        engine.add_request(prompts[1], SamplingParams(temperature=0.0, max_tokens=11)),
    ]
    got = {rid: [] for rid in rids}
    while engine.has_unfinished:
        for rid, token in engine.step():
            got[rid].append(token)
    outputs = [got[rid] for rid in rids]
    assert [len(o) for o in outputs] == [3, 11]
    assert_teacher_forced(hf, params, prompts, outputs)


@pytest.mark.parametrize('pool_dtype', ['float32', 'bfloat16'])
def test_the_state_a_finished_request_left_is_the_references(pool_dtype):
    """What the benchmark's second limit reads: the ``request`` record
    names the slot a request held when it finished, the pool keeps what the
    slot held, and that is the reference's SSM state after everything but
    the request's last token. A pool that keeps the state in bfloat16 (the
    precision below the one the module states) is told apart by two orders."""
    hf, cfg, params = tiny(0)
    if pool_dtype == 'bfloat16':
        class Bf16Pool(type(cfg)):
            def state_spec(self):
                spec = super().state_spec()
                return {**spec, 'ssm': tuple(
                    jax.ShapeDtypeStruct(s.shape, jnp.bfloat16) for s in spec['ssm']
                )}
        cfg = Bf16Pool(**cfg.model_dump())
    engine = LLMEngine(cfg, params, _NoTokenizer(), EngineConfig(
        block_size=4, num_blocks=64, max_num_seqs=4, max_model_len=96,
        prefill_chunk_tokens=8, decode_steps=4, attn_backend='xla',
        enable_prefix_cache=False,
    ))
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, 6), _prompt(rng, 19), _prompt(rng, 11)]
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=13)
    )
    records = sorted(
        (r for r in engine.flight.snapshot()[before - engine.flight.total_recorded:]
         if r['kind'] == 'request'), key=lambda r: r['request_id'],
    )
    slots = [r['state_slot'] for r in records]
    assert sorted(slots) == [0, 1, 2]
    width = max(len(p) + len(o) for p, o in zip(prompts, outputs))
    ids = np.zeros((3, width), np.int32)
    for row, (p, o) in enumerate(zip(prompts, outputs)):
        ids[row, :len(p) + len(o)] = list(p) + list(o)
    fed = [len(p) + len(o) - 1 for p, o in zip(prompts, outputs)]
    _, want = ref.granite_forward(params, hf, ids, fed)
    got = [np.asarray(leaf[np.asarray(slots)]) for leaf in engine.state_pool.state['ssm']]
    errors = ref.state_errors(got, want)
    slow = ref.slow_head_state_error(
        got[0], want[0], params['mamba']['dt_bias'][0], params['mamba']['A_log'][0]
    )
    assert len(errors) == 3 and len(ref.slow_heads(
        params['mamba']['dt_bias'][0], params['mamba']['A_log'][0])) == 1  # of 8 heads
    if pool_dtype == 'float32':
        assert max(errors) < 1e-5 and slow < 1e-5, (errors, slow)
    else:
        assert min(errors) > 1e-3 and slow > 1e-3, (errors, slow)


def test_a_reused_slot_does_not_leak_stale_state():
    hf, params, engine = make_engine(max_num_seqs=1)
    rng = np.random.default_rng(2)
    sampling = SamplingParams(temperature=0.0, max_tokens=6)
    first = _prompt(rng, 17)
    engine.generate_ids([first], sampling)
    # The one slot now holds the first request's state; the next request
    # takes it, alone and after a call that left the pipeline empty.
    for n in (4, 13):  # one span, and chunks
        later = _prompt(rng, n)
        out = engine.generate_ids([later], sampling)
        assert_teacher_forced(hf, params, [later], out)


def test_a_preempted_request_is_admitted_again_from_zero_state():
    # 10 usable blocks of 4 tokens; two rows of 12 + 20 tokens need 16.
    from distllm_tpu.observability import instruments

    hf, params, engine = make_engine(num_blocks=11, max_num_seqs=2)
    # As if finished requests had used none of their budgets: the
    # look-ahead then admits both rows, and the pool runs short under them.
    engine._ewma['budget_use'] = 0.0
    before = instruments.SCHED_PREEMPTIONS.value
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, 12), _prompt(rng, 12)]
    outputs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=20)
    )
    assert [len(o) for o in outputs] == [20, 20]
    assert instruments.SCHED_PREEMPTIONS.value > before
    assert_teacher_forced(hf, params, prompts, outputs)


def test_sampled_generation_and_records():
    hf, params, engine = make_engine(
        hf_over=dict(num_local_experts=4, num_routed_experts=8)
    )
    rng = np.random.default_rng(4)
    before = engine.flight.total_recorded
    outputs = engine.generate_ids(
        [_prompt(rng, 9), _prompt(rng, 30), _prompt(rng, 3)],
        SamplingParams(temperature=0.7, top_p=0.9, max_tokens=9),
    )
    assert [len(o) for o in outputs] == [9, 9, 9]
    grew = engine.flight.total_recorded - before
    records = engine.flight.snapshot()[-grew:]
    windows = [r for r in records if r['kind'] == 'decode']
    assert windows and all(
        0 < r['moe_pairs_held'] < r['moe_pairs']
        for r in windows
    )
    # 4 layers x 3 picks a token: every decode token routes 12 pairs.
    assert sum(r['moe_pairs'] for r in windows) == 12 * sum(
        r['tokens'] for r in windows
    )
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert prefills and all(
        r['route'] in ('paged', 'chunk') for r in prefills
    )


# (f) each refusal raises, naming the setting.
@pytest.mark.parametrize('setting, over', [
    ('enable_prefix_cache', dict(enable_prefix_cache=True)),
    ('host_kv_tier_bytes', dict(enable_prefix_cache=True, host_kv_tier_bytes=1 << 20)),
    ('enable_mixed_batching', dict(enable_mixed_batching=True)),
    ('draft_k', dict(draft_k=2)),
    ('kv_cache_dtype=int8', dict(kv_cache_dtype='int8')),
    ('quantization', dict(quantization='int8')),
])
def test_hybrid_refuses_what_needs_state_snapshots(setting, over):
    if setting == 'host_kv_tier_bytes':
        setting = 'enable_prefix_cache'  # a tier needs the cache: first refusal
    with pytest.raises(ValueError, match=f'{setting} cannot serve a hybrid'):
        make_engine(**over)


def test_hybrid_refuses_a_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ('expert', 'model'))
    hf, cfg, params = tiny(0)
    with pytest.raises(ValueError, match='mesh cannot serve a hybrid'):
        LLMEngine(
            cfg, params, _NoTokenizer(),
            EngineConfig(block_size=4, num_blocks=16, max_num_seqs=2), mesh=mesh,
        )


def test_hybrid_warmup_compiles_every_shape_and_serves_after():
    hf, params, engine = make_engine(max_model_len=32, max_num_seqs=2)
    engine.warmup()
    prompt = _prompt(np.random.default_rng(6), 10)
    out = engine.generate_ids([prompt], SamplingParams(temperature=0.0, max_tokens=5))
    assert_teacher_forced(hf, params, [prompt], out)


def _assert_programs_lower_as_the_parents(engine, cfg):
    """The decode window and the paged prefill, lowering for lowering: the
    engine's programs against functions written as the parent commit wrote
    them (its own name, its own signature, ``mistral``'s entry points by
    name), over the engine's own operands."""
    from distllm_tpu.models import mistral

    econf = engine.config

    def window_fn(
        params, ids, pos, ctx, k, v, bt, steps_left, temp, top_p, min_p,
        top_k, seeds,
    ):
        return mistral.decode_loop(
            params, cfg, ids, pos, k, v, bt, ctx, steps_left,
            temp, top_p, min_p, top_k, seeds, num_steps=econf.decode_steps,
            attn_backend='xla', max_table_positions=econf.max_model_len,
            sampling_top_window=econf.sampling_top_window,
        )

    def prefill_paged_fn(params, ids, pos, k, v, bt, ctx, tails):
        return mistral.prefill_paged(
            params, cfg, ids, pos, k, v, bt, ctx, tails,
            max_table_positions=econf.max_model_len, attn_backend='xla',
        )

    b, width = econf.max_num_seqs, engine.max_blocks_per_seq
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    f32 = lambda *shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
    window_args = (
        engine.params, i32(b), i32(b), i32(b), engine.kv.k_pool, engine.kv.v_pool,
        i32(b, width), i32(b), f32(b), f32(b), f32(b), i32(b),
        jnp.zeros((b,), jnp.uint32),
    )
    prefill_args = (
        engine.params, i32(1, 8), i32(1, 8), engine.kv.k_pool, engine.kv.v_pool,
        i32(1, width), i32(1), i32(1),
    )
    for parent, ours, args, donate in (
        (window_fn, engine._decode_window, window_args, (4, 5)),
        (prefill_paged_fn, engine._prefill_paged, prefill_args, (3, 4)),
    ):
        want = jax.jit(parent, donate_argnums=donate).lower(*args).as_text()
        assert ours.lower(*args).as_text() == want, parent.__name__


# (g) a model without recurrent layers is served as before.
def test_mistral_pool_and_programs_are_what_they_were():
    from distllm_tpu.models import mistral

    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=3, num_heads=4,
        num_kv_heads=2, intermediate_size=48, dtype='float32',
    )
    params = mistral.init_on_device(jax.random.PRNGKey(0), cfg)
    engine = LLMEngine(
        cfg, params, _NoTokenizer(),
        EngineConfig(block_size=4, num_blocks=32, max_num_seqs=2,
                     max_model_len=64, prefill_chunk_tokens=8),
    )
    assert engine.state_pool is None
    assert engine.kv.shape == (3, 32, 4, 2, 8)  # every layer owns pages
    assert 'state_pool_bytes' not in engine.telemetry
    # One group, no window: one stacked pool whose blocks are the
    # scheduler's, one table a row, no second allocator.
    assert [(g.name, g.num_layers, g.window) for g in engine.cache_spec.paged] == [
        ('kv', 3, None)
    ]
    assert engine.window_kv is None and engine.window_blocks is None
    assert 'kv_pools' not in engine.telemetry
    # The default declaration: K and V rows of num_kv_heads x head_dim, no
    # row of the group's own (a latent group's), a V pool like the K pool.
    assert not engine.cache_spec.latent and engine.cache_spec.paged[0].row is None
    assert not engine.kv.latent and engine.kv.v_pool.shape == engine.kv.k_pool.shape
    assert engine.kv.k_pool.shape == engine.kv.pool_shape  # stacked, not a buffer a layer
    assert engine._pools() == (engine.kv.k_pool, engine.kv.v_pool)
    assert engine._group_tables('tables') == 'tables'
    _assert_programs_lower_as_the_parents(engine, cfg)
    compiled = []

    def on_duration(event, seconds, **kw):
        if event == '/jax/core/compile/backend_compile_duration':
            compiled.append(str(kw.get('fun_name')))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    rng = np.random.default_rng(0)
    out = engine.generate_ids(
        [_prompt(rng, 5), _prompt(rng, 21)],
        SamplingParams(temperature=0.0, max_tokens=6),
    )
    assert [len(o) for o in out] == [6, 6]
    # The dense prefill with its scatter, the chunked paged prefill and the
    # decode window: the serving programs of the seed, and no program of
    # the hybrid family.
    names = {n.removeprefix('jit(').removesuffix(')') for n in compiled}
    assert names - {'<lambda>'} == {
        'prefill_fn', '_write_prefill_all_layers', 'prefill_paged_fn',
        'window_fn',
    }
    records = engine.flight.snapshot()
    assert not any('state_slot' in r or 'moe_pairs' in r for r in records[-20:])


def test_roofline_counts_the_parameters_a_token_reaches():
    """``2 * n_params`` a token prices a routed bank at the share a token is
    multiplied by (ROADMAP R6): 3 of the router's 8 experts here."""
    from distllm_tpu.observability.roofline import CostModel

    hf, cfg, params = tiny(0, num_local_experts=4, num_routed_experts=8)
    every = sum(x.size for x in jax.tree.leaves(params))
    banks = sum(
        params[kind][n]['kernel'].size
        for kind in ('mamba', 'attention') for n in ('gate', 'up', 'down')
    )
    dense = CostModel.from_params(params, 4)
    routed = CostModel.from_params(params, 4, experts_per_token=3)
    assert dense.n_params == every and dense.weight_bytes == routed.weight_bytes
    assert routed.n_params == pytest.approx(every - banks * (1 - 3 / 8))
    hf, params, engine = make_engine()
    assert engine._cost_model.n_params < sum(
        x.size for x in jax.tree.leaves(engine.params)
    )
