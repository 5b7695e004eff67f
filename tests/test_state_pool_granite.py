"""granitemoehybrid on the CPU at tiny widths: the program (models/
granite_hybrid.py, models/moe.py) against the plain reference
(benchmarks/reference_granite.py), seeded weights; and what of the engine is
this family's alone (what every family's engine owes:
``test_engine_families.py``).

Tolerances. The program and the reference are both float32 here, so they
differ only by the order of sums (chunked SSD against step-by-step
recurrence, grouped against masked experts): measured differences are a few
1e-6 of the logits' spread, the limits 2e-4. A bfloat16 SSM state (2^-9 a
step, compounding) or a score scale of 1/sqrt(head) instead of
``attention_multiplier`` moves logits by more than 1e-2 of their spread at
these sizes, which the tests of the perturbed program below show.

The file's name sorts it after ``test_startup_attribution.py`` on purpose:
started beside ``test_history.py`` (as ``test_granite_hybrid.py`` would be
under ``--dist loadfile``) its compiles begin between the two arms of that
file's timing sentinel, which then failed in two whole runs of three.
"""

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_granite as ref
from distllm_tpu.generate.engine.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.models import granite_hybrid as gh
from distllm_tpu.models.moe import routed_experts
from granite_toy import (
    LAYERS,
    NoTokenizer,
    left_errors,
    make_engine,
    prompt,
    spread,
    tiny,
    tiny_hf,
)


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
def test_a_bfloat16_state_pool_is_told_apart_by_two_orders():
    """What the benchmark's second limit reads (the float32 pool:
    ``test_engine_families.py``, ``left``): a pool that keeps the state in
    bfloat16, the precision below the one the module states, reads 1e-3 and
    more where the module's own reads under 1e-5."""
    from test_engine_families import finished

    hf, cfg, params = tiny(0)

    class Bf16Pool(type(cfg)):
        def state_spec(self):
            spec = super().state_spec()
            return {**spec, 'ssm': tuple(
                jax.ShapeDtypeStruct(s.shape, jnp.bfloat16) for s in spec['ssm']
            )}

    engine = LLMEngine(Bf16Pool(**cfg.model_dump()), params, NoTokenizer(), EngineConfig(
        block_size=4, num_blocks=64, max_num_seqs=4, max_model_len=96,
        prefill_chunk_tokens=8, decode_steps=4, attn_backend='xla',
        enable_prefix_cache=False,
    ))
    for fed, record in finished('granite', engine, 3, (6, 19, 11), 13):
        errors, slow = left_errors(engine, hf, params, fed, record)
        assert min(errors) > 1e-3 and slow > 1e-3, (errors, slow)


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
        cfg, params, NoTokenizer(),
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
        [prompt(rng, 5), prompt(rng, 21)],
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
