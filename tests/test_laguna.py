"""laguna on the CPU at tiny widths: the program (models/laguna.py,
models/moe.py, the paged path over two cache groups) against the plain
reference (benchmarks/reference_laguna.py), seeded weights.

Tolerances. The program and the reference are both float32 here, so they
differ only by the order of sums (grouped against masked experts, paged
against dense attention; since PR 43 the toy's paged path runs as one jitted
program a kind of dispatch, as the engine's, which XLA fuses): measured
differences are 1.5e-5 to 6.4e-5 of the logits' spread, the limit ``LIMIT``
2e-4. Every wrong program of ISSUE 30's list
moves the logits by more than 30 times that at these sizes (the parametrised
test below gives each reading its floor), an int8 KV pool, the nearest
precision below, included.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_laguna as ref
from distllm_tpu.generate.engine.kv_cache import WindowBlocks
from distllm_tpu.models import common, decoder_family, laguna
from laguna_toy import (
    BLOCK,
    WINDOW,
    paged_logits,
    prompt,
    spread,
    tiny,
    tiny_hf,
)

LIMIT = 2e-4
CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')
CONFIG = Path(__file__).resolve().parents[1] / 'benchmarks/configs/laguna-xs.2.json'


def published() -> dict:
    """The catalog row's config where the catalog is present, else the
    benchmark configuration with its ``published`` values put back."""
    if CATALOG.is_file():
        for line in CATALOG.read_text().splitlines():
            row = json.loads(line)
            if row['name'] == 'Laguna-XS.2':
                return row['config']
    model = json.loads(CONFIG.read_text())
    layers = model['published']['num_hidden_layers']
    period = len(model['layer_types']) // 5
    model.update(
        num_hidden_layers=layers, num_experts=model['published']['num_experts'],
        vocab_size=model['published']['vocab_size'],
        layer_types=(model['layer_types'][:period] * layers)[:layers],
        mlp_layer_types=['dense'] + ['sparse'] * (layers - 1),
        num_attention_heads_per_layer=(
            model['num_attention_heads_per_layer'][:period] * layers
        )[:layers],
    )
    return model


def test_config_reads_the_published_keys():
    hf = published()
    cfg = laguna.LagunaConfig.from_hf_config(hf)
    assert cfg.num_layers == 40 and cfg.hidden_size == 2048
    assert (cfg.count('full'), cfg.count('window')) == (10, 30)
    assert (cfg.count('dense'), cfg.count('sparse')) == (1, 39)
    assert (cfg.num_heads('full'), cfg.num_heads('window')) == (48, 64)
    assert (cfg.num_kv_heads, cfg.head_size, cfg.sliding_window) == (8, 128, 512)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.experts_per_token) == (256, 256, 8)
    assert cfg.routed_scaling_factor == 2.5 and cfg.rms_norm_eps == 1e-6
    assert cfg.rope_parameters['full']['rope_type'] == 'yarn'
    assert cfg.rope_parameters['window']['rope_theta'] == 10000
    assert cfg.layer_runs()[:3] == [
        ('full', 'dense', 0, 0, 1), ('window', 'sparse', 0, 0, 3),
        ('full', 'sparse', 1, 3, 1),
    ]
    spec = cfg.cache_spec()
    assert [(g.name, g.num_layers, g.window) for g in spec.paged] == [
        ('full', 10, None), ('window', 30, 512),
    ]
    assert spec.state is None and not spec.dense_prefill
    # K/V groups both: neither declares a row of its own.
    assert not spec.latent and all(g.stored_row is None for g in spec.paged)


def test_benchmark_configuration_is_the_published_one_but_for_its_cut():
    model, hf = json.loads(CONFIG.read_text()), published()
    assert len(model['reduced']) == 6
    for key, value in hf.items():
        if key in model['reduced']:
            continue
        assert model[key] == value, key
    for key in ('layer_types', 'mlp_layer_types', 'num_attention_heads_per_layer'):
        assert model[key] == hf[key][:20]
    cfg = laguna.LagunaConfig.from_hf_config(model)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert) == (256, 64, 0)
    assert cfg.num_layers == 20 and cfg.vocab_size == 25088


@pytest.mark.parametrize('key, value', [
    ('gating', 'per-dim'),
    ('moe_apply_router_weight_on_input', True),
    ('attention_bias', True),
    ('rope_type', 'llama3'),
    ('norm_topk_prob', False),
    ('layer_types', ['full_attention'] * 6),
])
def test_config_refuses_what_is_not_implemented(key, value):
    hf = tiny_hf(**({} if key == 'rope_type' else {key: value}))
    if key == 'rope_type':
        hf['rope_parameters']['full_attention']['rope_type'] = value
    with pytest.raises(ValueError, match='laguna'):
        laguna.LagunaConfig.from_hf_config(hf)


def test_family_row_and_no_guessed_checkpoint_loader():
    cls, module = decoder_family('laguna')
    assert cls is laguna.LagunaConfig and module is laguna
    with pytest.raises(NotImplementedError, match='no converter'):
        laguna.params_from_hf({}, tiny()[1])


@pytest.mark.parametrize('seed', [0, 1])
def test_dense_forward_logits_match_reference(seed):
    hf, cfg, params = tiny(seed)
    ids = np.random.default_rng(seed).integers(4, 96, size=(2, 45))
    hidden = laguna.apply(params, cfg, jnp.asarray(ids), jnp.ones_like(ids))
    got = laguna.logits(params, cfg, hidden)
    want = ref.laguna_logits(params, hf, ids, np.tile(np.arange(45), (2, 1)))
    assert spread(got, want) < LIMIT


# Prompts longer than window + chunk (12 + 8): by the last chunk the window
# group has given back the blocks behind the window, and decode goes on
# freeing them.
@pytest.mark.parametrize('n_prompt, backend', [
    (37, 'xla'), (37, 'interpret'), (21, 'xla'), (5, 'xla'),
])
def test_paged_prefill_and_decode_logits_match_reference(n_prompt, backend):
    hf, cfg, params = tiny(2)
    tokens = prompt(np.random.default_rng(n_prompt), n_prompt + 14)
    got, blocks = paged_logits(
        cfg, params, tokens, n_prompt, backend=backend
    )
    at = np.arange(n_prompt - 1, len(tokens))[None]
    want = ref.laguna_logits(params, hf, np.asarray([tokens]), at)[0]
    assert spread(got, want) < LIMIT
    if n_prompt > WINDOW + 8:
        assert blocks.freed_total >= (n_prompt - WINDOW) // BLOCK
    assert blocks.held(0) <= blocks.bound(1)


def test_program_with_a_share_matches_the_reference_with_that_share():
    hf, cfg, params = tiny(
        3, num_experts=4, num_routed_experts=8, first_local_expert=2
    )
    tokens = prompt(np.random.default_rng(5), 40)
    got, _ = paged_logits(cfg, params, tokens, 30)
    want = ref.laguna_logits(
        params, hf, np.asarray([tokens]), np.arange(29, 40)[None]
    )[0]
    assert spread(got, want) < LIMIT


class _FreesABlockEarly(WindowBlocks):
    def first_visible_block(self, position):
        return super().first_visible_block(position) + 1


def _no_gate(attn, normed, lp, cfg, kind):
    from distllm_tpu.models import common

    return common.dense(
        attn.reshape(*attn.shape[:-2], cfg.num_heads(kind) * cfg.head_dim),
        lp['o']['kernel'],
    )


def _full_rope(cfg, **over):
    rope = json.loads(json.dumps(cfg.rope_parameters))
    rope['full'].update(over)
    return {'rope_parameters': rope}


# what is wrong -> (the program's config differs by, keywords of the paged
# run, what is patched in the module, the least the logits have to move).
WRONG = {
    'window_a_block_short': (lambda c: {'sliding_window': WINDOW - BLOCK}, {}, {}, 0.5),
    'window_a_block_long': (lambda c: {'sliding_window': WINDOW + BLOCK}, {}, {}, 0.5),
    'freed_block_read': (lambda c: {}, {'blocks_cls': _FreesABlockEarly}, {}, 0.5),
    'gate_left_out': (lambda c: {}, {}, {'_attn_out': _no_gate}, 0.5),
    'yarn_left_out': (lambda c: _full_rope(c, rope_type='default'), {}, {}, 0.5),
    'full_layers_rotate_all_dims': (
        lambda c: _full_rope(c, partial_rotary_factor=1.0), {}, {}, 0.5),
    'routed_scale_left_out': (lambda c: {'routed_scaling_factor': 1.0}, {}, {}, 0.5),
    'int8_kv_pool': (lambda c: {}, {'int8': True}, {}, 0.5),
}


@pytest.mark.parametrize('what', sorted(WRONG))
def test_tolerance_breaks_on_a_wrong_program(what, monkeypatch):
    """Each wrong program of the list the cell's check has to catch moves
    the logits past ``LIMIT`` by a wide margin, at these widths."""
    update, run_kw, patches, floor = WRONG[what]
    hf, cfg, params = tiny(2)
    for name, fn in patches.items():
        monkeypatch.setattr(laguna, name, fn)
    tokens = prompt(np.random.default_rng(37), 51)
    got, _ = paged_logits(
        cfg.model_copy(update=update(cfg)), params, tokens, 37, **run_kw
    )
    want = ref.laguna_logits(
        params, hf, np.asarray([tokens]), np.arange(36, 51)[None]
    )[0]
    assert spread(got, want) > floor > 30 * LIMIT


@pytest.fixture(scope='module')
def probe_check():
    """``scripts/probe_laguna_reference.py``'s ``check`` over the cell's toy
    rehearsal configuration: the cell's own check (``laguna_closed``: the
    greedy call through ``LLMEngine``, then the reference) on an engine
    built as an arm says; returns the arm's result line."""
    import functools
    import io
    import runpy
    from contextlib import redirect_stdout
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    probe = runpy.run_path(str(root / 'scripts/probe_laguna_reference.py'))
    model = json.loads((
        root / 'benchmarks/tests/rehearsal_laguna/configs/tiny-laguna.json'
    ).read_text())
    # int8's rounding error goes with a row's largest entry over its width:
    # at the toy's 16 dims a head it reads just over the limit calibrated at
    # 128 (0.0046 against 0.0045), at 64 well over.
    model['head_dim'] = 64

    @functools.cache
    def run(arm):
        out = io.StringIO()
        with redirect_stdout(out):
            probe['check'](model, [3000000123], [arm])
        return json.loads(out.getvalue().strip().splitlines()[-1])

    return run


@pytest.mark.parametrize('arm, by', [
    ('window_496', 'token_gap_mean_std'),
    ('window_528', 'token_gap_mean_std'),
    ('int8_kv', 'kv_content_error'),
])
def test_the_cells_check_refuses_a_wrong_window_and_an_int8_pool(
    probe_check, arm, by
):
    """The three controls the cell's first check let through (a window a
    block short or long read inside the right program's range of largest
    gaps, an int8 K/V pool under it): each now fails the limit made for it,
    at toy widths here and at the configuration's on the chip
    (``benchmarks/reference_laguna.py`` has the readings)."""
    limit = {
        'token_gap_mean_std': ref.MEAN_GAP_LIMIT_STD,
        'kv_content_error': ref.KV_CONTENT_LIMIT,
    }[by]
    right = probe_check('program')
    assert right['correct'] is True and right[by] < limit / 10
    wrong = probe_check(arm)
    assert wrong['correct'] is False and wrong[by] > limit


def _sparse_layer(hf, params, i=0):
    return jax.tree.map(lambda a: jnp.asarray(a[i], jnp.float32), params['sparse'])


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of the expert axis, two experts each: their routed parts
    and the shared expert, which every chip computes alike, counted once,
    add up to the reference's whole layer."""
    hf, cfg, params = tiny(4)
    x = jax.random.normal(jax.random.PRNGKey(9), (13, 64), jnp.float32)
    whole = ref.sparse_mlp(x, _sparse_layer(hf, params), 2, 2.5, 0)
    shared = None
    routed = jnp.zeros_like(x)
    for first in (0, 2, 4, 6):
        share = cfg.model_copy(
            update={'num_local_experts': 2, 'first_local_expert': first}
        )
        held = jax.tree.map(lambda a: a, params)
        held['sparse'] = {
            n: ({'kernel': leaf['kernel'][:, first:first + 2]}
                if n in ('gate', 'up', 'down') else leaf)
            for n, leaf in params['sparse'].items()
        }
        mp = laguna._mlp_layer_at(held, 'sparse', 0)
        out, pairs = laguna._mlp(
            x, mp, share, 'sparse', jnp.ones((13,), bool), held['sparse'], 0
        )
        own_shared = common.swiglu(
            x, mp['shared_gate']['kernel'], mp['shared_up']['kernel'],
            mp['shared_down']['kernel'],
        )
        routed = routed + (out - own_shared)
        shared = own_shared if shared is None else shared
        assert int(pairs[0]) == 13 * 2 and 0 <= int(pairs[1]) <= 26
    assert spread(routed + shared, whole) < LIMIT


def test_yarn_tables_are_the_references():
    from distllm_tpu.models import common

    spec = tiny_hf()['rope_parameters']['full_attention']
    cos, sin = common.rope_frequencies(8, 64, 500000.0, spec)
    want_cos, want_sin, rotated = ref.rope_angles(spec, 16, np.arange(64))
    assert rotated == 8
    np.testing.assert_allclose(cos, want_cos, atol=1e-6)
    np.testing.assert_allclose(sin, want_sin, atol=1e-6)
    plain, _ = common.rope_frequencies(8, 64, 500000.0)
    assert np.abs(cos - plain).max() > 0.1  # the scaling does something


def test_serving_programs_lower_each_kind_of_layer_once():
    """The serving programs walk six layers here, twenty in the benchmark,
    and a run of the cell has a time limit: a kind of layer (full + dense,
    full + sparse, window + sparse) is one function of the lowered module,
    called a layer, and not one copy of its text a layer."""
    hf, cfg, params = tiny()
    pools = tuple(
        jnp.zeros(
            (cfg.count(kind), 9, BLOCK, cfg.num_kv_heads * cfg.head_dim),
            jnp.float32,
        ) for kind in ('full', 'window')
    )
    tables = (jnp.zeros((1, 8), jnp.int32),) * 2
    text = jax.jit(
        lambda p, k, v: laguna.prefill_paged(
            p, cfg, jnp.zeros((1, 8), jnp.int32),
            jnp.arange(8, dtype=jnp.int32)[None], k, v, tables,
            jnp.full((1,), 8, jnp.int32), jnp.full((1,), 8, jnp.int32),
            max_table_positions=32,
        )
    ).lower(params, pools, pools).as_text()
    kinds = {(a, m) for a, m, _, _ in cfg.layer_indices()}
    assert len(kinds) == 3 and len(cfg.layer_indices()) == 6
    assert text.count('func.func private @laguna_layer') == len(kinds)
    assert text.count('call @laguna_layer') == len(cfg.layer_indices())


def test_compile_ahead_leaves_the_check_nothing_to_compile():
    """``compile_ahead`` (the cell's driver runs it on a thread beside the
    engine's set-up) compiles from shapes what ``laguna_logits`` and
    ``first_layer_kv`` then call: neither compiles a program of its own."""
    hf, cfg, params = tiny(3)
    model = dict(hf, first_local_expert=0)
    shapes = jax.eval_shape(lambda: params)
    compiled = []

    def on_compile(event, seconds, **kw):
        if event == '/jax/core/compile/backend_compile_duration':
            compiled.append(str(kw.get('fun_name')))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        ref.compile_ahead(model, shapes, [24], scored=5, kv_rows=2 * BLOCK)
        ahead = [n for n in compiled if n in ('jit(layer)', 'jit(head)', 'jit(first_kv)')]
        assert sorted(set(ahead)) == ['jit(first_kv)', 'jit(head)', 'jit(layer)']
        assert ahead.count('jit(layer)') == 3  # the three kinds, one width
        del compiled[:]
        ids = np.zeros((1, 24), np.int32)
        ids[0, :20] = prompt(np.random.default_rng(3), 20)
        ref.laguna_logits(params, model, ids, 15 + np.arange(5)[None])
        at = np.arange(2 * BLOCK)
        ref.first_layer_kv(params, model, ids[0, at], at)
        assert not [n for n in compiled if n in ahead]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
