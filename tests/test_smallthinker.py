"""smallthinker on the CPU at tiny widths: the program (models/
smallthinker.py, models/moe.py with the ranking made ahead of attention, the
paged path over two cache groups) against the plain reference
(benchmarks/reference_smallthinker.py), seeded weights.

Tolerances. The program and the reference are both float32 here, so they
differ only by the order of sums: measured differences are 3e-5 to 7e-5 of
the logits' spread, the limit ``LIMIT`` 2e-4. Every wrong program below
moves the logits by more than 30 times that at these sizes.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_smallthinker as ref
from distllm_tpu.models import decoder_family, moe, smallthinker
from smallthinker_toy import (
    BLOCK,
    LAYOUT,
    WINDOW,
    paged_logits,
    prompt,
    spread,
    tiny,
    tiny_hf,
)

LIMIT = 2e-4
CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')
CONFIG = (
    Path(__file__).resolve().parents[1]
    / 'benchmarks/configs/smallthinker-21b-a3b.json'
)


def published() -> dict:
    """The catalog row's config where the catalog is present, else the
    benchmark configuration with its ``published`` values put back."""
    if CATALOG.is_file():
        for line in CATALOG.read_text().splitlines():
            row = json.loads(line)
            if row['name'] == 'SmallThinker-21BA3B-Instruct':
                return row['config']
    model = json.loads(CONFIG.read_text())
    layers = model['published']['num_hidden_layers']
    model.update(
        num_hidden_layers=layers,
        moe_num_primary_experts=model['published']['moe_num_primary_experts'],
        sliding_window_layout=[0, 1, 1, 1] * (layers // 4),
        rope_layout=[0, 1, 1, 1] * (layers // 4),
    )
    return model


def _program_logits(cfg, params, tokens, module=smallthinker):
    ids = jnp.asarray([tokens])
    hidden = module.apply(params, cfg, ids, jnp.ones_like(ids))
    return np.asarray(module.logits(params, cfg, hidden))[0]


def _reference_logits(hf, params, tokens, keep=()):
    return ref.smallthinker_logits(
        params, hf, [tokens], [np.arange(len(tokens))], keep=keep
    )


# ------------------------------------------------------------------- config
def test_config_reads_the_published_keys():
    cfg = smallthinker.SmallThinkerConfig.from_hf_config(published())
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == (52, 2560, 151936)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (28, 4, 128)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.experts_per_token) == (64, 64, 6)
    assert (cfg.moe_intermediate_size, cfg.sliding_window) == (768, 4096)
    assert cfg.rope_theta == 1.5e6 and cfg.max_position_embeddings == 16384
    assert (cfg.count('full'), cfg.count('window'), cfg.count('sparse')) == (13, 39, 52)
    assert cfg.layer_runs()[:2] == [
        ('full', 'nope', 0, 0, 1), ('window', 'rope', 0, 1, 3),
    ]
    spec = cfg.cache_spec()
    assert [(g.name, g.num_layers, g.window) for g in spec.paged] == [
        ('full', 13, None), ('window', 39, 4096),
    ]


def test_benchmark_configuration_is_the_published_one_but_for_its_cut():
    model, source = json.loads(CONFIG.read_text()), published()
    reduced = set(model['reduced'])
    assert reduced == {
        'num_hidden_layers', 'sliding_window_layout', 'rope_layout',
        'moe_num_primary_experts',
    }
    for key, value in source.items():
        if key not in reduced:
            assert model[key] == value, key
    assert model['sliding_window_layout'] == source['sliding_window_layout'][:16]
    assert model['rope_layout'] == source['rope_layout'][:16]
    cfg = smallthinker.SmallThinkerConfig.from_hf_config(model)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert) == (64, 16, 0)
    shapes = jax.eval_shape(
        lambda: smallthinker.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    # ISSUE 52's arithmetic: a layer held 115,512,320, 2.626 G in all.
    assert sum(a.size for a in jax.tree.leaves(shapes)) == (
        16 * 115_512_320 + 2 * 151_936 * 2560 + 2560
    )


@pytest.mark.parametrize('key, value, says', [
    ('moe_primary_router_apply_softmax', False, 'apply_softmax'),
    ('norm_topk_prob', False, 'norm_topk_prob'),
    ('rope_scaling', {'rope_type': 'yarn', 'factor': 4}, 'rope_scaling'),
    ('tie_word_embeddings', True, 'tied'),
    ('sliding_window_layout', [0, 1, 1], 'num_hidden_layers'),
    ('rope_layout', [0, 1] * 5, 'num_hidden_layers'),
    ('sliding_window_layout', [0, 2, 1, 1, 0, 1, 1, 1], 'window size'),
    ('sliding_window_layout', [1] * 8, 'no full layer'),
    ('sliding_window_layout', [0] * 8, 'no window layer'),
])
def test_config_refuses_what_is_not_implemented(key, value, says):
    with pytest.raises(ValueError, match=f'smallthinker.*{says}'):
        smallthinker.SmallThinkerConfig.from_hf_config(tiny_hf(**{key: value}))


def test_family_row_and_a_loader_that_refuses_by_name():
    config_cls, module = decoder_family('smallthinker')
    assert (config_cls, module) == (smallthinker.SmallThinkerConfig, smallthinker)
    _, cfg, params = tiny()
    # The published names round-trip; a name that is not there is refused.
    state = {
        'model.embed_tokens.weight': np.asarray(params['embed']),
        'lm_head.weight': np.asarray(params['head']).T,
        'model.norm.weight': np.asarray(params['final_ln']['scale']),
    }
    seen = {'full': 0, 'window': 0}
    for layer, window in enumerate(LAYOUT):
        tree = ('full', 'window')[window]
        lp = jax.tree.map(lambda a: np.asarray(a[seen[tree]]), params[tree])
        mp = jax.tree.map(lambda a: np.asarray(a[layer]), params['sparse'])
        seen[tree] += 1
        at = f'model.layers.{layer}'
        state[f'{at}.input_layernorm.weight'] = lp['ln']['scale']
        state[f'{at}.post_attention_layernorm.weight'] = mp['mlp_ln']['scale']
        for n in 'qkvo':
            state[f'{at}.self_attn.{n}_proj.weight'] = lp[n]['kernel'].T
        moe_at = f'{at}.block_sparse_moe'
        state[f'{moe_at}.primary_router.weight'] = mp['router']['kernel'].T
        for e in range(cfg.num_experts):
            for n in ('gate', 'up', 'down'):
                state[f'{moe_at}.experts.{e}.{n}.weight'] = mp[n]['kernel'][e].T
    back = smallthinker.params_from_hf(state, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    jax.tree.map(np.testing.assert_array_equal, back, jax.tree.map(np.asarray, params))
    del state['model.layers.3.block_sparse_moe.primary_router.weight']
    with pytest.raises(KeyError, match='primary_router'):
        smallthinker.params_from_hf(state, cfg)


# ------------------------------------------------------ against the reference
@pytest.mark.parametrize('seed, kv_heads', [(0, 1), (1, 2)])
def test_dense_forward_logits_match_reference(seed, kv_heads):
    """7 queries on 1 KV head, and 14 on 2."""
    hf, cfg, params = tiny(
        seed, num_key_value_heads=kv_heads, num_attention_heads=7 * kv_heads
    )
    tokens = prompt(np.random.default_rng(seed), 3 * WINDOW)
    got = _program_logits(cfg, params, tokens)
    assert spread(got, _reference_logits(hf, params, tokens)[0]) < LIMIT


@pytest.mark.parametrize('n_prompt, chunk, backend', [
    (19, 8, 'xla'), (WINDOW + 7, 16, 'xla'), (WINDOW + 7, 8, 'interpret'),
])
def test_paged_prefill_and_decode_logits_match_reference(n_prompt, chunk, backend):
    """Chunked prefill at two splits, then decode steps across the window
    and well past it, against the reference's full forward pass."""
    hf, cfg, params = tiny()
    tokens = prompt(np.random.default_rng(n_prompt), 2 * WINDOW + 11)
    got, _, _, blocks = paged_logits(
        cfg, params, tokens, n_prompt, chunk=chunk, backend=backend
    )
    want = _reference_logits(hf, params, tokens)[0][n_prompt - 1:]
    assert spread(got, want) < LIMIT
    assert blocks.freed_total > 0  # blocks went back behind the window


def test_pages_of_both_groups_hold_the_references_keys_and_values():
    """Layer 0's pages (full group: every position) and layer 1's (window
    group: the last ``WINDOW`` positions and what shares their blocks)."""
    hf, cfg, params = tiny()
    total, n_prompt = 2 * WINDOW + 9, WINDOW - 5
    tokens = prompt(np.random.default_rng(3), total)
    _, (k, v), full_row, blocks = paged_logits(cfg, params, tokens, n_prompt)
    _, kept = _reference_logits(hf, params, tokens, keep=(0, 1))
    written = total - 1  # the last token was never fed
    for name, pools in (('k', k), ('v', v)):
        full = np.asarray(pools[0][0])[full_row].reshape(-1, 1, 16)[:written]
        np.testing.assert_allclose(
            full, kept[0][0][name][:written], atol=2e-5, rtol=1e-4
        )
        row = blocks.table_row(0, np.zeros((len(full_row),), np.int32))
        first = next(i for i, b in enumerate(row) if b) * BLOCK
        assert first <= written - WINDOW  # the window's lower edge is held
        held = np.asarray(pools[1][0])[row].reshape(-1, 1, 16)[first:written]
        np.testing.assert_allclose(
            held, kept[0][1][name][first:written], atol=2e-5, rtol=1e-4
        )


def test_every_layers_ranking_is_the_references():
    """The kept expert ids and gates of every layer, from the reference's
    own input to that layer: what ``smallthinker._rank`` hands the
    matmuls."""
    hf, cfg, params = tiny()
    tokens = prompt(np.random.default_rng(5), 40)
    _, kept = _reference_logits(hf, params, tokens, keep=tuple(range(len(LAYOUT))))
    x = np.asarray(params['embed'])[np.asarray(tokens)]
    for li, (group, _, ai, mi) in enumerate(cfg.layer_indices()):
        scale = params[group]['ln']['scale'][ai]
        mp = jax.tree.map(lambda a: a[mi], {
            n: params['sparse'][n] for n in ('router', 'mlp_ln')
        })
        ranking = smallthinker._rank(
            smallthinker._norm(jnp.asarray(x), scale, cfg), mp,
            params['sparse'], cfg, mi,
        )
        order = np.argsort(-kept[0][li]['gates'], axis=-1)
        np.testing.assert_array_equal(
            np.asarray(ranking.local) + cfg.first_local_expert,
            np.take_along_axis(kept[0][li]['experts'], order, -1),
        )
        np.testing.assert_allclose(
            np.asarray(ranking.weights),
            np.take_along_axis(kept[0][li]['gates'], order, -1), atol=1e-5,
        )
        x = kept[0][li]['out']


def test_program_with_a_share_matches_the_reference_with_that_share():
    hf, cfg, params = tiny(
        moe_num_primary_experts=4, num_routed_experts=8, first_local_expert=2
    )
    assert (cfg.num_experts, cfg.num_local_experts) == (8, 4)
    tokens = prompt(np.random.default_rng(2), 2 * WINDOW)
    got = _program_logits(cfg, params, tokens)
    assert spread(got, _reference_logits(hf, params, tokens)[0]) < LIMIT


def test_expert_shares_add_up_to_the_uncut_layer():
    """Shares ``first_local_expert`` 0, 2, 4, 6 of 2 experts each: what the
    four add to the residual stream, the residual and attention counted
    once, is the uncut reference's layer."""
    hf, cfg, params = tiny()
    tokens = prompt(np.random.default_rng(7), WINDOW + 9)
    _, kept = _reference_logits(hf, params, tokens, keep=(0, 1))
    whole = kept[0][1]['out']  # layer 1: a window layer with RoPE
    x = jnp.asarray(kept[0][0]['out'])[None]
    one = dict(hf, num_hidden_layers=2, sliding_window_layout=[0, 1],
               rope_layout=[0, 1])
    parts = []
    for first in (0, 2, 4, 6):
        share = smallthinker.SmallThinkerConfig.from_hf_config(dict(
            one, moe_num_primary_experts=2, num_routed_experts=8,
            first_local_expert=first,
        )).model_copy(update={'dtype': 'float32'})
        held = {
            n: {'kernel': params['sparse'][n]['kernel'][:, first:first + 2]}
            for n in ('gate', 'up', 'down')
        }
        sparse = {**params['sparse'], **held}
        parts.append(_layer_out(share, params, sparse, x, layer=1))
    h = _layer_out(share, params, jax.tree.map(jnp.zeros_like, sparse), x, 1)
    total = h + sum(part - h for part in parts)
    assert spread(total[0], whole) < LIMIT


def _layer_out(cfg, params, sparse, x, layer):
    """Layer ``layer`` of the toy (a window layer) over ``x [1, S, H]``
    through the program's own functions."""
    s = x.shape[1]
    lp = jax.tree.map(lambda a: a[0], params['window'])
    mp = jax.tree.map(
        lambda a: a[layer], {n: sparse[n] for n in ('router', 'mlp_ln')}
    )
    u = smallthinker._norm(x, lp['ln']['scale'], cfg)
    ranking = smallthinker._rank(u, mp, sparse, cfg, layer)
    positions = jnp.arange(s)[None]
    q, k, v = smallthinker._qkv(
        u, lp, cfg, 'rope', *smallthinker._rope_table(cfg, s), positions
    )
    near = jnp.arange(s)[None, :] > jnp.arange(s)[:, None] - cfg.sliding_window
    mask = (jnp.tril(jnp.ones((s, s), bool)) & near)[None, None]
    attn = smallthinker.common.sdpa(q, k, v, mask=mask)
    out, _ = smallthinker._finish_layer(
        x, attn, lp, mp, sparse, cfg, layer, ranking, jnp.ones((1, s), bool)
    )
    return np.asarray(out)


# ------------------------------------------------------------ wrong programs
def _router_reads_the_post_attention_stream(monkeypatch):
    # No ranking made ahead: ``routed_experts`` ranks from the rows it is
    # given, the post-attention norm's (the usual placement).
    monkeypatch.setattr(smallthinker, '_rank', lambda *a: None)
    return {}


def _silu_experts(monkeypatch):
    real = moe.routed_experts
    monkeypatch.setattr(
        smallthinker, 'routed_experts',
        lambda *a, **kw: real(*a, **{**kw, 'activation': 'silu'}),
    )
    return {}


WRONG = {
    'router_after_attention': (_router_reads_the_post_attention_stream, 0.05),
    'silu': (_silu_experts, 0.05),
    'rope_in_full_layers': (lambda mp: {'rope_layout': [1] * 8}, 0.05),
    'no_rope_in_window_layers': (lambda mp: {'rope_layout': [0] * 8}, 0.05),
    'window_half': (lambda mp: {'sliding_window_size': WINDOW // 2}, 0.01),
}


@pytest.mark.parametrize('what', sorted(WRONG))
def test_tolerance_breaks_on_a_wrong_program(what, monkeypatch):
    """Each wrong program leaves the reference by more than ``floor`` of
    the logits' spread, 50 times ``LIMIT`` or more: the router fed the
    post-attention stream, SiLU for ReLU, ``rope_layout`` not honoured
    layer by layer (either way), a window half as long."""
    make, floor = WRONG[what]
    hf, _, params = tiny()
    over = make(monkeypatch)
    cfg = smallthinker.SmallThinkerConfig.from_hf_config(
        {**hf, **over}
    ).model_copy(update={'dtype': 'float32'})
    tokens = prompt(np.random.default_rng(11), 2 * WINDOW)
    got = _program_logits(cfg, params, tokens)
    assert spread(got, _reference_logits(hf, params, tokens)[0]) > floor


def test_rope_layout_is_honoured_layer_by_layer():
    """A layout that rotates the FULL layers and not the window layers is
    served as it says, in the dense forward and through the pages."""
    layout = [1, 0, 0, 1, 1, 0, 1, 0]
    hf, cfg, params = tiny(rope_layout=layout)
    assert [r for _, r, _, _ in cfg.layer_indices()] == [
        ('nope', 'rope')[r] for r in layout
    ]
    tokens = prompt(np.random.default_rng(13), 2 * WINDOW)
    want = _reference_logits(hf, params, tokens)[0]
    assert spread(_program_logits(cfg, params, tokens), want) < LIMIT
    got, *_ = paged_logits(cfg, params, tokens, WINDOW + 3)
    assert spread(got, want[WINDOW + 2:]) < LIMIT


def test_serving_programs_lower_each_kind_of_layer_once():
    """8 layers of two kinds: the prefill program holds two private layer
    functions, called 2 and 6 times."""
    _, cfg, params = tiny()
    sds = jax.ShapeDtypeStruct
    pool = lambda n: sds(  # noqa: E731
        (n, 9, BLOCK, cfg.num_kv_heads * cfg.head_dim), jnp.float32
    )
    pools = (pool(cfg.count('full')), pool(cfg.count('window')))
    i32 = jnp.int32
    text = jax.jit(
        lambda p, *a: smallthinker.prefill_paged(p, cfg, *a)
    ).lower(
        params, sds((2, 8), i32), sds((2, 8), i32), pools, pools,
        (sds((2, 6), i32),) * 2, sds((2,), i32), sds((2,), i32),
    ).as_text()
    assert text.count('func.func private @smallthinker_layer') == 2
    assert text.count('call @smallthinker_layer') == 8


def test_compile_ahead_leaves_the_check_nothing_to_compile():
    hf, cfg, params = tiny()
    shapes = jax.eval_shape(lambda: params)
    ref._programs.cache_clear()
    ref.compile_ahead(hf, shapes, [32], scored=5)
    tokens = prompt(np.random.default_rng(1), 32)
    at = np.arange(27, 32)
    want = ref.token_gaps(
        ref.smallthinker_logits(params, hf, [tokens], [at]), [tokens[27:]]
    )
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, s, **kw: compiled.append(kw.get('fun_name'))
        if event == '/jax/core/compile/backend_compile_duration' else None
    )
    got, _ = ref.smallthinker_token_gaps(
        params, hf, [tokens], [at], [tokens[27:]]
    )
    np.testing.assert_allclose(got, want, atol=1e-5)
    # ``layer`` ran in ``smallthinker_logits`` above; ``head_gaps`` was
    # compiled ahead: the call found every program it needs.
    assert not [name for name in compiled if name in ('layer', 'head_gaps')]
