"""The two forms of ``models/moe.py``'s expert matmuls: the dense
all-held-experts form against the grouped one on one table of routings, the
rule that chooses between them as a table of the benchmark's program
shapes, and the engine's account of which form each program took."""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distllm_tpu.generate.engine import SamplingParams
from distllm_tpu.models import (
    deepseek_v3, granite_hybrid, laguna, lfm2, mistral, moe,
)

ROOT = Path(__file__).resolve().parents[1]
H, I, HELD, ROUTED, LAYERS = 32, 24, 4, 16, 3



def _inputs(dtype, tokens):
    rng = np.random.default_rng(0)
    held = HELD

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    return dict(
        x=normal(tokens, H),
        router=jnp.asarray(rng.normal(size=(H, ROUTED)) * 0.4, jnp.float32),
        gate=normal(LAYERS, held, H, I, scale=0.2),
        up=normal(LAYERS, held, H, I, scale=0.2),
        down=normal(LAYERS, held, I, H, scale=0.2),
        bias=jnp.asarray(rng.normal(size=(ROUTED,)) * 0.3, jnp.float32),
        counted=jnp.asarray(rng.random(tokens) < 0.6),
    )


def _both_forms(monkeypatch, call):
    out = {}
    for form in ('grouped', 'dense'):
        monkeypatch.setattr(moe, 'expert_form', lambda *shape, f=form: f)
        out[form] = jax.tree.map(np.asarray, call())
    return out['grouped'], out['dense']


# (id, tokens, keyword arguments, which stacked layer and how it is given)
CASES = [
    ('softmax', 16, dict(), None),
    ('softmax_scaled_odd_rows', 13, dict(routed_scale=2.5), None),
    ('sigmoid_bias_eps', 19, dict(scoring='sigmoid', bias=True, norm_eps=1e-6), None),
    ('sigmoid_no_bias', 24, dict(scoring='sigmoid'), None),
    ('held_elsewhere', 21, dict(first_expert=8), None),
    ('first_expert_sigmoid_scaled', 7, dict(
        first_expert=12, scoring='sigmoid', bias=True, routed_scale=2.448), None),
    ('counted_rows', 18, dict(counted=True, first_expert=4), None),
    ('layer_static', 16, dict(first_expert=4), ('static', 1)),
    ('layer_traced', 11, dict(scoring='sigmoid', bias=True), ('traced', 2)),
    ('layer_traced_counted', 96, dict(counted=True, first_expert=8), ('traced', 0)),
]


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize(
    'tokens, kw, layer', [c[1:] for c in CASES], ids=[c[0] for c in CASES]
)
def test_dense_form_is_the_grouped_form(monkeypatch, dtype, tokens, kw, layer):
    """Same routing, same gates, same pairs counted; the outputs apart by
    the rounding of one pair's down projection at the most (the grouped
    form rounds each pair to the model's dtype before the float32 sum, as
    the dense one does)."""
    kw = dict(kw)
    data = _inputs(jnp.dtype(dtype), tokens)
    k = 2 if kw.get('first_expert') else 3
    if kw.pop('bias', False):
        kw['select_bias'] = data['bias']
    if kw.pop('counted', False):
        kw['counted'] = data['counted']
    banks = [data[n] for n in ('gate', 'up', 'down')]

    def call():
        if layer is None:
            return moe.routed_experts(
                data['x'], data['router'], *(b[0] for b in banks), k, **kw
            )
        how, index = layer
        fn = jax.jit(
            lambda x, li: moe.routed_experts(
                x, data['router'], *banks, k, layer=li, **kw
            ),
            static_argnums=(1,) if how == 'static' else (),
        )
        return fn(data['x'], index)

    (want, want_pairs), (got, got_pairs) = _both_forms(monkeypatch, call)
    assert got.dtype == want.dtype and got.shape == (tokens, H)
    np.testing.assert_array_equal(got_pairs, want_pairs)
    counted = int(data['counted'].sum()) if 'counted' in kw else tokens
    assert int(got_pairs[0]) == counted * k
    want, got = want.astype(np.float32), got.astype(np.float32)
    # One pair's output rounded once: an ulp of the largest value around.
    ulp = 2.0 ** -8 if dtype == 'bfloat16' else 2.0 ** -20
    assert np.abs(got - want).max() <= 2 * ulp * max(1.0, np.abs(want).max())
    if kw.get('first_expert'):
        # Tokens whose whole top-k is held elsewhere add exactly nothing.
        nothing = ~np.abs(want).any(axis=-1)
        assert 0 < int(got_pairs[1]) < int(got_pairs[0])
        if tokens >= 16:
            assert nothing.any()
        assert not np.abs(got[nothing]).any()


def test_dense_form_gates_match_the_router(monkeypatch):
    """The dense form's ``[T, E_held]`` gates are the k kept scores over
    their sum in the columns of the held experts and zero elsewhere: each
    expert made the constant one-hot of itself, the output is the gates."""
    rng = np.random.default_rng(5)
    first, held, k = 4, 8, 3
    h = jnp.asarray(rng.normal(size=(40, H)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(H, ROUTED)) * 0.3, jnp.float32)
    reads_ones = jnp.zeros((held, H + 1, 1)).at[:, -1].set(1.0)
    down = jnp.eye(held)[:, None, :] / float(jax.nn.silu(1.0))
    hx = jnp.concatenate([h, jnp.ones((40, 1))], -1)
    router1 = jnp.concatenate([router, jnp.zeros((1, ROUTED))], axis=0)
    monkeypatch.setattr(moe, 'expert_form', lambda *shape: 'dense')
    got, _ = moe.routed_experts(
        hx, router1, reads_ones, reads_ones, down, k, first_expert=first,
        scoring='sigmoid', norm_eps=1e-6,
    )
    s = 1.0 / (1.0 + np.exp(-np.asarray(h @ router, np.float64)))
    kept = np.argsort(-s, axis=-1)[:, :k]
    want = np.zeros_like(s)
    top = np.take_along_axis(s, kept, -1)
    np.put_along_axis(want, kept, top / (top.sum(-1, keepdims=True) + 1e-6), -1)
    np.testing.assert_allclose(
        np.asarray(got), want[:, first:first + held], atol=1e-6
    )


# ---- the rule, as a table of the benchmark's program shapes ----

FAMILIES = {
    'granite-4.0-h-small': (granite_hybrid, 'GraniteHybridConfig'),
    'laguna-xs.2': (laguna, 'LagunaConfig'),
    'kanana-2-30b-a3b': (deepseek_v3, 'DeepseekV3Config'),
    'lfm2-8b-a1b': (lfm2, 'Lfm2MoeConfig'),
    'mistral7b': (mistral, 'MistralConfig'),
}


def _cell_shapes(config):
    """``(rows of the decode window, k, (E_held, E_routed, H, I) or None)``
    of a benchmark configuration, from the program's own tree."""
    hf = json.loads((ROOT / f'benchmarks/configs/{config}.json').read_text())
    module, cls = FAMILIES[config]
    cfg = getattr(module, cls).from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: module.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    return (
        hf['engine']['max_num_seqs'], getattr(cfg, 'experts_per_token', None),
        moe.bank_widths(shapes),
    )


# The decode window of each cell and its (512, 4) chunk-prefill program.
RULE_TABLE = [
    ('granite-4.0-h-small', 'window', (96, 10, 36, 72, 4096, 768), 'dense'),
    ('granite-4.0-h-small', 'prefill', (2048, 10, 36, 72, 4096, 768), 'grouped'),
    ('lfm2-8b-a1b', 'window', (96, 4, 16, 32, 2048, 1792), 'dense'),
    ('lfm2-8b-a1b', 'prefill', (2048, 4, 16, 32, 2048, 1792), 'grouped'),
    ('kanana-2-30b-a3b', 'window', (48, 6, 32, 128, 2048, 768), 'dense'),
    ('kanana-2-30b-a3b', 'prefill', (2048, 6, 32, 128, 2048, 768), 'grouped'),
    ('laguna-xs.2', 'window', (48, 8, 64, 256, 2048, 512), 'dense'),
    ('laguna-xs.2', 'prefill', (2048, 8, 64, 256, 2048, 512), 'grouped'),
    ('mistral7b', 'window', None, None),
    ('mistral7b', 'prefill', None, None),
]


@pytest.mark.parametrize(
    'config, program, shape, form', RULE_TABLE,
    ids=[f'{c}.{p}' for c, p, _, _ in RULE_TABLE],
)
def test_rule_over_the_cells_program_shapes(config, program, shape, form):
    """A changed constant of the rule shows here: the form each cell's
    decode window and ``(512, 4)`` prefill program takes, with the shapes
    read from the cell's own configuration."""
    rows, k, widths = _cell_shapes(config)
    if shape is None:  # no routed experts: nothing to choose
        assert widths is None
        return
    tokens = rows if program == 'window' else 512 * 4
    assert (tokens, k, *widths) == shape
    assert moe.expert_form(*shape) == form


@pytest.mark.parametrize('tokens, k, routed, form', [
    (16, 10, 72, 'dense'),    # a chunk tail: 16 rows, 0.91 of experts hit
    (16, 8, 256, 'grouped'),  # 16 rows over 256 experts: 0.40 hit
    (120, 4, 32, 'dense'),    # half the stream time
    (128, 4, 32, 'grouped'),  # a whole 128-row tile: XLA turns the stack over
    (256, 4, 32, 'grouped'),  # the arithmetic over the stream time
    (512, 4, 32, 'grouped'),
    (1, 4, 32, 'grouped'),    # one row reaches 4 experts of 32
])
def test_rule_at_rows_no_cell_runs(tokens, k, routed, form):
    """Shapes the engine can run and the cells do not (chunk tails, mixed
    and speculative windows) fall under the same rule by their rows."""
    assert moe.expert_form(tokens, k, routed // 2, routed, 2048, 768) == form


def test_rule_ignores_the_widths():
    """Arithmetic and stream both grow with ``E_held * H * I``: rows, k and
    the router's width alone decide."""
    forms = {
        moe.expert_form(96, 4, held, 32, hidden, width)
        for held in (8, 16, 32) for hidden in (512, 4096)
        for width in (256, 1792)
    }
    assert forms == {'dense'}


# ---- the engine's account ----

def test_engine_says_which_form_each_program_took(monkeypatch):
    from lfm2_toy import make_engine, prompt

    _, _, engine = make_engine()
    forms = engine.telemetry['moe_form']
    # 4 slots, 3 of 8 experts a token: at 4 rows 0.85 of the experts are
    # expected to hold a pair; 4 rows of the 96-token bucket are past the
    # rows the arithmetic hides under.
    assert forms['decode(4)'] == 'dense'
    assert forms['prefill(16, 1)'] == 'dense'
    assert forms['prefill(96, 4)'] == 'grouped'
    assert forms == {  # every key's rows through the rule: 3 of 8, all held
        key: moe.expert_form(
            int(np.prod([int(n) for n in re.findall(r'\d+', key)])),
            3, 8, 8, 64, 24,
        )
        for key in forms
    }
    # the grouped programs and no other, each with what runs its matmuls:
    # ``ragged_dot`` on the tests' backend, the kernel's tiles on a TPU
    tiles = engine.telemetry['moe_grouped_tiles']
    assert set(tiles) == {k for k, form in forms.items() if form == 'grouped'}
    assert tiles['prefill(96, 4)'] == 'xla'
    monkeypatch.setattr(moe, 'grouped_backend', lambda: 'pallas')
    assert moe.grouped_tiles(96 * 4, 3, 64, 24) == (128, 24, 64)
    before = engine.flight.total_recorded
    engine.generate_ids(
        [prompt(np.random.default_rng(1), 20)],
        SamplingParams(temperature=0.0, max_tokens=6),
    )
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    windows = [r for r in records if r['kind'] == 'decode']
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert windows and all(r['moe_form'] == 'dense' for r in windows)
    assert prefills and all(
        r['moe_form'] in ('dense', 'grouped') for r in prefills
    )


def test_engine_without_routed_experts_says_nothing():
    """``mistral7b``'s family: no key in the telemetry, no field on a
    record."""
    from distllm_tpu.observability.flight import get_flight_recorder
    from serving_smoke import build_engine

    before = get_flight_recorder().total_recorded  # one ring a process
    engine = build_engine(warm=False)
    try:
        assert 'moe_form' not in engine.telemetry
        assert 'moe_grouped_tiles' not in engine.telemetry
        records = engine.flight.snapshot()[
            before - engine.flight.total_recorded:
        ]
        assert any(r['kind'] == 'decode' for r in records)
        assert not any('moe_form' in r for r in records)
    finally:
        engine.shutdown()


# ---- the activation and the ranking as a step of its own (PR 52) ----
def _loop_over_experts(data, k, first_expert, activation, layer=None):
    """A plain loop over the held experts in float64: a token's k largest
    router logits, softmax over them, each held expert's
    ``(act(x G) * (x U)) D`` times its gate."""
    act = {
        'relu': lambda g: np.maximum(g, 0.0),
        'silu': lambda g: g / (1.0 + np.exp(-g)),
    }[activation]
    x = np.asarray(data['x'], np.float64)
    logits = x @ np.asarray(data['router'], np.float64)
    kept = np.argsort(-logits, axis=-1)[:, :k]
    top = np.take_along_axis(logits, kept, -1)
    gates = np.exp(top - top.max(-1, keepdims=True))
    gates /= gates.sum(-1, keepdims=True)
    banks = [np.asarray(data[n], np.float64) for n in ('gate', 'up', 'down')]
    if layer is not None:
        banks = [b[layer] for b in banks]
    else:
        banks = [b[0] for b in banks]
    out = np.zeros_like(x)
    for e in range(HELD):
        g_e = np.where(kept == first_expert + e, gates, 0.0).sum(-1)
        y = (act(x @ banks[0][e]) * (x @ banks[1][e])) @ banks[2][e]
        out += g_e[:, None] * y
    return out


@pytest.mark.parametrize('activation', ['relu', 'silu'])
@pytest.mark.parametrize('form, backend, layer', [
    ('dense', 'xla', None), ('dense', 'xla', 2), ('grouped', 'xla', None),
    ('grouped', 'xla', 1), ('grouped', 'interpret', None),
    ('grouped', 'interpret', 2),
], ids=['dense', 'dense-layer', 'ragged_dot', 'ragged_dot-layer', 'kernel',
        'kernel-layer'])
def test_activation_in_every_form_is_the_loop_over_experts(
    monkeypatch, activation, form, backend, layer
):
    """``activation`` reaches the dense einsum, ``ragged_dot``'s twin and
    the kernel's epilogue (the Pallas interpreter)."""
    data = _inputs(jnp.float32, 24)
    monkeypatch.setattr(moe, 'expert_form', lambda *shape: form)
    monkeypatch.setattr(moe, 'grouped_backend', lambda: backend)
    banks = [data[n] if layer is not None else data[n][0] for n in ('gate', 'up', 'down')]
    out, pairs = moe.routed_experts(
        data['x'], data['router'], *banks, 3, first_expert=4, layer=layer,
        activation=activation,
    )
    want = _loop_over_experts(data, 3, 4, activation, layer)
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)
    assert int(pairs[0]) == 24 * 3
    other = _loop_over_experts(
        data, 3, 4, 'silu' if activation == 'relu' else 'relu', layer
    )
    assert np.abs(np.asarray(out) - other).max() > 0.05  # and not the other


def test_an_unknown_activation_is_refused_by_name():
    data = _inputs(jnp.float32, 8)
    with pytest.raises(ValueError, match="activation must be one of.*'gelu'"):
        moe.routed_experts(
            data['x'], data['router'], data['gate'][0], data['up'][0],
            data['down'][0], 2, activation='gelu',
        )


@pytest.mark.parametrize('form', ['dense', 'grouped'])
@pytest.mark.parametrize(
    'tokens, kw, layer', [c[1:] for c in CASES], ids=[c[0] for c in CASES]
)
def test_ranking_alone_is_what_routed_experts_made_inline(
    monkeypatch, form, tokens, kw, layer
):
    """``rank_experts`` on the same input, handed back as ``ranking=``, gives
    the bits ``routed_experts`` gives when it ranks itself (the result the
    function had inline before the ranking was taken apart: the table of
    ``test_dense_form_is_the_grouped_form`` holds that one to the other
    form); handed a ranking made from ANOTHER tensor it follows that one."""
    kw = dict(kw)
    data = _inputs(jnp.float32, tokens)
    k = 2 if kw.get('first_expert') else 3
    if kw.pop('bias', False):
        kw['select_bias'] = data['bias']
    if kw.pop('counted', False):
        kw['counted'] = data['counted']
    banks = [data[n][0] for n in ('gate', 'up', 'down')]
    if layer is not None:
        banks = [data[n] for n in ('gate', 'up', 'down')]
        kw['layer'] = layer[1] if layer[0] == 'static' else jnp.int32(layer[1])
    monkeypatch.setattr(moe, 'expert_form', lambda *shape: form)
    inline = moe.routed_experts(data['x'], data['router'], *banks, k, **kw)
    rank_kw = {n: v for n, v in kw.items() if n != 'counted'}
    ranking = moe.rank_experts(
        data['x'], data['router'], k, banks[0].shape, **rank_kw
    )
    assert (ranking.order is None) == (form == 'dense')
    handed = moe.routed_experts(
        data['x'], None, *banks, k, ranking=ranking, **kw
    )
    for got, want in zip(handed, inline):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # Ranked from another tensor, the experts still read ``x``.
    other = moe.rank_experts(
        data['x'][::-1], data['router'], k, banks[0].shape, **rank_kw
    )
    elsewhere = moe.routed_experts(
        data['x'], None, *banks, k, ranking=other, **kw
    )
    assert np.abs(np.asarray(elsewhere[0]) - np.asarray(inline[0])).max() > 1e-3
