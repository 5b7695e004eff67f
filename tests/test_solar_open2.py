"""``solar_open2`` (models/solar_open2.py): Kimi-delta layers beside a gated
attention layer, routed and shared experts in every layer, held against the
plain reference (``benchmarks/reference_solar_open2.py``) on LOGITS at toy
widths (``solar_open2_toy``: G K K K G, 3 KDA heads of 8, 4 queries a KV
head, 8 experts of which 2 are chosen), float32: the dense forward, prefill
then decode through pool and state, the span form of ``ops/kda.py`` against
the step-by-step recurrence, each mechanism the benchmark's check has to
catch, the expert shares against the uncut layer, and the config."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_solar_open2 as ref
from distllm_tpu.models import decoder_family, solar_open2
from distllm_tpu.ops import kda
from solar_open2_toy import (
    load_probe,
    paged_logits,
    prompt,
    reference_logits,
    spread,
    tiny,
    tiny_hf,
)

ROOT = Path(__file__).resolve().parents[1]
CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')
TOLERANCE = 1e-3


def _rows(seed, sizes):
    rng = np.random.default_rng(seed)
    return [(prompt(rng, total), n) for n, total in sizes]


def _at(row):
    tokens, n_prompt = row
    return tokens, n_prompt - 1


def _assert_rows_match(hf, cfg, params, rows, kv_state):
    """What the pools hold afterwards, EVERY layer of each kind: in a row's
    slot the matrix state and the convolutions' last rows after every token,
    in its pages the reference's K and V."""
    k, v, state = kv_state
    lanes = cfg.num_kv_heads * cfg.head_size
    width = (k.shape[1] - 1) // len(rows)
    for i, (tokens, _) in enumerate(rows):
        _, held = ref.forward(params, hf, np.asarray(tokens)[None], [[0]])
        assert len(held[0]['kda']) == cfg.count('kda')
        for xi, (want_state, want_conv) in enumerate(held[0]['kda']):
            assert ref.content_error(state['kda'][xi][i], want_state) < 1e-5
            assert ref.content_error(state['conv'][xi][i], want_conv) < 1e-5
        pages = slice(1 + i * width, 1 + (i + 1) * width)
        for xi, (want_k, want_v) in enumerate(held[0]['gqa']):
            for pool, want in ((k, want_k), (v, want_v)):
                got = np.asarray(pool[xi, pages]).reshape(-1, lanes)[:len(tokens)]
                assert ref.content_error(got, want.reshape(len(tokens), lanes)) < 1e-5


def test_dense_forward_is_the_references():
    hf, cfg, params = tiny(3)
    ids = np.asarray([prompt(np.random.default_rng(3), 19)], np.int32)
    hidden = solar_open2.apply(params, cfg, jnp.asarray(ids), jnp.ones_like(ids))
    got = solar_open2.logits(params, cfg, hidden)[0]
    want = ref.solar_open2_logits(params, hf, ids, np.arange(19)[None])[0]
    assert spread(got, want) < TOLERANCE


# One row alone: prompts whose last span brings 1, 2 and 3 tokens (the
# convolutions' state then keeps rows of the span before), one that ends a
# span of 8, one over several spans; then decode.
@pytest.mark.parametrize('n_prompt, total', [
    (1, 5), (2, 6), (3, 6), (8, 11), (9, 12), (11, 14), (21, 30),
])
def test_paged_logits_are_the_references(n_prompt, total):
    hf, cfg, params = tiny(0)
    rows = _rows(n_prompt, [(n_prompt, total)])
    (got,), kv_state = paged_logits(cfg, params, rows)
    assert got.shape == (total - n_prompt + 1, hf['vocab_size'])
    assert spread(got, reference_logits(params, hf, *_at(rows[0]))) < TOLERANCE
    _assert_rows_match(hf, cfg, params, rows, kv_state)


# Rows of unequal tails in one dispatch: in the second round one row brings
# 8 tokens, one 1, one 2 and two are pad rows. A stale state pool under it
# is a slot reused after a longer holder: the first span starts from zeros.
@pytest.mark.parametrize('stale', [None, 7.0])
def test_rows_of_unequal_tails_share_a_dispatch(stale):
    hf, cfg, params = tiny(1)
    rows = _rows(7, [(21, 26), (9, 12), (10, 11), (1, 4), (5, 9)])
    got, kv_state = paged_logits(cfg, params, rows, stale=stale)
    for logits, row in zip(got, rows):
        assert spread(logits, reference_logits(params, hf, *_at(row))) < TOLERANCE
    _assert_rows_match(hf, cfg, params, rows, kv_state)


def test_spans_of_another_size_carry_the_same_state():
    hf, cfg, params = tiny(2)
    rows = _rows(5, [(45, 48), (6, 9)])
    # spans shorter than a chunk of the span form (``kda.CHUNK`` 32), and
    # spans of one chunk and a tail of another
    for chunk in (5, 40):
        got, kv_state = paged_logits(cfg, params, rows, chunk=chunk)
        for logits, row in zip(got, rows):
            assert spread(logits, reference_logits(params, hf, *_at(row))) < TOLERANCE
        _assert_rows_match(hf, cfg, params, rows, kv_state)


# ------------------------------------------------- the span form of the rule
def _recurrence_inputs(seed=0, b=2, s=37, h=3, d_k=8, d_v=6):
    """Unit keys, the strongest decay of the initialisation in head 0 (0.2
    a step: A 16, dt 0.1) beside the weakest, beta at 1.99 in the last head, a
    state to start from, and a second row that counts 20 positions."""
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(b, s, h, d_k)).astype(np.float32)
    k = rng.normal(size=(b, s, h, d_k)).astype(np.float32)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.normal(size=(b, s, h, d_v)).astype(np.float32)
    g = -np.exp(
        rng.uniform(np.log(1e-3), np.log(1.6), size=(b, s, h, d_k))
    ).astype(np.float32)
    g[:, :, 0] = -1.6
    beta = rng.uniform(0, 2, size=(b, s, h)).astype(np.float32)
    beta[:, :, -1] = 1.99
    valid = np.arange(s)[None] < np.asarray([s, 20][:b])[:, None]
    g = np.where(valid[..., None, None], g, 0.0)
    beta = np.where(valid[..., None], beta, 0.0)
    state = rng.normal(size=(b, h, d_k, d_v)).astype(np.float32)
    return (q, k, v, g, beta), state, valid


def _step_by_step(inputs, state):
    outs = []
    for t in range(inputs[0].shape[1]):
        o, state = kda.kda_step(*(x[:, t] for x in inputs), state)
        outs.append(o)
    return np.stack(outs, axis=1), np.asarray(state)


def test_a_step_is_the_written_recurrence():
    (q, k, v, g, beta), state, _ = _recurrence_inputs(b=1, s=1, h=1)
    o, after = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
    k0, b0 = k[0, 0, 0].astype(np.float64), float(beta[0, 0, 0])
    decayed = np.exp(g[0, 0, 0].astype(np.float64))[:, None] * state[0, 0]
    want = (
        (np.eye(len(k0)) - b0 * np.outer(k0, k0)) @ decayed
        + b0 * np.outer(k0, v[0, 0, 0])
    )
    np.testing.assert_allclose(after[0, 0], want, atol=1e-5)
    np.testing.assert_allclose(o[0, 0], want.T @ q[0, 0, 0], atol=1e-5)


@pytest.mark.parametrize('chunk', [1, 4, 8, 16, 64])
def test_span_form_is_the_recurrence_at_any_chunk(chunk):
    inputs, state, valid = _recurrence_inputs()
    want_o, want_state = _step_by_step(inputs, state)
    o, after = kda.kda_span(*inputs, state, chunk)
    mask = valid[..., None, None]
    assert np.abs(np.where(mask, np.asarray(o) - want_o, 0.0)).max() < 2e-5
    assert np.abs(np.asarray(after) - want_state).max() < 1e-5


@pytest.mark.parametrize('cuts', [(5, 6, 30), (1, 2, 3, 36), (32,)])
def test_span_form_carries_its_state_over_uneven_spans(cuts):
    inputs, state, valid = _recurrence_inputs(seed=1)
    want_o, want_state = _step_by_step(inputs, state)
    edges = (0, *cuts, inputs[0].shape[1])
    outs = []
    for lo, hi in zip(edges, edges[1:]):
        o, state = kda.kda_span(*(x[:, lo:hi] for x in inputs), state, 8)
        outs.append(np.asarray(o))
    mask = valid[..., None, None]
    assert np.abs(np.where(mask, np.concatenate(outs, 1) - want_o, 0.0)).max() < 2e-5
    assert np.abs(np.asarray(state) - want_state).max() < 1e-5


def test_a_long_chunk_of_the_strongest_decay_stays_finite():
    """64 steps at 0.2 a step: ``exp(G_t) exp(-G_j)`` would be ``exp(103)``
    in its second factor; the difference is formed first."""
    inputs, state, _ = _recurrence_inputs(seed=2, s=64)
    inputs = (*inputs[:3], np.full_like(inputs[3], -1.6), inputs[4])
    want_o, want_state = _step_by_step(inputs, state)
    o, after = kda.kda_span(*inputs, state, 64)
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.asarray(o) - want_o).max() < 2e-5
    assert np.abs(np.asarray(after) - want_state).max() < 1e-5


# ------------------------------------------- what the cell's check must catch
def _no_attn_gate(attn, u, lp, cfg, out=solar_open2._attn_out):
    return 2.0 * out(attn, jnp.zeros_like(u), lp, cfg)  # sigmoid(0) = 1/2


# The probe's wrong programs (what the cell's limits have to catch on the
# chip), and the attention layer's gate.
@pytest.mark.parametrize('name, wrong', [
    ('_kda_inputs', '_beta_half'), ('_kda_inputs', '_scalar_decay'),
    ('routed_experts', '_softmax_scoring'), ('_attn_out', _no_attn_gate),
])
def test_each_mechanism_moves_the_logits(name, wrong, monkeypatch):
    hf, cfg, params = tiny(4)
    rows = _rows(11, [(13, 17)])
    if isinstance(wrong, str):
        wrong = load_probe()[wrong]
    monkeypatch.setattr(solar_open2, name, wrong)
    (got,), _ = paged_logits(cfg, params, rows)
    assert spread(got, reference_logits(params, hf, *_at(rows[0]))) > 30 * TOLERANCE


# ---------------------------------------------------------- the expert shares
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """A deployment splits the 16 routed experts over 8 chips, 2 a chip;
    every chip computes the shared expert alike and it is counted once.
    The program's layer on each share, the shared expert taken off all but
    one, adds up to the reference's layer with every expert held."""
    hf, cfg, params = tiny(5, n_routed_experts=16, num_experts_per_tok=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (9, hf['hidden_size']))
    li = 1
    want = ref.moe_block(params['moe'], hf, x, li) - x  # uncut: all 16 held
    mp = solar_open2.common.layer_at(params['moe'], li, skip=('gate', 'up', 'down'))
    n = solar_open2._norm(x, mp['mlp_ln']['scale'], cfg)
    shared = solar_open2.common.swiglu(
        n, mp['shared_gate']['kernel'], mp['shared_up']['kernel'],
        mp['shared_down']['kernel'],
    )
    total, held_pairs = jnp.zeros_like(x), 0
    for share in range(8):
        lo = 2 * share
        share_cfg = cfg.model_copy(
            update={'num_local_experts': 2, 'first_local_expert': lo}
        )
        banks = {
            name: {'kernel': params['moe'][name]['kernel'][:, lo:lo + 2]}
            for name in ('gate', 'up', 'down')
        }
        out, pairs = solar_open2._mlp(
            n, mp, share_cfg, jnp.ones((9,), bool), banks, jnp.int32(li)
        )
        total = total + out
        held_pairs += int(pairs[1])
        assert int(pairs[0]) == 9 * 4
    assert held_pairs == 9 * 4  # every routed pair is held by exactly one share
    assert spread(total - 7.0 * shared, want) < TOLERANCE
    assert spread(shared, want) > 0.1  # the routed experts are not a rounding


def test_a_share_computes_nothing_for_the_absent_experts():
    """The reference given a share is the program given that share: both
    leave the experts of other chips out, neither stands in for them."""
    hf, cfg, params = tiny(6, n_routed_experts=4, num_routed_experts=16,
                           first_local_expert=4, num_experts_per_tok=4)
    assert cfg.num_experts == 16 and cfg.num_local_experts == 4
    rows = _rows(2, [(9, 12)])
    (got,), _ = paged_logits(cfg, params, rows)
    assert spread(got, reference_logits(params, hf, *_at(rows[0]))) < TOLERANCE


# ------------------------------------------------------------------ the config
def test_config_reads_the_catalog_rows_keys():
    row = next(
        json.loads(line) for line in CATALOG.read_text().splitlines()
        if '"Solar-Open2-250B"' in line
    ) if CATALOG.exists() else None
    file = json.loads(
        (ROOT / 'benchmarks/configs/solar-open2-250b.json').read_text()
    )
    cut_keys = {
        'num_hidden_layers': 4, 'gqa_layers': [0], 'n_routed_experts': 40,
        'vocab_size': 24576,
    }
    assert sorted(file['reduced']) == sorted(cut_keys)
    if row:  # every published key but the reduced ones, unchanged in the file
        assert {k: file[k] for k in row['config']} == {**row['config'], **cut_keys}
        assert file['source'] == row['source_url']
        published = row['config']
    else:
        published = {
            **file, 'num_hidden_layers': 48, 'n_routed_experts': 320,
            'vocab_size': 196608, 'gqa_layers': list(range(0, 48, 4)),
            'num_routed_experts': 320,
        }
    cfg = solar_open2.SolarOpen2Config.from_hf_config(published)
    assert (cfg.num_layers, cfg.count('gqa'), cfg.count('kda')) == (48, 12, 36)
    assert [m for m, _ in cfg.layer_indices()[:5]] == ['gqa', 'kda', 'kda', 'kda', 'gqa']
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_size) == (64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv) == (64, 128, 4)
    assert cfg.kda_neg_eigval and cfg.num_experts == cfg.num_local_experts == 320
    cut = solar_open2.SolarOpen2Config.from_hf_config(file)
    assert (cut.num_experts, cut.num_local_experts, cut.first_local_expert) == (320, 40, 0)
    spec = cut.cache_spec()
    assert [(g.name, g.num_layers, g.window, g.row) for g in spec.paged] == [
        ('kv', 1, None, None)
    ]
    assert not spec.dense_prefill and spec.program_prefix == 'solar_open2_'
    kinds = {
        name: {(x.shape, x.dtype) for x in leaves}
        for name, leaves in spec.state.items()
    }
    assert len(spec.state['kda']) == len(spec.state['conv']) == 3
    assert kinds == {
        'kda': {((64, 128, 128), jnp.dtype('float32'))},
        'conv': {((3, 24576), jnp.dtype('bfloat16'))},
    }
    shapes = jax.eval_shape(
        lambda: solar_open2.init_on_device(jax.random.PRNGKey(0), cut)
    )
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    from benchmarks import solar_open2_bytes

    assert held == solar_open2_bytes.held_params(file)
    assert shapes['moe']['gate']['kernel'].shape == (4, 40, 4096, 1280)
    assert shapes['moe']['router']['kernel'].shape == (4, 4096, 320)


@pytest.mark.parametrize('over, key', [
    ({'kda_use_full_proj': True}, 'kda_use_full_proj'),
    ({'use_rope': True}, 'use_rope'),
    ({'use_rope': True, 'partial_rotary_factor': 0.5}, 'partial_rotary_factor'),
    ({'first_k_dense_replace': 1}, 'first_k_dense_replace'),
    ({'n_shared_experts': 2}, 'n_shared_experts'),
    ({'n_shared_experts': 0}, 'n_shared_experts'),
    ({'use_gqa_gate': False}, 'use_gqa_gate'),
    ({'norm_topk_prob': False}, 'norm_topk_prob'),
    ({'tie_word_embeddings': True}, 'tie_word_embeddings'),
    ({'linear_attn_config': {'short_conv_kernel_size': 4, 'head_dim': 8,
                             'num_heads': 3, 'num_kv_heads': 1}},
     'linear_attn_config'),
])
def test_config_refuses_what_is_not_implemented(over, key):
    with pytest.raises(ValueError, match=f'solar_open2: {key}='):
        solar_open2.SolarOpen2Config.from_hf_config(tiny_hf(**over))


def test_decoder_family_has_the_row():
    cls, module = decoder_family('solar_open2')
    assert cls is solar_open2.SolarOpen2Config and module is solar_open2
    with pytest.raises(NotImplementedError, match='solar_open2: no converter'):
        solar_open2.params_from_hf({}, cls())


def test_beta_without_negative_eigenvalues_stays_under_one():
    hf, cfg, params = tiny(7, kda_allow_neg_eigval=False)
    assert not cfg.kda_neg_eigval
    rows = _rows(3, [(9, 12)])
    (got,), _ = paged_logits(cfg, params, rows)
    assert spread(got, reference_logits(params, hf, *_at(rows[0]))) < TOLERANCE
