"""``falcon_h1`` (models/falcon_h1.py): a Mamba-2 mixer AND a paged attention
mixer in every layer, held against the plain reference
(``benchmarks/reference_falcon_h1.py``) on LOGITS at toy widths
(``falcon_h1_toy``: 2 B/C groups, state 16 beside heads of 6 and 8, 5 queries
a KV head, an inner width of 24 under a hidden size of 40), float32: prefill
then decode through pool and state, the chunked scan with groups against the
step-by-step recurrence, each multiplier and the per-group norm, granite's
programs through the generalised Mamba-2 functions, the config, and the
kernel at 5 queries a KV head."""

import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_falcon_h1 as ref
from distllm_tpu.models import decoder_family, falcon_h1, granite_hybrid
from falcon_h1_toy import paged_logits, prompt, reference_logits, spread, tiny, tiny_hf

ROOT = Path(__file__).resolve().parents[1]
CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')
TOLERANCE = 1e-3


def _rows(seed, sizes):
    rng = np.random.default_rng(seed)
    return [(prompt(rng, total), n) for n, total in sizes]


def _assert_rows_match(hf, cfg, params, rows, kv_state):
    """What the pools hold of LAYER 0 afterwards: in a row's slot the SSM
    state and the convolution's last rows after every token, in its pages
    the reference's K (after the multiplier and the rotation) and V."""
    k, v, state = kv_state
    lanes = cfg.num_kv_heads * cfg.head_size
    width = (k.shape[1] - 1) // len(rows)
    for i, (tokens, _) in enumerate(rows):
        _, held = ref.forward(params, hf, np.asarray(tokens)[None], [[0]])
        want_ssm, want_conv, want_k, want_v = held[0]
        assert ref.content_error(state['ssm'][0][i], want_ssm) < 1e-5
        assert ref.content_error(state['conv'][0][i], want_conv) < 1e-5
        pages = slice(1 + i * width, 1 + (i + 1) * width)
        for pool, want in ((k, want_k), (v, want_v)):
            got = np.asarray(pool[0, pages]).reshape(-1, lanes)[:len(tokens)]
            assert ref.content_error(got, want.reshape(len(tokens), lanes)) < 1e-5


# One row alone: prompts whose last span brings 1, 2 and 3 tokens (the
# convolution's state then keeps rows of the chunk before), one that ends a
# chunk of 8 (the chunked scan: mamba_chunk_size 8), one over several
# chunks; then decode.
@pytest.mark.parametrize('n_prompt, total', [
    (1, 5), (2, 6), (3, 6), (8, 11), (9, 12), (10, 12), (11, 14), (21, 30),
])
def test_paged_logits_are_the_references(n_prompt, total):
    hf, cfg, params = tiny(0)
    rows = _rows(n_prompt, [(n_prompt, total)])
    (got,), kv_state = paged_logits(cfg, params, rows)
    assert got.shape == (total - n_prompt + 1, hf['vocab_size'])
    assert spread(got, reference_logits(params, hf, *_at(rows[0]))) < TOLERANCE
    _assert_rows_match(hf, cfg, params, rows, kv_state)


def _at(row):
    tokens, n_prompt = row
    return tokens, n_prompt - 1


# Rows of unequal tails in one dispatch: in the second round one row brings
# 8 tokens, one 1, one 2 and two are pad rows. A stale state pool under it
# is a slot reused after a longer holder: the first span starts from zeros.
@pytest.mark.parametrize('backend, stale', [('xla', None), ('xla', 7.0)])
def test_rows_of_unequal_tails_share_a_dispatch(backend, stale):
    hf, cfg, params = tiny(1)
    rows = _rows(7, [(21, 26), (9, 12), (10, 11), (1, 4), (5, 9)])
    got, kv_state = paged_logits(cfg, params, rows, backend=backend, stale=stale)
    for logits, row in zip(got, rows):
        assert spread(logits, reference_logits(params, hf, *_at(row))) < TOLERANCE
    _assert_rows_match(hf, cfg, params, rows, kv_state)


def test_published_widths_of_a_head_go_through_the_kernel():
    """128-wide heads, 5 queries a KV head, through the Pallas interpreter
    in prefill spans and decode steps, pages and state in every layer."""
    hf, cfg, params = tiny(
        2, hidden_size=64, num_attention_heads=5, num_key_value_heads=1,
        head_dim=128, num_hidden_layers=2,
    )
    assert cfg.head_size == 128 and cfg.num_heads // cfg.num_kv_heads == 5
    rows = _rows(3, [(11, 14), (3, 6)])
    got, _ = paged_logits(cfg, params, rows, backend='interpret')
    for logits, row in zip(got, rows):
        assert spread(logits, reference_logits(params, hf, *_at(row))) < TOLERANCE


def test_dense_forward_is_the_references():
    hf, cfg, params = tiny(3)
    ids = np.asarray([prompt(np.random.default_rng(3), 19)], np.int32)
    hidden = falcon_h1.apply(params, cfg, jnp.asarray(ids), jnp.ones_like(ids))
    got = falcon_h1.logits(params, cfg, hidden)[0]
    want = ref.falcon_h1_logits(params, hf, ids, np.arange(19)[None])[0]
    assert spread(got, want) < TOLERANCE


# Every multiplier and the per-group norm: the program with the key's value
# dropped is told apart from the reference with it, by far more than the
# tolerance the right program is held to.
@pytest.mark.parametrize('dropped', [
    'embedding_multiplier', 'lm_head_multiplier', 'attention_in_multiplier',
    'attention_out_multiplier', 'key_multiplier', 'ssm_in_multiplier',
    'ssm_out_multiplier', 'ssm_multipliers', 'mlp_multipliers',
    'group_norm', 'groups',
])
def test_each_multiplier_and_the_group_norm_moves_the_logits(dropped, monkeypatch):
    hf, cfg, params = tiny(0)
    if dropped == 'group_norm':  # the gated norm over all channels at once
        out = granite_hybrid._mamba_out
        monkeypatch.setattr(
            granite_hybrid, '_mamba_out',
            lambda y, z, lp, cfg_, dtype: out(
                y, z, lp, cfg_.model_copy(update={'mamba_n_groups': 1}), dtype
            ),
        )
        wrong = cfg
    elif dropped == 'groups':  # every head reads group 0's B and C
        inputs = granite_hybrid._mamba_inputs

        def group0(lp, cfg_, window):
            x, b_in, c_in = inputs(lp, cfg_, window)
            first = lambda t: jnp.broadcast_to(t[..., :1, :], t.shape)  # noqa: E731
            return x, first(b_in), first(c_in)

        monkeypatch.setattr(granite_hybrid, '_mamba_inputs', group0)
        wrong = cfg
    else:
        one = {'ssm_multipliers': None, 'mlp_multipliers': (1.0, 1.0)}
        wrong = cfg.model_copy(update={dropped: one.get(dropped, 1.0)})
    rows = _rows(5, [(13, 16)])
    (got,), _ = paged_logits(wrong, params, rows)
    assert spread(got, reference_logits(params, hf, *_at(rows[0]))) > 0.02


# ------------------------------------------------------------ the chunked scan
def _steps(x, dt, a, b_in, c_in, ssm0):
    """The recurrence one step after the other, numpy float64."""
    bsz, s, h, p = x.shape
    g = b_in.shape[2]
    state = np.asarray(ssm0, np.float64).copy()
    y = np.zeros((bsz, s, h, p))
    for t in range(s):
        b_t = np.repeat(b_in[:, t], h // g, axis=1)  # [B, H, N]
        c_t = np.repeat(c_in[:, t], h // g, axis=1)
        state = (
            state * np.exp(dt[:, t] * a)[..., None, None]
            + (dt[:, t][..., None] * x[:, t])[..., None] * b_t[:, :, None, :]
        )
        y[:, t] = (state * c_t[:, :, None, :]).sum(-1)
    return y, state


@pytest.mark.parametrize('chunk', [4, 16])
@pytest.mark.parametrize('groups', [1, 2, 4])
def test_chunked_scan_with_groups_is_the_recurrence(chunk, groups):
    rng = np.random.default_rng(chunk + groups)
    bsz, s, h, p, n = 2, 13, 4, 6, 5
    x = rng.standard_normal((bsz, s, h, p))
    dt = rng.uniform(0.01, 0.5, (bsz, s, h))
    dt[1, 9:] = 0.0  # a row that ends early: the state passes through
    a = -rng.uniform(0.5, 4.0, (h,))
    b_in, c_in = (rng.standard_normal((bsz, s, groups, n)) for _ in range(2))
    ssm0 = rng.standard_normal((bsz, h, p, n))
    want_y, want_state = _steps(x, dt, a, b_in, c_in, ssm0)
    squeeze = (lambda t: t[:, :, 0]) if groups == 1 else (lambda t: t)
    y, state = granite_hybrid.ssd_chunked(
        *(jnp.asarray(t, jnp.float32) for t in (
            x, dt, a, squeeze(b_in), squeeze(c_in), ssm0
        )), chunk,
    )
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)


# ------------------------------------- granite through the generalised functions
# sha1 of the lowered text of granite's decode window and (512, 4) prefill
# program at its toy widths on the CPU, taken on the parent commit (733e4a1):
# one group and no multipliers trace the very operations they traced before.
# The prefill program's value moved with PR 43, on purpose: its routed
# experts' grouped form (``models/moe.py``, every backend) gathers a
# token's k rows back and gates and sums them in one pass, where
# ``acc2db91...`` held a float32 product, its gather and a sum.
_GRANITE_PARENT = {
    'window': '4d7b41350e84cf22b26500af3b9684cfcc5cac1b',
    'prefill': '355085aaa16ee0e3ed487b434b6c8aebd6030eae',
}


@pytest.mark.parametrize('program', ['window', 'prefill'])
def test_granite_lowers_to_the_parents_text(program):
    """The same program is the same numbers, bit for bit."""
    hf = json.loads((
        ROOT / 'benchmarks/tests/rehearsal_hybrid/configs/tiny-granite.json'
    ).read_text())
    cfg = granite_hybrid.GraniteHybridConfig.from_hf_config(hf)
    shapes = jax.eval_shape(
        lambda: granite_hybrid.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    sds = jax.ShapeDtypeStruct
    b, i32, f32 = 4, jnp.int32, jnp.float32
    state = jax.tree.map(lambda a: sds((b, *a.shape), a.dtype), cfg.state_spec())
    pools = sds(
        (cfg.num_paged_layers, 64, 16, cfg.num_kv_heads * cfg.head_size),
        jnp.dtype(cfg.dtype),
    )
    if program == 'window':
        text = jax.jit(
            lambda p, i, po, c, k, v, bt, sl, tmp, tp_, mp, tk, sd, st:
                granite_hybrid.decode_loop(
                    p, cfg, i, po, k, v, bt, c, sl, tmp, tp_, mp, tk, sd,
                    num_steps=8, attn_backend='xla', state=st,
                ),
            donate_argnums=(4, 5, 13),
        ).lower(
            shapes, sds((b,), i32), sds((b,), i32), sds((b,), i32), pools,
            pools, sds((b, 16), i32), sds((b,), i32), sds((b,), f32),
            sds((b,), f32), sds((b,), f32), sds((b,), i32),
            sds((b,), jnp.uint32), state,
        ).as_text()
    else:
        text = jax.jit(
            lambda p, ids, pos, k, v, bt, ctx, tails, st, slots:
                granite_hybrid.prefill_paged(
                    p, cfg, ids, pos, k, v, bt, ctx, tails, st, slots,
                    attn_backend='xla',
                ),
            donate_argnums=(3, 4, 8),
        ).lower(
            shapes, sds((4, 512), i32), sds((4, 512), i32), pools, pools,
            sds((4, 16), i32), sds((4,), i32), sds((4,), i32), state,
            sds((4,), i32),
        ).as_text()
    assert hashlib.sha1(text.encode()).hexdigest() == _GRANITE_PARENT[program]


# ------------------------------------------------------------------- config
def test_config_reads_the_catalog_rows_keys():
    row = next(
        json.loads(line) for line in CATALOG.read_text().splitlines()
        if '"Falcon-H1-34B-Instruct"' in line
    ) if CATALOG.exists() else None
    file = json.loads(
        (ROOT / 'benchmarks/configs/falcon-h1-34b.json').read_text()
    )
    published = row['config'] if row else {**file, 'num_hidden_layers': 72}
    if row:  # every published key but the reduced one, unchanged in the file
        assert {k: file[k] for k in published} == {
            **published, 'num_hidden_layers': 6
        }
        assert file['source'] == row['source_url']
    cfg = falcon_h1.FalconH1Config.from_hf_config(published)
    assert cfg.num_layers == cfg.num_paged_layers == 72
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_size) == (20, 4, 128)
    assert (cfg.d_inner, cfg.conv_dim) == (4096, 4096 + 2 * 2 * 256)
    assert cfg.d_inner != 2 * cfg.hidden_size  # not mamba_expand x hidden
    assert cfg.ssm_multipliers == tuple(published['ssm_multipliers'])
    assert cfg.key_multiplier == published['key_multiplier']
    cut = falcon_h1.FalconH1Config.from_hf_config(file)
    spec = cut.cache_spec()
    assert [(g.name, g.num_layers, g.window, g.row) for g in spec.paged] == [
        ('kv', 6, None, None)
    ]
    assert not spec.dense_prefill and spec.program_prefix == 'falcon_h1_'
    kinds = {
        name: {(x.shape, x.dtype) for x in leaves}
        for name, leaves in spec.state.items()
    }
    assert len(spec.state['ssm']) == len(spec.state['conv']) == 6
    assert kinds == {
        'ssm': {((32, 128, 256), jnp.dtype('float32'))},
        'conv': {((3, 5120), jnp.dtype('bfloat16'))},
    }
    shapes = jax.eval_shape(
        lambda: falcon_h1.init_on_device(jax.random.PRNGKey(0), cut)
    )
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert held == 6 * 430_120_032 + 2 * 1_336_934_400 + 5_120
    assert shapes['layers']['in_proj']['kernel'].shape == (6, 5120, 9248)


@pytest.mark.parametrize('key, value', [
    ('mamba_d_ssm', 32), ('mamba_n_groups', 3), ('attention_bias', True),
    ('mamba_proj_bias', True), ('mlp_bias', True), ('mamba_conv_bias', False),
    ('mamba_rms_norm', False), ('mamba_norm_before_gate', True),
    ('rope_scaling', {'rope_type': 'yarn'}), ('tie_word_embeddings', True),
    ('attn_layer_indices', [0]), ('hidden_act', 'gelu'),
])
def test_config_refuses_what_is_not_implemented(key, value):
    with pytest.raises(ValueError, match=f'falcon_h1: {key}='):
        falcon_h1.FalconH1Config.from_hf_config(tiny_hf(**{key: value}))


def test_decoder_family_has_the_row():
    cls, module = decoder_family('falcon_h1')
    assert cls is falcon_h1.FalconH1Config and module is falcon_h1
    with pytest.raises(NotImplementedError, match='falcon_h1: no converter'):
        falcon_h1.params_from_hf({}, cls())


def test_rotation_at_theta_1e11_keeps_its_slowest_pair():
    """The smallest frequency, 1e11^(-126/128) = 1.5e-11 a token, is a
    float32 number and not zero, and the tables the program rotates with
    are the reference's float64 angles at 260 k positions."""
    from distllm_tpu.models import common

    cos, sin = common.rope_frequencies(128, 262144, 1e11)
    assert 0 < sin[1, -1] < 2e-11 and sin.dtype == np.float32
    want_cos, want_sin = ref.rope_angles(1e11, 128, [262143])
    np.testing.assert_allclose(cos[-1], want_cos[0], atol=2e-6)
    np.testing.assert_allclose(sin[-1], want_sin[0], atol=2e-6)


def test_roofline_leaves_the_looked_up_embedding_out():
    """An untied head: a token reaches all of a layer and the head, and a
    ROW of the embedding."""
    from distllm_tpu.observability.roofline import CostModel

    _, cfg, params = tiny(0)
    embed = params['embed'].size
    everything = sum(x.size for x in jax.tree.leaves(params))
    model = CostModel.from_params(params, decode_steps=4)
    assert model.n_params == everything - embed
    assert model.weight_bytes == 4 * (everything - embed)


# ------------------------------------------------------------------- kernel
@pytest.mark.parametrize('span, kv_heads, pages_per_chunk', [
    (1, 4, None),  # the row walk, the model's heads: a query block of 5 rows
    (1, 1, 2),  # the walk over several chunks, a context inside a chunk
    (8, 4, None),  # the grid over spans
    (5, 1, 2),  # spans over several chunks
])
def test_kernel_at_5_queries_a_kv_head_is_its_xla_twin(span, kv_heads, pages_per_chunk):
    """5 queries a KV head against the gather path: rows whose contexts end
    inside a chunk and inside a page, a row with no sequence."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention_pallas,
        ragged_paged_attention_xla,
    )

    rng = np.random.default_rng(span * 10 + kv_heads)
    b, block, heads = 4, 4, kv_heads * 5
    k, v = (
        jnp.asarray(rng.standard_normal((30, block, kv_heads * 128)), jnp.float32)
        for _ in range(2)
    )
    q = jnp.asarray(rng.standard_normal((b, span, heads, 128)), jnp.float32)
    tables = jnp.asarray(rng.permutation(29)[:b * 7].reshape(b, 7) + 1, jnp.int32)
    ctx = jnp.asarray([26, 9, 0, 17], jnp.int32)
    q_lens = jnp.asarray([span, max(span - 3, 1), 0, span], jnp.int32)
    pos = jnp.maximum(ctx - span, 0)[:, None] + jnp.arange(span)[None]
    args = (q, k, v, tables, ctx, pos)
    want = ragged_paged_attention_xla(*args, q_lens=q_lens)
    got = ragged_paged_attention_pallas(
        *args, q_lens=q_lens, interpret=True, pages_per_chunk=pages_per_chunk
    )
    assert got.shape == (b, span, heads, 128)
    for i, n in enumerate(np.asarray(q_lens)):  # pad queries are discarded
        np.testing.assert_allclose(got[i, :n], want[i, :n], rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[2]).any()  # no sequence: exact zeros
