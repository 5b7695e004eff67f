"""``models/lfm2.py`` (gated short convolutions, attention with QK-norm over
64-wide heads, sigmoid-scored experts with a selection bias) against
``benchmarks/reference_lfm2.py`` at toy widths on the CPU: LOGITS of the
paged path through pool and state, never tokens; the state and the pages a
sequence leaves; the expert shares adding up; the router; the paged kernel
at 64-wide heads against its XLA twin (``test_lfm2_engine.py``: the engine
over it).

Tolerance: the program and the reference are both float32 here, so they
differ only by the order of sums; a wrong program (a state carried a token
late, QK-norm after the rotation, the bias dropped) reads 0.05 or more.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_lfm2 as ref
from distllm_tpu.models import common, decoder_family, lfm2, moe
from lfm2_toy import paged_logits, prompt, spread, tiny, tiny_hf

CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')
TOLERANCE = 1e-3


def _reference_logits(hf, params, tokens, n_prompt):
    at = np.arange(n_prompt - 1, len(tokens))[None]
    return ref.lfm2_logits(params, hf, np.asarray(tokens)[None], at)[0]


def _rows(seed, sizes):
    rng = np.random.default_rng(seed)
    return [(prompt(rng, total), n) for n, total in sizes]


def _assert_rows_match(hf, cfg, params, rows, kv_state):
    """What the pools hold afterwards: layer 0's state is ``u`` of a row's
    last two tokens, the first attention layer's pages the reference's K
    and V (after QK-norm and the rotation) of every token."""
    k, v, state = kv_state
    lanes = cfg.num_kv_heads * cfg.head_size
    width = (k.shape[1] - 1) // len(rows)
    for i, (tokens, _) in enumerate(rows):
        want = ref.first_conv_inputs(params, hf, tokens[-2:])
        assert ref.content_error(state['conv'][0][i], want) < 1e-5
        pages = slice(1 + i * width, 1 + (i + 1) * width)
        want_k, want_v = ref.first_attn_kv(
            params, hf, tokens, np.arange(len(tokens))
        )
        for pool, want in ((k, want_k), (v, want_v)):
            got = np.asarray(pool[0, pages]).reshape(-1, lanes)[:len(tokens)]
            assert ref.content_error(got, want.reshape(len(tokens), lanes)) < 1e-5


# One row alone: a prompt of one token, of two, one that ends a chunk of 8,
# ones whose last span brings 1 and 2 tokens (the state then keeps rows of
# the chunk before), and one over several chunks; then decode.
@pytest.mark.parametrize('n_prompt, total', [
    (1, 5), (2, 6), (8, 11), (9, 12), (10, 12), (17, 21), (21, 30),
])
def test_paged_logits_are_the_references(n_prompt, total):
    hf, cfg, params = tiny(0)
    rows = _rows(n_prompt, [(n_prompt, total)])
    (got,), kv_state = paged_logits(cfg, params, rows)
    assert got.shape == (total - n_prompt + 1, hf['vocab_size'])
    assert spread(got, _reference_logits(hf, params, *rows[0])) < TOLERANCE
    _assert_rows_match(hf, cfg, params, rows, kv_state)


# Rows of unequal tails in one dispatch: in the second round one row brings
# 8 tokens, one 1, one 2 and two are pad rows; a stale state pool under it.
@pytest.mark.parametrize('backend, stale', [
    ('xla', None), ('xla', 7.0), ('interpret', None),
])
def test_rows_of_unequal_tails_share_a_dispatch(backend, stale):
    hf, cfg, params = tiny(1)
    rows = _rows(7, [(21, 26), (9, 12), (10, 11), (1, 4), (5, 9)])
    got, kv_state = paged_logits(
        cfg, params, rows, backend=backend, stale=stale
    )
    for logits, (tokens, n_prompt) in zip(got, rows):
        want = _reference_logits(hf, params, tokens, n_prompt)
        assert spread(logits, want) < TOLERANCE, n_prompt
    _assert_rows_match(hf, cfg, params, rows, kv_state)


def test_published_widths_of_a_head_go_through_the_kernel():
    """64-wide heads, 4 queries a KV head, through the Pallas interpreter
    (two heads a lane tile) in prefill spans and decode steps."""
    hf, cfg, params = tiny(
        2, hidden_size=512, num_attention_heads=8, num_key_value_heads=2,
        layer_types=('conv', 'full_attention', 'conv'), num_hidden_layers=3,
        num_dense_layers=1,
    )
    assert cfg.head_size == 64
    rows = _rows(3, [(11, 14), (3, 6)])
    got, _ = paged_logits(cfg, params, rows, backend='interpret')
    for logits, (tokens, n_prompt) in zip(got, rows):
        want = _reference_logits(hf, params, tokens, n_prompt)
        assert spread(logits, want) < TOLERANCE


def test_dense_forward_is_the_references():
    hf, cfg, params = tiny(3)
    ids = np.asarray([prompt(np.random.default_rng(3), 19)], np.int32)
    hidden = lfm2.apply(params, cfg, jnp.asarray(ids), jnp.ones_like(ids))
    got = lfm2.logits(params, cfg, hidden)[0]
    want = ref.lfm2_logits(params, hf, ids, np.arange(19)[None])[0]
    assert spread(got, want) < TOLERANCE


@pytest.mark.parametrize('what', ['state_a_token_late', 'norm_after_rope', 'bias_dropped'])
def test_tolerance_breaks_on_a_wrong_program(what, monkeypatch):
    hf, cfg, params = tiny(0)
    if what == 'state_a_token_late':
        span = lfm2.conv_span

        def late_state(h, lp, conv0, tail_lens):
            out, _ = span(h, lp, conv0, tail_lens)
            return out, span(h, lp, conv0, jnp.maximum(tail_lens - 1, 0))[1]

        monkeypatch.setattr(lfm2, 'conv_span', late_state)
    elif what == 'norm_after_rope':
        params = jax.tree.map(lambda a: a, params)
        scale = 1.0 + 0.5 * jnp.asarray(
            np.random.default_rng(0).standard_normal(cfg.head_size), jnp.float32
        )
        params['attn']['q_ln']['scale'] = params['attn']['q_ln']['scale'] * scale

        def swapped(normed, lp, cfg_, cos, sin, positions):
            heads = lambda t, n: t.reshape(*t.shape[:-1], n, cfg_.head_size)  # noqa: E731
            q = heads(common.dense(normed, lp['q']['kernel']), cfg_.num_heads)
            k = heads(common.dense(normed, lp['k']['kernel']), cfg_.num_kv_heads)
            v = heads(common.dense(normed, lp['v']['kernel']), cfg_.num_kv_heads)
            q = lfm2._norm(common.apply_rope(q, cos, sin, positions), lp['q_ln']['scale'], cfg_)
            k = lfm2._norm(common.apply_rope(k, cos, sin, positions), lp['k_ln']['scale'], cfg_)
            return q, k, v

        rows = _rows(5, [(13, 16)])
        (right,), _ = paged_logits(cfg, params, rows)
        assert spread(right, _reference_logits(hf, params, *rows[0])) < TOLERANCE
        monkeypatch.setattr(lfm2, '_qkv', swapped)
    else:
        routed = moe.routed_experts

        def unbiased(*args, select_bias=None, **kw):
            return routed(*args, select_bias=None, **kw)

        monkeypatch.setattr(lfm2, 'routed_experts', unbiased)
    rows = _rows(5, [(13, 16)])
    (got,), _ = paged_logits(cfg, params, rows)
    assert spread(got, _reference_logits(hf, params, *rows[0])) > 0.05


# ------------------------------------------------------------------ experts
def _sparse_layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params['sparse'])


def test_expert_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 on one chip and 4-7 on the other (the published model: 0-15
    and 16-31): the two shares' outputs add up to the uncut reference
    layer. There is no shared expert to count once; what both chips
    compute alike (the router) is computed by each and adds nothing."""
    hf, cfg, params = tiny(4)
    mp = _sparse_layer(params)
    h = jnp.asarray(np.random.default_rng(4).normal(size=(19, 64)), jnp.float32)
    with jax.default_matmul_precision('highest'):
        whole = ref.sparse_mlp(h, mp, 3, 1.0, 0)
        parts = jnp.zeros_like(whole)
        for first in (0, 4):
            share = {**mp, **{
                n: {'kernel': mp[n]['kernel'][first:first + 4]}
                for n in ('gate', 'up', 'down')
            }}
            want = ref.sparse_mlp(h, share, 3, 1.0, first)
            got, pairs = moe.routed_experts(
                h, share['router']['kernel'], share['gate']['kernel'],
                share['up']['kernel'], share['down']['kernel'], 3,
                first_expert=first, scoring='sigmoid',
                select_bias=share['router_bias']['bias'],
                norm_eps=lfm2.ROUTER_EPS,
            )
            assert spread(got, want) < 2e-5
            assert int(pairs[0]) == 19 * 3 and 0 < int(pairs[1]) < 19 * 3
            parts = parts + got
    assert spread(parts, whole) < 2e-5


def test_program_with_a_share_matches_the_reference_with_that_share():
    hf, cfg, params = tiny(
        5, num_experts=4, num_routed_experts=8, first_local_expert=4
    )
    assert params['sparse']['gate']['kernel'].shape == (4, 4, 64, 24)
    assert params['sparse']['router']['kernel'].shape == (4, 64, 8)
    rows = _rows(5, [(11, 15)])
    (got,), _ = paged_logits(cfg, params, rows)
    assert spread(got, _reference_logits(hf, params, *rows[0])) < TOLERANCE


def _gates(h, router, bias, k, **kw):
    """The gate of every (token, expert) through ``routed_experts``: expert
    ``e``'s SwiGLU is made the constant one-hot ``e`` (its gate and up
    kernels read a column of ones appended to ``h``, its down kernel is row
    ``e`` of the identity over ``silu(1)``), so the output is the gates."""
    e = router.shape[1]
    reads_ones = jnp.zeros((e, h.shape[1] + 1, 1)).at[:, -1].set(1.0)
    down = jnp.eye(e)[:, None, :] / float(jax.nn.silu(1.0))
    hx = jnp.concatenate([h, jnp.ones((h.shape[0], 1), h.dtype)], -1)
    router = jnp.concatenate([router, jnp.zeros((1, e))], axis=0)
    out, _ = moe.routed_experts(
        hx, router, reads_ones, reads_ones, down, k, scoring='sigmoid',
        select_bias=bias, **kw,
    )
    return np.asarray(out)  # [T, E]: the gate, 0 where not kept


def test_router_bias_chooses_and_never_weighs():
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(16, 8)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)) * 0.2, jnp.float32)
    got = _gates(h, router, bias, 3, norm_eps=1e-6)
    s = 1.0 / (1.0 + np.exp(-np.asarray(h @ router, np.float64)))
    kept = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :3]
    want = np.zeros_like(s)
    top = np.take_along_axis(s, kept, -1)
    np.put_along_axis(want, kept, top / (top.sum(-1, keepdims=True) + 1e-6), -1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # Dropping the bias keeps another set for many tokens: a program that
    # drops it is told apart.
    unbiased = _gates(h, router, None, 3, norm_eps=1e-6)
    changed = ((got > 0) != (unbiased > 0)).any(-1).mean()
    assert changed > 0.3


def test_router_epsilon_is_the_published_one():
    """Scores near 1e-7 show the normaliser's epsilon: 1e-6 as published
    for ``lfm2_moe``, 1e-20 (the default) for the other sigmoid family."""
    h = jnp.ones((4, 16), jnp.float32)
    router = jnp.full((16, 8), -1.0, jnp.float32)  # logits -16
    small = _gates(h, router, None, 2, norm_eps=1e-6).sum(-1)
    whole = _gates(h, router, None, 2).sum(-1)
    s = 1.0 / (1.0 + np.exp(16.0))
    np.testing.assert_allclose(small, 2 * s / (2 * s + 1e-6), rtol=1e-4)
    np.testing.assert_allclose(whole, 1.0, rtol=1e-5)


def test_qk_norm_comes_before_the_rotation():
    hf, cfg, params = tiny(7)
    lp = jax.tree.map(lambda a: a[1], params['attn'])
    rng = np.random.default_rng(7)
    lp['q_ln']['scale'] = jnp.asarray(1 + 0.5 * rng.standard_normal(16), jnp.float32)
    lp['k_ln']['scale'] = jnp.asarray(1 + 0.5 * rng.standard_normal(16), jnp.float32)
    s = 9
    h = jnp.asarray(rng.standard_normal((s, 64)), jnp.float32)
    positions = np.arange(3, 3 + s)
    cos, sin = ref.rope_angles(cfg.rope_theta, cfg.head_size, positions)
    with jax.default_matmul_precision('highest'):
        want = ref.qkv(h, lp, 4, 2, cfg.norm_eps, cos, sin)
        got = lfm2._qkv(
            h[None], lp, cfg, *lfm2._rope_tables(cfg, 16), jnp.asarray(positions)[None]
        )
    for g, w in zip(got, want):
        assert spread(g[0], w) < 1e-5
    # rms(rope(q)) * scale is another function where the scale is not flat
    q = (h @ lp['q']['kernel']).reshape(s, 4, 16)
    wrong = ref._rms(ref._rotate(q, cos, sin), lp['q_ln']['scale'], cfg.norm_eps)
    assert spread(wrong, want[0]) > 0.05


# ------------------------------------------------------------------- config
def test_config_reads_the_published_keys_and_the_share():
    row = next(
        json.loads(line) for line in CATALOG.read_text().splitlines()
        if '"LFM2-8B-A1B"' in line
    ) if CATALOG.exists() else None
    published = row['config'] if row else json.loads(
        (Path(__file__).resolve().parents[1]
         / 'benchmarks/configs/lfm2-8b-a1b.json').read_text()
    )
    cfg = lfm2.Lfm2MoeConfig.from_hf_config(
        {**published, 'num_experts': 16, 'num_routed_experts': 32}
    )
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert) == (32, 16, 0)
    assert cfg.num_layers == 24 and cfg.num_paged_layers == 6
    assert [i for i, t in enumerate(cfg.layer_types) if t != 'conv'] == [2, 6, 10, 14, 18, 21]
    assert cfg.head_size == 64 and cfg.num_kv_heads == 8
    assert (cfg.count('conv'), cfg.count('dense'), cfg.count('sparse')) == (18, 2, 22)
    kinds = {(m, p) for m, _, p, _ in cfg.layer_indices()}
    assert kinds == {('conv', 'dense'), ('conv', 'sparse'), ('attn', 'sparse')}
    spec = cfg.cache_spec()
    assert [(g.name, g.num_layers, g.window, g.row) for g in spec.paged] == [
        ('kv', 6, None, None)
    ]
    assert not spec.dense_prefill and spec.program_prefix == 'lfm2_'
    leaves = jax.tree.leaves(spec.state)
    assert len(leaves) == 18 and {(x.shape, x.dtype) for x in leaves} == {
        ((2, 2048), jnp.dtype('bfloat16'))
    }
    shapes = jax.eval_shape(
        lambda: lfm2.init_on_device(jax.random.PRNGKey(0), cfg)
    )
    held = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 4.46e9 < held < 4.47e9  # the issue's 4,465 M


@pytest.mark.parametrize('key, value', [
    ('conv_bias', True), ('norm_topk_prob', False), ('use_expert_bias', False),
    ('conv_L_cache', 1), ('rope_scaling', {'rope_type': 'yarn'}),
    ('tie_word_embeddings', False),
])
def test_config_refuses_what_is_not_implemented(key, value):
    with pytest.raises(ValueError, match=f'lfm2_moe: {key}='):
        lfm2.Lfm2MoeConfig.from_hf_config(tiny_hf(**{key: value}))


def test_decoder_family_has_the_row():
    cls, module = decoder_family('lfm2_moe')
    assert cls is lfm2.Lfm2MoeConfig and module is lfm2
    with pytest.raises(NotImplementedError, match='lfm2_moe: no converter'):
        lfm2.params_from_hf({}, cls())


# ------------------------------------------------------------------- kernel
def _pool(rng, blocks, block, lanes):
    return jnp.asarray(rng.standard_normal((blocks, block, lanes)), jnp.float32)


@pytest.mark.parametrize('span, kv_heads, group, pages_per_chunk', [
    (1, 8, 4, None),  # the row walk, the model's heads
    (1, 2, 4, 2),  # the walk over several chunks, a context inside a chunk
    (8, 8, 4, None),  # the grid over spans
    (5, 2, 4, 2),  # spans over several chunks
    (1, 4, 1, 3),  # one query a KV head
])
def test_kernel_at_64_wide_heads_is_its_xla_twin(span, kv_heads, group, pages_per_chunk):
    """Two heads a lane tile against the gather path: rows whose contexts
    end inside a chunk and inside a page, a row with no sequence."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention_pallas,
        ragged_paged_attention_xla,
    )

    rng = np.random.default_rng(span * 10 + kv_heads)
    b, block, heads = 4, 4, kv_heads * group
    k, v = (_pool(rng, 30, block, kv_heads * 64) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((b, span, heads, 64)), jnp.float32)
    tables = jnp.asarray(rng.permutation(29)[:b * 7].reshape(b, 7) + 1, jnp.int32)
    ctx = jnp.asarray([26, 9, 0, 17], jnp.int32)
    q_lens = jnp.asarray([span, max(span - 3, 1), 0, span], jnp.int32)
    pos = jnp.maximum(ctx - span, 0)[:, None] + jnp.arange(span)[None]
    args = (q, k, v, tables, ctx, pos)
    want = ragged_paged_attention_xla(*args, q_lens=q_lens)
    got = ragged_paged_attention_pallas(
        *args, q_lens=q_lens, interpret=True, pages_per_chunk=pages_per_chunk
    )
    assert got.shape == (b, span, heads, 64)
    for i, n in enumerate(np.asarray(q_lens)):  # pad queries are discarded
        np.testing.assert_allclose(got[i, :n], want[i, :n], rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[2]).any()  # no sequence: exact zeros


def test_kernel_at_64_wide_heads_addresses_a_stacked_pool_and_a_window():
    from distllm_tpu.ops.paged_attention import (
        decode_attention,
        paged_attention_xla,
    )

    rng = np.random.default_rng(9)
    k, v = (
        jnp.asarray(rng.standard_normal((3, 12, 4, 256)), jnp.float32)
        for _ in range(2)
    )
    q = jnp.asarray(rng.standard_normal((2, 8, 64)), jnp.float32)
    tables = jnp.asarray(rng.permutation(11)[:10].reshape(2, 5) + 1, jnp.int32)
    ctx = jnp.asarray([19, 6], jnp.int32)
    for layer, window in ((0, None), (2, 5)):
        want = paged_attention_xla(
            q, k, v, tables, ctx, sliding_window=window, scale=0.2, layer=layer
        )
        got = decode_attention(
            q, k, v, tables, ctx, ctx - 1, sliding_window=window, scale=0.2,
            backend='interpret', layer=layer,
        )
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_kernel_refuses_the_heads_it_cannot_read():
    from distllm_tpu.ops import paged_attention as pa

    args = (jnp.ones((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.zeros((1, 1), jnp.int32))
    pool = jnp.zeros((4, 32, 128), jnp.int8)
    scale = jnp.zeros((4, 2))
    with pytest.raises(ValueError, match='kv_cache_dtype=int8 at 64-wide heads'):
        pa.ragged_paged_attention_pallas(
            jnp.zeros((1, 1, 4, 64)), pa.QuantizedKV(pool, scale),
            pa.QuantizedKV(pool, scale), *args, interpret=True,
        )
    with pytest.raises(ValueError, match='whole 128-lane tiles'):
        pa.ragged_paged_attention_pallas(
            jnp.zeros((1, 1, 4, 96)), jnp.zeros((4, 16, 192)),
            jnp.zeros((4, 16, 192)), *args,
        )
    # 'auto' takes the kernel for 64-wide heads only where a pool row is
    # whole lane tiles.
    class Heads:
        def __init__(self, n, d):
            self.num_kv_heads, self.head_size = n, d

    assert pa.supports_model(Heads(8, 64)) and pa.supports_model(Heads(8, 128))
    assert pa.supports_model(Heads(1, 640))
    assert not pa.supports_model(Heads(3, 64)) and not pa.supports_model(Heads(8, 96))
    assert pa.walk_keys_a_step(512, jnp.bfloat16, planes=2, block_size=16) == 1024


@pytest.mark.parametrize(
    ('kv_heads', 'head', 'kv_dtype', 'block', 'want'),
    [
        (8, 64, 'bfloat16', 16, 'pallas'),
        # The kernel refuses an int8 pool under heads that share a lane
        # tile, so 'auto' keeps such a model (any family's) on the XLA path
        # at a block size the int8 pool's DMAs would pass.
        (8, 64, 'int8', 32, 'xla'),
        (8, 128, 'int8', 32, 'pallas'),
        (8, 64, 'int8', 16, 'xla'),
    ],
)
def test_auto_never_takes_the_kernel_where_it_refuses_the_pool(
    monkeypatch, kv_heads, head, kv_dtype, block, want
):
    from types import SimpleNamespace

    from distllm_tpu.ops import paged_attention as pa

    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    model = SimpleNamespace(num_kv_heads=kv_heads, head_size=head)
    assert pa.resolve_attn_backend(
        'auto', model, block_size=block, kv_dtype=kv_dtype
    ) == want
