"""``models/deepseek_v3.py`` (latent attention, sigmoid-scored experts)
against ``benchmarks/reference_deepseek_v3.py`` at toy widths on the CPU:
LOGITS of the paged path, never tokens; the absorbed form against the
expanded one; every wrong program of ISSUE 32's list past the tolerance; the
expert shares adding up; what ``from_hf_config`` reads and refuses."""

import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_deepseek_v3 as ref
from deepseek_toy import paged_logits, prompt, spread, tiny, tiny_hf
from distllm_tpu.models import common, decoder_family, deepseek_v3, moe

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / 'scripts'))
import probe_deepseek_reference as probe  # noqa: E402

CATALOG = Path('/opt/skills/guides/model-configs/architectures.jsonl')
TOLERANCE = 1e-3  # float32 on both sides; a wrong program reads 0.7 or more


def _reference_logits(hf, params, tokens, n_prompt):
    at = np.arange(n_prompt - 1, len(tokens))[None]
    return ref.deepseek_logits(params, hf, np.asarray(tokens)[None], at)[0]


# Prompts longer than two chunks of 8, then decode, through the latent pool.
@pytest.mark.parametrize('backend, layers, n_prompt, total', [
    ('xla', 3, 21, 30), ('interpret', 2, 19, 22),
])
def test_paged_logits_are_the_references(backend, layers, n_prompt, total):
    hf, cfg, params = tiny(0, num_hidden_layers=layers)
    tokens = prompt(np.random.default_rng(0), total)
    got, planes = paged_logits(cfg, params, tokens, n_prompt, backend=backend)
    assert got.shape == (total - n_prompt + 1, hf['vocab_size'])
    assert spread(got, _reference_logits(hf, params, tokens, n_prompt)) < TOLERANCE
    # What the pool holds of layer 0 is a function of token and position.
    rows = np.asarray(planes[0])[1:].reshape(-1, cfg.stored_row)[:total - 1]
    want = ref.first_layer_rows(params, hf, tokens[:-1], np.arange(total - 1))
    assert ref.row_content_error(rows[:, :cfg.latent_row], want) < 1e-5
    assert not rows[:, cfg.latent_row:].any()  # the pad lanes stay zero


def test_prefill_alone_scores_every_chunk_boundary():
    hf, cfg, params = tiny(1)
    tokens = prompt(np.random.default_rng(1), 25)
    for n_prompt in (16, 17):
        got, _ = paged_logits(cfg, params, tokens[:n_prompt], n_prompt)
        want = _reference_logits(hf, params, tokens[:n_prompt], n_prompt)
        assert spread(got, want) < TOLERANCE, n_prompt


@pytest.mark.parametrize('span', [1, 8])
def test_kernel_with_32_queries_on_one_kv_head_is_its_xla_twin(span):
    """The ragged Pallas kernel in interpret mode over a latent plane: one KV
    head, 32 query heads on it, values the first lanes of the key rows, a
    scale that is not ``head_dim ** -0.5``."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention_pallas,
        ragged_paged_attention_xla,
    )

    rng = np.random.default_rng(2)
    b, heads, row, lanes, block = 3, 32, 256, 128, 4
    plane = jnp.asarray(rng.standard_normal((20, block, row)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, span, heads, row)), jnp.float32)
    tables = jnp.asarray(rng.permutation(19)[:b * 6].reshape(b, 6) + 1, jnp.int32)
    ctx = jnp.asarray([24, 9, 17], jnp.int32)
    pos = (ctx - span)[:, None] + jnp.arange(span)[None]
    q_lens = jnp.asarray([span, max(span - 3, 1), span], jnp.int32)
    args = (q, plane, None, tables, ctx, pos)
    kw = dict(q_lens=q_lens, scale=0.2, value_lanes=lanes)
    want = ragged_paged_attention_xla(*args, **kw)
    got = ragged_paged_attention_pallas(*args, **kw, interpret=True)
    assert got.shape == (b, span, heads, lanes)
    for i, n in enumerate(np.asarray(q_lens)):  # pad queries are discarded
        np.testing.assert_allclose(got[i, :n], want[i, :n], rtol=2e-5, atol=2e-5)


def test_kernel_refuses_a_latent_plane_it_cannot_read():
    from distllm_tpu.ops.paged_attention import (
        QuantizedKV,
        ragged_paged_attention_pallas,
    )

    q = jnp.zeros((1, 1, 4, 256))
    plane = jnp.zeros((4, 4, 256))
    args = (jnp.ones((1, 2), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.zeros((1, 1), jnp.int32))
    with pytest.raises(ValueError, match='value_lanes'):
        ragged_paged_attention_pallas(q, plane, None, *args, interpret=True)
    with pytest.raises(ValueError, match='no int8 form'):
        ragged_paged_attention_pallas(
            q, QuantizedKV(plane.astype(jnp.int8), jnp.zeros((4, 1))), None,
            *args, interpret=True, value_lanes=128,
        )


def test_absorbed_attention_equals_expanded_on_one_layer():
    """``qt_h = Wuk_h q_n,h`` over the cached rows and ``o_h = Wuv_h^T
    ot_h`` afterwards against per-head keys and values made from the latent
    (the reference's form), on one layer's attention alone."""
    from distllm_tpu.ops.paged_attention import (
        ragged_paged_attention_xla,
        write_chunk_kv,
    )

    hf, cfg, params = tiny(3)
    s = 13
    lp = jax.tree.map(lambda a: a[1], params['attn'])
    h = jnp.asarray(np.random.default_rng(3).standard_normal((s, cfg.hidden_size)), jnp.float32)
    cos, sin = ref.rope_angles(cfg.rope_theta, cfg.qk_rope_head_dim, np.arange(s))
    with jax.default_matmul_precision('highest'):
        want = ref.attention(
            h, lp, cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.kv_lora_rank, cfg.rms_norm_eps, cos, sin,
        )
        positions = jnp.arange(s)[None]
        q_n, q_r, row = deepseek_v3._latent_parts(
            h[None], lp, cfg, *deepseek_v3._rope_tables(cfg, s), positions
        )
        assert row.shape == (1, s, 1, cfg.stored_row)
        plane, none = write_chunk_kv(
            jnp.zeros((5, 4, cfg.stored_row)), None, row, None,
            jnp.arange(1, 5)[None], positions, jnp.ones((1, s), bool),
        )
        assert none is None
        ot = ragged_paged_attention_xla(
            deepseek_v3._absorb_queries(q_n, q_r, lp, cfg), plane, None,
            jnp.arange(1, 5)[None], jnp.asarray([s]), positions,
            scale=cfg.softmax_scale, value_lanes=cfg.kv_lora_rank,
        )
        got = deepseek_v3._attn_out(ot, lp, cfg)[0]
    assert spread(got, want) < 1e-4


@pytest.mark.parametrize('arm', probe.ARMS.split(','))
def test_tolerance_breaks_on_a_wrong_program(arm):
    """ISSUE 32's wrong programs, each the probe script's own arm (what the
    chip's check is calibrated with), through the paged path at toy widths:
    the program as served is inside the tolerance, every other arm far
    outside it."""
    hf, cfg, params = tiny(0, num_hidden_layers=2)
    tokens = prompt(np.random.default_rng(0), 24)
    wrong_cfg, patches = probe.arm(cfg, arm)
    with probe.patched(patches):
        got, _ = paged_logits(wrong_cfg, params, tokens, 19)
    gap = spread(got, _reference_logits(hf, params, tokens, 19))
    if arm == 'program':
        assert gap < TOLERANCE
    else:
        assert gap > 100 * TOLERANCE, gap
    # the patches are gone again
    assert deepseek_v3.routed_experts is moe.routed_experts
    assert cfg.softmax_scale == cfg.qk_head_dim ** -0.5


def test_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four chips of the expert axis hold 2 of 8 experts each: their routed
    parts and the shared experts counted ONCE are the uncut sparse layer of
    the reference, and the pair counts add up to every routed pair."""
    hf, cfg, params = tiny(4)
    mp = jax.tree.map(lambda a: a[0], params['sparse'])
    x = jnp.asarray(np.random.default_rng(4).standard_normal((11, cfg.hidden_size)), jnp.float32)
    with jax.default_matmul_precision('highest'):
        want = ref.sparse_mlp(
            x, mp, cfg.experts_per_token, cfg.routed_scaling_factor, 0
        )
        total, held = jnp.zeros_like(x), 0
        for first in range(0, 8, 2):
            share, pairs = moe.routed_experts(
                x, mp['router']['kernel'],
                *(mp[n]['kernel'][first:first + 2] for n in ('gate', 'up', 'down')),
                cfg.experts_per_token, first_expert=first,
                routed_scale=cfg.routed_scaling_factor, scoring='sigmoid',
                select_bias=mp['router_bias']['kernel'],
            )
            total, held = total + share, held + int(pairs[1])
            assert int(pairs[0]) == 11 * cfg.experts_per_token
        total = total + common.swiglu(
            x, mp['shared_gate']['kernel'], mp['shared_up']['kernel'],
            mp['shared_down']['kernel'],
        )
    assert held == 11 * cfg.experts_per_token
    assert spread(total, want) < 1e-4
    # The model's own layer with every expert held says the same.
    got, pairs = deepseek_v3._mlp(
        x, {n: leaf for n, leaf in mp.items() if n not in deepseek_v3._BANKS},
        cfg, 'sparse', jnp.ones((11,), bool), params['sparse'], 0,
    )
    assert spread(got, want) < 1e-4 and list(map(int, pairs)) == [33, 33]


def _routed_experts_of_the_parent(x, router_kernel, gate, up, down, k,
                                  first_expert=0, counted=None, layer=None,
                                  routed_scale=1.0):
    """``moe.routed_experts`` as it was before it could score by sigmoid
    (commit d7e89a7), statement for statement: the yardstick of "softmax
    callers get what they got"."""
    dtype = x.dtype
    tokens = x.shape[0]
    gate, up, down = (w.astype(dtype) for w in (gate, up, down))
    held = gate.shape[-3]
    if layer is not None:
        gate, up, down = (w.reshape(-1, *w.shape[2:]) for w in (gate, up, down))
    with jax.named_scope('distllm.moe'):
        logits = jnp.einsum(
            'th,he->te', x.astype(jnp.float32), router_kernel.astype(jnp.float32),
        )
        top_logits, top_idx = jax.lax.top_k(logits, k)
        weights = jax.nn.softmax(top_logits, axis=-1)
        if routed_scale != 1.0:
            weights = weights * routed_scale
        local = top_idx - first_expert
        is_held = (local >= 0) & (local < held)
        group = jnp.where(is_held, local, held).reshape(-1)
        order = jnp.argsort(group, stable=True)
        group_sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        if layer is not None:
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((gate.shape[0],), jnp.int32), group_sizes, (layer * held,),
            )
        rows = x[jnp.pad(order // k, (0, -tokens * k % 8))]
        hidden = jax.nn.silu(
            jax.lax.ragged_dot(rows, gate, group_sizes)
        ) * jax.lax.ragged_dot(rows, up, group_sizes)
        out = jax.lax.ragged_dot(hidden, down, group_sizes)[: tokens * k]
        out = jnp.where(
            is_held.reshape(-1)[order][:, None],
            out.astype(jnp.float32) * weights.reshape(-1)[order][:, None], 0.0,
        )
        out = out[jnp.argsort(order)].reshape(tokens, k, -1).sum(axis=1)
        rows_counted = jnp.ones((tokens,), bool) if counted is None else counted
        pairs = jnp.stack([
            rows_counted.sum() * k, (is_held & rows_counted[:, None]).sum(),
        ]).astype(jnp.int32)
    return out.astype(dtype), pairs


@pytest.mark.parametrize('layer, scale', [(None, 1.0), (1, 2.5)],
                         ids=['granite', 'laguna'])
def test_softmax_callers_of_routed_experts_get_what_they_got(layer, scale):
    """Softmax with no bias (granite's call, and laguna's with a layer
    stack and a routed scale) lowers to the parent's program, text for
    text as far as the matmuls, and gives its outputs bit for bit (the
    parent's three float32 passes behind them and ``moe.combine`` are the
    same products added in the same order): at a prefill dispatch's rows,
    where the rule keeps the grouped form (a handful of rows takes the
    dense one since PR 40, ``tests/test_moe_forms.py``)."""
    rng = np.random.default_rng(5)
    stack = () if layer is None else (3,)
    assert moe.expert_form(256, 3, 4, 8, 16, 12) == 'grouped'
    x = jnp.asarray(rng.standard_normal((256, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    banks = [
        jnp.asarray(rng.standard_normal((*stack, 4, *shape)), jnp.float32)
        for shape in ((16, 12), (16, 12), (12, 16))
    ]
    kw = dict(first_expert=2, layer=layer, routed_scale=scale)

    def program(fn):
        def routed(x, router, *banks):
            return fn(x, router, *banks, 3, **kw)

        return jax.jit(routed)

    ours, parents = program(moe.routed_experts), program(_routed_experts_of_the_parent)
    args = (x, router, *banks)

    def to_the_matmuls(text):
        # Since PR 43 the way back is ``moe.combine`` (one gather of a
        # token's k rows) where the parent has a float32 product in sorted
        # order, its gather and a sum: the texts part behind the third
        # ``ragged_dot`` (a ``dot_general`` here, the router's the first
        # of four), and the outputs below still may not.
        cut = text.rindex('stablehlo.dot_general')
        assert text.count('stablehlo.dot_general', 0, cut) == 3
        # Values inside a region are numbered behind all of the function's
        # own: name each by where it first appears, which keeps the wiring.
        seen = {}
        return re.sub(
            r'%\d+', lambda m: f'%v{seen.setdefault(m[0], len(seen))}',
            text[: text.index('\n', cut)],
        )

    assert to_the_matmuls(ours.lower(*args).as_text()) == to_the_matmuls(
        parents.lower(*args).as_text()
    )
    for got, want in zip(ours(*args), parents(*args)):
        assert (np.asarray(got) == np.asarray(want)).all()
    with pytest.raises(ValueError, match='selection bias'):
        moe.routed_experts(*args, 3, select_bias=jnp.zeros((8,)))
    with pytest.raises(ValueError, match='scoring'):
        moe.routed_experts(*args, 3, scoring='tanh')


def test_the_selection_bias_chooses_and_never_weighs():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    banks = [
        jnp.asarray(rng.standard_normal((8, *shape)), jnp.float32) * 0.3
        for shape in ((16, 12), (16, 12), (12, 16))
    ]
    plain, _ = moe.routed_experts(x, router, *banks, 3, scoring='sigmoid')
    # A bias that is the same for every expert chooses the same experts.
    same, _ = moe.routed_experts(
        x, router, *banks, 3, scoring='sigmoid', select_bias=jnp.full((8,), 0.7)
    )
    np.testing.assert_array_equal(np.asarray(same), np.asarray(plain))
    # One that lifts expert 7 over all others brings it into every token's
    # set: the output moves, and a softmax router is another function.
    lifted, _ = moe.routed_experts(
        x, router, *banks, 3, scoring='sigmoid',
        select_bias=jnp.zeros((8,)).at[7].set(2.0),
    )
    softmax, _ = moe.routed_experts(x, router, *banks, 3)
    assert spread(lifted, plain) > 0.1 and spread(softmax, plain) > 0.1


def _catalog_row():
    if not CATALOG.is_file():
        pytest.skip('no catalog beside the model-configs guide here')
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines() if line.strip()]
    return next(r for r in rows if r['name'] == 'kanana-2-30b-a3b-instruct-2601')


def test_from_hf_config_reads_the_published_keys():
    hf = tiny_hf(num_routed_experts=16, first_local_expert=8)
    cfg = deepseek_v3.DeepseekV3Config.from_hf_config(hf)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert) == (16, 8, 8)
    assert (cfg.latent_row, cfg.stored_row, cfg.head_size, cfg.num_kv_heads) == (136, 256, 256, 1)
    assert cfg.softmax_scale == 24 ** -0.5
    assert [cfg.mlp_of(i) for i in range(3)] == [('dense', 0), ('sparse', 0), ('sparse', 1)]
    spec = cfg.cache_spec()
    assert [(g.name, g.num_layers, g.window, g.row, g.value_lanes, g.stored_row)
            for g in spec.paged] == [('latent', 3, None, 136, 128, 256)]
    assert spec.latent and not spec.dense_prefill
    assert spec.program_prefix == 'deepseek_' and spec.state is None
    assert decoder_family('deepseek_v3') == (deepseek_v3.DeepseekV3Config, deepseek_v3)
    with pytest.raises(NotImplementedError, match='no converter'):
        deepseek_v3.params_from_hf({}, cfg)
    specs = deepseek_v3.param_specs(cfg)
    assert specs['sparse']['gate']['kernel'][1] == 'expert'
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)) \
        == jax.tree.structure(deepseek_v3.init_on_device(jax.random.PRNGKey(0), cfg))


def test_from_hf_config_reads_the_catalog_row():
    row = _catalog_row()['config']
    cfg = deepseek_v3.DeepseekV3Config.from_hf_config(row)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (48, 2048, 32, 128256)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank) == (128, 64, 128, 512)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.experts_per_token) == (128, 128, 6)
    assert (cfg.latent_row, cfg.stored_row) == (576, 640)
    assert cfg.routed_scaling_factor == 2.448 and cfg.rope_theta == 1e6
    # 30.67 B parameters, as the issue counts them.
    shapes = jax.eval_shape(lambda: deepseek_v3.init_on_device(jax.random.PRNGKey(0), cfg))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == pytest.approx(30.67e9, rel=2e-3)


def test_the_configuration_file_keeps_every_published_number():
    row = _catalog_row()
    held = json.loads((ROOT / 'benchmarks/configs/kanana-2-30b-a3b.json').read_text())
    assert held['source'] == row['source_url']
    differ = {k for k, v in row['config'].items() if held.get(k, 'absent') != v}
    assert differ == set(held['reduced']) == {
        'num_hidden_layers', 'n_routed_experts', 'vocab_size',
    }
    assert held['published'] == {k: row['config'][k] for k in held['reduced']}


@pytest.mark.parametrize('key, value', [
    ('q_lora_rank', 1536), ('rope_scaling', {'type': 'yarn', 'factor': 40}),
    ('n_group', 8), ('topk_group', 4), ('scoring_func', 'softmax'),
    ('topk_method', 'greedy'), ('norm_topk_prob', False), ('moe_layer_freq', 2),
    ('attention_bias', True), ('tie_word_embeddings', True),
    ('hidden_act', 'gelu'), ('kv_lora_rank', 96),
])
def test_from_hf_config_refuses_what_is_not_implemented_by_name(key, value):
    with pytest.raises(ValueError, match=f'deepseek_v3: {key}='):
        deepseek_v3.DeepseekV3Config.from_hf_config(tiny_hf(**{key: value}))


def test_named_scopes_are_in_the_programs():
    hf, cfg, params = tiny(0)
    planes = tuple(jnp.zeros((4, 4, cfg.stored_row)) for _ in range(3))
    text = jax.jit(lambda planes: deepseek_v3.prefill_paged(
        params, cfg, jnp.zeros((1, 8), jnp.int32), jnp.arange(8)[None], planes,
        (), jnp.ones((1, 3), jnp.int32), jnp.asarray([8]), jnp.asarray([8]),
        max_table_positions=8,
    )).lower(planes).as_text(debug_info=True)
    for scope in ('distllm.attn_latent', 'distllm.attn_latent_proj',
                  'distllm.moe', 'distllm.dense_mlp'):
        assert f'{scope}/' in text or f'{scope}"' in text, scope
    assert isinstance(common.PagedGroup('kv', 2).stored_row, type(None))
