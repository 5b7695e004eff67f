"""A toy ``solar_open2`` (models/solar_open2.py) for the CPU tests: the
published config's keys at tiny widths (a period of four layers, G K K K, and
one KDA layer more; 4 queries a KV head; a router wider than the held share;
heads whose key width differs from the hidden width's quotient), seeded
weights, an engine over it, and the paged path driven by hand
(``lfm2_toy.paged_logits``: rounds of prefill spans through the pools and the
state, rows of unequal tails in one dispatch, then decode steps) so that its
LOGITS can be held against the plain reference."""

import functools
import types

import jax
import numpy as np

from benchmarks import reference_solar_open2 as ref
from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
from distllm_tpu.models import solar_open2
import lfm2_toy

BLOCK = lfm2_toy.BLOCK
NoTokenizer = lfm2_toy.NoTokenizer
prompt = lfm2_toy.prompt
spread = lfm2_toy.spread


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'solar_open2', 'partial_rotary_factor': 1,
        'linear_attn_config': {
            'short_conv_kernel_size': 4, 'head_dim': 8, 'num_heads': 3,
            'num_kv_heads': None,
        },
        'hidden_size': 32, 'num_hidden_layers': 5, 'num_attention_heads': 4,
        'head_dim': 8, 'num_key_value_heads': 1, 'vocab_size': 96,
        'intermediate_size': 64, 'moe_intermediate_size': 16,
        'rms_norm_eps': 1e-5, 'rope_theta': 10000,
        'tie_word_embeddings': False, 'max_position_embeddings': 4096,
        'first_k_dense_replace': 0, 'use_rope': False, 'gqa_interval': 3,
        'gqa_layers': [0, 4], 'use_gqa_gate': True,
        'kda_use_full_proj': False, 'kda_allow_neg_eigval': True,
        'n_routed_experts': 8, 'n_shared_experts': 1, 'norm_topk_prob': True,
        'routed_scaling_factor': 1, 'num_experts_per_tok': 2,
    }
    hf.update(over)
    return hf


@functools.lru_cache(maxsize=None)
def _tiny(seed, over):
    hf = tiny_hf(**dict(over))
    cfg = solar_open2.SolarOpen2Config.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = solar_open2.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits.
    params = jax.tree.map(
        lambda a: a * 8.0 if a.ndim >= 3 and a.shape[-1] > 4 else a, params
    )
    params['embed'] = params['embed'] * 8.0
    params['head']['kernel'] = params['head']['kernel'] * 8.0
    for tree in ('kda',):
        params[tree]['conv']['taps'] = params[tree]['conv']['taps'] / 8.0
    return hf, cfg, params


def tiny(seed=0, **over):
    """``(hf, cfg, params)``; the weights of a (seed, widths) are made once
    a process (nothing here writes to them)."""
    over = {
        k: tuple(v) if isinstance(v, list) else v for k, v in over.items()
    }
    hf, cfg, params = _tiny(seed, tuple(sorted(over.items())))
    return dict(hf), cfg, params


def make_engine(seed=0, hf_over=None, **over):
    hf, cfg, params = tiny(seed, **(hf_over or {}))
    settings = dict(
        block_size=BLOCK, num_blocks=64, max_num_seqs=4, max_model_len=96,
        prefill_chunk_tokens=8, decode_steps=4, attn_backend='xla',
        enable_prefix_cache=False,
    )
    settings.update(over)
    engine = LLMEngine(cfg, params, NoTokenizer(), EngineConfig(**settings))
    return hf, params, engine


# ``lfm2_toy.paged_logits`` builds rope tables and hands them to the core; this
# family has no rotation, so its core takes none.
_PROGRAMS = types.SimpleNamespace(
    prefill_paged=solar_open2.prefill_paged,
    _rope_tables=lambda cfg, total: None,
    _decode_core=lambda params, cfg, rope, backend, *rest: (
        solar_open2._decode_core(params, cfg, backend, *rest)
    ),
)


def paged_logits(cfg, params, rows, **kw):
    """``lfm2_toy.paged_logits`` over this module's programs."""
    return lfm2_toy.paged_logits(cfg, params, rows, module=_PROGRAMS, **kw)


def reference_logits(params, hf, tokens, first):
    """The plain reference's logits at positions ``first`` onward of one
    row ``tokens``."""
    at = np.arange(first, len(tokens))[None]
    return ref.solar_open2_logits(params, hf, np.asarray(tokens)[None], at)[0]


def load_probe() -> dict:
    """``scripts/probe_solar_open2_reference.py``'s globals: the wrong
    programs the cell's limits have to catch (``_beta_half``,
    ``_scalar_decay``, ``_softmax_scoring``, ``arm``) and its ``check``."""
    return lfm2_toy.load_probe('probe_solar_open2_reference.py')


# ------------------------------------------ the row of the engine's contract
def token_gap(params, hf, ids, at, out):
    return ref.token_gaps(ref.solar_open2_logits(params, hf, ids, at), [out]).max()


def _after_greedy(engine, params, records, lengths, backend):
    # 3 KDA layers hold a matrix state and the convolutions' rows, the 2
    # attention layers pages; the engine read all of it from cache_spec().
    pool = engine.telemetry['state_pool']
    assert pool['slots'] == 4 and pool['bytes_per_slot'] == 3 * (3 * 72 + 3 * 8 * 8) * 4
    assert sorted((leaf['count'], leaf['shape']) for leaf in pool['leaves']) == [
        (3, [3, 8, 8]), (3, [3, 72]),
    ]
    assert engine.telemetry['kv_pools']['kv']['block_shape'] == [BLOCK, 8]
    assert engine.telemetry['kv_pools']['kv']['layers'] == 2
    (request,) = [r for r in records if r['kind'] == 'request']
    assert {'state_slot', 'kv_first_block', 'kv_tail_block'} <= set(request)
    windows = [r for r in records if r['kind'] == 'decode']
    fields = {'kv_blocks', 'state_rows', 'moe_pairs', 'moe_pairs_held'}
    assert windows and all(fields <= set(r) for r in windows)
    # one live row: a step of it reads and writes its slot once, and routes
    # 2 experts in each of 5 layers, all 8 held
    steps = sum(r['tokens'] for r in windows)
    assert steps == 6 == sum(r['state_rows'] for r in windows)
    assert sum(r['moe_pairs'] for r in windows) == steps * 2 * 5
    assert sum(r['moe_pairs_held'] for r in windows) == steps * 2 * 5
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert prefills and all(r['route'] in ('paged', 'chunk') for r in prefills)
    assert sum(r['tokens'] for r in prefills) == sum(lengths)


def _check_left(engine, hf, params, fed, record):
    _, held = ref.forward(params, hf, np.asarray(fed)[None], [[0]])
    state, slot = engine.state_pool.state, record['state_slot']
    for xi, (want_state, want_conv) in enumerate(held[0]['kda']):  # all three
        assert ref.content_error(state['kda'][xi][slot], want_state) < 1e-5
        assert ref.content_error(state['conv'][xi][slot], want_conv) < 1e-5
    lfm2_toy.check_pages(engine, record, *held[0]['gqa'][0], len(fed))


def _check_sampled(engine, records):
    windows = [r for r in records if r['kind'] == 'decode']
    # every decoded token is one live row of one step; half the router's
    # experts are held, so some pairs are and some are not
    tokens = sum(r['tokens'] for r in windows)
    assert sum(r['state_rows'] for r in windows) == tokens
    assert sum(r['moe_pairs'] for r in windows) == tokens * 2 * 5
    assert 0 < sum(r['moe_pairs_held'] for r in windows) < tokens * 2 * 5


ENGINE_CASES = dict(
    refusal='cannot serve a hybrid',
    refused=('enable_prefix_cache', 'enable_mixed_batching', 'draft_k',
             'kv_cache_dtype=int8', 'quantization'),
    # fewer tokens than taps, one span, a span's end, and several spans
    greedy=[(n, (n,), 'xla') for n in (1, 2, 3, 8, 20)],
    after_greedy=_after_greedy,
    left=dict(seed=3, lengths=(6, 19, 11), max_tokens=13, check=_check_left),
    turnover=True,
    reuse=(1, 2, 4, 13),  # fewer tokens than taps, one span, and chunks
    preempt=dict(seed=3, n=12, num_blocks=11),
    sampled=dict(
        hf_over=dict(n_routed_experts=4, num_routed_experts=8), seed=4,
        lengths=(9, 30, 3), sampling=dict(temperature=0.7, top_p=0.9, max_tokens=9),
        check=_check_sampled,
    ),
    warm_prompt=10,
    unnamed=('solar', 'kda'),
)
