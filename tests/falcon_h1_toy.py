"""A toy ``falcon_h1`` (models/falcon_h1.py) for the CPU tests: the published
config's keys at tiny widths (2 B/C groups, a state size and a head size that
differ, 5 queries a KV head, an inner width that is not twice the hidden one,
every multiplier away from one), seeded weights, an engine over it, and the
paged path driven by hand (``lfm2_toy.paged_logits``: rounds of prefill spans
through the pools and the state, rows of unequal tails in one dispatch, then
decode steps) so that its LOGITS can be held against the plain reference."""

import functools

import jax
import numpy as np

from benchmarks import reference_falcon_h1 as ref
from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
from distllm_tpu.models import falcon_h1
import lfm2_toy

BLOCK = lfm2_toy.BLOCK
NoTokenizer = lfm2_toy.NoTokenizer
prompt = lfm2_toy.prompt
spread = lfm2_toy.spread


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'falcon_h1', 'vocab_size': 96, 'hidden_size': 40,
        'num_hidden_layers': 3, 'num_attention_heads': 5,
        'num_key_value_heads': 1, 'head_dim': 8, 'intermediate_size': 64,
        'mamba_n_heads': 4, 'mamba_d_head': 6, 'mamba_d_ssm': 24,
        'mamba_d_state': 16, 'mamba_n_groups': 2, 'mamba_d_conv': 4,
        'mamba_chunk_size': 8, 'mamba_expand': 2, 'mamba_conv_bias': True,
        'mamba_proj_bias': False, 'mamba_rms_norm': True,
        'mamba_norm_before_gate': False, 'attention_bias': False,
        'mlp_bias': False, 'projectors_bias': False, 'hidden_act': 'silu',
        'embedding_multiplier': 5.5, 'lm_head_multiplier': 0.5,
        'attention_in_multiplier': 1.25, 'attention_out_multiplier': 0.6,
        'key_multiplier': 0.7, 'ssm_in_multiplier': 0.5,
        'ssm_out_multiplier': 0.8,
        'ssm_multipliers': [0.9, 0.7, 1.3, 1.6, 0.8],
        'mlp_multipliers': [0.75, 0.5], 'rope_theta': 1e11,
        'rope_scaling': None, 'rms_norm_eps': 1e-5,
        'tie_word_embeddings': False, 'max_position_embeddings': 4096,
    }
    hf.update(over)
    return hf


@functools.lru_cache(maxsize=None)
def _tiny(seed, over):
    hf = tiny_hf(**dict(over))
    cfg = falcon_h1.FalconH1Config.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = falcon_h1.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits;
    # one group's norm scale larger than the other's, so that a norm over
    # all channels at once differs from a group's apart.
    params = jax.tree.map(
        lambda a: a * 6.0 if a.ndim >= 3 and a.shape[-1] > 4 else a, params
    )
    params['embed'] = params['embed'] * 6.0
    params['head']['kernel'] = params['head']['kernel'] * 6.0
    taps = params['layers']['conv']
    params['layers']['conv'] = taps / 6.0
    return hf, cfg, params


def tiny(seed=0, **over):
    """``(hf, cfg, params)``; the weights of a (seed, widths) are made once
    a process (nothing here writes to them)."""
    over = {k: tuple(v) if isinstance(v, list) else v for k, v in over.items()}
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


def paged_logits(cfg, params, rows, **kw):
    """``lfm2_toy.paged_logits`` over this module's programs."""
    return lfm2_toy.paged_logits(cfg, params, rows, module=falcon_h1, **kw)


def reference_logits(params, hf, tokens, first):
    """The plain reference's logits at positions ``first`` onward of one
    row ``tokens``."""
    at = np.arange(first, len(tokens))[None]
    return ref.falcon_h1_logits(params, hf, np.asarray(tokens)[None], at)[0]


# ------------------------------------------ the row of the engine's contract
def token_gap(params, hf, ids, at, out):
    return ref.token_gaps(ref.falcon_h1_logits(params, hf, ids, at), [out]).max()


def _after_greedy(engine, params, records, lengths, backend):
    # Every one of the 3 layers holds pages AND both kinds of state.
    pool = engine.telemetry['state_pool']
    assert pool['slots'] == 4 and pool['bytes_per_slot'] == 3 * (3 * 88 + 4 * 6 * 16) * 4
    assert sorted((leaf['count'], leaf['shape']) for leaf in pool['leaves']) == [
        (3, [3, 88]), (3, [4, 6, 16]),
    ]
    assert engine.telemetry['kv_pools']['kv']['block_shape'] == [BLOCK, 8]
    assert engine.telemetry['kv_pools']['kv']['layers'] == 3
    (request,) = [r for r in records if r['kind'] == 'request']
    assert {'state_slot', 'kv_first_block', 'kv_tail_block'} <= set(request)
    windows = [r for r in records if r['kind'] == 'decode']
    assert windows and all({'kv_blocks', 'state_rows'} <= set(r) for r in windows)
    assert all('moe_pairs' not in r for r in windows)
    # one live row: a step of it reads and writes its slot once
    assert sum(r['state_rows'] for r in windows) == sum(r['tokens'] for r in windows) == 6


def _check_left(engine, hf, params, fed, record):
    """In the first layer, and something in the last."""
    _, held = ref.forward(params, hf, np.asarray(fed)[None], [[0]])
    want_ssm, want_conv, want_k, want_v = held[0]
    state, slot = engine.state_pool.state, record['state_slot']
    assert ref.content_error(state['ssm'][0][slot], want_ssm) < 1e-5
    assert ref.content_error(state['conv'][0][slot], want_conv) < 1e-5
    assert np.asarray(state['ssm'][2][slot]).any()
    lfm2_toy.check_pages(engine, record, want_k, want_v, len(fed))


def _check_sampled(engine, records):
    windows = [r for r in records if r['kind'] == 'decode']
    # every decoded token is one live row of one step
    assert sum(r['state_rows'] for r in windows) == sum(r['tokens'] for r in windows)


ENGINE_CASES = dict(
    refusal='cannot serve a hybrid',
    # a layer that holds pages AND state changes none of it
    refused=('enable_prefix_cache', 'enable_mixed_batching', 'draft_k',
             'kv_cache_dtype=int8', 'quantization'),
    greedy=[(n, (n,), 'xla') for n in (1, 2, 3, 8, 20)],
    after_greedy=_after_greedy,
    left=dict(seed=3, lengths=(6, 19, 11), max_tokens=13, check=_check_left),
    turnover=True,
    reuse=(1, 2, 4, 13),  # fewer tokens than taps, one span, and chunks
    preempt=dict(seed=3, n=12, num_blocks=11),
    sampled=dict(
        seed=4, lengths=(9, 30, 3),
        sampling=dict(temperature=0.7, top_p=0.9, max_tokens=9), check=_check_sampled,
    ),
    warm_prompt=10,
)
