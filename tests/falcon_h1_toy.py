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
    from benchmarks import reference_falcon_h1 as ref

    at = np.arange(first, len(tokens))[None]
    return ref.falcon_h1_logits(params, hf, np.asarray(tokens)[None], at)[0]
