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
    from benchmarks import reference_solar_open2 as ref

    at = np.arange(first, len(tokens))[None]
    return ref.solar_open2_logits(params, hf, np.asarray(tokens)[None], at)[0]


@functools.cache
def load_probe() -> dict:
    """``scripts/probe_solar_open2_reference.py``'s globals: the wrong
    programs the cell's limits have to catch (``_beta_half``,
    ``_scalar_decay``, ``_softmax_scoring``, ``arm``) and its ``check``."""
    import runpy
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root / 'scripts'))  # it imports its neighbours
    try:
        return runpy.run_path(str(root / 'scripts/probe_solar_open2_reference.py'))
    finally:
        sys.path.remove(str(root / 'scripts'))
