"""A toy ``deepseek_v3`` (models/deepseek_v3.py) for the CPU tests: the
published config's keys at tiny widths, seeded weights, an engine over it,
and the paged path driven by hand (prefill in chunks through the latent
pool, then decode steps) so that its LOGITS can be held against the plain
reference."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
from distllm_tpu.models import deepseek_v3

BLOCK = 4


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'deepseek_v3', 'vocab_size': 96, 'hidden_size': 64,
        'intermediate_size': 96, 'num_hidden_layers': 3,
        'num_attention_heads': 4, 'num_key_value_heads': 4, 'head_dim': 8,
        'qk_nope_head_dim': 16, 'qk_rope_head_dim': 8, 'qk_head_dim': 24,
        'v_head_dim': 16, 'kv_lora_rank': 128, 'q_lora_rank': None,
        'max_position_embeddings': 4096, 'attention_bias': False,
        'hidden_act': 'silu', 'rms_norm_eps': 1e-6,
        'first_k_dense_replace': 1, 'moe_layer_freq': 1,
        'n_routed_experts': 8, 'n_shared_experts': 2,
        'num_experts_per_tok': 3, 'moe_intermediate_size': 24,
        'n_group': 1, 'topk_group': 1, 'norm_topk_prob': True,
        'scoring_func': 'sigmoid', 'topk_method': 'noaux_tc',
        'routed_scaling_factor': 2.448, 'rope_theta': 10000,
        'rope_scaling': None, 'rope_interleave': True,
        'tie_word_embeddings': False,
    }
    hf.update(over)
    return hf


@functools.lru_cache(maxsize=None)
def _tiny(seed, over):
    hf = tiny_hf(**dict(over))
    cfg = deepseek_v3.DeepseekV3Config.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = deepseek_v3.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits;
    # the selection bias larger still, so that it changes who is chosen.
    params = jax.tree.map(lambda a: a * 4.0 if a.ndim > 1 else a, params)
    bias = params['sparse']['router_bias']
    bias['kernel'] = bias['kernel'] * 2.0
    return hf, cfg, params


def tiny(seed=0, **over):
    """``(hf, cfg, params)``; the weights of a (seed, widths) are made once
    a process (nothing here writes to them)."""
    hf, cfg, params = _tiny(seed, tuple(sorted(over.items())))
    return dict(hf), cfg, params


class NoTokenizer:
    eos_id = None


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


def prompt(rng, n):
    return [int(t) for t in rng.integers(4, 96, n)]


def spread(a, b):
    """Largest difference as a share of the reference's spread."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / b.std())


def paged_logits(cfg, params, tokens, n_prompt, *, chunk=8, backend='xla',
                 module=deepseek_v3):
    """Logits at positions ``n_prompt - 1`` onward of ``tokens`` through the
    paged path as the engine drives it: prefill of the first ``n_prompt`` in
    ``chunk``-token spans, then one decode step a token (teacher-forced).
    Returns ``(logits [len(tokens) - n_prompt + 1, V], the planes)``."""
    total = len(tokens)
    width = -(-total // BLOCK)
    planes = tuple(
        jnp.zeros((width + 1, BLOCK, cfg.stored_row), jnp.float32)
        for _ in range(cfg.num_layers)
    )
    table = jnp.asarray((1 + np.arange(width, dtype=np.int32))[None])
    rope = module._rope_tables(cfg, total)
    # One program a kind of dispatch, as the engine has.
    prefill = jax.jit(lambda planes, ids, positions, ctx, tails: module.prefill_paged(
        params, cfg, ids, positions, planes, (), table, ctx, tails,
        max_table_positions=total, attn_backend=backend,
    ))
    decode = jax.jit(lambda planes, ids, pos, ctx: module._decode_core(
        params, cfg, rope, backend, ids, pos, ctx, (planes,), table,
        jnp.asarray([True]),
    ))
    out = []
    for start in range(0, n_prompt, chunk):
        ntok = min(chunk, n_prompt - start)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :ntok] = tokens[start:start + ntok]
        positions = np.minimum(start + np.arange(chunk), total - 1)[None]
        last, planes, _ = prefill(
            planes, jnp.asarray(ids), jnp.asarray(positions),
            jnp.asarray([start + ntok]), jnp.asarray([ntok]),
        )
    out.append(np.asarray(last[0]))
    for pos in range(n_prompt, total):
        step, (planes,), _ = decode(
            planes, jnp.asarray([tokens[pos]]), jnp.asarray([pos]),
            jnp.asarray([pos + 1]),
        )
        out.append(np.asarray(step[0]))
    return np.stack(out), planes
