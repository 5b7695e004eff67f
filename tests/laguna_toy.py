"""A toy ``laguna`` (models/laguna.py) for the CPU tests: the published
config's keys at tiny widths, seeded weights, an engine over it, and the
paged path driven by hand (prefill in chunks through both cache groups with
``WindowBlocks`` freeing behind the window, then decode steps) so that its
LOGITS can be held against the plain reference."""

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
from distllm_tpu.generate.engine.kv_cache import WindowBlocks, window_bound
from distllm_tpu.models import laguna

WINDOW = 12
BLOCK = 4
LAYERS = ('full_attention', 'sliding_attention', 'sliding_attention',
          'sliding_attention', 'full_attention', 'sliding_attention')


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'laguna', 'vocab_size': 96, 'hidden_size': 64,
        'intermediate_size': 96, 'num_hidden_layers': len(LAYERS),
        'num_attention_heads': 4, 'num_key_value_heads': 2, 'head_dim': 16,
        'max_position_embeddings': 4096, 'attention_bias': False,
        'rms_norm_eps': 1e-6, 'num_experts': 8, 'num_experts_per_tok': 2,
        'moe_intermediate_size': 24, 'shared_expert_intermediate_size': 24,
        'tie_word_embeddings': False, 'gating': True,
        'sliding_window': WINDOW,
        'rope_parameters': {
            'full_attention': {
                'rope_theta': 500000, 'rope_type': 'yarn', 'factor': 8,
                'original_max_position_embeddings': 32, 'beta_slow': 1,
                'beta_fast': 4, 'attention_factor': 1.2,
                'partial_rotary_factor': 0.5,
            },
            'sliding_attention': {
                'rope_type': 'default', 'rope_theta': 10000,
                'partial_rotary_factor': 1,
            },
        },
        'layer_types': list(LAYERS),
        'moe_apply_router_weight_on_input': False,
        'partial_rotary_factor': 0.5,
        'mlp_layer_types': ['dense'] + ['sparse'] * (len(LAYERS) - 1),
        'moe_routed_scaling_factor': 2.5,
        'num_attention_heads_per_layer': [
            4 if t == 'full_attention' else 6 for t in LAYERS
        ],
    }
    hf.update(over)
    return hf


def tiny(seed=0, **over):
    hf = tiny_hf(**over)
    cfg = laguna.LagunaConfig.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = laguna.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits.
    params = jax.tree.map(lambda a: a * 4.0 if a.ndim > 1 else a, params)
    return hf, cfg, params


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
                 blocks_cls=WindowBlocks, int8=False):
    """Logits at positions ``n_prompt - 1`` onward of ``tokens`` through the
    paged path as the engine drives it: prefill of the first ``n_prompt`` in
    ``chunk``-token spans, then one decode step a token (teacher-forced).
    The window group's pool is as small as one sequence's bound, so freed
    ids come back; returns ``(logits [len(tokens) - n_prompt + 1, V], the
    WindowBlocks)``."""
    from distllm_tpu.ops.paged_attention import QuantizedKV

    total = len(tokens)
    full_blocks = -(-total // BLOCK) + 1
    window_blocks = blocks_cls(
        window_bound(cfg.sliding_window, BLOCK, chunk) + 1, BLOCK,
        cfg.sliding_window,
    )
    width = -(-total // BLOCK)

    def pool(layers, blocks):
        # stacked over the group's layers, a token's heads folded
        shape = (layers, blocks, BLOCK, cfg.num_kv_heads * cfg.head_dim)
        if int8:
            return QuantizedKV(
                jnp.zeros(shape, jnp.int8),
                jnp.zeros((layers, blocks, cfg.num_kv_heads), jnp.float32),
            )
        return jnp.zeros(shape, jnp.float32)

    k = (pool(cfg.count('full'), full_blocks),
         pool(cfg.count('window'), window_blocks.num_blocks))
    v = (pool(cfg.count('full'), full_blocks),
         pool(cfg.count('window'), window_blocks.num_blocks))
    full_row = np.zeros((width,), np.int32)
    full_row[:] = 1 + np.arange(width)

    def tables():
        row = window_blocks.table_row(0, np.zeros((width,), np.int32))
        return jnp.asarray(full_row[None]), jnp.asarray(row[None])

    # One program a kind of dispatch, as the engine has (called bare, the
    # model makes and compiles its jitted layers anew a call: ``once_a_kind``).
    prefill_chunk = jax.jit(
        lambda params, *arrays: laguna.prefill_paged(
            params, cfg, *arrays, max_table_positions=total,
            attn_backend=backend,
        )
    )
    decode_step = jax.jit(
        lambda params, rope, *arrays: laguna._decode_core(
            params, cfg, rope, backend, *arrays
        )
    )
    out = []
    for start in range(0, n_prompt, chunk):
        ntok = min(chunk, n_prompt - start)
        window_blocks.cover(0, start, start + ntok)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :ntok] = tokens[start:start + ntok]
        positions = np.minimum(start + np.arange(chunk), total - 1)[None]
        last, k, v = prefill_chunk(
            params, jnp.asarray(ids), jnp.asarray(positions), k, v,
            tables(), jnp.asarray([start + ntok]), jnp.asarray([ntok]),
        )
        window_blocks.trim_behind(0, start + ntok)
    out.append(np.asarray(last[0]))
    rope = laguna._rope_tables(cfg, total)
    for pos in range(n_prompt, total):
        window_blocks.cover(0, pos, pos + 1)
        step, (k, v), _ = decode_step(
            params, rope, jnp.asarray([tokens[pos]]), jnp.asarray([pos]),
            jnp.asarray([pos + 1]), (k, v), tables(), jnp.asarray([True]),
        )
        out.append(np.asarray(step[0]))
    return np.stack(out), window_blocks
