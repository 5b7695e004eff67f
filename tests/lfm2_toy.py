"""A toy ``lfm2_moe`` (models/lfm2.py) for the CPU tests: the published
config's keys at tiny widths, seeded weights, an engine over it, and the
paged path driven by hand (rounds of prefill spans through the pools and the
state, rows of unequal tails in one dispatch, then decode steps) so that its
LOGITS can be held against the plain reference."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
from distllm_tpu.models import lfm2

BLOCK = 4
# Not periodic, as published: runs of 2, 1 and 1 conv layers, the two
# leading layers dense, attention under a sparse MLP.
LAYERS = ('conv', 'conv', 'full_attention', 'conv', 'full_attention', 'conv')


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'lfm2_moe', 'vocab_size': 96, 'hidden_size': 64,
        'layer_types': list(LAYERS), 'num_hidden_layers': len(LAYERS),
        'num_attention_heads': 4, 'num_key_value_heads': 2,
        'conv_L_cache': 3, 'conv_bias': False, 'intermediate_size': 96,
        'moe_intermediate_size': 24, 'num_dense_layers': 2,
        'num_experts': 8, 'num_experts_per_tok': 3, 'norm_topk_prob': True,
        'use_expert_bias': True, 'routed_scaling_factor': 1.0,
        'norm_eps': 1e-5, 'rope_theta': 10000,
        'max_position_embeddings': 4096,
    }
    hf.update(over)
    return hf


@functools.lru_cache(maxsize=None)
def _tiny(seed, over):
    hf = tiny_hf(**dict(over))
    cfg = lfm2.Lfm2MoeConfig.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = lfm2.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits
    # (the taps are of size one already); the selection bias larger still,
    # so that it changes who is chosen.
    params = jax.tree.map(lambda a: a * 4.0 if a.ndim > 1 else a, params)
    params['conv']['conv']['taps'] = params['conv']['conv']['taps'] / 4.0
    bias = params['sparse']['router_bias']
    bias['bias'] = bias['bias'] * 2.0
    return hf, cfg, params


def tiny(seed=0, **over):
    """``(hf, cfg, params)``; the weights of a (seed, widths) are made once
    a process (nothing here writes to them)."""
    over = {k: tuple(v) if isinstance(v, list) else v for k, v in over.items()}
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


def paged_logits(cfg, params, rows, *, chunk=8, backend='xla', stale=None,
                 module=lfm2):
    """Every row of ``rows`` (``(tokens, n_prompt)`` each) through the paged
    path as the engine drives it, all rows in one dispatch a round: prefill
    of each row's first ``n_prompt`` tokens in ``chunk``-token spans (a row
    whose prompt has ended is a pad row of the later rounds), then one
    decode step a token (teacher-forced; a row that has ended is not live).
    Row ``i`` holds slot ``i``; ``stale`` fills the state pool before the
    first span. Returns ``([logits_i [len_i - n_i + 1, V]], (k, v, state))``
    with the logits at positions ``n_i - 1`` onward of row ``i``."""
    b = len(rows)
    total = max(len(tokens) for tokens, _ in rows)
    width = -(-total // BLOCK)
    lanes = cfg.num_kv_heads * cfg.head_size
    k, v = (
        jnp.zeros((cfg.num_paged_layers, b * width + 1, BLOCK, lanes), jnp.float32)
        for _ in range(2)
    )
    tables = 1 + np.arange(b * width, dtype=np.int32).reshape(b, width)
    state = jax.tree.map(
        lambda s: jnp.full((b, *s.shape), 0.0 if stale is None else stale, s.dtype),
        cfg.state_spec(),
    )
    rope = module._rope_tables(cfg, total)
    # One program a kind of dispatch, as the engine has.
    prefill = jax.jit(
        lambda k, v, state, ids, positions, table, ctx, tails, slots:
        module.prefill_paged(
            params, cfg, ids, positions, k, v, table, ctx, tails, state,
            slots, max_table_positions=total, attn_backend=backend,
        )
    )
    decode = jax.jit(
        lambda k, v, state, ids, pos, table, ctx, live: module._decode_core(
            params, cfg, rope, backend, ids, pos, ctx, (k, v, state), table, live,
        )
    )
    out = [[] for _ in rows]
    for start in range(0, max(n for _, n in rows), chunk):
        tails = np.asarray([min(max(n - start, 0), chunk) for _, n in rows])
        ids = np.zeros((b, chunk), np.int32)
        for i, (tokens, _) in enumerate(rows):
            ids[i, :tails[i]] = tokens[start:start + tails[i]]
        positions = np.minimum(start + np.arange(chunk), total - 1)[None]
        last, k, v, state = prefill(
            k, v, state, jnp.asarray(ids),
            jnp.asarray(np.repeat(positions, b, axis=0)),
            jnp.asarray(np.where(tails[:, None] > 0, tables, 0)),
            jnp.asarray(start + tails), jnp.asarray(tails),
            jnp.asarray(np.where(tails > 0, np.arange(b), b)),
        )
        for i, (_, n) in enumerate(rows):
            if start < n <= start + chunk:
                out[i].append(np.asarray(last[i]))
    for step in range(max(len(tokens) - n for tokens, n in rows)):
        live = np.asarray([n + step < len(tokens) for tokens, n in rows])
        pos = np.asarray([min(n + step, len(tokens) - 1) for tokens, n in rows])
        ids = np.asarray([tokens[p] for (tokens, _), p in zip(rows, pos)])
        logits, (k, v, state), _ = decode(
            k, v, state, jnp.asarray(ids), jnp.asarray(pos),
            jnp.asarray(np.where(live[:, None], tables, 0)),
            jnp.asarray(pos + 1), jnp.asarray(live),
        )
        for i in np.flatnonzero(live):
            out[i].append(np.asarray(logits[i]))
    return [np.stack(o) for o in out], (k, v, state)
