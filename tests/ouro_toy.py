"""A toy ``ouro`` (models/ouro.py) for the CPU tests: the published config's
keys at tiny widths (3 layers run 4 times, hidden 64, 4 heads of 16), seeded
weights with the exit gate drawn wide, an engine over it, and the paged path
driven by hand (rounds of prefill spans through the planes of every pass,
rows of unequal tails in one dispatch, then decode steps) so that its LOGITS
and every plane can be held against the plain reference."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference_ouro as ref
from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
from distllm_tpu.models import mistral, ouro

BLOCK = 4


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'ouro', 'vocab_size': 96, 'hidden_size': 64,
        'num_hidden_layers': 3, 'num_attention_heads': 4,
        'num_key_value_heads': 4, 'head_dim': 16, 'intermediate_size': 96,
        'hidden_act': 'silu', 'layer_types': ['full_attention'] * 3,
        'max_position_embeddings': 4096, 'max_window_layers': 3,
        'rms_norm_eps': 1e-6, 'rope_scaling': None, 'rope_theta': 1000000,
        'sliding_window': None, 'use_sliding_window': False,
        'tie_word_embeddings': False, 'total_ut_steps': 4,
        'early_exit_threshold': 1,
    }
    hf.update(over)
    return hf


@functools.lru_cache(maxsize=None)
def _tiny(seed, over):
    hf = tiny_hf(**dict(over))
    cfg = ouro.OuroConfig.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = ouro.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits, norm
    # scales away from one so that each of a layer's four norms tells, and
    # the gate and its bias drawn wide so that tokens leave at every pass.
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1000), 64))

    def widen(path, a):
        name = str(getattr(path[-1], 'key', ''))
        if name == 'scale':
            return a * jax.random.uniform(next(keys), a.shape, a.dtype, 0.5, 1.5)
        return a * 6.0

    params = jax.tree_util.tree_map_with_path(widen, params)
    h = cfg.hidden_size
    params['exit_gate'] = {
        'kernel': jax.random.normal(next(keys), (h, 1), jnp.float32) * 0.25,
        'bias': jax.random.normal(next(keys), (1,), jnp.float32),
    }
    return hf, cfg, params


def tiny(seed=0, **over):
    """``(hf, cfg, params)``; the weights of a (seed, widths) are made once
    a process (nothing here writes to them)."""
    over = {k: tuple(v) if isinstance(v, list) else v for k, v in over.items()}
    hf, cfg, params = _tiny(seed, tuple(sorted(over.items())))
    hf = {k: list(v) if isinstance(v, tuple) else v for k, v in hf.items()}
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


def paged_logits(cfg, params, rows, *, chunk=8, backend='xla', module=ouro):
    """Every row of ``rows`` (``(tokens, n_prompt)`` each) through the paged
    path as the engine drives it, all rows in one dispatch a round: prefill
    of each row's first ``n_prompt`` tokens in ``chunk``-token spans (a row
    whose prompt has ended is a pad row of the later rounds), then one
    decode step a token (teacher-forced; a row that has ended is not live).
    Returns ``([logits_i [len_i - n_i + 1, V]], (k, v), tables, counts)``
    with the logits at positions ``n_i - 1`` onward of row ``i`` and the
    decode steps' counts added up."""
    b = len(rows)
    total = max(len(tokens) for tokens, _ in rows)
    width = -(-total // BLOCK)
    lanes = cfg.num_kv_heads * cfg.head_size
    planes = cfg.cache_spec().paged[0].num_layers
    k, v = (
        jnp.zeros((planes, b * width + 1, BLOCK, lanes), jnp.float32)
        for _ in range(2)
    )
    tables = 1 + np.arange(b * width, dtype=np.int32).reshape(b, width)
    rope = mistral._rope_tables(cfg, total)
    prefill = jax.jit(
        lambda k, v, ids, positions, table, ctx, tails: module.prefill_paged(
            params, cfg, ids, positions, k, v, table, ctx, tails,
            max_table_positions=total, attn_backend=backend,
        )
    )
    decode = jax.jit(
        lambda k, v, ids, pos, table, ctx, live: module._decode_core(
            params, cfg, rope, backend, ids, pos, ctx, (k, v), table, live,
        )
    )
    out = [[] for _ in rows]
    for start in range(0, max(n for _, n in rows), chunk):
        tails = np.asarray([min(max(n - start, 0), chunk) for _, n in rows])
        ids = np.zeros((b, chunk), np.int32)
        for i, (tokens, _) in enumerate(rows):
            ids[i, :tails[i]] = tokens[start:start + tails[i]]
        positions = np.minimum(start + np.arange(chunk), total - 1)[None]
        last, k, v = prefill(
            k, v, jnp.asarray(ids),
            jnp.asarray(np.repeat(positions, b, axis=0)),
            jnp.asarray(np.where(tails[:, None] > 0, tables, 0)),
            jnp.asarray(start + tails), jnp.asarray(tails),
        )
        for i, (_, n) in enumerate(rows):
            if start < n <= start + chunk:
                out[i].append(np.asarray(last[i]))
    counts = 0
    for step in range(max(len(tokens) - n for tokens, n in rows)):
        live = np.asarray([n + step < len(tokens) for tokens, n in rows])
        pos = np.asarray([min(n + step, len(tokens) - 1) for tokens, n in rows])
        ids = np.asarray([tokens[p] for (tokens, _), p in zip(rows, pos)])
        logits, (k, v), step_counts = decode(
            k, v, jnp.asarray(ids), jnp.asarray(pos),
            jnp.asarray(np.where(live[:, None], tables, 0)),
            jnp.asarray(pos + 1), jnp.asarray(live),
        )
        counts = counts + np.asarray(step_counts)
        for i in np.flatnonzero(live):
            out[i].append(np.asarray(logits[i]))
    return [np.stack(o) for o in out], (k, v), tables, counts


def reference_logits(params, hf, tokens, first, **kw):
    """The plain reference's logits at positions ``first`` onward of one
    row ``tokens``."""
    at = np.arange(first, len(tokens))[None]
    return ref.forward(params, hf, np.asarray(tokens)[None], at, **kw)['logits'][0]


# ------------------------------------------ the row of the engine's contract
def token_gap(params, hf, ids, at, out):
    return ref.token_gaps(ref.forward(params, hf, ids, at)['logits'], [out]).max()


def _after_greedy(engine, params, records, lengths, backend):
    assert engine.telemetry['loop_window_form'] == 'passes_rolled_layers_unrolled'
    assert engine.kv.pool_shape[0] == 12  # one pool of T * L planes
    windows = [r for r in records if r['kind'] == 'decode']
    prefills = [r for r in records if r['kind'] == 'prefill']
    assert windows and prefills
    for r in windows + prefills:
        assert r['loop_passes'] == 4 and r['kv_planes'] == 12
    assert all(r['route'] in ('paged', 'chunk') for r in prefills)
    # at the published threshold every decoded token's head reads the last pass
    exits = np.sum([r['loop_exit_pass'] for r in windows], axis=0)
    np.testing.assert_array_equal(exits, [0, 0, 0, sum(r['tokens'] for r in windows)])
    # the step records price four sweeps of the stack
    stack = sum(leaf.size for leaf in jax.tree.leaves(params['layers']))
    rest = sum(leaf.size for leaf in jax.tree.leaves(params)) - stack
    assert engine._cost_model.n_params == 4 * stack + rest


def _check_left(engine, hf, params, fed, record):
    """In a plane of the first pass and in one of the last."""
    planes = (0, 2, 9, 11)  # pass 0's first and last layer, pass 3's
    want = ref.forward(params, hf, np.asarray(fed)[None], [[0]], planes=planes)
    at = (len(fed) - 1) // BLOCK * BLOCK
    for plane in planes:
        for pool, rows in zip((engine.kv.k, engine.kv.v), want['planes'][plane]):
            first = np.asarray(pool[plane][np.asarray([record['kv_first_block']])])[0]
            assert ref.content_error(first, rows[0, :BLOCK]) < 1e-5
            tail = np.asarray(pool[plane][np.asarray([record['kv_tail_block']])])[0]
            assert ref.content_error(tail[:len(fed) - at], rows[0, at:]) < 1e-5


ENGINE_CASES = dict(
    refusal='cannot serve a looped model',
    refused=('enable_mixed_batching', 'draft_k', 'kv_cache_dtype=int8', 'quantization'),
    greedy=[(n, (n,), 'xla') for n in (1, 3, 8, 20)],
    after_greedy=_after_greedy,
    left=dict(seed=3, lengths=(6, 19, 11), max_tokens=13, check=_check_left),
    turnover=True,
    preempt=dict(seed=3, n=12, num_blocks=11, roomy=True),
    sampled=dict(
        seed=4, lengths=(9, 30, 3),
        sampling=dict(temperature=0.5, top_p=0.95, max_tokens=9),
    ),
    warm_prompt=10,
    unnamed=('ouro',),
)
