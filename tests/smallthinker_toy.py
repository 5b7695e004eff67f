"""A toy ``smallthinker`` (models/smallthinker.py) for the CPU tests: the
published config's keys at tiny widths (8 layers in two periods, 7 queries a
KV head, 8 experts of which 3 a token, window 24), seeded weights, an engine
over it, and the paged path driven by hand (prefill in chunks through both
cache groups with ``WindowBlocks`` freeing behind the window, then decode
steps) so that its LOGITS can be held against the plain reference."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference_smallthinker as ref
from benchmarks.drivers import smallthinker_closed
from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
from distllm_tpu.generate.engine.kv_cache import WindowBlocks, window_bound
from distllm_tpu.models import smallthinker

WINDOW = 24
BLOCK = 4
LAYOUT = [0, 1, 1, 1, 0, 1, 1, 1]


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'smallthinker', 'vocab_size': 96, 'hidden_size': 64,
        'num_hidden_layers': len(LAYOUT), 'num_attention_heads': 7,
        'num_key_value_heads': 1, 'head_dim': 16,
        'max_position_embeddings': 4096, 'rms_norm_eps': 1e-6,
        'moe_num_primary_experts': 8, 'moe_num_active_primary_experts': 3,
        'moe_ffn_hidden_size': 32, 'moe_primary_router_apply_softmax': True,
        'norm_topk_prob': True, 'tie_word_embeddings': False,
        'sliding_window_size': WINDOW, 'sliding_window_layout': list(LAYOUT),
        'rope_layout': list(LAYOUT), 'rope_theta': 1500000,
        'rope_scaling': None,
    }
    hf.update(over)
    return hf


def tiny(seed=0, scale=4.0, **over):
    hf = tiny_hf(**over)
    cfg = smallthinker.SmallThinkerConfig.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = smallthinker.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits.
    params = jax.tree.map(lambda a: a * scale if a.ndim > 1 else a, params)
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
                 module=smallthinker):
    """Logits at positions ``n_prompt - 1`` onward of ``tokens`` through the
    paged path as the engine drives it: prefill of the first ``n_prompt`` in
    ``chunk``-token spans, then one decode step a token (teacher-forced).
    The window group's pool is as small as one sequence's bound, so freed
    ids come back; returns ``(logits [len(tokens) - n_prompt + 1, V], (k, v)
    pools, the full row's block ids, the WindowBlocks)``."""
    total = len(tokens)
    full_blocks = -(-total // BLOCK) + 1
    window_blocks = WindowBlocks(
        window_bound(cfg.sliding_window, BLOCK, chunk) + 1, BLOCK,
        cfg.sliding_window,
    )
    width = -(-total // BLOCK)

    def pool(layers, blocks):
        # stacked over the group's layers, a token's heads folded
        shape = (layers, blocks, BLOCK, cfg.num_kv_heads * cfg.head_dim)
        return jnp.zeros(shape, jnp.float32)

    k = (pool(cfg.count('full'), full_blocks),
         pool(cfg.count('window'), window_blocks.num_blocks))
    v = (pool(cfg.count('full'), full_blocks),
         pool(cfg.count('window'), window_blocks.num_blocks))
    full_row = 1 + np.arange(width, dtype=np.int32)

    def tables():
        row = window_blocks.table_row(0, np.zeros((width,), np.int32))
        return jnp.asarray(full_row[None]), jnp.asarray(row[None])

    prefill_chunk = jax.jit(
        lambda params, *arrays: module.prefill_paged(
            params, cfg, *arrays, max_table_positions=total,
            attn_backend=backend,
        )
    )
    decode_step = jax.jit(
        lambda params, rope, *arrays: module._decode_core(
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
    rope = module._rope_table(cfg, total)
    for pos in range(n_prompt, total):
        window_blocks.cover(0, pos, pos + 1)
        step, (k, v), _ = decode_step(
            params, rope, jnp.asarray([tokens[pos]]), jnp.asarray([pos]),
            jnp.asarray([pos + 1]), (k, v), tables(), jnp.asarray([True]),
        )
        out.append(np.asarray(step[0]))
    return np.stack(out), (k, v), full_row, window_blocks


# ------------------------------------------ the row of the engine's contract
def token_gap(params, hf, ids, at, out):
    return ref.smallthinker_token_gaps(params, hf, ids, at, [out])[0].max()


def _after_greedy(engine, params, records, lengths, backend):
    assert engine.window_blocks.num_held == 0  # everything went back
    assert engine.window_blocks.freed_total > 0
    assert engine.telemetry['attn_backend'] == backend


def _check_left(engine, hf, params, tokens, record):
    """The cell's own page check: layer 0's K and V in the full group's
    first and tail block, layer 1's in the window group's first HELD block
    (the window's lower edge) and tail block, against the reference's keys
    and values; pages rolled by a slot read as wrong."""
    ends = smallthinker_closed._ends(record, BLOCK)
    first_held = ends['window'][1][0]
    assert first_held <= max(0, len(tokens) - WINDOW) and min(ends['window'][0]) >= 1
    if len(tokens) > WINDOW + BLOCK:
        assert first_held > 0  # blocks behind the window went back
    pools = {'full': engine.kv, 'window': engine.window_kv}
    pages = {
        group: (at, *(
            np.asarray(side[0][np.asarray(blocks)], np.float32)
            for side in (pools[group].k, pools[group].v)
        ))
        for group, (blocks, at) in ends.items()
    }
    _, kept = ref.smallthinker_logits(
        params, hf, [tokens], [[len(tokens) - 1]], keep=(0, 1), fields=('k', 'v'),
    )
    errors = smallthinker_closed._page_errors(pages, kept[0], len(tokens))
    assert max(errors.values()) < 1e-5
    rolled = {
        g: (at, np.roll(k, 1, axis=1), np.roll(v, 1, axis=1))
        for g, (at, k, v) in pages.items()
    }
    wrong = smallthinker_closed._page_errors(rolled, kept[0], len(tokens))
    assert min(wrong.values()) > 0.5


def _check_sampled(engine, records):
    """The windowed pool's size and the rows under the window on the records."""
    pools = engine.telemetry['kv_pools']
    assert (pools['full']['layers'], pools['window']['layers']) == (2, 6)
    assert pools['window']['window'] == WINDOW
    steps = [r for r in records if r['kind'] in ('prefill', 'decode')]
    assert steps and all(
        r['kv_window_pool_blocks'] == engine.window_blocks.num_blocks - 1
        and 0 <= r['rows_under_window'] <= r['batch']
        and r['kv_blocks_window'] <= r['kv_window_pool_blocks'] for r in steps
    )
    decodes = [r for r in steps if r['kind'] == 'decode' and r['batch'] == 3]
    # Two rows under the window at first, one once the second has crossed.
    assert [r['rows_under_window'] for r in decodes][0] == 2
    assert [r['rows_under_window'] for r in decodes][-1] == 1
    # 8 layers x 3 picks a token; half the experts are held.
    windows = [r for r in steps if r['kind'] == 'decode']
    assert sum(r['moe_pairs'] for r in windows) == 24 * sum(r['tokens'] for r in windows)
    assert all(0 < r['moe_pairs_held'] < r['moe_pairs'] for r in windows)
    assert {r['moe_form'] for r in windows} == {'dense'}


ENGINE_CASES = dict(
    refusal='cannot serve a model with a windowed',
    refused=('enable_prefix_cache', 'host_kv_tier_bytes', 'enable_mixed_batching',
             'draft_k', 'kv_cache_dtype=int8', 'quantization'),
    # rows that stay under the window of 24, cross it while they decode and
    # start past it, prefilled in chunks of 8
    greedy=[(1, (5, 17, 61), 'xla'), (1, (17,), 'interpret')],
    greedy_tokens=14,
    after_greedy=_after_greedy,
    # under, across, past
    left=dict(seed=8, lengths=(40, 13, 25), max_tokens=12, check=_check_left),
    windows=(3, ((30, 3), (7, 17))),
    # 18 usable blocks of 4 tokens; two rows of 30 + 20 tokens need 26.
    preempt=dict(seed=4, n=30, num_blocks=19, roomy=True),
    # 9 + 10 stays under 24; 20 + 10 crosses it; 50 is past it.
    sampled=dict(
        hf_over=dict(moe_num_primary_experts=4, num_routed_experts=8), seed=2,
        lengths=(9, 20, 50), sampling=dict(temperature=0.7, top_p=0.9, max_tokens=10),
        check=_check_sampled,
    ),
    warm_prompt=20,
)
