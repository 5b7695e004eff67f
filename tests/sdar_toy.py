"""A toy ``sdar_moe`` (models/sdar.py) for the CPU tests: the published
config's keys at tiny widths (3 layers, 4 queries a KV head, 8 routed experts
of which 4 are held and 2 a token, blocks of 4 positions), seeded weights, an
engine over it, and the block window driven by hand so that the LOGITS of
every denoise forward can be held against the plain reference."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference_sdar as ref
from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
from distllm_tpu.models import sdar

BLOCK = 4  # positions decided together
PAGE = 8  # tokens a KV block


def tiny_hf(**over) -> dict:
    hf = {
        'model_type': 'sdar_moe', 'vocab_size': 96, 'hidden_size': 64,
        'num_hidden_layers': 3, 'num_attention_heads': 8,
        'num_key_value_heads': 2, 'head_dim': 16,
        'max_position_embeddings': 4096, 'rms_norm_eps': 1e-6,
        'moe_intermediate_size': 32, 'num_experts': 4,
        'num_routed_experts': 8, 'first_local_expert': 2,
        'num_experts_per_tok': 2, 'norm_topk_prob': True,
        'decoder_sparse_step': 1, 'mlp_only_layers': [],
        'use_sliding_window': False, 'rope_scaling': None,
        'rope_theta': 1000000, 'tie_word_embeddings': False,
        'attention_bias': False, 'hidden_act': 'silu',
        'block_length': BLOCK, 'mask_token_id': 95,
    }
    hf.update(over)
    return hf


def tiny(seed=0, scale=4.0, **over):
    hf = tiny_hf(**over)
    cfg = sdar.SdarConfig.from_hf_config(hf).model_copy(
        update={'dtype': 'float32'}
    )
    params = sdar.init_on_device(jax.random.PRNGKey(seed), cfg)
    # Larger kernels than 0.02 so that every mechanism moves the logits, and
    # norm scales away from one so that each norm is seen.
    key = jax.random.PRNGKey(seed + 100)
    params = jax.tree.map(
        lambda a: a * scale if a.ndim > 1 else a, params
    )
    for i, name in enumerate(('attn_ln', 'mlp_ln', 'q_norm', 'k_norm')):
        leaf = params['layers'][name]['scale']
        params['layers'][name]['scale'] = leaf + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), leaf.shape, leaf.dtype
        )
    return hf, cfg, params


class NoTokenizer:
    eos_id = None


def make_engine(seed=0, hf_over=None, **over):
    hf, cfg, params = tiny(seed, **(hf_over or {}))
    settings = dict(
        block_size=PAGE, num_blocks=64, max_num_seqs=4, max_model_len=96,
        prefill_chunk_tokens=16, decode_steps=8, attn_backend='xla',
        enable_prefix_cache=False,
    )
    settings.update(over)
    engine = LLMEngine(cfg, params, NoTokenizer(), EngineConfig(**settings))
    return hf, params, engine


def prompt(rng, n):
    return [int(t) for t in rng.integers(4, 95, n)]


def paged_run(cfg, params, prompt_ids, blocks, *, steps=BLOCK, threshold=None,
              backend='xla', sampling=(0.0, 1.0, 0), seed=5, span=16,
              module=sdar):
    """A prompt through the paged path as the engine drives it: its whole
    blocks prefilled in ``span``-token spans, then ONE window of ``blocks``
    blocks. Returns ``(tokens [blocks * B], decided_at, logits [blocks,
    steps, B, V] of every denoise forward, prefill logits [whole, V], (k,
    v) pools, the row's page ids, counters)``."""
    total = len(prompt_ids) // BLOCK * BLOCK + blocks * BLOCK
    pages = -(-total // PAGE)
    shape = (cfg.num_layers, pages + 1, PAGE, cfg.num_kv_heads * cfg.head_dim)
    k, v = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    row = 1 + np.arange(pages, dtype=np.int32)
    tables = jnp.asarray(row[None])
    whole = len(prompt_ids) // BLOCK * BLOCK
    seen = []
    for start in range(0, whole, span):
        ntok = min(span, whole - start)
        ids = np.zeros((1, span), np.int32)
        ids[0, :ntok] = prompt_ids[start:start + ntok]
        positions = np.minimum(start + np.arange(span), total - 1)[None]
        logits, k, v = module.prefill_paged(
            params, cfg, jnp.asarray(ids), jnp.asarray(positions), k, v,
            tables, jnp.asarray([start + ntok]), jnp.asarray([ntok]),
            max_table_positions=total, attn_backend=backend, all_logits=True,
        )
        seen.append(np.asarray(logits[0, :ntok]))
    given = np.zeros((1, BLOCK), np.int32)
    given[0, :len(prompt_ids) - whole] = prompt_ids[whole:]
    temperature, top_p, top_k = sampling
    tokens, k, v, _, counters, logits = module.decode_loop(
        params, cfg, jnp.asarray(given), jnp.asarray([len(prompt_ids) - 1]),
        k, v, tables, jnp.asarray([len(prompt_ids)]),
        jnp.asarray([blocks * BLOCK]), jnp.asarray([temperature], jnp.float32),
        jnp.asarray([top_p], jnp.float32), jnp.zeros((1,), jnp.float32),
        jnp.asarray([top_k], jnp.int32), jnp.asarray([seed], jnp.uint32),
        num_steps=blocks * BLOCK, attn_backend=backend,
        max_table_positions=total, denoise_steps=steps,
        unmask_threshold=(
            None if threshold is None else jnp.asarray([threshold], jnp.float32)
        ),
        return_logits=True,
    )
    counters = {n: np.asarray(c) for n, c in counters.items()}
    return (
        np.asarray(tokens)[:, 0], counters.pop('decided_at')[:, 0],
        np.asarray(logits)[:, :, 0],
        np.concatenate(seen) if seen else np.zeros((0, cfg.vocab_size)),
        (k, v), row, counters,
    )


def held_pages(pool, row, layer, cfg):
    """``[tokens, G, d]``: what ``layer``'s pages of ``row`` hold."""
    pages = np.asarray(pool[layer])[np.asarray(row)]
    return pages.reshape(-1, cfg.num_kv_heads, cfg.head_dim)


# ------------------------------------------ the row of the engine's contract
def token_gap(params, hf, ids, at, out, steps=BLOCK):
    """The share of ``out`` that is not the reference's own greedy loop's
    (float32 on both sides: a tie is the one way to differ). A block is
    decided by its own forwards, so there is no teacher-forced forward to
    score: the reference runs the loop from the same prompt."""
    n_prompt = int(np.asarray(at)[0][0]) + 1
    want, _ = ref.generate(
        params, hf, [int(t) for t in np.asarray(ids)[0][:n_prompt]], len(out),
        steps,
    )
    return float(np.mean(np.asarray(want) != np.asarray(out)))


def _after_greedy(engine, params, records, lengths, backend):
    assert engine.telemetry['attn_backend'] == backend
    windows = [r for r in records if r['kind'] == 'decode']
    # five forwards a live row's block of four positions, each row's first
    # block less by what it was given
    assert sum(r['forwards'] for r in windows) == 5 * sum(r['blocks'] for r in windows)
    given = sum(n % BLOCK for n in lengths)
    assert sum(r['decided'] for r in windows) == (
        BLOCK * sum(r['blocks'] for r in windows) - given
    )
    # a prefill yields no token: every token is a window's
    assert sum(r['tokens'] for r in windows) == sum(
        r['output_tokens'] for r in records if r['kind'] == 'request'
    )
    for r in records:
        if r['kind'] == 'request':
            assert len(r['decided_at']) == r['output_tokens']
            assert set(r['decided_at']) <= set(range(BLOCK))
            assert r['blocks'] == -(-(r['prompt_tokens'] % BLOCK + r['output_tokens']) // BLOCK)


def _check_left(engine, hf, params, tokens, record):
    """The cell's own page check: the first and the last layer's K and V in
    the request's first page (prefilled, or given in its first block) and in
    the page of the last whole block of its tokens (decided and committed),
    against the reference's. ``tokens`` lacks the request's last token: the
    case's lengths leave it outside the last whole block."""
    cfg = engine.model_cfg
    n = record['prompt_tokens'] + record['output_tokens']
    whole = n // BLOCK * BLOCK
    assert len(tokens) == n - 1 >= whole
    last = cfg.num_layers - 1
    _, kept = ref.forward(params, hf, tokens[:whole], keep=(0, last))
    for page_id, at in (
        (record['kv_first_block'], 0),
        (record['kv_tail_block'], (whole - 1) // PAGE * PAGE),
    ):
        for layer in (0, last):
            for side, pool in enumerate((engine.kv.k, engine.kv.v)):
                held = np.asarray(pool[layer][page_id], np.float32).reshape(
                    PAGE, cfg.num_kv_heads, cfg.head_dim
                )
                want = np.asarray(kept[layer][side])[at:at + PAGE]
                assert len(want) and ref.kv_content_error(
                    held[:len(want)], want
                ) < 1e-5
                assert ref.kv_content_error(
                    np.roll(held[:len(want)], 1, axis=0), want
                ) > 0.3


def _check_sampled(engine, records):
    windows = [r for r in records if r['kind'] == 'decode']
    assert windows and all(
        r['moe_form'] in ('dense', 'grouped') and r['moe_pairs'] > 0
        and 0 < r['moe_pairs_held'] < r['moe_pairs'] for r in windows
    )
    # 3 layers x 2 picks a position a forward
    assert sum(r['moe_pairs'] for r in windows) == 6 * BLOCK * sum(
        r['forwards'] for r in windows
    )


ENGINE_CASES = dict(
    refusal='cannot serve a model that decides blocks',
    refused=('enable_prefix_cache', 'host_kv_tier_bytes', 'enable_mixed_batching',
             'draft_k', 'kv_cache_dtype=int8', 'quantization'),
    # every remainder of a prompt over its blocks; one under a block; one
    # prefilled in three spans
    greedy=[(1, (5, 18, 43, 3), 'xla'), (1, (17, 8), 'interpret')],
    greedy_tokens=14,
    after_greedy=_after_greedy,
    # every row's last token falls outside its last whole block
    left=dict(seed=8, lengths=(40, 13, 26), max_tokens=13, check=_check_left),
    windows=(3, ((30, 3), (7, 17))),
    turnover=True,
    # 11 usable pages of 8 tokens; two rows of 30 + 20 tokens need 14.
    preempt=dict(seed=4, n=30, num_blocks=12, roomy=True),
    sampled=dict(
        seed=2, lengths=(9, 20, 50),
        sampling=dict(temperature=0.7, top_p=0.9, max_tokens=10),
        check=_check_sampled,
    ),
    warm_prompt=21,
    unnamed=('sdar', 'diffusion'),
)
