"""A toy ``lfm2_moe`` (models/lfm2.py) for the CPU tests: the published
config's keys at tiny widths, seeded weights, an engine over it, and the
paged path driven by hand (rounds of prefill spans through the pools and the
state, rows of unequal tails in one dispatch, then decode steps) so that its
LOGITS can be held against the plain reference."""

import functools
import io
import json
import runpy
import sys
from contextlib import redirect_stdout
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference_lfm2 as ref
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


ROOT = Path(__file__).resolve().parent.parent


@functools.cache
def load_probe(script) -> dict:
    """The globals of ``scripts/<script>``: the wrong programs a cell's
    limits have to catch (its arms) and its ``check``."""
    sys.path.insert(0, str(ROOT / 'scripts'))  # it imports its neighbours
    try:
        return runpy.run_path(str(ROOT / 'scripts' / script))
    finally:
        sys.path.remove(str(ROOT / 'scripts'))


def cell_check(script, config):
    """``arm -> result line`` of a cell's own check (its driver's greedy calls
    through ``LLMEngine``, then the reference) at toy size (``config``, under
    ``benchmarks/tests``), on an engine built as that arm of
    ``scripts/<script>`` says."""
    model = json.loads((ROOT / 'benchmarks/tests' / config).read_text())

    @functools.cache
    def run(arm):
        out = io.StringIO()
        with redirect_stdout(out):
            load_probe(script)['check'](model, [3000000123], [arm])
        return json.loads(out.getvalue().strip().splitlines()[-1])

    return run


# ------------------------------------------ the row of the engine's contract
def token_gap(params, hf, ids, at, out):
    return ref.token_gaps(ref.lfm2_logits(params, hf, ids, at), [out]).max()


def check_pages(engine, record, want_k, want_v, n_fed, layer=0):
    """A layer's K and V in the first and the last block a finished request
    held against the reference's rows ``[n_fed, lanes]``."""
    at = (n_fed - 1) // BLOCK * BLOCK
    for pool, want in ((engine.kv.k, want_k), (engine.kv.v, want_v)):
        first = np.asarray(pool[layer][np.asarray([record['kv_first_block']])])[0]
        assert ref.content_error(first, want[:BLOCK]) < 1e-5
        tail = np.asarray(pool[layer][np.asarray([record['kv_tail_block']])])[0]
        assert ref.content_error(tail[:n_fed - at], want[at:]) < 1e-5


def _after_greedy(engine, params, records, lengths, backend):
    # 4 conv layers x [2, 64] float32 a slot, one kind of leaf.
    assert engine.telemetry['state_pool'] == {
        'slots': 4, 'bytes': 4 * 4 * 2 * 64 * 4, 'bytes_per_slot': 4 * 2 * 64 * 4,
        'leaves': [{'count': 4, 'shape': [2, 64], 'dtype': 'float32'}],
    }
    assert engine.telemetry['kv_pools']['kv']['block_shape'] == [BLOCK, 2 * 16]
    assert engine.telemetry['kv_pools']['kv']['layers'] == 2
    (request,) = [r for r in records if r['kind'] == 'request']
    assert {'state_slot', 'kv_first_block', 'kv_tail_block'} <= set(request)
    windows = [r for r in records if r['kind'] == 'decode']
    assert windows and all(
        {'kv_blocks', 'moe_pairs', 'moe_pairs_held'} <= set(r) for r in windows
    )
    # 4 sparse layers x 3 picks a token
    assert sum(r['moe_pairs'] for r in windows) == 12 * sum(
        r['tokens'] for r in windows
    )
    if backend == 'interpret':
        assert all('kv_chunks' in r for r in windows)
        assert engine.telemetry['kv_walk_keys'] == {'kv': 96}


def _check_left(engine, hf, params, fed, record):
    want = ref.first_conv_inputs(params, hf, fed[-2:])
    got = engine.state_pool.state['conv'][0][record['state_slot']]
    assert ref.content_error(got, want) < 1e-5
    want_k, want_v = ref.first_attn_kv(params, hf, fed, np.arange(len(fed)))
    check_pages(engine, record, want_k, want_v, len(fed))


def _check_sampled(engine, records):
    windows = [r for r in records if r['kind'] == 'decode']
    assert windows and all(0 < r['moe_pairs_held'] < r['moe_pairs'] for r in windows)


ENGINE_CASES = dict(
    refusal='cannot serve a hybrid',
    refused=('enable_prefix_cache', 'host_kv_tier_bytes', 'enable_mixed_batching',
             'draft_k', 'kv_cache_dtype=int8', 'quantization'),
    greedy=[(n, (n,), 'xla') for n in (1, 2, 5, 8, 20)] + [(20, (20,), 'interpret')],
    after_greedy=_after_greedy,
    left=dict(seed=3, lengths=(6, 19, 11), max_tokens=13, check=_check_left),
    windows=(1, ((6, 3), (19, 11))),
    reuse=(1, 4, 13),  # one token, one span, and chunks
    preempt=dict(seed=3, n=12, num_blocks=11),
    sampled=dict(
        hf_over=dict(num_experts=4, num_routed_experts=8), seed=4,
        lengths=(9, 30, 3), sampling=dict(temperature=0.7, top_p=0.9, max_tokens=9),
        check=_check_sampled,
    ),
    warm_prompt=10,
)
