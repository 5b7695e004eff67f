"""Mixed prefill+decode windows against the split engine and the dense
forward, and the row walk's chunk counter on the decode records."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distllm_tpu.generate.engine import (
    EngineConfig,
    LLMEngine,
    RequestState,
    SamplingParams,
)
from distllm_tpu.models import mistral
from distllm_tpu.ops.paged_attention import paged_attention_xla
from test_engine import _dense_greedy_reference, _expect_short_answers, _tiny_engine
from test_engine_units import _dense_reference, _heads, _random_cache


# ----------------------------------------- mixed prefill+decode windows
def test_ragged_paged_attention_decode_rows_match_decode_kernel(rng):
    """A ragged row with q_len=1 at position ctx-1 IS a decode row: the
    ragged path must agree with paged_attention_xla, with multi-token
    chunk rows coexisting in the same ragged batch."""
    from distllm_tpu.ops.paged_attention import ragged_paged_attention_xla

    block_size = 4
    k_cache, v_cache = _random_cache(rng, block_size=block_size)
    block_tables = jnp.asarray([[2, 5], [7, 3]], dtype=jnp.int32)
    context_lens = jnp.asarray([6, 5], dtype=jnp.int32)
    s = 3
    q = jnp.asarray(rng.normal(size=(2, s, 4, 8)).astype(np.float32))
    # Row 0: decode row — one valid query at its last position. Row 1: a
    # causal 3-token chunk span ending at position 4.
    q_positions = jnp.asarray([[5, 5, 5], [2, 3, 4]], dtype=jnp.int32)
    q_lens = jnp.asarray([1, 3], dtype=jnp.int32)
    out = np.asarray(
        ragged_paged_attention_xla(
            q, k_cache, v_cache, block_tables, context_lens, q_positions,
            q_lens=q_lens,
        )
    )
    dec = np.asarray(
        paged_attention_xla(
            q[:, 0], k_cache, v_cache, block_tables, context_lens
        )
    )
    np.testing.assert_allclose(out[0, 0], dec[0], atol=1e-5, rtol=1e-5)
    # Chunk row: each query vs a dense causal reference over its prefix.
    for j, pos in enumerate([2, 3, 4]):
        k_lin = np.concatenate(
            [_heads(k_cache[7]), _heads(k_cache[3])]
        )
        v_lin = np.concatenate(
            [_heads(v_cache[7]), _heads(v_cache[3])]
        )
        ref = _dense_reference(np.asarray(q[1, j]), k_lin, v_lin, pos + 1)
        np.testing.assert_allclose(out[1, j], ref, atol=1e-5, rtol=1e-4)
    # Padding queries (masked by q_lens) must stay finite.
    assert np.isfinite(out).all()


def _mixed_ab_engines(model_cfg, init_fn, seed=0, **cfg_kw):
    """Build (off, on) engines with identical weights for A/B runs."""
    class IdTokenizer:
        eos_id = None

    engines = []
    for mixed in (False, True):
        base = dict(
            block_size=4, num_blocks=96, max_num_seqs=2, max_model_len=96,
            decode_steps=4, pipeline_depth=2,
            prefer_native_allocator=False, enable_mixed_batching=mixed,
            max_window_prefill_tokens=8, max_window_prefill_seqs=2,
        )
        base.update(cfg_kw)
        engines.append(
            LLMEngine(
                model_cfg,
                init_fn(jax.random.PRNGKey(seed), model_cfg),
                IdTokenizer(),
                EngineConfig(**base),
            )
        )
    return engines


_STAGGER_PROMPT_LENS = (5, 21, 3, 33, 7, 13)
_STAGGER_OUT_LENS = (3, 17, 9, 5, 12, 8)


def _stagger_prompts(vocab, seed=1):
    """Staggered serving workload: more prompts than slots, unequal
    budgets (slots free mid-stream — the mixed-batching trigger), two
    prompts sharing a 2-block prefix (prefix-cache-hit tails ride), and
    long prompts whose tails chunk (chunk spans ride)."""
    rng = np.random.default_rng(seed)
    prompts = [
        list(rng.integers(1, vocab, size=n)) for n in _STAGGER_PROMPT_LENS
    ]
    shared = list(rng.integers(1, vocab, size=8))  # 2 full 4-blocks
    prompts[0] = shared + prompts[0]
    prompts[4] = shared + prompts[4]
    return prompts


def _run_stagger(engine, vocab, seed=1):
    prompts = _stagger_prompts(vocab, seed)
    rids = [
        engine.add_request(
            p, SamplingParams(temperature=0.0, max_tokens=n)
        )
        for p, n in zip(prompts, _STAGGER_OUT_LENS)
    ]
    engine._run_to_completion()
    return [engine._finished.pop(r).output_ids for r in rids]


@pytest.mark.slow
@pytest.mark.parametrize(
    'cache_kw',
    [
        {'enable_prefix_cache': True},
        {'enable_prefix_cache': True, 'prefill_chunk_tokens': 4},
        {'prefill_chunk_tokens': 4},
    ],
    ids=['prefix_cache', 'prefix_cache_chunked', 'chunked'],
)
def test_mixed_windows_token_identity(cache_kw):
    """Mixed on/off must emit bit-identical greedy tokens across prefix
    cache on/off and chunked tails, under pipelined (pipeline_depth=2)
    dispatch with mid-stream admissions — and wherever paged-route tails
    exist, the on run must actually fold them into windows (mixed
    records, fewer standalone dispatches). Only paged-route tails ride
    (cache-hit tails / chunked spans): fresh short prompts keep the
    batched dense prefill in BOTH arms, which is what makes identity a
    structural property rather than a cross-kernel numerics gamble."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    off, on = _mixed_ab_engines(cfg, mistral.init, **cache_kw)
    assert _run_stagger(on, cfg.vocab_size) == _run_stagger(
        off, cfg.vocab_size
    )
    if cache_kw.get('enable_prefix_cache'):
        # Second pass over the same workload: pass 1 populated the prefix
        # cache, so these shared-prefix repeats are CACHE-HIT admissions —
        # the cached-tail ride path a single cold batch can never reach
        # (all add_requests land before anything prefills).
        assert _run_stagger(on, cfg.vocab_size) == _run_stagger(
            off, cfg.vocab_size
        )
    assert on._stats['mixed_windows'] > 0
    assert on._stats['mixed_prefill_tokens'] > 0
    assert (
        on._stats['prefill_dispatches'] < off._stats['prefill_dispatches']
    )


@pytest.mark.slow
def test_mixed_windows_token_identity_sliding_window():
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, sliding_window=4,
        dtype='float32',
    )
    off, on = _mixed_ab_engines(
        cfg, mistral.init, prefill_chunk_tokens=4
    )
    outs_off = _run_stagger(off, cfg.vocab_size)
    outs_on = _run_stagger(on, cfg.vocab_size)
    assert outs_on == outs_off
    assert on._stats['mixed_windows'] > 0


@pytest.mark.slow
def test_mixed_windows_token_identity_gemma2():
    """gemma2-style serving (alternating windows, softcaps, sandwich
    norms, query_scale) through mixed windows stays token-exact."""
    from distllm_tpu.models import gemma

    cfg = gemma.GemmaConfig(
        name='gemma2', vocab_size=64, hidden_size=32, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=64,
        max_position_embeddings=128, dtype='float32',
        activation='gelu_new', embedding_multiplier=32 ** 0.5,
        norm_plus_one=True, post_norms=True, query_scale=16 ** -0.5,
        attn_logit_softcap=50.0, final_logit_softcap=30.0,
        sliding_window=6, sliding_window_pattern='alternating',
        tie_word_embeddings=True, rms_norm_eps=1e-6,
    )
    off, on = _mixed_ab_engines(
        cfg, gemma.init, seed=1, prefill_chunk_tokens=4
    )
    outs_off = _run_stagger(off, cfg.vocab_size)
    outs_on = _run_stagger(on, cfg.vocab_size)
    assert outs_on == outs_off
    assert on._stats['mixed_windows'] > 0


@pytest.mark.slow
def test_mixed_windows_match_dense_reference_and_preemption():
    """Mixed serving equals the dense greedy gold path even when a tiny
    pool forces recompute preemption of mid-prefill rows."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(
        cfg, mistral.init, num_blocks=20, max_num_seqs=3, max_model_len=64,
        prefill_chunk_tokens=4, decode_steps=2,
    )
    victims = _expect_short_answers(on)
    outs = _run_stagger(on, cfg.vocab_size)
    assert victims() > 0
    prompts = _stagger_prompts(cfg.vocab_size)
    # Dense gold references for the two longest-prompt requests (the ones
    # whose chunk rides + preemption interact); the full-matrix identity
    # tests above cover the rest without the dense re-forward cost.
    for i in (1, 3):
        ref = _dense_greedy_reference(
            cfg, on.params, prompts[i], _STAGGER_OUT_LENS[i]
        )
        assert outs[i] == ref
    assert all(
        len(o) == n for o, n in zip(outs, _STAGGER_OUT_LENS)
    )
    assert on.sched.num_free_blocks == 19  # no block leaks


@pytest.mark.slow
def test_mixed_windows_step_api_mid_stream_admission():
    """The synchronous step() path plans and processes mixed windows too;
    a request injected mid-decode rides them and its TTFT lifecycle
    fields are recorded."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(
        cfg, mistral.init, prefill_chunk_tokens=2
    )
    # Budgets staggered so r1's slot frees while r2 still decodes: the
    # injected r3 is then admitted MID-STREAM (equal budgets drain both
    # slots in the same window and the admission would land on an idle
    # engine, which bootstraps standalone by design).
    prompts = [[5, 9, 12], [7, 3, 22, 31], [1, 2, 3, 4, 5]]
    budgets = [3, 14, 8]
    r1 = on.add_request(
        prompts[0], SamplingParams(temperature=0.0, max_tokens=budgets[0])
    )
    r2 = on.add_request(
        prompts[1], SamplingParams(temperature=0.0, max_tokens=budgets[1])
    )
    seen: dict[int, list[int]] = {}
    r3 = None
    while on.has_unfinished:
        for rid, tok in on.step():
            seen.setdefault(rid, []).append(tok)
        if r3 is None and len(seen.get(r1, [])) >= budgets[0]:
            r3 = on.add_request(
                prompts[2],
                SamplingParams(temperature=0.0, max_tokens=budgets[2]),
            )
    for prompt, n, rid in zip(prompts, budgets, (r1, r2, r3)):
        assert seen[rid] == _dense_greedy_reference(
            cfg, on.params, prompt, n
        )
    assert on._stats['mixed_windows'] > 0
    assert on._finished[r3].t_first_token > 0.0


@pytest.mark.slow
def test_mixed_windows_warmup_compiles_without_state_damage():
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(
        cfg, mistral.init, prefill_chunk_tokens=4, max_model_len=32,
    )
    on.warmup()
    assert on.sched.num_running == 0
    assert on.sched.num_free_blocks == 95
    # Short post-warmup serve must still match the dense gold path
    # (scheduler state was untouched by warmup; sampling keys are
    # counter-derived per request, so there is no RNG state to damage).
    prompts = [[5, 9, 12], [7, 3, 22, 31, 40, 2, 17]]
    outs = on.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=4)
    )
    for prompt, out in zip(prompts, outs):
        assert out == _dense_greedy_reference(cfg, on.params, prompt, 4)


def test_mixed_config_validation():
    with pytest.raises(ValueError, match='mutually exclusive'):
        EngineConfig(
            enable_mixed_batching=True, defer_prefill=True,
            prefill_chunk_tokens=16,
        )
    with pytest.raises(ValueError, match='max_window_prefill_tokens'):
        EngineConfig(
            enable_mixed_batching=True, max_window_prefill_tokens=0,
            prefill_chunk_tokens=16,
        )
    # Structurally inert combination: neither prefix cache nor chunking
    # means nothing can ever ride, yet warmup would compile the whole
    # mixed shape ladder — rejected at config time.
    with pytest.raises(ValueError, match='prefill_chunk_tokens'):
        EngineConfig(enable_mixed_batching=True)
    with pytest.raises(ValueError, match='>= 1'):
        EngineConfig(max_window_prefill_seqs=0)
    # defer_prefill alone stays a legal opt-in.
    assert EngineConfig(defer_prefill=True).defer_prefill


def test_mixed_windows_token_identity_fast_canary():
    """Fast-tier mixed on/off identity canary: chunked + prefix-cache
    config, staggered budgets, pipelined dispatch. The full matrix
    (cache on/off, sliding-window, gemma2, preemption, step API, warmup)
    runs in the slow tier — this keeps one end-to-end identity + fold
    assertion inside the 870 s tier-1 budget."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    off, on = _mixed_ab_engines(
        cfg, mistral.init, enable_prefix_cache=True,
        prefill_chunk_tokens=4,
    )
    prompts = _stagger_prompts(cfg.vocab_size)
    budgets = (2, 9, 4, 3, 6, 4)

    def run(engine):
        rids = [
            engine.add_request(
                p, SamplingParams(temperature=0.0, max_tokens=n)
            )
            for p, n in zip(prompts, budgets)
        ]
        engine._run_to_completion()
        return [engine._finished.pop(r).output_ids for r in rids]

    assert run(on) == run(off)
    assert on._stats['mixed_windows'] > 0
    assert (
        on._stats['prefill_dispatches'] < off._stats['prefill_dispatches']
    )


def test_mixed_flight_records_and_metrics():
    """Chunk-carrying windows record kind='mixed' with prefill payload
    fields, and the distllm_engine_mixed_* series advance."""
    from distllm_tpu.observability import instruments as metrics
    from distllm_tpu.observability.flight import get_flight_recorder

    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(
        cfg, mistral.init, prefill_chunk_tokens=4
    )
    before = len(
        [r for r in get_flight_recorder().snapshot() if r['kind'] == 'mixed']
    )
    windows_before = metrics.MIXED_WINDOWS.value
    tokens_before = metrics.MIXED_PREFILL_TOKENS.value
    _run_stagger(on, cfg.vocab_size)
    mixed_records = [
        r for r in get_flight_recorder().snapshot() if r['kind'] == 'mixed'
    ]
    assert len(mixed_records) > before
    rec = mixed_records[-1]
    assert rec['prefill_tokens'] > 0
    assert rec['prefill_rows'] >= 1
    assert metrics.MIXED_WINDOWS.value > windows_before
    assert metrics.MIXED_PREFILL_TOKENS.value > tokens_before


def test_mixed_exception_recovery_rolls_back_inflight_chunk_spans(
    monkeypatch,
):
    """A chunk span whose window is lost to an exception mid-drain must
    roll ``prefill_sent`` back to ``prefill_done`` so the span re-rides
    after a catch-and-continue resume — otherwise the planner skips the
    request as 'in flight' forever and the serving loop livelocks."""
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    _, on = _mixed_ab_engines(cfg, mistral.init, prefill_chunk_tokens=2)
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    # Bootstrap one decoding request so the second one's tail rides.
    r1 = on.add_request([5, 9, 12], sp)
    while not on._requests[r1].output_ids:
        on.step()
    r2 = on.add_request([7, 3, 22, 31, 40], sp)

    armed = {'on': True}
    orig = LLMEngine._process_window

    def boom(self, window):
        if armed['on'] and window.get('chunk_plan'):
            armed['on'] = False  # lose exactly one chunk-carrying window
            raise RuntimeError('injected mid-drain')
        return orig(self, window)

    monkeypatch.setattr(LLMEngine, '_process_window', boom)
    with pytest.raises(RuntimeError, match='injected'):
        on._run_to_completion()
    req2 = on._requests[r2]
    assert req2.state is RequestState.RUNNING
    assert req2.prefill_sent == req2.prefill_done  # rolled back
    # The planner re-plans the dropped span instead of skipping it.
    assert any(
        request.request_id == r2
        for request, _, _ in on._plan_window_chunks()
    )


# ------------------------------------------------- the row walk's counter
def _walked_chunks(contexts, keys, window=None, block=4):
    """Chunks a row walk fetches for rows at ``contexts``, counted page by
    page: the chunks that hold a page the row's one query sees."""
    total = 0
    for ctx in contexts:
        lo = max(int(ctx) - window, 0) if window else 0
        pages = range(lo // block, -(-int(ctx) // block))
        total += len({page // (keys // block) for page in pages})
    return total


@pytest.mark.parametrize('family', ['mistral', 'laguna'])
def test_decode_records_count_the_chunks_the_walk_fetches(
    family, monkeypatch
):
    """``kv_chunks*`` on ``decode`` records is what the rows' contexts
    give, group by group, and ``telemetry['kv_walk_keys']`` names the keys
    a step each pool's walk takes (the rule's, here held to 8 keys, two
    pages, so that rows span several chunks at toy lengths);
    ``telemetry['walk_block']`` and ``walk_block*`` on the records name the
    form of each group's softmax block, the kernel's own rule's answer."""
    from distllm_tpu.ops import paged_attention

    monkeypatch.setattr(paged_attention, 'WALK_MAX_KEYS', 8)
    monkeypatch.setattr(paged_attention, 'WALK_PAGES_A_TURN', 1)
    if family == 'mistral':
        _, _, engine = _tiny_engine(
            attn_backend='interpret', decode_steps=4, max_model_len=96,
        )
        windows, names = {'kv': None}, {'kv': 'kv_chunks'}
        prompts = [list(range(1, 38)), list(range(2, 11)), [5]]
    else:
        from laguna_toy import WINDOW, make_engine, prompt

        _, _, engine = make_engine(attn_backend='interpret')
        windows = {'full': None, 'window': WINDOW}
        names = {'full': 'kv_chunks_full', 'window': 'kv_chunks_window'}
        rng = np.random.default_rng(3)
        prompts = [prompt(rng, 41), prompt(rng, 9)]
    assert engine.telemetry['kv_walk_keys'] == dict.fromkeys(windows, 8)
    heads, kv_heads = engine.model_cfg.num_heads, engine.model_cfg.num_kv_heads
    blocks = {
        group: paged_attention.walk_block(
            kv_heads, (heads(group) if callable(heads) else heads) // kv_heads
        )[0]
        for group in windows
    }
    assert engine.telemetry['walk_block'] == blocks
    block_fields = {
        names[group].replace('kv_chunks', 'walk_block'): form
        for group, form in blocks.items()
    }

    seen = []
    reckon = engine._kv_chunks

    def spy(contexts):
        fields = reckon(contexts)
        seen.append((np.array(contexts), fields))
        return fields

    monkeypatch.setattr(engine, '_kv_chunks', spy)
    before = engine.flight.total_recorded
    engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=9)
    )
    assert seen and max(int(c.max()) for c, _ in seen) > 16
    for contexts, fields in seen:
        for group, window in windows.items():
            assert fields[names[group]] == _walked_chunks(
                contexts, 8, window
            ), (group, contexts)
        if family == 'laguna':
            assert fields['kv_chunks'] == fields['kv_chunks_full']
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    decodes = [r for r in records if r['kind'] == 'decode']
    assert [
        {k: v for k, v in r.items() if k.startswith('kv_chunks')}
        for r in decodes
    ] == [fields for _, fields in seen]
    assert [
        {k: v for k, v in r.items() if k.startswith('walk_block')}
        for r in decodes
    ] == [block_fields] * len(decodes)
    assert not any(k.startswith('kv_turns') for r in decodes for k in r)
    # a chunk holds two pages: the walk fetches fewer chunks than blocks,
    # and no more than one a block
    assert all(0 < r['kv_chunks'] <= r['kv_blocks'] for r in decodes)
    engine.shutdown()


@pytest.mark.parametrize('family', ['mistral', 'laguna'])
def test_chunks_of_known_contexts_reckoned_by_hand(family, monkeypatch):
    """``kv_chunks*`` for a dispatch of known contexts, by hand: chunks of
    16 keys (four pages of 4). Rows at 1, 8, 9, 16, 17 and 40 tokens walk
    1, 1, 1, 1, 2 and 3 chunks; under a window of 12 the last row's floor,
    28, is in its chunk 1: 2 chunks. Nothing else rides the records beside
    them (``kv_turns*`` went with PR 50: no sound metric could be made of
    it)."""
    from distllm_tpu.ops import paged_attention

    monkeypatch.setattr(paged_attention, 'WALK_MAX_KEYS', 16)
    if family == 'mistral':
        _, _, engine = _tiny_engine(attn_backend='interpret', max_model_len=96)
    else:
        from laguna_toy import WINDOW, make_engine

        assert WINDOW == 12
        _, _, engine = make_engine(attn_backend='interpret')
    fields = engine._kv_chunks(np.array([1, 8, 9, 16, 17, 40]))
    if family == 'mistral':
        assert fields == {'kv_chunks': 9}
    else:
        assert fields == {
            'kv_chunks_full': 9, 'kv_chunks': 9, 'kv_chunks_window': 8,
        }
    engine.shutdown()


def test_no_walk_no_chunk_count():
    """Under the XLA backend nothing walks: no telemetry entry and no
    ``kv_chunks`` on the records."""
    _, _, engine = _tiny_engine(attn_backend='xla')
    assert 'kv_walk_keys' not in engine.telemetry
    assert 'walk_block' not in engine.telemetry
    before = engine.flight.total_recorded
    engine.generate_ids(
        [[1, 2, 3]], SamplingParams(temperature=0.0, max_tokens=6)
    )
    records = engine.flight.snapshot()[before - engine.flight.total_recorded:]
    decodes = [r for r in records if r['kind'] == 'decode']
    assert decodes and not any(
        'kv_chunks' in r or 'kv_turns' in r or 'walk_block' in r
        for r in decodes
    )
    engine.shutdown()
