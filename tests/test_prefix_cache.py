"""Automatic prefix caching: hash-chain cache units, scheduler
borrowed-prefix accounting (both backends), and engine-level token-exact
reuse — cache on vs off must be byte-identical, with zero blocks
allocated for cached prefixes (docs/prefix_caching.md)."""

from __future__ import annotations

import numpy as np
import pytest

import jax

from distllm_tpu.generate.engine import (
    EngineConfig,
    LLMEngine,
    SamplingParams,
)
from distllm_tpu.generate.engine.kv_cache import (
    PrefixCache,
    block_digests,
    hash_block_tokens,
)
from distllm_tpu.generate.engine.scheduler import (
    NativeScheduler,
    PyScheduler,
)
from distllm_tpu.models import mistral


# ---------------------------------------------------------------- digests
def test_block_digests_chain_identifies_whole_prefix():
    bs = 4
    a = block_digests([1, 2, 3, 4, 5, 6, 7, 8, 9], bs)
    b = block_digests([1, 2, 3, 4, 5, 6, 7, 8], bs)
    assert len(a) == 2 and len(b) == 2
    assert a == b  # partial trailing token does not hash
    # Divergence in block 0 changes EVERY later digest (chained).
    c = block_digests([9, 2, 3, 4, 5, 6, 7, 8], bs)
    assert c[0] != a[0] and c[1] != a[1]
    # Same block content under a different prefix hashes differently.
    assert hash_block_tokens(None, [5, 6, 7, 8]) != a[1]


def test_block_digests_short_prompt_has_no_full_block():
    assert block_digests([1, 2, 3], 4) == []


# ------------------------------------------------------------ cache logic
def test_prefix_cache_acquire_insert_release_evict():
    cache = PrefixCache(block_size=4)
    d = block_digests(list(range(1, 13)), 4)  # 3 full blocks
    assert cache.match(d) == []
    # rid 0 prefills and inserts blocks 7, 8, 9.
    for digest, block in zip(d, (7, 8, 9)):
        assert cache.insert(0, digest, block)
    assert not cache.insert(1, d[0], 11)  # first writer wins
    assert cache.num_cached == 3 and cache.num_evictable == 0

    # rid 2 matches the full chain and pins it.
    assert cache.acquire(2, d) == [7, 8, 9]
    assert cache.num_shared == 3
    assert cache.evict(10) == []  # everything referenced -> nothing evicts

    cache.release(0)
    assert cache.num_evictable == 0  # rid 2 still holds refs
    cache.release(2)
    assert cache.num_evictable == 3
    # A new acquire resurrects evictable entries (removes them from LRU).
    assert cache.acquire(3, d[:1]) == [7]
    assert cache.num_evictable == 2
    # LRU eviction pops oldest-released first and skips referenced blocks.
    assert cache.evict(5) == [8, 9]
    assert cache.num_cached == 1
    cache.release(3)
    assert cache.evict(5) == [7]
    assert cache.num_cached == 0


def test_prefix_cache_partial_match_stops_at_first_miss():
    cache = PrefixCache(block_size=2)
    d = block_digests([1, 2, 3, 4, 5, 6], 2)
    cache.insert(0, d[0], 3)
    # d[1] missing: match must stop there even though d[2] is "cached".
    cache.insert(0, d[2], 4)
    assert cache.acquire(1, d) == [3]


# ------------------------------------------- scheduler borrowed prefixes
def _native_available() -> bool:
    try:
        NativeScheduler(8, 4, 2)
        return True
    except (RuntimeError, OSError):
        return False


@pytest.fixture(params=['py', 'native'])
def sched_cls(request):
    if request.param == 'native' and not _native_available():
        pytest.skip('no C++ toolchain')
    return PyScheduler if request.param == 'py' else NativeScheduler


class TestSchedulerBorrowedPrefix:
    def test_admission_allocates_only_shortfall(self, sched_cls):
        s = sched_cls(16, 4, 2)
        free0 = s.num_free_blocks
        s.add(0, 10, cached_blocks=[11, 12])  # 2 of the 3 needed blocks
        assert s.admit_next() == 0
        assert s.num_free_blocks == free0 - 1  # shortfall only
        row = s.block_row(0)
        assert row[:2] == [11, 12] and len(row) == 3
        assert s.num_borrowed(0) == 2

    def test_finish_frees_only_owned_tail(self, sched_cls):
        s = sched_cls(16, 4, 2)
        free0 = s.num_free_blocks
        s.add(0, 10, cached_blocks=[11, 12])
        s.admit_next()
        s.finish(0)
        # Borrowed blocks 11/12 are cache property: NOT back in free list.
        assert s.num_free_blocks == free0

    def test_release_blocks_returns_evicted_to_free_list(self, sched_cls):
        s = sched_cls(16, 4, 2)
        free0 = s.num_free_blocks
        s.release_blocks([11, 12])
        assert s.num_free_blocks == free0 + 2

    def test_lend_prefix_marks_blocks_unfreeable(self, sched_cls):
        s = sched_cls(16, 4, 2)
        free0 = s.num_free_blocks
        s.add(0, 10)
        s.admit_next()  # allocates 3
        s.lend_prefix(0, 2)
        assert s.num_borrowed(0) == 2
        s.finish(0)
        assert s.num_free_blocks == free0 - 2  # lent blocks stay out

    def test_preemption_keeps_borrowed_prefix(self, sched_cls):
        # block_size 1, pool 9 usable: rid 0 (3+1) and rid 1 (2 owned +
        # 2 borrowed + 1 headroom = 3 owned) fill the pool.
        s = sched_cls(10, 1, 2)
        s.add(0, 5)
        s.add(1, 4, cached_blocks=[20, 21])
        assert s.admit_next() == 0  # 6 blocks
        assert s.admit_next() == 1  # 3 more owned
        assert s.num_free_blocks == 0
        s.append_token(0)
        preempted = s.prepare_decode()
        assert preempted == [1]
        assert s.block_row(1) == [20, 21]  # borrowed prefix survives
        assert s.num_borrowed(1) == 2

    def test_lend_prefix_beyond_row_raises(self, sched_cls):
        s = sched_cls(16, 4, 2)
        s.add(0, 3)
        s.admit_next()
        with pytest.raises((ValueError, KeyError)):
            s.lend_prefix(0, 99)


# ----------------------------------------------------------------- engine
def _tiny_engine(
    num_blocks=64,
    max_num_seqs=4,
    max_model_len=64,
    prefer_native=False,
    **cfg_kwargs,
):
    cfg = mistral.MistralConfig(
        vocab_size=64,
        hidden_size=32,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        intermediate_size=64,
        dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)

    class IdTokenizer:
        eos_id = None

        def decode(self, ids):
            return ' '.join(str(i) for i in ids)

    engine = LLMEngine(
        cfg,
        params,
        IdTokenizer(),
        EngineConfig(
            block_size=4,
            num_blocks=num_blocks,
            max_num_seqs=max_num_seqs,
            max_model_len=max_model_len,
            prefer_native_allocator=prefer_native,
            **cfg_kwargs,
        ),
    )
    return cfg, params, engine


def _dense_greedy(cfg, params, prompt, n_tokens):
    ids = list(prompt)
    for _ in range(n_tokens):
        arr = np.asarray([ids], np.int32)
        hidden = mistral.apply(params, cfg, arr, np.ones_like(arr))
        lg = mistral.logits(params, cfg, hidden[:, -1])
        ids.append(int(np.argmax(np.asarray(lg)[0])))
    return ids[len(prompt):]


GREEDY = SamplingParams(temperature=0.0, max_tokens=6)


def test_second_request_reuses_prefix_blocks_and_tokens_match():
    """Acceptance: a second request sharing an N-block prefix allocates
    ZERO new blocks for that prefix and generates byte-identical tokens
    to a cache-off run."""
    cfg, params, engine = _tiny_engine(enable_prefix_cache=True)
    shared = [7, 3, 22, 31, 40, 2, 17, 9]  # 2 full blocks at block_size 4
    p1 = shared + [11, 12]
    p2 = shared + [33, 34, 35]
    out1 = engine.generate_ids([p1], GREEDY)[0]
    assert out1 == _dense_greedy(cfg, params, p1, 6)

    # p1 finished: its prompt blocks sit in the cache, evictable.
    assert engine.prefix_cache.num_evictable == 2
    free_before = engine.sched.num_free_blocks
    rid = engine.add_request(p2, GREEDY)
    request = engine._requests[rid]
    assert request.num_cached_tokens == 8
    assert request.num_borrowed_blocks == 2
    # Admission must allocate blocks for the TAIL only.
    while engine.has_unfinished:
        engine.step()
    out2 = engine._finished.pop(rid).output_ids
    assert out2 == _dense_greedy(cfg, params, p2, 6)
    # Zero new blocks for the shared prefix: total allocation for p2 ==
    # blocks_needed(len(p2) + 6 generated) - the 2 cached blocks. All
    # owned blocks are freed at finish, so free-count round-trips.
    assert engine.sched.num_free_blocks == free_before
    assert engine._stats['prefix_hit_tokens'] == 8


def test_cache_on_off_identical_across_workload():
    """Whole-workload identity: shared-stem prompts (the MCQA pattern),
    repeats, and unshared prompts — cache on == cache off, token for
    token, across sequential generate_ids calls. (The cache-off engine is
    dense-reference-checked by test_engine.py; identity is the claim
    here.)"""
    stem = list(range(1, 13))  # 3 full blocks
    prompts = [
        stem + [20 + i] for i in range(4)
    ] + [[5, 9, 12], stem + [20]]
    _, _, engine_off = _tiny_engine(num_blocks=128, max_num_seqs=4)
    _, _, engine_on = _tiny_engine(
        num_blocks=128, max_num_seqs=4, enable_prefix_cache=True
    )
    for batch in (prompts[:4], prompts[4:]):
        outs_off = engine_off.generate_ids(batch, GREEDY)
        outs_on = engine_on.generate_ids(batch, GREEDY)
        assert outs_on == outs_off
    assert engine_on.telemetry['prefix_hit_tokens'] > 0


def test_cow_on_aligned_full_cover_repeat():
    """Re-submitting a block-aligned prompt hits every block; the last
    token recomputes into a COW copy of the shared final block."""
    cfg, params, engine = _tiny_engine(enable_prefix_cache=True)
    prompt = [7, 3, 22, 31, 40, 2, 17, 9]  # len 8 == 2 * block_size
    out1 = engine.generate_ids([prompt], GREEDY)[0]
    out2 = engine.generate_ids([prompt], GREEDY)[0]
    assert out1 == out2 == _dense_greedy(cfg, params, prompt, 6)
    assert engine.telemetry['prefix_cow_copies'] == 1
    assert engine.telemetry['prefix_hit_tokens'] == 7  # len - 1


def test_eviction_under_pool_pressure_no_leaks():
    """A small pool forces LRU eviction of cached blocks; outputs stay
    exact and every block is accounted for afterwards."""
    cfg, params, engine = _tiny_engine(
        num_blocks=16, max_num_seqs=2, max_model_len=32,
        enable_prefix_cache=True,
    )
    rng = np.random.default_rng(7)
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    # Each 9-token prompt leaves 2 cached blocks behind; by run 8 the
    # 15-block pool cannot admit without evicting someone's prefix.
    for i in range(10):
        prompt = list(rng.integers(1, 64, size=9))
        out = engine.generate_ids([prompt], sp)[0]
        assert out == _dense_greedy(cfg, params, prompt, 4)
    # Invariant: free blocks + cache-held blocks == usable pool.
    assert (
        engine.sched.num_free_blocks + engine.prefix_cache.num_cached == 15
    )
    assert engine.prefix_cache.stats['evictions'] > 0


def test_chunked_prefill_matches_dense():
    """Long uncached tails split into chunks must stay token-exact (each
    chunk attends over the paged cache), with and without the cache."""
    prompts = [list(range(1, 23)), [5, 9, 12]]
    refs = None
    for extra in ({}, {'enable_prefix_cache': True}):
        cfg, params, engine = _tiny_engine(
            num_blocks=128, prefill_chunk_tokens=8, **extra
        )
        if refs is None:
            refs = [_dense_greedy(cfg, params, p, 6) for p in prompts]
        outs = engine.generate_ids(prompts, GREEDY)
        assert outs == refs, extra
        assert engine.telemetry['prefill_chunks'] >= 2


def test_prefix_cache_with_pipelined_decode_and_deferred_prefill():
    """Cache + chunking under the production serving loop shape
    (multi-step windows, pipeline depth 2, deferred prefill)."""
    cfg, params, engine = _tiny_engine(
        num_blocks=128,
        max_num_seqs=2,
        enable_prefix_cache=True,
        prefill_chunk_tokens=8,
        decode_steps=4,
        pipeline_depth=2,
        defer_prefill=True,
    )
    stem = list(range(1, 10))
    prompts = [stem + [30], stem + [31], list(range(40, 58)), [5, 9]]
    lens = [6, 9, 5, 7]
    rids = [
        engine.add_request(p, SamplingParams(temperature=0.0, max_tokens=n))
        for p, n in zip(prompts, lens)
    ]
    engine._run_to_completion()
    for p, n, rid in zip(prompts, lens, rids):
        got = engine._finished.pop(rid).output_ids
        assert got == _dense_greedy(cfg, params, p, n), p


def test_prefix_cache_preemption_pressure_matches_dense():
    """Recompute preemption with borrowed prefixes: victims keep cached
    blocks, re-prefill only the rest, outputs stay exact."""
    from test_engine import _expect_short_answers

    cfg, params, engine = _tiny_engine(
        num_blocks=10, max_num_seqs=3, enable_prefix_cache=True,
        decode_steps=2,
    )
    victims = _expect_short_answers(engine)
    stem = [7, 3, 22, 31]
    prompts = [stem + [5], stem + [9, 2], [1, 2, 3, 4, 5]]
    outs = engine.generate_ids(
        prompts, SamplingParams(temperature=0.0, max_tokens=12)
    )
    assert victims() > 0
    for p, o in zip(prompts, outs):
        assert o == _dense_greedy(cfg, params, p, 12)


@pytest.mark.skipif(not _native_available(), reason='no C++ toolchain')
def test_prefix_cache_scheduler_backend_parity():
    """PyScheduler and NativeScheduler drive identical cache decisions."""
    stem = list(range(1, 13))
    prompts = [stem + [20 + i] for i in range(5)] + [[9, 8, 7]]
    results = []
    for native in (False, True):
        _, _, engine = _tiny_engine(
            num_blocks=32,
            max_num_seqs=2,
            enable_prefix_cache=True,
            prefer_native=native,
        )
        outs = engine.generate_ids(prompts, GREEDY)
        results.append(
            (
                outs,
                engine.telemetry.get('prefix_hit_tokens', 0),
                engine.sched.num_free_blocks,
                engine.prefix_cache.num_cached,
            )
        )
    assert results[0] == results[1]


def test_warmup_covers_paged_prefill_without_state_damage():
    cfg, params, engine = _tiny_engine(
        enable_prefix_cache=True, prefill_chunk_tokens=8
    )
    engine.warmup()
    assert engine.sched.num_running == 0
    assert engine.sched.num_free_blocks == 63
    assert engine.prefix_cache.num_cached == 0
    # Sampling keys are per request (seed = hash(engine seed, request
    # id), counter = token index), so the only sampling state warmup
    # could damage is the request-id counter and the request table.
    assert not engine._requests and not engine._finished
    assert repr(engine._next_id) == 'count(0)'
    prompt = [5, 9, 12, 4, 7]
    out = engine.generate_ids([prompt], GREEDY)[0]
    assert out == _dense_greedy(cfg, params, prompt, 6)


def test_prefix_metrics_exported():
    from distllm_tpu.observability import render_prometheus

    _, _, engine = _tiny_engine(enable_prefix_cache=True)
    engine.generate_ids([[1, 2, 3, 4, 5]], GREEDY)
    text = render_prometheus()
    for series in (
        'distllm_prefix_cache_hit_tokens_total',
        'distllm_prefix_cache_lookup_tokens_total',
        'distllm_prefix_cache_blocks',
        'distllm_prefix_cache_evictions_total',
        'distllm_prefix_cache_cow_copies_total',
        'distllm_engine_prefill_chunks_total',
    ):
        assert series in text, series
