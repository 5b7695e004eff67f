"""Host-RAM/disk KV tier for the prefix cache (ISSUE 14 tentpole):
HostKVTier LRU/byte-budget units, DiskKVTier round-trip + restart
persistence, spill→promote bit-exactness against never-evicted KV
(token identity with the tier on/off under greedy fp32), and PrefixCache
refcount invariants under cascaded eviction (docs/prefix_caching.md
"Tier hierarchy")."""

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
    DiskKVTier,
    HostKVTier,
    block_digests,
)
from distllm_tpu.models import mistral


def _digest(i: int) -> bytes:
    return block_digests(list(range(i * 4 + 1, i * 4 + 5)), 4)[0]


def _block(i: int, nbytes: int = 256) -> tuple[np.ndarray, np.ndarray]:
    """One fake per-block KV pair of ``nbytes`` total (k + v)."""
    half = nbytes // 2
    k = np.full((half // 4,), i, np.float32)
    return k, k + 1


# ------------------------------------------------------------ host tier
def test_host_tier_lru_order_and_byte_budget():
    tier = HostKVTier(max_bytes=3 * 256)
    for i in range(3):
        assert tier.put(_digest(i), *_block(i))
    assert tier.num_blocks == 3 and tier.bytes_used == 3 * 256
    # get() refreshes LRU: 0 becomes most-recent, so inserting a fourth
    # block must evict 1 (the oldest untouched), never 0.
    k0, v0 = tier.get(_digest(0))
    assert k0[0] == 0 and v0[0] == 1
    tier.put(_digest(3), *_block(3))
    assert tier.bytes_used == 3 * 256  # budget enforced
    assert tier.get(_digest(1)) is None  # LRU victim
    assert tier.get(_digest(0)) is not None  # refreshed entry survived
    # Duplicate put: first writer wins, no double-counted bytes.
    assert not tier.put(_digest(0), *_block(9))
    assert tier.bytes_used == 3 * 256
    assert tier.get(_digest(0))[0][0] == 0


def test_host_tier_lookup_is_membership_only():
    tier = HostKVTier(max_bytes=2 * 256)
    tier.put(_digest(0), *_block(0))
    tier.put(_digest(1), *_block(1))
    # lookup must NOT refresh LRU (it runs in add_request's walk): after
    # looking 0 up, 0 is still the eviction victim.
    assert tier.lookup(_digest(0)) == 'host'
    assert tier.lookup(_digest(7)) is None
    tier.put(_digest(2), *_block(2))
    assert tier.get(_digest(0)) is None


# ------------------------------------------------------------ disk tier
def test_disk_tier_round_trip_and_budget(tmp_path):
    tier = DiskKVTier(tmp_path, max_bytes=1 << 20)
    k = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    v = k * 2
    assert tier.put(_digest(0), k, v)
    assert tier.contains(_digest(0))
    rk, rv = tier.get(_digest(0))
    assert rk.dtype == k.dtype and rk.shape == k.shape
    np.testing.assert_array_equal(rk, k)
    np.testing.assert_array_equal(rv, v)
    # bf16 KV round-trips byte-exactly through the raw-bytes format.
    import jax.numpy as jnp

    kb = np.asarray(jnp.arange(8, dtype=jnp.bfloat16).reshape(2, 4))
    assert tier.put(_digest(1), kb, kb)
    rb, _ = tier.get(_digest(1))
    assert rb.dtype == kb.dtype
    assert rb.tobytes() == kb.tobytes()
    # Byte budget: a tiny-budget tier keeps only the newest entries.
    # (Budget sized for ONE 256-byte block plus its v2 header.)
    small = DiskKVTier(tmp_path / 'small', max_bytes=340)
    small.put(_digest(2), *_block(2))
    small.put(_digest(3), *_block(3))
    assert not small.contains(_digest(2))
    assert small.contains(_digest(3))


def test_disk_tier_index_rebuilds_across_instances(tmp_path):
    a = DiskKVTier(tmp_path, max_bytes=1 << 20)
    a.put(_digest(0), *_block(0))
    a.put(_digest(1), *_block(1))
    b = DiskKVTier(tmp_path, max_bytes=1 << 20)  # fresh process stand-in
    assert b.num_blocks == 2
    assert b.get(_digest(0)) is not None


def test_host_tier_write_through_and_disk_fallback(tmp_path):
    disk = DiskKVTier(tmp_path, max_bytes=1 << 20)
    tier = HostKVTier(max_bytes=256, disk=disk)  # host holds ONE block
    tier.put(_digest(0), *_block(0))
    tier.put(_digest(1), *_block(1))  # evicts 0 from host; disk keeps it
    assert disk.num_blocks == 2  # write-through persisted both
    assert tier.lookup(_digest(0)) == 'disk'
    k0, _ = tier.get(_digest(0))  # disk hit re-enters the host pool
    assert k0[0] == 0


# ----------------------------------------------------------------- engine
def _tiny_engine(**cfg_kwargs):
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
            prefer_native_allocator=False,
            enable_prefix_cache=True,
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


GREEDY = SamplingParams(temperature=0.0, max_tokens=4)
# 11-usable-block pool vs 24-token (6-block) prompts: every admission
# after the first evicts cached blocks — constant tier churn.
TIER_POOL = dict(num_blocks=12, max_num_seqs=2, max_model_len=48)
PROMPT_A = list(range(1, 25))
PROMPT_B = list(range(30, 54))


def test_spill_promote_round_trip_bit_exact():
    """Acceptance: a spilled-then-promoted prefix generates byte-identical
    tokens to the dense reference AND to a tier-off engine (greedy fp32),
    with >= 1 spill and >= 1 promotion actually recorded."""
    cfg, params, on = _tiny_engine(host_kv_tier_bytes=64 << 20, **TIER_POOL)
    _, _, off = _tiny_engine(**TIER_POOL)
    for prompt in (PROMPT_A, PROMPT_B, PROMPT_A):
        got_on = on.generate_ids([prompt], GREEDY)[0]
        got_off = off.generate_ids([prompt], GREEDY)[0]
        assert got_on == got_off == _dense_greedy(cfg, params, prompt, 4)
    # The B run evicted A's blocks into the tier; the second A promoted.
    assert on.tier_summary()['spilled_blocks'] > 0
    assert on._stats['tier_promotions'] >= 1
    assert on._stats['tier_promoted_blocks'] >= 1
    assert off.kv_tier is None


def test_refcount_invariants_under_cascaded_eviction():
    """free + cache-held == usable pool after a workload that spilled,
    promoted, and dropped through the cascade; host tier stays within
    budget. The no-leak twin of test_prefix_cache's eviction test."""
    cfg, params, engine = _tiny_engine(
        host_kv_tier_bytes=3 * 2 * 2 * 4 * 4 * 16 * 4,  # ~3 blocks
        **TIER_POOL,
    )
    rng = np.random.default_rng(3)
    for _ in range(8):
        prompt = list(rng.integers(1, 64, size=17))
        out = engine.generate_ids([prompt], GREEDY)[0]
        assert out == _dense_greedy(cfg, params, prompt, 4)
    usable = TIER_POOL['num_blocks'] - 1
    assert (
        engine.sched.num_free_blocks + engine.prefix_cache.num_cached
        == usable
    )
    assert engine.kv_tier.bytes_used <= engine.kv_tier.max_bytes
    assert engine.tier_summary()['spilled_blocks'] > 0


def test_disk_tier_persists_across_engine_restart(tmp_path):
    """Cold-start warm TTFT: a FRESH engine on the same digest chain
    promotes from the previous engine's disk spills and emits identical
    tokens."""
    cfg, params, first = _tiny_engine(
        host_kv_tier_bytes=64 << 20,
        disk_kv_tier_dir=str(tmp_path),
        **TIER_POOL,
    )
    want_a = _dense_greedy(cfg, params, PROMPT_A, 4)
    assert first.generate_ids([PROMPT_A], GREEDY)[0] == want_a
    # Force A's blocks through eviction so the spill reaches disk.
    first.generate_ids([PROMPT_B], GREEDY)
    assert first.kv_tier.disk.num_blocks > 0
    first.shutdown()

    _, _, fresh = _tiny_engine(
        host_kv_tier_bytes=64 << 20,
        disk_kv_tier_dir=str(tmp_path),
        **TIER_POOL,
    )
    assert fresh.generate_ids([PROMPT_A], GREEDY)[0] == want_a
    assert fresh._stats['tier_promotions'] >= 1
    assert fresh._stats.get('prefix_hit_tokens', 0) > 0


def test_promotion_survives_warmup_and_preemption_pressure():
    """The tier under the production serving-loop shape: warmup first
    (tier gather/scatter ladder compiles without state damage), then a
    preemption-heavy workload — outputs stay dense-exact."""
    cfg, params, engine = _tiny_engine(
        host_kv_tier_bytes=64 << 20,
        num_blocks=14,
        max_num_seqs=3,
        max_model_len=48,
        decode_steps=2,
        pipeline_depth=2,
    )
    engine.warmup()
    assert engine.sched.num_running == 0
    from test_engine import _expect_short_answers

    victims = _expect_short_answers(engine)
    stem = list(range(1, 13))
    prompts = [stem + [20 + i] for i in range(3)] + [PROMPT_B[:9]]
    for _ in range(2):  # second pass re-arrives after eviction/spill
        outs = engine.generate_ids(prompts, GREEDY)
        for p, o in zip(prompts, outs):
            assert o == _dense_greedy(cfg, params, p, 4), p
    assert victims() > 0


def test_tier_config_validation():
    with pytest.raises(ValueError, match='enable_prefix_cache'):
        EngineConfig(host_kv_tier_bytes=1 << 20)
    with pytest.raises(ValueError, match='host_kv_tier_bytes'):
        EngineConfig(
            enable_prefix_cache=True, disk_kv_tier_dir='/tmp/x'
        )


# ------------------------------------------------ KV-tier serving smoke
def _last_request(engine) -> dict:
    return [
        r for r in engine.flight.snapshot() if r['kind'] == 'request'
    ][-1]


def test_gen_tier_stage_cpu_smoke(tmp_path):
    """The KV-tier scenario end to end over ``serving_smoke.build_engine``
    (the tier-on engine warmed) and one ``disk_kv_tier_dir``.

    Open loop: four warm sessions of a four-block prefix against a pool of
    19 usable blocks that two running rows can fill, so session prefixes
    cannot stay resident: they spill and are promoted back, by
    construction. Tier on and tier off serve the same schedule to the same
    tokens. Then one request at a time: a prompt whose prefix the pool has
    since evicted is served from the tier (its ``request`` record shows the
    four prefix blocks as ``cached_tokens`` and a prefill of the tail only,
    by the paged route), where the tier-off engine prefills all of it; and
    a fresh engine over the same directory does the same from disk.

    Whether a promotion is FASTER than the prefill it replaces is a
    question for a cell on the chip (ROADMAP R3); a CPU's clock cannot
    answer it and this test reads no clock."""
    from distllm_tpu.generate.loadgen import build_workload, run_loadgen
    from distllm_tpu.observability import instruments as _m
    from serving_smoke import MODEL, build_engine, workload_config

    pool = dict(num_blocks=20)
    tier = dict(
        host_kv_tier_bytes=64 << 20, disk_kv_tier_dir=str(tmp_path / 'tier')
    )
    workload = build_workload(
        workload_config(
            num_sessions=4, warm_fraction=0.75, prefix_tokens=32,
            prompt_tokens=(4, 16), output_tokens=(4, 8),
        )
    )
    rng = np.random.default_rng(5)
    prompts = [
        [int(t) for t in rng.integers(1, MODEL.vocab_size, size=37)]
        for _ in range(7)
    ]
    greedy = SamplingParams(temperature=0.0, max_tokens=4)

    def serve(engine):
        """The open-loop run, then the seven prompts one at a time and the
        first again: each leaves four blocks cached, and six push all four
        of the first one's out (eviction takes a chain's oldest block
        first: fewer would leave its tail on the device, unreachable
        behind a spilled head). Returns the run's report, every output,
        and the ``request`` record of the repeated prompt."""
        report = run_loadgen(engine, workload)
        outs = [engine.generate_ids([p], greedy)[0] for p in prompts]
        again = engine.generate_ids([prompts[0]], greedy)[0]
        return report, outs + [again], _last_request(engine)

    on = build_engine(**pool, **tier)
    try:
        on_report, on_outs, on_record = serve(on)
        summary = on.tier_summary()
    finally:
        on.shutdown()
    off = build_engine(warm=False, **pool)
    try:
        off_report, off_outs, off_record = serve(off)
    finally:
        off.shutdown()

    assert on_report.tokens_by_request == off_report.tokens_by_request
    assert on_outs == off_outs and on_outs[0] == on_outs[-1]
    assert summary['spills'] >= 1 and summary['spilled_blocks'] >= 1
    assert summary['promotions'] >= 1 and summary['promoted_blocks'] >= 1
    assert summary['disk_blocks'] >= 1
    assert 0.0 <= summary['promotion_overlap'] <= 1.0
    assert on_report.warm_prefix_hit_tokens > 0
    # The repeated prompt: 32 of its 37 tokens came from the tier.
    assert on_record['prompt_tokens'] == off_record['prompt_tokens'] == 37
    assert on_record['cached_tokens'] == 32
    assert on_record['prefill_tokens'] == 5
    assert on_record['routes'] == {'paged': 1}
    assert off_record['cached_tokens'] == 0
    assert off_record['prefill_tokens'] == 37
    assert off_record['routes'] == {'dense': 1}

    # Warm restart: a fresh engine over the same directory finds the
    # first engine's spills on disk.
    disk_before = _m.PREFIX_TIER_PROMOTIONS.labels(tier='disk').value
    fresh = build_engine(warm=False, **pool, **tier)
    try:
        got = fresh.generate_ids([prompts[0]], greedy)[0]
        record = _last_request(fresh)
    finally:
        fresh.shutdown()
    assert got == on_outs[0]
    assert record['cached_tokens'] == 32 and record['prefill_tokens'] == 5
    assert _m.PREFIX_TIER_PROMOTIONS.labels(tier='disk').value > disk_before


def test_peer_kv_handoff_token_identity():
    """The peer tier end to end (docs/routing.md "Peer KV handoff"; the old
    benchmark's router stage was its only exercise): engine A spills a
    prompt's blocks to its host tier and serves them over the fabric; a
    cold engine B that lists A as a peer adopts them like a disk promotion
    and emits the tokens of the dense reference."""
    from distllm_tpu.observability import instruments as _m

    cfg, params, a = _tiny_engine(
        host_kv_tier_bytes=64 << 20,
        peer_kv_serve_endpoint='tcp://127.0.0.1:0',
        **TIER_POOL,
    )
    want = _dense_greedy(cfg, params, PROMPT_A, 4)
    peer_hits_before = _m.PREFIX_TIER_HITS.labels(tier='peer').value
    try:
        assert a.generate_ids([PROMPT_A], GREEDY)[0] == want
        a.generate_ids([PROMPT_B], GREEDY)  # evicts A's blocks to the tier
        assert a.tier_summary()['spilled_blocks'] > 0
        _, _, b = _tiny_engine(
            host_kv_tier_bytes=64 << 20,
            peer_kv_endpoints=(a.peer_kv_endpoint,),
            **TIER_POOL,
        )
        try:
            assert b.generate_ids([PROMPT_A], GREEDY)[0] == want
            assert b.tier_summary()['peer_fetched_blocks'] >= 1
            assert _last_request(b)['cached_tokens'] > 0
        finally:
            b.shutdown()
        assert a.tier_summary()['peer_served_blocks'] >= 1
    finally:
        a.shutdown()
    assert _m.PREFIX_TIER_HITS.labels(tier='peer').value > peer_hits_before


def test_tier_metrics_exported(tmp_path):
    from distllm_tpu.observability import render_prometheus

    _, _, engine = _tiny_engine(
        host_kv_tier_bytes=64 << 20,
        disk_kv_tier_dir=str(tmp_path),
        **TIER_POOL,
    )
    for prompt in (PROMPT_A, PROMPT_B, PROMPT_A):
        engine.generate_ids([prompt], GREEDY)
    text = render_prometheus()
    for series in (
        'distllm_prefix_tier_hits_total',
        'distllm_prefix_tier_misses_total',
        'distllm_prefix_tier_spills_total',
        'distllm_prefix_tier_promotions_total',
        'distllm_prefix_tier_bytes',
        'distllm_prefix_tier_evictions_total',
        'distllm_prefix_tier_dropped_blocks_total',
    ):
        assert series in text, series


# ------------------------------------------- resilience satellites (ISSUE 15)
def test_disk_tier_corrupt_kvblock_degrades_to_miss(tmp_path):
    """A corrupt or truncated .kvblock (bad header, short read) must count
    a distllm_prefix_tier_errors_total{tier="disk"}, drop the entry, and
    return None — never raise toward add_request."""
    from distllm_tpu.observability import instruments as _m

    tier = DiskKVTier(tmp_path, max_bytes=1 << 20)
    k = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    for i in range(3):
        assert tier.put(_digest(i), k + i, k * 2)

    def _file(i):
        return tmp_path / f'{_digest(i).hex()}.kvblock'

    # Three corruption classes: no header line at all, a header that is
    # not a shape/dtype record, and a body truncated mid-array.
    _file(0).write_bytes(b'garbage with no newline header')
    _file(1).write_bytes(b'{"not": "a shape record"}\n1234')
    payload = _file(2).read_bytes()
    _file(2).write_bytes(payload[: len(payload) // 2 + 7])

    errors_before = _m.PREFIX_TIER_ERRORS.labels(tier='disk').value
    for i in range(3):
        assert tier.get(_digest(i)) is None
    assert (
        _m.PREFIX_TIER_ERRORS.labels(tier='disk').value == errors_before + 3
    )
    # Entries dropped and corrupt files unlinked: the tier self-heals
    # instead of serving the same corruption forever.
    assert tier.num_blocks == 0
    assert not any(_file(i).exists() for i in range(3))
    # A healthy put/get cycle still works after the corruption storm.
    assert tier.put(_digest(3), k, k * 2)
    got_k, _ = tier.get(_digest(3))
    np.testing.assert_array_equal(got_k, k)


def test_corrupt_disk_tier_falls_through_to_cold_prefill(tmp_path):
    """Engine-level regression: every .kvblock corrupted behind the
    engine's back — add_request's tier walk plans promotions, the loads
    fail, and the requests cold-prefill to bit-exact tokens with the
    error counter as the only trace (never an exception)."""
    from distllm_tpu.observability import instruments as _m

    tier_dir = tmp_path / 'tier'
    # host_kv_tier_bytes=1: every spill is immediately evicted from the
    # host pool (write-through has already persisted it), so the DISK
    # tier is the only place warm prefixes survive — exactly the restart
    # topology the corruption must not break.
    cfg, params, engine = _tiny_engine(
        host_kv_tier_bytes=1, disk_kv_tier_dir=str(tier_dir), **TIER_POOL
    )
    first = engine.generate_ids([PROMPT_A], GREEDY)[0]
    engine.generate_ids([PROMPT_B], GREEDY)  # evicts A's blocks -> disk
    files = list(tier_dir.glob('*.kvblock'))
    assert files
    for path in files:
        path.write_bytes(b'corrupt')
    errors_before = _m.PREFIX_TIER_ERRORS.labels(tier='disk').value
    got = engine.generate_ids([PROMPT_A], GREEDY)[0]
    assert got == first == _dense_greedy(cfg, params, PROMPT_A, 4)
    assert _m.PREFIX_TIER_ERRORS.labels(tier='disk').value > errors_before
    assert not engine._stats.get('tier_promotions')


# --------------------------------- quantized int8 KV tier (docs/serving.md)
def test_disk_tier_v2_scales_round_trip(tmp_path):
    """A quantized spill (int8 data + fp32 per-block scales) round-trips
    byte-exactly through the v2 .kvblock layout — the body is sliced at
    exact header-derived offsets, never halved."""
    tier = DiskKVTier(tmp_path, max_bytes=1 << 20)
    rng = np.random.default_rng(0)
    k = rng.integers(-127, 128, size=(2, 4, 2, 8)).astype(np.int8)
    v = rng.integers(-127, 128, size=(2, 4, 2, 8)).astype(np.int8)
    ks = rng.uniform(0.01, 0.1, size=(2, 2)).astype(np.float32)
    vs = rng.uniform(0.01, 0.1, size=(2, 2)).astype(np.float32)
    assert tier.put(_digest(0), k, v, ks, vs)
    got = tier.get(_digest(0))
    assert len(got) == 4
    for a, b in zip(got, (k, v, ks, vs)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    # Restart: a fresh instance parses the same v2 files.
    fresh = DiskKVTier(tmp_path, max_bytes=1 << 20)
    assert len(fresh.get(_digest(0))) == 4


def test_disk_tier_versionless_kvblock_still_loads(tmp_path):
    """Pre-int8 spills (no ``version`` field, body = K bytes then V
    bytes) must keep loading on the legacy halve-the-body path — a repo
    upgrade must not cold-start every existing spill directory."""
    import json as _json

    tier = DiskKVTier(tmp_path, max_bytes=1 << 20)
    k = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    v = k * 2
    # Index the digest via a normal put, then rewrite the file in the
    # legacy layout behind the tier's back.
    assert tier.put(_digest(0), k, v)
    header = _json.dumps(
        {'shape': list(k.shape), 'dtype': str(k.dtype)}
    ).encode() + b'\n'
    path = tmp_path / f'{_digest(0).hex()}.kvblock'
    path.write_bytes(header + k.tobytes() + v.tobytes())
    rk, rv = tier.get(_digest(0))
    np.testing.assert_array_equal(rk, k)
    np.testing.assert_array_equal(rv, v)


def test_disk_tier_unknown_version_degrades_to_miss(tmp_path):
    """A .kvblock from a NEWER format (version 3) counts a
    distllm_prefix_tier_errors_total{tier="disk"}, drops the entry, and
    returns None — an old reader must cold-prefill, never hand the
    attention kernel another layout's bytes."""
    import json as _json

    from distllm_tpu.observability import instruments as _m

    tier = DiskKVTier(tmp_path, max_bytes=1 << 20)
    k = np.arange(8, dtype=np.float32)
    assert tier.put(_digest(0), k, k)
    header = _json.dumps(
        {'version': 3, 'shape': [8], 'dtype': 'float32'}
    ).encode() + b'\n'
    path = tmp_path / f'{_digest(0).hex()}.kvblock'
    path.write_bytes(header + k.tobytes() + k.tobytes())
    errors_before = _m.PREFIX_TIER_ERRORS.labels(tier='disk').value
    assert tier.get(_digest(0)) is None
    assert (
        _m.PREFIX_TIER_ERRORS.labels(tier='disk').value == errors_before + 1
    )
    assert tier.num_blocks == 0
    assert not path.exists()


def test_int8_spill_promote_round_trip_bit_exact():
    """The int8 pool's spill→promote loop is LOSSLESS: int8 data and
    fp32 scales ride the tiers as-is (no requantization), so a tier-on
    int8 engine must emit byte-identical tokens to a tier-off int8
    engine on the same eviction-churn workload."""
    _, _, on = _tiny_engine(
        host_kv_tier_bytes=64 << 20, kv_cache_dtype='int8', **TIER_POOL
    )
    _, _, off = _tiny_engine(kv_cache_dtype='int8', **TIER_POOL)
    assert on.kv.quantized and off.kv.quantized
    for prompt in (PROMPT_A, PROMPT_B, PROMPT_A):
        assert (
            on.generate_ids([prompt], GREEDY)[0]
            == off.generate_ids([prompt], GREEDY)[0]
        )
    assert on.tier_summary()['spilled_blocks'] > 0
    assert on._stats['tier_promotions'] >= 1


def test_int8_disk_warm_restart_promotes(tmp_path):
    """A fresh int8 engine over the previous process's spill directory
    promotes int8 blocks + scales from disk and reproduces the first
    engine's tokens — the v2 format carries everything promotion needs."""
    _, _, first = _tiny_engine(
        host_kv_tier_bytes=64 << 20,
        disk_kv_tier_dir=str(tmp_path),
        kv_cache_dtype='int8',
        **TIER_POOL,
    )
    want = first.generate_ids([PROMPT_A], GREEDY)[0]
    first.generate_ids([PROMPT_B], GREEDY)  # evict A's blocks -> disk
    assert first.kv_tier.disk.num_blocks > 0
    first.shutdown()

    _, _, fresh = _tiny_engine(
        host_kv_tier_bytes=64 << 20,
        disk_kv_tier_dir=str(tmp_path),
        kv_cache_dtype='int8',
        **TIER_POOL,
    )
    assert fresh.generate_ids([PROMPT_A], GREEDY)[0] == want
    assert fresh._stats['tier_promotions'] >= 1


def test_fp32_engine_over_int8_spills_cold_prefills(tmp_path):
    """Payload-arity defense: a full-precision engine meeting a
    quantized pool's 4-array spills must treat every one as a miss
    (tier_payload_mismatches), cold-prefill, and still emit dense-exact
    tokens — never scatter int8 bytes into an fp32 pool."""
    _, _, q = _tiny_engine(
        host_kv_tier_bytes=1,  # write-through then immediate host evict
        disk_kv_tier_dir=str(tmp_path),
        kv_cache_dtype='int8',
        **TIER_POOL,
    )
    q.generate_ids([PROMPT_A], GREEDY)
    q.generate_ids([PROMPT_B], GREEDY)
    assert q.kv_tier.disk.num_blocks > 0
    q.shutdown()

    cfg, params, fp = _tiny_engine(
        host_kv_tier_bytes=64 << 20,
        disk_kv_tier_dir=str(tmp_path),
        **TIER_POOL,
    )
    got = fp.generate_ids([PROMPT_A], GREEDY)[0]
    assert got == _dense_greedy(cfg, params, PROMPT_A, 4)
    assert fp._stats.get('tier_payload_mismatches', 0) >= 1
    assert not fp._stats.get('tier_promoted_blocks')


def test_disk_tier_warm_restart_bit_exact(tmp_path):
    """ISSUE 15 satellite: kill an engine mid-run, rebuild over the same
    disk_kv_tier_dir, and the fresh engine promotes the previous
    process's spills — warm prefix coverage and bit-exact tokens versus
    an unkilled run."""
    from distllm_tpu.observability import instruments as _m

    tier_dir = str(tmp_path / 'tier')
    kwargs = dict(
        host_kv_tier_bytes=64 << 20, disk_kv_tier_dir=tier_dir, **TIER_POOL
    )
    cfg, params, a = _tiny_engine(**kwargs)
    first = a.generate_ids([PROMPT_A], GREEDY)[0]
    # Kill mid-run: admit PROMPT_B (its admission pressure spills A's
    # cached blocks, write-through persisting them), take a couple of
    # engine steps, then abandon the process state with no graceful
    # flush — exactly what a SIGKILL leaves behind.
    a.add_request(list(PROMPT_B), GREEDY)
    a.step()
    a.step()
    a.shutdown()
    assert list((tmp_path / 'tier').glob('*.kvblock'))

    disk_promos_before = _m.PREFIX_TIER_PROMOTIONS.labels(
        tier='disk'
    ).value
    _, _, b = _tiny_engine(**kwargs)  # fresh process over the same dir
    got = b.generate_ids([PROMPT_A], GREEDY)[0]
    # Unkilled reference: same engine shape, fresh tier dir.
    _, _, ref = _tiny_engine(
        host_kv_tier_bytes=64 << 20,
        disk_kv_tier_dir=str(tmp_path / 'ref'),
        **TIER_POOL,
    )
    want = ref.generate_ids([PROMPT_A], GREEDY)[0]
    assert got == want == first == _dense_greedy(cfg, params, PROMPT_A, 4)
    # Warm restart is real: the rebuilt engine promoted spilled blocks
    # from disk (prefill covered cached tokens) instead of cold-running.
    assert b._stats.get('tier_promotions', 0) >= 1
    assert b._stats.get('prefix_hit_tokens', 0) > 0
    assert (
        _m.PREFIX_TIER_PROMOTIONS.labels(tier='disk').value
        > disk_promos_before
    )


def test_kvblock_written_in_the_logical_layout_promotes_into_a_folded_pool(tmp_path):
    """The pool stores a token's heads folded into one row; a ``.kvblock``
    file keeps the block shape it had before, ``[L, block_size, N_kv,
    Hd]``, so a directory written before that change still loads. The
    files here are built by hand: the header bytes are pinned, the body is
    the dense forward's K then V of the block in that shape. A fresh
    engine over the directory promotes them (no prefill of those blocks),
    holds exactly those bytes under the host's view of the pool, and
    emits the dense reference's tokens."""
    from distllm_tpu.generate.engine.kv_cache import block_digests, decode_kvblock

    cfg, params, _ = _tiny_engine(**TIER_POOL)
    ids = np.asarray([PROMPT_A], np.int32)
    _, k_all, v_all = mistral.prefill(params, cfg, ids, np.ones_like(ids))
    header = b'{"version":2,"shape":[2,4,2,8],"dtype":"float32","scales":null}\n'
    blocks = []
    for i, digest in enumerate(block_digests(PROMPT_A, 4)):
        k, v = (
            np.ascontiguousarray(np.asarray(a)[:, 0, 4 * i:4 * i + 4])
            for a in (k_all, v_all)
        )
        assert k.shape == (2, 4, 2, 8) and k.dtype == np.float32
        payload = header + k.tobytes() + v.tobytes()
        assert len(payload) == len(header) + 2 * 512
        got_k, got_v = decode_kvblock(payload)
        assert got_k.shape == (2, 4, 2, 8) and got_k.tobytes() == k.tobytes()
        (tmp_path / f'{digest.hex()}.kvblock').write_bytes(payload)
        blocks.append((k, v))

    _, _, fresh = _tiny_engine(
        host_kv_tier_bytes=64 << 20, disk_kv_tier_dir=str(tmp_path),
        **TIER_POOL,
    )
    assert fresh.kv.k_pool.shape == (2, 12, 4, 16)  # folded
    rid = fresh.add_request(list(PROMPT_A), GREEDY)
    while fresh.has_unfinished:
        fresh.step()
    request = fresh._finished[rid]
    assert request.output_ids == _dense_greedy(cfg, params, PROMPT_A, 4)
    assert fresh._stats['tier_promotions'] >= 1
    promoted = fresh._stats['tier_promoted_blocks']
    assert promoted >= 5 and fresh._stats['prefix_hit_tokens'] >= 4 * promoted
    # The promoted blocks are still cached: under the host's view they hold
    # the files' bytes, layer by layer.
    held = fresh.prefix_cache.match(block_digests(PROMPT_A, 4))[:promoted]
    assert len(held) == promoted
    for layer in range(2):
        got_k = np.asarray(fresh.kv.k[layer][held])
        got_v = np.asarray(fresh.kv.v[layer][held])
        assert got_k.shape == (promoted, 4, 2, 8)
        for j in range(promoted):
            assert got_k[j].tobytes() == blocks[j][0][layer].tobytes()
            assert got_v[j].tobytes() == blocks[j][1][layer].tobytes()
