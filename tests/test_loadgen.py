"""Open-loop load generator tests (ISSUE 10 tentpole + CI satellite):
deterministic seeded workloads, the in-process run harness against a tiny
real engine, and the open-loop scenario over a warmed engine as a CPU smoke
(tens of requests, seeded): every latency field present, a warm-prefix
hit, and attribution-on/off token identity."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax

from distllm_tpu.generate.engine import EngineConfig, LLMEngine
from distllm_tpu.generate.loadgen import (
    LoadgenConfig,
    build_workload,
    run_loadgen,
)
from distllm_tpu.models import mistral

REPO = Path(__file__).resolve().parent.parent


# ----------------------------------------------------------- workload build
def test_build_workload_deterministic():
    cfg = LoadgenConfig(seed=7, num_requests=40)
    a = build_workload(cfg)
    b = build_workload(cfg)
    assert a == b  # same seed -> byte-identical workload
    c = build_workload(LoadgenConfig(seed=8, num_requests=40))
    assert a != c


def test_cache_blocks_is_an_engine_knob_not_a_workload_knob():
    """cache_blocks overrides the ENGINE pool size (so CPU smokes can
    force HBM-tier eviction with tiny pools — the gen_tier stage); the
    workload itself must be byte-identical across pool sizes, or tier
    on/off A/Bs would silently measure different traffic."""
    a = build_workload(LoadgenConfig(seed=7, num_requests=40))
    b = build_workload(
        LoadgenConfig(seed=7, num_requests=40, cache_blocks=48)
    )
    assert a == b
    assert LoadgenConfig().cache_blocks is None


def test_build_workload_poisson_arrivals_and_mix():
    cfg = LoadgenConfig(
        seed=0, num_requests=200, rate_rps=10.0, num_sessions=3,
        warm_fraction=0.5, prefix_tokens=16,
    )
    workload = build_workload(cfg)
    assert len(workload) == 200
    ats = [a.at_s for a in workload]
    assert ats == sorted(ats)
    assert all(at > 0 for at in ats)
    # Mean inter-arrival gap ~ 1/rate (Poisson process, generous bound).
    mean_gap = ats[-1] / len(ats)
    assert 0.05 < mean_gap < 0.2
    warm = [a for a in workload if a.session is not None]
    cold = [a for a in workload if a.session is None]
    assert len(warm) > 50 and len(cold) > 50  # both sides of the mix
    # Warm requests share their session's full prefix; sessions differ.
    by_session: dict = {}
    for a in warm:
        by_session.setdefault(a.session, []).append(a)
    assert len(by_session) == 3
    for session, arrivals in by_session.items():
        prefixes = {a.prompt_ids[: cfg.prefix_tokens] for a in arrivals}
        assert len(prefixes) == 1
    all_prefixes = {
        arrivals[0].prompt_ids[: cfg.prefix_tokens]
        for arrivals in by_session.values()
    }
    assert len(all_prefixes) == 3
    # Output budgets stay in range.
    lo, hi = cfg.output_tokens
    assert all(lo <= a.max_tokens <= hi for a in workload)


def test_build_workload_rejects_bad_config():
    import pytest

    with pytest.raises(ValueError):
        build_workload(LoadgenConfig(num_requests=0))
    with pytest.raises(ValueError):
        build_workload(LoadgenConfig(rate_rps=0.0))


# --------------------------------------------------------- run harness
def test_run_loadgen_tiny_engine_reports():
    cfg = mistral.MistralConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, dtype='float32',
    )
    params = mistral.init(jax.random.PRNGKey(0), cfg)

    class IdTokenizer:
        eos_id = None

    engine = LLMEngine(
        cfg, params, IdTokenizer(),
        EngineConfig(
            block_size=4, num_blocks=64, max_num_seqs=4, max_model_len=64,
            prefer_native_allocator=False, enable_prefix_cache=True,
            ttft_slo_s=30.0, decode_steps=4,
        ),
    )
    load_cfg = LoadgenConfig(
        seed=3, num_requests=10, rate_rps=200.0, num_sessions=2,
        warm_fraction=0.6, prefix_tokens=8, prompt_tokens=(3, 10),
        output_tokens=(2, 6), vocab_size=cfg.vocab_size,
    )
    workload = build_workload(load_cfg)
    report = run_loadgen(engine, workload)
    assert report.requests == 10
    assert report.tokens > 0
    assert len(report.tokens_by_request) == 10
    for arrival, tokens in zip(
        sorted(workload, key=lambda a: a.at_s), report.tokens_by_request
    ):
        assert 0 < len(tokens) <= arrival.max_tokens
    # Histogram-estimated percentiles exist and are positive and ordered.
    p50 = report.percentiles['ttft_p50']
    p95 = report.percentiles['ttft_p95']
    p99 = report.percentiles['ttft_p99']
    assert p50 and p50 > 0
    assert p95 and p50 <= p95 <= p99
    assert report.percentiles['queue_wait_p50'] is not None
    # Warm sessions actually hit the prefix cache (2-block prefixes).
    assert report.warm_prefix_hit_tokens > 0
    assert report.warm_requests + report.cold_requests == 10
    # SLO accounting: a 30 s SLO on a tiny engine is always met.
    assert report.slo_met == 10 and report.slo_missed == 0
    assert report.goodput_tokens == report.tokens
    # Roofline attribution ran per window kind.
    assert 'decode' in report.roofline and 'prefill' in report.roofline
    assert report.roofline['decode']['mfu'] > 0
    assert report.roofline['decode']['bw_util'] > 0
    # Flight records carry the attribution split on this run's windows.
    decode_records = [
        r for r in engine.flight.snapshot()
        if r['kind'] == 'decode' and 'fetch_s' in r
    ]
    assert decode_records
    assert all('dispatch_s' in r and 'mfu' in r for r in decode_records)
    # And the fragment flattening used by the bench stage is total —
    # and strict-JSON clean (no inf/nan leaks into the bench record).
    fragment = report.to_fragment('x_')
    assert fragment['x_requests'] == 10
    assert fragment['x_ttft_p50'] == round(p50, 6)
    assert 'x_mfu_decode' in fragment and 'x_bw_util_decode' in fragment
    json.loads(json.dumps(fragment, allow_nan=False))

    # Attribution-off replay on the SAME warm engine: bit-identical
    # greedy tokens, and the roofline summary is delta-scoped — nothing
    # accumulates while attribution is off, so the off arm reports {}
    # instead of the on arm's stale aggregate.
    engine.attribution = False
    off = run_loadgen(engine, workload)
    assert off.tokens_by_request == report.tokens_by_request
    assert off.roofline == {}
    # Flipping attribution ON at runtime works even though this engine
    # could have been built with attribution off (cost model is always
    # constructed): the next run accumulates again.
    engine.attribution = True
    back_on = run_loadgen(engine, workload)
    assert back_on.tokens_by_request == report.tokens_by_request
    assert 'decode' in back_on.roofline


def test_run_loadgen_single_request_offered_rps_is_json_safe():
    from distllm_tpu.generate.loadgen import LoadReport

    report = LoadReport(
        requests=1, tokens=4, elapsed_s=0.1, offered_rps=None,
        achieved_tok_s=40.0, percentiles={}, window_tok_s={},
        goodput_tokens=4, goodput_frac=1.0, slo_met=1, slo_missed=0,
        warm_prefix_hit_tokens=0, warm_requests=0, cold_requests=1,
        roofline={}, tokens_by_request=[[1, 2, 3, 4]],
    )
    fragment = report.to_fragment('x_')
    assert fragment['x_offered_rps'] is None
    json.loads(json.dumps(fragment, allow_nan=False))


# ------------------------------------------ open-loop serving smoke (CPU)
def test_gen_load_stage_cpu_smoke():
    """The open-loop scenario end to end, over the engine a deployment
    builds (``serving_smoke.build_engine``: device-made weights the engine
    owns, ``attn_backend='auto'``, the pipelined loop, warmed): a seeded
    Poisson schedule of warm and cold sessions is served whole, the report
    carries every TTFT/TPOT/queue-wait percentile and the goodput and
    roofline fields, a warm session hits the prefix cache, and the same
    schedule replayed with attribution off emits the same tokens. The
    values are a CPU's and are not judged; the cells of ``BENCHMARK.json``
    measure them (``mistral7b.chat_steady``)."""
    from serving_smoke import build_engine, workload_config

    engine = build_engine(ttft_slo_s=30.0)
    try:
        workload = build_workload(workload_config())
        on = run_loadgen(engine, workload)
        # Both arms start on an empty prefix cache: a prompt served from
        # cached blocks takes the paged tail prefill and the same prompt on
        # a cold cache the dense one. With no request live every cached
        # block is evictable.
        engine._evict_cached_blocks(engine.config.num_blocks)
        engine.attribution = False
        off = run_loadgen(engine, workload)
    finally:
        engine.shutdown()
    assert on.requests == len(workload) == 24
    assert on.tokens_by_request == off.tokens_by_request
    assert all(
        0 < len(tokens) <= arrival.max_tokens
        for arrival, tokens in zip(workload, on.tokens_by_request)
    )
    assert on.warm_prefix_hit_tokens >= 1
    assert on.slo_met + on.slo_missed == 24
    assert on.goodput_tokens > 0
    fragment = on.to_fragment('load_')
    json.loads(json.dumps(fragment, allow_nan=False))
    for field in (
        'ttft_p50', 'ttft_p95', 'ttft_p99', 'tpot_p50', 'tpot_p95',
        'queue_wait_p50', 'goodput_tok_s_p50', 'mfu_decode',
        'bw_util_decode', 'mfu_prefill',
    ):
        assert fragment[f'load_{field}'] is not None, field
    assert fragment['load_ttft_p50'] <= fragment['load_ttft_p95']
    assert fragment['load_ttft_p95'] <= fragment['load_ttft_p99']
    assert off.roofline == {}  # nothing accumulates while attribution is off


def test_loadgen_cli_reports_history_excerpt():
    """scripts/loadgen.py (ISSUE 18 satellite): the CLI owns the process
    history sampler for its run, and the JSON report line carries the
    compact ``loadgen_history_*`` excerpt — the sampled tok/s series plus
    the SLO burn-rate gauges — not just end-of-run aggregates.

    The shortest run that yields the excerpt: four requests through one slot
    (warm-up compiles one batch size), and a tick every 50 ms of a run of
    under half a second, so that a sampler thread a loaded machine starves
    still ticks twice (at 0.2 s the run ended after two ticks, or one)."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [
            sys.executable, str(REPO / 'scripts' / 'loadgen.py'),
            '--small', '--requests', '4', '--rate', '50', '--slo', '2.0',
            '--max-num-seqs', '1', '--history-interval', '0.05',
        ],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    fragment = json.loads(proc.stdout.strip().splitlines()[-1])
    assert fragment['loadgen_requests'] == 4
    assert fragment['loadgen_history_window_s'] == 60.0
    assert fragment['loadgen_history_samples'] >= 2
    assert fragment['loadgen_history_tok_s'] > 0
    assert fragment['loadgen_history_tok_points']
    assert set(fragment['loadgen_history_burn_rates']) == {
        '60s', '300s', '600s', '3600s'
    }
