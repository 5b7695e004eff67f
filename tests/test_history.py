"""Metric history ring, background sampler, SLO burn-rate engine, and
the runtime regression sentinel (ISSUE 18 tentpole + satellites)."""

from __future__ import annotations

import json
import threading
import time

import pytest

from distllm_tpu.observability import instruments as _metrics
from distllm_tpu.observability.baseline import (
    ENVELOPE_SCHEMA,
    build_envelope,
    gate_direction,
    load_envelope,
)
from distllm_tpu.observability.flight import FlightRecorder
from distllm_tpu.observability.history import (
    SAMPLER_THREAD_NAME,
    HistorySampler,
    MetricsHistory,
    get_metrics_history,
    history_excerpt,
    series_key,
)
from distllm_tpu.observability.metrics import MetricsRegistry
from distllm_tpu.observability.sentinel import RegressionSentinel
from distllm_tpu.observability.slo import (
    burn_rate,
    slo_status,
    update_burn_gauges,
)


def _fresh() -> tuple[MetricsRegistry, MetricsHistory]:
    registry = MetricsRegistry()
    return registry, MetricsHistory(registry, capacity=64)


# ------------------------------------------------------------------- ring
def test_series_key_sorts_labels():
    assert series_key('m') == 'm'
    assert series_key('m', {'b': '2', 'a': '1'}) == 'm{a=1,b=2}'


def test_counter_history_deltas_and_rates():
    registry, history = _fresh()
    c = registry.counter('test_tokens_total')
    c.inc(10)
    history.sample_once(now=100.0)  # first sighting: no interval yet
    c.inc(20)
    history.sample_once(now=101.0)
    c.inc(5)
    history.sample_once(now=103.0)
    win = history.counter_window('test_tokens_total', 10.0, now=103.0)
    assert win['delta'] == 25.0
    assert win['covered_s'] == pytest.approx(3.0)
    assert win['rate'] == pytest.approx(25.0 / 3.0)
    # A narrower window isolates the newest tick only.
    narrow = history.counter_window('test_tokens_total', 1.5, now=103.0)
    assert narrow['delta'] == 5.0
    assert narrow['rate'] == pytest.approx(2.5)
    # Counter reset (process restart): delta clamps to 0, never negative.
    c._default_child()._value = 1.0  # simulate a post-restart lower reading
    history.sample_once(now=104.0)
    after = history.counter_window('test_tokens_total', 0.9, now=104.0)
    assert after['delta'] == 0.0


def test_counter_history_unseen_series_is_empty():
    _, history = _fresh()
    win = history.counter_window('never_seen_total', 60.0, now=1.0)
    assert win == {
        'delta': 0, 'rate': None, 'covered_s': 0, 'points': 0,
    }
    assert history.counter_rate('never_seen_total', 60.0) is None


def test_gauge_history_window_aggregates():
    registry, history = _fresh()
    g = registry.gauge('test_depth')
    for now, value in ((1.0, 2.0), (2.0, 8.0), (3.0, 4.0)):
        g.set(value)
        history.sample_once(now=now)
    assert history.gauge_window('test_depth', 10, now=3.0) == pytest.approx(
        14.0 / 3.0
    )
    assert history.gauge_window('test_depth', 10, agg='last', now=3.0) == 4.0
    assert history.gauge_window('test_depth', 10, agg='min', now=3.0) == 2.0
    assert history.gauge_window('test_depth', 10, agg='max', now=3.0) == 8.0
    assert history.gauge_window('test_depth', 0.5, now=0.0) is None
    with pytest.raises(ValueError):
        history.gauge_window('test_depth', 10, agg='median', now=3.0)


def test_labeled_series_are_independent():
    registry, history = _fresh()
    c = registry.counter('test_by_kind_total', labelnames=('kind',))
    c.labels(kind='a').inc(1)
    c.labels(kind='b').inc(1)
    history.sample_once(now=1.0)
    c.labels(kind='a').inc(9)
    history.sample_once(now=2.0)
    a = history.counter_window(
        'test_by_kind_total', 10, labels={'kind': 'a'}, now=2.0
    )
    b = history.counter_window(
        'test_by_kind_total', 10, labels={'kind': 'b'}, now=2.0
    )
    assert a['delta'] == 9.0
    assert b['delta'] == 0.0


def test_histogram_window_quantile_isolates_window():
    """The tentpole quantile contract: a trailing window's quantile
    covers ONLY that window's observations — warmup noise before it must
    not leak in (the delta-cumulative estimator)."""
    registry, history = _fresh()
    h = registry.histogram('test_lat_seconds', buckets=(1.0, 2.0, 4.0))
    history.sample_once(now=100.0)  # baseline snapshot (no point yet)
    h.observe(0.5)  # pre-window noise, lands in tick 2's interval
    history.sample_once(now=101.0)
    for _ in range(10):
        h.observe(3.0)
    history.sample_once(now=102.0)
    p50 = history.window_quantile('test_lat_seconds', 0.5, 1.5, now=102.0)
    assert 2.0 < p50 <= 4.0  # the 0.5 s observation is excluded
    # A window spanning both ticks sees the noise too.
    p5 = history.window_quantile('test_lat_seconds', 0.05, 10.0, now=102.0)
    assert p5 <= 1.0
    # An idle window has no observations: None, never a division.
    history.sample_once(now=103.0)
    assert (
        history.window_quantile('test_lat_seconds', 0.95, 0.5, now=103.0)
        is None
    )
    assert history.window_quantile('missing_seconds', 0.5, 10.0) is None


def test_history_capacity_bounds_every_ring():
    registry = MetricsRegistry()
    history = MetricsHistory(registry, capacity=4)
    c = registry.counter('test_bounded_total')
    for i in range(10):
        c.inc()
        history.sample_once(now=float(i))
    win = history.counter_window('test_bounded_total', 1e9, now=9.0)
    assert win['points'] == 4  # oldest points evicted, never unbounded
    with pytest.raises(ValueError):
        MetricsHistory(registry, capacity=1)


def test_snapshot_schema_and_filters():
    registry, history = _fresh()
    registry.counter('test_snap_total').inc(2)
    registry.gauge('test_snap_depth').set(3.0)
    h = registry.histogram('test_snap_seconds', buckets=(1.0,))
    h.observe(0.5)
    history.sample_once(now=1.0)
    h.observe(0.7)
    registry.counter('test_snap_total').inc(1)
    history.sample_once(now=2.0)
    snap = history.snapshot()
    assert snap['schema'] == 'distllm-history/v1'
    assert snap['capacity'] == 64
    assert snap['samples'] == 2
    assert snap['quantiles'] == [0.5, 0.95, 0.99]
    counter = snap['series']['test_snap_total']
    assert counter['kind'] == 'counter'
    # [t, delta, rate] — the first sighting produced no point.
    assert counter['points'] == [[2.0, 1.0, 1.0]]
    gauge = snap['series']['test_snap_depth']
    assert gauge['points'] == [[1.0, 3.0], [2.0, 3.0]]
    hist = snap['series']['test_snap_seconds']
    (point,) = hist['points']
    t, count_delta, rate, p50, p95, p99 = point
    assert (t, count_delta, rate) == (2.0, 1, 1.0)
    assert p50 is not None and p50 <= 1.0
    # prefix filter + per-series limit
    only = history.snapshot(prefix='test_snap_t')
    assert list(only['series']) == ['test_snap_total']
    trimmed = history.snapshot(limit=1)
    assert len(trimmed['series']['test_snap_depth']['points']) == 1
    # The document is JSON-serializable as-is (the endpoint contract).
    json.dumps(snap)


def test_histogram_idle_tick_renders_null_quantiles():
    registry, history = _fresh()
    h = registry.histogram('test_idle_seconds', buckets=(1.0,))
    h.observe(0.5)
    history.sample_once(now=1.0)
    history.sample_once(now=2.0)  # no new observations this interval
    history.sample_once(now=3.0)
    points = history.snapshot()['series']['test_idle_seconds']['points']
    assert [p[1] for p in points] == [0, 0]
    assert all(p[3] is None for p in points)  # p50 null, not 0/0


def test_clear_drops_points_and_delta_state():
    registry, history = _fresh()
    c = registry.counter('test_clear_total')
    c.inc(5)
    history.sample_once(now=1.0)
    c.inc(5)
    history.sample_once(now=2.0)
    history.clear()
    assert history.samples == 0
    assert history.snapshot()['series'] == {}
    # Post-clear the next tick is a first sighting again: no giant delta.
    history.sample_once(now=3.0)
    assert history.counter_window('test_clear_total', 10, now=3.0)[
        'delta'
    ] == 0


def test_observer_runs_after_tick_and_errors_are_counted():
    registry, history = _fresh()
    registry.counter('test_obs_total').inc()
    seen: list[float] = []

    def ok_observer(h, now):
        # Observers run OUTSIDE the ring lock: window helpers (which
        # take the lock) must be callable from here without deadlock.
        h.counter_window('test_obs_total', 10.0, now=now)
        seen.append(now)

    def bad_observer(h, now):
        raise RuntimeError('observer exploded')

    history.add_observer(ok_observer)
    history.add_observer(bad_observer)
    errors_before = _metrics.HISTORY_SAMPLE_ERRORS.value
    history.sample_once(now=1.0)
    history.sample_once(now=2.0)
    assert seen == [1.0, 2.0]
    assert _metrics.HISTORY_SAMPLE_ERRORS.value == errors_before + 2
    history.remove_observer(ok_observer)
    history.sample_once(now=3.0)
    assert seen == [1.0, 2.0]


def test_sample_overhead_bound():
    """The documented overhead bound: one full-catalog tick (the REAL
    process registry, every instrument the repo registers) stays under
    50 ms — at the default 1 s interval that is <5% of one core even
    with a 10x margin for loaded machines."""
    history = MetricsHistory()  # the full default registry
    history.sample_once()  # warm allocation paths
    start = time.perf_counter()
    ticks = 5
    for _ in range(ticks):
        history.sample_once()
    per_tick = (time.perf_counter() - start) / ticks
    assert per_tick < 0.05, f'sampler tick took {per_tick:.4f}s'


# ---------------------------------------------------------------- sampler
def test_sampler_thread_lifecycle_no_leak():
    registry, history = _fresh()
    registry.counter('test_sampled_total').inc()
    sampler = HistorySampler(history, interval_s=0.01)
    assert not sampler.running
    sampler.start()
    assert sampler.running
    assert any(
        t.name == SAMPLER_THREAD_NAME for t in threading.enumerate()
    )
    assert history.interval_hint_s == 0.01
    with pytest.raises(RuntimeError):
        sampler.start()  # double start is a bug, not a silent no-op
    deadline = time.time() + 5.0
    while history.samples < 3 and time.time() < deadline:
        time.sleep(0.01)
    assert history.samples >= 3
    sampler.stop()
    sampler.stop()  # idempotent
    assert not sampler.running
    assert not any(
        t.name == SAMPLER_THREAD_NAME for t in threading.enumerate()
    )
    # Restartable after a clean stop (the bench identity arm pattern).
    sampler.start()
    assert sampler.running
    sampler.stop()
    assert not sampler.running


def test_sampler_context_manager_and_validation():
    registry, history = _fresh()
    with HistorySampler(history, interval_s=0.01) as sampler:
        assert sampler.running
    assert not sampler.running
    with pytest.raises(ValueError):
        HistorySampler(history, interval_s=0.0)


def test_engine_owns_sampler_only_when_configured():
    """EngineConfig.history_interval_s > 0 starts a sampler in __init__
    and shutdown() joins it — no leaked thread after engine shutdown
    (the ISSUE 18 acceptance assert)."""
    jax = pytest.importorskip('jax')
    from distllm_tpu.generate.engine.engine import EngineConfig, LLMEngine
    from distllm_tpu.models import mistral

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
            block_size=4, num_blocks=16, max_num_seqs=2, max_model_len=32,
            prefer_native_allocator=False,
            history_interval_s=0.05,
        ),
    )
    try:
        assert engine._history_sampler is not None
        assert engine._history_sampler.running
        assert any(
            t.name == SAMPLER_THREAD_NAME for t in threading.enumerate()
        )
    finally:
        engine.shutdown()
    assert engine._history_sampler is None
    assert not any(
        t.name == SAMPLER_THREAD_NAME for t in threading.enumerate()
    )
    with pytest.raises(Exception):
        EngineConfig(history_interval_s=-1.0)


# -------------------------------------------------------------------- slo
def _slo_history(met: int, missed: int) -> MetricsHistory:
    registry = MetricsRegistry()
    slo = registry.counter(
        'distllm_request_slo_total', labelnames=('outcome',)
    )
    slo.labels(outcome='met')  # pre-register both children
    slo.labels(outcome='missed')
    history = MetricsHistory(registry)
    history.sample_once(now=1000.0)
    slo.labels(outcome='met').inc(met)
    slo.labels(outcome='missed').inc(missed)
    history.sample_once(now=1010.0)
    return history


def test_burn_rate_math():
    history = _slo_history(met=90, missed=10)
    burn = burn_rate(history, 60.0, objective=0.99, now=1010.0)
    assert burn['met'] == 90 and burn['missed'] == 10
    # 10% miss fraction against a 1% budget: burning 10x too fast.
    assert burn['burn_rate'] == pytest.approx(10.0)
    with pytest.raises(ValueError):
        burn_rate(history, 60.0, objective=1.5)


def test_burn_rate_zero_traffic_is_zero():
    history = _slo_history(met=0, missed=0)
    burn = burn_rate(history, 60.0, now=1010.0)
    assert burn['total'] == 0
    assert burn['burn_rate'] == 0.0  # an idle replica burns no budget


def test_slo_status_verdicts_and_gauges():
    history = _slo_history(met=50, missed=50)  # burn 50x: on fire
    burns = update_burn_gauges(history, now=1010.0)
    assert set(burns) == set(_metrics.SLO_BURN_WINDOW_LABELS)
    assert burns['60s'] == pytest.approx(50.0)
    assert _metrics.SLO_BURN_RATE.labels(window='60s').value == (
        pytest.approx(50.0)
    )
    doc = slo_status(history, now=1010.0)
    assert doc['schema'] == 'distllm-slo/v1'
    assert doc['verdict'] == 'page'
    firing = [p for p in doc['pairs'] if p['firing']]
    assert any(p['verdict'] == 'page' for p in firing)
    assert doc['goodput_fraction'] is None  # no token counters here
    json.dumps(doc)

    quiet = _slo_history(met=1000, missed=0)
    assert slo_status(quiet, now=1010.0)['verdict'] == 'ok'
    # Slow burn: past 1.0 (warn pair) but under 6.0 (page pair).
    warm = _slo_history(met=97, missed=3)
    assert slo_status(warm, now=1010.0)['verdict'] == 'warn'


# --------------------------------------------------------------- baseline
def test_gate_direction_latencies_lower_everything_else_higher():
    """Every metric the sentinel can read live has a direction."""
    assert {
        name: gate_direction(name) for name in _metrics.SENTINEL_METRIC_LABELS
    } == {
        'tok_s': 'higher', 'ttft_p95_s': 'lower', 'tpot_p95_s': 'lower',
        'mfu_measured': 'higher', 'bw_util_measured': 'higher',
    }


def test_build_envelope_prefers_best_source_key():
    envelope = build_envelope(
        {
            'gen_load_tok_s': 800.0,
            'gen_value': 180.0,  # the fallback must NOT win
            'gen_load_ttft_p95': 0.5,
            'unrelated': 3.0,
        },
        source='r09',
    )
    assert envelope['schema'] == ENVELOPE_SCHEMA
    assert envelope['source'] == 'r09'
    tok = envelope['metrics']['tok_s']
    assert tok == {
        'value': 800.0, 'direction': 'higher', 'from_key': 'gen_load_tok_s',
    }
    assert envelope['metrics']['ttft_p95_s']['direction'] == 'lower'
    assert 'mfu_measured' not in envelope['metrics']


def test_build_envelope_without_a_source_key_is_empty_and_disarms():
    """Numbers under no key the table knows make a valid, empty envelope:
    the sentinel counts a disarm and never raises."""
    envelope = build_envelope({'unrelated': 3.0, 'gen_tok': 1.0}, source='x')
    assert envelope == {
        'schema': ENVELOPE_SCHEMA, 'source': 'x', 'metrics': {},
    }
    _, history = _fresh()
    sentinel = RegressionSentinel(history, envelope=envelope)
    assert not sentinel.armed
    assert sentinel.evaluate(now=1000.0) == []


def test_load_envelope_roundtrip_and_degraded_modes(tmp_path):
    envelope = build_envelope({'gen_load_tok_s': 500.0}, source='r08')
    path = tmp_path / 'baseline.json'
    path.write_text(json.dumps(envelope))
    loaded = load_envelope(path)
    assert loaded['metrics']['tok_s']['value'] == 500.0
    assert load_envelope(tmp_path / 'missing.json') is None
    (tmp_path / 'junk.json').write_text('{not json')
    assert load_envelope(tmp_path / 'junk.json') is None
    (tmp_path / 'wrong.json').write_text(json.dumps({'schema': 'other/v1'}))
    assert load_envelope(tmp_path / 'wrong.json') is None
    # Non-numeric values are dropped, not served to the sentinel.
    (tmp_path / 'dirty.json').write_text(json.dumps({
        'schema': ENVELOPE_SCHEMA,
        'source': 'x',
        'metrics': {'tok_s': {'value': 'fast'}, 'ttft_p95_s': {'value': 1.0}},
    }))
    dirty = load_envelope(tmp_path / 'dirty.json')
    assert list(dirty['metrics']) == ['ttft_p95_s']


# --------------------------------------------------------------- sentinel
def _token_history(rate_tok_s: float) -> tuple[MetricsRegistry, MetricsHistory]:
    registry = MetricsRegistry()
    c = registry.counter('distllm_engine_generated_tokens_total')
    history = MetricsHistory(registry)
    history.sample_once(now=1000.0)
    c.inc(rate_tok_s * 10.0)
    history.sample_once(now=1010.0)
    return registry, history


def test_sentinel_fires_once_per_episode_and_unlatches():
    registry, history = _token_history(rate_tok_s=40.0)  # 60% below baseline
    recorder = FlightRecorder(capacity=16)
    fired_before = _metrics.SENTINEL_REGRESSIONS.labels(
        metric='tok_s'
    ).value
    sentinel = RegressionSentinel(
        history,
        envelope=build_envelope({'gen_load_tok_s': 100.0}, source='r'),
        threshold=0.2,
        # One tick interval wide, so each evaluate() judges exactly the
        # newest point — episodes flip cleanly between samples.
        window_s=9.0,
        recorder=recorder,
    )
    assert sentinel.armed
    assert _metrics.SENTINEL_ARMED.value == 1.0
    events = sentinel.evaluate(now=1010.0)
    assert [e['metric'] for e in events] == ['tok_s']
    assert events[0]['baseline'] == 100.0
    assert events[0]['live'] == pytest.approx(40.0)
    assert sentinel.evaluate(now=1010.0) == []  # latched: once per episode
    assert _metrics.SENTINEL_REGRESSIONS.labels(metric='tok_s').value == (
        fired_before + 1
    )
    # The counted flight record (the 'regression' kind).
    kinds = [r['kind'] for r in recorder.snapshot()]
    assert kinds == ['regression']
    # Recovery unlatches; the NEXT degradation fires a fresh episode.
    c = registry.get('distllm_engine_generated_tokens_total')
    c.inc(100.0 * 10.0)
    history.sample_once(now=1020.0)
    assert sentinel.evaluate(now=1020.0) == []  # recovered, silent
    c.inc(10.0)
    history.sample_once(now=1030.0)
    refired = sentinel.evaluate(now=1030.0)
    assert [e['metric'] for e in refired] == ['tok_s']
    status = sentinel.status(now=1030.0)
    assert status['armed'] and status['degraded'] == ['tok_s']
    assert status['fired_total'] == 2
    json.dumps(status)


def test_sentinel_never_fires_without_traffic():
    registry = MetricsRegistry()
    registry.counter('distllm_engine_generated_tokens_total')
    history = MetricsHistory(registry)
    history.sample_once(now=1000.0)
    history.sample_once(now=1010.0)  # idle ticks: delta 0
    sentinel = RegressionSentinel(
        history,
        envelope=build_envelope(
            {'gen_load_tok_s': 100.0, 'gen_load_ttft_p95': 0.2}, source='r'
        ),
        recorder=FlightRecorder(capacity=4),
    )
    assert sentinel.evaluate(now=1010.0) == []


def test_sentinel_lower_better_direction():
    registry = MetricsRegistry()
    h = registry.histogram(
        'distllm_request_ttft_seconds', buckets=(0.1, 1.0, 10.0)
    )
    history = MetricsHistory(registry)
    history.sample_once(now=1000.0)
    for _ in range(20):
        h.observe(5.0)  # way above the 0.2 s baseline
    history.sample_once(now=1010.0)
    sentinel = RegressionSentinel(
        history,
        envelope=build_envelope({'gen_load_ttft_p95': 0.2}, source='r'),
        window_s=60.0,
        recorder=FlightRecorder(capacity=4),
    )
    events = sentinel.evaluate(now=1010.0)
    assert [e['metric'] for e in events] == ['ttft_p95_s']
    assert events[0]['direction'] == 'lower'


def test_sentinel_disarmed_modes_are_counted_never_raised(tmp_path):
    _, history = _token_history(rate_tok_s=100.0)

    def disarms(reason: str) -> float:
        return _metrics.SENTINEL_DISARMED.labels(reason=reason).value

    before_nb = disarms('no_baseline')
    sentinel = RegressionSentinel(history, recorder=FlightRecorder(capacity=4))
    # Plain construction without an envelope is NOT a counted disarm.
    assert not sentinel.armed
    assert disarms('no_baseline') == before_nb
    # Missing baseline file: counted, evaluate stays a no-op.
    assert sentinel.arm_from_file(tmp_path / 'missing.json') is False
    assert disarms('no_baseline') == before_nb + 1
    assert _metrics.SENTINEL_ARMED.value == 0.0
    assert sentinel.evaluate(now=1010.0) == []
    # An envelope with no usable metrics: the 'empty' reason.
    before_empty = disarms('empty')
    assert sentinel.arm({'schema': ENVELOPE_SCHEMA, 'metrics': {}}) is False
    assert disarms('empty') == before_empty + 1
    # Arming with a real envelope recovers.
    assert sentinel.arm(
        build_envelope({'gen_load_tok_s': 100.0}, source='r')
    )
    assert sentinel.armed and _metrics.SENTINEL_ARMED.value == 1.0


def test_sentinel_driven_by_sampler_observer():
    registry, history = _token_history(rate_tok_s=10.0)
    recorder = FlightRecorder(capacity=4)
    sentinel = RegressionSentinel(
        history,
        envelope=build_envelope({'gen_load_tok_s': 100.0}, source='r'),
        window_s=60.0,
        recorder=recorder,
    ).install()
    history.sample_once(now=1011.0)  # the tick drives evaluate()
    assert [r['kind'] for r in recorder.snapshot()] == ['regression']
    sentinel.uninstall()
    registry.get('distllm_engine_generated_tokens_total').inc(1)
    history.sample_once(now=1012.0)
    assert len(recorder.snapshot()) == 1  # uninstalled: no more evals


# ------------------------------------------------------------- integration
def test_history_excerpt_shape():
    registry = MetricsRegistry()
    c = registry.counter('distllm_engine_generated_tokens_total')
    history = MetricsHistory(registry)
    history.sample_once(now=1000.0)
    c.inc(500)
    history.sample_once(now=1010.0)
    excerpt = history_excerpt(history, window_s=60.0, now=1010.0)
    assert excerpt['tok_s'] == pytest.approx(50.0)
    assert excerpt['samples'] == 2
    assert excerpt['tok_points']  # [t, rate] rows
    assert isinstance(excerpt['burn_rates'], dict)
    json.dumps(excerpt)


def test_debug_bundle_carries_history_and_slo(tmp_path):
    from distllm_tpu.observability import dump_debug_bundle

    get_metrics_history().sample_once()
    paths = dump_debug_bundle(str(tmp_path / 'bundle'), reason='test')
    assert {'history', 'slo'} <= set(paths)
    history_doc = json.loads(
        (tmp_path / 'bundle' / 'history.json').read_text()
    )
    assert history_doc['schema'] == 'distllm-history/v1'
    assert history_doc['samples'] >= 1
    slo_doc = json.loads((tmp_path / 'bundle' / 'slo.json').read_text())
    assert slo_doc['slo']['schema'] == 'distllm-slo/v1'
    assert slo_doc['slo']['verdict'] in ('ok', 'warn', 'page')
    assert 'sentinel' in slo_doc


def test_perfetto_history_counter_track():
    from distllm_tpu.observability import to_trace_events, validate_trace_events

    registry = MetricsRegistry()
    c = registry.counter('distllm_engine_generated_tokens_total')
    g = registry.gauge('distllm_scheduler_queue_depth')
    history = MetricsHistory(registry)
    history.sample_once(now=1000.0)
    c.inc(100)
    g.set(3.0)
    history.sample_once(now=1001.0)
    doc = to_trace_events([], history=history, time_origin_s=1000.0)
    counters = [e for e in doc['traceEvents'] if e.get('ph') == 'C']
    assert counters, 'history produced no counter events'
    assert {e['cat'] for e in counters} == {'history'}
    by_name = {e['name'] for e in counters}
    assert 'tok/s' in by_name and 'queue_depth' in by_name
    tok = [e for e in counters if e['name'] == 'tok/s']
    assert tok[0]['args']['value'] == pytest.approx(100.0)
    problems = validate_trace_events(doc)
    assert problems == [], problems
    # A pre-rendered snapshot dict works too (the bundle path).
    doc2 = to_trace_events(
        [], history=history.snapshot(), time_origin_s=1000.0
    )
    assert any(e.get('ph') == 'C' for e in doc2['traceEvents'])


def test_build_info_and_uptime_instruments():
    from distllm_tpu import __version__
    from distllm_tpu.observability.metrics import get_registry

    rendered = get_registry().render()
    assert 'distllm_build_info{version="%s"} 1' % __version__ in rendered
    assert 'distllm_server_uptime_seconds' in rendered


def test_gen_history_stage_cpu_smoke():
    """The telemetry scenario end to end: real traffic from the open-loop
    load generator over one warmed engine (``serving_smoke.build_engine``)
    with the process history ring and its sampler thread live.

    - the ring retains windows of the run (tokens, TTFT and TPOT quantiles);
    - a sentinel calibrated on those recorded windows, by value, stays quiet
      when it judges the same windows (the old stage timed a second run and
      compared two clocks; a CPU's two readings differ by more than any
      threshold worth having, PERF.md);
    - with the sampler stopped the same schedule emits the same tokens:
      history is observation only;
    - a ``slow_window`` sleep long enough that no machine can serve half the
      calibrated rate under it fires the sentinel on ``tok_s``, once: the
      second pass is latched;
    - with an SLO no request can meet every request misses, the 60 s burn
      gauge leaves zero and the verdict is ``page``; with admission control
      on, the same SLO sheds;
    - no sampler thread outlives ``stop()``."""
    from distllm_tpu.generate.loadgen import build_workload, run_loadgen
    from distllm_tpu.observability.sentinel import LIVE_EXTRACTORS
    from distllm_tpu.resilience import get_fault_injector
    from serving_smoke import build_engine, workload_config

    interval_s = 0.05
    engine = build_engine(ttft_slo_s=30.0)
    history = get_metrics_history()
    history.clear()  # this test's windows, not an earlier test's tail
    sampler = HistorySampler(history, interval_s=interval_s)
    injector = get_fault_injector()
    try:
        workload = build_workload(workload_config())
        sampler.start()
        clean = run_loadgen(engine, workload)
        sampler.stop()
        history.sample_once()  # fold the tail; the ring is now still
        assert history.samples >= 3

        # Calibrate on the recorded windows, then judge the same windows.
        now = time.time()
        window_s = clean.elapsed_s + 4 * interval_s
        recorded = {
            name: LIVE_EXTRACTORS[name](history, window_s, now)
            for name in ('tok_s', 'ttft_p95_s', 'tpot_p95_s')
        }
        assert all(v is not None and v > 0 for v in recorded.values()), recorded
        envelope = build_envelope(
            {
                'gen_history_tok_s': recorded['tok_s'],
                'gen_history_ttft_p95': recorded['ttft_p95_s'],
                'gen_history_tpot_p95': recorded['tpot_p95_s'],
            },
            source='the clean arm, as the ring recorded it',
        )
        assert len(envelope['metrics']) == 3
        quiet = RegressionSentinel(
            history, envelope=envelope, threshold=0.05, window_s=window_s
        )
        assert quiet.evaluate(now) == []

        identity = run_loadgen(engine, workload)  # no sampler thread runs
        assert identity.tokens_by_request == clean.tokens_by_request

        # Slow arm. A window emits at most rows x (steps + 1) tokens (a
        # prefill's first token beside each row's steps) and now lasts at
        # least delay_s, so the rate is at most a quarter of the calibrated
        # one whatever the machine does; the sentinel fires under a half.
        rows, steps = engine.config.max_num_seqs, engine.config.decode_steps
        delay_s = rows * (steps + 1) / (0.25 * recorded['tok_s'])
        history.clear()
        sampler.start()
        injector.arm('slow_window', times=10**6, delay_s=delay_s, after=0)
        slow = run_loadgen(
            engine, build_workload(workload_config(num_requests=6))
        )
        injector.disarm()
        sampler.stop()
        history.sample_once()
        alarm = RegressionSentinel(
            history, envelope=envelope, threshold=0.5,
            window_s=slow.elapsed_s + 4 * interval_s,
        )
        fired = alarm.evaluate()
        assert 'tok_s' in [event['metric'] for event in fired], fired
        assert alarm.evaluate() == []  # one alarm an episode

        # Overload: every request is served and misses its SLO.
        engine.config.ttft_slo_s = 1e-9
        sampler.start()
        overload = run_loadgen(engine, workload)
        sampler.stop()
        history.sample_once()
        assert overload.slo_missed == len(workload) and overload.slo_met == 0
        assert update_burn_gauges(history)['60s'] > 0
        assert slo_status(history)['verdict'] == 'page'
        engine.admission_control = True
        assert run_loadgen(engine, workload).shed_requests > 0
    finally:
        injector.disarm()
        sampler.stop()
        engine.shutdown()
    assert not any(
        t.name == SAMPLER_THREAD_NAME for t in threading.enumerate()
    )
