"""Metrics registry, tracing, Timer shim, aggregation, scheduler wiring
(ISSUE 1 tentpole + satellites) and the flight-recorder layer (ISSUE 3)."""

from __future__ import annotations

import json
import math
import re
import time

import pytest

from distllm_tpu.observability import (
    FlightRecorder,
    MetricsRegistry,
    StallWatchdog,
    TraceBuffer,
    dump_debug_bundle,
    get_registry,
    get_trace_buffer,
    log_buckets,
    log_event,
    span,
)
from distllm_tpu.observability.aggregate import (
    aggregate_lines,
    aggregate_logs,
    format_stats_table,
)
from distllm_tpu.timer import TimeLogger, TimeStats, Timer


# ------------------------------------------------------------------ metrics
def test_counter_semantics():
    registry = MetricsRegistry()
    c = registry.counter('test_events_total', 'events')
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_counter_labels_are_independent():
    registry = MetricsRegistry()
    c = registry.counter('test_by_kind_total', labelnames=('kind',))
    c.labels(kind='a').inc()
    c.labels(kind='a').inc()
    c.labels(kind='b').inc(5)
    assert c.labels(kind='a').value == 2
    assert c.labels(kind='b').value == 5
    with pytest.raises(ValueError):
        c.labels(wrong='x')
    with pytest.raises(ValueError):
        c.inc()  # labeled metric used without labels


def test_registry_get_or_create_and_conflicts():
    registry = MetricsRegistry()
    a = registry.counter('test_total', 'help')
    assert registry.counter('test_total') is a
    with pytest.raises(ValueError):
        registry.gauge('test_total')  # type conflict
    with pytest.raises(ValueError):
        registry.counter('test_total', labelnames=('x',))  # label conflict
    with pytest.raises(ValueError):
        registry.counter('bad name')


def test_gauge_semantics():
    registry = MetricsRegistry()
    g = registry.gauge('test_depth')
    g.set(10)
    g.inc(3)
    g.dec()
    assert g.value == 12


def test_histogram_semantics():
    registry = MetricsRegistry()
    h = registry.histogram('test_seconds', buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(56.05)
    text = registry.render()  # bucket counts are cumulative
    assert 'test_seconds_bucket{le="0.1"} 1' in text
    assert 'test_seconds_bucket{le="1"} 3' in text
    assert 'test_seconds_bucket{le="10"} 4' in text
    assert 'test_seconds_bucket{le="+Inf"} 5' in text
    with pytest.raises(ValueError):
        registry.histogram('test_bad', buckets=(1.0, 1.0))


def test_histogram_quantile_known_distribution():
    """Pin the linear-interpolation estimator on a known distribution:
    100 observations spread uniformly inside (0, 10] against buckets
    (1, 2, ..., 10) — every quantile is exact for uniform-in-bucket
    data, which is precisely the estimator's model."""
    registry = MetricsRegistry()
    h = registry.histogram(
        'test_quantile_seconds', buckets=tuple(float(b) for b in range(1, 11))
    )
    for i in range(100):
        h.observe((i + 0.5) / 10.0)  # 10 observations per bucket
    assert h.quantile(0.5) == pytest.approx(5.0)
    assert h.quantile(0.95) == pytest.approx(9.5)
    assert h.quantile(0.99) == pytest.approx(9.9)
    assert h.quantile(0.0) == pytest.approx(0.0)
    assert h.quantile(1.0) == pytest.approx(10.0)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_quantile_edge_cases():
    registry = MetricsRegistry()
    h = registry.histogram('test_q_edge_seconds', buckets=(1.0, 10.0))
    assert h.quantile(0.5) is None  # empty histogram has no quantiles
    h.observe(0.5)
    # Single observation in the first bucket interpolates from 0.
    assert 0 < h.quantile(0.5) <= 1.0
    h.observe(100.0)  # +Inf bucket
    # Ranks landing in +Inf clamp to the highest finite edge.
    assert h.quantile(0.99) == pytest.approx(10.0)
    # Labeled children expose the same estimator.
    labeled = registry.histogram(
        'test_q_labeled_seconds', labelnames=('kind',), buckets=(1.0, 2.0)
    )
    labeled.labels(kind='a').observe(1.5)
    assert 1.0 <= labeled.labels(kind='a').quantile(0.5) <= 2.0
    # ALL mass in the +Inf bucket: every quantile clamps to the highest
    # finite edge — the estimator cannot invent an upper bound the
    # ladder never recorded.
    inf_only = registry.histogram('test_q_inf_seconds', buckets=(1.0, 10.0))
    inf_only.observe(50.0)
    inf_only.observe(500.0)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert inf_only.quantile(q) == pytest.approx(10.0)
    # Zero-delta interval (two identical cumulative snapshots — the
    # history ring's idle tick): None, never a division.
    from distllm_tpu.observability import quantile_from_cumulative

    before = inf_only.cumulative_counts()
    delta = [a - b for a, b in zip(inf_only.cumulative_counts(), before)]
    assert quantile_from_cumulative(inf_only.buckets, delta, 0.5) is None


def test_quantile_from_cumulative_delta_isolates_window():
    """The loadgen pattern: difference two cumulative_counts() snapshots
    to get quantiles over only the observations in between."""
    from distllm_tpu.observability import quantile_from_cumulative

    registry = MetricsRegistry()
    h = registry.histogram('test_q_delta_seconds', buckets=(1.0, 2.0, 4.0))
    h.observe(0.5)  # pre-window noise (a warmup request)
    before = h.cumulative_counts()
    for _ in range(10):
        h.observe(3.0)  # the measured window: all in bucket (2, 4]
    delta = [a - b for a, b in zip(h.cumulative_counts(), before)]
    assert sum(
        n for n in delta
    ) == 10 or delta[-1] == 10  # cumulative: final entry counts all
    p50 = quantile_from_cumulative(h.buckets, delta, 0.5)
    assert 2.0 < p50 <= 4.0  # the warmup 0.5 s observation is excluded
    assert quantile_from_cumulative(h.buckets, [0, 0, 0, 0], 0.5) is None


def test_log_buckets_ladder():
    buckets = log_buckets(1e-3, 10.0, per_decade=1)
    assert buckets == (0.001, 0.01, 0.1, 1.0, 10.0)
    assert list(buckets) == sorted(buckets)
    with pytest.raises(ValueError):
        log_buckets(0, 1)


def test_prometheus_exposition_format():
    registry = MetricsRegistry()
    c = registry.counter('app_requests_total', 'requests', ('path',))
    c.labels(path='/x "quoted"\nline').inc()
    registry.gauge('app_depth', 'depth').set(4)
    h = registry.histogram('app_latency_seconds', 'latency', buckets=(1.0,))
    h.observe(0.5)
    text = registry.render()
    assert '# HELP app_requests_total requests' in text
    assert '# TYPE app_requests_total counter' in text
    # Label values escape backslash/quote/newline.
    assert 'app_requests_total{path="/x \\"quoted\\"\\nline"} 1' in text
    assert 'app_depth 4' in text
    assert 'app_latency_seconds_bucket{le="1"} 1' in text
    assert 'app_latency_seconds_bucket{le="+Inf"} 1' in text
    assert 'app_latency_seconds_sum 0.5' in text
    assert 'app_latency_seconds_count 1' in text
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? (\+Inf|-Inf|[0-9.eE+-]+)$'
    )
    for line in text.strip().splitlines():
        if not line.startswith('#'):
            assert sample_re.match(line), line


# ------------------------------------------------------------------ tracing
def test_span_nesting_and_status():
    buffer = TraceBuffer()
    with span('outer', buffer=buffer) as outer:
        with span('inner', 'tag-1', buffer=buffer) as inner:
            assert inner.parent_id == outer.span_id
    spans = buffer.snapshot()
    assert [s.name for s in spans] == ['inner', 'outer']  # close order
    assert all(s.status == 'ok' for s in spans)
    assert spans[0].duration_s >= 0

    with pytest.raises(RuntimeError, match='boom'):
        with span('failing', buffer=buffer):
            raise RuntimeError('boom')
    failed = buffer.snapshot()[-1]
    assert failed.status == 'error'
    assert 'boom' in failed.error


def test_trace_ring_eviction_and_dump(tmp_path):
    buffer = TraceBuffer(capacity=3)
    for i in range(5):
        with span(f's{i}', buffer=buffer):
            pass
    assert len(buffer) == 3
    assert buffer.total_recorded == 5
    assert [s.name for s in buffer.snapshot()] == ['s2', 's3', 's4']
    assert [s.name for s in buffer.snapshot(limit=2)] == ['s3', 's4']

    out = tmp_path / 'traces.jsonl'
    assert buffer.dump_jsonl(out) == 3
    records = [json.loads(line) for line in out.read_text().splitlines()]
    assert [r['name'] for r in records] == ['s2', 's3', 's4']
    assert all(r['status'] == 'ok' for r in records)
    assert all(r['duration_s'] is not None for r in records)


def test_request_scope_stamps_spans_and_nests():
    from distllm_tpu.observability import (
        current_request_id,
        request_scope,
    )

    buffer = TraceBuffer()
    assert current_request_id() is None
    with request_scope('req-42'):
        assert current_request_id() == 'req-42'
        with span('scoped-work', buffer=buffer) as s:
            assert s.attributes['request_id'] == 'req-42'
        with request_scope('req-inner'):
            assert current_request_id() == 'req-inner'
        assert current_request_id() == 'req-42'
    assert current_request_id() is None
    # None scope is a no-op (optional ids pass through unconditionally).
    with request_scope(None):
        assert current_request_id() is None
        with span('unscoped-work', buffer=buffer) as s:
            assert 'request_id' not in s.attributes
    # An explicit attribute wins over the scope.
    with request_scope('req-outer'):
        with span('explicit', buffer=buffer, request_id='req-pinned') as s:
            assert s.attributes['request_id'] == 'req-pinned'
    # Spans record their opening thread (the Perfetto track key).
    import threading

    recorded = buffer.snapshot()[-1]
    assert recorded.thread_id == threading.get_ident()
    assert recorded.to_dict()['thread_id'] == recorded.thread_id


# --------------------------------------------------------------- Timer shim
def test_timer_emits_legacy_line_and_span(capsys):
    buffer = get_trace_buffer()
    before = buffer.total_recorded
    with Timer('shim-stage', 'file-9'):
        pass
    out = capsys.readouterr().out
    stats = TimeLogger().parse_lines(out)  # legacy format still parses
    assert stats[('shim-stage', 'file-9')].count == 1
    assert buffer.total_recorded == before + 1
    recorded = buffer.snapshot()[-1]
    assert recorded.name == 'shim-stage'
    assert recorded.tags == ('shim-stage', 'file-9')
    assert recorded.status == 'ok'


def test_timer_tags_error_spans(capsys):
    buffer = get_trace_buffer()
    with pytest.raises(ValueError):
        with Timer('doomed-stage'):
            raise ValueError('nope')
    # Legacy line still emitted for failed work (scrapers expect it)...
    assert '[timer] tags=doomed-stage' in capsys.readouterr().out
    # ...but the span distinguishes the outcome.
    recorded = buffer.snapshot()[-1]
    assert recorded.status == 'error'
    assert 'nope' in recorded.error


def test_timer_observes_stage_histogram():
    h = get_registry().get('distllm_stage_duration_seconds')
    child = h.labels(stage='histo-stage', status='ok')
    before = child.count
    with Timer('histo-stage', echo=False):
        pass
    assert child.count == before + 1


def test_timer_restart_without_stop_does_not_leak_stack():
    from distllm_tpu.observability import tracing

    t = Timer('restarted', echo=False)
    t.start()
    t.start()  # restart with no stop(): the stale span must be abandoned
    t.stop()
    assert tracing._stack() == []
    with span('after-restart') as s:
        assert s.parent_id is None


def test_timer_never_started_raises():
    t = Timer('idle')
    with pytest.raises(RuntimeError):
        t.elapsed_s
    with pytest.raises(RuntimeError):
        t.stop()


def test_timestats_percentiles():
    stats = TimeStats(tags=('x',), elapsed_s=[4.0, 1.0, 3.0, 2.0])
    assert stats.p50_s == 2.0
    assert stats.p95_s == 4.0
    assert stats.max_s == 4.0
    empty = TimeStats(tags=('y',))
    assert empty.p50_s == 0.0 and empty.p95_s == 0.0 and empty.max_s == 0.0
    single = TimeStats(tags=('z',), elapsed_s=[7.0])
    assert single.p50_s == single.p95_s == single.max_s == 7.0


# -------------------------------------------------------------- aggregation
def _fake_log(tag: str, values: list[float]) -> str:
    return '\n'.join(
        f'[timer] tags={tag} elapsed_s={v:.9f} start_ns=0 end_ns=1'
        for v in values
    )


def test_aggregate_multi_host_logs(tmp_path):
    log_a = tmp_path / 'host-a.log'
    log_b = tmp_path / 'host-b.log'
    log_a.write_text(_fake_log('embed,f1', [1.0, 2.0]))
    log_b.write_text(_fake_log('embed,f1', [3.0]) + '\n' + _fake_log('write', [0.5]))
    merged = aggregate_logs([log_a, log_b])
    assert merged[('embed', 'f1')].count == 3
    assert merged[('embed', 'f1')].total_s == pytest.approx(6.0)
    assert merged[('write',)].count == 1

    table = format_stats_table(merged)
    lines = table.splitlines()
    assert lines[0].split()[:2] == ['tags', 'count']
    assert 'p50_s' in lines[0] and 'p95_s' in lines[0] and 'max_s' in lines[0]
    assert lines[2].startswith('embed,f1')  # sorted by total desc

    assert aggregate_lines([]) == {}


def test_aggregate_merges_span_jsonl_with_timer_lines(tmp_path):
    # A [timer] log from one host...
    timer_log = tmp_path / 'host-a.log'
    timer_log.write_text(_fake_log('embed,f1', [1.0]))
    # ...and a span-JSONL dump from another (Timer-shim spans carry the
    # same tags, so both formats merge into ONE stats row).
    buffer = TraceBuffer()
    with span('embed', 'embed', 'f1', buffer=buffer):
        pass
    with span('solo-span', buffer=buffer):
        pass
    span_dump = tmp_path / 'host-b-traces.jsonl'
    buffer.dump_jsonl(span_dump)
    # Flight-ring dumps merge too (keyed by record kind)...
    flight = FlightRecorder()
    flight.record('decode', duration_s=0.25)
    flight.record('decode', duration_s=0.35)
    flight.record('request', ttft_s=0.1)  # no duration_s -> skipped
    flight_dump = tmp_path / 'host-b-flight.jsonl'
    flight.dump_jsonl(flight_dump)
    # ...and torn lines (killed process mid-write) are skipped.
    with open(span_dump, 'a') as handle:
        handle.write('{"name": "torn", "duration_s"')

    merged = aggregate_logs([timer_log, span_dump, flight_dump])
    assert merged[('embed', 'f1')].count == 2  # timer line + span record
    assert merged[('solo-span',)].count == 1
    assert merged[('decode',)].count == 2
    assert merged[('decode',)].total_s == pytest.approx(0.6)
    assert ('torn',) not in merged


def test_aggregate_dedups_same_measurement_across_formats(tmp_path, capsys):
    """timer.Timer emits BOTH a [timer] line and a span for every timed
    region; passing a worker's stdout log AND its trace dump must not
    double count the measurement (same tags + same clock bounds)."""
    buffer = get_trace_buffer()
    with Timer('dedup-stage', 'f7'):
        pass
    timer_log = tmp_path / 'worker.log'
    timer_log.write_text(capsys.readouterr().out)
    span_dump = tmp_path / 'traces.jsonl'
    recorded = buffer.snapshot()[-1]
    span_dump.write_text(json.dumps(recorded.to_dict()) + '\n')

    merged = aggregate_logs([timer_log, span_dump])
    assert merged[('dedup-stage', 'f7')].count == 1


def test_aggregate_table_reports_cross_host_percentiles(tmp_path):
    """The table carries p50/p95/p99 computed over the MERGED multi-host
    distribution, not per-file."""
    log_a = tmp_path / 'a.log'
    log_b = tmp_path / 'b.log'
    log_a.write_text(_fake_log('embed', [1.0] * 50))
    log_b.write_text(_fake_log('embed', [2.0] * 49 + [10.0]))
    merged = aggregate_logs([log_a, log_b])
    stats = merged[('embed',)]
    assert stats.count == 100
    assert stats.p50_s == pytest.approx(1.0)
    assert stats.p99_s == pytest.approx(2.0)
    assert stats.max_s == pytest.approx(10.0)
    table = format_stats_table(merged)
    header = table.splitlines()[0]
    assert 'p50_s' in header and 'p95_s' in header and 'p99_s' in header


def test_aggregate_cli_writes_combined_perfetto(tmp_path, capsys):
    """--perfetto merges flight/span JSONL dumps from multiple hosts into
    one valid trace with a process group per input file."""
    import json as _json

    from distllm_tpu.observability import validate_trace_events
    from distllm_tpu.observability.aggregate import main

    flight = FlightRecorder()
    flight.record('decode', duration_s=0.25, batch=2, tokens=32)
    flight.record(
        'request', e2e_s=0.5, ttft_s=0.1, request_id=0, output_tokens=8
    )
    flight_dump = tmp_path / 'host-a-flight.jsonl'
    flight.dump_jsonl(flight_dump)
    buffer = TraceBuffer()
    with span('host-b-work', buffer=buffer):
        pass
    span_dump = tmp_path / 'host-b-traces.jsonl'
    buffer.dump_jsonl(span_dump)
    out = tmp_path / 'combined.json'
    assert main(
        [str(flight_dump), str(span_dump), '--perfetto', str(out)]
    ) == 0
    captured = capsys.readouterr().out
    assert 'combined.json' in captured
    doc = _json.loads(out.read_text())
    assert validate_trace_events(doc) == []
    pids = {e['pid'] for e in doc['traceEvents']}
    assert pids == {1, 2}
    names = {e['name'] for e in doc['traceEvents'] if e.get('ph') != 'M'}
    assert 'decode' in names and 'host-b-work' in names


def test_aggregate_cli_entry_point(tmp_path, capsys):
    from distllm_tpu.observability.aggregate import main

    log = tmp_path / 'worker.log'
    log.write_text(_fake_log('cli-stage', [1.0, 3.0]))
    assert main([str(log)]) == 0
    out = capsys.readouterr().out
    assert 'cli-stage' in out and 'p95_s' in out
    # No parseable telemetry in the inputs -> nonzero exit.
    empty = tmp_path / 'empty.log'
    empty.write_text('nothing here\n')
    assert main([str(empty)]) == 1


def test_aggregate_runs_as_module(tmp_path):
    """``python -m distllm_tpu.observability.aggregate`` is the operator
    CLI — keep the module executable."""
    import subprocess
    import sys

    log = tmp_path / 'worker.log'
    log.write_text(_fake_log('mod-stage', [2.0]))
    proc = subprocess.run(
        [
            sys.executable, '-m', 'distllm_tpu.observability.aggregate',
            str(log),
        ],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert 'mod-stage' in proc.stdout


# ------------------------------------------------------- flight recorder
def test_flight_recorder_ring_and_dump(tmp_path):
    recorder = FlightRecorder(capacity=3)
    for i in range(5):
        recorder.record('decode', step=i, duration_s=0.01)
    assert len(recorder) == 3
    assert recorder.total_recorded == 5
    steps = [r['step'] for r in recorder.snapshot()]
    assert steps == [2, 3, 4]
    assert [r['step'] for r in recorder.snapshot(limit=2)] == [3, 4]
    assert all(r['kind'] == 'decode' for r in recorder.snapshot())
    assert all('t_wall' in r for r in recorder.snapshot())

    out = tmp_path / 'flight.jsonl'
    assert recorder.dump_jsonl(out) == 3
    records = [json.loads(line) for line in out.read_text().splitlines()]
    assert [r['step'] for r in records] == [2, 3, 4]


def test_debug_bundle_contents(tmp_path):
    recorder = FlightRecorder()
    recorder.record('prefill', duration_s=0.5, batch=4)
    with span('bundle-span'):
        pass
    paths = dump_debug_bundle(
        tmp_path / 'bundle', reason='unit test', recorder=recorder,
        extra={'stage': 'gen'},
    )
    assert set(paths) >= {'flight', 'metrics', 'traces', 'meta'}
    flight = [
        json.loads(line)
        for line in (tmp_path / 'bundle' / 'flight.jsonl').read_text().splitlines()
    ]
    assert flight[0]['kind'] == 'prefill'
    assert 'distllm_engine_steps_total' in (
        tmp_path / 'bundle' / 'metrics.prom'
    ).read_text()
    meta = json.loads((tmp_path / 'bundle' / 'meta.json').read_text())
    assert meta['reason'] == 'unit test'
    assert meta['stage'] == 'gen'


def test_stall_watchdog_fires_on_stall_and_respects_progress():
    recorder = FlightRecorder()
    fired = []
    dog = StallWatchdog(
        0.2,
        progress_fn=lambda: recorder.total_recorded,
        on_stall=fired.append,
        poll_s=0.05,
    )
    with dog:
        # Keep making progress: the dog must stay quiet.
        for _ in range(4):
            recorder.record('decode')
            time.sleep(0.08)
        assert fired == []
        # Stop progressing: the dog fires exactly once (max_fires=1).
        time.sleep(0.6)
    assert len(fired) == 1
    assert dog.fired == 1


def test_stall_watchdog_beat_counts_as_progress():
    fired = []
    dog = StallWatchdog(
        0.2, progress_fn=lambda: 0, on_stall=fired.append, poll_s=0.05
    )
    with dog:
        for _ in range(4):
            dog.beat()
            time.sleep(0.08)
        assert fired == []


def test_stall_watchdog_default_dumps_bundle(tmp_path):
    recorder_value = [0]
    dog = StallWatchdog(
        0.15,
        progress_fn=lambda: recorder_value[0],
        bundle_dir=tmp_path / 'stall',
        poll_s=0.05,
        name='unit-dog',
    )
    from distllm_tpu.observability import instruments

    stalls_before = instruments.WATCHDOG_STALLS.value
    with dog:
        time.sleep(0.5)
    assert (tmp_path / 'stall' / 'meta.json').exists()
    assert instruments.WATCHDOG_STALLS.value == stalls_before + 1


# ------------------------------------------------- stalled stretches of spans
class _Watched:
    """What the span watcher asks of an engine, with no engine behind it."""

    def __init__(self, unfinished=1, compiling=False):
        import threading

        self.context = {
            'thread': threading.get_ident(), 'in_flight': 1, 'ready': [True],
            'unfinished': unfinished, 'compiling': compiling,
        }

    def stall_context(self):
        return dict(self.context)


@pytest.fixture
def span_dog(monkeypatch):
    """A span watcher of the test's own, quick to poll and to call a
    stretch long, over a clean slate of typical seconds."""
    from distllm_tpu.observability import flight, steps

    monkeypatch.setattr(steps, 'STALL_FLOOR_S', 0.2)
    monkeypatch.setattr(steps, '_typical', {})
    monkeypatch.setattr(steps, '_longest', {})
    dog = StallWatchdog(float('inf'), poll_s=0.05, name='test-span-dog')
    monkeypatch.setattr(flight, '_span_watchdog', dog)
    yield dog
    dog.stop()
    steps.abandon()


def _stalls(before):
    from distllm_tpu.observability import get_flight_recorder

    ring = get_flight_recorder()
    grew = ring.total_recorded - before
    return [r for r in ring.snapshot()[-grew:] if r['kind'] == 'stall'] if grew else []


def _nap_in_a_span(seconds):
    time.sleep(seconds)


def test_a_span_held_open_is_one_stall_record_with_its_evidence(span_dog, capsys):
    from distllm_tpu.observability import get_flight_recorder, instruments, steps

    engine = _Watched()
    span_dog.watch(engine)
    before = get_flight_recorder().total_recorded
    counted = instruments.WATCHDOG_STALLS.value
    step = steps.StepSpan(seq=990001)
    step.mark('plan')
    t_fetch = step.mark('fetch')
    _nap_in_a_span(0.7)
    step.close()
    records = _stalls(before)
    # detection near 0.2-0.25 s, again when that age has doubled; the
    # third reading would need 0.8 s or more
    assert [r['sample'] for r in records] == [0, 1]
    first, second = records
    assert first['span'] == 'fetch' and first['seq'] == 990001
    assert first['thread'] == 'MainThread'
    assert first['t_edge_s'] == round(t_fetch, 6)
    assert first['t_s'] - first['t_edge_s'] == pytest.approx(first['age_s'], abs=1e-5)
    assert 0.2 < first['age_s'] < 0.45
    assert second['age_s'] >= 2 * first['age_s']
    assert second['t_edge_s'] == first['t_edge_s']
    # the stalled thread's stack first, innermost frame first
    stack = first['stacks'][0]
    assert stack['thread'] == 'MainThread'
    assert stack['frames'][0].endswith(' _nap_in_a_span')
    assert 'test_observability.py:' in stack['frames'][0]
    assert len(stack['frames']) <= 12 and len(first['stacks']) <= 8
    assert 0.0 <= first['tick_late_s'] < 0.5
    # the kernel's counters over the stretch, where the platform has them:
    # a sleeping thread burns next to nothing
    assert 0.0 <= first['cpu_process_s'] < 0.5
    if 'cpu_thread_s' in first:
        assert 0.0 <= first['cpu_thread_s'] < 0.2
    if 'sched_delay_s' in first:
        assert first['sched_delay_s'] >= 0.0 and first['timeslices'] >= 0
    # the engine's say
    assert first['in_flight'] == 1 and first['ready'] == [True]
    assert first['unfinished'] == 1 and first['compiling'] is False
    # what it cost is on the step's record, the whole stretch
    assert step.fields()['stalled_s'] == pytest.approx(0.7, abs=0.1)
    assert step.seconds['fetch_s'] >= step.seconds['stalled_s']
    # counted and logged once
    assert instruments.WATCHDOG_STALLS.value == counted + 1
    out = capsys.readouterr().out
    assert out.count('[test-span-dog]') == 1 and 'distllm:fetch' in out
    # the flagged stretch did not feed what is typical of a fetch
    assert steps._typical.get('fetch', 0.0) < 0.2


def test_a_compile_is_recorded_and_neither_counted_nor_logged(span_dog, capsys):
    from distllm_tpu.observability import get_flight_recorder, instruments, steps

    engine = _Watched(compiling=True)
    span_dog.watch(engine)
    before = get_flight_recorder().total_recorded
    counted = instruments.WATCHDOG_STALLS.value
    step = steps.StepSpan(seq=990002)
    step.mark('decode')
    _nap_in_a_span(0.35)
    step.close()
    (record,) = _stalls(before)
    assert record['compiling'] is True and record['span'] == 'decode'
    assert step.fields()['stalled_s'] == pytest.approx(0.35, abs=0.1)
    assert instruments.WATCHDOG_STALLS.value == counted
    assert '[test-span-dog]' not in capsys.readouterr().out


def test_a_long_stretch_is_long_for_its_kind(span_dog):
    """Eight times what stretches in the span typically take, where that
    is over the floor: a span whose stretches run 0.05 s is not stalled at
    0.3 s, one whose stretches run microseconds is."""
    from distllm_tpu.observability import get_flight_recorder, steps

    engine = _Watched()  # held: the watcher keeps engines weakly
    span_dog.watch(engine)
    before = get_flight_recorder().total_recorded
    for seq in range(990010, 990016):
        step = steps.StepSpan(seq=seq)
        step.mark('fetch')
        time.sleep(0.05)
        step.close()
    assert steps.stall_threshold_s('fetch') >= 0.39
    assert steps.stall_threshold_s('emit') == 0.2
    slow = steps.StepSpan(seq=990016)
    slow.mark('fetch')
    time.sleep(0.3)
    slow.mark('emit')
    time.sleep(0.3)
    slow.close()
    (record,) = _stalls(before)
    assert record['span'] == 'emit'
    assert slow.fields()['stalled_s'] == pytest.approx(0.3, abs=0.1)


def test_a_wait_that_recurs_is_the_programs_not_a_stall(span_dog):
    """A span's longest stretch is remembered for a while (compiles left
    out): the second round of the same wait is under twice the first and
    no stall; one well over it is."""
    from distllm_tpu.observability import get_flight_recorder, steps

    engine = _Watched()
    span_dog.watch(engine)
    before = get_flight_recorder().total_recorded

    def fetch(seq, seconds):
        step = steps.StepSpan(seq=seq)
        step.mark('fetch')
        time.sleep(seconds)
        step.close()
        return step.fields()

    for seq in range(990036, 990040):  # what a fetch typically takes
        fetch(seq, 0.01)
    assert fetch(990040, 0.4)['stalled_s'] == pytest.approx(0.4, abs=0.1)
    assert 0.7 < steps.stall_threshold_s('fetch') <= 0.9  # twice, fading
    assert 'stalled_s' not in fetch(990041, 0.4)
    assert fetch(990042, 1.1)['stalled_s'] == pytest.approx(1.1, abs=0.1)
    assert [r['seq'] for r in _stalls(before) if r['sample'] == 0] == [
        990040, 990042,
    ]
    # a compile's stretch teaches nothing
    engine.context['compiling'] = True
    step = steps.StepSpan(seq=990043)
    step.mark('decode')
    time.sleep(0.4)
    step.close()
    assert steps.stall_threshold_s('decode') == 0.2


def test_a_hole_is_a_stall_only_while_somebody_waits(span_dog):
    """A thread between two spans with nothing unfinished is an idle
    server; with a request unfinished the hole is a stall, and its seconds
    go to the next step that opens on the thread."""
    from distllm_tpu.observability import get_flight_recorder, steps

    engine = _Watched(unfinished=0)
    span_dog.watch(engine)
    before = get_flight_recorder().total_recorded
    step = steps.StepSpan(seq=990019)
    step.mark('plan')
    step.close()
    time.sleep(0.4)  # between two calls, nothing unfinished
    assert _stalls(before) == []
    assert None not in steps._typical  # nor does idling teach what is typical
    root = steps.StepSpan(seq=990020)
    root.mark('serve')
    step = steps.StepSpan(seq=990021)
    step.mark('plan')
    step.close()
    assert 'stalled_s' not in step.fields()
    engine.context['unfinished'] = 2
    time.sleep(0.4)
    step = steps.StepSpan(seq=990022)
    step.mark('plan')
    step.close()
    root.close()
    (record,) = _stalls(before)
    assert record['span'] is None and record['sample'] == 0
    assert record['seq'] == 990021  # the step whose span closed last
    assert record['unfinished'] == 2
    fields = step.fields()
    assert fields['stalled_s'] == pytest.approx(0.4, abs=0.1)
    assert fields['serve_self_s'] >= fields['stalled_s']
    # another thread's engine is not this thread's waiting
    engine.context['thread'] = -1
    before = get_flight_recorder().total_recorded
    time.sleep(0.4)
    assert _stalls(before) == []


def test_the_span_watcher_lives_from_the_first_engine_to_the_last(span_dog):
    import gc
    import threading

    def alive():
        return [t for t in threading.enumerate() if t.name == 'test-span-dog']

    first, second = _Watched(), _Watched()
    assert not alive()
    span_dog.watch(first)
    span_dog.watch(second)
    assert len(alive()) == 1
    span_dog.unwatch(first)
    assert len(alive()) == 1
    span_dog.unwatch(second)  # the last shutdown() stops it
    assert not alive()
    span_dog.watch(first)  # and a later engine starts it again
    assert len(alive()) == 1
    del first  # dropped without a shutdown(): held weakly
    gc.collect()
    deadline = time.monotonic() + 2.0
    while alive() and time.monotonic() < deadline:
        time.sleep(0.02)
    assert not alive()
    assert span_dog.engine_contexts() == []


def test_an_engine_slowed_by_the_fault_writes_a_stall_record(span_dog):
    """The injected stall ``slow_window`` sleeps where a wedged fetch
    would: the record's first stack names it, and the engine says what it
    had in flight."""
    from serving_smoke import build_engine

    from distllm_tpu.generate.engine import SamplingParams
    from distllm_tpu.observability import get_stall_watchdog
    from distllm_tpu.resilience.faults import get_fault_injector

    engine = build_engine(warm=False)
    assert get_stall_watchdog() is span_dog
    assert engine in span_dog._engines
    params = SamplingParams(temperature=0.0, max_tokens=6)
    engine.generate_ids([[1, 2, 3, 4]], params)  # compiles the window
    before = engine.flight.total_recorded
    injector = get_fault_injector()
    injector.arm('slow_window', times=1, delay_s=0.5)
    try:
        engine.generate_ids([[5, 6, 7, 8]], params)
    finally:
        injector.disarm()
    stalls = [r for r in _stalls(before) if not r['compiling']]
    assert [r['sample'] for r in stalls][:1] == [0]
    record = stalls[0]
    assert any(' maybe_sleep' in f for f in record['stacks'][0]['frames'][:2])
    assert any(' _process_window' in f for f in record['stacks'][0]['frames'])
    assert record['in_flight'] >= 1 and len(record['ready']) == record['in_flight']
    assert record['unfinished'] == 1
    grew = engine.flight.total_recorded - before
    steps_with = [
        r for r in engine.flight.snapshot()[-grew:] if r.get('stalled_s')
    ]
    assert sum(r['stalled_s'] for r in steps_with) == pytest.approx(0.5, abs=0.1)
    engine.shutdown()
    assert engine not in span_dog._engines
    assert not [
        t for t in __import__('threading').enumerate()
        if t.name == 'test-span-dog'
    ]


def test_debug_bundle_says_where_the_process_is(tmp_path):
    from distllm_tpu.observability import steps

    step = steps.StepSpan(seq=990030)
    step.mark('fetch')
    try:
        paths = dump_debug_bundle(tmp_path / 'bundle', reason='stacks')
    finally:
        step.close()
    stacks = json.loads((tmp_path / 'bundle' / 'stacks.json').read_text())
    assert paths['stacks'].endswith('stacks.json')
    mine = [s for s in stacks['stacks'] if s['thread'] == 'MainThread']
    # the caller's own thread is whoever dumps the bundle: left out, as the
    # watcher's is from its record
    assert mine == []
    spans = [s for s in stacks['spans'] if s['thread'] == 'MainThread']
    assert spans and spans[0]['span'] == 'fetch' and spans[0]['seq'] == 990030
    assert spans[0]['age_s'] >= 0.0


# ---------------------------------------------------------------- log_event
def test_log_event_prints_and_counts(capsys):
    counter = get_registry().get('distllm_log_messages_total')
    child = counter.labels(component='test-comp')
    before = child.value
    log_event('[test] hello', component='test-comp')
    assert capsys.readouterr().out == '[test] hello\n'
    assert child.value == before + 1


# --------------------------------------------------- scheduler instrumentation
def test_instrumented_scheduler_publishes_metrics():
    from distllm_tpu.generate.engine.scheduler import (
        InstrumentedScheduler,
        PyScheduler,
    )
    from distllm_tpu.observability import instruments

    sched = InstrumentedScheduler(
        PyScheduler(num_blocks=9, block_size=4, max_num_seqs=2),
        num_blocks=9,
    )
    assert instruments.KV_BLOCKS_TOTAL.value == 8
    admitted_before = instruments.SCHED_ADMITTED.value
    deferred = instruments.SCHED_DEFERRED.labels(reason='capacity')
    deferred_before = deferred.value

    sched.add(0, 4)
    sched.add(1, 4)
    sched.add(2, 4)
    assert instruments.SCHED_QUEUE_DEPTH.value == 3
    assert sched.admit_next() == 0
    assert sched.admit_next() == 1
    assert sched.admit_next() is None  # no free slot -> deferred
    assert instruments.SCHED_ADMITTED.value == admitted_before + 2
    assert deferred.value == deferred_before + 1
    assert instruments.SCHED_RUNNING.value == 2
    assert instruments.SCHED_QUEUE_DEPTH.value == 1
    assert instruments.KV_BLOCKS_IN_USE.value == 4  # 2 blocks per request
    assert instruments.KV_OCCUPANCY.value == pytest.approx(0.5)

    sched.finish(0)
    sched.finish(1)
    sched.finish(2)
    assert instruments.SCHED_RUNNING.value == 0
    assert instruments.KV_BLOCKS_IN_USE.value == 0


def test_instrumented_scheduler_counts_preemptions():
    from distllm_tpu.generate.engine.scheduler import (
        InstrumentedScheduler,
        PyScheduler,
    )
    from distllm_tpu.observability import instruments

    sched = InstrumentedScheduler(
        PyScheduler(num_blocks=5, block_size=2, max_num_seqs=2),
        num_blocks=5,
    )
    preempt_before = instruments.SCHED_PREEMPTIONS.value
    sched.add(0, 2)
    sched.add(1, 2)
    assert sched.admit_next() == 0
    assert sched.admit_next() == 1
    # Grow both sequences until the pool runs dry -> youngest preempted.
    for _ in range(4):
        sched.append_token(0)
        sched.append_token(1)
    preempted = sched.prepare_decode(2)
    assert preempted == [1]
    assert instruments.SCHED_PREEMPTIONS.value == preempt_before + 1


# ------------------------------------------------------- known-series catalog
def test_instruments_catalog_renders_engine_series():
    """The full serving schema is present in a scrape before any traffic."""
    from distllm_tpu.observability import render_prometheus

    text = render_prometheus()
    for name in (
        'distllm_engine_generated_tokens_total',
        'distllm_engine_prefill_dispatches_total',
        'distllm_engine_decode_windows_total',
        'distllm_kv_cache_blocks_total',
        'distllm_kv_cache_occupancy_ratio',
        'distllm_scheduler_queue_depth',
        'distllm_scheduler_preemptions_total',
        'distllm_http_requests_in_flight',
    ):
        assert f'# TYPE {name} ' in text, name


def test_histogram_inf_bucket_formatting():
    registry = MetricsRegistry()
    h = registry.histogram('edge_seconds', buckets=(1.0,))
    h.observe(math.inf)  # lands in +Inf bucket without error
    assert h.count == 1
    assert 'edge_seconds_bucket{le="+Inf"} 1' in registry.render()
