"""Engine-to-endpoint metrics & tracing (ISSUE 1 tentpole).

Three layers, all dependency-free:

- :mod:`~distllm_tpu.observability.metrics` — ``Counter`` / ``Gauge`` /
  ``Histogram`` registry with Prometheus text exposition;
- :mod:`~distllm_tpu.observability.tracing` — ``Span`` records + a bounded
  in-memory trace ring dumpable to JSONL (``timer.Timer`` is a shim over
  this: every timer emits both the legacy ``[timer]`` line and a span);
- :mod:`~distllm_tpu.observability.instruments` — the catalog of well-known
  series (engine, KV cache, scheduler, HTTP, fabric workers) plus the
  ``log_event`` stdout funnel;
- :mod:`~distllm_tpu.observability.flight` — the flight-recorder layer
  (ISSUE 3 tentpole): bounded per-engine-step ring, stall watchdog, debug
  bundles;
- :mod:`~distllm_tpu.observability.perfetto` — Perfetto/Chrome trace-event
  export of the flight + span rings and per-request lifecycles (ISSUE 10
  tentpole; ``GET /debug/perfetto``, ``perfetto.json`` in bundles);
- :mod:`~distllm_tpu.observability.roofline` — the analytic FLOPs/bytes
  cost model behind ``distllm_engine_mfu`` and the weight-stream
  bandwidth-utilization gauges;
- :mod:`~distllm_tpu.observability.startup` — startup & compile-phase
  attribution (ISSUE 11 tentpole): the ``compile`` flight kind,
  ``distllm_compile_seconds`` series, and dead-phase state for bundles;
- :mod:`~distllm_tpu.observability.xla_cost` — measured executable cost
  from ``compiled.cost_analysis()`` behind the
  ``distllm_engine_mfu_measured`` gauges and the analytic-vs-measured
  calibration ratios;
- :mod:`~distllm_tpu.observability.profiling` — the bounded
  ``jax.profiler`` capture helper (``GET /debug/xprof``);
- :mod:`~distllm_tpu.observability.history` — the bounded metric-history
  ring + background sampler (ISSUE 18 tentpole): retained time series
  over the live registry (``GET /debug/history``, ``history.json`` in
  bundles, the Perfetto ``history`` counter track);
- :mod:`~distllm_tpu.observability.slo` — multi-window multi-burn-rate
  SLO engine over the history (``distllm_slo_burn_rate{window}``,
  ``slo_status()`` ok/warn/page, ``GET /debug/slo``);
- :mod:`~distllm_tpu.observability.baseline` — the baseline envelope
  the sentinel compares against: its schema, maker and reader;
- :mod:`~distllm_tpu.observability.sentinel` — the runtime regression
  sentinel: live history windows vs the baseline envelope, firing the
  ``regression`` flight kind + ``distllm_sentinel_regressions_total``.

``aggregate`` (imported lazily to avoid a cycle with ``timer``) rolls
multi-host ``[timer]`` logs into one stats table. Metric names and
conventions are documented in ``docs/observability.md``.
"""

from __future__ import annotations

from distllm_tpu.observability.baseline import (
    build_envelope,
    load_envelope,
)
from distllm_tpu.observability.flight import (
    FlightRecorder,
    StallWatchdog,
    dump_debug_bundle,
    get_flight_recorder,
    get_stall_watchdog,
    stall_evidence,
)
from distllm_tpu.observability.history import (
    HistorySampler,
    MetricsHistory,
    get_metrics_history,
    history_excerpt,
)
from distllm_tpu.observability.instruments import log_event
from distllm_tpu.observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    log_buckets,
    quantile_from_cumulative,
    render_prometheus,
)
from distllm_tpu.observability.perfetto import (
    merge_host_traces,
    to_trace_events,
    validate_trace_events,
)
from distllm_tpu.observability.profiling import (
    ProfilerCapture,
    get_profiler_capture,
)
from distllm_tpu.observability.roofline import CostModel, device_peaks
from distllm_tpu.observability.sentinel import (
    RegressionSentinel,
    get_regression_sentinel,
    install_regression_sentinel,
)
from distllm_tpu.observability.slo import (
    install_slo_observer,
    slo_status,
    update_burn_gauges,
)
from distllm_tpu.observability.startup import (
    CompileWatcher,
    get_compile_watcher,
    record_backend_init,
)
from distllm_tpu.observability.xla_cost import XlaCost, price_callable
from distllm_tpu.observability.tracing import (
    Span,
    TraceBuffer,
    begin_span,
    current_request_id,
    dump_traces,
    end_span,
    get_trace_buffer,
    request_scope,
    span,
)

__all__ = [
    'CompileWatcher',
    'CostModel',
    'Counter',
    'FlightRecorder',
    'Gauge',
    'Histogram',
    'HistorySampler',
    'MetricsHistory',
    'MetricsRegistry',
    'ProfilerCapture',
    'RegressionSentinel',
    'Span',
    'StallWatchdog',
    'TraceBuffer',
    'XlaCost',
    'begin_span',
    'build_envelope',
    'current_request_id',
    'device_peaks',
    'dump_debug_bundle',
    'dump_traces',
    'end_span',
    'get_compile_watcher',
    'get_flight_recorder',
    'get_metrics_history',
    'get_profiler_capture',
    'get_stall_watchdog',
    'get_registry',
    'get_regression_sentinel',
    'get_trace_buffer',
    'history_excerpt',
    'install_regression_sentinel',
    'install_slo_observer',
    'load_envelope',
    'log_buckets',
    'log_event',
    'merge_host_traces',
    'price_callable',
    'quantile_from_cumulative',
    'record_backend_init',
    'render_prometheus',
    'request_scope',
    'slo_status',
    'span',
    'stall_evidence',
    'to_trace_events',
    'update_burn_gauges',
    'validate_trace_events',
]
