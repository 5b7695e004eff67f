"""Flight recorder, stall watchdog and debug bundles.

- :class:`FlightRecorder` — a bounded, thread-safe ring of per-engine-step
  records (step kind, batch occupancy, token counts, duration, queue depth,
  KV occupancy). The serving engine appends one record per prefill dispatch
  / decode window / finished request; the ring is cheap enough to stay on
  in production and is what a debug bundle or ``/debug/flight`` replays
  after a crash — the black-box flight recorder of the title.
- :class:`StallWatchdog` — a daemon thread that watches any monotonic
  progress function (by default the process flight ring's record count) and
  fires a callback when progress stops for ``stall_s`` seconds. The default
  callback dumps a debug bundle; it never kills the watched work.
- :func:`dump_debug_bundle` — flight ring + metrics exposition + trace ring
  (+ best-effort ``jax.profiler`` device-memory capture) written to one
  directory, so a dead process still explains itself.

Everything here is dependency-free and safe to import on any backend.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from pathlib import Path

from distllm_tpu.observability import instruments as _metrics
from distllm_tpu.observability.metrics import render_prometheus
from distllm_tpu.observability.tracing import get_trace_buffer


class FlightRecorder:
    """Bounded ring of per-step flight records (oldest evicted first).

    A record is one dict: ``{'kind': ..., 't_wall': ..., **fields}``.
    Every ``kind`` the package emits is registered in
    ``instruments.FLIGHT_KINDS`` (``'prefill'``, ``'decode'``, ``'mixed'``
    — a decode window carrying prefill-chunk rows — ``'request'``,
    ``'preempt'``, ``'event'``; enforced by ``tests/test_lint.py`` so the
    flight schema cannot fragment). Appends are O(1) under a lock — safe
    from the engine thread, the aiohttp event loop, and watchdog threads
    at once.
    """

    def __init__(self, capacity: int = 4096) -> None:
        if capacity < 1:
            raise ValueError('capacity must be >= 1')
        self.capacity = capacity
        self._records: deque[dict] = deque(maxlen=capacity)  # guarded by self._lock
        self._lock = threading.Lock()
        self._recorded = 0  # guarded by self._lock
        self._last_record_monotonic = time.monotonic()  # guarded by self._lock

    def record(self, kind: str, **fields) -> dict:
        entry = {'kind': kind, 't_wall': time.time(), **fields}
        with self._lock:
            self._records.append(entry)
            self._recorded += 1
            self._last_record_monotonic = time.monotonic()
        return entry

    def snapshot(self, limit: int | None = None) -> list[dict]:
        """Most recent records, oldest first (``limit`` trims old ones)."""
        with self._lock:
            records = list(self._records)
        if limit is not None:
            records = records[-limit:]
        return records

    def clear(self) -> None:
        with self._lock:
            self._records.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    @property
    def total_recorded(self) -> int:
        """Lifetime record count (survives ring eviction) — the progress
        signal :class:`StallWatchdog` monitors by default."""
        with self._lock:
            return self._recorded

    @property
    def seconds_since_last_record(self) -> float:
        with self._lock:
            return time.monotonic() - self._last_record_monotonic

    def dump_jsonl(self, path: str | Path) -> int:
        records = self.snapshot()
        with open(path, 'w') as handle:
            for entry in records:
                handle.write(json.dumps(entry, default=str) + '\n')
        return len(records)


_default_recorder = FlightRecorder()


def get_flight_recorder() -> FlightRecorder:
    """The process-wide flight ring (what ``/debug/flight`` serves)."""
    return _default_recorder


# ------------------------------------------------------------ debug bundle
def dump_debug_bundle(
    directory: str | Path,
    *,
    reason: str = 'unspecified',
    recorder: FlightRecorder | None = None,
    extra: dict | None = None,
) -> dict[str, str]:
    """Write the full observability state to ``directory`` and return the
    written paths. Called by the watchdog on stall and by
    ``GET /debug/bundle`` on demand.

    Contents: ``flight.jsonl`` (engine-step ring), ``metrics.prom``
    (Prometheus exposition snapshot), ``traces.jsonl`` (span ring),
    ``startup.json`` (compile-phase records + the phase currently in
    progress + profiler-capture state — an init-stall bundle names the
    dead phase instead of arriving empty), ``history.json`` (the
    metric-history ring: the minutes BEFORE the incident, not just the
    final values), ``slo.json`` (burn-rate status + regression-sentinel
    state), ``meta.json``
    (reason/pid/time/extra), and — best-effort, when a JAX backend is
    initialized and supports it — ``device_memory.prof``
    (``jax.profiler.save_device_memory_profile``). Every piece is written
    independently: a failure in one never loses the others.
    """
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    recorder = recorder if recorder is not None else _default_recorder
    paths: dict[str, str] = {}

    flight_path = directory / 'flight.jsonl'
    try:
        recorder.dump_jsonl(flight_path)
        paths['flight'] = str(flight_path)
    except Exception:
        pass
    metrics_path = directory / 'metrics.prom'
    try:
        metrics_path.write_text(render_prometheus())
        paths['metrics'] = str(metrics_path)
    except Exception:
        pass
    traces_path = directory / 'traces.jsonl'
    try:
        get_trace_buffer().dump_jsonl(traces_path)
        paths['traces'] = str(traces_path)
    except Exception:
        pass
    # Startup/compile attribution + profiler-capture state: the r03/r04
    # failure mode is a process wedged INSIDE backend init or a warmup
    # compile — the flight ring is empty then, but the compile watcher's
    # in-progress phase names exactly where it died. Lazy imports: both
    # modules import this one.
    startup_path = directory / 'startup.json'
    try:
        from distllm_tpu.observability.profiling import get_profiler_capture
        from distllm_tpu.observability.startup import get_compile_watcher

        startup_path.write_text(
            json.dumps(
                {
                    'compile': get_compile_watcher().state(),
                    'profiler': get_profiler_capture().state(),
                },
                default=str,
            )
        )
        paths['startup'] = str(startup_path)
    except Exception:
        pass
    # Metric history + SLO/sentinel state: the time-resolved twin of the
    # instantaneous metrics.prom snapshot — a bundle dumped mid-incident
    # shows the minutes BEFORE the stall, not just the final values.
    # Lazy imports (history/slo/sentinel import instruments, which sits
    # beside this module in the package).
    history_path = directory / 'history.json'
    try:
        from distllm_tpu.observability.history import get_metrics_history

        history_path.write_text(
            json.dumps(get_metrics_history().snapshot(), default=str)
        )
        paths['history'] = str(history_path)
    except Exception:
        pass
    slo_path = directory / 'slo.json'
    try:
        from distllm_tpu.observability.history import get_metrics_history
        from distllm_tpu.observability.sentinel import (
            get_regression_sentinel,
        )
        from distllm_tpu.observability.slo import slo_status

        sentinel = get_regression_sentinel()
        slo_path.write_text(
            json.dumps(
                {
                    'slo': slo_status(get_metrics_history()),
                    'sentinel': (
                        sentinel.status() if sentinel is not None else None
                    ),
                },
                default=str,
            )
        )
        paths['slo'] = str(slo_path)
    except Exception:
        pass
    # Perfetto/Chrome trace of the same state: drop flight.jsonl's raw
    # rings into https://ui.perfetto.dev without any conversion step —
    # the post-mortem view of where the dying process's time went.
    perfetto_path = directory / 'perfetto.json'
    try:
        from distllm_tpu.observability.history import get_metrics_history
        from distllm_tpu.observability.perfetto import dump_trace

        dump_trace(
            perfetto_path,
            recorder.snapshot(),
            [s.to_dict() for s in get_trace_buffer().snapshot()],
            history=get_metrics_history(),
        )
        paths['perfetto'] = str(perfetto_path)
    except Exception:
        pass
    # Optional device-memory capture: only when jax is already imported
    # (importing it here could initialize a backend inside a dying
    # process) and the backend supports the profiler.
    try:  # pragma: no cover - backend-dependent
        import sys

        jax = sys.modules.get('jax')
        if jax is not None:
            prof_path = directory / 'device_memory.prof'
            jax.profiler.save_device_memory_profile(str(prof_path))
            paths['device_memory'] = str(prof_path)
    except Exception:
        pass
    meta_path = directory / 'meta.json'
    try:
        meta_path.write_text(
            json.dumps(
                {
                    'reason': reason,
                    'pid': os.getpid(),
                    'wall_time_s': time.time(),
                    'flight_records': len(recorder),
                    **(extra or {}),
                },
                default=str,
            )
        )
        paths['meta'] = str(meta_path)
    except Exception:
        pass
    _metrics.DEBUG_BUNDLES.inc()
    return paths


# ---------------------------------------------------------------- watchdog
class StallWatchdog:
    """Detects stalled progress and dumps a debug bundle.

    ``progress_fn`` returns any value; the watchdog fires ``on_stall``
    when the value has not *changed* for ``stall_s`` seconds. The default
    progress function is the process flight ring's lifetime record count,
    so an engine that stops dispatching windows (wedged backend, deadlocked
    host loop) trips the dog without any engine-side wiring. The default
    ``on_stall`` dumps a bundle to ``bundle_dir`` and logs it — it never
    kills the watched work; it exists so the corpse carries evidence.

    Fires at most ``max_fires`` times (default 1) per arm; ``beat()``
    force-marks progress for work that is alive but quiet. Use as a
    context manager around the work, or ``start()``/``stop()`` manually.
    """

    def __init__(
        self,
        stall_s: float,
        *,
        progress_fn=None,
        on_stall=None,
        bundle_dir: str | Path | None = None,
        poll_s: float | None = None,
        max_fires: int = 1,
        name: str = 'watchdog',
    ) -> None:
        if stall_s <= 0:
            raise ValueError('stall_s must be > 0')
        self.stall_s = stall_s
        self.name = name
        self._progress_fn = progress_fn or (
            lambda: _default_recorder.total_recorded
        )
        self._on_stall = on_stall
        self._bundle_dir = bundle_dir
        self._poll_s = poll_s if poll_s is not None else min(1.0, stall_s / 4)
        self._max_fires = max_fires
        self.fired = 0
        self._beats = 0
        self._stop_event = threading.Event()
        self._thread: threading.Thread | None = None

    def beat(self) -> None:
        """Mark progress explicitly (for work the ring cannot see)."""
        self._beats += 1

    def _fire(self) -> None:
        self.fired += 1
        _metrics.WATCHDOG_STALLS.inc()
        _metrics.log_event(
            f'[{self.name}] no progress for {self.stall_s:.0f}s — '
            'dumping debug bundle',
            component='watchdog',
        )
        if self._on_stall is not None:
            self._on_stall(self)
        elif self._bundle_dir is not None:
            paths = dump_debug_bundle(
                self._bundle_dir,
                reason=f'{self.name}: stalled for {self.stall_s:.0f}s',
            )
            _metrics.log_event(
                f'[{self.name}] debug bundle: '
                f'{paths.get("meta", self._bundle_dir)}',
                component='watchdog',
            )

    def _run(self) -> None:
        last = (self._progress_fn(), self._beats)
        last_change = time.monotonic()
        while not self._stop_event.wait(self._poll_s):
            try:
                current = (self._progress_fn(), self._beats)
            except Exception:
                continue  # a dying progress probe must not kill the dog
            if current != last:
                last = current
                last_change = time.monotonic()
                continue
            if (
                time.monotonic() - last_change >= self.stall_s
                and self.fired < self._max_fires
            ):
                try:
                    self._fire()
                except Exception:
                    pass  # the watchdog must survive its own handler
                last_change = time.monotonic()

    def start(self) -> 'StallWatchdog':
        if self._thread is not None:
            raise RuntimeError('watchdog already started')
        self._stop_event.clear()
        self._thread = threading.Thread(
            target=self._run, name=self.name, daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop_event.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self) -> 'StallWatchdog':
        return self.start()

    def __exit__(self, *exc_info) -> None:
        self.stop()
