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
  callback dumps a debug bundle; it never kills the watched work. The
  process's own instance (:func:`get_stall_watchdog`) also watches the
  serving threads' span edges (``steps.edges()``) for the engines
  registered with it, and writes a ``stall`` record with its evidence for a
  stretch that is long for its kind.
- :func:`stall_evidence` — where the process is: every thread's stack, what
  the kernel's counters say of a stalled thread, what each watched engine
  has in flight. The ``stall`` record's and the bundle's ``stacks.json``.
- :func:`dump_debug_bundle` — flight ring + metrics exposition + trace ring
  (+ best-effort ``jax.profiler`` device-memory capture) written to one
  directory, so a dead process still explains itself.

Everything here is dependency-free and safe to import on any backend.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import weakref
from collections import deque
from pathlib import Path

try:
    import resource
except ImportError:  # no getrusage on this platform
    resource = None

from distllm_tpu.observability import instruments as _metrics
from distllm_tpu.observability import steps as _steps
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


# ---------------------------------------------------------- stall evidence
SPAN_POLL_S = 0.25  # the span watcher's period
STALL_SAMPLES = 4  # records a stalled stretch: at detection, then as its age doubles
_STACK_FRAMES = 12
_STACK_THREADS = 8


def _stacks(first: int | None = None) -> list[dict]:
    """Name and innermost frames (``file:line function``, the path cut to
    its last three parts) of the Python threads, ``first`` first and the
    caller's own left out."""
    frames = sys._current_frames()
    frames.pop(threading.get_ident(), None)
    names = {t.ident: t.name for t in threading.enumerate()}
    order = sorted(frames, key=lambda ident: ident != first)
    stacks = []
    for ident in order[:_STACK_THREADS]:
        lines, frame = [], frames[ident]
        while frame is not None and len(lines) < _STACK_FRAMES:
            code = frame.f_code
            path = '/'.join(code.co_filename.split('/')[-3:])
            lines.append(f'{path}:{frame.f_lineno} {code.co_name}')
            frame = frame.f_back
        stacks.append({'thread': names.get(ident, str(ident)), 'frames': lines})
    return stacks


def _counters(edge: _steps.Edge) -> dict:
    """The running counters the kernel keeps of ``edge``'s thread and of
    the process; a stall's evidence is their rise over the stretch. A
    counter the platform lacks (or a thread that has ended) is left out."""
    out = {'cpu_process_s': time.process_time()}
    try:
        out['cpu_thread_s'] = time.clock_gettime(edge.cpu_clock)
    except (AttributeError, TypeError, OSError):
        pass
    try:
        with open(f'/proc/self/task/{edge.native_id}/schedstat') as handle:
            _ran_ns, waited_ns, slices = handle.read().split()
        out['sched_delay_s'] = int(waited_ns) / 1e9
        out['timeslices'] = int(slices)
    except (OSError, ValueError):
        pass
    if resource is not None:
        usage = resource.getrusage(resource.RUSAGE_SELF)
        out['majflt'] = usage.ru_majflt
        out['nivcsw'] = usage.ru_nivcsw
    return out


def stall_evidence(
    edge: _steps.Edge | None = None,
    since: dict | None = None,
    tick_late_s: float | None = None,
    contexts: list[dict] | None = None,
) -> dict:
    """Where the process is, for a ``stall`` record and a bundle's
    ``stacks.json``: ``stacks`` of every Python thread (``edge``'s first);
    ``tick_late_s``, how late the watcher itself woke (a frozen process
    shows here); the rise of ``edge``'s thread's counters since the sample
    ``since`` (``cpu_thread_s``, ``cpu_process_s``, ``sched_delay_s``,
    ``timeslices``, ``majflt``, ``nivcsw``); and what the watched engines
    say of themselves (``LLMEngine.stall_context``, summed over them):
    windows ``in_flight``, whether each one's tokens are ``ready``,
    ``unfinished`` requests, and ``compiling``."""
    out: dict = {'stacks': _stacks(edge.ident if edge is not None else None)}
    if tick_late_s is not None:
        out['tick_late_s'] = round(tick_late_s, 6)
    if edge is not None and since is not None:
        for name, value in _counters(edge).items():
            if name in since:
                out[name] = round(value - since[name], 6)
    if contexts is None:
        contexts = get_stall_watchdog().engine_contexts()
    if contexts:
        out['in_flight'] = sum(c['in_flight'] for c in contexts)
        out['ready'] = [r for c in contexts for r in c['ready']]
        out['unfinished'] = sum(c['unfinished'] for c in contexts)
        out['compiling'] = any(c['compiling'] for c in contexts)
    return out


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
    state), ``stacks.json`` (:func:`stall_evidence`: every thread's stack
    and what the watched engines have in flight, beside each serving
    thread's open span and its age — where the process IS, not only what
    it did), ``meta.json``
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
    stacks_path = directory / 'stacks.json'
    try:
        now = _steps.clock()
        stacks_path.write_text(
            json.dumps(
                {
                    **stall_evidence(),
                    'spans': [
                        {
                            'thread': e.thread, 'span': e.span, 'seq': e.seq,
                            'root': e.root, 'age_s': round(now - e.t, 6),
                        }
                        for e in _steps.edges()
                    ],
                },
                default=str,
            )
        )
        paths['stacks'] = str(stacks_path)
    except Exception:
        pass
    # Optional device-memory capture: only when jax is already imported
    # (importing it here could initialize a backend inside a dying
    # process) and the backend supports the profiler.
    try:  # pragma: no cover - backend-dependent
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

    An instance that engines are registered with (``watch``; the process's
    own, :func:`get_stall_watchdog`) also reads the serving threads' span
    edges every period: a stretch older than ``steps.stall_threshold_s``
    (a hole between spans only while a watched engine that serves on the
    thread has unfinished work) is flagged on its edge, so that the thread
    books its seconds on the step's record (``stalled_s``), and written to
    the flight ring as a ``stall`` record with :func:`stall_evidence`:
    ``sample`` 0 at detection, one more each time the age doubles,
    ``STALL_SAMPLES`` at most. It runs from the first ``watch`` to the last
    ``unwatch`` (engines are held weakly: one that is dropped without a
    ``shutdown()`` leaves no thread behind either).
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
        # The engines whose serving threads' spans are watched, and of each
        # thread's stretch what the watcher has seen: its edge, the
        # counters' last sample and the last one at or before the edge,
        # the stall records written.
        self._watch_lock = threading.Lock()
        self._engines = weakref.WeakSet()  # guarded by self._watch_lock
        self._watches_spans = False  # from the first ``watch`` on
        self._stretches: dict[int, dict] = {}
        self._tick_late_s = 0.0

    # ------------------------------------------------------ span watching
    def watch(self, engine) -> None:
        """Watch the spans of ``engine``'s serving thread from now on
        (starts the thread with the first engine)."""
        with self._watch_lock:
            self._engines.add(engine)
            self._watches_spans = True
            if self._thread is None:
                self.start()

    def unwatch(self, engine) -> None:
        """``engine`` shut down: the thread stops with the last one."""
        with self._watch_lock:
            watched = engine in self._engines
            self._engines.discard(engine)
            last = watched and not self._engines
        if last:
            self.stop()
            with self._watch_lock:  # an engine built while it stopped
                if self._engines and self._thread is None:
                    self.start()

    def engine_contexts(self) -> list[dict]:
        """``stall_context()`` of every watched engine that gives one."""
        with self._watch_lock:
            engines = list(self._engines)
        contexts = []
        for engine in engines:
            try:
                contexts.append(engine.stall_context())
            except Exception:
                pass  # an engine half torn down has nothing to say
        return contexts

    def _watch_spans(self) -> None:
        """One reading of the edge table (``steps.edges()``)."""
        now = _steps.clock()
        alive = {t.ident for t in threading.enumerate()}
        for edge in _steps.edges():
            if edge.ident not in alive:
                _steps.forget(edge.ident)
                self._stretches.pop(edge.ident, None)
                continue
            t_edge, span, seq = edge.t, edge.span, edge.seq
            seen = self._stretches.get(edge.ident)
            counters = _counters(edge)
            if seen is None or seen['t_edge'] != t_edge:
                # The edge moved since the last reading: the sample taken
                # then is the last one at or before it.
                seen = self._stretches[edge.ident] = {
                    't_edge': t_edge, 'samples': 0, 'next_age_s': 0.0,
                    'since': seen['last'] if seen is not None else counters,
                }
            seen['last'] = counters
            age_s = now - t_edge
            if (
                seen['samples'] >= STALL_SAMPLES
                or age_s < seen['next_age_s']
                or age_s <= _steps.stall_threshold_s(span, now)
            ):
                continue
            contexts = self.engine_contexts()
            if span is None and not any(
                c['unfinished'] and c['thread'] == edge.ident for c in contexts
            ):
                continue  # nobody waits for this thread: an idle server
            evidence = stall_evidence(
                edge, seen['since'], self._tick_late_s, contexts
            )
            if seen['samples'] == 0:
                edge.excused = bool(evidence.get('compiling'))
                edge.flagged = t_edge
                # A compile is a stretch with a known cause and a record of
                # its own (``compile``): kept, neither logged nor counted.
                if not evidence.get('compiling'):
                    _metrics.WATCHDOG_STALLS.inc()
                    _metrics.log_event(
                        f'[{self.name}] thread {edge.thread} has been in '
                        f'{"distllm:" + span if span else "no span"} for '
                        f'{age_s:.2f}s (seq {seq})',
                        component='watchdog',
                    )
            _default_recorder.record(
                'stall', seq=seq, span=span, thread=edge.thread,
                t_edge_s=round(t_edge, 6), t_s=round(now, 6),
                age_s=round(age_s, 6), sample=seen['samples'], **evidence,
            )
            seen['samples'] += 1
            seen['next_age_s'] = 2 * age_s

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
        while True:
            asleep = _steps.clock()
            if self._stop_event.wait(self._poll_s):
                return
            self._tick_late_s = max(
                0.0, _steps.clock() - asleep - self._poll_s
            )
            if self._watches_spans:
                with self._watch_lock:
                    if not self._engines:  # every engine shut down or dropped
                        self._thread = None
                        self._stretches.clear()
                        return
                try:
                    self._watch_spans()
                except Exception:
                    pass  # the watcher must survive a reading it cannot make
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
        thread = self._thread  # a span watcher may end itself meanwhile
        if thread is not None:
            thread.join(timeout=5)
            self._thread = None

    def __enter__(self) -> 'StallWatchdog':
        return self.start()

    def __exit__(self, *exc_info) -> None:
        self.stop()


_span_watchdog = StallWatchdog(
    float('inf'), poll_s=SPAN_POLL_S, name='span-watchdog'
)


def get_stall_watchdog() -> StallWatchdog:
    """The process's span watcher: ring growth never fires it (``stall_s``
    is infinite); the engines built with ``attribution`` on register with
    it and it runs while one of them lives."""
    return _span_watchdog
