"""Bounded, error-safe programmatic profiler capture (ISSUE 11 tentpole).

``jax.profiler.start_trace`` / ``stop_trace`` are the ground-truth device
attribution tool (XPlane + TensorBoard format), but raw use has two
serving-path hazards this helper removes:

- **unbounded captures** — a started trace that is never stopped grows
  until the process dies; every capture here auto-stops after
  ``max_seconds`` via a daemon timer;
- **fatal errors** — on backends without profiler support,
  ``start_trace`` raises and previously could kill a whole bench stage.
  Every profiler call here is caught; failures land in
  :meth:`ProfilerCapture.state` (and debug bundles, via ``startup.json``)
  and in ``distllm_profiler_captures_total{outcome}``, never in the
  caller's stack.

One capture may be active at a time (jax's profiler is a process-global
session); concurrent starts are *rejected*, not queued. Consumers:

- ``GET /debug/xprof?seconds=N`` on the chat server — on-demand blocking
  capture of a live serving process, returns the trace directory;
- debug bundles — the capture state (active/last_error/total) rides
  ``startup.json`` so a bundle says whether a capture was in flight.

Dependency-free at import time; jax is imported lazily inside the calls.
"""

from __future__ import annotations

import math
import threading
import time

from distllm_tpu.observability import instruments as _metrics

# Hard ceiling on any capture: profiler traces of a busy engine grow at
# tens of MB/s, and an operator typo ("seconds=3600") must not fill the
# disk of a serving host.
MAX_CAPTURE_SECONDS = 1800.0


def _clamp_seconds(value, default: float = 60.0) -> float:
    """Clamp into (0.1, MAX_CAPTURE_SECONDS]. NaN/inf would slide through
    ``min``/``max`` unchanged and later crash ``Timer``/``sleep`` — a
    malformed duration must degrade to the default, never raise."""
    try:
        value = float(value)
    except (TypeError, ValueError):
        value = default
    if not math.isfinite(value) or value <= 0:
        value = default
    return min(max(value, 0.1), MAX_CAPTURE_SECONDS)


class ProfilerCapture:
    """At-most-one bounded ``jax.profiler`` trace session."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._active: dict | None = None  # guarded by self._lock
        self._timer: threading.Timer | None = None  # guarded by self._lock
        self._last_error: str | None = None  # guarded by self._lock
        self._captures = 0  # guarded by self._lock

    def start(self, log_dir, max_seconds: float = 60.0) -> bool:
        """Begin a capture into ``log_dir``; returns whether it started.

        ``False`` means rejected (one already active) or the backend's
        profiler failed — both recorded in :meth:`state` and the outcome
        counter, neither raised. A started capture auto-stops after
        ``max_seconds`` (clamped to :data:`MAX_CAPTURE_SECONDS`).
        """
        return self._start(log_dir, max_seconds) is None

    def _start(self, log_dir, max_seconds: float) -> tuple[str, str] | None:
        """``None`` on success, else ``(outcome, message)`` with outcome
        ``'rejected'`` or ``'error'`` — returned to the caller directly
        so classification never round-trips through the shared
        ``_last_error`` slot (a concurrent stop-flush error could
        overwrite it between write and read)."""
        max_seconds = _clamp_seconds(max_seconds)
        with self._lock:
            if self._active is not None:
                message = (
                    f'capture already active in {self._active["log_dir"]}'
                )
                self._last_error = message
                _metrics.PROFILER_CAPTURES.labels(outcome='rejected').inc()
                return 'rejected', message
            # Reserve the slot before the (slow, lock-free) profiler call
            # so two concurrent starts cannot both reach start_trace.
            self._active = {
                'log_dir': str(log_dir),
                'started_wall_s': time.time(),
                'max_seconds': max_seconds,
            }
        try:
            import jax

            jax.profiler.start_trace(str(log_dir))
        except Exception as exc:
            message = repr(exc)[:300]
            with self._lock:
                self._active = None
                self._last_error = message
            _metrics.PROFILER_CAPTURES.labels(outcome='error').inc()
            return 'error', message
        timer = threading.Timer(max_seconds, self.stop)
        timer.daemon = True
        with self._lock:
            self._timer = timer
        timer.start()
        return None

    def stop(self) -> bool:
        """Stop the active capture; returns whether one was stopped.

        Idempotent (the auto-stop timer and an explicit caller may race);
        profiler flush errors are swallowed into :meth:`state`.
        """
        with self._lock:
            if self._active is None:
                return False
            self._active = None
            timer, self._timer = self._timer, None
        if timer is not None:
            timer.cancel()
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as exc:
            with self._lock:
                self._last_error = repr(exc)[:300]
            _metrics.PROFILER_CAPTURES.labels(outcome='error').inc()
            return False
        with self._lock:
            self._captures += 1
        _metrics.PROFILER_CAPTURES.labels(outcome='ok').inc()
        return True

    def capture(self, log_dir, seconds: float) -> dict:
        """Blocking convenience for ``GET /debug/xprof``: start, sleep,
        stop; returns ``{'ok', 'rejected', 'trace_dir', 'error'}``. Runs
        in an executor thread server-side — the event loop never sleeps.
        """
        seconds = _clamp_seconds(seconds, default=1.0)
        # The auto-stop bound is a BACKSTOP against a wedged sleep/stop,
        # not a twin deadline: armed at exactly ``seconds`` it would race
        # the deliberate stop below and turn a clean capture into a
        # spurious failure (observed live on /debug/xprof).
        failure = self._start(log_dir, max_seconds=seconds + 30.0)
        if failure is not None:
            outcome, message = failure
            return {
                'ok': False,
                'rejected': outcome == 'rejected',
                'trace_dir': str(log_dir),
                'error': message,
            }
        time.sleep(seconds)
        ok = self.stop()
        with self._lock:
            error = None if ok else self._last_error
        return {
            'ok': ok,
            'rejected': False,
            'trace_dir': str(log_dir),
            'error': error,
        }

    def state(self) -> dict:
        """Snapshot for bundles/endpoints: the active capture (or None),
        the last profiler error, and the lifetime completed count."""
        with self._lock:
            return {
                'active': dict(self._active) if self._active else None,
                'last_error': self._last_error,
                'captures_total': self._captures,
            }


_default_capture = ProfilerCapture()


def get_profiler_capture() -> ProfilerCapture:
    """The process-wide capture slot (jax's profiler is process-global)."""
    return _default_capture
