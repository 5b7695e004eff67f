"""Startup & compile-phase attribution (ISSUE 11 tentpole).

Three official bench rounds (r03–r05) died inside backend init or the
warmup compile ladder — the single most expensive startup phase, 22–45
minutes cold for int8 — and left *nothing* behind: no spans, no flight
records, no hint of which shape the process was compiling when it
stopped. This module makes startup attributable the same way ISSUE 3
made the serving loop attributable:

- :class:`CompileWatcher` — a process-wide watcher whose ``phase(kind,
  shape)`` context manager times one startup phase (a warmup shape, the
  weight-layout migration, backend init, ...) and emits a ``compile``
  flight-ring record per phase, plus the
  ``distllm_compile_seconds{kind,shape}`` histogram and
  ``distllm_compile_cache_hits_total`` counter. Phase kinds are
  registered in ``instruments.COMPILE_PHASES`` (enforced by
  ``tests/test_lint.py``) so the startup schema cannot fragment.
- **cache-hit marking** — a phase is marked ``cache_hit`` when its
  (kind, shape) already completed in this process (re-warmup fast path)
  or when the phase added zero new entries to a configured persistent
  compilation cache (an AOT-preflight-seeded cold start).
- **dead-phase attribution** — the watcher tracks the phase currently
  *in progress*; ``state()`` (written into every debug bundle as
  ``startup.json``) names it, so an init-stall bundle — the r03/r04
  failure mode — says *which shape* the process died in instead of
  arriving empty.
- :func:`record_backend_init` — wraps the first ``jax.devices()`` touch
  in a ``backend_init`` phase; later calls are near-instant and marked
  as cache hits, so it is safe to call from every engine constructor.

Rendering: ``compile`` records get a dedicated *startup* track in the
Perfetto export (``observability/perfetto.py``), beside the serving
window tracks. Phase durations are host wall time around the dispatch —
on TPU, compilation happens inside the traced call, so a cold phase's
duration IS its compile time (plus a negligible dummy execution).

Everything here is dependency-free and safe to import on any backend.
"""

from __future__ import annotations

import contextlib
import itertools
import sys
import threading
import time

from distllm_tpu.observability import instruments as _metrics
from distllm_tpu.observability.flight import FlightRecorder, get_flight_recorder

# Completed-phase summaries kept for state()/debug bundles; a bench run's
# whole warmup ladder is tens of phases, so this never truncates in
# practice — it only bounds a pathological caller.
_MAX_PHASES = 256


class CompileWatcher:
    """Times startup/compile phases into flight records + metric series.

    One watcher serves the whole process (:func:`get_compile_watcher`);
    tests inject their own ``recorder`` for isolation. Thread-safe: the
    engine thread, the aiohttp event loop, and bundle dumps may touch it
    at once — though phases themselves are expected to run sequentially
    (startup is single-threaded), so ``active`` is a single slot.
    """

    def __init__(self, recorder: FlightRecorder | None = None) -> None:
        self._recorder = recorder
        self._lock = threading.Lock()
        self._seen: set[tuple[str, str, str]] = set()  # guarded by self._lock
        self._phases: list[dict] = []  # guarded by self._lock
        self._active: dict | None = None  # guarded by self._lock
        self._scopes = itertools.count()

    def new_scope(self, prefix: str = 'engine') -> str:
        """A fresh dedup namespace for :meth:`phase`'s ``scope`` — one
        per engine instance, so rebuilt engines start cold."""
        return f'{prefix}-{next(self._scopes)}'

    @property
    def recorder(self) -> FlightRecorder:
        return (
            self._recorder
            if self._recorder is not None
            else get_flight_recorder()
        )

    @staticmethod
    def _persistent_cache_entries() -> int | None:
        """Entry count of jax's persistent compilation cache dir, or
        ``None`` when no cache is configured / jax is not imported.
        Before/after deltas per phase reveal whether a cold start HIT the
        preflight-seeded cache or re-lowered everything (the same signal
        bench.py's ``warm_start`` field reports per stage)."""
        if 'jax' not in sys.modules:
            return None
        from distllm_tpu.utils import compile_cache_entries

        return compile_cache_entries()

    @contextlib.contextmanager
    def phase(self, kind: str, shape: str, *, compiles: bool = True,
              scope: str = '', **fields):
        """Time one startup phase; yields a mutable fields dict the body
        may enrich (platform, entry counts, ...). On exit — success OR
        failure — one ``compile`` flight record lands in the ring and
        ``distllm_compile_seconds{kind,shape}`` observes the duration;
        failures carry an ``error`` field and never count as cache hits.
        The phase is visible via :meth:`state` while in progress, which
        is what lets a bundle dumped mid-stall name the dead phase.

        ``compiles=False`` declares a phase that does real work but no
        XLA compilation (backend init, weight migration, pool
        allocation): such phases can only be cache hits via the
        process-repeat path. Without the flag, a cold first run with a
        persistent cache dir configured would mark every non-compiling
        phase as a "hit" (zero new cache entries), poisoning exactly the
        warm-start evidence the counter exists to provide.

        ``scope`` namespaces the process-repeat dedup: each engine
        passes its own scope, because a SECOND engine in one process
        (bench A/B stages, the quantization fallback ladder) builds new
        jit wrappers whose warmup really recompiles — the same (kind,
        shape) under a fresh scope must not read as a hit. The
        persistent-cache-delta signal is deliberately scope-free (that
        cache IS shared)."""
        entry: dict = {'phase': kind, 'shape': shape, **fields}
        entries_before = self._persistent_cache_entries()
        with self._lock:
            seen = (scope, kind, shape) in self._seen
            self._active = {**entry, 't_start_wall': time.time()}
        t0 = time.monotonic()
        error: str | None = None
        try:
            yield entry
        except BaseException as exc:
            error = repr(exc)[:300]
            raise
        finally:
            duration_s = time.monotonic() - t0
            entries_after = self._persistent_cache_entries()
            persistent_delta = (
                entries_after - entries_before
                if entries_before is not None and entries_after is not None
                else None
            )
            cache_hit = error is None and (
                seen or (compiles and persistent_delta == 0)
            )
            entry['duration_s'] = round(duration_s, 6)
            entry['cache_hit'] = cache_hit
            if persistent_delta is not None:
                entry['persistent_cache_delta'] = persistent_delta
            if error is not None:
                entry['error'] = error
            with self._lock:
                self._active = None
                if error is None:
                    self._seen.add((scope, kind, shape))
                self._phases.append({**entry, 't_wall': time.time()})
                del self._phases[:-_MAX_PHASES]
            try:
                self.recorder.record('compile', **entry)
            except Exception:
                pass  # a full disk must not turn startup fatal
            _metrics.COMPILE_SECONDS.labels(kind=kind, shape=shape).observe(
                duration_s
            )
            if cache_hit:
                _metrics.COMPILE_CACHE_HITS.inc()

    def state(self) -> dict:
        """Snapshot for debug bundles: the completed phase list plus the
        phase currently in progress (``None`` between phases). A bundle
        dumped during a wedged init shows ``active`` naming the exact
        (kind, shape) the process is stuck compiling."""
        with self._lock:
            return {
                'active': dict(self._active) if self._active else None,
                'phases': [dict(p) for p in self._phases],
            }


_default_watcher = CompileWatcher()


def get_compile_watcher() -> CompileWatcher:
    """The process-wide compile watcher (what engines and bundles use)."""
    return _default_watcher


def record_backend_init(watcher: CompileWatcher | None = None):
    """Time the jax backend/device init as a ``backend_init`` phase.

    The first call in a process pays (and attributes) the real PJRT
    client init — the phase r03/r04 died in, previously invisible; later
    calls return in microseconds and are marked as cache hits. Returns
    the device list. Exceptions propagate (a dead backend is fatal to
    the caller) but the phase record lands first, with the error.
    """
    watcher = watcher if watcher is not None else _default_watcher
    import jax

    with watcher.phase('backend_init', 'devices', compiles=False) as fields:
        devices = jax.devices()
        fields['platform'] = devices[0].platform
        fields['device_kind'] = devices[0].device_kind
        fields['num_devices'] = len(devices)
    return devices
