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
  ``distllm_compile_seconds{kind,shape,path}`` histogram and
  ``distllm_compile_cache_hits_total`` counter. Phase kinds are
  registered in ``instruments.COMPILE_PHASES`` (enforced by
  ``tests/test_lint.py``) so the startup schema cannot fragment.
- **cache-hit marking** — a phase is marked ``cache_hit`` when its
  (kind, shape) already completed in this process (re-warmup fast path)
  or when every program it compiled came out of the persistent
  compilation cache, by jax's own cache-hit event (an
  AOT-preflight-seeded cold start).
- **every compiled program is a record too** — a listening watcher
  (:meth:`CompileWatcher.listen`, one pair of ``jax.monitoring`` listeners
  a process) turns each backend-compile event into a ``compile`` record
  with ``program``, ``duration_s``, ``cache_hit`` and ``path``:
  ``startup`` inside a ``phase(...)``, whose ``phase`` and ``shape`` it
  then carries, else ``serving``, where it names the open step span
  (``during``, ``seq``; observability/steps.py).
  When the compile interrupted one of the engine's jit calls, the record
  carries that call's argument signature, and a program lowered again for
  shapes it already ran is flagged ``relowered`` with the arguments that
  ``changed``.
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
import hashlib
import inspect
import itertools
import threading
import time

from distllm_tpu.observability import instruments as _metrics
from distllm_tpu.observability import steps as _steps
from distllm_tpu.observability.flight import FlightRecorder, get_flight_recorder

# Completed-phase summaries kept for state()/debug bundles; a bench run's
# whole warmup ladder is tens of phases, so this never truncates in
# practice — it only bounds a pathological caller.
_MAX_PHASES = 256

# jax.monitoring's names in the installed jax (_src/dispatch.py,
# _src/compiler.py). The cache-hit event fires inside the backend-compile
# event's extent, on the compiling thread, before it.
_BACKEND_COMPILE = '/jax/core/compile/backend_compile_duration'
_CACHE_HIT = '/jax/compilation_cache/cache_hits'

_listening: list['CompileWatcher'] = []
_install_lock = threading.Lock()
_installed = False
_pending = threading.local()  # this thread's cache hit, not yet claimed


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT:
        _pending.hit = True


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    if event == _BACKEND_COMPILE:
        hit = getattr(_pending, 'hit', False)
        _pending.hit = False
        for watcher in list(_listening):
            watcher._on_compile(
                str(kwargs.get('fun_name', '?')), seconds, hit
            )


def _leaf_signature(x) -> tuple:
    """What can make jax lower a program again for an argument of equal
    shape: dtype, weak type, committed or not, sharding, device layout."""
    import jax

    if not isinstance(x, jax.Array):
        return (type(x).__name__, repr(x)[:40])
    try:
        layout = str(x.format.layout)
    except Exception as exc:  # a deleted (donated) buffer has none to show
        layout = f'unreadable: {type(exc).__name__}'
    return (
        tuple(x.shape), str(x.dtype), bool(x.weak_type),
        bool(x._committed), str(x.sharding), layout,
    )


_SIGNATURE_PARTS = ('shape', 'dtype', 'weak_type', 'committed', 'sharding',
                    'layout')


def call_signature(fn, args) -> dict[str, tuple]:
    """``{argument name: signature}`` of one jit call. An array is
    ``(shape, dtype, weak_type, committed, sharding, layout)``; a pytree
    (the parameter tree) is its leaf count, its shapes' hash and the hash
    of its leaves' signatures."""
    import jax

    try:
        names = [
            p.name for p in inspect.signature(fn).parameters.values()
        ]
    except (TypeError, ValueError):  # an AOT executable has no signature
        names = []
    out = {}
    for i, arg in enumerate(args):
        name = names[i] if i < len(names) else f'arg{i}'
        if isinstance(arg, jax.Array) or not isinstance(
            arg, (dict, list, tuple)
        ):
            out[name] = _leaf_signature(arg)
            continue
        leaves = [_leaf_signature(x) for x in jax.tree.leaves(arg)]
        shapes = hashlib.sha1(
            repr([leaf[0] for leaf in leaves]).encode()
        ).hexdigest()[:12]
        whole = hashlib.sha1(repr(leaves).encode()).hexdigest()[:12]
        out[name] = ('tree', len(leaves), shapes, whole)
    return out


def _shapes_of(signature: dict[str, tuple]) -> tuple:
    return tuple(
        sig[:3] if sig and sig[0] == 'tree' else sig[0]
        for sig in signature.values()
    )


def _changed(before: dict[str, tuple], now: dict[str, tuple]) -> list[dict]:
    out = []
    for name, sig in now.items():
        was = before.get(name)
        if was == sig:
            continue
        if was is None or sig[0] == 'tree' or len(was) != len(sig):
            out.append({'arg': name, 'what': 'signature',
                        'was': str(was), 'now': str(sig)})
            continue
        for part, a, b in zip(_SIGNATURE_PARTS, was, sig):
            if a != b:
                out.append({'arg': name, 'what': part,
                            'was': str(a), 'now': str(b)})
    return out


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
        # [programs, cache hits] compiled inside the active phase.
        self._active_programs = [0, 0]  # guarded by self._lock
        # Last argument signature per (jit function, program, shapes).
        self._signatures: dict[tuple, dict] = {}  # guarded by self._lock
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

    def listen(self) -> 'CompileWatcher':
        """Receive jax's compile events from now on. The jax listeners are
        registered once a process, whatever the number of watchers."""
        global _installed
        with _install_lock:
            if not _installed:
                import jax.monitoring as monitoring

                monitoring.register_event_listener(_on_event)
                monitoring.register_event_duration_secs_listener(
                    _on_duration
                )
                _installed = True
            if self not in _listening:
                _listening.append(self)
        return self

    def unlisten(self) -> None:
        with _install_lock:
            if self in _listening:
                _listening.remove(self)

    def _on_compile(self, program: str, seconds: float,
                    cache_hit: bool) -> None:
        """One program compiled (or loaded from the persistent cache) on
        the calling thread: one ``compile`` record. ``path`` says where:
        ``startup`` inside a :meth:`phase`, whose ``phase`` and ``shape``
        the record then carries, else ``serving``, with the open step
        span (``during``) and its step (``seq``)."""
        entry: dict = {
            'program': program,
            'duration_s': round(seconds, 6),
            'cache_hit': cache_hit,
        }
        with self._lock:
            active = self._active
            if active is not None:
                self._active_programs[0] += 1
                self._active_programs[1] += cache_hit
        if active is not None:
            entry.update(
                path='startup', phase=active['phase'], shape=active['shape']
            )
        else:
            during, seq = _steps.current() or (None, None)
            entry.update(path='serving', during=during, seq=seq)
        call = _steps.call_in_flight()
        if call is not None:
            # Only now, with a compile in hand, is the call's signature
            # worth its cost; the arguments are still alive (the compile
            # precedes the execution that donates them). A signature that
            # cannot be read leaves the record without one: this runs
            # inside jax's compile and must not fail it.
            try:
                signature = call_signature(*call)
            except Exception:
                signature = None
            if signature is not None:
                key = (id(call[0]), program, _shapes_of(signature))
                with self._lock:
                    before = self._signatures.get(key)
                    self._signatures[key] = signature
                entry['relowered'] = before is not None
                if before is not None:
                    entry['changed'] = _changed(before, signature)
        try:
            self.recorder.record('compile', **entry)
        except Exception:
            pass  # a full disk must not turn a compile fatal
        if active is None:
            # Startup seconds are observed once, by the phase around the
            # program; a serving program has no phase and is observed here.
            _metrics.COMPILE_SECONDS.labels(
                kind=str(during), shape=program, path='serving'
            ).observe(seconds)

    @contextlib.contextmanager
    def phase(self, kind: str, shape: str, *, compiles: bool = True,
              scope: str = '', **fields):
        """Time one startup phase; yields a mutable fields dict the body
        may enrich (platform, entry counts, ...). On exit — success OR
        failure — one ``compile`` flight record lands in the ring and
        ``distllm_compile_seconds{kind,shape,path}`` observes the duration;
        failures carry an ``error`` field and never count as cache hits.
        The phase is visible via :meth:`state` while in progress, which
        is what lets a bundle dumped mid-stall name the dead phase.

        ``compiles=False`` declares a phase that does real work but no
        XLA compilation (backend init, weight migration, pool
        allocation): such phases can only be cache hits via the
        process-repeat path. Without the flag, a cold first run would
        mark every non-compiling phase as a "hit" (no program missed the
        cache), poisoning exactly the warm-start evidence the counter
        exists to provide.

        A listening watcher (:meth:`listen`) counts the programs jax
        compiled inside the phase (``programs``) and how many of them the
        persistent cache served (``cache_hits``); a compiling phase in
        which none missed is a hit. A watcher that does not listen knows
        only the process-repeat path.

        ``scope`` namespaces the process-repeat dedup: each engine
        passes its own scope, because a SECOND engine in one process
        (an A/B of two engines, the quantization fallback ladder) builds new
        jit wrappers whose warmup really recompiles — the same (kind,
        shape) under a fresh scope must not read as a hit. The
        persistent-cache signal is deliberately scope-free (that cache
        IS shared)."""
        entry: dict = {'phase': kind, 'shape': shape, **fields}
        listening = self in _listening
        with self._lock:
            seen = (scope, kind, shape) in self._seen
            self._active = {**entry, 't_start_wall': time.time()}
            self._active_programs = [0, 0]
        t0 = time.monotonic()
        error: str | None = None
        try:
            yield entry
        except BaseException as exc:
            error = repr(exc)[:300]
            raise
        finally:
            duration_s = time.monotonic() - t0
            with self._lock:
                self._active = None
                programs, hits = self._active_programs
            cache_hit = error is None and (
                seen or (listening and compiles and hits == programs)
            )
            entry['duration_s'] = round(duration_s, 6)
            entry['cache_hit'] = cache_hit
            if listening:
                entry['programs'] = programs
                entry['cache_hits'] = hits
            if error is not None:
                entry['error'] = error
            with self._lock:
                if error is None:
                    self._seen.add((scope, kind, shape))
                self._phases.append({**entry, 't_wall': time.time()})
                del self._phases[:-_MAX_PHASES]
            try:
                self.recorder.record('compile', **entry)
            except Exception:
                pass  # a full disk must not turn startup fatal
            _metrics.COMPILE_SECONDS.labels(
                kind=kind, shape=shape, path='startup'
            ).observe(duration_s)
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
