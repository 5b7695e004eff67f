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
  (:meth:`CompileWatcher.listen`, one set of ``jax.monitoring`` listeners
  a process) turns each backend-compile event into a ``compile`` record
  with ``program``, ``duration_s``, ``cache_hit`` and ``path``:
  ``startup`` inside a ``phase(...)``, whose ``phase`` and ``shape`` it
  then carries (the innermost open phase's), and with no phase before
  the watcher's first ``engine_init`` has closed (what an entry point
  compiles before it builds an engine); else ``serving``, where it names
  the open step span (``during``, ``seq``; observability/steps.py).
  When the compile interrupted one of the engine's jit calls, the record
  carries that call's argument signature, and a program lowered again for
  shapes it already ran is flagged ``relowered`` with the arguments that
  ``changed``.
- **a program's whole cost, by stage** — the record also carries
  ``trace_s`` and ``lower_s`` (jax's jaxpr-trace and jaxpr-to-MLIR
  durations on the compiling thread since its last backend compile: the
  Python a program costs warm or cold) and ``cache``: ``'hit'`` (loaded
  from the persistent cache), ``'miss'`` (compiled and written to it) or
  ``'uncached'`` (compiled and not written: under jax's minimum compile
  time or entry size, or the cache is off).
- **one clock** — every ``compile`` record, phase or program, carries
  ``t0_s`` and ``t1_s`` on ``observability.steps.clock``, the clock of the
  step records, beside its ``t_wall``; :func:`process_start_s` gives the
  process's start on it.
- **an account that outlives the ring and the engine** — the watcher
  keeps its program records as it keeps its phases; ``state()`` returns
  both and :meth:`CompileWatcher.summary` reduces them to where the
  seconds before a point of the clock went: three stretches (before the
  first ``engine_init``, inside it, after it) and the programs by stage.
- **dead-phase attribution** — the watcher tracks the phases currently
  *in progress* (a stack: ``engine_init`` holds the engine's inner
  phases); ``state()`` (written into every debug bundle as
  ``startup.json``) names them, so an init-stall bundle — the r03/r04
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
import functools
import hashlib
import inspect
import itertools
import os
import threading
import time

from distllm_tpu.observability import instruments as _metrics
from distllm_tpu.observability import steps as _steps
from distllm_tpu.observability.flight import FlightRecorder, get_flight_recorder

# Completed-phase summaries kept for state()/debug bundles; a bench run's
# whole warmup ladder is tens of phases, so this never truncates in
# practice — it only bounds a pathological caller.
_MAX_PHASES = 256
# Program records kept beside them: a set-up compiles or loads 53-88.
_MAX_PROGRAMS = 1024

# jax.monitoring's names in the installed jax (_src/dispatch.py,
# _src/compiler.py, _src/compilation_cache.py). The cache events fire
# inside the backend-compile event's extent, on the compiling thread,
# before its duration; the trace and lowering events before its start.
_BACKEND_COMPILE = '/jax/core/compile/backend_compile_duration'
_TRACE = '/jax/core/compile/jaxpr_trace_duration'
_LOWER = '/jax/core/compile/jaxpr_to_mlir_module_duration'
_CACHE_HIT = '/jax/compilation_cache/cache_hits'
_CACHE_MISS = '/jax/compilation_cache/cache_misses'
_CACHE_VERDICTS = {_CACHE_HIT: 'hit', _CACHE_MISS: 'miss'}

_IMPORTED_S = _steps.clock()  # process_start_s() where /proc has no say

_listening: list['CompileWatcher'] = []
_install_lock = threading.Lock()
_installed = False


class _Pending(threading.local):
    """What this thread's jax events have said since its last backend
    compile, which claims it: the persistent cache's verdict, the seconds
    of tracing and of lowering, and how many traces are open (an inner
    jit is traced inside the outer one's extent, whose seconds hold it)."""

    cache = 'uncached'
    trace_s = 0.0
    lower_s = 0.0
    tracing = 0


_pending = _Pending()
# Threads inside one of jax's three compile stages (trace, lowering,
# backend compile), each with how many it has open: what another thread
# may ask (``CompileWatcher.compiling``).
_STAGES = (_TRACE, _LOWER, _BACKEND_COMPILE)
_in_stage: dict[int, int] = {}


def _on_event(event: str, **kwargs) -> None:
    verdict = _CACHE_VERDICTS.get(event)
    if verdict is not None:
        _pending.cache = verdict


def _on_scalar(event: str, value, **kwargs) -> None:
    # jax announces a timed extent at its start with a scalar.
    if event in _STAGES:
        ident = threading.get_ident()
        _in_stage[ident] = _in_stage.get(ident, 0) + 1
    if event == _TRACE:
        _pending.tracing += 1


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    if event in _STAGES:
        ident = threading.get_ident()
        if _in_stage.get(ident, 0) <= 1:
            _in_stage.pop(ident, None)
        else:
            _in_stage[ident] -= 1
    if event == _TRACE:
        if _pending.tracing:
            _pending.tracing -= 1
        if not _pending.tracing:  # the outermost trace holds the inner ones
            _pending.trace_s += seconds
    elif event == _LOWER:
        _pending.lower_s += seconds
    elif event == _BACKEND_COMPILE:
        now = _steps.clock()
        stages = (_pending.cache, _pending.trace_s, _pending.lower_s)
        _pending.cache, _pending.trace_s, _pending.lower_s = 'uncached', 0.0, 0.0
        for watcher in list(_listening):
            watcher._on_compile(
                str(kwargs.get('fun_name', '?')), seconds, now, *stages
            )


@functools.cache
def process_start_s() -> float:
    """When this process started, on ``observability.steps.clock``: from
    the start time the kernel keeps in ``/proc/self/stat`` (ticks since
    boot, so a hundredth of a second fine) where there is one to read,
    else the clock read when this module was imported."""
    try:
        with open('/proc/self/stat', 'rb') as stat:
            # The fields after the command, which may hold spaces: the
            # start time is the 22nd of the line, the 20th after it.
            ticks = int(stat.read().rpartition(b')')[2].split()[19])
        since_boot = time.clock_gettime(time.CLOCK_BOOTTIME)
        age_s = since_boot - ticks / os.sysconf('SC_CLK_TCK')
        started = _steps.clock() - age_s
    except (OSError, ValueError, IndexError, AttributeError):
        return _IMPORTED_S
    # A start after the import is a clock this reckoning does not hold for.
    return started if started <= _IMPORTED_S else _IMPORTED_S


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
    (startup is single-threaded) and nest, so the open ones are a stack.
    """

    def __init__(self, recorder: FlightRecorder | None = None) -> None:
        self._recorder = recorder
        self._lock = threading.Lock()
        self._seen: set[tuple[str, str, str]] = set()  # guarded by self._lock
        self._phases: list[dict] = []  # guarded by self._lock
        self._programs: list[dict] = []  # guarded by self._lock
        # The phases in progress, innermost last; each carries its thread
        # and the [programs, cache hits] that thread compiled inside it.
        self._open: list[dict] = []  # guarded by self._lock
        # Whether an ``engine_init`` has closed: a program under no phase
        # and no step span is start-up until then, serving after.
        self._serving = False  # guarded by self._lock
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
                monitoring.register_scalar_listener(_on_scalar)
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

    def _on_compile(self, program: str, seconds: float, now: float,
                    cache: str, trace_s: float, lower_s: float) -> None:
        """One program compiled (or loaded from the persistent cache) on
        the calling thread: one ``compile`` record. ``now`` is the clock
        read at jax's event, the record's ``t1_s``. ``path`` says where:
        ``startup`` inside a :meth:`phase`, whose ``phase`` and ``shape``
        the record then carries, and with no phase before the first
        ``engine_init`` has closed; else ``serving``, with the open step
        span (``during``) and its step (``seq``)."""
        cache_hit = cache == 'hit'
        thread = threading.current_thread().name
        entry: dict = {
            'program': program,
            'duration_s': round(seconds, 6),
            'cache_hit': cache_hit,
            'cache': cache,
            'trace_s': round(trace_s, 6),
            'lower_s': round(lower_s, 6),
            't0_s': round(now - seconds, 6),
            't1_s': round(now, 6),
            'thread': thread,
        }
        with self._lock:
            # A phase holds what its own thread compiles: a program that
            # another thread compiles beside it is under no phase.
            around = [p for p in self._open if p['thread'] == thread]
            for phase in around:  # every open phase holds the program
                phase['_programs'][0] += 1
                phase['_programs'][1] += cache_hit
            serving = self._serving
        active = around[-1] if around else None
        during, seq = _steps.current() or (None, None)
        if active is not None:
            entry.update(
                path='startup', phase=active['phase'], shape=active['shape']
            )
        elif during is None and not serving:
            entry['path'] = 'startup'
        else:
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
        with self._lock:
            self._programs.append({**entry, 't_wall': time.time()})
            del self._programs[:-_MAX_PROGRAMS]
        try:
            self.recorder.record('compile', **entry)
        except Exception:
            pass  # a full disk must not turn a compile fatal
        if active is None:
            # Startup seconds are observed once, by the phase around the
            # program; a program under no phase is observed here.
            _metrics.COMPILE_SECONDS.labels(
                kind=str(during), shape=program, path=entry['path']
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
        Phases nest (``engine_init`` around the engine's own): a program
        names the innermost open phase and every open phase counts it.

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
        entry: dict = {
            'phase': kind, 'shape': shape, **fields,
            'thread': threading.current_thread().name,
        }
        listening = self in _listening
        opened = {**entry, 't_start_wall': time.time(), '_programs': [0, 0]}
        with self._lock:
            seen = (scope, kind, shape) in self._seen
            self._open.append(opened)
        t0 = _steps.clock()
        error: str | None = None
        try:
            yield entry
        except BaseException as exc:
            error = repr(exc)[:300]
            raise
        finally:
            t1 = _steps.clock()
            with self._lock:
                self._open[:] = [p for p in self._open if p is not opened]
                programs, hits = opened['_programs']
            cache_hit = error is None and (
                seen or (listening and compiles and hits == programs)
            )
            entry['duration_s'] = round(t1 - t0, 6)
            entry['t0_s'] = round(t0, 6)
            entry['t1_s'] = round(t1, 6)
            entry['cache_hit'] = cache_hit
            if listening:
                entry['programs'] = programs
                entry['cache_hits'] = hits
            if error is not None:
                entry['error'] = error
            with self._lock:
                if error is None:
                    self._seen.add((scope, kind, shape))
                if kind == 'engine_init':
                    self._serving = True
                self._phases.append({**entry, 't_wall': time.time()})
                del self._phases[:-_MAX_PHASES]
            try:
                self.recorder.record('compile', **entry)
            except Exception:
                pass  # a full disk must not turn startup fatal
            _metrics.COMPILE_SECONDS.labels(
                kind=kind, shape=shape, path='startup'
            ).observe(t1 - t0)
            if cache_hit:
                _metrics.COMPILE_CACHE_HITS.inc()

    def compiling(self) -> bool:
        """Whether a phase is open, or any thread stands in one of jax's
        compile stages (trace, lowering, backend compile), right now: what
        a stall's evidence asks from another thread."""
        with self._lock:
            return bool(self._open) or bool(_in_stage)

    def state(self) -> dict:
        """Snapshot for debug bundles: the completed phases, the program
        records, and the phases in progress, outermost first (``None``
        between phases). A bundle dumped during a wedged init shows
        ``active`` ending in the exact (kind, shape) the process is stuck
        compiling, under the phases that hold it."""
        with self._lock:
            return {
                'active': [
                    {k: v for k, v in p.items() if k != '_programs'}
                    for p in self._open
                ] or None,
                'phases': [dict(p) for p in self._phases],
                'programs': [dict(p) for p in self._programs],
            }

    def summary(self, until_s: float | None = None) -> dict:
        """Where the seconds between :func:`process_start_s` and
        ``until_s`` (now, if None) went, from the records that start
        before ``until_s``. Three stretches that add up to that time:
        ``before_engine_s`` (to the first ``engine_init``'s start),
        ``engine_init_s`` and ``after_engine_s``; without an
        ``engine_init`` record the first is the whole and the others are
        None. Of ``engine_init``: ``unphased_init_s``, its seconds under no
        inner phase and no program record. Of the programs: ``programs``
        and ``cache_miss_programs`` (``cache == 'miss'`` that took a
        second or more of compiling), and their seconds by stage,
        ``trace_lower_s``, ``cache_load_s`` (``duration_s`` of the hits)
        and ``compile_miss_s`` (of the rest); ``after_engine_program_s`` is
        all three stages of the programs that the thread of
        ``engine_init`` compiled after it."""
        start = process_start_s()
        until = _steps.clock() if until_s is None else until_s
        state = self.state()
        phases = [p for p in state['phases'] if p['t0_s'] < until]
        programs = [p for p in state['programs'] if p['t0_s'] < until]
        init = next((p for p in phases if p['phase'] == 'engine_init'), None)

        def whole(program: dict) -> float:
            return (
                program['trace_s'] + program['lower_s'] + program['duration_s']
            )

        out = {
            'process_start_s': start,
            'until_s': until,
            'before_engine_s': until - start,
            'engine_init_s': None,
            'after_engine_s': None,
            'unphased_init_s': None,
            'after_engine_program_s': None,
            'programs': len(programs),
            'cache_miss_programs': sum(
                p['cache'] == 'miss' and p['duration_s'] >= 1.0
                for p in programs
            ),
            'trace_lower_s': sum(p['trace_s'] + p['lower_s'] for p in programs),
            'cache_load_s': sum(
                p['duration_s'] for p in programs if p['cache'] == 'hit'
            ),
            'compile_miss_s': sum(
                p['duration_s'] for p in programs if p['cache'] != 'hit'
            ),
        }
        if init is None:
            return out
        t0, t1 = init['t0_s'], min(init['t1_s'], until)
        inner = [  # the constructor's own phases, which are siblings
            p for p in phases
            if p is not init and t0 <= p['t0_s'] and p['t1_s'] <= init['t1_s']
            and p['thread'] == init['thread']
        ]
        loose = [  # programs of __init__ under no inner phase
            p for p in programs
            if p.get('phase') == 'engine_init' and t0 < p['t1_s'] <= t1
            and p['thread'] == init['thread']
        ]
        out.update(
            before_engine_s=t0 - start,
            engine_init_s=t1 - t0,
            after_engine_s=until - t1,
            unphased_init_s=max(
                0.0,
                t1 - t0
                - sum(p['duration_s'] for p in inner)
                - sum(whole(p) for p in loose),
            ),
            after_engine_program_s=sum(
                whole(p) for p in programs
                if p['t1_s'] > t1 and p['thread'] == init['thread']
            ),
        )
        return out


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
