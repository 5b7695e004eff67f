"""Step spans: one set of clock reads for a serving step's flight record
and its profiler annotations (docs/observability.md "Serving-path spans").

A :class:`StepSpan` belongs to one engine step (a prefill dispatch or a
decode/mixed/spec window). ``mark(name)`` closes the step's open child span
and opens ``distllm:<name>`` with ONE clock read: that read ends the
previous child's flight field (``host_s``, ``put_s``, ...), starts the next
one, and is where the ``jax.profiler.TraceAnnotation`` closes and the next
opens, so the record and the device trace can never disagree about a
boundary. Span names are registered in ``instruments.STEP_SPANS``
(enforced by distlint's ``step-span-catalog`` rule).

The clock is ``time.monotonic`` — on Linux the same ``CLOCK_MONOTONIC`` as
``time.perf_counter`` (``tests/test_step_spans.py`` asserts it), so
``t0_s``/``t1_s`` join a record to any capture window taken on either.

Each thread keeps the stack of its open spans; ``current()`` is what the
compile watcher reads to say which step a compile fell in, and
``call_in_flight()`` the jit call (function and arguments) it interrupted.
"""

from __future__ import annotations

import threading
import time

clock = time.monotonic

# Flight field fed by each child span; a span not listed here is a step
# kind's jit call and feeds ``dispatch_s``.
SPAN_FIELDS = {
    'admit': 'admit_s',
    'plan': 'host_s',
    'put': 'put_s',
    'fetch': 'fetch_s',
    'emit': 'emit_s',
    'preempt': 'preempt_s',
}

_local = threading.local()
_trace_annotation = None


def _annotation(name: str, seq: int):
    global _trace_annotation
    if _trace_annotation is None:
        import jax

        _trace_annotation = jax.profiler.TraceAnnotation
    # ``seq`` rides as TraceMe metadata: the event keeps the plain name the
    # trace reduction groups by, and XProf shows seq among its stats.
    return _trace_annotation(f'distllm:{name}', seq=seq)


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def current() -> tuple[str, int] | None:
    """``(span name, step seq)`` of this thread's innermost open span."""
    stack = getattr(_local, 'stack', None)
    if not stack:
        return None
    entry = stack[-1]
    return entry[1], entry[0].seq


def set_call(fn, args) -> None:
    """Name the jit call about to run on this thread (``fn=None`` clears):
    a compile that fires inside it reads the live arguments from here."""
    _local.call = (fn, args) if fn is not None else None


def call_in_flight():
    return getattr(_local, 'call', None)


def abandon() -> None:
    """Close every span this thread left open (a dispatch raised midway),
    so no annotation outlives the step it belonged to."""
    stack = getattr(_local, 'stack', None)
    while stack:
        StepSpan._pop(clock())


class StepSpan:
    """The clock reads of one engine step. ``annotate=False`` (attribution
    off) keeps the reads and the seconds and opens no annotation."""

    __slots__ = ('seq', 'annotate', 't0', 't1', 'seconds', 'counts')

    def __init__(self, seq: int, annotate: bool = True) -> None:
        self.seq = seq
        self.annotate = annotate
        self.t0 = clock()
        self.t1: float | None = None
        self.seconds: dict[str, float] = {}
        # What the dispatch knew of its rows when it built their arrays
        # (``sampled_rows``); rides to the record with the seconds.
        self.counts: dict[str, int] = {}

    def _push(self, name: str, now: float) -> None:
        annotation = _annotation(name, self.seq) if self.annotate else None
        _stack().append((self, name, now, annotation))
        if annotation is not None:
            annotation.__enter__()

    @staticmethod
    def _pop(now: float) -> None:
        """Close this thread's innermost span and credit its owner."""
        owner, name, opened, annotation = _stack().pop()
        if annotation is not None:
            annotation.__exit__(None, None, None)
        field = SPAN_FIELDS.get(name, 'dispatch_s')
        owner.seconds[field] = owner.seconds.get(field, 0.0) + now - opened

    def _open_child(self) -> bool:
        stack = _stack()
        return bool(stack) and stack[-1][0] is self

    def mark(self, name: str) -> float:
        """Close this step's open child and open ``distllm:<name>`` at one
        clock read, which is returned. Another step that runs inside the
        new child (a prefill step inside ``admit``) nests its own spans
        there and stays inside the child's seconds."""
        now = clock()
        if self._open_child():
            self._pop(now)
        self._push(name, now)
        return now

    def inside(self, name: str) -> '_Inside':
        """Context manager for a span nested in this step's open child
        (``distllm:preempt`` inside ``distllm:plan``): the child stays
        open and keeps its boundaries."""
        return _Inside(self, name)

    def pause(self) -> float:
        """Close the open child without opening another (the window is in
        flight; ``mark`` resumes the step at its fetch)."""
        now = clock()
        if self._open_child():
            self._pop(now)
        return now

    def close(self) -> float:
        """End the step: closes the open child and fixes ``t1``."""
        self.t1 = self.pause()
        return self.t1

    def fields(self) -> dict:
        """The record's share: ``seq``, ``t0_s``/``t1_s`` on the shared
        clock, the seconds of each child span, and the step's counts."""
        out = {'seq': self.seq, 't0_s': round(self.t0, 6), **self.counts}
        if self.t1 is not None:
            out['t1_s'] = round(self.t1, 6)
        for field, seconds in self.seconds.items():
            out[field] = round(seconds, 6)
        return out


class _Inside:
    __slots__ = ('step', 'name')

    def __init__(self, step: StepSpan, name: str) -> None:
        self.step = step
        self.name = name

    def __enter__(self) -> StepSpan:
        self.step._push(self.name, clock())
        return self.step

    def __exit__(self, *exc_info) -> None:
        # The nested span belongs to the same step as the child around it:
        # its seconds are its own field and stay inside the parent's.
        self.step._pop(clock())
