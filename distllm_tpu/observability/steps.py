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

Beside the stack, every thread that has opened an annotated span keeps one
:class:`Edge` in the module's table (``edges()``), which another thread may
read: when the thread last opened or closed a span, and what it has been in
since. The time since that edge is a *stretch*; ``stall_threshold_s`` says
when one is long for its kind. ``flight.StallWatchdog`` polls the table and flags a
stalled stretch; when its edge at last moves, the thread itself adds the
stretch's seconds to the owning step's ``stalled_s``. The seconds a thread
spends under an open root ``serve`` and under no other span collect on its
edge and leave with the next record (``serve_self_s``).
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

ROOT_SPAN = 'serve'
# What "stalled" means, stated once: a stretch is stalled when its edge is
# older than the largest of a floor, a multiple of what stretches in the
# same span (or in a hole between spans) typically take, and a multiple of
# the longest one the span has lately been through: a wait that recurs (a
# round of prefill chunks whose last fetch waits 1.4 s for the device,
# every call) is the program's, not a stall.
STALL_FLOOR_S = 1.0
STALL_FACTOR = 8.0
STALL_OVER_LONGEST = 2.0
_TYPICAL_WEIGHT = 0.125  # of the newest stretch in a name's running mean
_LONGEST_HALF_LIFE_S = 60.0  # a span's longest stretch fades from memory

_local = threading.local()
_trace_annotation = None
# thread ident -> Edge; written by the span's own thread, read by a watcher
_edges: dict[int, 'Edge'] = {}
# span name (None: a hole under a root) -> running mean of its unflagged
# stretches; a hole between two roots is held to the holes' under one
_typical: dict[str | None, float] = {}
# span name -> (seconds, when) of its longest stretch, compiles left out
_longest: dict[str | None, tuple[float, float]] = {}


class Edge:
    """What another thread may know of one thread's spans: ``t``, the
    ``clock`` time of its last span edge (a push or a pop); ``span`` and
    ``seq``, its innermost open span that is not the root (``None``: a
    hole) and that span's step (in a hole, the step whose span closed
    last); ``root``, whether a ``serve`` is open.
    ``flagged`` is the watcher's mark on the stretch that began at the
    ``t`` it holds, ``excused`` its word that the stretch was a compile. ``self_s`` collects the seconds under the root alone
    until a record takes them; ``carry`` is a flagged hole's seconds and
    edge until a step opens behind it."""

    __slots__ = ('ident', 'native_id', 'cpu_clock', 'thread', 't', 'span',
                 'seq', 'root', 'flagged', 'excused', 'self_s', 'carry')

    def __init__(self, now: float) -> None:
        thread = threading.current_thread()
        self.ident = thread.ident
        self.native_id = thread.native_id
        self.thread = thread.name
        try:  # read here, by the thread itself: its id may outlive it
            self.cpu_clock = time.pthread_getcpuclockid(thread.ident)
        except (AttributeError, OSError):
            self.cpu_clock = None
        self.t = now
        self.span: str | None = None
        self.seq: int | None = None
        self.root = False
        self.flagged: float | None = None
        self.excused = False
        self.self_s = 0.0
        self.carry: tuple[float, float] | None = None

    def move(self, now: float, owner: 'StepSpan | None', stack: list,
             seq: int | None) -> None:
        """This thread's span edge at ``now``, made by step ``seq``: close
        the stretch since the last one (to the root's self time, to the
        names' typical seconds or, flagged, to ``owner``'s ``stalled_s``)
        and note where the thread is with ``stack`` as it stands after the
        edge. In a hole ``seq`` stays the step whose span closed last."""
        seconds = now - self.t
        flagged = self.flagged == self.t
        if flagged:
            if owner is None:
                behind = self.carry[0] if self.carry else 0.0
                self.carry = (behind + seconds, self.t)
            else:
                owner.add_stalled(seconds, self.t)
        if self.span is None and not self.root:
            pass  # between two roots: the caller's time, idle or not
        elif not flagged:
            _learn(self.span, seconds, now, typical=True)
        elif not self.excused:
            _learn(self.span, seconds, now, typical=False)
        self.flagged, self.excused = None, False
        if self.span is None and self.root:
            self.self_s += seconds
        self.root = bool(stack) and stack[0][1] == ROOT_SPAN  # undermost
        if stack and stack[-1][1] != ROOT_SPAN:
            self.span, self.seq = stack[-1][1], stack[-1][0].seq
        else:
            self.span = None
            if seq is not None:
                self.seq = seq
        self.t = now


def _learn(span: str | None, seconds: float, now: float,
           typical: bool) -> None:
    """A finished stretch of ``seconds`` in ``span``: the span's longest
    if it is (faded by its age), and one more of its ``typical`` ones
    unless the watcher flagged it."""
    if seconds >= _longest_s(span, now):
        _longest[span] = (seconds, now)
    if not typical:
        return
    if span in _typical:
        _typical[span] += _TYPICAL_WEIGHT * (seconds - _typical[span])
    else:
        _typical[span] = seconds


def _longest_s(span: str | None, now: float) -> float:
    seconds, when = _longest.get(span, (0.0, now))
    return seconds * 0.5 ** ((now - when) / _LONGEST_HALF_LIFE_S)


def _edge(now: float) -> Edge:
    try:
        return _local.edge
    except AttributeError:
        edge = _local.edge = _edges[threading.get_ident()] = Edge(now)
        return edge


def edges() -> list[Edge]:
    """Every thread's edge (a copy of the table's values)."""
    return list(_edges.values())


def forget(ident: int) -> None:
    """Drop a thread's edge from the table (the watcher's, for a thread
    that has ended)."""
    _edges.pop(ident, None)


def stall_threshold_s(span: str | None, now: float | None = None) -> float:
    """The age past which a stretch in ``span`` (``None``: in a hole) is
    long for its kind, at clock time ``now``."""
    return max(
        STALL_FLOOR_S,
        STALL_FACTOR * _typical.get(span, 0.0),
        STALL_OVER_LONGEST * _longest_s(span, clock() if now is None else now),
    )


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
        # (``sampled_rows``), and a flagged stretch's edge; rides to the
        # record with the seconds.
        self.counts: dict[str, int | float] = {}

    def _push(self, name: str, now: float) -> None:
        stack = _stack()
        if not self.annotate:
            stack.append((self, name, now, None))
            return
        annotation = _annotation(name, self.seq)
        # The stretch this edge ends belongs to the span it opens inside;
        # a hole's goes to the step that opens behind it (not to a root,
        # which writes no record).
        owner = stack[-1][0] if stack and stack[-1][1] != ROOT_SPAN else None
        stack.append((self, name, now, annotation))
        annotation.__enter__()
        edge = _edge(now)
        edge.move(now, owner, stack, None if name == ROOT_SPAN else self.seq)
        if edge.carry is not None and name != ROOT_SPAN:
            self.add_stalled(*edge.carry)
            edge.carry = None

    @staticmethod
    def _pop(now: float) -> None:
        """Close this thread's innermost span and credit its owner."""
        stack = _stack()
        owner, name, opened, annotation = stack.pop()
        if annotation is not None:
            annotation.__exit__(None, None, None)
            if name == ROOT_SPAN:
                _edge(now).move(now, None, stack, None)
            else:
                _edge(now).move(now, owner, stack, owner.seq)
        field = SPAN_FIELDS.get(name, 'dispatch_s')
        owner.seconds[field] = owner.seconds.get(field, 0.0) + now - opened

    def add_stalled(self, seconds: float, t_edge: float) -> None:
        """A stretch the watcher flagged as stalled, ``seconds`` long from
        the edge at ``t_edge``: ``stalled_s`` sums a step's, and
        ``stalled_edge_s`` (the latest one's edge) is the ``t_edge_s`` of
        its ``stall`` records."""
        self.seconds['stalled_s'] = (
            self.seconds.get('stalled_s', 0.0) + seconds
        )
        self.counts['stalled_edge_s'] = round(t_edge, 6)

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
        clock, the seconds of each child span (``stalled_s`` among them
        where the watcher flagged a stretch of the step's), the step's
        counts and, with annotations on, ``serve_self_s``."""
        out = {'seq': self.seq, 't0_s': round(self.t0, 6), **self.counts}
        if self.t1 is not None:
            out['t1_s'] = round(self.t1, 6)
        for field, seconds in self.seconds.items():
            out[field] = round(seconds, 6)
        edge = getattr(_local, 'edge', None) if self.annotate else None
        if edge is not None:
            # The root's self time since the last record that carried it.
            out['serve_self_s'] = round(edge.self_s, 6)
            edge.self_s = 0.0
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
