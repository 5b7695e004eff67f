"""Profiler capture, and the reduction from a trace to numbers.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a plain structure
(planes -> lines -> events ``[name, start_ns, duration_ns, detail]``); every
reduction below works on that structure, so that it can be checked on a small
recorded trace kept as JSON (``tests/data``) with no chip.

What the reduction reads from a TPU trace: planes named ``/device:TPU:<n>``,
their line ``XLA Ops`` (one event per executed HLO op; ops nest, a ``while``
spans its body) and ``XLA Modules`` (one event per executed program), and on
the host planes the ``TraceAnnotation`` events the engine writes
(``distllm:<kind>``) and the harness's own (``bench:<span>``).
"""

from __future__ import annotations

import glob
import re
import shutil
import tempfile
import time
from collections import defaultdict

DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
ANNOTATION_PREFIXES = ('distllm:', 'bench:')


class Capture:
    """Traces one slice of the measured window. The driver calls ``poll()``
    at its natural boundaries (between passes, steps or calls); the capture
    starts at the first boundary ``delay_s`` after ``arm()`` and stops at the
    first boundary ``length_s`` later. ``length_s <= 0`` captures nothing."""

    def __init__(self, delay_s: float, length_s: float) -> None:
        self.delay_s = delay_s
        self.length_s = length_s
        self.dir: str | None = None
        self.t_armed: float | None = None
        self.t_start: float | None = None  # perf_counter, as t_armed and t_stop
        self.t_stop: float | None = None

    @property
    def active(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    @property
    def done(self) -> bool:
        return self.t_stop is not None

    def arm(self) -> None:
        self.t_armed = time.perf_counter()

    def poll(self, last: bool = False) -> None:
        """``last=True`` at the window's end stops a capture still running."""
        if self.length_s <= 0 or self.t_armed is None or self.done:
            return
        now = time.perf_counter()
        if self.t_start is None:
            if not last and now - self.t_armed >= self.delay_s:
                self._start()
        elif last or now - self.t_start >= self.length_s:
            self._stop()

    def _start(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix='bench_trace_')
        options = jax.profiler.ProfileOptions()
        # The Python tracer records every call of the host loop and slows it
        # severalfold; the annotations the reduction needs are TraceMe events.
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_start = time.perf_counter()

    def _stop(self) -> None:
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def load(self) -> dict | None:
        """The captured trace as a plain structure, or None. Removes the
        profiler's files."""
        if self.dir is None:
            return None
        try:
            if self.active:
                self._stop()
            found = glob.glob(f'{self.dir}/plugins/profile/*/*.xplane.pb')
            return load_xplane(found[0]) if found else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


def _split_op(text: str) -> tuple[str, str]:
    """An op's event name is its whole HLO line, ``%name = type opcode(...)``:
    the name, and ``opcode type`` (the result type cut short). The type of a
    tuple result is in parentheses."""
    name, _, rest = text.partition(' = ')
    depth = 0
    for i, ch in enumerate(rest):
        if ch == '(':
            depth += 1
        elif ch == ')':
            depth -= 1
        elif ch == ' ' and depth == 0:
            result, call = rest[:i], rest[i + 1:]
            return name, f"{call.partition('(')[0]} {result[:48]}"
    return name, rest[:48]


def load_xplane(path: str) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            is_ops = device and line.name == OPS_LINE
            events = []
            for event in line.events:
                name, detail = event.name, ''
                if not device and not name.startswith(ANNOTATION_PREFIXES):
                    continue
                if is_ops:
                    name, detail = _split_op(name)
                events.append(
                    [name, int(event.start_ns), int(event.duration_ns), detail]
                )
            if events:
                lines.append({'name': line.name, 'events': events})
        if lines:
            planes.append({'name': plane.name, 'lines': lines})
    return {'planes': planes}


# ------------------------------------------------------------- reduction
def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def gaps_between(merged) -> list[tuple[int, int]]:
    """The idle intervals between consecutive busy intervals."""
    return [
        (merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)
    ]


def self_times(events) -> dict[str, int]:
    """Nanoseconds by event name, counting each instant once: an event that
    encloses others (a ``while`` and its body) keeps only the time no child
    covers."""
    out: dict[str, int] = defaultdict(int)
    stack: list[list] = []  # [end, name, self_ns]
    for name, start, dur, *_ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            end, done_name, self_ns = stack.pop()
            out[done_name] += max(self_ns, 0)
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, name, dur])
    while stack:
        end, done_name, self_ns = stack.pop()
        out[done_name] += max(self_ns, 0)
    return dict(out)


def attribute(gap, spans) -> str:
    """What the host was doing in the idle ``gap``: the shortest host span
    (``[name, start, dur, ...]``) that covers at least half of it, so that
    an inner span wins over the harness span around it; else the span that
    overlaps it most; ``unattributed`` where none does."""
    g0, g1 = gap
    covering, best, best_overlap = None, 'unattributed', 0
    for name, start, dur, *_ in spans:
        overlap = min(g1, start + dur) - max(g0, start)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
        if 2 * overlap >= g1 - g0 and (covering is None or dur < covering[1]):
            covering = (name, dur)
    return covering[0] if covering else best


def _device_planes(trace: dict) -> list[dict]:
    return [p for p in trace['planes'] if DEVICE_PLANE.match(p['name'])]


def _line(plane: dict, name: str) -> list:
    for line in plane['lines']:
        if line['name'] == name:
            return line['events']
    return []


def host_spans(trace: dict) -> list:
    spans = []
    for plane in trace['planes']:
        if DEVICE_PLANE.match(plane['name']):
            continue
        for line in plane['lines']:
            spans.extend(
                e for e in line['events']
                if e[0].startswith(ANNOTATION_PREFIXES)
            )
    return spans


def summarize(trace: dict, min_gap_ns: int = 20_000) -> dict | None:
    """Everything the readers and the result line take from one trace,
    averaged over the device planes that ran anything:

    ``busy_s`` seconds in which an op ran (union of the op intervals),
    ``span_s`` first op start to last op end, ``op_s`` self time by op (``name opcode type``, so a
    pattern anchored at the start finds a kernel by name and opcode), ``module_s`` and
    ``module_n`` time and runs by program name, ``gap_s`` idle seconds
    between ops by what the host was doing, ``host_gap_s`` their sum.
    Gaps under ``min_gap_ns`` (launch latency between back-to-back ops) are
    counted in ``small_gap_s`` and not attributed.
    """
    planes = [p for p in _device_planes(trace) if _line(p, OPS_LINE)]
    if not planes:
        return None
    spans = host_spans(trace)
    n = len(planes)
    busy = span = small = 0.0
    op_s: dict[str, float] = defaultdict(float)
    module_s: dict[str, float] = defaultdict(float)
    module_n: dict[str, float] = defaultdict(float)
    gap_s: dict[str, float] = defaultdict(float)
    for plane in planes:
        ops = _line(plane, OPS_LINE)
        merged = union((e[1], e[1] + e[2]) for e in ops)
        busy += total(merged) / 1e9 / n
        span += (merged[-1][1] - merged[0][0]) / 1e9 / n
        labelled = [
            [f'{e[0]} {e[3]}'.strip() if len(e) > 3 else e[0], e[1], e[2]]
            for e in ops
        ]
        for name, ns in self_times(labelled).items():
            op_s[name] += ns / 1e9 / n
        for name, _start, dur, *_ in _line(plane, MODULES_LINE):
            module_s[name] += dur / 1e9 / n
            module_n[name] += 1 / n
        for gap in gaps_between(merged):
            length = gap[1] - gap[0]
            if length < min_gap_ns:
                small += length / 1e9 / n
            else:
                gap_s[attribute(gap, spans)] += length / 1e9 / n
    return {
        'devices': n,
        'busy_s': busy,
        'span_s': span,
        'op_s': dict(op_s),
        'module_s': dict(module_s),
        'module_n': dict(module_n),
        'gap_s': dict(gap_s),
        'host_gap_s': sum(gap_s.values()),
        'small_gap_s': small,
    }


def seconds_matching(table: dict[str, float], pattern: str) -> float:
    """Sum of a name -> seconds table over the names ``pattern`` finds."""
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k))


def top(table: dict[str, float], n: int = 10) -> list[list]:
    ranked = sorted(table.items(), key=lambda kv: kv[1], reverse=True)[:n]
    return [[name[:120], seconds] for name, seconds in ranked]
