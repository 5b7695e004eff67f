"""Readers of what the engine's stall watcher leaves behind
(``docs/observability.md`` "Stall watchdog"): ``stalled_s`` and
``serve_self_s`` on the step records, and the ``stall`` records of the
flight ring, inside the measured window.

Each returns None where the program under test writes no ``serve_self_s``
(a program from before the watcher), so that the line leaves the metric
out; with the watcher there, "no stall" is a reading: 0.0.

A traced run's own profiler is left out. ``trace.Capture`` starts and stops
it on the driver's thread; in the open loop that is the serving thread
between two ``step()`` calls with requests unfinished, a true stall with a
true cause (its stack says ``stop_trace``) that is the tracer's cost and not
the program's: a stalled stretch that holds ``capture.t_start`` or
``t_stop`` (the step records' clock) is not counted. No stretch under an
open root can hold either (the thread is inside the engine then), so
``serve_self_s`` needs no such care.
"""

from __future__ import annotations

_STEP_KINDS = ('prefill', 'decode', 'mixed', 'spec')


def _steps(obs) -> list[dict]:
    return [r for r in obs['flight'] if r.get('kind') in _STEP_KINDS]


def _watched(obs) -> bool:
    """Whether the window's step records come from a program that keeps
    the span edges at all."""
    return any('serve_self_s' in r for r in _steps(obs))


def _holds_a_boundary(ctx, t0: float, t1: float) -> bool:
    capture = ctx.capture
    return any(
        t is not None and t0 <= t <= t1
        for t in (capture.t_start, capture.t_stop)
    )


def _tracers_own(ctx, obs) -> set[float]:
    """Edges (``stalled_edge_s`` of a step record, which is the
    ``t_edge_s`` of its ``stall`` records) of the stalled stretches that
    hold a boundary of the capture. A stretch runs from its edge for the
    step's ``stalled_s``."""
    return {
        r['stalled_edge_s'] for r in _steps(obs)
        if r.get('stalled_s') and 'stalled_edge_s' in r and _holds_a_boundary(
            ctx, r['stalled_edge_s'], r['stalled_edge_s'] + r['stalled_s']
        )
    }


def stall_s(ctx, obs):
    """Seconds of the window's steps that the watcher flagged as stalled:
    the sum of ``stalled_s`` over the step records."""
    if not _watched(obs):
        return None
    own = _tracers_own(ctx, obs)
    return float(sum(
        r['stalled_s'] for r in _steps(obs)
        if r.get('stalled_s') and r.get('stalled_edge_s') not in own
    ))


def stalls_in_window(ctx, obs):
    """Stalled stretches the watcher met in the window: its ``stall``
    records at ``sample`` 0, a compile's left out (``compiling``: a known
    cause with a record and a metric of its own). A stretch that no step
    record took (the step behind a hole wrote none) is held to its own
    reading: edge to the record's time."""
    if not _watched(obs):
        return None
    own = _tracers_own(ctx, obs)
    return float(sum(
        1 for r in obs['flight']
        if r.get('kind') == 'stall' and r.get('sample') == 0
        and not r.get('compiling') and r['t_edge_s'] not in own
        and not _holds_a_boundary(ctx, r['t_edge_s'], r['t_s'])
    ))


def serve_self_share(ctx, obs):
    """Share of the window the serving thread spent under the root span
    ``distllm:serve`` and under no other: the loop's own lines between
    the steps' spans. 100 x the sum of ``serve_self_s`` over ``window_s``."""
    if not _watched(obs) or not obs.get('window_s'):
        return None
    return 100.0 * sum(
        r.get('serve_self_s', 0.0) for r in _steps(obs)
    ) / obs['window_s']
