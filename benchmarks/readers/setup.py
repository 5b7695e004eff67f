"""Readers of the program's own account of set-up
(``distllm_tpu/observability/startup.py``, ``CompileWatcher.summary``): the
seconds between the process's start and the window's first operation, cut
into three stretches, and every program jax traced, lowered, compiled or
loaded on the way, by stage. The account is the process watcher's, which
outlives the engine: the readers run after the driver has shut it down.

Each returns None where the program under test keeps no such account (a
program from before ``summary`` or the ``engine_init`` phase existed), so
that the line leaves the metric out.
"""

from __future__ import annotations


def _summaries(ctx, obs) -> tuple[dict, dict] | None:
    """The account up to the window's start (``capture.t_armed``: every
    driver arms the capture there, traced or not), and the account up to
    where set-up ends once the check's call is cut from its end: the
    harness leaves that call out of ``setup_s``, so the cut lies ``setup_s``
    after the process's start."""
    from distllm_tpu.observability.startup import get_compile_watcher

    summary = getattr(get_compile_watcher(), 'summary', None)
    if summary is None or ctx.capture.t_armed is None:
        return None
    whole = summary(until_s=ctx.capture.t_armed)
    if whole.get('engine_init_s') is None:
        return None
    cut = whole['process_start_s'] + obs['end_to_end']['setup_s']
    return whole, summary(until_s=cut)


def seconds(ctx, obs, what: str):
    """One of the account's stretches or stage sums, in seconds.
    ``warmup_run_s`` is the stretch after ``engine_init`` up to the cut,
    less all three stages of the programs compiled in it: what the
    warm-up's own traffic takes once its programs exist."""
    found = _summaries(ctx, obs)
    if found is None:
        return None
    whole, cut = found
    if what == 'warmup_run_s':
        return cut['after_engine_s'] - cut['after_engine_program_s']
    return float(whole[what])


def count(ctx, obs, what: str):
    """``programs`` or ``cache_miss_programs`` of the account."""
    found = _summaries(ctx, obs)
    return None if found is None else float(found[0][what])


def unphased_init_share(ctx, obs):
    """Share of ``engine_init``'s seconds under no inner phase and no
    program record."""
    found = _summaries(ctx, obs)
    if found is None or not found[0]['engine_init_s']:
        return None
    return 100.0 * found[0]['unphased_init_s'] / found[0]['engine_init_s']
