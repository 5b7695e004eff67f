"""Readers of the device trace's summary (``trace.summarize``). Without a
traced slice they return None. The patterns that find a program or a kernel
in the trace are data, in the metric's file."""

from __future__ import annotations

from benchmarks import peaks
from benchmarks.trace import seconds_matching


def host_gap_share(ctx, obs):
    """Share of the time between the first and the last device op of the
    traced slice in which no op ran, launch gaps under 20 us left out."""
    summary = obs['trace']
    if not summary or not summary['span_s']:
        return None
    return 100.0 * summary['host_gap_s'] / summary['span_s']


def op_time_share(ctx, obs, pattern: str):
    """Share of the device's busy time spent in ops whose name or scope the
    pattern finds (self time: an enclosing ``while`` is not counted twice)."""
    summary = obs['trace']
    if not summary or not summary['busy_s']:
        return None
    return 100.0 * seconds_matching(summary['op_s'], pattern) / summary['busy_s']


def module_time_share(ctx, obs, pattern: str):
    """Share of the device's busy time inside programs whose name the
    pattern finds."""
    summary = obs['trace']
    if not summary or not summary['busy_s']:
        return None
    return 100.0 * seconds_matching(summary['module_s'], pattern) / summary['busy_s']


def module_step_ms(ctx, obs, pattern: str, steps_key: str):
    """Device milliseconds per step of the programs the pattern finds: their
    device time over runs times the steps each run makes (an engine setting
    named by ``steps_key``)."""
    summary = obs['trace']
    if not summary:
        return None
    runs = seconds_matching(summary['module_n'], pattern)
    if not runs:
        return None
    steps = ctx.config['engine'][steps_key]
    return 1e3 * seconds_matching(summary['module_s'], pattern) / (runs * steps)


def encoder_flops_share(ctx, obs):
    """FLOPs the real tokens of the traced passes need, over the bf16 peak,
    over the device's busy seconds in the slice. Counts whole passes inside
    the slice; needs the slice to hold at least one."""
    summary = obs['trace']
    capture = ctx.capture
    if not summary or not summary['busy_s'] or capture.t_start is None:
        return None
    counters = obs['counters']
    t0 = capture.t_start - capture.t_armed
    t1 = capture.t_stop - capture.t_armed
    inside = [p for p in counters['passes'] if p[0] >= t0 - 1e-3 and p[1] <= t1 + 1e-3]
    if not inside:
        return None
    flops = len(inside) * peaks.encoder_flops(
        ctx.config, counters['pass_tokens'], counters['pass_sum_sq_len']
    )
    peak_flops, _, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * flops / peak_flops / summary['busy_s']


def decode_bw_share(ctx, obs, pattern: str):
    """Least bytes a decode step moves (weights once, and the K and V of the
    batch's contexts, from shapes) over the HBM peak, over the device time
    of one step of the decode program. Says how close decode is to the
    memory roofline."""
    step_ms = module_step_ms(ctx, obs, pattern, 'decode_steps')
    batches = [
        r['batch'] for r in obs['flight']
        if r.get('kind') == 'decode' and 'batch' in r
    ]
    per_sequence = obs['counters'].get('mean_context_tokens')
    if step_ms is None or not batches or per_sequence is None:
        return None
    context_tokens = per_sequence * sum(batches) / len(batches)
    bytes_moved = peaks.decode_step_bytes(ctx.config, context_tokens)
    _, peak_bw, _ = peaks.device_peaks(ctx.device_kind)
    return 100.0 * bytes_moved / peak_bw / (step_ms / 1e3)
